"""paddle.fft — spectral transforms (reference: python/paddle/fft.py).

Thin dispatch layer over jnp.fft: XLA lowers FFTs to the backend's native
implementation (DUCC on CPU, the TPU FFT lowering on device). Norm-mode
semantics ("backward"/"ortho"/"forward") match the reference, which follows
numpy.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .core.dispatch import op
from .core.tensor import Tensor

__all__ = [
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2",
    "fftn", "ifftn", "rfftn", "irfftn",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]


def _norm(norm):
    if norm not in (None, "backward", "ortho", "forward"):
        raise ValueError(f"invalid norm {norm!r}")
    return norm or "backward"


def _mk1(name):
    fn = getattr(jnp.fft, name)

    @op(f"fft_{name}")
    def _impl(x, n=None, axis=-1, norm="backward"):
        return fn(x, n=n, axis=axis, norm=norm)

    def api(x, n=None, axis=-1, norm="backward", name=None):
        return _impl(x, n=None if n is None else int(n), axis=int(axis),
                     norm=_norm(norm))

    api.__name__ = name
    api.__doc__ = f"paddle.fft.{name} (jnp.fft.{name} under dispatch)."
    return api


def _mkn(name, ref_name):
    fn = getattr(jnp.fft, name)

    @op(f"fft_{name}")
    def _impl(x, s=None, axes=None, norm="backward"):
        return fn(x, s=s, axes=axes, norm=norm)

    def api(x, s=None, axes=None, norm="backward", name=None):
        return _impl(x, s=None if s is None else tuple(int(v) for v in s),
                     axes=None if axes is None else tuple(int(a)
                                                          for a in axes),
                     norm=_norm(norm))

    api.__name__ = ref_name
    api.__doc__ = f"paddle.fft.{ref_name} (jnp.fft.{name} under dispatch)."
    return api


fft = _mk1("fft")
ifft = _mk1("ifft")
rfft = _mk1("rfft")
irfft = _mk1("irfft")
hfft = _mk1("hfft")
ihfft = _mk1("ihfft")

fftn = _mkn("fftn", "fftn")
ifftn = _mkn("ifftn", "ifftn")
rfftn = _mkn("rfftn", "rfftn")
irfftn = _mkn("irfftn", "irfftn")


def _mk2(nd_api, ref_name):
    def api(x, s=None, axes=(-2, -1), norm="backward", name=None):
        return nd_api(x, s=s, axes=axes, norm=norm)

    api.__name__ = ref_name
    return api


fft2 = _mk2(fftn, "fft2")
ifft2 = _mk2(ifftn, "ifft2")
rfft2 = _mk2(rfftn, "rfft2")
irfft2 = _mk2(irfftn, "irfft2")


def fftfreq(n, d=1.0, dtype="float32", name=None):
    return Tensor(np.fft.fftfreq(int(n), d).astype(dtype))


def rfftfreq(n, d=1.0, dtype="float32", name=None):
    return Tensor(np.fft.rfftfreq(int(n), d).astype(dtype))


@op("fftshift")
def _fftshift(x, axes=None):
    return jnp.fft.fftshift(x, axes=axes)


@op("ifftshift")
def _ifftshift(x, axes=None):
    return jnp.fft.ifftshift(x, axes=axes)


def fftshift(x, axes=None, name=None):
    return _fftshift(x, axes=None if axes is None else tuple(
        int(a) for a in np.atleast_1d(axes)))


def ifftshift(x, axes=None, name=None):
    return _ifftshift(x, axes=None if axes is None else tuple(
        int(a) for a in np.atleast_1d(axes)))


def _resolve_axes(x, axes, n_default=2):
    if axes is None:
        nd = len(x.shape)
        return tuple(range(nd - n_default, nd))
    return tuple(int(a) for a in axes)


def hfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """2-D FFT of a signal hermitian-symmetric along the LAST axis
    (reference python/paddle/fft.py hfft2): c2c FFT over the leading axis,
    hermitian c2r over the last — the mirror is only on the final axis, so
    the composition is exact (norm factors multiply per-axis)."""
    return hfftn(x, s=s, axes=axes, norm=norm)


def ihfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return ihfftn(x, s=s, axes=axes, norm=norm)


def hfftn(x, s=None, axes=None, norm="backward", name=None):
    axes = _resolve_axes(x, axes, n_default=len(x.shape))
    s_rest = None if s is None else list(s[:-1])
    y = x
    if len(axes) > 1:
        y = fftn(y, s=s_rest, axes=list(axes[:-1]), norm=norm)
    return hfft(y, n=None if s is None else int(s[-1]), axis=axes[-1],
                norm=norm)


def ihfftn(x, s=None, axes=None, norm="backward", name=None):
    axes = _resolve_axes(x, axes, n_default=len(x.shape))
    y = ihfft(x, n=None if s is None else int(s[-1]), axis=axes[-1],
              norm=norm)
    if len(axes) > 1:
        s_rest = None if s is None else list(s[:-1])
        y = ifftn(y, s=s_rest, axes=list(axes[:-1]), norm=norm)
    return y


__all__ += ["hfft2", "ihfft2", "hfftn", "ihfftn"]
