"""Fused residual-add + RMSNorm — Pallas TPU kernel.

Replaces the reference's fused norm family
(paddle/phi/kernels/gpu/rms_norm_kernel.cu, exposed as
paddle.incubate.nn.functional.fused_rms_norm, and the residual variants in
paddle/fluid/operators/fused/fused_dropout_helper.h) with a TPU-native
kernel that computes, in one HBM pass::

    resid = x + y                       # the new residual stream value
    out   = resid * rsqrt(mean(resid^2) + eps) * weight

returning (out, resid). The unfused XLA path materializes resid once for
the add and re-reads it for the norm; the kernel writes both outputs from
a single read of x and y.

Backward recomputes rsqrt from the saved bf16 ``resid`` (exactly what the
unfused path's norm does with the bf16 residual stream), so gradients match
the unfused composition bit-for-bit in expectation; dw reduces over rows in
XLA. Routing contract: hidden % 128 == 0, else callers fall back to the
jnp composition. Opt-in at the model level via ``PT_FUSED_NORM=1``
(measured on v5e before flipping any default — see PERF.md).

``fused_add_layer_norm`` is the same fusion for post-norm transformer
blocks (BERT/ERNIE): resid-add + mean/variance LayerNorm with weight+bias —
the direct analog of the reference's
paddle/fluid/operators/fused/fused_dropout_helper.h residual+LN epilogue.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _pick_block

__all__ = ["fused_add_rms_norm", "fused_add_layer_norm",
           "use_fused_rms_norm"]


def use_fused_rms_norm():
    """One flag gates both fused-norm kernels (rms + layer)."""
    return os.environ.get("PT_FUSED_NORM", "0") == "1"


def _row_block(n_rows):
    return _pick_block("PT_RMSNORM_BR", 256, n_rows)


def _fwd_kernel(x_ref, y_ref, w_ref, out_ref, r_ref, *, eps):
    r = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    r_ref[...] = r.astype(r_ref.dtype)
    # norm reads the bf16-rounded residual, matching the unfused composition
    rf = r_ref[...].astype(jnp.float32)
    ms = jnp.mean(rf * rf, axis=-1, keepdims=True)
    out = rf * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


def _fwd(x, y, w, eps):
    rows, h = x.shape
    br = _row_block(rows)
    kern = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x.dtype),
                   jax.ShapeDtypeStruct((rows, h), x.dtype)],
        interpret=_interpret(),
        name="rms_norm_fwd",
    )
    return kern(x, y, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_add_rms_norm(x, y, w, eps):
    out, r = _fwd(x, y, w, eps)
    return out, r


def _fused_fwd(x, y, w, eps):
    out, r = _fwd(x, y, w, eps)
    return (out, r), (r, w)


def _fused_bwd(eps, res, cts):
    r, w = res
    d_out, d_r = cts
    rf = r.astype(jnp.float32)
    g = d_out.astype(jnp.float32) * w.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(rf * rf, axis=-1, keepdims=True) + eps)
    dr = inv * g - rf * (inv ** 3) * jnp.mean(g * rf, axis=-1, keepdims=True)
    dr = dr + d_r.astype(jnp.float32)
    dw = jnp.sum(d_out.astype(jnp.float32) * rf * inv, axis=0,
                 keepdims=True)
    dx = dr.astype(r.dtype)
    return dx, dx, dw.astype(w.dtype)


_fused_add_rms_norm.defvjp(_fused_fwd, _fused_bwd)


def _fused_add_rms_norm_nd(x, y, weight, epsilon=1e-6):
    h = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    out, r = _fused_add_rms_norm(
        x.reshape(rows, h), y.reshape(rows, h), weight.reshape(1, h),
        float(epsilon))
    return out.reshape(*lead, h), r.reshape(*lead, h)


from ...core.dispatch import op as _op  # noqa: E402


@_op("fused_add_rms_norm_pallas")
def fused_add_rms_norm(x, y, weight, *, epsilon=1e-6):
    """(normed, resid) = RMSNorm(x + y) with one read of x and y.

    x, y: [..., hidden]; weight: [hidden]. Requires hidden % 128 == 0 (TPU
    lane tiling); callers check :func:`use_fused_rms_norm` and fall back to
    the jnp composition otherwise. Directly callable with jax arrays or
    framework Tensors (dispatch handles autograd either way).
    """
    return _fused_add_rms_norm_nd(x, y, weight, epsilon=float(epsilon))


# ---------------------------------------------------------------------------
# fused residual-add + LayerNorm (post-norm transformer epilogue)
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, y_ref, w_ref, b_ref, out_ref, r_ref, *, eps):
    r = x_ref[...].astype(jnp.float32) + y_ref[...].astype(jnp.float32)
    r_ref[...] = r.astype(r_ref.dtype)
    rf = r_ref[...].astype(jnp.float32)
    mu = jnp.mean(rf, axis=-1, keepdims=True)
    xc = rf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    out = (xc * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
           + b_ref[...].astype(jnp.float32))
    out_ref[...] = out.astype(out_ref.dtype)


def _ln_fwd(x, y, w, b, eps):
    rows, h = x.shape
    br = _row_block(rows)
    kern = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, h), x.dtype),
                   jax.ShapeDtypeStruct((rows, h), x.dtype)],
        interpret=_interpret(),
        name="layer_norm_fwd",
    )
    return kern(x, y, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_add_layer_norm(x, y, w, b, eps):
    return _ln_fwd(x, y, w, b, eps)


def _ln_vjp_fwd(x, y, w, b, eps):
    out, r = _ln_fwd(x, y, w, b, eps)
    return (out, r), (r, w)


def _ln_vjp_bwd(eps, res, cts):
    r, w = res
    d_out, d_r = cts
    rf = r.astype(jnp.float32)
    mu = jnp.mean(rf, axis=-1, keepdims=True)
    xc = rf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    g = d_out.astype(jnp.float32) * w.astype(jnp.float32)
    dr = inv * (g - jnp.mean(g, axis=-1, keepdims=True)
                - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    dr = dr + d_r.astype(jnp.float32)
    dw = jnp.sum(d_out.astype(jnp.float32) * xhat, axis=0, keepdims=True)
    db = jnp.sum(d_out.astype(jnp.float32), axis=0, keepdims=True)
    dx = dr.astype(r.dtype)
    return dx, dx, dw.astype(w.dtype), db.astype(w.dtype)


_fused_add_layer_norm.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def _fused_add_layer_norm_nd(x, y, weight, bias, epsilon=1e-12):
    h = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    out, r = _fused_add_layer_norm(
        x.reshape(rows, h), y.reshape(rows, h), weight.reshape(1, h),
        bias.reshape(1, h), float(epsilon))
    return out.reshape(*lead, h), r.reshape(*lead, h)


@_op("fused_add_layer_norm_pallas")
def fused_add_layer_norm(x, y, weight, bias, *, epsilon=1e-12):
    """(normed, resid) = LayerNorm(x + y) with one read of x and y.

    Post-norm transformer epilogue (BERT/ERNIE): only ``normed`` feeds the
    next sublayer, but ``resid`` is returned for parity with the rms
    variant. Same routing contract: hidden % 128 == 0.
    """
    return _fused_add_layer_norm_nd(x, y, weight, bias,
                                    epsilon=float(epsilon))
