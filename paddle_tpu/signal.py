"""paddle.signal — frame / overlap_add / stft / istft.

Reference: python/paddle/signal.py:30 (frame), :145 (overlap_add),
:246 (stft), :423 (istft). TPU-native: frame is a gather with a static
index grid, overlap_add a scatter-add (`.at[].add`) — both lower to XLA
gather/scatter, no as_strided views needed. The FFT leg rides paddle.fft;
the normalization scaling is applied on the REAL side of the transform.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import fft as _fft
from .core.dispatch import op
from .core.tensor import Tensor

__all__ = ["frame", "overlap_add", "stft", "istft"]


@op("signal_frame")
def _frame(x, frame_length, hop_length, axis=-1):
    if axis not in (0, -1):
        raise ValueError(f"frame axis must be 0 or -1, got {axis}")
    seq = x.shape[axis]
    if not 0 < frame_length <= seq:
        raise ValueError(
            f"frame_length {frame_length} out of range for axis size {seq}")
    n_frames = 1 + (seq - frame_length) // hop_length
    if axis == -1:
        # [..., frame_length, num_frames]
        idx = (hop_length * jnp.arange(n_frames)[None, :]
               + jnp.arange(frame_length)[:, None])
        return x[..., idx]
    # axis == 0: [num_frames, frame_length, ...]
    idx = (hop_length * jnp.arange(n_frames)[:, None]
           + jnp.arange(frame_length)[None, :])
    return x[idx]


def frame(x, frame_length, hop_length, axis=-1, name=None):
    """reference signal.py:30."""
    return _frame(x, frame_length=int(frame_length),
                  hop_length=int(hop_length), axis=int(axis))


@op("signal_overlap_add")
def _overlap_add(x, hop_length, axis=-1):
    if axis not in (0, -1):
        raise ValueError(f"overlap_add axis must be 0 or -1, got {axis}")
    if axis == -1:
        frame_length, n_frames = x.shape[-2], x.shape[-1]
        seq = (n_frames - 1) * hop_length + frame_length
        idx = (hop_length * jnp.arange(n_frames)[None, :]
               + jnp.arange(frame_length)[:, None])  # [fl, nf]
        out = jnp.zeros(x.shape[:-2] + (seq,), x.dtype)
        return out.at[..., idx].add(x)
    n_frames, frame_length = x.shape[0], x.shape[1]
    seq = (n_frames - 1) * hop_length + frame_length
    idx = (hop_length * jnp.arange(n_frames)[:, None]
           + jnp.arange(frame_length)[None, :])  # [nf, fl]
    out = jnp.zeros((seq,) + x.shape[2:], x.dtype)
    return out.at[idx].add(x)


def overlap_add(x, hop_length, axis=-1, name=None):
    """reference signal.py:145."""
    return _overlap_add(x, hop_length=int(hop_length), axis=int(axis))


def _pad_window(window, win_length, n_fft):
    """Center-pad a [win_length] window to n_fft (reference stft contract)."""
    if window is None:
        w = np.ones(win_length, np.float32)
    else:
        w = np.asarray(window._data if isinstance(window, Tensor) else window,
                       dtype=np.float32)
        assert w.shape == (win_length,), (
            f"window must be 1-D of size {win_length}, got {w.shape}")
    if win_length < n_fft:
        pad_l = (n_fft - win_length) // 2
        w = np.pad(w, (pad_l, n_fft - win_length - pad_l))
    return w


@op("signal_stft_frames")
def _stft_frames(x, w, n_fft, hop_length, center=True, pad_mode="reflect",
                 scale=1.0):
    if center:
        pad = [(0, 0)] * (x.ndim - 1) + [(n_fft // 2, n_fft // 2)]
        x = jnp.pad(x, pad, mode=pad_mode)
    frames = _frame.raw_fn(x, n_fft, hop_length, axis=-1)
    return frames * (w[:, None] * scale).astype(frames.dtype)


def stft(x, n_fft, hop_length=None, win_length=None, window=None, center=True,
         pad_mode="reflect", normalized=False, onesided=True, name=None):
    """reference signal.py:246 — output [..., n_fft//2+1 | n_fft,
    num_frames] complex."""
    hop_length = int(hop_length if hop_length is not None else n_fft // 4)
    win_length = int(win_length if win_length is not None else n_fft)
    w = _pad_window(window, win_length, int(n_fft))
    # fold the 1/sqrt(n_fft) normalization into the REAL frames
    scale = 1.0 / float(np.sqrt(n_fft)) if normalized else 1.0
    frames = _stft_frames(x, w, n_fft=int(n_fft), hop_length=hop_length,
                          center=bool(center), pad_mode=str(pad_mode),
                          scale=scale)
    if onesided:
        return _fft.rfft(frames, n=int(n_fft), axis=-2)
    return _fft.fft(frames, n=int(n_fft), axis=-2)


@op("signal_istft_finish")
def _istft_finish(frames, w, hop_length, n_fft, center, length, scale=1.0):
    """frames: [..., n_fft, num_frames] REAL; window-weight, overlap-add,
    divide by the squared-window envelope, trim."""
    n_frames = frames.shape[-1]
    wf = w.astype(frames.dtype)
    frames = frames * (wf[:, None] * scale)
    out = _overlap_add.raw_fn(frames, hop_length, axis=-1)
    env = _overlap_add.raw_fn(
        jnp.broadcast_to((wf * wf)[:, None], (n_fft, n_frames)),
        hop_length, axis=-1)
    out = out / jnp.maximum(env, 1e-11)
    if center:
        out = out[..., n_fft // 2: out.shape[-1] - n_fft // 2]
    if length is not None:
        out = out[..., :length]
    return out


def istft(x, n_fft, hop_length=None, win_length=None, window=None,
          center=True, normalized=False, onesided=True, length=None,
          return_complex=False, name=None):
    """reference signal.py:423 — input [..., n_fft//2+1 | n_fft,
    num_frames] complex; least-squares (windowed overlap-add) inverse."""
    if return_complex:
        raise NotImplementedError(
            "istft(return_complex=True) is unsupported on the TPU backend "
            "(complex time-domain signals)")
    hop_length = int(hop_length if hop_length is not None else n_fft // 4)
    win_length = int(win_length if win_length is not None else n_fft)
    w = _pad_window(window, win_length, int(n_fft))
    if onesided:
        frames = _fft.irfft(x, n=int(n_fft), axis=-2)
    else:
        frames = _fft.ifft(x, n=int(n_fft), axis=-2).real()
    scale = float(np.sqrt(n_fft)) if normalized else 1.0
    return _istft_finish(frames, w, hop_length=hop_length, n_fft=int(n_fft),
                         center=bool(center), length=length, scale=scale)
