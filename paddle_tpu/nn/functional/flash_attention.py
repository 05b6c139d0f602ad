"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py (flash_attention
at :146, scaled_dot_product_attention at :441) binding third_party/flashattn
CUDA kernels. TPU-native design: a Pallas flash-attention kernel
(paddle_tpu/ops/pallas/flash_attention.py) on TPU backends, with an XLA
reference path (still fused well by XLA) elsewhere. Layout follows paddle:
[batch, seqlen, num_heads, head_dim].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...core.dispatch import op
from ...core.tensor import Tensor

__all__ = ["flash_attention", "scaled_dot_product_attention",
           "flash_attn_unpadded", "sdp_kernel", "fused_rope_attention"]


#: the flash kernels keep one head's whole K/V sequence (and q/dO/O in the
#: dK/dV pass) resident in VMEM, lane-padded to 128. Past this many bytes
#: per operand Mosaic runs out of scoped VMEM: 8192 x 128 bf16 compiles,
#: 16384 x 128 bf16 and 8192 x 128 f32 are refused (v5e, libtpu 0.0.34).
_FLASH_MAX_SEQ_BYTES = 8192 * 128 * 2


def _use_pallas(q, k=None):
    """The shapes the Pallas flash kernels tile, decided from the backend
    and shapes alone; everything else takes the XLA path. The kernel's
    causal mask is aligned for seq_q == seq_k only, so KV-cache prefill
    (seq_k > seq_q) takes the XLA path, whose tril mask is bottom-right
    aligned like the reference. The lse block's lane dim is the q block,
    hence whole 128-row tiles."""
    if jax.default_backend() != "tpu":
        return False
    if k is not None and q.shape[1] != k.shape[1]:
        return False
    seq, d = q.shape[1], q.shape[3]
    itemsize = jnp.dtype(getattr(q, "dtype", jnp.float32)).itemsize
    return (seq % 128 == 0 and d % 64 == 0
            and seq * max(d, 128) * itemsize <= _FLASH_MAX_SEQ_BYTES)


@op("sdpa_ref")
def _sdpa_ref(q, k, v, attn_mask=None, dropout_key=None, causal=False,
              dropout=0.0, scale=None):
    # [B, S, H, D] -> [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # GQA: broadcast kv heads if fewer than q heads
    hq, hk = qt.shape[1], kt.shape[1]
    if hk != hq:
        rep = hq // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_key is not None and dropout > 0.0:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


#: which kernel the last scaled_dot_product_attention call used
#: ("pallas" | "pallas_rope" | "xla"), chosen by ``_use_pallas`` from the
#: backend and shapes alone — a kernel that fails to build raises
LAST_PATH = None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """paddle.nn.functional.scaled_dot_product_attention
    (reference flash_attention.py:441)."""
    global LAST_PATH
    from ...core import rng

    dk = None
    if dropout_p > 0.0 and training:
        dk = rng.next_key()
    if _use_pallas(query, key) and attn_mask is None and dropout_p == 0.0:
        from ...ops.pallas.flash_attention import flash_attention_fwd

        LAST_PATH = "pallas"
        return flash_attention_fwd(query, key, value, causal=bool(is_causal))
    LAST_PATH = "xla"
    return _sdpa_ref(query, key, value, attn_mask, dk, causal=bool(is_causal),
                     dropout=float(dropout_p))


def fused_rope_attention_enabled(batch, seq, heads, head_dim):
    """Cheap pre-projection gate so callers can skip building q/k/v for the
    fused path when it will not be taken (the shapes alone decide)."""
    import os

    if os.environ.get("PT_FUSED_ROPE", "0") != "1":
        return False

    class _S:
        shape = (batch, seq, heads, head_dim)

    return _use_pallas(_S(), _S()) and head_dim % 2 == 0


def fused_rope_attention(query, key, value, cos, sin, is_causal=True,
                         training=True):
    """Rope-fused flash attention: q/k arrive PRE-rotary and the rotation
    runs inside the Pallas kernels (ops/pallas/flash_attention.py), saving
    one HBM round-trip per q/k per layer in forward AND backward. Returns
    None when the fused path is unavailable (caller applies rope + sdpa).

    Analog: the reference's fused rope kernels
    (paddle/phi/kernels/fusion/gpu/fused_rope_grad_kernel.cu,
    fused_multi_transformer_op.cu) bound via incubate.nn.functional."""
    global LAST_PATH
    import os

    # default OFF: on v5e the in-kernel rotation recomputes rope on every
    # (q-block, kv-block) pair in backward, and the measured extra VPU work
    # outweighs the saved HBM round-trips (PERF.md r4 ablation: 120.4k vs
    # 124.9k tok/s on the llama-125m bench). Opt in with PT_FUSED_ROPE=1 —
    # profitable when attention is DMA-bound rather than VPU-bound.
    if os.environ.get("PT_FUSED_ROPE", "0") != "1":
        return None
    if not (_use_pallas(query, key) and query.shape[3] % 2 == 0
            and cos.shape[0] == query.shape[1]):
        return None
    from ...ops.pallas.flash_attention import flash_attention_rope_fwd

    LAST_PATH = "pallas_rope"
    return flash_attention_rope_fwd(query, key, value, cos, sin,
                                    causal=bool(is_causal))


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention (reference :146).
    Returns (out, softmax) like the reference (softmax is None unless
    return_softmax)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal,
                                       training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, training=True,
                        name=None):
    """Varlen API parity: fall back to dense by reshaping (single sequence)."""
    q = query.unsqueeze(0) if query.ndim == 3 else query
    k = key.unsqueeze(0) if key.ndim == 3 else key
    v = value.unsqueeze(0) if value.ndim == 3 else value
    out = scaled_dot_product_attention(q, k, v, None, dropout, causal, training)
    return (out.squeeze(0) if query.ndim == 3 else out), None


class sdp_kernel:
    """Context manager API parity (torch-style backend selection no-op)."""

    def __init__(self, **kwargs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
