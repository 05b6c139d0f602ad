"""Flash attention — Pallas TPU kernels.

Replaces the reference's CUDA flash-attention binding
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party/flashattn) with a
TPU-native online-softmax kernel:

* forward: one pass over KV blocks per Q block, fp32 accumulators in VMEM,
  saves per-row logsumexp for the backward
* backward: recompute-style — a dQ kernel (loop over KV) and a dKV kernel
  (loop over Q), the standard FlashAttention-2 split
* causal masking bounds the KV loop per Q block (traced fori_loop bound), so
  causal attention does ~half the FLOPs — the analog of the CUDA kernel's
  block early-exit

Layout contract: [batch, seq, heads, head_dim] (paddle convention) at the API;
kernels run on [batch*heads, seq, head_dim]. The nn.functional wrapper only
routes here when head_dim % 128 == 0 and seq divides the block size — else it
falls back to the XLA path.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


import os


def _interpret():
    """Run the kernels in Pallas interpret mode (CPU parity tests)."""
    return os.environ.get("PT_PALLAS_INTERPRET", "0") == "1"


def _pick_block(env_var, default, extent, floor=1):
    """Largest size <= min(env override, default) that divides ``extent``
    (halving search), clamped to ``floor``. Shared by all Pallas modules."""
    b = min(int(os.environ.get(env_var, default)), extent)
    while extent % b:
        b //= 2
    return max(b, floor)


def _block_sizes(seq_q, seq_k):
    return (_pick_block("PT_FA_BQ", 512, seq_q, floor=8),
            _pick_block("PT_FA_BK", 512, seq_k))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rot_f32(x, c, s):
    """Apply rotary embedding in-register: x (n, d) f32, c/s full-width
    (n, d) cos/sin tables. rot(x) = [-x2, x1]; rope(x) = x*c + rot(x)*s.
    The inverse rotation (used on gradients) is the same with s negated."""
    d2 = x.shape[-1] // 2
    rot = jnp.concatenate([-x[:, d2:], x[:, :d2]], axis=-1)
    return x * c + rot * s


def _fwd_kernel(*refs, scale, causal, block_k, rope=False):
    if rope:
        q_ref, k_ref, v_ref, cs_ref, sn_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    _, bq, d = q_ref.shape
    sk = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    if rope:
        qsl = pl.ds(qi * bq, bq)
        q = _rot_f32(q, cs_ref[qsl, :], sn_ref[qsl, :])
    q = q * scale

    acc = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)

    num_k = sk // block_k
    if causal:
        num_k_run = jnp.minimum(
            num_k, ((qi + 1) * bq + block_k - 1) // block_k)
    else:
        num_k_run = num_k

    def body(kb, carry):
        acc, m, l = carry
        ksl = pl.ds(kb * block_k, block_k)
        k = k_ref[0, ksl, :].astype(jnp.float32)
        v = v_ref[0, ksl, :].astype(jnp.float32)
        if rope:
            k = _rot_f32(k, cs_ref[ksl, :], sn_ref[ksl, :])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                            (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot(p, v,
                                       preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, m, l = jax.lax.fori_loop(0, num_k_run, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


def _fwd(q, k, v, scale, causal, block_q, block_k, rope_cs=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
    ]
    args = [q, k, v]
    if rope_cs is not None:
        in_specs += [pl.BlockSpec((sk, d), lambda b, i: (0, 0))] * 2
        args += list(rope_cs)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, rope=rope_cs is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, block_k, rope=False):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, cs_ref, sn_ref,
         dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref = refs
    _, bq, d = q_ref.shape
    sk = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    if rope:
        qsl = pl.ds(qi * bq, bq)
        q = _rot_f32(q, cs_ref[qsl, :], sn_ref[qsl, :])
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    delta = jnp.sum(do * o, axis=1, keepdims=True)

    num_k = sk // block_k
    if causal:
        num_k_run = jnp.minimum(
            num_k, ((qi + 1) * bq + block_k - 1) // block_k)
    else:
        num_k_run = num_k

    def body(kb, dq):
        ksl = pl.ds(kb * block_k, block_k)
        k = k_ref[0, ksl, :].astype(jnp.float32)
        v = v_ref[0, ksl, :].astype(jnp.float32)
        if rope:
            k = _rot_f32(k, cs_ref[ksl, :], sn_ref[ksl, :])
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                            (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_k_run, body,
                           jnp.zeros((bq, d), jnp.float32))
    if rope:
        # grads rotate back through the q rope (inverse = negated sin)
        qsl = pl.ds(qi * bq, bq)
        dq = _rot_f32(dq, cs_ref[qsl, :], -sn_ref[qsl, :])
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, rope=False):
    if rope:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, cs_ref, sn_ref,
         dk_ref, dv_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_ref, dv_ref = refs
    _, bk, d = k_ref.shape
    sq = q_ref.shape[1]
    kb = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    if rope:
        kvsl = pl.ds(kb * bk, bk)
        k = _rot_f32(k, cs_ref[kvsl, :], sn_ref[kvsl, :])
    v = v_ref[0].astype(jnp.float32)

    num_q = sq // block_q
    if causal:
        # first q block that sees this kv block
        q_start = (kb * bk) // block_q
    else:
        q_start = 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        if rope:
            qsl = pl.ds(qi * block_q, block_q)
            q = _rot_f32(q, cs_ref[qsl, :], sn_ref[qsl, :])
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        o = o_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        delta = jnp.sum(do * o, axis=1, keepdims=True)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        q_start, num_q, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    if rope:
        kvsl = pl.ds(kb * bk, bk)
        dk = _rot_f32(dk, cs_ref[kvsl, :], -sn_ref[kvsl, :])
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, dout, rope_cs=None):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    rope = rope_cs is not None
    rope_specs = ([pl.BlockSpec((sk, d), lambda b, i: (0, 0))] * 2
                  if rope else [])
    rope_args = list(rope_cs) if rope else []
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, rope=rope),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ] + rope_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(q, k, v, dout, out, lse, *rope_args)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, rope=rope),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda b, i: (b, 0, 0)),
        ] + rope_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, dout, out, lse, *rope_args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_mha(q, k, v, scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_mha_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(scale, causal, block_q, block_k, res, dout):
    return _bwd(scale, causal, block_q, block_k, res, dout)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


# ---------------------------------------------------------------------------
# rope-fused variant: q/k arrive PRE-rotary; the rotation happens in VMEM
# inside every kernel (and its transpose on the dq/dk gradients), so the
# roped q/k never round-trip through HBM. Analog of the reference's fused
# rope + attention ops (paddle/phi/kernels/fusion/gpu/fused_rope_*.cu,
# fused_multi_transformer_op.cu) — here it also shrinks the custom-vjp
# residuals to the raw projection outputs.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_mha_rope(q, k, v, c2, s2, scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k,
                  rope_cs=(c2, s2))
    return out


def _flash_mha_rope_fwd(q, k, v, c2, s2, scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k,
                    rope_cs=(c2, s2))
    return out, (q, k, v, out, lse, c2, s2)


def _flash_mha_rope_bwd(scale, causal, block_q, block_k, res, dout):
    q, k, v, out, lse, c2, s2 = res
    dq, dk, dv = _bwd(scale, causal, block_q, block_k,
                      (q, k, v, out, lse), dout, rope_cs=(c2, s2))
    return dq, dk, dv, jnp.zeros_like(c2), jnp.zeros_like(s2)


_flash_mha_rope.defvjp(_flash_mha_rope_fwd, _flash_mha_rope_bwd)


from ...core.dispatch import op as _op


def _run_bshd(mha, q, k, v, *tables):
    """Run a ``[B*H, S, D]`` flash kernel ``mha(qt, kt, vt, *tables, bq,
    bk)`` on paddle-layout ``[B, S, H, D]`` operands (GQA: kv heads
    broadcast). When a multi-device plan is tracing, the call runs per
    shard — GSPMD cannot partition a Mosaic call: batch over the plan's
    data axis, heads over its head axis, each only where it divides;
    ``tables`` (rope) are replicated."""
    from ...distributed.plan import active_plan
    from jax.sharding import PartitionSpec as P

    def local(q, k, v, *tables):
        b, sq, hq, d = q.shape
        hk = k.shape[2]
        if hk != hq:
            rep = hq // hk
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        qt = jnp.swapaxes(q, 1, 2).reshape(b * hq, sq, d)
        kt = jnp.swapaxes(k, 1, 2).reshape(b * hq, k.shape[1], d)
        vt = jnp.swapaxes(v, 1, 2).reshape(b * hq, v.shape[1], d)
        out = mha(qt, kt, vt, *tables, *_block_sizes(sq, kt.shape[1]))
        return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2)

    plan = active_plan()
    if plan is not None:
        spec = P(plan.batch_axis_for(q.shape[0]), None,
                 plan.head_axis_for(q.shape[2], k.shape[2]), None)
        local = plan.per_shard(
            local, (spec,) * 3 + (P(),) * len(tables), spec)
    return local(q, k, v, *tables)


@_op("flash_attention_pallas")
def _flash_attention_arrays(q, k, v, causal=True, scale=None):
    """q/k/v: [B, S, H, D] (paddle layout). GQA: kv heads broadcast."""
    s = float(scale if scale is not None else 1.0 / math.sqrt(q.shape[3]))
    return _run_bshd(
        lambda qt, kt, vt, bq, bk: _flash_mha(qt, kt, vt, s, bool(causal),
                                              bq, bk), q, k, v)


def flash_attention_fwd(q, k, v, causal=True, scale=None):
    """Tensor-level entry used by nn.functional (dispatch wraps autograd)."""
    return _flash_attention_arrays(q, k, v, causal=bool(causal), scale=scale)


def _widen_tables(cos, sin):
    """[S, D/2] rope tables -> full-width [S, D] f32 (both halves)."""
    return (jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32),
            jnp.concatenate([sin, sin], axis=-1).astype(jnp.float32))


def _rope_widened(x, c2, s2):
    """Batched rope with full-width tables; x [..., S, D], c2/s2
    broadcastable [S, D]. Same half-split convention as _rot_f32 /
    models/llama.py:_rope_apply."""
    d2 = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., d2:], x[..., :d2]], axis=-1)
    return (x.astype(jnp.float32) * c2
            + rot.astype(jnp.float32) * s2).astype(x.dtype)


@_op("flash_attention_rope_pallas")
def _flash_attention_rope_arrays(q, k, v, cos, sin, causal=True, scale=None):
    """Rope-fused flash attention. q/k/v: [B, S, H, D] PRE-rotary;
    cos/sin: [S, D/2] rope tables (models/llama.py:_rope_cache layout)."""
    s = float(scale if scale is not None else 1.0 / math.sqrt(q.shape[3]))
    return _run_bshd(
        lambda qt, kt, vt, c2, s2, bq, bk: _flash_mha_rope(
            qt, kt, vt, c2, s2, s, bool(causal), bq, bk),
        q, k, v, *_widen_tables(cos, sin))


def flash_attention_rope_fwd(q, k, v, cos, sin, causal=True, scale=None):
    """Tensor-level rope-fused entry used by nn.functional."""
    return _flash_attention_rope_arrays(q, k, v, cos, sin,
                                        causal=bool(causal), scale=scale)


@_op("attention_block_bhsd")
def _attention_block_bhsd(x, wq, wk, wv, wo, cos, sin, num_heads=1,
                          num_kv_heads=1, causal=True):
    """Whole attention block as ONE op with head-major internal layout:
    the projections produce [b, h, s, d] directly (einsum folds the head
    transpose into the matmul), rope applies in that layout, the kernel
    consumes a free reshape to [b*h, s, d], and the output projection
    contracts [b, h, s, d] straight back to [b, s, H] — the four 25 MB
    HBM transposes per layer of the [b, s, h, d] path never happen.

    Experimental (PT_ATTN_EINSUM=1): measured against the default path in
    PERF.md. x: [B, S, K]; wq/wk/wv: [K, H*D] or [K, Hkv*D]; wo: [H*D, K];
    cos/sin: [S, D/2]."""
    b, s, kdim = x.shape
    d = wq.shape[1] // num_heads
    wq4 = wq.reshape(kdim, num_heads, d)
    wk4 = wk.reshape(kdim, num_kv_heads, d)
    wv4 = wv.reshape(kdim, num_kv_heads, d)
    q = jnp.einsum("bsk,khd->bhsd", x, wq4)
    k = jnp.einsum("bsk,khd->bhsd", x, wk4)
    v = jnp.einsum("bsk,khd->bhsd", x, wv4)
    c2, s2 = _widen_tables(cos, sin)
    q = _rope_widened(q, c2, s2)
    k = _rope_widened(k, c2, s2)
    if num_kv_heads != num_heads:
        rep = num_heads // num_kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / math.sqrt(d)
    bq, bk = _block_sizes(s, s)
    out = _flash_mha(q.reshape(b * num_heads, s, d),
                     k.reshape(b * num_heads, s, d),
                     v.reshape(b * num_heads, s, d),
                     float(scale), bool(causal), bq, bk)
    out4 = out.reshape(b, num_heads, s, d)
    wo4 = wo.reshape(num_heads, d, kdim)
    return jnp.einsum("bhsd,hdk->bsk", out4, wo4)
