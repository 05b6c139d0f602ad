"""Paged-attention decode kernel — Pallas TPU (ISSUE 7 tentpole, part b).

Single-token decode over a block-paged KV cache (PAPERS.md: "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU").
Each grid step (request b, page p) DMAs ONE pool block — chosen by the
scalar-prefetched block table, so the gather never materializes the
per-request KV in HBM — and folds it into an online-softmax accumulator
held in VMEM scratch across the page loop. Ragged per-request lengths come
from the scalar-prefetched ``context_lens``: pages past a request's length
are skipped (``pl.when``), and the tail page masks positions beyond the
length, so ONE compiled kernel serves any mix of request lengths — the
whole point of the paged layout.

Layouts:
  q            [B, H, D]         (one decode token per request)
  k/v pool     [N, block, Hkv, D]
  block_tables [B * P] int32     (flattened; P = max pages per request)
  context_lens [B]     int32     (tokens INCLUDING the one just written)

GQA: q arrives grouped ``[B, G, Hkv, D]`` (head ``h = kvh * G + g``), so
each group's heads line up one-to-one with the pool block's kv heads and
the pool stays at Hkv.

The decode kernel is a VPU kernel: one query row per head has no matmul
``M`` dimension for the MXU (Mosaic rejects the ``[H,D]·[H,blk,D]`` batched
mat-vec outright), so scores are an elementwise multiply + lane reduce
over the block exactly as it sits in VMEM (``[blk, Hkv, D]``, no
transpose) and the PV fold is a multiply + sum over the block's leading
dim. The multi-query kernel (prefill chunks, speculative verify) has real
``M = T`` rows and uses batched MXU dots; its query rows are tiled over a
grid axis so VMEM holds one tile (``_MQ_ROWS``), not the whole chunk.

**Quantized pools (ISSUE 14, dequant-in-kernel):** with
``kv_dtype="int8"`` the pools hold int8 codes and two sidecar scale
pools ``[N, block, Hkv]`` f32 ride along. The kernels take two extra
scalar-prefetch-indexed operands — the scale rows of exactly the block
being DMA'd — and dequantize IN VMEM (``codes.astype(f32) *
scale[..., None]``) right before the existing online-softmax fold, so
HBM traffic per page drops ~4x while the attention math past the
dequant is bit-identical to the fp kernel fed the dequantized values.
The lax path in ``inference/serving/paged_attention.py`` (CPU backends)
mirrors the same gather + multiply. The scale pools' lane dim is Hkv:
Mosaic takes it as is, but HBM tiles pad it to 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _interpret

__all__ = ["paged_decode_attention_pallas",
           "paged_multiquery_attention_pallas", "use_pallas_paged"]


def use_pallas_paged(head_dim, block_size):
    """The real-TPU gate: MXU-friendly head_dim and a lane-aligned block.
    Interpret mode (PT_PALLAS_INTERPRET=1) runs anywhere for parity tests."""
    if _interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    return head_dim % 128 == 0 and block_size % 8 == 0


def _kernel(tables_ref, lens_ref, *refs, block_size, groups, scale,
            quantized=False):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = lens_ref[pl.program_id(0)]
    n_pages = (ctx + block_size - 1) // block_size

    @pl.when(p < n_pages)
    def _page():
        k = k_ref[0].astype(jnp.float32)                  # [block, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequant-in-kernel: the DMA'd block is int8 codes; its scale
            # rows [block, Hkv] ride in as scalar-prefetch-indexed
            # operands and the multiply happens here in VMEM — HBM never
            # sees a dequantized page
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        tok = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, k.shape[1], 1), 0)
        visible = tok < ctx
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32) * scale   # [Hkv, D]
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True)  # [blk, Hkv, 1]
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[g]                             # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            pexp = jnp.exp(s - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * corr + jnp.sum(pexp, axis=0)
            acc_ref[g] = acc_ref[g] * corr + jnp.sum(pexp * v, axis=0)
            m_ref[g] = m_new
        # revisited output block: the LAST active page's write survives
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                  context_lens, scale,
                                  k_scale=None, v_scale=None):
    """q [B, H, D]; pools [N, block, Hkv, D]; block_tables [B, P] int32;
    context_lens [B] int32. Returns [B, H, D]. With int8 pools,
    ``k_scale``/``v_scale`` [N, block, Hkv] f32 arm dequant-in-kernel."""
    b, h, d = q.shape
    n, block_size, hkv, _ = k_pool.shape
    p = block_tables.shape[1]
    groups = h // hkv
    quantized = k_scale is not None
    tables_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    # head h = kvh * groups + g  ->  [B, G, Hkv, D]
    qg = jnp.swapaxes(q.reshape(b, hkv, groups, d), 1, 2)

    q_spec = pl.BlockSpec((1, groups, hkv, d),
                          lambda i, j, T, L: (i, 0, 0, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, block_size, hkv, d),
                     lambda i, j, T, L: (T[i * p + j], 0, 0, 0)),
        pl.BlockSpec((1, block_size, hkv, d),
                     lambda i, j, T, L: (T[i * p + j], 0, 0, 0)),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block_size, hkv),
                         lambda i, j, T, L: (T[i * p + j], 0, 0)),
            pl.BlockSpec((1, block_size, hkv),
                         lambda i, j, T, L: (T[i * p + j], 0, 0)),
        ]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((groups, hkv, d), jnp.float32),
            pltpu.VMEM((groups, hkv, 1), jnp.float32),
            pltpu.VMEM((groups, hkv, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, groups=groups,
                          scale=float(scale), quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, groups, hkv, d), q.dtype),
        interpret=_interpret(),
        name="paged_decode_attention",
    )(tables_flat, lens, *operands)
    return jnp.swapaxes(out, 1, 2).reshape(b, h, d)


#: multi-query grid tile: at most ``_MQ_ROWS`` query rows and at most
#: ``_MQ_ACC_ROWS`` accumulator rows (query rows x heads). The kernel keeps
#: one tile's ``[rows*H, D]`` f32 accumulator plus lane-padded
#: ``[rows*H, 1]`` max/sum in VMEM beside double-buffered q/out blocks:
#: ~5 MiB at 128 rows x 16 heads x 128 bf16, ~7 MiB with f32 queries —
#: inside the 16 MiB scoped-VMEM default, where a whole 2048-row prefill
#: bucket asked for ~130 MiB and 128 rows x 32 heads of f32 for 16.45 MiB.
_MQ_ROWS = 128
_MQ_ACC_ROWS = 2048


def _mq_kernel(tables_ref, lens_ref, starts_ref, *refs, block_size,
               groups, t_q, scale, quantized=False):
    """Multi-query variant (ISSUE 11): one ``t_q``-row tile of a request's
    query rows per grid step, folded into the accumulator's leading dim
    ([t_q*H, D]), per-row causal masking against the row's absolute
    position ``start + t``. Same one-block-DMA-per-grid-step structure as
    the decode kernel (CuBridge's iterate-on-the-verify-kernel guidance,
    PAPERS.md). ``quantized`` dequantizes the DMA'd int8 block in VMEM
    from its sidecar scale rows (ISSUE 14)."""
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    b = pl.program_id(0)
    ctx = lens_ref[b]
    # absolute position of this tile's first row
    start = starts_ref[b] + pl.program_id(1) * t_q
    # causal: no row of the tile sees past its last row
    seen = jnp.minimum(ctx, start + t_q)
    n_pages = (seen + block_size - 1) // block_size

    @pl.when(p < n_pages)
    def _page():
        h = q_ref.shape[2]
        q = q_ref[0].astype(jnp.float32) * scale          # [T, H, D]
        k = k_ref[0].astype(jnp.float32)                  # [block, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        kt = jnp.repeat(jnp.swapaxes(k, 0, 1), groups, axis=0)  # [H, blk, D]
        vt = jnp.repeat(jnp.swapaxes(v, 0, 1), groups, axis=0)
        # scores per (row=t*H+h, token-in-block): contract D against the
        # row's head slice of this page
        s = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((1,), (0,))))           # [H, T, blk]
        s = jnp.swapaxes(s, 0, 1).reshape(t_q * h, block_size)
        tok = p * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // h
        ok = (tok <= start + row_t) & (tok < ctx)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(ok, pexp, 0.0)  # rows with no visible token yet
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=1, keepdims=True)
        av = jax.lax.dot_general(
            pexp.reshape(t_q, h, block_size), vt,
            (((2,), (1,)), ((1,), (0,))))                  # [H, T, D]
        acc_ref[...] = acc_ref[...] * corr + \
            jnp.swapaxes(av, 0, 1).reshape(t_q * h, -1)
        m_ref[...] = m_new
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).reshape(t_q, h, -1).astype(o_ref.dtype)


def paged_multiquery_attention_pallas(q, k_pool, v_pool, block_tables,
                                      context_lens, q_start, scale,
                                      k_scale=None, v_scale=None):
    """q [B, T, H, D] at absolute positions ``q_start[b] + t``; pools
    [N, block, Hkv, D]; block_tables [B, P] int32; context_lens [B] int32
    (visible tokens including the last real query row). Returns
    [B, T, H, D]; rows past ``context_lens - q_start`` are padding and
    undefined. With int8 pools, ``k_scale``/``v_scale`` [N, block, Hkv]
    f32 arm dequant-in-kernel."""
    b, t, h, d = q.shape
    n, block_size, hkv, _ = k_pool.shape
    p = block_tables.shape[1]
    groups = h // hkv
    quantized = k_scale is not None
    tables_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    starts = q_start.astype(jnp.int32)
    # largest power of two within the tile bounds that divides T (T is a
    # leading block dim, so any divisor tiles; prefill buckets are block
    # multiples)
    t_q = min(_MQ_ROWS, t, max(1, _MQ_ACC_ROWS // h))
    t_q = 1 << (t_q.bit_length() - 1)
    while t % t_q:
        t_q //= 2

    q_spec = pl.BlockSpec((1, t_q, h, d),
                          lambda i, r, j, T, L, S: (i, r, 0, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, block_size, hkv, d),
                     lambda i, r, j, T, L, S: (T[i * p + j], 0, 0, 0)),
        pl.BlockSpec((1, block_size, hkv, d),
                     lambda i, r, j, T, L, S: (T[i * p + j], 0, 0, 0)),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block_size, hkv),
                         lambda i, r, j, T, L, S: (T[i * p + j], 0, 0)),
            pl.BlockSpec((1, block_size, hkv),
                         lambda i, r, j, T, L, S: (T[i * p + j], 0, 0)),
        ]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t // t_q, p),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((t_q * h, d), jnp.float32),
            pltpu.VMEM((t_q * h, 1), jnp.float32),
            pltpu.VMEM((t_q * h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mq_kernel, block_size=block_size, groups=groups,
                          t_q=t_q, scale=float(scale), quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
        interpret=_interpret(),
        name="paged_prefill_attention",
    )(tables_flat, lens, starts, *operands)
