"""Paged decode attention — kernel routing + pure-``lax`` fallback.

The serving engine's decode step calls :func:`paged_decode_attention` once
per layer inside its compiled graph. On TPU (or in Pallas interpret mode)
it routes to the Pallas kernel in ``ops/pallas/paged_attention.py``; on
CPU it runs the pure-``lax`` fallback below — a gather of each request's
pages out of the pool followed by a masked dense attention — which is the
numerical reference the kernel (and the tests) are matched against.

CPU-fallback contract (see DESIGN_DECISIONS.md): same signature, same
ragged-length semantics, outputs matched to the dense llama attention —
only the memory-traffic shape differs (the fallback materializes the
gathered [B, P*block, Hkv, D] view; the kernel never does).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from ...nn.functional.flash_attention import _sdpa_ref

__all__ = ["paged_decode_attention", "paged_multiquery_attention",
           "kv_pool_specs"]


def kv_pool_specs(plan, num_heads, num_kv_heads):
    """``(pool spec, scale-pool spec)`` of the paged KV pools
    ``[N, block, Hkv, D]`` / ``[N, block, Hkv]`` under a plan: kv heads
    over the plan's head axis when it divides both head counts, else
    replicated. The engine commits its pools to these and the kernels
    below run per shard on the same split."""
    from jax.sharding import PartitionSpec as P

    ax = plan.head_axis_for(num_heads, num_kv_heads)
    return P(None, None, ax, None), P(None, None, ax)


def _per_shard_paged(kernel, q, k_pool, quantized, n_index):
    """The Pallas paged kernel, run per shard when a multi-device plan is
    tracing: q heads and pool kv heads split over the plan's head axis,
    the ``n_index`` block-table/length operands replicated."""
    from ...distributed.plan import active_plan
    from jax.sharding import PartitionSpec as P

    plan = active_plan()
    if plan is None:
        return kernel
    pool, scale = kv_pool_specs(plan, q.shape[-2], k_pool.shape[2])
    q_spec = P(*([None] * (q.ndim - 2)), pool[2], None)
    in_specs = (q_spec, pool, pool) + (P(),) * n_index \
        + ((scale, scale) if quantized else ())
    return plan.per_shard(kernel, in_specs, q_spec)


def _gather_kv(pool, scale_pool, block_tables):
    """Gather a request-major [B, P*block, Hkv, D] view of the pool,
    dequantizing int8 codes with their per-row scales when a scale pool
    is given — the SAME ``codes * scale`` multiply the Pallas kernel
    does in VMEM, just materialized (this is the fallback's documented
    memory-traffic difference)."""
    b, p = block_tables.shape
    n, block_size, hkv, d = pool.shape
    g = pool[block_tables].reshape(b, p * block_size, hkv, d)
    if scale_pool is not None:
        s = scale_pool[block_tables].reshape(b, p * block_size, hkv)
        g = g.astype(jnp.float32) * s[..., None]
    return g


def _lax_fallback(q, k_pool, v_pool, block_tables, context_lens, scale,
                  k_scale=None, v_scale=None):
    """q [B, 1, H, D] -> [B, 1, H, D] via gather + masked dense sdpa."""
    b, p = block_tables.shape
    block_size = k_pool.shape[1]
    k = _gather_kv(k_pool, k_scale, block_tables)
    v = _gather_kv(v_pool, v_scale, block_tables)
    pos = jnp.arange(p * block_size, dtype=jnp.int32)[None, :]
    mask = (pos < context_lens[:, None])[:, None, None, :]  # [B,1,1,S]
    return _sdpa_ref.raw_fn(q, k, v, attn_mask=mask, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           scale=None, k_scale=None, v_scale=None):
    """One decode token per request against the paged pool.

    q: [B, 1, H, D] (the just-written token's query); pools
    [N, block, Hkv, D]; block_tables [B, P] int32; context_lens [B] int32
    counting tokens INCLUDING the one just written. Returns [B, 1, H, D].
    ``k_scale``/``v_scale`` ([N, block, Hkv] f32) arm the int8
    dequant-in-kernel path (ISSUE 14) when the pools hold codes.
    """
    d = q.shape[-1]
    block_size = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    from ...ops.pallas.paged_attention import (
        paged_decode_attention_pallas, use_pallas_paged)

    if use_pallas_paged(d, block_size):
        def kernel(q, k_pool, v_pool, tables, lens, *scales):
            return paged_decode_attention_pallas(
                q, k_pool, v_pool, tables, lens, scale,
                **dict(zip(("k_scale", "v_scale"), scales)))

        scales = () if k_scale is None else (k_scale, v_scale)
        out = _per_shard_paged(kernel, q[:, 0], k_pool, bool(scales), 2)(
            q[:, 0], k_pool, v_pool, block_tables, context_lens, *scales)
        return out[:, None]
    return _lax_fallback(q, k_pool, v_pool, block_tables, context_lens,
                         float(scale), k_scale=k_scale, v_scale=v_scale)


def _lax_multiquery_fallback(q, k_pool, v_pool, block_tables, context_lens,
                             q_start, scale, k_scale=None, v_scale=None):
    """q [B, T, H, D] -> [B, T, H, D]: gather + per-row causal mask."""
    b, t = q.shape[0], q.shape[1]
    block_size = k_pool.shape[1]
    p = block_tables.shape[1]
    k = _gather_kv(k_pool, k_scale, block_tables)
    v = _gather_kv(v_pool, v_scale, block_tables)
    pos = jnp.arange(p * block_size, dtype=jnp.int32)[None, None, :]
    row = jnp.arange(t, dtype=jnp.int32)[None, :, None]
    # query row i sits at absolute position q_start+i: it may attend to
    # every token at position <= q_start+i that is inside the context
    allowed = (pos <= q_start[:, None, None] + row) \
        & (pos < context_lens[:, None, None])
    return _sdpa_ref.raw_fn(q, k, v, attn_mask=allowed[:, None], scale=scale)


def paged_multiquery_attention(q, k_pool, v_pool, block_tables, context_lens,
                               q_start, scale=None, k_scale=None,
                               v_scale=None):
    """T query tokens per request against the paged pool — the shared
    primitive behind chunked prefill (a block-aligned chunk of the prompt
    at offset ``q_start``) and speculative verify (k+1 draft positions
    scored in one step).

    q: [B, T, H, D] (queries at absolute positions ``q_start[b] + t``);
    pools [N, block, Hkv, D]; block_tables [B, P] int32; context_lens [B]
    int32 — total visible tokens INCLUDING the last real query row (rows
    past ``context_lens - q_start`` are padding; their output is
    unspecified and must be ignored by the caller). Causal within the
    window: row t attends to positions <= q_start + t. Returns
    [B, T, H, D].
    """
    d = q.shape[-1]
    block_size = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    from ...ops.pallas.paged_attention import (
        paged_multiquery_attention_pallas, use_pallas_paged)

    if use_pallas_paged(d, block_size):
        def kernel(q, k_pool, v_pool, tables, lens, starts, *scales):
            return paged_multiquery_attention_pallas(
                q, k_pool, v_pool, tables, lens, starts, float(scale),
                **dict(zip(("k_scale", "v_scale"), scales)))

        scales = () if k_scale is None else (k_scale, v_scale)
        return _per_shard_paged(kernel, q, k_pool, bool(scales), 3)(
            q, k_pool, v_pool, block_tables, context_lens, q_start, *scales)
    return _lax_multiquery_fallback(q, k_pool, v_pool, block_tables,
                                    context_lens, q_start, float(scale),
                                    k_scale=k_scale, v_scale=v_scale)
