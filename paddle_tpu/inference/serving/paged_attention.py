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

import functools
import math

import jax
import jax.numpy as jnp

from ...nn.functional.flash_attention import _sdpa_ref

__all__ = ["paged_decode_attention", "paged_decode_attention_latent",
           "paged_multiquery_attention", "paged_chunk_attention",
           "chunk_reads_in_a_row", "chunk_attention", "kv_pool_specs",
           "ChunkAttnState", "DecodeAttnState"]


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


def _softmax_with_sink(scores, allowed, sink):
    """Softmax over the last axis of ``scores [B, H, T, S]`` with
    ``allowed`` (broadcastable) masking keys out and, if given, ``sink [H]``
    joining the denominator as one more logit that carries no value."""
    scores = jnp.where(allowed, scores, -jnp.inf)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(scores.dtype)[None, :, None, None],
            scores.shape[:-1] + (1,))
        scores = jnp.concatenate([scores, col], -1)
    m = jnp.max(scores, -1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)   # a row that sees nothing
    e = jnp.exp(scores - m)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    return p[..., :-1] if sink is not None else p


def _masked_attention(q, k, v, allowed, scale, sink=None):
    """q [B, T, H, Dk], k [B, S, Hkv, Dk], v [B, S, Hkv, Dv], ``allowed``
    [B, 1, T, S] -> [B, T, H, Dv]; float32 inside."""
    groups = q.shape[2] // k.shape[2]
    kf = jnp.repeat(k.astype(jnp.float32), groups, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), groups, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kf) * scale
    p = _softmax_with_sink(s, allowed, sink)
    return jnp.einsum("bhts,bshd->bthd", p, vf).astype(q.dtype)


def _lax_fallback(q, k_pool, v_pool, block_tables, context_lens, scale,
                  k_scale=None, v_scale=None, window=None, ring=False,
                  sink=None):
    """q [B, 1, H, D] -> [B, 1, H, D] via gather + masked dense sdpa."""
    b, p = block_tables.shape
    block_size = k_pool.shape[1]
    k = _gather_kv(k_pool, k_scale, block_tables)
    v = _gather_kv(v_pool, v_scale, block_tables)
    slot_pos = jnp.arange(p * block_size, dtype=jnp.int32)[None, :]
    if ring:
        # slot s of a ring row holds the newest logical page congruent to
        # s: count back from the page of the token just written
        last = (context_lens[:, None] - 1) // block_size
        page = last - (last - slot_pos // block_size) % p
        pos = page * block_size + slot_pos % block_size
    else:
        pos = slot_pos
    allowed = (pos >= 0) & (pos < context_lens[:, None])
    if window is not None:
        allowed = allowed & (pos >= context_lens[:, None] - window)
    mask = allowed[:, None, None, :]  # [B,1,1,S]
    if window is None and sink is None and k.shape[-1] == v.shape[-1]:
        return _sdpa_ref.raw_fn(q, k, v, attn_mask=mask, scale=scale)
    return _masked_attention(q, k, v, mask, scale, sink)


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           scale=None, k_scale=None, v_scale=None, *,
                           window=None, ring=False, sink=None,
                           num_kv_heads=None,
                           name="paged_decode_attention"):
    """One decode token per request against the paged pool.

    q: [B, 1, H, D] (the just-written token's query); pools
    [N, block, Hkv, D] (V's width may differ from K's); block_tables [B, P]
    int32; context_lens [B] int32 counting tokens INCLUDING the one just
    written. Returns [B, 1, H, Dv].
    ``k_scale``/``v_scale`` ([N, block, Hkv] f32) arm the int8
    dequant-in-kernel path (ISSUE 14) when the pools hold codes.
    ``window`` keeps the newest ``window`` keys only, ``ring`` reads the
    table row as a ring of pages (slot = page % P), ``sink`` [H] joins the
    softmax's denominator; ``name`` is the kernel's name in a trace. Pools
    held as rows ``[N, block * Hkv, D]`` come with ``num_kv_heads``.
    """
    d = q.shape[-1]
    block_size = k_pool.shape[1] // (num_kv_heads if k_pool.ndim == 3 else 1)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    from ...ops.pallas.paged_attention import (
        paged_decode_attention_pallas, use_pallas_paged)

    if use_pallas_paged(d, block_size):
        extra = () if sink is None else (sink,)

        def kernel(q, k_pool, v_pool, tables, lens, *rest):
            rest = list(rest)
            sk = rest.pop(0) if sink is not None else None
            return paged_decode_attention_pallas(
                q, k_pool, v_pool, tables, lens, scale,
                **dict(zip(("k_scale", "v_scale"), rest)),
                window=window, ring=ring, sink=sk,
                num_kv_heads=num_kv_heads, name=name)

        scales = () if k_scale is None else (k_scale, v_scale)
        if sink is not None:
            from ...distributed.plan import active_plan
            if active_plan() is not None:
                raise NotImplementedError(
                    "paged attention with a sink runs on one device")
            out = kernel(q[:, 0], k_pool, v_pool, block_tables,
                         context_lens, *extra, *scales)
        else:
            out = _per_shard_paged(kernel, q[:, 0], k_pool, bool(scales), 2)(
                q[:, 0], k_pool, v_pool, block_tables, context_lens, *scales)
        return out[:, None]
    if k_pool.ndim == 3:
        k_pool, v_pool = (a.reshape(a.shape[0], block_size, num_kv_heads,
                                    a.shape[-1]) for a in (k_pool, v_pool))
    return _lax_fallback(q, k_pool, v_pool, block_tables, context_lens,
                         float(scale), k_scale=k_scale, v_scale=v_scale,
                         window=window, ring=ring, sink=sink)


def paged_decode_attention_latent(q, pool, block_tables, context_lens, scale,
                                  v_dim, name="paged_decode_attention_latent"):
    """One decode token a request against latent pages (ISSUE 31): q
    ``[B, H, D]``, every head's ``[q_lat | q_rope]`` at the stored width;
    pool ``[N, block, D]``, one row a token that all heads share. Scores
    over the whole row, values its first ``v_dim``; returns ``[B, H,
    v_dim]``. Pallas on the TPU, the gather below here."""
    from ...ops.pallas.paged_attention import (
        paged_decode_attention_latent_pallas, use_pallas_paged)

    if use_pallas_paged(q.shape[-1], pool.shape[1]):
        return paged_decode_attention_latent_pallas(
            q, pool, block_tables, context_lens, float(scale), v_dim,
            name=name)
    b, p = block_tables.shape
    rows = pool[block_tables].reshape(b, p * pool.shape[1], -1)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] \
        < context_lens[:, None, None]
    prob = _softmax_with_sink(s[:, :, None], live[:, :, None], None)[:, :, 0]
    # a page's unwritten slots may hold anything: 0 x NaN is NaN
    vals = jnp.where(live[:, 0, :, None], rows[..., :v_dim], 0.0)
    return jnp.einsum("bhs,bsc->bhc", prob, vals).astype(q.dtype)


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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pages_in_a_row(q, k_pool, v_pool, tables_row, q_start, upto, *, scale,
                    interpret):
    """The chunk kernel over one request's pages of 4-D pools, gathered in a
    row. A ``jax.jit`` of its own: a program's layers are the same shapes,
    so the gather and the kernel are traced and lowered once a program and
    not once a layer (``ops/pallas``'s ``_decode_call`` says what that
    cost). ``interpret`` only keys the trace: the kernel's wrapper reads the
    environment itself."""
    from ...ops.pallas.paged_attention import chunk_attention_pallas

    def row(pool):
        # [P, block, Hkv, D] as tokens [P * block, Hkv, D]: no transpose
        return pool[tables_row].reshape((-1,) + pool.shape[2:])

    return chunk_attention_pallas(q[0], row(k_pool), row(v_pool), q_start, 0,
                                  upto, scale, name="chunk_attention")[None]


def chunk_reads_in_a_row(spec, quantized):
    """Whether a prefill chunk reads this layer's keys laid out in a row
    (the chunk kernel on the TPU, a gather off it) and not page by page:
    every layer but a Llama-form one (``prefill == "paged"``, 4-D pools)
    whose pools hold int8 codes. ``LLMEngine`` counts its chunks by it
    (``metrics()["prefill_chunks_in_a_row"]``)."""
    return spec.prefill != "paged" or not quantized


def paged_chunk_attention(q, k_pool, v_pool, tables_row, q_start, upto, scale,
                          k_scale=None, v_scale=None):
    """ONE request's prefill chunk over its pages of ``[N, block, Hkv, D]``
    pools (ISSUE 36): q ``[1, T, H, D]`` at positions ``q_start + t``,
    ``tables_row [P]`` the request's pages, ``upto`` its visible tokens.
    Returns ``[1, T, H, D]``; rows past ``upto`` are undefined.

    Over unquantized pools on the TPU the request's pages are gathered in a
    row and the chunk kernel (``chunk_attention_pallas``: bf16 products, a
    key tile of 256; ``chunk_attention`` in a trace, as ``_kernel_name``
    has it for this form) reads them, per shard under a plan, each shard
    gathering its own kv heads. Int8 pools keep the page-by-page
    multi-query kernel (the chunk kernel takes no scales), and off the TPU
    the gather fallback stays what the bit-exact suites compare with."""
    from ...ops.pallas.paged_attention import _interpret, use_pallas_paged

    if k_scale is not None or not use_pallas_paged(q.shape[-1],
                                                   k_pool.shape[1]):
        return paged_multiquery_attention(
            q, k_pool, v_pool, tables_row[None], upto[None], q_start[None],
            scale=scale, k_scale=k_scale, v_scale=v_scale)

    def kernel(q, k_pool, v_pool, tables_row, q_start, upto):
        return _pages_in_a_row(
            q, k_pool, v_pool, tables_row, q_start, upto,
            scale=float(scale), interpret=_interpret())

    return _per_shard_paged(kernel, q, k_pool, False, 3)(
        q, k_pool, v_pool, tables_row, q_start, upto)


def chunk_attention(q, k, v, q_start, k_start, upto, scale, *, window=None,
                    sink=None, block_size=8, name="chunk_attention"):
    """One request's prefill chunk over keys laid out in a row: q
    [T, H, Dk] at positions ``q_start + t``, k/v [L, Hkv, D] at ``k_start +
    l``; causal, banded by ``window`` if given, ``sink`` [H] in the
    denominator; positions ``>= upto`` (and negative ones) are no tokens.
    Pallas on the TPU (``chunk_attention_pallas``), ``jax.numpy`` here."""
    from ...ops.pallas.paged_attention import (chunk_attention_pallas,
                                               use_pallas_paged)

    if use_pallas_paged(q.shape[-1], block_size):
        return chunk_attention_pallas(q, k, v, q_start, k_start, upto,
                                      float(scale), window=window,
                                      sink=sink, name=name)
    qp = q_start + jnp.arange(q.shape[0], dtype=jnp.int32)[:, None]
    kp = k_start + jnp.arange(k.shape[0], dtype=jnp.int32)[None, :]
    ok = (kp <= qp) & (kp >= 0) & (kp < upto)
    if window is not None:
        ok = ok & (kp > qp - window)
    return _masked_attention(q[None], k[None], v[None], ok[None, None],
                             float(scale), sink)[0]


# ---------------------------------------------------------------------------
# the attention-state handles a model's layer step is given (ISSUE 27)
# ---------------------------------------------------------------------------
#
# ``LLMEngine`` builds its prefill-chunk and decode graphs around ONE call a
# layer, ``layer.serve_step(x, state)``. The layer computes its own
# projections, asks the state to rotate them (``state.rope``: the state
# knows the positions), and hands q, k and v to ``state.attend``, which
# writes k and v where this request's tokens live and returns the attention
# of q over the request's state. What a "state" is (pages of one pool, a
# ring of pages, int8 codes) is the engine's and the cache's business, not
# the model's. ``state.count`` adds to the step's device-side counters.
#
# A layer with compressed keys and values (ISSUE 31) hands over one row a
# token and the matrix that expands it, ``state.attend_latent``: the state
# writes the row and attends in its phase's way (a decode step absorbed, a
# chunk expanded), which the model does not choose either.
#
# A state-space layer (ISSUE 33) does not attend: it hands over what enters
# its causal convolution and its step sizes, ``state.scan``, and gets back
# the convolved channels and the recurrence's output. The state holds the
# convolution's tail and the recurrent state in the request's SLOT (a decode
# step: every row its own slot, a dead row the null slot, updated in place
# by the kernel; a chunk: from zeros if it is the request's first, from what
# the chunk before left otherwise, its padding changing nothing). A
# gated-delta layer (ISSUE 37) is the same kind with another recurrence,
# ``state.delta``: it hands over what enters its convolution (q, k and v),
# its decays and its ``beta``, and gets back ``S_t^T q_t``; the slot, its
# tail, the first chunk's zeros and the null slot are ``scan``'s, as code. A
# layer that keeps nothing (kind ``"none"``) is handed a state too, for
# ``state.count``; it calls nothing else of it.


def _pad_last(x, width):
    extra = width - x.shape[-1]
    if extra == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def _conv_silu(shifted, conv_w, conv_b=None):
    """The causal depthwise convolution and its ``silu``. ``shifted``: the
    ``K`` arrays ``[..., D]`` whose row ``t`` is the row ``K - 1 - j`` before
    token ``t``'s own (oldest first, the last the tokens themselves);
    ``conv_w [D, K]``, ``conv_b [D]`` or None. Returns ``silu(sum_j w[:, j] *
    shifted[j] + b)`` in the rows' dtype."""
    import jax

    f32 = jnp.float32
    out = None if conv_b is None else conv_b.astype(f32)
    for j, rows in enumerate(shifted):
        term = rows.astype(f32) * conv_w[:, j].astype(f32)
        out = term if out is None else out + term
    return jax.nn.silu(out).astype(shifted[-1].dtype)


def _conv_and_split(spec, shifted, conv_w, conv_b):
    """``_conv_silu`` cut into what the selective scan reads: ``(x [..., H,
    P], B [..., G, N], C [..., G, N])``."""
    out = _conv_silu(shifted, conv_w, conv_b)
    heads, p, n = spec.num_kv_heads, spec.v_dim, spec.state_dim
    groups = (spec.k_dim - heads * p) // (2 * n)
    lead = out.shape[:-1]
    x = out[..., :heads * p].reshape(*lead, heads, p)
    b = out[..., heads * p:heads * p + groups * n].reshape(*lead, groups, n)
    c = out[..., heads * p + groups * n:].reshape(*lead, groups, n)
    return x, b, c


def _conv_and_split_qkv(spec, shifted, conv_w):
    """``_conv_silu`` (no bias) cut into what the gated delta rule reads:
    ``(q [..., Hk, N], k [..., Hk, N], v [..., Hv, P])``, ``Hk`` key heads by
    what the ``Hv`` value heads leave of ``k_dim``."""
    out = _conv_silu(shifted, conv_w)
    heads, p, n = spec.num_kv_heads, spec.v_dim, spec.state_dim
    key_heads = (spec.k_dim - heads * p) // (2 * n)
    if spec.heads_a_lane_row != 1 or key_heads < 1 or heads % key_heads:
        raise ValueError(
            "a delta layer's state kind holds a value head a lane row, its "
            "value heads a multiple of its key heads; got "
            f"{heads} value heads of {p}, {key_heads} key heads of {n}")
    lead = out.shape[:-1]
    q = out[..., :key_heads * n].reshape(*lead, key_heads, n)
    k = out[..., key_heads * n:2 * key_heads * n].reshape(*lead, key_heads, n)
    return q, k, out[..., 2 * key_heads * n:].reshape(*lead, heads, p)


class _AttnState:
    def __init__(self, spec, block_size, k_pool, v_pool, k_scale, v_scale,
                 counters, kept=None):
        self.spec, self.block_size = spec, block_size
        self.k_pool, self.v_pool = k_pool, v_pool
        self.k_scale, self.v_scale = k_scale, v_scale
        self._counters, self._kept = counters, kept

    @property
    def quantized(self):
        return self.k_scale is not None

    def count(self, name, value):
        """Add ``value`` to the step's counter ``name`` (a device scalar the
        engine carries through the graph and fetches in ``metrics()``)."""
        if self._counters is not None:
            self._counters[name] = self._counters.get(name, 0) + value

    def keep(self, name, rows):
        """Hand the host ``rows`` (``[tokens, ...]``: a row a token of the
        step, in its order) under ``name``, beside the logits: what the
        layers keep comes out of the graph stacked, a layer after another,
        and an engine that captures (``LLMEngine.capture_logits``) puts each
        request's rows on it (``Request.kept``). Fetched by nothing
        otherwise."""
        if self._kept is not None:
            self._kept.setdefault(name, []).append(rows)

    def _kernel_name(self, base):
        return base if self.spec.prefill == "paged" \
            else f"{base}_{self.spec.kind}"

    def _latent_row(self, row):
        """A token's latent row at the pool's width and dtype."""
        return _pad_last(row, self.spec.k_store).astype(self.k_pool.dtype)

    def _require(self, *kinds):
        if self.spec.kind not in kinds:
            raise ValueError(
                f"a {self.spec.kind!r} layer has no such state: this entry "
                f"is for {' / '.join(kinds)} layers")


class DecodeAttnState(_AttnState):
    """One layer's state inside the decode graph: every row of the batch
    writes ONE token at ``positions`` and attends over ``positions + 1``
    tokens. ``table`` is the kind's block table ([B, P] pages, or [B, R]
    ring slots for a window kind)."""

    def __init__(self, spec, block_size, positions, table, k_pool, v_pool,
                 k_scale=None, v_scale=None, counters=None, slots=None,
                 kept=None):
        super().__init__(spec, block_size, k_pool, v_pool, k_scale, v_scale,
                         counters, kept)
        self.positions, self.table = positions, table
        #: a state kind's slot a row ``[B]``: the null slot for a dead row
        self.slots = slots

    def rope(self, x, cos_t, sin_t):
        """Rotate ``x [B, 1, H, D]`` at each row's own position; ``cos_t``/
        ``sin_t`` are the full ``[max_pos, D/2]`` tables."""
        from ...models.llama import rope_rotate

        c = cos_t[self.positions][:, None, None, :]
        sn = sin_t[self.positions][:, None, None, :]
        return rope_rotate(x, c, sn)

    def _shifted(self, rows):
        """What a state layer's convolution reads at this step: each row's
        slot gives the ``K - 1`` rows before this one (``rows [B, 1, D]``)
        and takes the newest ``K - 1`` back. Every row of the batch does so:
        a dead row's slot is the null slot. Returns the ``K`` arrays ``[B,
        D]``, oldest first."""
        spec, slots = self.spec, self.slots
        tail = self.k_pool[slots]                     # [B, (K - 1) * D]
        window = jnp.concatenate([tail, rows[:, 0].astype(tail.dtype)], -1)
        self.k_pool = self.k_pool.at[slots].set(window[:, spec.k_dim:])
        d = spec.k_dim
        return [window[:, j * d:(j + 1) * d]
                for j in range(spec.conv_rows + 1)]

    def _live_rows(self):
        """Rows of the batch whose slot is not the null slot."""
        return jnp.sum(self.slots != self.v_pool.shape[0] - 1)

    def scan(self, xbc, dt, a, conv_w, conv_b):
        """A state-space layer's step (ISSUE 33), one token a row. ``xbc
        [B, 1, D]``: what enters the convolution; ``dt [B, 1, H]`` float32,
        the step after its softplus; ``a [H]`` (negative); the convolution's
        ``conv_w [D, K]`` and ``conv_b [D]``. The convolution reads and
        renews the slot's tail (``_shifted``);
        the recurrent state is read and written where it lies by
        ``mamba2_decode_update``. Returns ``(x [B, 1, H, P], y [B, 1, H,
        P] float32)``: the convolved channels and ``h_t C_t``."""
        from ...ops.pallas.mamba2 import mamba2_decode_update

        self._require("state")
        x, b, c = _conv_and_split(self.spec, self._shifted(xbc), conv_w,
                                  conv_b)
        y, self.v_pool = mamba2_decode_update(self.v_pool, self.slots, x,
                                              dt[:, 0], a, b, c)
        self.count("ssm_state_rows_updated", self._live_rows())
        return x[:, None], y[:, None]

    def delta(self, qkv, g, beta, conv_w):
        """A gated-delta layer's step (ISSUE 37), one token a row: the other
        recurrence of the state kind. ``qkv [B, 1, D]``: what enters the
        convolution (no bias), ``[q | k | v]``; ``g [B, 1, H]`` float32, the
        log of a value head's decay; ``beta [B, 1, H]``; ``conv_w [D, K]``.
        The tail as ``scan``'s; the state ``S [H, N, P]`` is read (``S^T
        k``), corrected and written where it lies by
        ``gated_delta_decode_update``, which norms q and k. Returns ``o [B,
        1, H, P]`` float32: ``S_t^T q_t`` from the new state."""
        from ...ops.pallas.gated_delta import gated_delta_decode_update

        self._require("state")
        q, k, v = _conv_and_split_qkv(self.spec, self._shifted(qkv), conv_w)
        o, self.v_pool = gated_delta_decode_update(
            self.v_pool, self.slots, q, k, v, g[:, 0], beta[:, 0])
        self.count("delta_state_rows_updated", self._live_rows())
        return o[:, None]

    def attend(self, q, k, v, scale, sink=None):
        import jax

        from .kv_cache import quantize_kv_rows

        self._require("global", "window")
        spec, bs = self.spec, self.block_size
        positions, tables = self.positions, self.table
        kp, vp, ksc, vsc = self.k_pool, self.v_pool, self.k_scale, self.v_scale
        bsz = q.shape[0]
        qa = _pad_last(q, spec.k_store)
        ka, va = _pad_last(k, spec.k_store), v
        page = positions // bs
        if spec.kind == "window":
            page = page % tables.shape[1]
        blk = tables[jnp.arange(bsz), page]
        off = positions % bs
        if self.quantized:
            qk, sk = quantize_kv_rows(ka)   # [B,1,Hkv,D]
            qv, sv = quantize_kv_rows(va)
        if spec.prefill != "paged":
            # one scatter a pool, of a token's Hkv rows a request; the
            # rows of empty slots all land on the null block's first
            hkv = spec.num_kv_heads
            rows = off[:, None] * hkv + jnp.arange(hkv)[None, :]
            kp = kp.at[blk[:, None], rows].set(ka[:, 0].astype(kp.dtype))
            vp = vp.at[blk[:, None], rows].set(va[:, 0].astype(vp.dtype))
        else:
            for i in range(bsz):
                if self.quantized:
                    kp = jax.lax.dynamic_update_slice(
                        kp, qk[i:i + 1], (blk[i], off[i], 0, 0))
                    vp = jax.lax.dynamic_update_slice(
                        vp, qv[i:i + 1], (blk[i], off[i], 0, 0))
                    ksc = jax.lax.dynamic_update_slice(
                        ksc, sk[i:i + 1], (blk[i], off[i], 0))
                    vsc = jax.lax.dynamic_update_slice(
                        vsc, sv[i:i + 1], (blk[i], off[i], 0))
                else:
                    kp = jax.lax.dynamic_update_slice(
                        kp, ka[i:i + 1].astype(kp.dtype),
                        (blk[i], off[i], 0, 0))
                    vp = jax.lax.dynamic_update_slice(
                        vp, va[i:i + 1].astype(vp.dtype),
                        (blk[i], off[i], 0, 0))
        self.k_pool, self.v_pool, self.k_scale, self.v_scale = kp, vp, ksc, vsc
        return paged_decode_attention(
            qa, kp, vp, tables, positions + 1, scale=scale,
            k_scale=ksc, v_scale=vsc, window=spec.window,
            ring=spec.kind == "window", sink=sink,
            num_kv_heads=spec.num_kv_heads,
            name=self._kernel_name("paged_decode_attention"))


    def attend_latent(self, q_nope, q_rope, row, w_kvb, scale):
        """A latent layer's step (ISSUE 31). ``q_nope [B, 1, H, dn]`` and
        ``q_rope [B, 1, H, dr]`` (rotated), ``row [B, 1, k_dim]``: the
        token's ``[c | k_r]`` (normed, rotated), which is all the layer
        caches; ``w_kvb [v_dim, H * (dn + dv)]`` expands a row's ``c`` to
        every head's ``[k_nope | v]``. Writes the row, then attends
        ABSORBED: the queries go through ``W_UK`` into the rows' own space,
        the kernel takes scores and values from the rows as they lie, and
        the result comes back through ``W_UV``. Returns ``[B, 1, H, dv]``."""
        spec, bs = self.spec, self.block_size
        positions, tables = self.positions, self.table
        h, dn = q_nope.shape[2], q_nope.shape[3]
        w = w_kvb.reshape(spec.v_dim, h, -1)
        blk = tables[jnp.arange(tables.shape[0]), positions // bs]
        # the rows of empty slots all land on the null block's first
        self.k_pool = self.k_pool.at[blk, positions % bs].set(
            self._latent_row(row[:, 0]))
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w[:, :, :dn])
        qa = _pad_last(jnp.concatenate(
            [q_lat, q_rope[:, 0].astype(q_lat.dtype)], -1), spec.k_store)
        out = paged_decode_attention_latent(
            qa, self.k_pool, tables, positions + 1, scale, spec.v_dim,
            name=self._kernel_name("paged_decode_attention"))
        # a slot with no request reads the null page: not a live row
        self.count("mla_latent_tokens_read", jnp.sum(
            jnp.where(tables[:, 0] != 0, positions + 1, 0)))
        return jnp.einsum("bhc,chd->bhd", out, w[:, :, dn:])[:, None]


class ChunkAttnState(_AttnState):
    """One layer's state inside the prefill-chunk graph: ONE request's
    block-aligned chunk of ``C`` tokens at ``start``, of which those before
    ``upto`` are real. A global kind writes the chunk's pages into the
    blocks ``tables_row`` names and attends over the request's pages. A
    window kind attends over the chunk's own keys and the ``n_tail`` pages
    before it (``window_row[:n_tail]``, read BEFORE anything is written),
    and keeps only the chunk's newest pages: ``window_row[n_tail:-1]`` are
    the blocks of chunk pages ``first ..`` (0, the null block, for a page
    past the last real one), ``window_row[-1]`` is ``first``."""

    def __init__(self, spec, block_size, start, upto, tables_row, k_pool,
                 v_pool, k_scale=None, v_scale=None, window_row=None,
                 n_tail=0, counters=None, slot=None, kept=None):
        super().__init__(spec, block_size, k_pool, v_pool, k_scale, v_scale,
                         counters, kept)
        self.start, self.upto, self.tables_row = start, upto, tables_row
        self.window_row, self.n_tail = window_row, n_tail
        #: a state kind: the request's slot, int32 ``[1]``
        self.slot = slot

    def _shifted(self, rows, slot):
        """What a state layer's convolution reads over this chunk (``rows
        [1, C, D]``), and the tail at ``slot`` renewed. The request's FIRST chunk
        (``start == 0``) starts from zeros whatever its slot holds: the slot
        may be recycled, and a row dispatched ahead for the request that
        left it may have written there since (programs run in order, and
        this one does not read it). A later chunk starts from what the chunk
        before wrote. The tail that goes back is the last ``K - 1`` REAL
        rows. Returns ``(the K arrays [C, D], oldest first; whether the
        chunk is the first; its real tokens)``."""
        import jax

        spec = self.spec
        fresh = self.start == 0
        real = self.upto - self.start                 # tokens of the chunk
        tail = jnp.where(fresh, 0, self.k_pool[slot]).reshape(-1, spec.k_dim)
        rows = jnp.concatenate([tail.astype(rows.dtype), rows[0]])
        keep = tail.shape[0]
        self.k_pool = self.k_pool.at[slot].set(jax.lax.dynamic_slice_in_dim(
            rows, real, keep).reshape(-1).astype(self.k_pool.dtype))
        c_len = rows.shape[0] - keep
        return [rows[j:j + c_len] for j in range(keep + 1)], fresh, real

    def scan(self, xbc, dt, a, conv_w, conv_b):
        """A state-space layer's chunk (``DecodeAttnState.scan`` has the
        operands, ``[1, C, ...]`` here). The tail and the first chunk's
        zeros are ``_shifted``'s. Positions at or past ``upto`` change
        nothing: their step is 0. The recurrence runs chunked,
        ``spec.scan_block`` tokens a block (``ssd_chunk_scan``)."""
        from ...ops.pallas.mamba2 import from_stored, ssd_chunk_scan, to_stored

        self._require("state")
        spec, slot = self.spec, self.slot[0]
        pack = spec.heads_a_lane_row
        shifted, fresh, real = self._shifted(xbc, slot)
        c_len = xbc.shape[1]
        x, b, c = _conv_and_split(spec, shifted, conv_w, conv_b)
        dt = jnp.where(jnp.arange(c_len)[:, None] < real, dt[0], 0.0)
        h0 = from_stored(jnp.where(fresh, 0.0, self.v_pool[slot]), pack)
        y, h = ssd_chunk_scan(x, dt, a, b, c, h0, spec.scan_block)
        self.v_pool = self.v_pool.at[slot].set(
            to_stored(h, pack).astype(self.v_pool.dtype))
        self.count("ssm_tokens_scanned", real)
        return x[None], y[None]

    def delta(self, qkv, g, beta, conv_w):
        """A gated-delta layer's chunk (``DecodeAttnState.delta`` has the
        operands, ``[1, C, ...]`` here). The tail and the first chunk's
        zeros are ``_shifted``'s. Positions at or past ``upto`` change
        nothing: their decay is 1 and their ``beta`` 0. The recurrence runs
        in its chunked form (``gated_delta_chunk``)."""
        from ...ops.pallas.gated_delta import gated_delta_chunk

        self._require("state")
        spec, slot = self.spec, self.slot[0]
        shifted, fresh, real = self._shifted(qkv, slot)
        q, k, v = _conv_and_split_qkv(spec, shifted, conv_w)
        live = jnp.arange(qkv.shape[1])[:, None] < real
        o, s = gated_delta_chunk(
            q, k, v, jnp.where(live, g[0], 0.0), jnp.where(live, beta[0], 0.0),
            jnp.where(fresh, 0.0, self.v_pool[slot]))
        self.v_pool = self.v_pool.at[slot].set(s.astype(self.v_pool.dtype))
        self.count("delta_tokens_scanned", real)
        return o[None]

    def rope(self, x, cos_t, sin_t):
        """Rotate ``x [1, C, H, D]``, whose rows sit at ``start + i``."""
        from ...models.llama import _rope_apply_at

        return _rope_apply_at.raw_fn(x, cos_t, sin_t, self.start)

    def attend(self, q, k, v, scale, sink=None):
        import jax

        from .kv_cache import quantize_kv_rows

        self._require("global", "window")
        spec, bs = self.spec, self.block_size
        kp, vp, ksc, vsc = self.k_pool, self.v_pool, self.k_scale, self.v_scale
        start, upto = self.start, self.upto
        qa = _pad_last(q, spec.k_store)
        ka, va = _pad_last(k, spec.k_store), v
        pages = q.shape[1] // bs

        def paged(a):
            return a.reshape((pages, bs) + a.shape[2:])

        def in_a_row(a):
            """Pages ``[n, block * Hkv, D]`` as tokens ``[n * block, Hkv, D]``."""
            return a.reshape(-1, spec.num_kv_heads, a.shape[-1])

        def as_pages(a):
            """The chunk's rows ``[1, C, Hkv, D]`` as pages of the pool's
            own form (``KVLayerSpec.pool_shape``)."""
            return a.reshape(spec.pool_shape(pages, bs, a.shape[-1]))

        if spec.kind == "window":
            n_tail = self.n_tail
            row = self.window_row
            n_w = row.shape[0] - n_tail - 1
            tail_k = in_a_row(kp[row[:n_tail]])
            tail_v = in_a_row(vp[row[:n_tail]])
            out = chunk_attention(
                qa[0], jnp.concatenate([tail_k, ka[0].astype(kp.dtype)]),
                jnp.concatenate([tail_v, va[0].astype(vp.dtype)]),
                start, start - n_tail * bs, upto, scale,
                window=spec.window, sink=sink, block_size=bs,
                name=self._kernel_name("chunk_attention"))[None]
            first = row[-1]
            blks = row[n_tail:-1]
            kp = kp.at[blks].set(jax.lax.dynamic_slice_in_dim(
                as_pages(ka), first, n_w).astype(kp.dtype))
            vp = vp.at[blks].set(jax.lax.dynamic_slice_in_dim(
                as_pages(va), first, n_w).astype(vp.dtype))
            self.k_pool, self.v_pool = kp, vp
            return out

        blks = jax.lax.dynamic_slice(self.tables_row, (start // bs,), (pages,))
        # one scatter per pool: the chunk's pages land
        # in its blocks at once (a page-by-page
        # dynamic_update_slice loop made the 128-page top
        # bucket a minutes-long compile). Bucket pages
        # past the request's blocks all hit null block 0.
        if self.quantized:
            qk, sk = quantize_kv_rows(ka)
            qv, sv = quantize_kv_rows(va)
            kp = kp.at[blks].set(paged(qk))
            vp = vp.at[blks].set(paged(qv))
            ksc = ksc.at[blks].set(paged(sk))
            vsc = vsc.at[blks].set(paged(sv))
        else:
            kp = kp.at[blks].set(as_pages(ka).astype(kp.dtype))
            vp = vp.at[blks].set(as_pages(va).astype(vp.dtype))
        self.k_pool, self.v_pool, self.k_scale, self.v_scale = kp, vp, ksc, vsc
        if spec.prefill == "paged":
            return paged_chunk_attention(
                qa, kp, vp, self.tables_row, start, upto, scale, ksc, vsc)
        # one request's pages in a row: a few tens of MB at the longest
        # context, against the chunk's own matmuls
        return chunk_attention(
            qa[0], in_a_row(kp[self.tables_row]),
            in_a_row(vp[self.tables_row]), start, 0, upto, scale, sink=sink,
            block_size=bs, name=self._kernel_name("chunk_attention"))[None]

    def attend_latent(self, q_nope, q_rope, row, w_kvb, scale):
        """A latent layer's chunk (``DecodeAttnState.attend_latent`` has the
        operands, ``[1, C, ...]`` here). Writes the chunk's rows into its
        pages, then attends EXPANDED: the request's cached rows, in a row,
        go through ``w_kvb`` to every head's ``[k_nope | k_r]`` and ``v``,
        and the chunk kernel runs over them as over any heads. Rows are
        expanded a block of ``C`` at a time up to the chunk's end, so the
        work follows the context and not the table's length."""
        import jax

        spec, bs = self.spec, self.block_size
        start, upto = self.start, self.upto
        c_len, h, dn = q_nope.shape[1], q_nope.shape[2], q_nope.shape[3]
        r, dr = spec.v_dim, q_rope.shape[-1]
        dv = w_kvb.shape[1] // h - dn
        pages = c_len // bs
        blks = jax.lax.dynamic_slice(self.tables_row, (start // bs,), (pages,))
        self.k_pool = kp = self.k_pool.at[blks].set(
            self._latent_row(row[0]).reshape(pages, bs, -1))
        n_rows = self.tables_row.shape[0] * bs
        step = min(c_len, n_rows)
        width = -(-(dn + dr) // 128) * 128 if dn + dr >= 128 else dn + dr
        n_blocks = (upto + step - 1) // step

        def expand(i, kv):
            k, v = kv
            at = jnp.minimum(i * step, n_rows - step)     # the last may lap
            rows = kp[jax.lax.dynamic_slice(
                self.tables_row, (at // bs,), (step // bs,))].reshape(step, -1)
            e = jnp.dot(rows[:, :r], w_kvb).reshape(step, h, dn + dv)
            k_r = jnp.broadcast_to(rows[:, None, r:r + dr], (step, h, dr))
            k_blk = _pad_last(jnp.concatenate([e[..., :dn], k_r], -1), width)
            return (jax.lax.dynamic_update_slice(k, k_blk, (at, 0, 0)),
                    jax.lax.dynamic_update_slice(v, e[..., dn:], (at, 0, 0)))

        k, v = jax.lax.fori_loop(0, n_blocks, expand, (
            jnp.zeros((n_rows, h, width), kp.dtype),
            jnp.zeros((n_rows, h, dv), kp.dtype)))
        self.count("mla_context_tokens_expanded", n_blocks * step)
        q = _pad_last(jnp.concatenate([q_nope[0], q_rope[0]], -1), width)
        return chunk_attention(
            q.astype(kp.dtype), k, v, start, 0, upto, scale, block_size=bs,
            name="chunk_attention_global")[None]
