"""Paged-attention kernels — Pallas TPU (ISSUE 7 tentpole, part b; the decode
kernel rewritten by ISSUE 26, its page copies' issue path by ISSUE 32).

Attention over a block-paged KV cache (PAPERS.md: "Ragged Paged Attention:
A High-Performance and Flexible LLM Inference Kernel for TPU"). The block
table and the context lengths are scalar-prefetched, so the gather never
materializes a request's KV in HBM, and ONE compiled kernel serves any mix
of request lengths — the whole point of the paged layout.

Layouts:
  q            [B, H, D]         (one decode token per request)
  k/v pool     [N, block, Hkv, D]
  block_tables [B * P] int32     (flattened; P = max pages per request)
  context_lens [B]     int32     (tokens INCLUDING the one just written)

**Decode: a chunk of pages a step, copied by hand, folded on the MXU.** The
pools stay in HBM (``pl.ANY``). One grid step is one request; inside, a
``fori_loop`` runs over the request's live chunks of ``C`` pages and never
visits a dead table slot. For each chunk the kernel starts one async copy a
live page for K and for V into one slot of a double buffer in VMEM, and it
starts the next chunk's copies — the first chunk of the NEXT request, at a
request's last chunk — before it waits for and folds this one, so the copy
pipeline does not drain between requests. ``C`` follows from the operands'
shapes (``_decode_chunk``): 16 pages, 256 tokens, at 8 kv heads x 128 bf16.

**What a page's copy costs to issue (ISSUE 31, 32).** Pages are small (8 to
32 KB) and the core that folds a chunk also issues the next one's copies,
so beside the bytes there is a cost a PAGE: a start, a wait, and whatever
loop they sit in. Every page kind takes the same path, because the kinds
differ in counts (pools, pages a chunk) and not in what they want. A full
chunk, which all but a request's last are, has its ``C`` starts a pool
written out; a last chunk's run in a loop a page. Waits are by bytes: the
chip's DMA semaphore counts what was transferred and a wait takes off the
size of ITS descriptor, whatever copies signalled (asked of the chip by
``scripts/dma_wait_probe.py``, PR 32: right data, no hang, no early return,
as jax's two interpreters have it), so a chunk is waited for by the binary
digits of its live pages: one descriptor of ``C`` pages a pool when it is
full, at most ``log2 C`` smaller ones when not. The two slots have
semaphores of their own, so the prefetch into the other slot is never
counted in. A wait for more bytes than were started never returns:
``tests/test_paged_decode_dma_books.py`` runs every kind in the TPU
interpreter with byte-counted semaphores under a time limit.

A page arrives as ``block * Hkv`` rows of D, token-major, exactly as the
pool holds it (the wrapper's ``[N, block * Hkv, D]`` view is a bitcast), so
a chunk is ``[C * block * Hkv, D]`` and the fold is two plain 2-D dots for
all heads at once: scores ``q [H, D] . chunk^T -> [H, C * block * Hkv]``,
the columns of another kv head than the row's masked with the positions
past the context, then ``p . V -> [H, D]``. That spends Hkv times the
flops a per-head dot would, on an MXU that is otherwise idle, and needs no
strided load; max, exp and sum run on lane-dense rows. Scale is applied to
the f32 scores; max, sum and accumulator are f32; ``p`` enters the second
dot in V's dtype. A dead column has ``p = 0``, and ``0 x NaN`` is NaN on
the MXU, so a tail chunk's V rows past the context (never copied, or a
page's unwritten slots) are zeroed before the dot. GQA, one kv head a
shard, one query head a kv head are all shapes of this one kernel.

**Latent pages (ISSUE 31).** A layer with compressed keys and values caches
ONE row a token, ``[c | k_r]``, shared by every query head, whose first
``v_dim`` values are the values too: pool ``[N, block, D]``, no V pool. Its
decode (``paged_decode_attention_latent_pallas``) is the kernel above with
``latent=v_dim``: one copy a page, ``groups = H`` (one "kv head", nothing
masked away), scores of the absorbed queries ``[q_lat | q_rope]`` over the
whole row and the second dot over the first ``v_dim`` lanes of the SAME
buffer, so a row is read from HBM once. 64 pages a chunk at 640-wide bf16
rows.

**Quantized pools (ISSUE 14, dequant-in-kernel):** with
``kv_dtype="int8"`` the pools hold int8 codes and two sidecar scale
pools ``[N, block, Hkv]`` f32 ride along. The kernels take two extra
scalar-prefetch-indexed operands — the scale rows of exactly the block
being DMA'd — and dequantize IN VMEM (``codes.astype(f32) *
scale[..., None]``) right before the online-softmax fold, so HBM traffic
per page drops ~4x while the attention math past the dequant is that of
the fp kernel fed the dequantized values. The lax path in
``inference/serving/paged_attention.py`` (CPU backends) mirrors the same
gather + multiply. The scale pools' lane dim is Hkv: HBM tiles pad it to
128 lanes, and Mosaic refuses a hand-made copy of a ``[block, Hkv]`` slab
of them (a slice must be aligned to the 128-lane tile). So **int8 decode
keeps the per-page kernel** (``_int8_page_kernel``: grid ``(B, P)``, one
pool block a step through the ``BlockSpec`` pipeline, a VPU fold), chosen
by the scale operands being there.

The multi-query kernel (speculative verify, and a prefill chunk over int8
codes; since ISSUE 36 a chunk over unquantized pools reads the request's
pages in a row through ``chunk_attention_pallas`` whatever the pool's
form) has real ``M = T`` rows, one pool block a grid step, and batched MXU
dots; its query rows are tiled over a grid axis so VMEM holds one tile
(``_MQ_ROWS``), not the whole chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _interpret

__all__ = ["paged_decode_attention_pallas",
           "paged_decode_attention_latent_pallas",
           "paged_multiquery_attention_pallas", "chunk_attention_pallas",
           "use_pallas_paged"]


def use_pallas_paged(head_dim, block_size):
    """The real-TPU gate: MXU-friendly head_dim and a lane-aligned block.
    Interpret mode (PT_PALLAS_INTERPRET=1) runs anywhere for parity tests."""
    if _interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    if head_dim % 128 or block_size % 8:
        # PR 21: no hidden fallback. On the chip a geometry the kernels
        # cannot take is an error; the lax gather is the CPU's path.
        raise ValueError(
            f"the paged Pallas kernels take head_dim % 128 == 0 and "
            f"block_size % 8 == 0; got head_dim={head_dim}, "
            f"block_size={block_size} (pad the stored width, as "
            "KVLayerSpec.k_store does)")
    return True


#: VMEM the decode kernel plans for: both double buffers of a chunk's K and
#: V pages plus the f32 working set of its fold. A quarter of Mosaic's 16 MiB
#: scoped default, so the pipeline's q/out blocks and spills have room.
_DECODE_VMEM_BUDGET = 4 * 1024 * 1024


def _decode_chunk(block_size, hkv, h, d, itemsize, p, dv=None):
    """``(C, bytes)``: the pages of a decode chunk — the largest power of
    two whose VMEM plan fits ``_DECODE_VMEM_BUDGET``, and no more than a
    request's table holds — and that plan's bytes: two slots of C pages for
    K (``d`` wide) and for V (``dv`` wide, ``d`` if not given), and four
    live ``[H, C * block * Hkv]`` f32 arrays of the fold (scores,
    probabilities, the two masks)."""
    dv = d if dv is None else dv

    def plan(c):
        cols = c * block_size * hkv
        return 2 * cols * (d + dv) * itemsize + 4 * h * cols * 4

    c = 1
    while 2 * c <= pl.next_power_of_2(p) \
            and plan(2 * c) <= _DECODE_VMEM_BUDGET:
        c *= 2
    return c, plan(c)


def _kernel(tables_ref, lens_ref, *refs, block_size, chunk, groups, scale,
            window=None, ring=False, sink=False, latent=None):
    """Decode over fp pools: one request a grid step, a loop over its live
    chunks of ``chunk`` pages inside (see the module docstring). K rows are
    ``q``'s width and V rows the output's; the two may differ.

    ``window`` (tokens) puts a lower bound on the keys walked: the loop
    starts at the page that holds position ``ctx - window`` and never
    touches an older one. ``ring`` says the table row is a ring: logical
    page ``p`` sits in slot ``p % P``, so a row of ``ceil(window / block) +
    1`` slots serves any context. ``sink`` adds one operand ``[H, 1]`` f32,
    a per-head logit that joins the softmax's denominator and carries no
    value: the fold simply starts from ``m = sink, l = 1``.

    ``latent`` (a width) says the pool holds ONE row a token that every
    query head shares, whose first ``latent`` values are the values too
    (ISSUE 31): there is no V pool and no V buffer, a page is copied once,
    and the second dot reads the first ``latent`` lanes of the SAME block the
    scores were taken over."""
    if latent:
        q_ref, k_hbm, o_ref, k_buf, sems, slot_ref = refs
        v_hbm = v_buf = None
    elif sink:
        q_ref, sink_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = refs
    # the pools copied a page, and the buffer the values are read from
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))[:1 if latent else 2]
    val_buf = k_buf if latent else v_buf
    b = pl.program_id(0)
    h = q_ref.shape[1]
    d = latent or v_buf.shape[-1]
    hkv = h // groups
    rows = block_size * hkv                 # pool rows a page
    cols = chunk * rows
    p_max = tables_ref.shape[0] // lens_ref.shape[0]

    def first_page(r):
        if window is None:
            return 0
        return jnp.maximum(lens_ref[r] - window, 0) // block_size

    def n_pages(r):
        """Live pages of request r, counted from ``first_page(r)``."""
        top = pl.cdiv(lens_ref[r], block_size)
        if not ring:
            top = jnp.minimum(top, p_max)
        return top - first_page(r)

    def live_pages(r, c):
        """Pages of request r's chunk c that hold a live token."""
        return jnp.clip(n_pages(r) - c * chunk, 0, chunk)

    def start(r, c, slot):
        """Start one copy a live page a pool of request r's chunk c, all on
        the slot's semaphores. A full chunk, which all but a request's last
        are, is written out: a loop a page costs a third more a start (the
        latent kind alone on the chip, PR 31: 41 ns a page against 31)."""
        at0 = first_page(r) + c * chunk

        def page(j, carry=None):
            at = at0 + j
            idx = tables_ref[r * p_max + (at % p_max if ring else at)]
            for i, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    hbm.at[idx], buf.at[slot, pl.ds(j * rows, rows)],
                    sems.at[i, slot]).start()
            return carry

        live = live_pages(r, c)

        @pl.when(live == chunk)
        def _full():
            for j in range(chunk):
                page(j)

        @pl.when(live != chunk)
        def _part():
            jax.lax.fori_loop(0, live, page, 0)

    def wait(r, c, slot):
        """Wait for what ``start(r, c, slot)`` started. A DMA semaphore
        counts bytes and a wait takes off those of ITS descriptor (the chip
        as jax's interpreters: ``scripts/dma_wait_probe.py``, PR 32), so a
        chunk's copies are waited for by the binary digits of their number:
        one descriptor of ``chunk`` pages a pool when it is full, at most
        ``log2(chunk)`` smaller ones when it is a request's last."""
        live = live_pages(r, c)
        for k in range(chunk.bit_length()):
            n = 1 << k

            @pl.when(live & n != 0)
            def _digit():
                for i, (_, buf) in enumerate(pools):
                    part = buf.at[slot, pl.ds(0, n * rows)]
                    pltpu.make_async_copy(part, part, sems.at[i, slot]).wait()

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    ctx = lens_ref[b]
    base = first_page(b)
    # an empty request still takes one (all-masked) chunk, so the next
    # request's first copies are started
    n_chunks = jnp.maximum(pl.cdiv(n_pages(b), chunk), 1)
    slot0 = slot_ref[0]
    cdt = jnp.promote_types(q_ref.dtype, k_buf.dtype)
    q = q_ref[0].astype(cdt)                              # [H, D]
    # column c of a chunk is token c // Hkv of it, kv head c % Hkv; a query
    # head sees the columns of its own kv head (h = kvh * groups + g)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
    own = col % hkv == jax.lax.broadcasted_iota(
        jnp.int32, (h, cols), 0) // groups
    tok = col // hkv

    def fold(c, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + c) % 2
        last = c + 1 == n_chunks
        nr = jnp.where(last, b + 1, b)

        @pl.when(nr < pl.num_programs(0))
        def _prefetch():
            start(nr, jnp.where(last, 0, c + 1), 1 - slot)

        wait(b, c, slot)
        # live tokens from this chunk's first on
        seen = ctx - (base + c * chunk) * block_size

        @pl.when(seen < chunk * block_size)
        def _tail():
            # rows past the context were not copied, or are a page's
            # unwritten slots: 0 x NaN is NaN in the PV dot
            live = jax.lax.broadcasted_iota(
                jnp.int32, (cols, val_buf.shape[-1]), 0) < seen * hkv
            val_buf[slot] = jnp.where(live, val_buf[slot],
                                      jnp.zeros((), val_buf.dtype))

        s = jax.lax.dot_general(
            q, k_buf[slot].astype(cdt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, cols]
        ok = own & (tok < seen)
        if window is not None:
            ok = ok & (tok >= seen - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            pexp.astype(cdt),
            (val_buf[slot, :, :d] if latent else v_buf[slot]).astype(cdt),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    if sink:
        m0, l0 = sink_ref[...], jnp.ones((h, 1), jnp.float32)
    else:
        m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((h, 1), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, fold, (m0, l0, jnp.zeros((h, d), jnp.float32)))
    slot_ref[0] = (slot0 + n_chunks) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _int8_page_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
                      vs_ref, o_ref, acc_ref, m_ref, l_ref, *, block_size,
                      groups, scale):
    """Decode over int8 pools: one pool block a grid step (see the module
    docstring for why it is not the chunked kernel)."""
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
        # dequant-in-kernel: the DMA'd block is int8 codes; its scale rows
        # [block, Hkv] ride in as scalar-prefetch-indexed operands and the
        # multiply happens here in VMEM — HBM never sees a dequantized page
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
        tok = p * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, k.shape[1], 1), 0)
        visible = tok < ctx
        # a page's unwritten slots may hold anything: 0 x NaN is NaN
        v = jnp.where(visible,
                      v_ref[0].astype(jnp.float32) * vs_ref[0][..., None], 0.0)
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


def _int8_page_call(q, k_pool, v_pool, tables_flat, lens, scale, k_scale,
                    v_scale):
    b, h, d = q.shape
    _, block_size, hkv, _ = k_pool.shape
    p = tables_flat.shape[0] // b
    groups = h // hkv
    # head h = kvh * groups + g  ->  [B, G, Hkv, D]: each group's heads line
    # up one-to-one with the pool block's kv heads
    qg = jnp.swapaxes(q.reshape(b, hkv, groups, d), 1, 2)
    q_spec = pl.BlockSpec((1, groups, hkv, d),
                          lambda i, j, T, L: (i, 0, 0, 0))
    page = pl.BlockSpec((1, block_size, hkv, d),
                        lambda i, j, T, L: (T[i * p + j], 0, 0, 0))
    rows = pl.BlockSpec((1, block_size, hkv),
                        lambda i, j, T, L: (T[i * p + j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, p),
        in_specs=[q_spec, page, page, rows, rows],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((groups, hkv, d), jnp.float32),
            pltpu.VMEM((groups, hkv, 1), jnp.float32),
            pltpu.VMEM((groups, hkv, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_int8_page_kernel, block_size=block_size,
                          groups=groups, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, groups, hkv, d), q.dtype),
        interpret=_interpret(),
        name="paged_decode_attention",
    )(tables_flat, lens, qg, k_pool, v_pool, k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32))
    return jnp.swapaxes(out, 1, 2).reshape(b, h, d)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                  context_lens, scale,
                                  k_scale=None, v_scale=None, *,
                                  window=None, ring=False, sink=None,
                                  num_kv_heads=None,
                                  name="paged_decode_attention"):
    """q [B, H, Dk]; pools [N, block, Hkv, Dk] and [N, block, Hkv, Dv], or
    already as rows ``[N, block * Hkv, D]`` with ``num_kv_heads`` given;
    block_tables [B, P] int32; context_lens [B] int32. Returns [B, H, Dv].
    With int8 pools, ``k_scale``/``v_scale`` [N, block, Hkv] f32 arm
    dequant-in-kernel. ``window``/``ring``/``sink`` ([H] f32) as
    ``_kernel`` has them; ``name`` is the call's name in a device trace."""
    b, h, d = q.shape
    if k_pool.ndim == 3:
        n, hkv = k_pool.shape[0], int(num_kv_heads)
        block_size = k_pool.shape[1] // hkv
    else:
        n, block_size, hkv, _ = k_pool.shape
    dv = v_pool.shape[-1]
    tables_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    if k_scale is not None:
        if window is not None or sink is not None or dv != d:
            raise NotImplementedError(
                "the int8 decode kernel takes one K/V width and neither a "
                "window nor a sink")
        return _int8_page_call(q, k_pool, v_pool, tables_flat, lens,
                               float(scale), k_scale, v_scale)
    chunk, _ = _decode_chunk(block_size, hkv, h, d, k_pool.dtype.itemsize,
                             block_tables.shape[1], dv)
    rows = block_size * hkv
    return _decode_call(
        tables_flat, lens, q, sink,
        (k_pool.reshape(n, rows, d), v_pool.reshape(n, rows, dv)),
        block_size=block_size, chunk=chunk, scale=float(scale), window=window,
        ring=ring, latent=None, interpret=_interpret(), name=name)


def paged_decode_attention_latent_pallas(q, pool, block_tables, context_lens,
                                         scale, v_dim, *,
                                         name="paged_decode_attention_latent"):
    """Decode over latent pages (ISSUE 31). q ``[B, H, D]``: every head's
    ``[q_lat | q_rope]`` at the stored width; pool ``[N, block, D]``: one
    row a token, shared by all heads; block_tables ``[B, P]`` and
    context_lens ``[B]`` int32. Scores over all ``D``, values the rows' first
    ``v_dim``. Returns ``[B, H, v_dim]``. ``_kernel``'s copy pipeline with
    one pool: a row is read from HBM once."""
    b, h, d = q.shape
    block_size = pool.shape[1]
    chunk, _ = _decode_chunk(block_size, 1, h, d, pool.dtype.itemsize,
                             block_tables.shape[1], 0)
    return _decode_call(
        block_tables.reshape(-1).astype(jnp.int32),
        context_lens.astype(jnp.int32), q, None, (pool,),
        block_size=block_size, chunk=chunk, scale=float(scale), window=None,
        ring=False, latent=int(v_dim), interpret=_interpret(), name=name)


# a jit of its own, as ``grouped_ffn._call``: a model's layers of one kind
# are the same shapes, so the kernel is traced and lowered once a program
# and not once a layer. A full chunk's written-out copies are text: sixteen
# layers of them took a decode program's tracing and lowering from 1.0 to
# 3.0 s and decode-sat's ``setup_s`` from 51 to 67 s (PR 32). ``chunk`` and
# ``interpret`` are read by the callers: what a trace is kept under
@functools.partial(jax.jit, static_argnames=(
    "block_size", "chunk", "scale", "window", "ring", "latent", "interpret",
    "name"))
def _decode_call(tables_flat, lens, q, sink, pools, *, block_size, chunk,
                 scale, window, ring, latent, interpret,
                 name="paged_decode_attention"):
    """``_kernel`` over ``pools``: K and V as rows ``[N, block * Hkv, D]``,
    or the one pool of a latent layer (``latent`` its value width)."""
    b, h, d = q.shape
    dv = latent or pools[1].shape[-1]
    rows = pools[0].shape[1]
    in_specs = [pl.BlockSpec((1, h, d), lambda i, T, L: (i, 0, 0))]
    operands = [q]
    if sink is not None:
        in_specs.append(pl.BlockSpec((h, 1), lambda i, T, L: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(h, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, h, dv), lambda i, T, L: (i, 0, 0)),
        scratch_shapes=[
            *(pltpu.VMEM((2, chunk * rows, pool.shape[-1]), pool.dtype)
              for pool in pools),
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, chunk=chunk,
                          groups=h // (rows // block_size), scale=scale,
                          window=window, ring=ring, sink=sink is not None,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        # the copy pipeline runs from one request into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(tables_flat, lens, *operands, *pools)


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


# ---------------------------------------------------------------------------
# one request's prefill chunk against keys laid out in a row (ISSUE 27)
# ---------------------------------------------------------------------------

#: rows of one chunk-attention tile: query rows x the heads of one kv group
_CHUNK_TILE_ROWS = 1024
#: lanes a chunk tile's running max and sum are held in, a row's value in
#: every lane: as ``[rows, 1]`` each fold reduced into and broadcast out of a
#: layout of one lane a vreg, which cost a v5e two thirds of a tile's time
_LANES = 128


def _lanes_to(x, width):
    """``x`` ``[rows, _LANES]`` (one value a row) as ``[rows, width]``."""
    if width % _LANES == 0:
        return jnp.tile(x, (1, width // _LANES))
    return x[:, :1]


def _chunk_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, tq, tk, groups, scale,
                  window, sink):
    """One ``[groups * tq]``-row query tile of one kv head against one
    ``tk``-key tile: a flash fold with the causal (and, with ``window``,
    banded) mask worked out from absolute positions. ``pos_ref`` holds
    (position of query row 0, position of key row 0, visible tokens).
    The running max ``m`` and sum ``l`` are held ``[rows, _LANES]``, a
    row's value in every lane: the numbers ``[rows, 1]`` would hold."""
    if sink:
        sink_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    i, j = pl.program_id(1), pl.program_id(2)
    q0, k0, live = _tile(pos_ref, i, j, tq, tk, window)
    upto = pos_ref[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sink:
            m_ref[...] = jnp.broadcast_to(sink_ref[0], m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _fold():
        rows = groups * tq
        q = q_ref[0].reshape(rows, q_ref.shape[-1])
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [rows, tk]
        qp = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % tq
        kp = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (kp <= qp) & (kp >= 0) & (kp < upto)
        if window is not None:
            ok = ok & (kp > qp - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                                  # [rows, lanes]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pexp = jnp.where(ok, jnp.exp(s - _lanes_to(m_new, tk)), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes_to(corr, acc_ref.shape[1]) + \
            jax.lax.dot_general(
                pexp.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _out():
        l = _lanes_to(l_ref[...], acc_ref.shape[1])
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


def _chunk_tiles(t, groups, ln, window):
    """``(tq, tk, n_k)``: query rows a tile (of each of a kv head's
    ``groups`` query heads), keys a tile and key-tile grid steps a query
    tile, for a chunk of ``t`` rows over ``ln`` keys."""
    tq = min(t, max(8, _CHUNK_TILE_ROWS // groups))
    while t % tq:
        tq //= 2
    tk = min(ln, 256 if window is None else tq)
    while ln % tk:
        tk //= 2
    n_k = ln // tk if window is None else min(ln // tk, -(-(window + tq - 1) // tk) + 1)
    return tq, tk, n_k


def _tile(pos, i, j, tq, tk, window):
    """``(q0, k0, live)`` of grid step ``(i, j)``: the positions of the
    query tile's first row and of the key tile's first key, and whether
    some row sees some key of the tile. Elementwise in ``i`` and ``j``, so
    ``chunk_tile_counts`` takes the whole grid at once."""
    q0 = pos[0] + i * tq
    k0 = pos[1] + _key_tile(pos, i, j, tq, tk, window) * tk
    upto = pos[2]
    lo = q0 - window + 1 if window is not None else 0
    live = (k0 <= jnp.minimum(q0 + tq, upto) - 1) & (k0 + tk > lo) \
        & (j < _live_tiles(pos, i, tq, tk, window))
    return q0, k0, live


def chunk_tile_counts(start, tokens, rung, heads, kv_heads, ln, window=None,
                      k_start=0):
    """``(live, dead)`` grid steps of ``chunk_attention_pallas`` for a chunk
    of ``tokens`` real queries at position ``start``, padded to ``rung``
    rows, of ``heads`` query heads over ``kv_heads``, against ``ln`` keys in
    a row from position ``k_start``: the tiles it folds and the steps that
    fold nothing. By the kernel's own tile arithmetic (``_chunk_tiles``,
    ``_tile``), so a count cannot drift from the grid: the dead steps are
    what a grid over live tiles alone would drop, and this is what such a
    grid is sized by and checked against."""
    import numpy as np

    tq, tk, n_k = _chunk_tiles(rung, heads // kv_heads, ln, window)
    i, j = np.arange(rung // tq)[:, None], np.arange(n_k)[None, :]
    live = _tile((start, k_start, start + tokens), i, j, tq, tk, window)[2]
    live = kv_heads * int(np.sum(np.broadcast_to(live, (i.size, n_k))))
    return live, kv_heads * i.size * n_k - live


def _live_tiles(pos, i, tq, tk, window):
    """Key tiles query tile ``i`` folds, counted from its first."""
    last = jnp.minimum(pos[0] + (i + 1) * tq, pos[2]) - 1 - pos[1]
    n = last // tk + 1
    if window is not None:
        n = n - _first_tile(pos, i, tq, tk, window)
    return jnp.maximum(n, 1)


def _first_tile(pos, i, tq, tk, window):
    """The key tile of the first token query tile ``i``'s window reaches:
    a tile of negative positions alone holds no token."""
    lo = jnp.maximum(pos[0] + i * tq - window + 1, 0)
    return jnp.maximum(lo - pos[1], 0) // tk


def _key_tile(pos, i, j, tq, tk, window):
    """The key tile grid step ``(i, j)`` reads: the walk starts at the first
    tile the window reaches, and a step past the last live tile repeats it,
    so the pipeline copies nothing for a dead step."""
    j = jnp.minimum(j, _live_tiles(pos, i, tq, tk, window) - 1)
    return j + _first_tile(pos, i, tq, tk, window) if window is not None else j


def chunk_attention_pallas(q, k, v, q_start, k_start, upto, scale, *,
                           window=None, sink=None, name="chunk_attention"):
    """Causal attention of ONE request's chunk of queries over keys laid
    out in a row. q [T, H, Dk] at absolute positions ``q_start + t``; k
    [L, Hkv, Dk] and v [L, Hkv, Dv] at ``k_start + l`` (negative positions
    are no tokens); positions ``>= upto`` are padding. Query t sees keys
    ``max(0, t - window + 1) .. t`` (all of ``0 .. t`` without a window);
    ``sink`` [H] f32 is a per-head logit in the softmax's denominator.
    Returns [T, H, Dv]; rows past ``upto`` are undefined.

    The grid is (kv head, query tile, key tile); with a window a query tile
    walks only the ``window / tk + 1`` key tiles its band reaches, so the
    work follows T x window, not T x L."""
    t, h, dk = q.shape
    ln, hkv, dv = v.shape
    groups = h // hkv
    tq, tk, n_k = _chunk_tiles(t, groups, ln, window)
    pos = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                     for x in (q_start, k_start, upto)])
    # [Hkv, G, T, Dk]: a tile's rows are (head of the group, query row)
    qg = jnp.transpose(q.reshape(t, hkv, groups, dk), (1, 2, 0, 3))
    kg = jnp.swapaxes(k, 0, 1)
    vg = jnp.swapaxes(v, 0, 1)
    q_spec = pl.BlockSpec((1, groups, tq, dk), lambda a, i, j, P: (a, 0, i, 0))
    o_spec = pl.BlockSpec((1, groups, tq, dv), lambda a, i, j, P: (a, 0, i, 0))

    def key_spec(width):
        return pl.BlockSpec(
            (1, tk, width),
            lambda a, i, j, P: (a, _key_tile(P, i, j, tq, tk, window), 0))

    in_specs, operands = [q_spec, key_spec(dk), key_spec(dv)], [qg, kg, vg]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, groups * tq, 1),
                                     lambda a, i, j, P: (a, 0, 0)))
        operands.append(jnp.repeat(
            sink.astype(jnp.float32).reshape(hkv, groups), tq,
            axis=1)[..., None])
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, tq=tq, tk=tk, groups=groups,
                          scale=float(scale), window=window,
                          sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, t // tq, n_k),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((groups * tq, dv), jnp.float32),
                pltpu.VMEM((groups * tq, _LANES), jnp.float32),
                pltpu.VMEM((groups * tq, _LANES), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((hkv, groups, t, dv), q.dtype),
        interpret=_interpret(),
        name=name,
    )(pos, *operands)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(t, h, dv)
