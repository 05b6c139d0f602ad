"""Grouped expert feed-forward — Pallas TPU (ISSUE 30; the ungated form
ISSUE 33).

The feed-forward of a layer's held experts as ONE call: the (token, expert)
pairs sorted by expert go in, ``(silu(x g_e) * (x u_e)) d_e`` of each pair's
token by its expert comes out, and every hit expert's three matrices are
streamed through VMEM once a pass, block by block, where they lie.

**The gate is optional.** An expert given as ``(up, down)`` has no gate and
the activation is then ``relu(.)^2``: ``relu(x u_e)^2 d_e``. It is the same
kernel body with the gate's buffers, copies and product absent, named
``moe_grouped_relu2`` in a trace (``moe_grouped_swiglu`` with a gate). Below,
"gate and up" reads "up" for such experts.

**A width that is no multiple of 128 is stored wider.** The blocks are
multiples of 128 rows, so an expert 1,856 wide is held 1,920 wide with zeros
in ``up``'s last columns and ``down``'s last rows (``relu(0)^2 = 0`` and
``silu(0) * 0 = 0``: exact); a roofline counts the published width.

**Weights are read where they lie.** The ``3 x experts`` matrices are the
model's own parameters, one array each, left in HBM (``pl.ANY``): nothing is
stacked or copied beside them. A unit of work is an *item*: one expert and
at most ``rows`` of its pairs. The items (expert, first sorted pair, pairs)
are scalar-prefetched; the grid walks them in order and a step past the last
item does nothing, so an expert without a pair is never read. Which array a
copy reads from is chosen at run time by a ``pl.when`` chain over the
experts around the copy's start; a wait needs only the shape.

**One copy pipeline for the whole call.** An item's weights are cut into
row blocks, which are contiguous in HBM: ``[kb, F]`` of gate and of up
(``D / kb`` of them), then ``[fb, D]`` of down (``F / fb``), one loop over
them all. Each block is copied by hand into one slot of a double buffer,
and the next block's copy — the next item's first block and its rows, at an
item's last — is started before this block is waited for and multiplied, so
the pipeline drains once a call, not once an expert. ``x g`` and ``x u`` accumulate in float32 over
the gate/up blocks; ``silu(.) * .`` is taken in float32 and rounded once to
the weights' dtype for the down dot, which accumulates in float32: the
intermediate never leaves VMEM.

**Rows are fetched and put back one by one, the live ones only.** ``x`` is
the layer's input in float32 as ``[T, D / 128, 128]``, left in HBM: a row
is then ``D / 128`` whole (8, 128) tiles wherever it lies, which a copy may
address alone (one row of a ``[T, D]`` array is a sublane of its tiles,
and Mosaic refuses that slice). An item copies the rows of its pairs'
tokens into a buffer of that shape in VMEM, turns it lane block by lane
block (a strided load each) into the ``[rows, D]`` tile the dots take,
rounded to the weights' dtype, and sends each result row back the same way,
float32, to its pair's place in the output ``[T x k, D / 128, 128]``. What
a call moves besides the weights therefore follows the pairs routed here,
not the most that could be; a pair of no held expert has its output row
left as it was, and the caller masks it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["grouped_swiglu", "use_pallas_grouped_ffn", "rows_for"]

#: the rows of a tile are a multiple of this: the bf16 sublane tile
ROW_ALIGN = 16
#: most rows an item multiplies: under the MXU's 128 x 128 a pass costs the
#: weights' load whatever the rows, over it the pass is no longer bound by
#: the bytes it streams
MAX_ROWS = 128
#: bytes of one matrix's block in a copy (two slots each of gate, up, down)
_BLOCK_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def use_pallas_grouped_ffn(d, f):
    """The kernel's gate, as ``use_pallas_paged``: the chip's path, and the
    CPU's under ``PT_PALLAS_INTERPRET=1`` at widths it takes."""
    ok = d % 128 == 0 and f % 128 == 0
    if _interpret():
        return ok
    if jax.default_backend() != "tpu":
        return False
    if not ok:
        # PR 21: no hidden fallback on the chip
        raise ValueError(
            "the grouped feed-forward kernel (gated or not) takes hidden "
            "and intermediate widths that are multiples of 128, an expert's "
            "matrices stored wider with zeros where its published width is "
            f"none; got {d} and {f}")
    return True


def rows_for(tokens):
    """The rows an item holds for a call of ``tokens`` tokens: all of an
    expert's pairs in one pass while they fit ``MAX_ROWS``."""
    return min(-(-tokens // ROW_ALIGN) * ROW_ALIGN, MAX_ROWS)


def _block_rows(extent, width, itemsize, block_bytes):
    """Rows of a weight block: a multiple of 128 that divides ``extent``,
    the largest under ``block_bytes``."""
    b = max(128, min(extent, block_bytes // (width * itemsize)) // 128 * 128)
    while extent % b:
        b -= 128
    return b


def _kernel(order_ref, e_ref, start_ref, rows_ref, n_ref, x_hbm, *refs,
            n_held, top_k, kb, fb, gated=True):
    m = 3 if gated else 2
    w = refs[:m * n_held]                     # (gate,) up, down an expert
    y_hbm = refs[m * n_held]
    scratch = refs[m * n_held + 1:]
    if gated:
        (xbuf, xcast, gbuf, ubuf, dbuf, g_acc, u_acc, h_buf, o_acc, obuf,
         sems) = scratch
    else:
        xbuf, xcast, ubuf, dbuf, u_acc, h_buf, o_acc, obuf, sems = scratch
    d, f = w[0].shape
    n_k, n_f = d // kb, f // fb
    i = pl.program_id(0)
    n = n_ref[0]

    def gate_up(k, j, slot):
        at = pl.ds(j * kb, kb)
        gate = [pltpu.make_async_copy(w[3 * k].at[at], gbuf.at[slot],
                                      sems.at[0, slot])] if gated else []
        return gate + [pltpu.make_async_copy(
            w[m * k + m - 2].at[at], ubuf.at[slot], sems.at[1, slot])]

    def down(k, j, slot):
        return [pltpu.make_async_copy(w[m * k + m - 1].at[pl.ds(j * fb, fb)],
                                      dbuf.at[slot], sems.at[2, slot])]

    def start(e, b):
        """Start the copies of block ``b`` of expert ``e``'s weights (its
        gate/up blocks first, then its down blocks): the source is picked
        at run time, and this is the one place it is (every leaf is traced
        and lowered: 80 of them over five such places were four fifths of
        the kernel's tracing)."""
        which = 2 * e + (b >= n_k).astype(jnp.int32)
        leaves = [(block, k, j) for k in range(n_held)
                  for block, j in ((gate_up, b), (down, b - n_k))]
        # one `when` after another: Mosaic's compiler overflowed its stack
        # on the 32-deep nest a `lax.switch` lowers to
        for at, (block, k, j) in enumerate(leaves):
            @pl.when(which == at)
            def _():
                for cp in block(k, j, j % 2):
                    cp.start()

    def wait(block, j):
        for cp in block(0, j, j % 2):         # a wait reads the shape alone
            cp.wait()

    def each_row(item, act):
        """``act`` on the copy of every live row of ``item``: its token's
        row of x into the item's tile, or its result out to its pair's."""
        def row(r, carry):
            act(order_ref[start_ref[item] + r], r)
            return carry

        jax.lax.fori_loop(0, rows_ref[item], row, 0)

    def fetch(item, how):
        each_row(item, lambda pair, r: how(pltpu.make_async_copy(
            x_hbm.at[pl.ds(pair // top_k, 1)],
            xbuf.at[item % 2, pl.ds(r, 1)], sems.at[3, item % 2])))

    def put_back(item, how):
        each_row(item, lambda pair, r: how(pltpu.make_async_copy(
            obuf.at[pl.ds(r, 1)], y_hbm.at[pl.ds(pair, 1)], sems.at[4, 0])))

    @pl.when(i < n)
    def _item():
        e = e_ref[i]
        more = i + 1 < n
        e_next = e_ref[jnp.minimum(i + 1, e_ref.shape[0] - 1)]

        def block(b, carry):
            # what comes after block b: this expert's next block, or the
            # next item's first with its rows; before the call's first
            # block (b = -1, once a call) that block itself
            within = b + 1 < n_k + n_f

            @pl.when(within | more)
            def _():
                start(jnp.where(within, e, e_next),
                      jnp.where(within, b + 1, 0))

            @pl.when(b < 0)
            def _():
                fetch(i, lambda cp: cp.start())

            @pl.when(jnp.logical_not(within) & more)
            def _():
                fetch(i + 1, lambda cp: cp.start())

            @pl.when((b >= 0) & (b < n_k))
            def _gate_up():
                @pl.when(b == 0)
                def _rows():
                    fetch(i, lambda cp: cp.wait())
                    for c in range(d // 128):     # a lane block of every row
                        at = c * 128 % kb
                        xcast[c * 128 // kb, :, at:at + 128] = \
                            xbuf[i % 2, :, c, :].astype(xcast.dtype)
                    if gated:
                        g_acc[...] = jnp.zeros_like(g_acc)
                    u_acc[...] = jnp.zeros_like(u_acc)

                wait(gate_up, b)
                if gated:
                    g_acc[...] += jnp.dot(xcast[b], gbuf[b % 2],
                                          preferred_element_type=jnp.float32)
                u_acc[...] += jnp.dot(xcast[b], ubuf[b % 2],
                                      preferred_element_type=jnp.float32)

            @pl.when(b >= n_k)
            def _down():
                j = b - n_k

                @pl.when(j == 0)
                def _act():
                    for c in range(n_f):
                        if gated:
                            g = g_acc[:, c * fb:(c + 1) * fb]
                            h_buf[c] = (g * jax.nn.sigmoid(g)
                                        * u_acc[:, c * fb:(c + 1) * fb]
                                        ).astype(h_buf.dtype)
                        else:
                            u = jnp.maximum(u_acc[:, c * fb:(c + 1) * fb], 0.0)
                            h_buf[c] = (u * u).astype(h_buf.dtype)
                    o_acc[...] = jnp.zeros_like(o_acc)

                wait(down, j)
                o_acc[...] += jnp.dot(h_buf[j], dbuf[j % 2],
                                      preferred_element_type=jnp.float32)

            return carry

        jax.lax.fori_loop(jnp.where(i == 0, -1, 0), n_k + n_f, block, 0)

        @pl.when(i > 0)
        def _written():                       # the rows obuf still held
            put_back(i - 1, lambda cp: cp.wait())

        for c in range(d // 128):
            obuf[:, c, :] = o_acc[:, c * 128:(c + 1) * 128]
        put_back(i, lambda cp: cp.start())

        @pl.when(i + 1 == n)
        def _last():
            put_back(i, lambda cp: cp.wait())


def grouped_swiglu(x, order, item_expert, item_start, item_rows, n_items,
                   experts, *, rows, top_k, block_bytes=_BLOCK_BYTES,
                   name=None):
    """``x`` [T, D]; ``order`` int32 [T x k]: the pairs (pair ``p``
    is token ``p // top_k``) sorted by expert; ``item_expert`` /
    ``item_start`` / ``item_rows`` int32 [I]: an item's expert (its place in
    ``experts``), its first pair's place in ``order`` and how many pairs it
    holds (at most ``rows``); ``n_items`` int32: the items that exist (at
    most I); ``experts``: ``(gate [D, F], up [D, F], down [F, D])`` an
    expert, or ``(up [D, F], down [F, D])`` for experts with no gate (the
    activation is then ``relu(.)^2``), of one dtype, which the dots run in.
    Returns float32 ``[T x k, D / 128, 128]``: row ``p`` the feed-forward
    of pair ``p``'s token by the expert of the item that holds it; a pair
    in no item has its row left as it was. ``name`` is the kernel's name in
    a trace: ``moe_grouped_swiglu``, or ``moe_grouped_relu2`` without a
    gate."""
    if name is None:
        name = ("moe_grouped_swiglu" if len(experts[0]) == 3
                else "moe_grouped_relu2")
    return _call(x, order, item_expert, item_start, item_rows, n_items,
                 experts, rows=rows, top_k=top_k, block_bytes=block_bytes,
                 interpret=_interpret(), name=name)


# a jit of its own: a model's expert layers are the same shapes, so the
# kernel is traced and lowered once a program, not once a layer (six layers
# of it were 5 of a decode program's 6 seconds of tracing and lowering)
@functools.partial(jax.jit, static_argnames=(
    "rows", "top_k", "block_bytes", "interpret", "name"))
def _call(x, order, item_expert, item_start, item_rows, n_items, experts, *,
          rows, top_k, block_bytes, interpret, name="moe_grouped_swiglu"):
    t, d = x.shape
    gated = len(experts[0]) == 3
    f = experts[0][0].shape[1]
    dtype = experts[0][0].dtype
    if d % 128 or f % 128 or rows % ROW_ALIGN:
        raise ValueError(
            f"grouped_swiglu takes widths that are multiples of 128 and row "
            f"tiles that are multiples of {ROW_ALIGN}; got D={d}, F={f}, "
            f"rows={rows}")
    shapes = ((d, f),) * (len(experts[0]) - 1) + ((f, d),)
    for mats in experts:
        if len(mats) not in (2, 3) \
                or tuple(m.shape for m in mats) != shapes \
                or any(m.dtype != dtype for m in mats):
            raise ValueError("every expert is (gate [D, F], up [D, F], down "
                             "[F, D]), or (up, down) alike, of one dtype")
    kb = _block_rows(d, f, dtype.itemsize, block_bytes)
    fb = _block_rows(f, d, dtype.itemsize, block_bytes)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    up_block = pltpu.VMEM((2, kb, f), dtype)
    up_acc = pltpu.VMEM((rows, f), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(item_expert.shape[0],),
        in_specs=[hbm] * (1 + len(experts[0]) * len(experts)),
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, rows, d // 128, 128), jnp.float32),  # rows fetched
            pltpu.VMEM((d // kb, rows, kb), dtype),       # rounded, by block
            *([up_block] if gated else []),               # gate blocks
            up_block,                                     # up blocks
            pltpu.VMEM((2, fb, d), dtype),                # down blocks
            *([up_acc] if gated else []),                 # x g
            up_acc,                                       # x u
            pltpu.VMEM((f // fb, rows, fb), dtype),       # the activation
            pltpu.VMEM((rows, d), jnp.float32),           # the down dot
            pltpu.VMEM((rows, d // 128, 128), jnp.float32),     # rows put back
            pltpu.SemaphoreType.DMA((5, 2)),
        ],
    )
    ints = [jnp.asarray(a, jnp.int32) for a in
            (order, item_expert, item_start, item_rows,
             jnp.reshape(n_items, (1,)))]
    kernel = dict(n_held=len(experts), top_k=top_k, kb=kb, fb=fb)
    if not gated:
        kernel["gated"] = False
    return pl.pallas_call(
        functools.partial(_kernel, **kernel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((order.shape[0], d // 128, 128),
                                       jnp.float32),
        # the copy pipeline and the rows put back run from one item into
        # the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*ints, x.astype(jnp.float32).reshape(t, d // 128, 128),
      *(m for mats in experts for m in mats))
