"""Mamba-2's recurrence for the serving path (ISSUE 33): the decode update as
a Pallas TPU kernel, and the chunked scan (SSD) of a prefill chunk in XLA.

The layer's recurrence, a head ``h`` of ``P`` channels over a state of ``N``:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,    y_t = h_t C_t

(``x_t [P]``, ``B_t``, ``C_t [N]`` shared by the heads of a group, ``dt_t``
and ``A < 0`` scalars a head; the skip ``D x_t``, the gate and the norm are
the model's). What a request carries from token to token is ``h``, float32.

**How a state lies in the cache** (``KVLayerSpec.state_shapes``): transposed,
``[slots, H / pack, N, pack * P]``: the state dim over the sublanes and
``pack`` heads side by side in the 128 lanes (two heads of 64). A decode
step's update is then lane-wise in ``x`` as it comes out of the convolution
(``x`` is ``[H * P]``: head-major, so a pair of heads is 128 consecutive
values), ``B`` and ``C`` run down the sublanes, and ``y = h C`` sums over
sublanes: adds of whole registers, not a reduction across lanes a row.
``to_stored`` / ``from_stored`` go between that and ``[H, P, N]``.

**The decode update** (``mamba2_decode_update``, that name in a trace): one
call a layer, one grid step a row of the batch. Row ``b``'s state is the
block at ``slots[b]`` (scalar-prefetched), read, updated and written back IN
PLACE (``input_output_aliases``): a dead row points at the null slot. The
call is bound by bytes: a row's state read once and written once. The
pure-``lax`` form of the same signature is the CPU's path and the kernel's
test reference (``inference/serving/paged_attention.py``'s contract).

**The chunk's scan** (``ssd_chunk_scan``): the chunked form at the config's
``chunk_size``. Inside a block of ``L`` tokens matrix products (``C B^T``
masked by the decay between the two tokens, times ``dt x``); between blocks
a carried state, from the state the chunk before left to the state this
chunk leaves. No loop over tokens. A position whose ``dt`` is 0 changes
nothing (``exp(0) h + 0``): that is how a chunk's padding is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["mamba2_decode_update", "mamba2_decode_update_lax",
           "ssd_chunk_scan", "ssd_recurrence", "to_stored", "from_stored",
           "use_pallas_mamba2"]

#: a row's state in and out, double-buffered, is 8 MiB at the published
#: widths: over Mosaic's scoped default of 16 MiB with the temporaries
_VMEM_LIMIT = 40 * 1024 * 1024


def to_stored(h, pack):
    """``[..., H, P, N]`` -> ``[..., H / pack, N, pack * P]``."""
    *lead, heads, p, n = h.shape
    h = h.reshape(*lead, heads // pack, pack, p, n)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, heads // pack, n, pack * p)


def from_stored(s, pack):
    """``[..., H / pack, N, pack * P]`` -> ``[..., H, P, N]``."""
    *lead, rows, n, lanes = s.shape
    s = s.reshape(*lead, rows, n, pack, lanes // pack)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, rows * pack, lanes // pack, n)


def use_pallas_mamba2(lanes, n):
    """The kernel's gate, as ``use_pallas_paged``: the chip's path, and the
    CPU's under ``PT_PALLAS_INTERPRET=1``. On the chip a stored state whose
    rows are not whole (8, 128) tiles is an error, not a fallback."""
    if _interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    if lanes % 128 or n % 8:
        raise ValueError(
            "the Mamba-2 decode kernel takes a stored state whose rows are "
            f"multiples of 128 lanes over multiples of 8 sublanes; got "
            f"{lanes} lanes over a state dim of {n}")
    return True


def _kernel(slots_ref, h_ref, da_ref, dtx_ref, bt_ref, ct_ref, y_ref, ho_ref,
            *, groups):
    del slots_ref                       # the index maps read it
    rows, n, lanes = h_ref.shape[1:]
    a_group = rows // groups            # lane rows (head pairs) of a group
    for g in range(groups):
        b_col = jnp.broadcast_to(bt_ref[0, :, g:g + 1], (n, lanes))
        c_col = jnp.broadcast_to(ct_ref[0, :, g:g + 1], (n, lanes))
        for k in range(g * a_group, (g + 1) * a_group):
            new = (h_ref[0, k] * da_ref[0, k:k + 1, :]
                   + b_col * dtx_ref[0, k:k + 1, :])
            ho_ref[0, k] = new
            y_ref[0, k:k + 1, :] = jnp.sum(new * c_col, axis=0, keepdims=True)


def _operands(x, dt, a, b, c, pack):
    """What both forms of the update take, float32: the decay and ``dt x``
    over the stored state's lanes ``[B, H / pack, pack * P]``, ``B`` and
    ``C`` with the state dim first ``[B, N, G]``."""
    bsz, heads, p = x.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    da = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=-1)
    dtx = dt[..., None] * x.astype(f32)
    shape = (bsz, heads // pack, pack * p)
    return (da.reshape(shape), dtx.reshape(shape),
            jnp.swapaxes(b.astype(f32), 1, 2), jnp.swapaxes(c.astype(f32), 1, 2))


def mamba2_decode_update_lax(state, slots, x, dt, a, b, c):
    """The update in ``jax.numpy``: ``mamba2_decode_update``'s signature
    and numbers (the same operations in the same order, a gather of the
    rows' states before and a scatter after)."""
    bsz, heads, p = x.shape
    pack = heads // state.shape[1]
    groups = b.shape[1]
    da, dtx, bt, ct = _operands(x, dt, a, b, c, pack)
    per = state.shape[1] // groups
    bt, ct = (jnp.repeat(jnp.swapaxes(m, 1, 2), per, axis=1)[..., None]
              for m in (bt, ct))                       # [B, H / pack, N, 1]
    new = state[slots] * da[:, :, None, :] + bt * dtx[:, :, None, :]
    y = jnp.sum(new * ct, axis=2)
    return y.reshape(bsz, heads, p), state.at[slots].set(new)


def mamba2_decode_update(state, slots, x, dt, a, b, c,
                         name="mamba2_decode_update"):
    """One token a row. ``state`` float32 ``[slots, H / pack, N, pack *
    P]`` (``KVLayerSpec.state_shapes``); ``slots`` int32 ``[B]``: where row
    ``b``'s state lies (a dead row: the null slot); ``x [B, H, P]`` the
    token's channels, ``dt [B, H]`` its step after the softplus, ``a [H]``
    (negative), ``b`` and ``c [B, G, N]``. Returns ``(y [B, H, P] float32,
    the states)``: ``state[slots[b]] <- exp(dt a) state[slots[b]] + dt x (x)
    b`` and ``y = state[slots[b]] c`` from the new state. The states are
    updated in place where the caller donates them. Pallas on the TPU, the
    ``lax`` form here."""
    if not use_pallas_mamba2(state.shape[-1], state.shape[-2]):
        return mamba2_decode_update_lax(state, slots, x, dt, a, b, c)
    return _call(state, slots, x, dt, a, b, c, interpret=_interpret(),
                 name=name)


# a jit of its own: a model's state layers are the same shapes, so the
# kernel is traced and lowered once a program, not once a layer
# (``grouped_ffn._call``'s reason)
@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _call(state, slots, x, dt, a, b, c, *, interpret,
          name="mamba2_decode_update"):
    bsz, heads, p = x.shape
    _, rows, n, lanes = state.shape
    pack, groups = heads // rows, b.shape[1]
    if rows % groups or state.dtype != jnp.float32:
        raise ValueError(
            "mamba2_decode_update takes float32 states whose lane rows "
            f"divide among the groups; got {rows} rows, {groups} groups, "
            f"{state.dtype}")
    da, dtx, bt, ct = _operands(x, dt, a, b, c, pack)
    row = lambda i, slots: (i, 0, 0)                       # noqa: E731
    at_slot = lambda i, slots: (slots[i], 0, 0, 0)         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, rows, n, lanes), at_slot),
                  pl.BlockSpec((1, rows, lanes), row),
                  pl.BlockSpec((1, rows, lanes), row),
                  pl.BlockSpec((1, n, groups), row),
                  pl.BlockSpec((1, n, groups), row)],
        out_specs=[pl.BlockSpec((1, rows, lanes), row),
                   pl.BlockSpec((1, rows, n, lanes), at_slot)],
    )
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups=groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 0 is the slots; the states go out where they came in
        input_output_aliases={1: 1},
        # dead rows share the null slot: one row after another
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.asarray(slots, jnp.int32), state, da, dtx, bt, ct)
    return y.reshape(bsz, heads, p), new


# ---------------------------------------------------------------------------
# a prefill chunk
# ---------------------------------------------------------------------------

def ssd_recurrence(x, dt, a, b, c, h0):
    """The recurrence token by token (``lax.scan`` over ``t``): what
    ``ssd_chunk_scan`` is tested against, and nothing's path. Operands and
    results as there."""
    f32 = jnp.float32
    per = x.shape[1] // b.shape[1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(m, per, axis=0) for m in (b_t, c_t))  # [H, N]
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    h, y = jax.lax.scan(step, h0.astype(f32), (
        x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32)))
    return y, h


def ssd_chunk_scan(x, dt, a, b, c, h0, chunk):
    """One request's tokens through the recurrence in blocks of ``chunk``.
    ``x [T, H, P]``, ``dt [T, H]`` (after the softplus; 0 where a position
    is padding), ``a [H]``, ``b`` and ``c [T, G, N]``, ``h0 [H, P, N]`` the
    state before the first token. Returns ``(y [T, H, P], the state after
    the last token [H, P, N])``, float32.

    The matrix products take the default precision: on the chip ONE bfloat16
    pass over their float32 factors (the decays, ``x dt``, the carried
    state), accumulated in float32, as the family's own chunked kernels
    multiply; the CPU and ``ssd_recurrence`` compute them in full float32.
    What lies in a slot between chunks and steps is float32 either way."""
    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    per = heads // groups
    pad = -t % chunk
    if pad:         # dt = 0: a padded position changes nothing
        x, dt, b, c = (jnp.pad(m, [(0, pad)] + [(0, 0)] * (m.ndim - 1))
                       for m in (x, dt, b, c))
    nb = (t + pad) // chunk
    x = x.astype(f32).reshape(nb, chunk, groups, per, p)
    dt = dt.astype(f32).reshape(nb, chunk, groups, per)
    b = b.astype(f32).reshape(nb, chunk, groups, n)
    c = c.astype(f32).reshape(nb, chunk, groups, n)
    a = a.astype(f32).reshape(groups, per)
    # cum[t]: the log of the decay from the block's start through token t
    cum = jnp.cumsum(dt * a, axis=1)                     # [nb, L, G, per]
    xdt = x * dt[..., None]
    # inside a block: token s reaches token t >= s decayed by the steps
    # after s up to t
    seg = cum[:, :, None] - cum[:, None, :]              # [nb, t, s, G, per]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->btsg", c, b)
    y = jnp.einsum("btsgh,bsghp->btghp", decay * cb[..., None], xdt)
    # what a block adds to the state at its end, and how much of the state
    # at its start is left by then
    to_end = jnp.exp(cum[:, -1:] - cum)                  # [nb, L, G, per]
    local = jnp.einsum("bsgn,bsghp->bghpn", b, xdt * to_end[..., None])
    through = jnp.exp(cum[:, -1])                        # [nb, G, per]

    def carry(h, blk):
        add, keep = blk
        return h * keep[..., None, None] + add, h        # the state before

    h_end, before = jax.lax.scan(
        carry, h0.astype(f32).reshape(groups, per, p, n), (local, through))
    y = y + jnp.einsum("btgn,bghpn->btghp", c, before) \
        * jnp.exp(cum)[..., None]
    return (y.reshape(nb * chunk, heads, p)[:t],
            h_end.reshape(heads, p, n))
