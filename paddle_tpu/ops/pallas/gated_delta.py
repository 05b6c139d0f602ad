"""The gated delta rule for the serving path (ISSUE 37): the decode update as
a Pallas TPU kernel, and the chunked form of a prefill chunk in XLA.

The layer's recurrence, a value head of ``P`` channels over keys of ``N``:

    S' = exp(g_t) S_{t-1};   d_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t (x) d_t;  o_t = S_t^T q_t

(``S [N, P]`` float32, zero at the start; ``q_t``, ``k_t [N]`` shared by the
``Hv / Hk`` value heads of a key head, L2-normed a head and ``q`` scaled by
``N^-1/2`` HERE, as the family's kernels do it (``use_qk_l2norm_in_kernel``):
``normed_qk``; ``v_t [P]``; ``g_t <= 0`` and ``0 < beta_t < 1`` scalars a
value head). Against Mamba-2's update (``ops/pallas/mamba2.py``) the state is
READ before it is written (``S'^T k``), and the output reads the NEW state.

**How a state lies in the cache** (``KVLayerSpec.state_shapes``, a head a
lane row: ``pack`` 1): ``[slots, Hv, N, P]``, the keys over the sublanes and
the values over the lanes. ``S^T k`` and ``S^T q`` are then sums over
sublanes (adds of whole registers), ``v``, ``d`` and ``o`` lie along the
lanes as the projections give them, and ``k (x) d`` is a column times a row.

**The decode update** (``gated_delta_decode_update``, that name in a trace):
one call a layer, one grid step a row of the batch. Row ``b``'s state is the
block at ``slots[b]`` (scalar-prefetched), read, updated and written back IN
PLACE (``input_output_aliases``); a dead row points at the null slot. Bound
by bytes: a row's state read once and written once. ``_lax`` is the same
operations in the same order in ``jax.numpy``: the CPU's path and the
kernel's test reference.

**The chunk's form** (``gated_delta_chunk``, a ``jax.jit`` of that name): the
family's ``chunk_gated_delta_rule`` in blocks of 64. With ``G`` the running
sum of ``g`` in a block and ``M = tril(beta k k^T exp(G_i - G_j), -1)``, the
block's corrections solve ``(I + M) D = beta V - (beta K exp(G)) S``: ``T =
(I + M)^-1`` is a product of ``log2(64)`` factors (``M`` is nilpotent), in
float32 at precision "highest"; ``U = T (beta V)``, ``W = T (beta K
exp(G))``. Then a ``lax.scan`` over the blocks carries ``S``: ``D = U - W
S``, ``o = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) D``, ``S <- exp(G_last)
S + (k exp(G_last - G))^T D``. No loop over tokens. A position with ``g = 0``
and ``beta = 0`` changes nothing: that is how a chunk's padding is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret
from .mamba2 import _VMEM_LIMIT, use_pallas_mamba2

__all__ = ["gated_delta_decode_update", "gated_delta_decode_update_lax",
           "gated_delta_chunk", "gated_delta_recurrence", "normed_qk",
           "BLOCK"]

#: tokens a block of the chunked form takes (the family's kernels' 64)
BLOCK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def normed_qk(q, k):
    """``q`` and ``k [..., N]`` L2-normed over their last axis (eps 1e-6
    under the root, as the family's kernels), ``q`` times ``N^-1/2``;
    float32."""
    f32 = jnp.float32

    def unit(x):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return unit(q) * (q.shape[-1] ** -0.5), unit(k)


def _kernel(slots_ref, s_ref, da_ref, beta_ref, v_ref, kt_ref, qt_ref, y_ref,
            so_ref, *, key_heads):
    del slots_ref                       # the index maps read it
    heads, n, lanes = s_ref.shape[1:]
    per = heads // key_heads            # value heads of a key head
    for j in range(key_heads):
        k_col = jnp.broadcast_to(kt_ref[0, :, j:j + 1], (n, lanes))
        q_col = jnp.broadcast_to(qt_ref[0, :, j:j + 1], (n, lanes))
        for h in range(j * per, (j + 1) * per):
            s = s_ref[0, h] * da_ref[0, h:h + 1, :]
            d = beta_ref[0, h:h + 1, :] * (
                v_ref[0, h:h + 1, :] - jnp.sum(s * k_col, axis=0, keepdims=True))
            new = s + k_col * d
            so_ref[0, h] = new
            y_ref[0, h:h + 1, :] = jnp.sum(new * q_col, axis=0, keepdims=True)


def _operands(q, k, v, g, beta):
    """What both forms of the update take, float32: the decay and ``beta``
    over a head's lanes ``[B, Hv, P]``, ``v`` likewise, the normed ``k`` and
    ``q`` with the key dim first ``[B, N, Hk]``."""
    f32 = jnp.float32
    p = v.shape[-1]
    q, k = normed_qk(q, k)
    lanes = lambda m: jnp.repeat(m.astype(f32)[..., None], p, -1)  # noqa: E731
    return (lanes(jnp.exp(g.astype(f32))), lanes(beta), v.astype(f32),
            jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2))


def gated_delta_decode_update_lax(state, slots, q, k, v, g, beta):
    """The update in ``jax.numpy``: ``gated_delta_decode_update``'s signature
    and numbers (the same operations in the same order, a gather of the
    rows' states before and a scatter after)."""
    per = v.shape[1] // k.shape[1]
    da, bt, vf, kt, qt = _operands(q, k, v, g, beta)
    k_col, q_col = (jnp.repeat(jnp.swapaxes(m, 1, 2), per, axis=1)[..., None]
                    for m in (kt, qt))                       # [B, Hv, N, 1]
    s = state[slots] * da[:, :, None, :]
    d = bt * (vf - jnp.sum(s * k_col, axis=2))
    new = s + k_col * d[:, :, None, :]
    return jnp.sum(new * q_col, axis=2), state.at[slots].set(new)


def gated_delta_decode_update(state, slots, q, k, v, g, beta,
                              name="gated_delta_decode_update"):
    """One token a row. ``state`` float32 ``[slots, Hv, N, P]``; ``slots``
    int32 ``[B]``: where row ``b``'s state lies (a dead row: the null slot);
    ``q`` and ``k [B, Hk, N]`` as the convolution leaves them (normed here),
    ``v [B, Hv, P]``, ``g [B, Hv]`` the log of the decay, ``beta [B, Hv]``.
    Returns ``(o [B, Hv, P] float32, the states)``: ``S <- exp(g) S``, ``S <-
    S + k (x) beta (v - S^T k)``, ``o = S^T q`` from the new state. Updated
    in place where the caller donates the states. Pallas on the TPU, the
    ``lax`` form here."""
    if not use_pallas_mamba2(state.shape[-1], state.shape[-2]):
        return gated_delta_decode_update_lax(state, slots, q, k, v, g, beta)
    return _call(state, slots, q, k, v, g, beta, interpret=_interpret(),
                 name=name)


# a jit of its own: a model's delta layers are the same shapes, so the kernel
# is traced and lowered once a program, not once a layer
@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _call(state, slots, q, k, v, g, beta, *, interpret,
          name="gated_delta_decode_update"):
    bsz, heads, p = v.shape
    key_heads, n = k.shape[1], k.shape[2]
    if state.shape[1:] != (heads, n, p) or heads % key_heads \
            or state.dtype != jnp.float32:
        raise ValueError(
            "gated_delta_decode_update takes float32 states [slots, Hv, N, "
            f"P], a value head a lane row; got {state.shape} {state.dtype} "
            f"for {heads} value heads of {p} over {key_heads} keys of {n}")
    da, bt, vf, kt, qt = _operands(q, k, v, g, beta)
    row = lambda i, slots: (i, 0, 0)                       # noqa: E731
    at_slot = lambda i, slots: (slots[i], 0, 0, 0)         # noqa: E731
    lane_rows = pl.BlockSpec((1, heads, p), row)
    columns = pl.BlockSpec((1, n, key_heads), row)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, heads, n, p), at_slot),
                  lane_rows, lane_rows, lane_rows, columns, columns],
        out_specs=[lane_rows, pl.BlockSpec((1, heads, n, p), at_slot)],
    )
    y, new = pl.pallas_call(
        functools.partial(_kernel, key_heads=key_heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 0 is the slots; the states go out where they came in
        input_output_aliases={1: 1},
        # dead rows share the null slot: one row after another
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.asarray(slots, jnp.int32), state, da, bt, vf, kt, qt)
    return y, new


# ---------------------------------------------------------------------------
# a prefill chunk
# ---------------------------------------------------------------------------

def gated_delta_recurrence(q, k, v, g, beta, s0):
    """The recurrence token by token (``lax.scan`` over ``t``): what
    ``gated_delta_chunk`` is tested against, and nothing's path. Operands
    and results as there."""
    f32 = jnp.float32
    per = v.shape[1] // k.shape[1]
    q, k = (jnp.repeat(m, per, axis=1) for m in normed_qk(q, k))  # [T, Hv, N]

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hnp,hn->hp", s, k_t,
                                             precision=_HIGHEST))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hnp,hn->hp", s, q_t, precision=_HIGHEST)

    s, o = jax.lax.scan(step, s0.astype(f32), (
        q, k, v.astype(f32), g.astype(f32), beta.astype(f32)))
    return o, s


def _inverse_unit_lower(m):
    """``(I + m)^-1`` for strictly lower triangular ``m [..., L, L]``, ``L``
    a power of two: ``(I - a)^-1 = (I + a)(I + a^2)(I + a^4) ...`` with ``a =
    -m``, which ends because ``a^L = 0``. Float32 products at precision
    "highest": what the corrections of a whole block pass through."""
    size = m.shape[-1]
    eye = jnp.eye(size, dtype=m.dtype)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    power = -m
    inv = eye + power
    span = 2
    while span < size:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


@functools.partial(jax.jit, static_argnames=("block",))
def gated_delta_chunk(q, k, v, g, beta, s0, block=BLOCK):
    """One request's tokens through the recurrence in blocks of ``block``.
    ``q`` and ``k [T, Hk, N]`` as the convolution leaves them (normed here),
    ``v [T, Hv, P]``, ``g [T, Hv]`` (the log of the decay; 0 where a position
    is padding), ``beta [T, Hv]`` (0 where padding), ``s0 [Hv, N, P]`` the
    state before the first token. Returns ``(o [T, Hv, P], the state after
    the last token [Hv, N, P])``, float32.

    The inverse of a block's triangular system is computed at precision
    "highest"; the other matrix products take the default precision: on the
    chip ONE bfloat16 pass over their float32 factors, accumulated in
    float32, as the family's chunked kernels multiply (and as
    ``ssd_chunk_scan`` does); the CPU and ``gated_delta_recurrence`` compute
    them in full float32. What lies in a slot between chunks and steps is
    float32 either way."""
    f32 = jnp.float32
    t, heads, p = v.shape
    key_heads, n = k.shape[1], k.shape[2]
    per = heads // key_heads
    q, k = normed_qk(q, k)
    pad = -t % block
    if pad:         # g = 0, beta = 0: a padded position changes nothing
        q, k, v, g, beta = (jnp.pad(m, [(0, pad)] + [(0, 0)] * (m.ndim - 1))
                            for m in (q, k, v, g, beta))
    nb = (t + pad) // block
    # [nb, Hk, per, L, .]: a key head's value heads beside it
    q = jnp.swapaxes(q.reshape(nb, block, key_heads, n), 1, 2)[:, :, None]
    k = jnp.swapaxes(k.reshape(nb, block, key_heads, n), 1, 2)[:, :, None]

    def by_head(m):
        m = m.astype(f32).reshape((nb, block, key_heads, per) + m.shape[2:])
        return jnp.moveaxis(m, 1, 3)                    # [nb, Hk, per, L, ..]

    v, g, beta = by_head(v), by_head(g), by_head(beta)
    cum = jnp.cumsum(g, axis=-1)                        # [nb, Hk, per, L]
    seg = cum[..., :, None] - cum[..., None, :]         # G_i - G_j
    rows = jnp.arange(block)
    lower = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))    # 0 above the diagonal
    kk = jnp.einsum("bhxin,bhxjn->bhxij", k, k)         # [nb, Hk, 1, L, L]
    strict = rows[:, None] > rows[None, :]
    m = jnp.where(strict, beta[..., None] * kk * decay, 0.0)
    inv = _inverse_unit_lower(m)                        # [nb, Hk, per, L, L]
    u = jnp.matmul(inv, beta[..., None] * v, precision=_HIGHEST)
    w = jnp.matmul(inv, (beta * jnp.exp(cum))[..., None] * k,
                   precision=_HIGHEST)                  # [nb, Hk, per, L, N]
    qk = jnp.einsum("bhxin,bhxjn->bhxij", q, k) * decay  # tril with diagonal
    q_in = q * jnp.exp(cum)[..., None]                  # what reads S
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # what S keeps
    through = jnp.exp(cum[..., -1])                     # [nb, Hk, per]

    def carry(s, blk):
        u, w, qk, q_in, k_out, through = blk
        d = u - jnp.matmul(w, s)                        # [Hk, per, L, P]
        o = jnp.matmul(q_in, s) + jnp.matmul(qk, d)
        s = s * through[..., None, None] \
            + jnp.matmul(jnp.swapaxes(k_out, -1, -2), d)
        return s, o

    s_end, o = jax.lax.scan(
        carry, s0.astype(f32).reshape(key_heads, per, n, p),
        (u, w, qk, q_in, k_out, through))
    o = jnp.moveaxis(o, 3, 1).reshape(nb * block, heads, p)[:t]
    return o, s_end.reshape(heads, n, p)
