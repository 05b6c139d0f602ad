"""Row-sparse gradient representation for embedding lookups.

The problem (PERF.md; round-5 chip run on an earlier installation, not
reproduced): DeepFM's 1M-row embedding tables train at 0.4% MFU because every step materializes a dense ``[vocab, dim]``
gradient (the transpose of the gather is a vocab-sized scatter-add) and the
optimizer then streams the full table plus BOTH Adam moments through HBM to
update the ~0.04% of rows a batch actually touches. The reference's answer
is ``Adam(lazy_mode=True)`` over SelectedRows gradients
(``paddle/phi/kernels/selected_rows/adam_kernel.h``); this module is the
JAX-native equivalent.

Mechanism: JAX's ``custom_vjp`` cannot return a sparse cotangent for a dense
input (cotangent structure must match the primal), so the row-sparse backward
is built the other way around — the lookup is *captured*:

1. the table enters the loss through ``jax.lax.stop_gradient`` (no dense
   cotangent is ever built), and
2. the gathered rows get a zeros ``[n_ids, dim]`` **delta** added — a real
   differentiation input, so ``grad`` w.r.t. the delta is exactly the
   per-occurrence row gradient, at batchxfields size instead of vocab size.

Duplicate ids are then segment-summed into unique slots
(:func:`segment_rows`) with a **static** size bound ``n_ids = batch*fields``
— shapes stay bucket-stable for the PR-1 jit cache; the dynamic "how many
unique" lives in a ``valid`` mask, never in a shape. The capture is
activated by :class:`FusedTrainStep` (see ``incubate/fused_train_step.py``)
around its traced loss; ``F.embedding`` / ``F.embedding_bag`` consult
:func:`captured_lookup` / :func:`captured_pooled_lookup` and take the
delta route when their table is registered.

Eager mode has no trace to capture, so :func:`note_eager_lookup` records
the looked-up ids at forward time (``SparseEmbedding.forward``) and the
eager ``Adam(lazy_mode=True)`` path consumes them to gather the touched
rows of the (dense) autograd gradient.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

__all__ = [
    "SparseCapture", "capture", "active_capture", "captured_lookup",
    "captured_pooled_lookup", "segment_rows", "note_eager_lookup",
    "consume_eager_lookups", "peek_eager_lookups",
]

_TLS = threading.local()


class SparseCapture:
    """One trace's capture state.

    ``registry`` maps ``id(weight array)`` (the traced table array as bound
    by ``functional_call``) to the parameter's structured name. Two modes:

    - ``discover``: an abstract pass (``jax.eval_shape``) that only records
      each lookup's flattened id count per table, so the caller can build
      the zero deltas *before* differentiating;
    - ``apply``: the real pass — each lookup consumes its delta (in call
      order, which is deterministic because tracing is) and records its
      flattened ids for the backward's dedup.
    """

    def __init__(self, registry, mode, deltas=None):
        self.registry = dict(registry)
        self.mode = mode  # "discover" | "apply"
        self.deltas = deltas or {}  # name -> list of [n_ids, dim] arrays
        self.counts = {}  # name -> per-lookup n_ids (discover)
        self.ids = {}  # name -> per-lookup flat ids (apply)
        self._cursor = {}  # name -> next delta index (apply)

    def match(self, weight):
        return self.registry.get(id(weight))

    def on_lookup(self, name, flat_ids, rows):
        """Route one lookup's gathered rows through its delta."""
        if self.mode == "discover":
            self.counts.setdefault(name, []).append(int(flat_ids.shape[0]))
            return rows
        i = self._cursor.get(name, 0)
        self._cursor[name] = i + 1
        chunk = self.deltas[name][i]
        self.ids.setdefault(name, []).append(flat_ids)
        return rows + chunk.astype(rows.dtype)


class _Scope:
    def __init__(self, cap):
        self.cap = cap

    def __enter__(self):
        prev = getattr(_TLS, "capture", None)
        if prev is not None:
            raise RuntimeError("sparse-grad captures do not nest")
        _TLS.capture = self.cap
        return self.cap

    def __exit__(self, *exc):
        _TLS.capture = None
        return False


def capture(registry, mode, deltas=None):
    """Context manager installing a :class:`SparseCapture` for this thread."""
    return _Scope(SparseCapture(registry, mode, deltas))


def active_capture():
    return getattr(_TLS, "capture", None)


def captured_lookup(x, weight):
    """The capture hook ``F.embedding`` consults. Returns the looked-up
    ``x.shape + (dim,)`` rows when ``weight`` is a registered table inside
    an active capture, else ``None`` (caller takes the dense gather).

    The forward value is bit-identical to the dense gather — the delta is
    zeros — but the table itself is wrapped in ``stop_gradient``, so the
    backward produces ``[n_ids, dim]`` delta grads instead of a
    vocab-sized scatter-add."""
    cap = active_capture()
    if cap is None:
        return None
    name = cap.match(weight)
    if name is None:
        return None
    flat = x.reshape(-1)
    rows = jnp.take(jax.lax.stop_gradient(weight), flat, axis=0)
    rows = cap.on_lookup(name, flat, rows)
    return rows.reshape(tuple(x.shape) + (weight.shape[-1],))


def captured_pooled_lookup(x, weight, mode):
    """Capture hook for the fused lookup+pool (``F.embedding_bag``):
    gathered rows flow through the delta, then the pool reduces over the
    field axis in the same expression — the ``[B, F, dim]`` intermediate
    is never handed to another op, so XLA fuses gather+reduce into one
    loop. Returns ``[B, dim]`` or ``None`` when not captured."""
    cap = active_capture()
    if cap is None:
        return None
    name = cap.match(weight)
    if name is None:
        return None
    flat = x.reshape(-1)
    rows = jnp.take(jax.lax.stop_gradient(weight), flat, axis=0)
    rows = cap.on_lookup(name, flat, rows)
    rows = rows.reshape(tuple(x.shape) + (weight.shape[-1],))
    if mode == "mean":
        return rows.mean(axis=-2)
    return rows.sum(axis=-2)


def _dedup_plan(ids):
    """The one shared slot layout every dedup consumer depends on (the
    masked-slot aliasing in ``lazy_adam_rows`` relies on it): sort the
    ids, flag segment heads, and assign each sorted position its unique
    slot. Returns ``(order, sids, slot, valid)`` for non-empty ``ids``."""
    K = int(ids.shape[0])
    order = jnp.argsort(ids)
    sids = ids[order]
    head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sids[1:] != sids[:-1]])
    slot = jnp.cumsum(head) - 1  # [K] in [0, n_unique)
    valid = jnp.arange(K) < jnp.sum(head)
    return order, sids, slot, valid


def unique_ids(ids):
    """Static-shape dedup of a flat id vector: ``(uniq_ids [K],
    valid [K])`` with each distinct id once in the leading slots (the
    :func:`segment_rows` slot layout, via the shared :func:`_dedup_plan`).
    Pure jnp — call it inside a jitted consumer so the sort/cumsum fuse
    into its executable."""
    if int(ids.shape[0]) == 0:
        return ids, jnp.zeros((0,), jnp.bool_)
    _, sids, slot, valid = _dedup_plan(ids)
    return jnp.zeros_like(sids).at[slot].set(sids), valid


def lookup_only_tables(closed_jaxpr, tables):
    """Which of ``tables`` (name -> array, matched by IDENTITY against the
    jaxpr's consts) are consumed ONLY through ``stop_gradient`` — i.e. the
    capture's lookup route — in the traced loss?

    This is the safety gate for the row-sparse path: a table used anywhere
    else (tied output projection, a direct matmul, a dtype cast before the
    lookup that breaks identity matching) would silently lose that
    gradient contribution, so such tables must fall back to the dense
    path. The check is conservative: any non-``stop_gradient`` consumer —
    including an opaque sub-call the table is passed into — marks the
    table unsafe. Returns the set of SAFE names."""
    jaxpr = closed_jaxpr.jaxpr
    var_of = {}
    for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
        for name, arr in tables.items():
            if cval is arr:
                var_of[name] = cv
    safe = set()
    for name in tables:
        v = var_of.get(name)
        if v is None:
            safe.add(name)  # never consumed at all: no gradient to lose
            continue
        ok = True
        for eqn in jaxpr.eqns:
            if any(iv is v for iv in eqn.invars) \
                    and eqn.primitive.name != "stop_gradient":
                ok = False
                break
        if ok:
            safe.add(name)
    return safe


def segment_rows(ids, vals, combine="add"):
    """Deduplicate row gradients into unique slots with STATIC shapes.

    ``ids [K]`` int, ``vals [K, dim]``. Returns ``(uniq_ids [K],
    uniq_vals [K, dim], valid [K] bool)`` where the first ``n_unique``
    slots hold each distinct id once; slots beyond that are zero and
    masked out by ``valid``. K is the static bound (batch*fields), so the
    output shape never depends on the batch's id distribution — the price
    is carrying dead slots, which the consumer masks.

    ``combine="add"`` sums duplicates (per-occurrence delta grads — the
    segment-sum dedup); ``combine="set"`` keeps one representative
    (rows gathered from an already-summed dense gradient, where summing
    duplicates would multiply-count)."""
    if int(ids.shape[0]) == 0:
        return ids, vals, jnp.zeros((0,), jnp.bool_)
    order, sids, slot, valid = _dedup_plan(ids)
    svals = vals[order]
    if combine == "add":
        uniq_vals = jnp.zeros_like(svals).at[slot].add(svals)
    else:  # duplicates of one id carry identical values: set is exact
        uniq_vals = jnp.zeros_like(svals).at[slot].set(svals)
    uniq_ids = jnp.zeros_like(sids).at[slot].set(sids)
    return uniq_ids, uniq_vals, valid


# ---------------------------------------------------------------------------
# eager-mode lookup recording (the lazy path's id source outside a trace)
# ---------------------------------------------------------------------------

# The record lives ON the table's Tensor (``_lazy_lookup_rec`` attribute):
# its lifecycle is the tensor's — no global registry, no stale entries for
# collected tables, no id()-reuse aliasing one table's ids onto another.
# Consume-on-step protocol; a non-lazy optimizer never consumes, so the
# per-table list is capped: past _MAX_CHUNKS it collapses to an OVERFLOW
# marker until the next consume resets it (dense fallback — always
# correct; silently dropping chunks could LOSE touched rows instead).
_REC_ATTR = "_lazy_lookup_rec"
_OVERFLOW = "overflow"
_MAX_CHUNKS = 32


def note_eager_lookup(weight_tensor, ids):
    """Record one eager lookup's ids against the table parameter (called
    from ``SparseEmbedding.forward`` outside a trace). The eager
    ``Adam(lazy_mode=True)`` update consumes these to know which rows of
    the dense autograd gradient are live."""
    cur = getattr(weight_tensor, _REC_ATTR, None)
    if cur is _OVERFLOW:
        return
    arr = ids._data if hasattr(ids, "_data") else jnp.asarray(ids)
    if cur is None:
        cur = []
        setattr(weight_tensor, _REC_ATTR, cur)
    cur.append(arr.reshape(-1).astype(jnp.int32))
    if len(cur) > _MAX_CHUNKS:
        setattr(weight_tensor, _REC_ATTR, _OVERFLOW)


def peek_eager_lookups(weight_tensor):
    got = getattr(weight_tensor, _REC_ATTR, None)
    return None if got is _OVERFLOW else got


def consume_eager_lookups(weight_tensor):
    """Pop and concatenate the recorded flat ids for this table. Returns
    ``None`` (→ dense path) when nothing was recorded since the last
    consume, or when the record overflowed (an un-consuming optimizer or
    >32 forwards of gradient accumulation — the dense update stays
    correct either way)."""
    chunks = getattr(weight_tensor, _REC_ATTR, None)
    if chunks is not None:
        setattr(weight_tensor, _REC_ATTR, None)
    if not chunks or chunks is _OVERFLOW:
        return None
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks)
