"""Block-allocated paged KV cache (ISSUE 7 tentpole, part a; prefix
sharing added by ISSUE 11).

The flagship decode loop used to grow its cache by ``concat`` — a fresh
XLA compile and a full cache copy per generated token, and worse, memory
sized for every request's MAXIMUM length up front. The serving fix
(vLLM-style, per PAPERS.md "Ragged Paged Attention … for TPU") is a
static block pool:

* one ``[num_blocks, block_size, num_kv_heads, head_dim]`` K and V array
  per layer, allocated ONCE — shapes never change, so one compiled decode
  graph serves any mix of request lengths;
* a host-side free-list ``BlockAllocator`` hands blocks to requests as
  they grow, token by token — memory is proportional to tokens actually
  alive, not to worst-case lengths;
* per-request **block tables** (host lists, shipped to the device as a
  small int32 array each step) map logical token positions to pool
  blocks; all pool writes happen in-graph via ``lax.dynamic_update_slice``
  so the decode executable is reused forever.

Block 0 is reserved as the **null block**: padded table entries point at
it, so in-graph writes for padding land somewhere harmless instead of
clobbering a live request's block. It is never handed out.

ISSUE 11 extends the allocator with **ref-counted block identity** so N
requests sharing a prompt prefix hold the SAME pool blocks:

* every allocated block carries a refcount; ``acquire`` increfs a block
  another request already filled, ``free`` decrefs — a block returns to
  circulation only at refcount 0 (eviction of a shared block waits for
  the last holder);
* a refcount-0 block whose content is registered in a :class:`PrefixCache`
  is not recycled immediately: it parks in an LRU *reusable* pool, still
  holding its K/V, so a later request with the same prefix can revive it.
  ``allocate`` reclaims reusable blocks (oldest first, dropping their
  hash entries) only after the free list runs dry;
* ``PrefixCache`` maps hash *chains* — ``sha1(parent_hash ‖ block's
  tokens)`` — to block ids, so block identity is positional content, not
  raw bytes: the same 16 tokens at a different prefix offset hash
  differently, exactly like vLLM's prefix tree flattened into a dict.

A partially-filled tail block is never registered (only FULL blocks enter
the hash index), so in-place writes always land in private blocks; the
copy-on-write helpers (``BlockAllocator.is_shared`` +
``PagedKVCache.copy_block``) guard the invariant anyway — a divergent
write to a block some other request can see must copy first, never
mutate.

ISSUE 14 adds **quantized pools** (``kv_dtype="int8"``): the K/V payload
is stored as int8 codes with a float32 abs-max scale per (block,
position, kv-head) row kept in sidecar scale pools the engine threads
through its compiled steps exactly like the payload pools. The scale
granularity is deliberately PER ROW (one scalar per written token per
head), not one scalar per block: a row's codes are then a pure function
of that row's values alone, so prefill (whole pages at once), decode
(one token at a time), eviction re-prefill and fleet redispatch replay
all quantize a given token identically — greedy decode stays
deterministic and bit-reproducible across every write path, which a
block-scalar scale (write-order-dependent rescaling) cannot guarantee.
Block identity, refcounts, prefix hashes and COW never touch payload
dtype, so sharing/eviction/speculation compose unchanged.

ISSUE 15 adds **page export/import** for disaggregated prefill/decode
serving: ``export_request_pages`` gathers one request's pool blocks
(codes AND scale rows for int8 pools) into host arrays, and
``import_request_pages`` writes such a payload into another pool's
blocks — the prefill→decode KV handoff. Because per-row quantization is
a pure function of the row, an imported page is byte-identical to the
page local prefill would have written, so the handoff preserves greedy
determinism by construction. ``pack_kv_pages``/``unpack_kv_pages``
serialize the payload for the transfer channel (the fleet frames the
bytes with CRCs; corruption is the CHANNEL's problem, detected there).

ISSUE 16 adds the **host-RAM tier** (:class:`HostKVTier`): when the
device free list dries up, cold pages — a preempted request's blocks, or
a refcount-0 registered block being reclaimed out of the reusable pool —
are snapshotted (:meth:`PagedKVCache.snapshot_request_pages`, a zero-copy
device-side gather) and drained to host numpy arrays on a transfer
thread (the ``DevicePrefetcher`` idiom: async D2H that never blocks the
step loop, dies once and degrades to synchronous conversion). The tier
is budget-bounded (``max_host_blocks``) with its own LRU, so host RAM is
a sized cache, not a leak. Revival is ``import_request_pages`` instead
of re-prefill — bit-exact by construction (PR 15) — and spilled prefix
blocks keep their chain hashes as tier keys, so
:meth:`PrefixCache.match_with_tier` extends a device chain walk into the
host tier and the scheduler revives host-resident prefixes on admission.

ISSUE 27 adds **per-layer pool geometry and a second kind of pages**. A
model hands the cache one :class:`KVLayerSpec` a layer (``kv_layout()``):
kv heads, the width of a K row and of a V row (they may differ, and a K
row may be stored wider than published so that its lane dim is a multiple
of 128), and a ``kind``. *Global* layers page the whole context through
the allocator and block table above. *Window* layers only ever need the
newest ``window`` tokens, so their pools are carved by a second allocator
and a request holds a **ring** of ``ceil(window / block) + 1`` pages in
them (:class:`WindowPages`): logical page ``p`` sits in ring slot ``p % R``,
and the page that falls out of the window goes back to the allocator as
the request advances, in prefill chunks and in decode. Everything that
assumes ONE layout (prefix sharing, the host tier, page export/import,
int8 pools, checksums, copy-on-write) refuses a cache that is not uniform,
at construction and by name, rather than corrupt.

ISSUE 31 adds *latent* layers: one row a token that all heads share, one
pool a layer and no V pool, paged through the global table.

ISSUE 33 adds **state that is not pages, and layers that keep nothing**. A
*state* layer (a state-space mixer) holds, a REQUEST and not a token, the
last few rows that enter its causal convolution and one recurrent state of
fixed size, in float32 whatever the model's dtype. The cache holds two
arrays a state layer over ``max_batch_size + 1`` slots, in the places a
paged layer's K and V pools take in the lists the step functions thread and
donate. A request's slot IS its decode slot: nothing is allocated, nothing
can run out, so the kind never preempts; the last slot is the **null
slot**, which every dead row of a decode step reads and writes, as a dead
row's K/V lands in the null block. A *none* layer (a feed-forward block
that is a layer of its own) keeps nothing: two empty arrays hold its place.
Neither is counted a token: ``bytes_per_token``, ``kv_live_byte_steps`` and
the scheduler's room are of the layers that page; ``state_bytes`` reports
the state beside them. So the five kinds are ``global`` and ``latent``
(pages of the global table), ``window`` (a ring of pages), ``state`` (a
slot) and ``none``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import queue
import threading
import time
import warnings
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ...observability import metrics as _obs_metrics
from ...utils import fault_injection as _fi
from . import integrity as _integrity
from .errors import KVIntegrityError

__all__ = ["BlockAllocator", "PagedKVCache", "PrefixCache", "HostKVTier",
           "PageSnapshot", "KV_QMAX", "KVLayerSpec", "WindowPages",
           "quantize_kv_rows", "kv_pool_bytes_per_block",
           "pack_kv_pages", "unpack_kv_pages"]

# KV tiering observability (ISSUE 16): spills/revives are counted per
# EVENT (one preempted request's page set, or one reclaimed prefix
# block); bytes counters carry the volume, the gauge tracks host-tier
# residency, and the histograms time the actual transfers (D2H
# materialization on spill, pool import on revive). Instance-labeled by
# engine, like every serving metric.
_M_SPILLS = _obs_metrics.counter(
    "serving_kv_spills_total",
    "KV page-spill events into the host tier (one per preempted request "
    "or per reclaimed prefix block)")
_M_REVIVES = _obs_metrics.counter(
    "serving_kv_revives_total",
    "KV revive events out of the host tier (import_request_pages instead "
    "of re-prefill: one per revived request or prefix block)")
_M_SPILL_BYTES = _obs_metrics.counter(
    "serving_kv_spill_bytes_total",
    "bytes moved device->host by KV tier spills (codes + scale sidecars "
    "for int8 pools)")
_M_REVIVE_BYTES = _obs_metrics.counter(
    "serving_kv_revive_bytes_total",
    "bytes moved host->device by KV tier revivals")
_M_HOST_EVICT = _obs_metrics.counter(
    "serving_kv_host_evictions_total",
    "entries LRU-dropped from the host tier to fit its block budget "
    "(the spilled content is recomputable; dropping costs a re-prefill, "
    "never correctness)")
_G_HOST_BLOCKS = _obs_metrics.gauge(
    "serving_kv_host_blocks",
    "KV blocks currently resident in the host-RAM tier")
_H_SPILL_MS = _obs_metrics.histogram(
    "serving_kv_spill_ms",
    "device->host materialization latency per spill event",
    buckets=_obs_metrics.DEFAULT_MS_BUCKETS)
_H_REVIVE_MS = _obs_metrics.histogram(
    "serving_kv_revive_ms",
    "host->device import latency per revive event",
    buckets=_obs_metrics.DEFAULT_MS_BUCKETS)

# symmetric int8: codes in [-127, 127], scale = absmax/127 per row.
# -128 is deliberately unused so the scheme stays symmetric (dequant is
# a single multiply, no zero point).
KV_QMAX = 127.0


def quantize_kv_rows(x):
    """Quantize K/V rows ``[..., Hkv, D]`` to int8 codes + per-row scales.

    Returns ``(codes int8 [..., Hkv, D], scales f32 [..., Hkv])`` with
    ``scale = max(|row|) / 127`` (floored at 1e-8 so an all-zero row
    dequantizes to exact zeros instead of NaN). Pure per-row function —
    the SAME row values always produce the SAME codes regardless of how
    many tokens share the block or which write path (prefill chunk,
    decode step, verify window, re-prefill) materializes them. That
    purity is the determinism contract the fleet's redispatch replay and
    the scheduler's eviction re-prefill rely on.
    """
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1) / KV_QMAX
    s = jnp.maximum(s, 1e-8)
    codes = jnp.clip(jnp.round(xf / s[..., None]), -KV_QMAX, KV_QMAX)
    return codes.astype(jnp.int8), s


def kv_pool_bytes_per_block(block_size, num_kv_heads, head_dim,
                            kv_dtype=None, base_dtype=None):
    """Bytes ONE pool block costs (K and V together, one layer),
    including the f32 scale sidecar rows for ``kv_dtype="int8"``. The
    bench's same-memory-budget capacity A/B and the engine's
    ``serving_kv_bytes_saved_total`` accounting both use this, so the
    claim and the telemetry can never disagree."""
    payload = block_size * num_kv_heads * head_dim
    if kv_dtype == "int8":
        return 2 * (payload + block_size * num_kv_heads * 4)
    itemsize = jnp.dtype(base_dtype or jnp.float32).itemsize
    return 2 * payload * itemsize


class BlockAllocator:
    """Ref-counted LIFO free-list over ``num_blocks`` pool blocks.

    Block 0 is the reserved null block (see module docstring) and is never
    allocated. ``allocate`` is all-or-nothing: asking for more blocks than
    are available returns ``None`` and takes nothing — the scheduler's
    signal to queue (or evict), never a partial grab to unwind. ``free``
    is all-or-nothing too: the whole id list is validated up front, so a
    bad id (double-free, foreign block, duplicate in one call) raises
    BEFORE any refcount moves and the allocator is never left
    half-mutated.
    """

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (one is the reserved null "
                             f"block), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # LIFO: recently-freed (cache-warm) blocks are reused first
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = {}                     # block id -> refcount (>= 1)
        # refcount-0 blocks still registered in a PrefixCache: content is
        # intact and revivable; reclaimed LRU-first when the free list is
        # empty. Insertion order = least recently released first.
        self._reusable = OrderedDict()
        # PrefixCache hooks (set by PrefixCache.__init__): ``on_reclaim``
        # is called with a block id when a reusable block is reclaimed for
        # a fresh allocation (its cached identity dies); ``cache_probe``
        # answers ``registered(block_id)`` so ``free`` knows which
        # refcount-0 blocks are worth parking instead of recycling
        self.on_reclaim = None
        self.cache_probe = None
        self.high_water = 0

    @property
    def _allocated(self):
        """Set view of live (refcount >= 1) blocks — kept for tests and
        invariant checks that predate refcounting."""
        return set(self._ref)

    @property
    def num_free(self):
        """Blocks available to ``allocate``: the free list plus reusable
        (refcount-0, cached-content) blocks that can be reclaimed."""
        return len(self._free) + len(self._reusable)

    def ref(self, block_id):
        """Current refcount of ``block_id`` (0 if not live)."""
        return self._ref.get(block_id, 0)

    def is_shared(self, block_id):
        """True when more than one holder references the block — an
        in-place write would be visible to another request (COW trigger)."""
        return self._ref.get(block_id, 0) > 1

    def allocate(self, n=1):
        """``n`` fresh private blocks (refcount 1), or ``None`` (and no
        state change) if fewer than ``n`` are available. Reusable cached
        blocks are reclaimed (oldest first) only after the free list runs
        dry — reclaiming drops their prefix-cache identity via
        ``on_reclaim``."""
        if n > self.num_free:
            return None
        ids, reclaimed = [], []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, _ = self._reusable.popitem(last=False)  # LRU reclaim
                reclaimed.append(b)
            self._ref[b] = 1
            ids.append(b)
        if reclaimed and self.on_reclaim is not None:
            # one notification for the whole wave: the ISSUE-16 spill
            # path turns each wave into ONE device gather + ONE queued
            # D2H, so reclaim cost is per-allocate, not per-block
            self.on_reclaim(reclaimed)
        self.high_water = max(self.high_water, len(self._ref))
        return ids

    def unpark(self, block_id):
        """Move a parked reusable block back to the plain free list —
        its cached identity was retracted (per-tenant share enforcement,
        ISSUE 17), so it is no longer worth reclaim bookkeeping. A block
        that is live or already free is left alone."""
        if block_id in self._reusable:
            del self._reusable[block_id]
            self._free.append(block_id)

    def acquire(self, ids):
        """Share existing blocks: incref each id, reviving reusable
        (refcount-0 cached) blocks. Raises on ids that are neither live
        nor reusable — validated up front, all-or-nothing."""
        for b in ids:
            if b not in self._ref and b not in self._reusable:
                raise ValueError(f"acquire of free/foreign block {b}")
        for b in ids:
            if b in self._ref:
                self._ref[b] += 1
            else:
                del self._reusable[b]
                self._ref[b] = 1
        self.high_water = max(self.high_water, len(self._ref))

    def free(self, ids):
        """Decref every id; a block reaching refcount 0 returns to the
        free list, or — when the attached :class:`PrefixCache` (via
        ``cache_probe``) says its content is registered — parks in the
        reusable pool instead, revivable by a later prefix match.

        All-or-nothing (ISSUE 11 satellite): the WHOLE list is validated
        before any mutation, so a duplicate id in one call or a foreign/
        double-freed block raises with the allocator untouched.
        """
        seen = set()
        for b in ids:
            if b in seen:
                raise ValueError(f"duplicate block {b} in one free() call")
            if b not in self._ref:
                raise ValueError(f"double-free or foreign block {b}")
            seen.add(b)
        probe = self.cache_probe
        for b in ids:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if probe is not None and probe.registered(b):
                    self._reusable[b] = None
                else:
                    self._free.append(b)


class PrefixCache:
    """Content-hashed block identity: hash chains -> pool block ids.

    A block's identity is ``sha1(parent_chain_hash ‖ its block_size
    tokens)`` — the chain makes identity positional (the same tokens
    after a different prefix are a different block), so a lookup walking
    chunks from position 0 finds exactly the blocks whose ENTIRE causal
    content matches. Only FULL blocks are ever registered: the partially
    filled tail of a prompt stays private (its content is still growing),
    which is what makes in-place decode writes safe without copies in the
    common path.
    """

    def __init__(self, allocator, block_size):
        self.allocator = allocator
        self.block_size = int(block_size)
        self._by_hash = {}      # chain hash (bytes) -> block id
        self._block_hash = {}   # block id -> chain hash
        # ISSUE 16: optional spill hook ``on_spill(pairs)`` taking a
        # batch of ``(block_id, chain_hash)`` pairs (set by the engine
        # when a HostKVTier is attached). Reclaiming reusable blocks out
        # of the device pool offers their content to the host tier
        # BEFORE the identities are forgotten — a reclaim becomes a
        # demotion, not a loss. A divergent-write ``forget`` never
        # spills: that content no longer matches its published hash.
        self.on_spill = None
        allocator.on_reclaim = self._reclaim
        allocator.cache_probe = self
        # per-tenant accounting (ISSUE 17): how many registered blocks
        # each tenant has published, oldest-first, plus optional shares.
        # A tenant over its share demotes ITS OWN oldest identities to
        # the host tier (and unparks the blocks) — it can grow the warm
        # set only up to its budget, never by evicting another tenant's
        # published blocks past theirs.
        self._block_tenant = {}     # block id -> tenant name
        self._tenant_lru = {}       # tenant -> OrderedDict[block id, None]
        self._tenant_share = {}     # tenant -> max registered blocks

    def __len__(self):
        return len(self._by_hash)

    def set_tenant_share(self, name, max_blocks):
        """Cap tenant ``name`` at ``max_blocks`` registered (published)
        blocks; ``None`` removes the cap."""
        if max_blocks is None:
            self._tenant_share.pop(str(name), None)
        else:
            if int(max_blocks) < 1:
                raise ValueError(
                    f"tenant prefix share must be >= 1, got {max_blocks}")
            self._tenant_share[str(name)] = int(max_blocks)

    def tenant_blocks(self, name):
        """Registered blocks currently attributed to tenant ``name``."""
        return len(self._tenant_lru.get(str(name), ()))

    def _tag(self, block_id, tenant):
        if tenant is None:
            return
        self._block_tenant[block_id] = tenant
        self._tenant_lru.setdefault(tenant, OrderedDict())[block_id] = None

    def _enforce_share(self, tenant):
        share = self._tenant_share.get(tenant)
        if share is None:
            return
        lru = self._tenant_lru.get(tenant)
        while lru and len(lru) > share:
            b = next(iter(lru))  # tenant's oldest published block
            h = self._block_hash.get(b)
            if self.on_spill is not None and h is not None:
                self.on_spill([(b, h)], [tenant])  # demote, don't lose
            self._forget(b)
            self.allocator.unpark(b)

    def registered(self, block_id):
        return block_id in self._block_hash

    def _chunk_hash(self, parent, chunk):
        return hashlib.sha1(
            parent + np.asarray(chunk, np.int64).tobytes()).digest()

    def match(self, tokens):
        """Longest chain of cached full blocks covering a PROPER prefix
        of ``tokens``; returns ``(block_ids, tokens_covered)``. The match
        is capped at ``len(tokens) - 1`` so admission always has at least
        one token left to prefill — the last position's logits must be
        computed to sample the first output token."""
        tokens = np.asarray(tokens)
        bs = self.block_size
        max_chunks = max((len(tokens) - 1) // bs, 0)
        blocks, parent = [], b""
        for i in range(max_chunks):
            h = self._chunk_hash(parent, tokens[i * bs:(i + 1) * bs])
            b = self._by_hash.get(h)
            if b is None:
                break
            blocks.append(b)
            parent = h
        return blocks, len(blocks) * bs

    def register(self, tokens, blocks, upto, tenant=None):
        """Publish the identity of every FULL block among ``blocks`` whose
        tokens (``tokens[:upto]``) are materialized in the pool. First
        writer wins: a chain hash already mapping to a (different) block
        keeps its mapping and the duplicate block simply stays private;
        a block already registered under another chain is never re-keyed.
        Newly published blocks are attributed to ``tenant`` (ISSUE 17);
        a tenant over its share demotes its own oldest identities.
        """
        tokens = np.asarray(tokens)
        bs = self.block_size
        n_chunks = min(int(upto) // bs, len(blocks))
        parent = b""
        tagged = False
        for i in range(n_chunks):
            h = self._chunk_hash(parent, tokens[i * bs:(i + 1) * bs])
            cur = self._by_hash.get(h)
            if cur is None and blocks[i] not in self._block_hash:
                self._by_hash[h] = blocks[i]
                self._block_hash[blocks[i]] = h
                self._tag(blocks[i], tenant)
                tagged = True
            parent = h
        if tagged and tenant is not None:
            self._enforce_share(tenant)

    def match_with_tier(self, tokens, tier):
        """:meth:`match`, extended into the host tier (ISSUE 16): after
        the device chain walk stops, keep hashing chunks and probing
        ``tier`` for host-resident continuations of the SAME chain.
        Returns ``(block_ids, device_covered, host_hashes)`` — the host
        hashes cover the chunks immediately after ``device_covered``;
        the caller allocates fresh blocks for them and revives their
        pages via ``import_request_pages``. The combined coverage obeys
        the same proper-prefix cap as :meth:`match`."""
        tokens = np.asarray(tokens)
        bs = self.block_size
        max_chunks = max((len(tokens) - 1) // bs, 0)
        blocks, parent = [], b""
        host = []
        i = 0
        while i < max_chunks:
            h = self._chunk_hash(parent, tokens[i * bs:(i + 1) * bs])
            b = self._by_hash.get(h)
            if b is None:
                break
            blocks.append(b)
            parent = h
            i += 1
        while tier is not None and i < max_chunks:
            h = self._chunk_hash(parent, tokens[i * bs:(i + 1) * bs])
            if not tier.has_prefix(h):
                break
            host.append(h)
            parent = h
            i += 1
        return blocks, len(blocks) * bs, host

    def adopt(self, block_id, chain_hash, tenant=None):
        """Publish a revived block under its KNOWN chain hash (host-tier
        or prefix-store revival: the pages just imported are
        byte-identical to what the chain's original writer produced, so
        the identity transfers with them — no token rehash needed).
        First writer wins, exactly like :meth:`register`."""
        if chain_hash in self._by_hash or block_id in self._block_hash:
            return
        self._by_hash[chain_hash] = block_id
        self._block_hash[block_id] = chain_hash
        self._tag(block_id, tenant)
        if tenant is not None:
            self._enforce_share(tenant)

    def registered_chains(self):
        """Snapshot of ``(chain_hash, block_id)`` pairs currently
        published — the prefix store serializes these (plus the host
        tier's entries) on save."""
        return list(self._by_hash.items())

    def invalidate(self):
        """Drop EVERY cached identity (``reload_weights`` with a
        different weight fingerprint: pool content no longer corresponds
        to any chain under the new model). Blocks parked in the
        allocator's reusable pool stay parked — with their hashes gone
        they recycle as plain free blocks and are never spilled."""
        self._by_hash.clear()
        self._block_hash.clear()
        self._block_tenant.clear()
        self._tenant_lru.clear()

    def forget(self, block_id):
        """Drop a block's cached identity (divergent write to a
        refcount-1 registered block — its content no longer matches the
        published hash)."""
        self._forget(block_id)

    def _reclaim(self, block_ids):
        """Allocator ``on_reclaim`` hook: a WAVE of reusable blocks is
        being handed to new owners. Offer their (still intact) content
        to the host tier in one batch — one device gather and one queued
        D2H for the whole wave — then forget the device identities."""
        if self.on_spill is not None:
            pairs = [(b, self._block_hash[b]) for b in block_ids
                     if b in self._block_hash]
            if pairs:
                tenants = [self._block_tenant.get(b) for b, _ in pairs]
                self.on_spill(pairs, tenants)
        for b in block_ids:
            self._forget(b)

    def _forget(self, block_id):
        h = self._block_hash.pop(block_id, None)
        if h is not None:
            self._by_hash.pop(h, None)
        t = self._block_tenant.pop(block_id, None)
        if t is not None:
            lru = self._tenant_lru.get(t)
            if lru is not None:
                lru.pop(block_id, None)


@dataclasses.dataclass(frozen=True)
class KVLayerSpec:
    """What one layer keeps in the cache, by ``kind``: ``"global"`` pages
    of K and V over the whole context, ``"window"`` a ring of such pages,
    ``"latent"`` pages of one row a token, ``"state"`` a slot of fixed size
    a request, ``"none"`` nothing.

    ``k_store`` is the width a K row is stored at (``k_dim`` unless padded
    up to the lanes); ``prefill`` is the form the layer's pools are held in
    (:meth:`pool_shape`) and so who may read them: ``"paged"`` pools
    ``[N, block, Hkv, D]`` (Llama's: a plan shards them over the kv heads,
    int8 codes keep scales ``[N, block, Hkv]``, and the page-by-page
    multi-query kernel reads them for the verify step and for a chunk over
    int8 codes), ``"linear"`` pools ``[N, block * Hkv, D]``. A prefill
    chunk over unquantized pools reads the request's pages laid out in a
    row through the chunk kernel in EITHER form (ISSUE 36:
    ``paged_chunk_attention`` gathers a 4-D pool's pages).

    A ``"latent"`` layer (compressed keys and values) caches ONE row of
    ``k_dim`` a token, shared by every query head, whose first ``v_dim``
    values are also what the softmax weighs: one pool a layer and no V pool.
    Its pages come from the global allocator and table, so to the scheduler
    they are global pages.

    A ``"state"`` layer (ISSUE 33) keeps, a request, ``conv_rows`` rows of
    ``k_dim`` (what enters its causal convolution next, in the model's
    dtype) and a recurrent state of ``num_kv_heads`` heads x ``v_dim`` x
    ``state_dim`` in float32: :meth:`state_shapes` says how the cache holds
    them; ``scan_block`` is the tokens a block of a prefill chunk's scan
    takes. With ``state_dim == 0`` (a short convolution) it keeps
    the tail alone: no recurrent state is held. A ``"none"`` layer states
    no width.

    A global ``"linear"`` layer whose heads are narrower than the lanes
    (``heads_a_lane_row`` > 1: K and V 64 wide, an even number of kv heads)
    holds ``pack`` kv heads side by side in a row of 128: a page is ``block
    * Hkv / pack`` rows, the bytes of ``[block, Hkv, D]`` with no lane
    padding, and the decode and chunk kernels read it in that form."""
    kind: str = "global"            # global | window | latent | state | none
    num_kv_heads: int = 1
    k_dim: int = 128
    v_dim: int = 128
    k_store: int = 0                # 0: as published
    window: int | None = None
    prefill: str = "paged"
    conv_rows: int = 0              # a state kind's convolution tail
    state_dim: int = 0              # a state kind's N
    scan_block: int = 128           # a state kind's block of a chunk's scan

    def __post_init__(self):
        if self.kind not in ("global", "window", "latent", "state", "none"):
            raise ValueError(f"unknown KV layer kind {self.kind!r}")
        if self.kind == "latent" and (
                self.num_kv_heads != 1 or self.prefill != "linear"
                or not 0 < self.v_dim <= self.k_dim):
            raise ValueError(
                "a latent layer caches one row a token (num_kv_heads 1), "
                "its first v_dim values the values, read in a row by a "
                "chunk (prefill='linear')")
        if (self.kind != "state" and self.state_dim > 0) or (
                self.kind == "state" and self.conv_rows < 1):
            raise ValueError(
                "a state kind, and only it, states the rows of its "
                "convolution tail (at least one) and the width of its "
                "recurrent state (0: none)")
        if (self.kind == "window") != (self.window is not None):
            raise ValueError("a window kind, and only it, states a window")
        if self.kind == "window" and self.prefill != "linear":
            raise ValueError("a window layer's chunk reads keys in a row")
        if not self.k_store:
            object.__setattr__(self, "k_store", self.k_dim)

    @property
    def paged(self):
        """Whether the layer keeps rows a token, in pages."""
        return self.kind in ("global", "window", "latent")

    def pool_shape(self, n, block_size, width):
        """The shape a pool of ``n`` pages is held in. A ``"paged"`` layer
        keeps ``[N, block, Hkv, D]``. A ``"linear"`` one keeps a page as
        the ``block * Hkv`` rows of ``D`` the decode kernel copies,
        ``[N, block * Hkv, D]`` (row = token * Hkv + head): with fewer kv
        heads than sublanes XLA tiles the 4-D form ``(Hkv, 128)``, and
        every scatter into it and every view of it as rows then copied
        the whole pool (ISSUE 27, the deviceless compile's HLO)."""
        if self.prefill == "paged":
            return (n, block_size, self.num_kv_heads, width)
        pack = self.heads_a_lane_row
        return (n, block_size * self.num_kv_heads // pack, width * pack)

    @property
    def heads_a_lane_row(self):
        """Heads that lie side by side in the lanes: a head ``v_dim`` wide
        fills ``v_dim`` of 128 lanes, so ``128 // v_dim`` of them share a
        row where the head count allows it. A state kind's recurrent state
        is held so; a global kind's pages are where K is as wide as V and
        the pool is held in rows (``prefill == "linear"``), and only at a
        width of 64, the one the kernels take in this form."""
        pack = 128 // self.v_dim if 0 < self.v_dim < 128 else 1
        if self.kind != "state" and not (
                self.kind == "global" and self.prefill == "linear"
                and self.k_dim == self.k_store == self.v_dim == 64):
            return 1
        return pack if pack > 0 and self.num_kv_heads % pack == 0 else 1

    def state_shapes(self, slots):
        """``(convolution tail, recurrent state)`` of ``slots`` slots. The
        tail's rows lie in a row, ``[slots, conv_rows * k_dim]``, oldest
        first (three rows of a ``[slots, 3, D]`` array would be padded to a
        tile of sixteen). The state lies TRANSPOSED, ``[slots, H / pack, N,
        pack * P]``: the state dim over the sublanes and ``pack`` heads
        (``heads_a_lane_row``) side by side in the lanes, so that a decode
        step's ``y = h C`` sums over sublanes and ``x`` lies as it comes. A
        kind that holds the tail alone (``state_dim == 0``) has no state:
        None in its place."""
        pack = self.heads_a_lane_row
        return ((slots, self.conv_rows * self.k_dim),
                (slots, self.num_kv_heads // pack, self.state_dim,
                 pack * self.v_dim) if self.state_dim else None)

    def state_bytes(self, itemsize=2):
        """What ONE request holds in this layer whatever its length: the
        convolution tail in the model's dtype and the state in float32 (0
        for a kind that pages or keeps nothing)."""
        if self.kind != "state":
            return 0
        return (self.conv_rows * self.k_dim * itemsize
                + self.num_kv_heads * self.v_dim * self.state_dim * 4)

    def bytes_per_token(self, itemsize=2):
        """K and V of one token in this layer, at the published widths (a
        latent row holds both; a state or a none kind holds nothing a
        token)."""
        if not self.paged:
            return 0
        if self.kind == "latent":
            return self.k_dim * itemsize
        return self.num_kv_heads * (self.k_dim + self.v_dim) * itemsize


def uniform_layout(config):
    """The layout of a model whose layers all cache alike (Llama)."""
    spec = KVLayerSpec("global", config.num_key_value_heads,
                       config.head_dim, config.head_dim)
    return [spec] * config.num_hidden_layers


def ring_pages(window, block_size):
    """Pages a window layer holds a request: the window can straddle
    ``ceil(window / block)`` page boundaries, plus the page being written."""
    return -(-int(window) // int(block_size)) + 1


class WindowPages:
    """The window kind's pages: a ring of ``R = ring_pages(window, block)``
    slots a request, over an allocator of its own. Slot ``p % R`` holds
    logical page ``p``; asking for a newer page in a slot sends the older
    one back to the allocator. The pool is sized so that every slot of the
    batch can hold a full ring (checked where the engine is built), so an
    allocation here never fails and never preempts."""

    def __init__(self, allocator, window, block_size):
        self.allocator = allocator
        self.window = int(window)
        self.block_size = int(block_size)
        self.ring = ring_pages(window, block_size)
        self.n_tail = self.ring - 1
        self._rings = {}            # rid -> (blocks [R], pages [R])
        self.released = 0           # pages sent back behind a request

    def _ring(self, rid):
        ring = self._rings.get(rid)
        if ring is None:
            ring = self._rings[rid] = ([0] * self.ring, [-1] * self.ring)
        return ring

    def held(self, rid):
        ring = self._rings.get(rid)
        return sum(1 for b in ring[0] if b) if ring else 0

    @property
    def blocks_in_use(self):
        return self.allocator.num_blocks - 1 - self.allocator.num_free

    def ensure(self, rid, lo_page, hi_page):
        """Hold pages ``lo_page..hi_page`` (at most a ring of them), each in
        its slot; whatever older page sat there is released."""
        blocks, pages = self._ring(rid)
        for p in range(max(lo_page, hi_page - self.ring + 1), hi_page + 1):
            s = p % self.ring
            if pages[s] == p:
                continue
            if blocks[s]:
                self.allocator.free([blocks[s]])
                self.released += 1
            got = self.allocator.allocate(1)
            if got is None:
                raise RuntimeError(
                    "window page pool exhausted: it is sized at "
                    "max_batch_size rings, so this is a leak")
            blocks[s], pages[s] = got[0], p

    def release(self, rid):
        """Everything the request holds goes back (finish, abort, evict)."""
        ring = self._rings.pop(rid, None)
        if ring is None:
            return
        held = [b for b in ring[0] if b]
        if held:
            self.allocator.free(held)

    def table_row(self, rid):
        """The request's ring as a table row ``[R]`` (0 = no page)."""
        ring = self._rings.get(rid)
        return list(ring[0]) if ring else [0] * self.ring

    def chunk_row(self, rid, start, upto, chunk_pages):
        """What a prefill chunk ``[start, upto)`` of ``chunk_pages`` pages
        needs (``ChunkAttnState``): the blocks of the ``n_tail`` pages
        before ``start`` as they are NOW, then — after the ring has turned —
        the blocks of the chunk's newest pages and the chunk page they start
        at. int32 ``[n_tail + n_w + 1]``."""
        bs = self.block_size
        blocks, pages = self._ring(rid)
        p0 = start // bs
        tail = []
        for p in range(p0 - self.n_tail, p0):
            s = p % self.ring
            tail.append(blocks[s] if p >= 0 and pages[s] == p else 0)
        n_w = min(self.ring, chunk_pages)
        last = (upto - 1 - start) // bs           # chunk page of the last token
        first = max(last - (n_w - 1), 0)
        self.ensure(rid, p0 + first, p0 + last)
        blocks, pages = self._ring(rid)
        write = [blocks[(p0 + j) % self.ring] if j <= last else 0
                 for j in range(first, first + n_w)]
        return np.asarray(tail + write + [first], np.int32)


class PagedKVCache:
    """Static per-layer K/V block pools + the allocator that carves them.

    ``k``/``v`` are lists (one per layer) of
    ``[num_blocks, block_size, num_kv_heads, head_dim]`` arrays. They are
    plain jax arrays deliberately: the engine threads them through its
    compiled step functions (donated on TPU) and rebinds the returned
    buffers, exactly like ``FusedTrainStep`` handles optimizer state.

    ``kv_dtype="int8"`` (ISSUE 14) stores the payload as int8 codes and
    adds per-layer ``k_scale``/``v_scale`` pools of shape
    ``[num_blocks, block_size, num_kv_heads]`` f32 — one abs-max scale
    per written row per head (see :func:`quantize_kv_rows` for why the
    granularity is per-row, not per-block-scalar). Scale pools are
    threaded through compiled steps exactly like the payload pools;
    ``kv_dtype=None`` keeps ``k_scale``/``v_scale`` as empty lists so
    the fp path's pytrees carry zero extra leaves.

    A layer that does not page (ISSUE 33) keeps its place in both lists:
    a ``"state"`` layer's convolution tails stand in ``k`` and its recurrent
    states (float32) in ``v``, each over ``state_slots = max_batch_size + 1``
    slots, the last the null slot; a ``"none"`` layer has an empty array in
    each. The step functions thread and donate them as they do the pools.
    """

    # ISSUE 20: when armed (``LLMEngine(kv_page_checksums=True)`` sets
    # it), every :meth:`PageSnapshot.materialize` — the single choke
    # point behind export_request_pages, host-tier spills and the
    # prefix-store save pass — seals the payload with per-block CRC32s
    # (``integrity.seal_pages``); read-back boundaries verify them.
    page_checksums = False

    def __init__(self, config, num_blocks, block_size, dtype=None,
                 allocator=None, kv_dtype=None, layout=None,
                 max_batch_size=None):
        if dtype is None:
            dtype = jnp.float32
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8'; got "
                f"{kv_dtype!r}")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        self.base_dtype = dtype
        self.layout = list(layout) if layout is not None \
            else uniform_layout(config)
        #: one geometry and one kind: what sharing, spill, export, int8 and
        #: copy-on-write assume
        self.uniform = (len(set(self.layout)) == 1
                        and self.layout[0].kind == "global"
                        and self.layout[0].k_store == self.layout[0].v_dim)
        if self.quantized:
            self._require_uniform("int8 KV pools (kv_dtype='int8')")
        windows = {sp.window for sp in self.layout if sp.kind == "window"}
        if len(windows) > 1:
            raise ValueError(f"one window size a cache; got {sorted(windows)}")
        self.window = None
        if windows:
            if not max_batch_size:
                raise ValueError("a window kind needs max_batch_size: its "
                                 "pool holds a ring for every slot")
            window = windows.pop()
            # every slot of the batch a full ring and a page of slack, and
            # the null page: an allocation there never fails, so the window
            # kind never preempts
            self.window_num_blocks = int(max_batch_size) * (
                ring_pages(window, self.block_size) + 1) + 1
            self.window = WindowPages(BlockAllocator(self.window_num_blocks),
                                      window, self.block_size)
        pool_dtype = jnp.int8 if self.quantized else dtype
        #: slots of a state kind: one a decode slot and the null slot, which
        #: is the last (a dead row of a decode step reads and writes it)
        self.state_slots = None
        if any(sp.kind == "state" for sp in self.layout):
            if not max_batch_size:
                raise ValueError("a state kind needs max_batch_size: it "
                                 "holds a slot for every decode slot")
            self.state_slots = int(max_batch_size) + 1

        def pool(sp, width):
            n = self.window_num_blocks if sp.kind == "window" \
                else self.num_blocks
            return jnp.zeros(sp.pool_shape(n, self.block_size, width),
                             pool_dtype)

        def held(sp, which):
            """What stands in the K list (0) or the V list (1) for ``sp``:
            a pool of pages, a state kind's convolution tails (model's
            dtype) or its recurrent states (float32), or an empty array that
            keeps the place (a latent layer's V, a none layer's both, the
            V of a state kind that holds a tail alone)."""
            shape = sp.state_shapes(self.state_slots)[which] \
                if sp.kind == "state" else None
            if shape:
                return jnp.zeros(shape, jnp.float32 if which else dtype)
            # a tail-only state kind's V, too
            if sp.kind in ("none", "state") or (which and sp.kind == "latent"):
                return jnp.zeros((0,), pool_dtype)
            return pool(sp, sp.v_dim if which else sp.k_store)

        self.k = [held(sp, 0) for sp in self.layout]
        self.v = [held(sp, 1) for sp in self.layout]
        if self.quantized:
            self.k_scale = [jnp.zeros(kp.shape[:-1], jnp.float32)
                            for kp in self.k]
            self.v_scale = [jnp.zeros(kp.shape[:-1], jnp.float32)
                            for kp in self.k]
        else:
            self.k_scale = []
            self.v_scale = []
        # a draft-model pool (speculative decoding) shares the target
        # pool's allocator: one block table indexes both pools
        self.allocator = (allocator if allocator is not None
                          else BlockAllocator(num_blocks))

    def _require_uniform(self, what):
        if not self.uniform:
            kinds = sorted({f"{sp.kind}: {sp.num_kv_heads} kv heads, K "
                            f"{sp.k_store} / V {sp.v_dim}"
                            for sp in self.layout})
            raise ValueError(
                f"{what} assumes one pool geometry and one kind of pages; "
                f"this cache has {kinds}")

    def published_bytes_per_token(self, kind, itemsize=2):
        """K and V bytes of one token over all layers whose pages are of
        ``kind`` (``"window"``: the rings; ``"global"``: the global table's,
        latent rows among them), at the published widths. A state is no
        bytes a token: ``state_bytes_per_request`` has it."""
        return sum(sp.bytes_per_token(itemsize) for sp in self.layout
                   if (sp.kind == "window") == (kind == "window"))

    def state_bytes_per_request(self, itemsize=2):
        """What one request holds in the state layers, whatever its length,
        at the published widths (0 without a state kind)."""
        return sum(sp.state_bytes(itemsize) for sp in self.layout)

    def bytes_saved_vs_unquantized(self, config):
        """Total pool bytes an int8 cache saves versus the SAME pool in
        the model's dtype (0 for an unquantized cache) — scale sidecars
        charged against the saving."""
        if not self.quantized:
            return 0
        fp = kv_pool_bytes_per_block(
            self.block_size, config.num_key_value_heads, config.head_dim,
            kv_dtype=None, base_dtype=self.base_dtype)
        q8 = kv_pool_bytes_per_block(
            self.block_size, config.num_key_value_heads, config.head_dim,
            kv_dtype="int8")
        return (fp - q8) * self.num_blocks * config.num_hidden_layers

    def blocks_for_tokens(self, n_tokens):
        """Blocks needed to hold ``n_tokens``."""
        return -(-int(n_tokens) // self.block_size)

    def table_array(self, block_lists, max_blocks):
        """Host block tables -> device int32 [len(block_lists), max_blocks],
        padded with the null block."""
        out = np.zeros((len(block_lists), max_blocks), np.int32)
        for i, blocks in enumerate(block_lists):
            out[i, :len(blocks)] = blocks
        return jax.device_put(out)

    def copy_block(self, src, dst):
        """Copy one pool block's K/V from ``src`` to ``dst`` across all
        layers (the COW move: the writer gets a private copy, the shared
        original is never mutated). Host-triggered and rare — this is NOT
        inside the compiled step. Quantized pools copy the scale rows
        too: codes without their scales are not a copy."""
        self._require_uniform("copy-on-write of a shared block")
        self.k = [kp.at[dst].set(kp[src]) for kp in self.k]
        self.v = [vp.at[dst].set(vp[src]) for vp in self.v]
        if self.quantized:
            self.k_scale = [s.at[dst].set(s[src]) for s in self.k_scale]
            self.v_scale = [s.at[dst].set(s[src]) for s in self.v_scale]

    # -- disaggregated prefill/decode page handoff (ISSUE 15) -----------
    def export_request_pages(self, blocks, covered):
        """Gather the pool content of ``blocks`` (one request's pages, in
        table order) into host arrays: ``{"k": [L, n, block, Hkv, D],
        "v": ..., covered, block_size, kv_dtype}``, plus
        ``k_scale``/``v_scale`` ``[L, n, block, Hkv]`` rows for int8
        pools (codes without their scales are not a page). ``covered``
        records how many leading tokens the pages actually hold — the
        tail block may be partial; its trailing rows are whatever the
        pool holds and are masked by context lengths on the other side,
        exactly as they are here."""
        return self.snapshot_request_pages(blocks, covered).materialize()

    def snapshot_request_pages(self, blocks, covered):
        """Device-side capture of ``blocks`` for the host tier (ISSUE
        16): the per-layer gathers are DISPATCHED now — against the pool
        arrays as they are at this instant, which jax's immutability
        makes safe no matter how soon the allocator hands the blocks to
        a new owner — but the D2H transfer is deferred to
        :meth:`PageSnapshot.materialize` (normally run on the tier's
        transfer thread). The materialized payload is exactly an
        :meth:`export_request_pages` dict."""
        self._require_uniform("page export (handoff, host tier, prefix store)")
        return PageSnapshot(self, blocks, covered)

    def validate_request_pages(self, pages):
        """Typed geometry validation of an import payload WITHOUT
        mutating anything: dtype/block-size match, payload shapes fit
        this pool, and — on quantized pools — the scale rows exist and
        fit too. The decode engine calls this at admission (before any
        blocks are allocated); :meth:`import_request_pages` calls it
        again before writing, so a bad payload can never leave the pool
        half-imported. Returns the number of payload blocks."""
        self._require_uniform("page import")
        if pages.get("kv_dtype") != self.kv_dtype:
            raise ValueError(
                f"imported pages carry kv_dtype={pages.get('kv_dtype')!r} "
                f"but this pool stores {self.kv_dtype!r}")
        if int(pages.get("block_size", -1)) != self.block_size:
            raise ValueError(
                f"imported pages use block_size={pages.get('block_size')} "
                f"but this pool uses {self.block_size}")
        k, v = pages["k"], pages["v"]
        want = (len(self.k),) + self.k[0].shape[1:]
        if k.shape[:1] + k.shape[2:] != want or k.shape != v.shape:
            raise ValueError(
                f"imported page shape {k.shape} does not fit this pool "
                f"(layers+block geometry {want})")
        n = k.shape[1]
        if self.quantized:
            swant = want[:-1]
            for nm in ("k_scale", "v_scale"):
                s = pages.get(nm)
                if s is None:
                    raise ValueError(
                        f"int8 pages are missing their {nm} rows — "
                        "codes without scales are not a page")
                if (s.shape[:1] + s.shape[2:] != swant
                        or s.shape[1] != n):
                    raise ValueError(
                        f"imported {nm} shape {s.shape} does not fit "
                        f"this pool (layers+block geometry {swant}, "
                        f"{n} payload blocks)")
        return n

    def import_request_pages(self, blocks, pages):
        """Write an :meth:`export_request_pages` payload into ``blocks``
        of THIS pool (host-triggered, like :meth:`copy_block` — not
        inside a compiled step). ``blocks`` may be longer than the
        payload (admission also allocates room for the next token);
        only the payload's blocks are written. Raises ``ValueError`` on
        any pool-geometry mismatch BEFORE any pool array moves —
        importing pages of the wrong shape/dtype would decode garbage
        silently, and a mid-write failure would be worse."""
        n = self.validate_request_pages(pages)
        if n > len(blocks):
            raise ValueError(
                f"payload holds {n} blocks but only {len(blocks)} were "
                "allocated for the import")
        k, v = pages["k"], pages["v"]
        idx = jnp.asarray(np.asarray(blocks[:n], np.int32))
        self.k = [kp.at[idx].set(jnp.asarray(k[i], kp.dtype))
                  for i, kp in enumerate(self.k)]
        self.v = [vp.at[idx].set(jnp.asarray(v[i], vp.dtype))
                  for i, vp in enumerate(self.v)]
        if self.quantized:
            ks, vs = pages["k_scale"], pages["v_scale"]
            self.k_scale = [s.at[idx].set(jnp.asarray(ks[i], s.dtype))
                            for i, s in enumerate(self.k_scale)]
            self.v_scale = [s.at[idx].set(jnp.asarray(vs[i], s.dtype))
                            for i, s in enumerate(self.v_scale)]


# One compiled gather for a whole spill: every pool array of a capture
# (all layers' k, v and — on int8 pools — scales) goes through a single
# jitted dispatch instead of one eager fancy-index per array. jit's own
# aval cache keys on (pool count, shapes, dtypes, index length), so the
# same callable serves every pool geometry; spilling under device-pressure
# is pure dispatch overhead and this turns ~8 slow eager gathers per
# spill into one fast-path call.
_POOL_GATHER = jax.jit(lambda pools, idx: [p[idx] for p in pools])


class PageSnapshot:
    """Lazily-materialized page capture (see
    :meth:`PagedKVCache.snapshot_request_pages`). ``materialize`` is
    idempotent and thread-safe: the transfer thread races the consumer
    only for who PAYS the D2H, never for what the payload contains."""

    def __init__(self, cache, blocks, covered):
        idx = np.asarray(blocks, np.int32)
        self.nblocks = len(blocks)
        self.covered = int(covered)
        # capture the arming flag NOW: the seal must reflect the policy
        # at snapshot time, not whenever the transfer thread gets around
        # to materializing
        self._seal = bool(cache.page_checksums)
        self._meta = {"covered": int(covered),
                      "block_size": cache.block_size,
                      "kv_dtype": cache.kv_dtype}
        # gathers dispatch against the CURRENT pool bindings; results are
        # device arrays the pool can no longer mutate
        groups = [("k", cache.k), ("v", cache.v)]
        if cache.quantized:
            groups += [("k_scale", cache.k_scale),
                       ("v_scale", cache.v_scale)]
        flat = _POOL_GATHER([p for _, g in groups for p in g],
                            jnp.asarray(idx))
        self._parts, off = {}, 0
        for name, g in groups:
            self._parts[name] = flat[off:off + len(g)]
            off += len(g)
        self._pages = None
        self._lock = threading.Lock()
        # set by the tier: called exactly once, under the snapshot lock,
        # with (nbytes, ms) when the D2H actually runs — whichever of the
        # transfer thread / a consumer gets there first
        self.on_materialized = None

    @property
    def ready(self):
        return self._pages is not None

    def materialize(self):
        """Host payload dict (``export_request_pages`` format); first
        caller pays the D2H and the spill byte/latency telemetry is
        recorded exactly once."""
        with self._lock:
            if self._pages is None:
                t0 = time.perf_counter()
                pages = dict(self._meta)
                for name, parts in self._parts.items():
                    pages[name] = np.stack(
                        [np.asarray(p) for p in parts])
                if self._seal:
                    _integrity.seal_pages(pages)
                nbytes = sum(a.nbytes for a in pages.values()
                             if isinstance(a, np.ndarray))
                self._pages = pages
                self._parts = None  # release device refs
                if self.on_materialized is not None:
                    self.on_materialized(
                        nbytes, (time.perf_counter() - t0) * 1e3)
            return self._pages

    def view(self, i):
        """Single-block view into this capture (batched prefix spill:
        one snapshot serves a whole reclaim wave; each chain hash keys a
        view of its own block)."""
        return _SnapshotView(self, i)


class _SnapshotView:
    """One block of a batched :class:`PageSnapshot` — same ``nblocks``/
    ``materialize`` surface the tier stores, backed by the shared parent
    capture (the wave pays one gather and one D2H, not one per block)."""

    def __init__(self, snap, i):
        self._snap = snap
        self._i = int(i)
        self.nblocks = 1
        self.covered = snap._meta["block_size"]

    def materialize(self):
        pages = self._snap.materialize()
        i = self._i
        # the CRC sidecar is per-block 1-D: slice it by block index, not
        # by the [layer, block, ...] payload axes
        out = {k: (v[i:i + 1] if k == "crc"
                   else v[:, i:i + 1] if isinstance(v, np.ndarray) else v)
               for k, v in pages.items()}
        out["covered"] = self.covered
        return out


class HostKVTier:
    """Bounded host-RAM tier over a :class:`PagedKVCache` (ISSUE 16).

    Two kinds of entries share one LRU under one block budget:

    * ``("req", rid)`` — a preempted request's full page set, spilled by
      the scheduler at eviction and revived (``import_request_pages``)
      on re-admission instead of re-prefilling;
    * ``("prefix", chain_hash)`` — a single refcount-0 registered block
      demoted when the allocator reclaimed it, keyed by the SAME chain
      hash it had on device so :meth:`PrefixCache.match_with_tier` can
      extend a chain walk into host RAM. Prefix-store boot entries land
      here too.

    ``max_host_blocks`` bounds total resident blocks; ``put`` evicts
    oldest entries to fit (spilled content is recomputable — dropping an
    entry costs a re-prefill, never correctness). D2H materialization
    runs on a transfer thread (``DevicePrefetcher`` idiom: dies once,
    warns once, degrades to synchronous conversion on access); every
    access path calls ``materialize()`` itself, so correctness never
    depends on the thread having run.
    """

    def __init__(self, cache, max_host_blocks, instance=None,
                 async_transfer=True):
        if max_host_blocks < 1:
            raise ValueError(
                f"max_host_blocks must be >= 1, got {max_host_blocks}")
        self.cache = cache
        self.max_host_blocks = int(max_host_blocks)
        self.instance = instance
        self._entries = OrderedDict()   # key -> PageSnapshot | dict
        self._blocks_used = 0
        self._tenant_of = {}            # key -> tenant name (tagged only)
        self._tenant_blocks = {}        # tenant -> resident block count
        self._tenant_share = {}         # tenant -> max resident blocks
        self._lock = threading.RLock()
        self._q: queue.Queue = queue.Queue()
        self._thread = None
        if async_transfer:
            self._thread = threading.Thread(
                target=self._worker, daemon=True,
                name=f"{instance or 'kv-tier'}-spill")
            self._thread.start()
        _G_HOST_BLOCKS.set(0, instance=self.instance)

    # -- transfer thread ------------------------------------------------
    def _worker(self):
        while True:
            snap = self._q.get()
            if snap is None:
                return
            try:
                snap.materialize()
            except BaseException as e:  # degrade: consumers materialize
                warnings.warn(
                    f"HostKVTier transfer thread died ({e!r}); degrading "
                    "to synchronous spill materialization", RuntimeWarning)
                return

    def close(self):
        if self._thread is not None:
            self._q.put(None)
            self._thread.join(timeout=2.0)
            self._thread = None
        with self._lock:
            self._entries.clear()
            self._blocks_used = 0
            self._tenant_of.clear()
            self._tenant_blocks.clear()
        _G_HOST_BLOCKS.set(0, instance=self.instance)

    # -- internals ------------------------------------------------------
    def _entry_blocks(self, entry):
        return (int(entry["k"].shape[1]) if isinstance(entry, dict)
                else entry.nblocks)

    def _gauge(self):
        _G_HOST_BLOCKS.set(self._blocks_used, instance=self.instance)

    def set_tenant_share(self, name, max_blocks):
        """Cap one tenant's RESIDENT host blocks (ISSUE 17). Over-share
        inserts evict that tenant's own oldest entries first, so a flood
        of spills from one tenant cannot push other tenants' warm pages
        out of the shared LRU. ``None`` removes the cap."""
        name = str(name)
        with self._lock:
            if max_blocks is None:
                self._tenant_share.pop(name, None)
                return
            if max_blocks < 1:
                raise ValueError(
                    f"tenant share must be >= 1 block, got {max_blocks}")
            self._tenant_share[name] = int(max_blocks)

    def _account(self, key, nblocks, tenant):
        self._blocks_used += nblocks
        if tenant is not None:
            self._tenant_of[key] = tenant
            self._tenant_blocks[tenant] = (
                self._tenant_blocks.get(tenant, 0) + nblocks)

    def _unaccount(self, key, entry):
        n = self._entry_blocks(entry)
        self._blocks_used -= n
        t = self._tenant_of.pop(key, None)
        if t is not None:
            left = self._tenant_blocks.get(t, 0) - n
            if left > 0:
                self._tenant_blocks[t] = left
            else:
                self._tenant_blocks.pop(t, None)

    def _put(self, key, entry, nblocks, tenant=None):
        """Insert under the budget, LRU-evicting other entries to fit.
        A tagged tenant over its share evicts ITS OWN oldest entries
        first before touching the shared LRU. Returns False (no state
        change) when the entry alone exceeds the whole budget or the
        tenant's share."""
        if nblocks > self.max_host_blocks:
            return False
        tenant = str(tenant) if tenant is not None else None
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._unaccount(key, old)
            share = (self._tenant_share.get(tenant)
                     if tenant is not None else None)
            if share is not None:
                if nblocks > share:
                    return False
                while (self._tenant_blocks.get(tenant, 0) + nblocks
                       > share):
                    victim_key = next(
                        (k for k in self._entries
                         if self._tenant_of.get(k) == tenant), None)
                    if victim_key is None:
                        break
                    victim = self._entries.pop(victim_key)
                    self._unaccount(victim_key, victim)
                    _M_HOST_EVICT.inc(instance=self.instance)
            while (self._blocks_used + nblocks > self.max_host_blocks
                   and self._entries):
                victim_key, victim = self._entries.popitem(last=False)
                self._unaccount(victim_key, victim)
                _M_HOST_EVICT.inc(instance=self.instance)
            self._entries[key] = entry
            self._account(key, nblocks, tenant)
            self._gauge()
        return True

    def _get(self, key, pop):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            if pop:
                self._entries.pop(key)
                self._unaccount(key, entry)
            else:
                self._entries.move_to_end(key)
            self._gauge()
        pages = entry if isinstance(entry, dict) else entry.materialize()
        # ISSUE 20 read-back boundary: a sealed payload (page checksums
        # armed when it was written, or loaded from the prefix store)
        # verifies before it can revive. Mismatch degrades EXACTLY like
        # an LRU drop — the entry is freed and the caller re-prefills;
        # a corrupt page is never served.
        try:
            _integrity.verify_pages(pages, instance=self.instance,
                                    key=key)
        except KVIntegrityError as e:
            warnings.warn(f"HostKVTier dropping corrupt entry: {e}",
                          RuntimeWarning)
            with self._lock:
                stale = self._entries.pop(key, None)
                if stale is not None:
                    self._unaccount(key, stale)
                    self._gauge()
            return None
        return pages

    def _spill(self, key, blocks, covered, tenant=None):
        """Shared spill path: fire the fault site (failure degrades to
        recompute-eviction — the caller just proceeds as if no tier were
        attached), snapshot, insert, queue the async D2H."""
        try:
            _fi.fire("serve.kv_spill")
        except Exception:
            return False
        snap = self.cache.snapshot_request_pages(blocks, covered)
        snap.on_materialized = lambda nbytes, ms: (
            _M_SPILL_BYTES.inc(nbytes, instance=self.instance),
            _H_SPILL_MS.observe(ms, instance=self.instance))
        if not self._put(key, snap, snap.nblocks, tenant=tenant):
            return False
        _M_SPILLS.inc(instance=self.instance)
        if self._thread is not None:
            self._q.put(snap)
        return True

    # -- preempted-request entries (scheduler-facing) -------------------
    def spill_request(self, rid, blocks, covered, tenant=None):
        """Spill one preempted request's pages under ``("req", rid)``;
        the caller frees the device blocks right after (the snapshot's
        gathers already dispatched)."""
        n = -(-int(covered) // self.cache.block_size)
        return self._spill(("req", int(rid)), list(blocks)[:n], covered,
                           tenant=tenant)

    def peek_request(self, rid):
        """Materialized payload for a spilled request (MRU-touched, NOT
        removed — removal happens at :meth:`drop_request` once admission
        actually succeeds), or None if the tier LRU dropped it."""
        return self._get(("req", int(rid)), pop=False)

    def drop_request(self, rid):
        with self._lock:
            key = ("req", int(rid))
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._unaccount(key, entry)
                self._gauge()

    # -- prefix-block entries -------------------------------------------
    def spill_blocks(self, pairs, tenants=None):
        """Demote a reclaim WAVE of registered blocks — ``(block_id,
        chain_hash)`` pairs — in one batch: one fault-site fire, one
        device gather, one queued D2H for the whole wave; each chain
        hash keys a single-block view of the shared capture. ``tenants``
        (parallel to ``pairs``, entries may be None) tags each demoted
        block for per-tenant share accounting. Wired as
        ``PrefixCache.on_spill``."""
        if not pairs:
            return
        try:
            _fi.fire("serve.kv_spill")
        except Exception:
            return
        blocks = [b for b, _ in pairs]
        snap = self.cache.snapshot_request_pages(
            blocks, len(blocks) * self.cache.block_size)
        snap.on_materialized = lambda nbytes, ms: (
            _M_SPILL_BYTES.inc(nbytes, instance=self.instance),
            _H_SPILL_MS.observe(ms, instance=self.instance))
        put_any = False
        for i, (_, h) in enumerate(pairs):
            tenant = tenants[i] if tenants is not None else None
            if self._put(("prefix", bytes(h)), snap.view(i), 1,
                         tenant=tenant):
                put_any = True
                _M_SPILLS.inc(instance=self.instance)
        if put_any and self._thread is not None:
            self._q.put(snap)

    def spill_block(self, block_id, chain_hash, tenant=None):
        """Demote one reclaimed registered block (its chain hash is the
        tier key); single-pair form of :meth:`spill_blocks`."""
        self.spill_blocks([(block_id, chain_hash)], [tenant])

    def has_prefix(self, chain_hash):
        with self._lock:
            key = ("prefix", bytes(chain_hash))
            if key not in self._entries:
                return False
            self._entries.move_to_end(key)
            return True

    def pop_prefix(self, chain_hash):
        """Materialized single-block payload for a host-resident chain
        link (removed: the block is being revived into the device pool,
        where it is re-registered under the same hash)."""
        return self._get(("prefix", bytes(chain_hash)), pop=True)

    def put_prefix_payload(self, chain_hash, pages, tenant=None):
        """Insert an already-materialized single-block payload (prefix
        store boot path)."""
        return self._put(("prefix", bytes(chain_hash)), pages,
                         int(pages["k"].shape[1]), tenant=tenant)

    def prefix_items(self):
        """Materialized ``(chain_hash, payload)`` pairs currently
        resident (for the prefix store's save pass; entries stay put)."""
        with self._lock:
            keys = [k for k in self._entries if k[0] == "prefix"]
        out = []
        for key in keys:
            pages = self._get(key, pop=False)
            if pages is not None:
                out.append((key[1], pages))
        return out

    def drop_prefixes(self):
        """Drop every prefix entry (weight fingerprint changed: host
        content no longer matches any chain under the new weights)."""
        with self._lock:
            for key in [k for k in self._entries if k[0] == "prefix"]:
                entry = self._entries.pop(key)
                self._unaccount(key, entry)
            self._gauge()

    @property
    def host_blocks_in_use(self):
        with self._lock:
            return self._blocks_used

    def tenant_blocks_in_use(self, name):
        """Resident host blocks currently accounted to one tenant."""
        with self._lock:
            return self._tenant_blocks.get(str(name), 0)

    def __len__(self):
        with self._lock:
            return len(self._entries)


def pack_kv_pages(pages):
    """Serialize an ``export_request_pages`` payload to bytes (npz,
    pickle-free) for the fleet's CRC-framed transfer channel."""
    buf = io.BytesIO()
    arrays = {k: v for k, v in pages.items()
              if isinstance(v, np.ndarray)}
    arrays["covered"] = np.int64(pages["covered"])
    arrays["block_size"] = np.int64(pages["block_size"])
    arrays["kv_dtype"] = np.frombuffer(
        (pages["kv_dtype"] or "").encode(), np.uint8)
    np.savez(buf, **arrays)
    return buf.getvalue()


def unpack_kv_pages(data):
    """Inverse of :func:`pack_kv_pages`. Raises ``ValueError`` on a
    payload that does not parse as the page format — the caller treats
    that as a corrupt transfer (the CRC framing should have caught it
    first)."""
    try:
        with np.load(io.BytesIO(data), allow_pickle=False) as z:
            out = {k: z[k] for k in z.files}
    except Exception as e:
        raise ValueError(f"undecodable KV page payload: {e}") from e
    for key in ("covered", "block_size", "kv_dtype", "k", "v"):
        if key not in out:
            raise ValueError(f"KV page payload missing field {key!r}")
    out["covered"] = int(out["covered"])
    out["block_size"] = int(out["block_size"])
    dt = bytes(out["kv_dtype"]).decode() or None
    out["kv_dtype"] = dt
    if dt == "int8":
        for key in ("k_scale", "v_scale"):
            if key not in out:
                raise ValueError(
                    f"int8 KV page payload missing field {key!r} — "
                    "codes without scales are not a page")
    return out
