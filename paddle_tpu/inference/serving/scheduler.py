"""Continuous batching scheduler (ISSUE 7 tentpole, part c; prefix-aware
admission + chunked prefill added by ISSUE 11).

Token-granularity admission into a fixed set of decode slots:

* a fixed ``max_batch_size`` of decode slots so the decode graph compiles
  ONCE — a finished request's slot is refilled by the next waiting request
  at the very next step (continuous batching), never by re-batching into a
  new shape;
* **prefill/decode split with chunking**: prompts run through compiled
  chunk-prefill graphs (block-aligned chunks, one graph per chunk-length
  bucket — the PR-1 shape-bucket discipline); ``prefill_work`` hands out
  at most ``max_prefill_tokens_per_step`` NEW prompt tokens per engine
  step, so a 2k-token prompt is interleaved with decode steps instead of
  monopolizing them — decode inter-token latency is bounded by the chunk
  budget, not the longest queued prompt;
* **prefix-aware admission**: when a :class:`~.kv_cache.PrefixCache` is
  attached, admission matches the request's tokens against the hash-chain
  index and charges the allocator only for the UNSHARED tail — matched
  blocks are ``acquire``\\ d (ref-counted), ``num_cached`` starts past
  them, and the engine prefills only the remainder;
* **copy-on-write guard**: before decode/verify writes, any block in the
  write window that another request can see (refcount > 1) is replaced by
  a private copy (the device-side page copy is queued on ``pending_cow``
  for the engine to execute); a refcount-1 block that is still registered
  in the prefix index merely retracts its published identity. By
  construction only FULL blocks are shared, so the common path never
  copies — the guard enforces the invariant rather than paying for it;
* **graceful degradation**: a request that cannot get blocks stays queued
  (FIFO) — the engine never crashes on pool exhaustion. If a RUNNING
  request cannot grow by one block, the scheduler evicts the
  most-recently-admitted running request (its blocks free immediately, it
  re-queues at the FRONT and will re-prefill from its full
  prompt+generated prefix later — greedy decode makes the re-derived
  tokens identical), mirroring vLLM's recompute preemption;
* blocks free the moment a request finishes (EOS or max_new_tokens) —
  under prefix sharing "free" means decref: a shared block is reclaimed
  only when its LAST holder releases it.

``version`` counts every block-table mutation (admission, growth,
eviction, finish, COW, trim): the engine caches the device block-table
array against it, so steady-state decode re-uploads nothing (ISSUE 11
satellite).

**Multi-tenant QoS (ISSUE 17).** Requests carry a ``tenant=`` identity
and a ``tier`` (``latency`` | ``batch``). Once any tenant is configured
(:meth:`Scheduler.configure_tenant`) or non-default traffic is queued,
admission switches from strict FIFO to weighted-fair queuing: the
latency tier strictly outranks the batch tier, and within a tier the
backlogged tenant with the lowest *virtual time* (served tokens /
weight) admits next — its own requests still in FIFO order, so each
request's token outputs stay bit-identical to an undisturbed run (QoS
moves *when* work runs, never *which* tokens). Per-tenant token-rate
quotas (:class:`TenantQuota`, the launcher's ``RestartBudget`` leaky
bucket over served tokens) DEFER an over-quota tenant's admissions
instead of shedding them. Batch-tier requests *yield* decode slots
under latency pressure: they are preempted through the normal eviction
path — which spills decode-ready pages to the ISSUE-16 host tier, so
revival is a page import, not a re-prefill — and re-admit when the
pressure drops. Pure-default traffic never touches any of this: the
FIFO admission order of PR 7 is preserved exactly.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque

import numpy as np

from ...observability import metrics as _obs_metrics

__all__ = ["SamplingParams", "Request", "Scheduler", "TenantQuota",
           "TIER_LATENCY", "TIER_BATCH"]

# engine-owned admission/eviction counters (ISSUE 10 satellite): the
# registry — labeled by the owning engine/scheduler instance — is the
# authoritative store; ``Scheduler.stats`` is a thin backward-compatible
# dict view over it, and bench_serving reads the registry instead of
# recomputing from private fields.
_M_ADMITTED = _obs_metrics.counter(
    "serving_requests_admitted_total", "requests admitted to decode slots")
_M_EVICTIONS = _obs_metrics.counter(
    "serving_evictions_total",
    "recompute-preemption evictions under pool pressure")
_M_FINISHED = _obs_metrics.counter(
    "serving_requests_finished_total", "requests finished (EOS or length)")
_M_QUEUED_EXH = _obs_metrics.counter(
    "serving_queued_on_exhaustion_total",
    "admissions deferred because the block pool was exhausted")
_M_PREFIX_REUSED = _obs_metrics.counter(
    "serving_prefix_blocks_reused_total",
    "pool blocks admitted from the prefix cache instead of fresh prefill")
_M_COW = _obs_metrics.counter(
    "serving_cow_copies_total",
    "copy-on-write block copies (divergent write to a shared block)")
# multi-tenant QoS (ISSUE 17)
_M_THROTTLED = _obs_metrics.counter(
    "serving_quota_throttled_total",
    "admission passes that deferred every waiting tenant on its token-"
    "rate quota (deferred, never shed)")
_M_BATCH_YIELD = _obs_metrics.counter(
    "serving_batch_yields_total",
    "batch-tier requests preempted (spilled to the host tier when "
    "decode-ready) so latency-tier work could take their slot")
_M_TENANT_TOKENS = _obs_metrics.counter(
    "serving_tenant_tokens_total",
    "tokens served per tenant (prefill chunks + decode emissions); the "
    "tenant label is bounded to configured tenant names plus 'default'")

WAITING, RUNNING, FINISHED = "waiting", "running", "finished"
TIER_LATENCY, TIER_BATCH = "latency", "batch"


class TenantQuota:
    """Per-tenant token-rate quota: a rolling-window leaky bucket over
    SERVED tokens, mirroring the launcher's ``RestartBudget`` (events
    pruned past the window, injectable clock so tests never sleep).

    ``rate_tokens_per_s * window_s`` tokens may be served per rolling
    ``window_s`` window. The scheduler charges tokens as they are served
    (prefill chunks and decode emissions) and gates *admission* on the
    bucket: an over-quota tenant's waiting requests are deferred — never
    shed — until enough history ages out. One in-flight request may
    overshoot the limit; throttling mid-decode would hold a decode slot
    idle, the one thing a fixed-slot engine can never afford.
    :meth:`retry_after` estimates the wait, the machine-readable backoff
    hint the router's typed quota rejection carries (ISSUE 17 satellite).
    """

    def __init__(self, rate_tokens_per_s, window_s=1.0,
                 clock=time.monotonic):
        self.rate = float(rate_tokens_per_s)
        if self.rate <= 0:
            raise ValueError(
                f"rate_tokens_per_s must be > 0, got {rate_tokens_per_s}")
        self.window_s = float(window_s)
        self.limit = self.rate * self.window_s
        self._clock = clock
        self._events: deque[tuple[float, float]] = deque()
        self._used = 0.0

    def _prune(self, now):
        ev = self._events
        while ev and now - ev[0][0] > self.window_s:
            self._used -= ev.popleft()[1]

    @property
    def used(self):
        """Tokens served inside the current rolling window."""
        self._prune(self._clock())
        return self._used

    def admissible(self):
        return self.used < self.limit

    def note(self, n):
        """Charge ``n`` served tokens to the window."""
        now = self._clock()
        self._prune(now)
        self._events.append((now, float(n)))
        self._used += float(n)

    def retry_after(self):
        """Seconds until the bucket re-admits (0.0 while admissible)."""
        now = self._clock()
        self._prune(now)
        if self._used < self.limit:
            return 0.0
        over = self._used - self.limit
        expired = 0.0
        for t, n in self._events:
            expired += n
            if expired > over:
                return max(0.0, t + self.window_s - now)
        return self.window_s


class _TenantState:
    """Scheduler-side per-tenant accounting: the WFQ virtual time plus
    the optional rate quota. ``configured`` marks tenants registered via
    ``configure_tenant`` — only their names appear as metric label
    values (the cardinality bound); ad-hoc tenant names are served under
    default weight and labeled ``default``."""

    __slots__ = ("name", "weight", "quota", "served_tokens", "vtime",
                 "configured")

    def __init__(self, name, weight=1.0, quota=None, vtime=0.0):
        self.name = str(name)
        self.weight = float(weight)
        self.quota = quota
        self.served_tokens = 0
        self.vtime = float(vtime)
        self.configured = False


@dataclasses.dataclass
class SamplingParams:
    max_new_tokens: int = 32
    eos_token_id: int | None = None
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int | None = None
    top_p: float | None = None
    seed: int | None = None


class Request:
    """One in-flight generation request."""

    _ids = itertools.count(1)

    def __init__(self, prompt_ids, sampling: SamplingParams | None = None,
                 rid=None, deadline=None, tenant=None, tier=None):
        self.rid = rid if rid is not None else next(Request._ids)
        self.prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.sampling = sampling or SamplingParams()
        # multi-tenant QoS (ISSUE 17): who this request bills to and how
        # urgent it is. ``latency`` requests hold their decode slots;
        # ``batch`` requests admit behind latency work and yield their
        # slots under pressure (spill to the host tier, revive later).
        self.tenant = str(tenant) if tenant else "default"
        tier = tier or TIER_LATENCY
        if tier not in (TIER_LATENCY, TIER_BATCH):
            raise ValueError(f"unknown tier {tier!r}; expected "
                             f"{TIER_LATENCY!r} or {TIER_BATCH!r}")
        self.tier = tier
        # absolute wall-clock deadline (time.time() seconds, ISSUE 12):
        # the engine checks it at admission and at every step; expiry
        # aborts the request with a typed RequestTimeoutError finish
        self.deadline = float(deadline) if deadline is not None else None
        # set by Scheduler.abort: "timeout" / "cancelled" — overrides the
        # eos/length finish reasons
        self.abort_reason = None
        self.state = WAITING
        # observability timestamps (perf_counter_ns; host clocks only):
        # queue-entry time for the queued->running span, first/last token
        # times for TTFT / inter-token latency, decode-phase start
        self.t_queue_start = time.perf_counter_ns()
        self.t_submit = None
        self.t_first_token = None
        self.t_last_token = None
        self.t_decode_start = None
        self.output_tokens: list[int] = []
        self.blocks: list[int] = []       # pool block ids, in order
        self.num_cached = 0               # tokens materialized in the pool
        # chunked prefill: tokens the CURRENT admission must materialize
        # before the request is decode-ready; ``prefilling`` is True from
        # admission until the final chunk's logits were sampled
        self.prefill_upto = 0
        self.prefilling = False
        # speculative decoding: tokens materialized in the DRAFT pool
        self.draft_cached = 0
        # disaggregated handoff (ISSUE 15): pages computed by a prefill
        # worker, imported at admission instead of prefilling. Cleared
        # after the one-time import — an eviction re-prefills normally.
        self.preloaded = None
        # KV tiering (ISSUE 16): non-None while this request's pages sit
        # in the host tier (set at spill-eviction, keyed by rid);
        # ``revived_from_tier`` marks an admission whose ``preloaded``
        # payload came back FROM the tier, so the engine can count the
        # revive (and its bytes/latency) separately from fleet handoffs.
        self.spill_key = None
        self.revived_from_tier = False
        self.admit_seq = -1               # admission order (eviction policy)
        self.evictions = 0
        # last-position logits row, stashed by the engine only when it was
        # built with ``capture_logits=True`` (ISSUE 18: the copy is a [V]
        # f32 D2H per emission, so it is opt-in); None otherwise
        self.last_logits = None
        # what the model's layers kept for the host (``state.keep``) beside
        # those rows, a name a list of ``[layers, tokens, ...]`` arrays in
        # the order the engine computed this request's positions (a chunk,
        # ..., a token a decode step); filled under ``capture_logits`` only
        self.kept = {}
        self._rng = (np.random.RandomState(self.sampling.seed)
                     if self.sampling.do_sample else None)

    @property
    def tokens(self):
        """Prompt + generated so far (the re-prefill prefix on eviction)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.output_tokens, np.int32)])

    @property
    def num_tokens(self):
        """O(1) token count — ``.tokens`` concatenates, so hot scheduler
        loops must not call it just to measure."""
        return len(self.prompt) + len(self.output_tokens)

    @property
    def last_token(self):
        return (self.output_tokens[-1] if self.output_tokens
                else int(self.prompt[-1]))

    @property
    def finished(self):
        return self.state == FINISHED

    def finish_reason(self):
        if self.state != FINISHED:
            return None
        if self.abort_reason is not None:
            return self.abort_reason
        s = self.sampling
        if (s.eos_token_id is not None and self.output_tokens
                and self.output_tokens[-1] == s.eos_token_id):
            return "eos"
        return "length"

    def should_finish(self):
        s = self.sampling
        if len(self.output_tokens) >= s.max_new_tokens:
            return True
        return (s.eos_token_id is not None and self.output_tokens
                and self.output_tokens[-1] == s.eos_token_id)


class Scheduler:
    """Slots + FIFO wait queue over a :class:`BlockAllocator`.

    ``instance`` names this scheduler's registry label (the owning
    ``LLMEngine`` passes its own name, so every serving counter of one
    engine shares one label); standalone schedulers get an auto name.
    ``prefix_cache`` (a :class:`~.kv_cache.PrefixCache`) arms prefix-aware
    admission; ``None`` keeps the PR-7 charge-everything behavior.
    """

    _ids = itertools.count(1)

    def __init__(self, allocator, block_size, max_batch_size,
                 max_prefills_per_step=1, instance=None, prefix_cache=None,
                 kv_tier=None, window_pages=None):
        self.allocator = allocator
        # the window kind's pages (ISSUE 27, a kv_cache.WindowPages): a
        # request's ring goes back with its blocks on finish, abort, evict
        self.window_pages = window_pages
        self.block_size = int(block_size)
        self.slots: list[Request | None] = [None] * int(max_batch_size)
        self.waiting: deque[Request] = deque()
        self.max_prefills_per_step = int(max_prefills_per_step)
        self._admit_seq = itertools.count()
        self.instance = instance or f"scheduler#{next(Scheduler._ids)}"
        self.prefix_cache = prefix_cache
        # host-RAM tier (ISSUE 16, a kv_cache.HostKVTier): eviction spills
        # decode-ready requests' pages instead of dropping them, admission
        # revives spilled requests / host-resident prefix chains by page
        # import instead of re-prefill. None keeps recompute preemption.
        self.kv_tier = kv_tier
        # (req, block_id, chain_hash) host-prefix revivals the engine must
        # import+adopt before this step's prefill work (drained like
        # pending_cow)
        self.pending_revive: list[tuple] = []
        # spill revivals that missed: entry LRU-dropped or freed by the
        # tier's read-back integrity check (ISSUE 20) — each one is a
        # silent degrade to re-prefill, worth seeing when it spikes
        self.revive_misses = 0
        # block-table mutation counter: the engine invalidates its cached
        # device table array on change, so steady-state decode does ZERO
        # table H2D (ISSUE 11 satellite)
        self.version = 0
        # (src, dst) device page copies the engine must run before the
        # next pool write — queued by the COW guard, drained by step()
        self.pending_cow: list[tuple[int, int]] = []
        # multi-tenant QoS (ISSUE 17): per-tenant WFQ/quota state, lazily
        # created per tenant name. The weighted-fair admission path arms
        # itself only once a tenant is configured or non-default traffic
        # is queued — pure-default traffic keeps the exact FIFO order.
        self.tenants: dict[str, _TenantState] = {}
        self._qos_configured = False
        # pre-touch the series so stats reads zeros before any event
        for m in (_M_ADMITTED, _M_EVICTIONS, _M_FINISHED, _M_QUEUED_EXH,
                  _M_PREFIX_REUSED, _M_COW, _M_THROTTLED, _M_BATCH_YIELD):
            m.inc(0, instance=self.instance)
        _M_TENANT_TOKENS.inc(0, instance=self.instance, tenant="default")

    @property
    def stats(self):
        """Backward-compatible dict view over the registry counters."""
        inst = self.instance
        return {
            "admitted": int(_M_ADMITTED.value(instance=inst)),
            "evictions": int(_M_EVICTIONS.value(instance=inst)),
            "finished": int(_M_FINISHED.value(instance=inst)),
            "queued_on_exhaustion": int(_M_QUEUED_EXH.value(instance=inst)),
            "prefix_blocks_reused": int(
                _M_PREFIX_REUSED.value(instance=inst)),
            "cow_copies": int(_M_COW.value(instance=inst)),
            "quota_throttled": int(_M_THROTTLED.value(instance=inst)),
            "batch_yields": int(_M_BATCH_YIELD.value(instance=inst)),
            "revive_misses": self.revive_misses,
        }

    # -- multi-tenant QoS (ISSUE 17) -------------------------------------
    def configure_tenant(self, name, *, weight=1.0, rate_tokens_per_s=None,
                         window_s=1.0, clock=time.monotonic):
        """Register (or refresh) tenant ``name``: its weighted-fair
        ``weight`` — the share of admission it gets while backlogged
        against other tenants — and an optional :class:`TenantQuota`
        token-rate quota. The first configured tenant arms the QoS
        admission path; until then admission is plain FIFO."""
        if float(weight) <= 0:
            raise ValueError(f"tenant weight must be > 0, got {weight}")
        st = self._tenant(name)
        st.weight = float(weight)
        st.quota = (TenantQuota(rate_tokens_per_s, window_s, clock=clock)
                    if rate_tokens_per_s else None)
        st.configured = True
        self._qos_configured = True
        return st

    def _tenant(self, name):
        st = self.tenants.get(name)
        if st is None:
            # a tenant joining late starts at the LOWEST live virtual
            # time, not 0 — otherwise it would monopolize admission
            # until it "caught up" with tenants that served all along
            vt = min((s.vtime for s in self.tenants.values()), default=0.0)
            st = self.tenants[name] = _TenantState(name, vtime=vt)
        return st

    def _qos_active(self):
        return self._qos_configured or any(
            r.tier != TIER_LATENCY or r.tenant != "default"
            for r in self.waiting)

    def note_served(self, req, n):
        """Charge ``n`` served tokens (a prefill chunk or a decode
        emission) to the request's tenant: advances its WFQ virtual time
        by ``n / weight``, feeds its rate quota, and the per-tenant
        token counter. Called by the engine on the serving hot path —
        host-side arithmetic only."""
        if n <= 0:
            return
        st = self._tenant(req.tenant)
        st.served_tokens += int(n)
        st.vtime += n / st.weight
        if st.quota is not None:
            st.quota.note(n)
        _M_TENANT_TOKENS.inc(
            n, instance=self.instance,
            tenant=st.name if st.configured else "default")

    def _admissible(self, st):
        return st.quota is None or st.quota.admissible()

    def _next_admission(self):
        """QoS admission choice: the ``waiting`` position to admit next,
        or ``None`` when every waiting request's tenant is quota-
        deferred. The latency tier strictly outranks batch; within a
        tier, the tenant with the lowest virtual time wins and its
        EARLIEST queued request goes — per-tenant order stays FIFO, so
        a tenant's own requests admit in submission order regardless of
        what the other tenants do. Over-quota tenants are skipped
        (deferred, never shed)."""
        throttled = False
        for tier in (TIER_LATENCY, TIER_BATCH):
            best = None
            seen = set()
            for pos, req in enumerate(self.waiting):
                if req.tier != tier or req.tenant in seen:
                    continue
                seen.add(req.tenant)
                st = self._tenant(req.tenant)
                if not self._admissible(st):
                    throttled = True
                    continue
                if best is None or (st.vtime, pos) < best:
                    best = (st.vtime, pos)
            if best is not None:
                return best[1]
        if throttled:
            _M_THROTTLED.inc(instance=self.instance)
        return None

    def _yield_batch_slot(self):
        """Batch-tier yield (ISSUE 17): the slots are full and a
        latency-tier request is waiting admissibly — preempt the most
        recently admitted batch-tier running request through the normal
        eviction path (which spills decode-ready pages to the host
        tier, so revival is a page import, not a re-prefill). Returns
        True when a slot was freed."""
        wants_latency = any(
            r.tier == TIER_LATENCY and self._admissible(self._tenant(
                r.tenant))
            for r in self.waiting)
        if not wants_latency:
            return False
        batch = [r for r in self.running if r.tier == TIER_BATCH]
        if not batch:
            return False
        # prefer decode-ready victims: their pages spill (mid-prefill
        # pages are incomplete and degrade to recompute preemption)
        ready = [r for r in batch if not r.prefilling]
        victim = max(ready or batch, key=lambda r: r.admit_seq)
        self._evict(victim)
        _M_BATCH_YIELD.inc(instance=self.instance)
        return True

    # -- queries ---------------------------------------------------------
    @property
    def running(self):
        return [r for r in self.slots if r is not None]

    def has_work(self):
        return bool(self.waiting) or any(self.slots)

    def _free_slot(self):
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    # -- admission (prefill picks) --------------------------------------
    def pick_prefills(self):
        """Waiting requests to admit THIS step: pops up to
        ``max_prefills_per_step`` requests that fit (a free slot + blocks
        for prompt-and-first-token, charging only blocks the prefix cache
        cannot supply). A chosen request that does not fit stays queued —
        no overtaking within the step — and the engine simply decodes
        with what is running. Default traffic picks the FIFO head; with
        QoS active the weighted-fair ``_next_admission`` chooses, and a
        full slot set may first make room by preempting batch-tier work
        (``_yield_batch_slot``)."""
        picked = []
        while len(picked) < self.max_prefills_per_step and self.waiting:
            qos = self._qos_active()
            if self._free_slot() is None:
                # batch-tier yield (ISSUE 17): under latency pressure a
                # full slot set preempts batch work to the host tier
                # instead of queueing latency requests behind it
                if not (qos and self._yield_batch_slot()):
                    break
            pos = self._next_admission() if qos else 0
            if pos is None:
                break  # every waiting tenant is quota-deferred
            req = self.waiting[pos]
            # a spill-evicted request revives from the host tier: its
            # payload becomes a ``preloaded`` import, exactly the
            # disaggregated-handoff shape. A tier that LRU-dropped the
            # entry under budget pressure degrades to plain re-prefill.
            if req.spill_key is not None and self.kv_tier is not None:
                payload = self.kv_tier.peek_request(req.spill_key)
                if payload is not None:
                    req.preloaded = payload
                    req.revived_from_tier = True
                else:
                    # LRU-dropped under budget pressure, or freed by the
                    # tier's read-back CRC verification (ISSUE 20) — both
                    # degrade identically to plain re-prefill, and the
                    # miss is counted so an elevated rate is visible
                    self.revive_misses += 1
                    req.spill_key = None
            # preloaded (disaggregated-handoff) requests charge full
            # blocks and skip prefix matching: their pages arrive by
            # import, not by sharing — the engine registers the imported
            # full blocks afterwards so LATER admissions can share them
            host_hits = []
            if self.prefix_cache is not None and req.preloaded is None:
                if self.kv_tier is not None:
                    matched, mtok, host_hits = (
                        self.prefix_cache.match_with_tier(
                            req.tokens, self.kv_tier))
                else:
                    matched, mtok = self.prefix_cache.match(req.tokens)
            else:
                matched, mtok = [], 0
            need = -(-(req.num_tokens + 1) // self.block_size) - len(matched)
            if matched:
                # pin the matched blocks FIRST: the fresh allocation below
                # may reclaim reusable (refcount-0) blocks, and the match
                # must not be reclaimed out from under its own admission
                self.allocator.acquire(matched)
            blocks = self.allocator.allocate(need) if need > 0 else []
            if blocks is None:
                if matched:
                    self.allocator.free(matched)
                _M_QUEUED_EXH.inc(instance=self.instance)
                break
            del self.waiting[pos]
            slot = self._free_slot()
            req.blocks = list(matched) + blocks
            if req.preloaded is not None:
                # decode-ready immediately: pages cover every token but
                # the last one (whose KV the first decode step writes);
                # the engine imports the payload into req.blocks before
                # this step's decode runs. The draft pool (speculative
                # decoding) was NOT transferred — its catch-up loop
                # re-derives the prompt positions deterministically.
                req.num_cached = int(req.preloaded["covered"])
                req.draft_cached = 0
                req.prefilling = False
                if req.revived_from_tier:
                    self.kv_tier.drop_request(req.spill_key)
                    req.spill_key = None
            else:
                # host-resident chain links continue the device match:
                # queue their page imports (drained by the engine before
                # prefill work) and start num_cached past them — the
                # blocks that would otherwise be re-prefilled arrive by
                # host->device copy instead. The draft pool (speculative
                # decoding) only mirrors the DEVICE match; the catch-up
                # loop re-derives the revived span deterministically.
                for j, h in enumerate(host_hits):
                    self.pending_revive.append((req, blocks[j], h))
                req.num_cached = mtok + len(host_hits) * self.block_size
                req.draft_cached = mtok
                req.prefilling = True
            req.prefill_upto = req.num_tokens
            req.state = RUNNING
            req.admit_seq = next(self._admit_seq)
            self.slots[slot] = req
            self.version += 1
            _M_ADMITTED.inc(instance=self.instance)
            if matched:
                _M_PREFIX_REUSED.inc(len(matched), instance=self.instance)
            picked.append((slot, req))
        return picked

    # -- chunked prefill work -------------------------------------------
    def prefill_work(self, budget=None, align=None):
        """Chunk assignments ``[(req, start, n_new_tokens)]`` for this
        engine step: oldest-admitted prefilling requests first, total NEW
        tokens bounded by ``budget`` (``None`` = unlimited — whole prompts
        in one chunk, the PR-7 behavior). Non-final chunks are
        block-aligned (chunk starts must sit on page boundaries for
        whole-page pool writes); the head assignment always gets at least
        one block so prefill can never stall under a tiny budget.
        ``align`` (a multiple of the block size, the block size if not
        given) is what a non-final chunk's length is cut down to: the
        engine passes its smallest chunk rung, so that what is left of a
        request's staged bucket behind any chunk still holds a rung."""
        out = []
        align = int(align or self.block_size)
        remaining = float("inf") if budget is None else int(budget)
        for req in sorted((r for r in self.slots
                           if r is not None and r.prefilling),
                          key=lambda r: r.admit_seq):
            todo = req.prefill_upto - req.num_cached
            if todo <= 0:
                continue
            if remaining <= 0:
                break
            allowed = remaining
            if allowed < todo:
                allowed = int(allowed) // align * align
                if allowed == 0:
                    if out:
                        break
                    allowed = align  # guaranteed progress
            take = int(min(todo, allowed))
            out.append((req, req.num_cached, take))
            remaining -= take
        return out

    # -- decode-time growth / eviction / COW ----------------------------
    def _grow_one(self, req, evicted):
        """One block for ``req``, evicting peers (then self) on
        exhaustion. Returns the block id or None if ``req`` itself was
        evicted."""
        while True:
            got = self.allocator.allocate(1)
            if got is not None:
                return got[0]
            peers = [r for r in self.running if r is not req]
            # batch-tier peers yield first (ISSUE 17): growing latency
            # work never preempts a latency peer while batch work still
            # occupies slots
            batch = [r for r in peers if r.tier == TIER_BATCH]
            victim = max(batch or peers, key=lambda r: r.admit_seq,
                         default=None)
            if victim is None:
                victim = req  # alone and out of memory: preempt self
            if victim.tier == TIER_BATCH and req.tier == TIER_LATENCY:
                _M_BATCH_YIELD.inc(instance=self.instance)
            self._evict(victim)
            evicted.append(victim)
            if victim is req:
                return None

    def ensure_decode_room(self, extra=0):
        """Grow every running request that is about to write past its last
        block; ``extra`` reserves additional lookahead positions (the
        speculative verify window writes ``k+1`` tokens at once). On
        exhaustion, evict the most-recently-admitted running request (free
        its blocks, re-queue at the FRONT) and retry — token-granularity
        eviction. Divergent-write targets that are shared get a private
        copy queued on ``pending_cow`` (COW). Returns the evicted
        requests."""
        evicted = []
        for req in self.slots:
            if req is None:
                continue
            if req.prefilling:
                # mid-prefill requests already own blocks for prompt+1
                # tokens (charged at admission), take no speculative
                # lookahead and write nothing here
                self._room(req, None, req.num_tokens - 1, evicted)
                continue
            # the decode step writes ONE token at position len(tokens)-1
            # (plus ``extra`` speculative positions), so capacity
            # len(tokens)+extra is exactly enough — demanding more
            # would evict needlessly when the pool is full at a boundary
            self._room(req, req.num_cached,
                       req.num_tokens - 1 + extra, evicted)
        return evicted

    def reserve_ahead(self, rows):
        """Room for a decode step that is enqueued while the one before it
        is still in flight (ISSUE 28): ``rows`` is ``[(request, the
        position it will write)]``, in slot order. ``ensure_decode_room``'s
        own arithmetic (``_room``), but only out of what the allocator has
        free: it never evicts, copies or retracts. Returns False at the
        first row for which that does not do: a block short, or a write
        into a block another request shares or the prefix index still
        names. The step is then not dispatched ahead, and the next call's
        ``ensure_decode_room`` evicts, copies or retracts as ever, with
        nothing in flight; the blocks the rows before that one took are
        those it would have given them first."""
        return all(self._room(req, pos, pos, None) for req, pos in rows)

    def _room(self, req, first, last, evicted):
        """Blocks for ``req`` to hold position ``last``, and the blocks it
        writes from ``first`` on (None: it writes none) its own alone.
        With a list to evict into it takes what that needs: peers evicted
        on exhaustion (then ``req`` itself), a shared block swapped for a
        private copy, a published one retracted. With ``evicted=None`` it
        takes free blocks only and returns False where more is needed."""
        bs = self.block_size
        while req.state == RUNNING and last >= len(req.blocks) * bs:
            if evicted is None and not self.allocator.num_free:
                return False
            got = self._grow_one(req, evicted)
            if got is None:
                return False
            req.blocks.append(got)
            self.version += 1
        if req.state != RUNNING or first is None:
            return True
        # COW guard over the write window: a shared block must never be
        # mutated in place
        for bi in range(first // bs, min(last // bs, len(req.blocks) - 1) + 1):
            b = req.blocks[bi]
            shared = self.allocator.is_shared(b)
            if not shared and not (self.prefix_cache is not None
                                   and self.prefix_cache.registered(b)):
                continue
            if evicted is None:
                return False
            if shared:
                got = self._grow_one(req, evicted)
                if got is None:
                    return False
                self.pending_cow.append((b, got))
                self.allocator.free([b])
                req.blocks[bi] = got
                self.version += 1
                _M_COW.inc(instance=self.instance)
            else:
                # sole holder, but the content is published: the write
                # diverges it from its hash — retract the identity
                self.prefix_cache.forget(b)
        return True

    def trim_to_capacity(self, req, extra=0):
        """Free tail blocks beyond what ``req.num_tokens + extra`` needs
        (the speculative-rollback path: a rejected window leaves
        over-allocated lookahead blocks behind). ``extra`` keeps the NEXT
        verify window's lookahead room — trimming to the bare token count
        would free a block that ``ensure_decode_room`` re-allocates one
        step later, ping-ponging the allocator and invalidating the
        engine's device table cache every step near a block boundary.
        Tail blocks are private by construction; ``free`` decrefs anyway,
        so a forged shared tail is still safe."""
        keep = max(-(-(req.num_tokens + int(extra)) // self.block_size), 1)
        if len(req.blocks) > keep:
            extras = req.blocks[keep:]
            del req.blocks[keep:]
            self.allocator.free(extras)
            self.version += 1

    def _evict(self, req):
        slot = self.slots.index(req)
        # KV tiering (ISSUE 16): spill a decode-ready victim's pages to
        # the host tier BEFORE the blocks free — the snapshot's gathers
        # dispatch against the still-bound pool arrays, so freeing (and
        # even re-writing) the blocks afterwards cannot corrupt the
        # spilled copy. Mid-prefill victims are not spilled (their pages
        # are incomplete); a failed/over-budget spill degrades to the
        # plain recompute preemption below.
        if (self.kv_tier is not None and not req.prefilling
                and req.num_cached > 0
                and req.num_cached == req.num_tokens - 1):
            if self.kv_tier.spill_request(req.rid, req.blocks,
                                          req.num_cached,
                                          tenant=req.tenant):
                req.spill_key = req.rid
        self.allocator.free(req.blocks)
        self._release_window(req)
        req.blocks = []
        req.num_cached = 0
        req.draft_cached = 0
        req.prefilling = False
        req.state = WAITING
        req.evictions += 1
        req.t_queue_start = time.perf_counter_ns()  # re-queued span start
        self.slots[slot] = None
        self.waiting.appendleft(req)
        self.version += 1
        _M_EVICTIONS.inc(instance=self.instance)

    # -- early termination (deadline expiry / cancel / engine close) -----
    def abort(self, req, reason="cancelled"):
        """Finish ``req`` early, releasing everything it holds: a RUNNING
        request frees its blocks (decref under sharing) and recycles its
        slot for the very next admission; a WAITING request just leaves
        the queue. Idempotent on already-finished requests. The typed
        reason lands in ``finish_reason()`` — deliberately NOT counted as
        ``serving_requests_finished_total`` (an aborted request did not
        finish; the fleet's completed+typed-error accounting depends on
        the distinction)."""
        if req.state == FINISHED:
            return
        # early termination must also unwind queued device-page work that
        # references the dying request (ISSUE 17 satellite): a pending
        # host-tier revive would index into the emptied block list, and a
        # pending COW copy would write into a freed (possibly re-
        # allocated) destination block.
        if self.pending_revive:
            mine = [t for t in self.pending_revive if t[0] is req]
            if mine:
                self.pending_revive = [t for t in self.pending_revive
                                       if t[0] is not req]
                for _, _, h in mine:
                    # the chain's host pages were pinned for this
                    # admission; drop them the way the engine's dead-
                    # request drain path does — a payload nobody will
                    # import must not sit in the host tier forever
                    if self.kv_tier is not None:
                        self.kv_tier.pop_prefix(h)
        if req.state == RUNNING:
            slot = self.slots.index(req)
            if self.pending_cow and req.blocks:
                dying = set(req.blocks)
                self.pending_cow = [(s, d) for s, d in self.pending_cow
                                    if d not in dying]
            if req.blocks:
                self.allocator.free(req.blocks)
            self._release_window(req)
            req.blocks = []
            self.slots[slot] = None
            self.version += 1
        else:  # WAITING
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
        req.prefilling = False
        req.preloaded = None  # never-imported handoff pages die here
        if req.spill_key is not None and self.kv_tier is not None:
            self.kv_tier.drop_request(req.spill_key)  # host pages too
            req.spill_key = None
        req.abort_reason = reason
        req.state = FINISHED

    # -- completion ------------------------------------------------------
    def _release_window(self, req):
        if self.window_pages is not None:
            self.window_pages.release(req.rid)

    def finish(self, req):
        slot = self.slots.index(req)
        self.allocator.free(req.blocks)
        self._release_window(req)
        req.blocks = []
        req.state = FINISHED
        self.slots[slot] = None
        self.version += 1
        _M_FINISHED.inc(instance=self.instance)
