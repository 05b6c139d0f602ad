"""LLM serving engine front-end (ISSUE 7 tentpole, part d; ISSUE 11 adds
prefix sharing, chunked prefill and speculative decoding).

``LLMEngine`` turns a ``LlamaForCausalLM`` into a continuously-batched
server:

* ``add_request`` enqueues a prompt; a ``DevicePrefetcher``-style ingest
  thread pads it to its prefill bucket (PR-1 ``BucketSpec`` semantics, via
  ``io.prefetch.np_pad_to_bucket``) and starts the host→device transfer
  off the decode thread's critical path;
* ``step`` runs one scheduler tick: admit queued prompts (charging only
  blocks the prefix cache cannot supply), advance prefills by at most
  ``max_prefill_tokens_per_step`` tokens of block-aligned chunks (so long
  prompts interleave with decode instead of monopolizing steps), then ONE
  fixed-shape decode step for every decode-ready slot against the paged
  KV pool — the decode graph compiles once and is reused for the life of
  the engine (``paddle.jit.cache_stats()`` row ``llm_engine_decode#n``
  proves it). The per-step decode path runs a step AHEAD of the host
  (ISSUE 28): once this call's decode step is on the device the next one
  is enqueued behind it, fed by its greedy tokens on the device, and only
  then are this step's tokens fetched and emitted — call k still returns
  step k's tokens, and the host's work lies beside the device's, not
  between two of its steps;
* with ``enable_prefix_cache=True``, full prompt blocks are registered
  under hash-chain identities after prefill: N requests sharing a prompt
  prefix prefill its full blocks ONCE, later admissions ``acquire`` the
  shared blocks (ref-counted, copy-on-write guarded) and prefill only
  their unshared tail;
* with ``draft_model=``, decode runs **speculative**: the draft llama
  proposes ``spec_tokens`` greedy continuations per step (its own paged
  pools indexed by the SAME block tables), and a single multi-query
  paged-attention verify step scores all k+1 positions at once with
  in-graph accept counting; rollback rewinds the block-table length and
  frees over-allocated tail blocks, so greedy outputs stay bit-exact
  versus the non-speculative arm;
* ``stream`` iterates steps and yields tokens as they are produced;
* ``reload_weights`` hot-swaps weights from a ``CheckpointManager``
  (``latest_healthy_step()`` — the divergence-sentinel-approved step)
  WITHOUT recompiling: weights are jit arguments, not baked constants.

Pool writes happen in-graph (``lax.dynamic_update_slice``); attention
reads route through ``serving.paged_attention`` (Pallas on TPU, pure-lax
gather on CPU). A greedy step's tokens are the decode graph's own argmax
(``greedy_tokens_in_graph``: the host sampler's choice, bit for bit); a
request that samples is sampled host-side via
``models.llama.sample_next_tokens`` — the same function the eager
``generate`` path uses, so engine outputs are bit-exact against it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import queue
import threading
import time
import warnings

import numpy as np

from ...observability import metrics as _obs_metrics
from ...observability import trace as _obs_trace
from .errors import (EngineClosedError, KVIntegrityError,
                     RequestTimeoutError)
from .integrity import (_M_PAGES_REJECTED, _M_PAGES_VERIFIED,
                        _M_WEIGHT_AUDIT_FAIL)
from .integrity import verify_pages as _verify_pages
from .kv_cache import (PagedKVCache, PrefixCache, HostKVTier,
                       _G_HOST_BLOCKS, _H_REVIVE_MS, _H_SPILL_MS,
                       _M_HOST_EVICT, _M_REVIVES, _M_REVIVE_BYTES,
                       _M_SPILLS, _M_SPILL_BYTES)
from .prefix_store import (PrefixStoreMismatch, load_prefix_store,
                           pool_geometry, save_prefix_store,
                           weights_fingerprint, _M_STORE_LOADED,
                           _M_STORE_REJECTED, _M_STORE_SAVED)
from .scheduler import (Request, SamplingParams, Scheduler,
                        _M_ADMITTED, _M_BATCH_YIELD, _M_COW, _M_EVICTIONS,
                        _M_FINISHED, _M_PREFIX_REUSED, _M_QUEUED_EXH,
                        _M_TENANT_TOKENS, _M_THROTTLED)

__all__ = ["LLMEngine", "StepOutput", "save_llama_artifact",
           "load_llama_artifact", "load_llama_state_dict",
           "is_quantized_artifact", "quantize_state_dict",
           "dequantize_state_dict", "EngineClosedError",
           "RequestTimeoutError"]

# engine-owned latency/utilization observability (ISSUE 10): TTFT and
# inter-token latency are recorded HERE, from host timestamps the engine
# already takes at its sampling points (post-fetch — sampling is host-side
# by design), so bench_serving reports serving percentiles from the
# engine's own histograms instead of bench-side timing. Labeled by engine
# instance; request ids ride in trace spans (bounded rows), never labels.
_H_TTFT = _obs_metrics.histogram(
    "serving_ttft_ms", "time to first token per request (submit -> first "
    "sampled token)", buckets=_obs_metrics.DEFAULT_MS_BUCKETS)
_H_ITL = _obs_metrics.histogram(
    "serving_itl_ms", "inter-token latency per decoded token",
    buckets=_obs_metrics.DEFAULT_MS_BUCKETS)
_H_QUEUE_WAIT = _obs_metrics.histogram(
    "serving_queue_wait_ms", "time a request waited for admission: from "
    "when it was due (add_request's arrival_t) or else accepted, or from "
    "its re-queue after an eviction, to the step that admitted it",
    buckets=_obs_metrics.DEFAULT_MS_BUCKETS)
_M_TOKENS = _obs_metrics.counter(
    "serving_tokens_out_total", "tokens sampled across all requests")
_M_PREFILLS = _obs_metrics.counter(
    "serving_prefills_total", "prefill completions (incl. eviction "
    "re-prefills)")
_M_PREFILL_BEHIND = _obs_metrics.counter(
    "serving_prefill_ends_behind_decode_total",
    "prefill completions whose last chunk's logits were fetched with a "
    "decode step enqueued behind the chunk: the call found a step in "
    "flight and dispatched the next one ahead before it fetched (the "
    "others fetched at once, with nothing in flight, or with a step in "
    "flight behind which nothing could be dispatched)")
_M_PREFILL_CHUNKS = _obs_metrics.counter(
    "serving_prefill_chunks_total",
    "block-aligned prefill chunk executions (chunked prefill splits one "
    "prompt across several of these)")
_M_PREFILL_IN_A_ROW = _obs_metrics.counter(
    "serving_prefill_chunks_in_a_row_total",
    "of serving_prefill_chunks_total, the chunks whose program read the "
    "request's keys laid out in a row, through the chunk kernel on the TPU "
    "(every layer of it: chunk_reads_in_a_row); 0 on an engine whose "
    "Llama-form pools hold int8 codes, which the page-by-page multi-query "
    "kernel reads")
_M_PREFILL_TOKENS = _obs_metrics.counter(
    "serving_prefill_tokens_total",
    "prompt tokens the prefill chunks materialized (re-prefills after an "
    "eviction included)")
_M_PREFILL_PADDED = _obs_metrics.counter(
    "serving_prefill_padded_tokens_total",
    "tokens the prefill chunk graphs computed: each chunk runs at a rung "
    "of prefill_buckets at least as long as its tokens, so this over "
    "serving_prefill_tokens_total, less 1, is the share of chunk work "
    "spent on padding")
_M_SPEC_PROPOSED = _obs_metrics.counter(
    "serving_spec_proposed_total",
    "draft tokens proposed by the speculative decoder")
_M_SPEC_ACCEPTED = _obs_metrics.counter(
    "serving_spec_accepted_total",
    "draft tokens accepted by the verify step")
_G_SPEC_RATIO = _obs_metrics.gauge(
    "serving_spec_accept_ratio",
    "running accepted/proposed ratio of the speculative decoder")
_G_KV_UTIL = _obs_metrics.gauge(
    "serving_kv_block_utilization",
    "fraction of usable KV pool blocks in use after the last step")
_G_OCCUPANCY = _obs_metrics.gauge(
    "serving_decode_batch_occupancy",
    "fraction of decode slots occupied after the last step")
_M_DEADLINE = _obs_metrics.counter(
    "serving_deadline_expired_total",
    "requests aborted by the engine because their deadline expired "
    "(admission-time rejections raise before a request exists and are "
    "not counted here)")
_M_KV_SAVED = _obs_metrics.counter(
    "serving_kv_bytes_saved_total",
    "pool bytes saved by int8 KV quantization vs the same pool in the "
    "model dtype (scale sidecars charged against the saving; counted "
    "once at engine construction)")
_G_QUANT_BLOCKS = _obs_metrics.gauge(
    "serving_quantized_kv_blocks_in_use",
    "int8-quantized KV pool blocks held by live requests after the last "
    "step (0 series absent on unquantized engines) — the occupancy the "
    "halved block memory buys")
# device-resident decode (ISSUE 18): how often the decode loop blocks on
# a device->host fetch and how many bytes it pulls. A step whose rows the
# host samples from or keeps (do_sample, capture_logits) fetches [B, V]
# f32 logits; a greedy step fetches [B] int32 tokens, the decode graph's
# own argmax (ISSUE 27).
_M_HOST_SYNCS = _obs_metrics.counter(
    "serving_host_syncs_total",
    "blocking device->host fetches made by the decode loop (logits or "
    "sampled tokens); one per decode round-trip, prefill fetches excluded")
_M_FETCH_BYTES = _obs_metrics.counter(
    "serving_decode_fetch_bytes_total",
    "bytes fetched device->host by the decode loop: B*V*4 per step whose "
    "rows the host samples from or keeps, B*4 per greedy step")

# decode dispatch-ahead (ISSUE 28): how each emitted decode step of the
# plain path reached the device, and what the overlap cost in dropped rows
_M_DECODE_AHEAD = _obs_metrics.counter(
    "serving_decode_steps_ahead_total",
    "decode steps emitted that were dispatched ahead: enqueued behind the "
    "step before them, before that step's tokens were fetched")
_M_DECODE_SYNC = _obs_metrics.counter(
    "serving_decode_steps_sync_total",
    "decode steps emitted that were dispatched with nothing in flight, by "
    "reason: idle (first after a break), sampled (a do_sample row), evict "
    "(room only by evicting or copying), drain (import, export, reload, "
    "store save), path (speculative, process-spanning mesh)")
_M_ROWS_DISCARDED = _obs_metrics.counter(
    "serving_decode_rows_discarded_total",
    "rows of a decode step in flight whose token was dropped: the request "
    "finished by EOS, was cancelled, expired or preempted meanwhile, or "
    "the step was drained")

# the ONE list of every serving metric handle an engine instance owns —
# metrics() and reset_metrics() both iterate it, so a new metric cannot
# be added to one and silently missed by the other (a reset that skips a
# histogram would leak warm-phase samples into bench percentiles)
_SERVING_METRICS = (_M_ADMITTED, _M_EVICTIONS, _M_FINISHED, _M_QUEUED_EXH,
                    _M_PREFIX_REUSED, _M_COW, _M_PREFILLS,
                    _M_PREFILL_BEHIND, _M_PREFILL_CHUNKS,
                    _M_PREFILL_IN_A_ROW,
                    _M_PREFILL_TOKENS, _M_PREFILL_PADDED,
                    _M_SPEC_PROPOSED, _M_SPEC_ACCEPTED,
                    _M_TOKENS, _M_DEADLINE, _M_KV_SAVED, _H_TTFT, _H_ITL,
                    _H_QUEUE_WAIT,
                    _G_SPEC_RATIO, _G_KV_UTIL, _G_OCCUPANCY,
                    _G_QUANT_BLOCKS,
                    # KV tiering + prefix store (ISSUE 16);
                    # _M_STORE_REJECTED is reason-labeled (ISSUE 20), so
                    # metrics()/reset_metrics() handle it like
                    # _M_TENANT_TOKENS (exact-match remove can't reach it)
                    _M_SPILLS, _M_REVIVES, _M_SPILL_BYTES, _M_REVIVE_BYTES,
                    _M_HOST_EVICT, _G_HOST_BLOCKS, _H_SPILL_MS,
                    _H_REVIVE_MS, _M_STORE_SAVED, _M_STORE_LOADED,
                    # multi-tenant QoS (ISSUE 17); _M_TENANT_TOKENS is
                    # tenant-labeled, so metrics()/reset_metrics() handle
                    # it separately (exact-match remove can't reach it)
                    _M_THROTTLED, _M_BATCH_YIELD,
                    # device-resident decode (ISSUE 18)
                    _M_HOST_SYNCS, _M_FETCH_BYTES,
                    # decode dispatch-ahead (ISSUE 28); _M_DECODE_SYNC is
                    # reason-labeled, handled like _M_STORE_REJECTED
                    _M_DECODE_AHEAD, _M_ROWS_DISCARDED,
                    # serving integrity (ISSUE 20)
                    _M_PAGES_VERIFIED, _M_PAGES_REJECTED,
                    _M_WEIGHT_AUDIT_FAIL)


#: what a model exposes to be served (``models/llama.py`` has the
#: reference implementation of each)
_SERVING_CALLS = ("kv_layout", "serve_dtype", "serve_embed", "serve_layer",
                  "serve_norm", "serve_head")


def _counter_names(model):
    """The device-side counters a model's ``serve_layer`` adds to."""
    return tuple(getattr(model, "serve_counters", ()))


def _keep_names(model):
    """The rows a model's ``serve_layer`` hands the host beside the logits
    (``state.keep``), by name."""
    return tuple(getattr(model, "serve_keeps", ()))


def _stack_kept(kept, names):
    """What the layers kept, a name an array ``[layers, tokens, ...]``."""
    import jax.numpy as jnp

    return {n: jnp.stack(kept[n]) for n in names}


#: a device-side counter is two int32 limbs, ``low + _LIMB * carried``: a
#: count of millions a step (cached rows a decode step walks, over the
#: layers) passes 2^31 within a thousand steps, and ``metrics()`` is what
#: fetches and resets it. A step's own count stays under ``_LIMB``.
_LIMB = 1 << 30


def _add_counts(counters, counts, names, decode):
    """The step's counter array ``[2, 2 * len(names)]`` (low limbs, carried
    limbs) plus what the layers counted: the decode graph's counts in the
    first ``len(names)`` places, the prefill chunk's in the second."""
    import jax.numpy as jnp

    if not names:
        return counters
    got = jnp.stack([jnp.asarray(counts.get(n, 0), jnp.int32) for n in names])
    zero = jnp.zeros_like(got)
    low = counters[0] + jnp.concatenate([got, zero] if decode else [zero, got])
    return jnp.stack([low % _LIMB, counters[1] + low // _LIMB])


@contextlib.contextmanager
def _params_swapped(params, arrays):
    """What every graph builder does around its trace: the operand
    ``arrays`` stand in the model's ``params`` while the body traces under
    ``trace_guard``, and the arrays that were there come back whatever the
    trace does, so that one that raises leaves no tracer in the model."""
    from ...core import state as _state

    old = [p._data for p in params]
    try:
        for p, a in zip(params, arrays):
            p._data = a
        with _state.trace_guard():
            yield
    finally:
        for p, a in zip(params, old):
            p._data = a


def _scales_in(k_pools, v_pools, k_scales, v_scales):
    """``(quantized, ks_in, vs_in)``: the scale pools a layer loop zips
    beside the payload pools, a None a layer where the cache holds none
    (the float path's scale lists are empty)."""
    if len(k_scales) > 0:
        return True, k_scales, v_scales
    return False, [None] * len(k_pools), [None] * len(v_pools)


class _StepPhases:
    """The phases of one engine step as spans (``observability.trace``):
    ``begin(name)`` ends the phase that was open and opens the next, so the
    phases of a step follow each other and never overlap, whichever decode
    path the step takes. Same names on every path:

    ``engine.admit`` (ingest drain, deadline scan, admission, tier
    revivals), ``engine.prefill`` (one chunk: staging, the choice of its
    rung, the dispatch, the books; the call of the chunk executable alone
    is a child span, ``engine.prefill.chunk``, that says what the chunk
    did: ``rid``, ``start``, ``tokens`` real ones in a graph of ``padded``,
    ``last``), ``engine.prefill.first_token`` (the fetch of a last chunk's
    logits and the first token: at once behind its chunk with nothing in
    flight, ``requests`` 1; beside a decode step in flight once a call,
    behind the call's decode dispatch, for all the call's last chunks,
    ``behind`` 1 if a step was enqueued behind them: ISSUE 34; one name,
    one meaning: ISSUE 35), ``engine.decode.prepare`` (decode
    room, copy-on-write, the ready list, the step's inputs and their
    puts), ``engine.decode.dispatch`` (the call of the decode or
    verify executable until it returns), ``engine.decode.fetch`` (the wait
    for the device, then the transfer), ``engine.decode.emit`` (sampling,
    commit, latency observations, finishes), ``engine.bookkeeping`` (store
    autosave, gauges); the speculative path adds ``engine.decode.draft``
    (the draft model's catch-up and proposals, with their own fetches).
    All lie inside the step's ``engine.step`` and carry its ``args``,
    built under ``trace.live()`` (in a profile the scalars among them are
    the event's statistics). A state kind adds no name: a decode step's
    slots are made in ``engine.decode.prepare``, a chunk's slot in
    ``engine.prefill``, and a step that ``_drain`` commits between two
    calls records its fetch and emit under an ``engine.step`` of their own.

    On the per-step path a steady call's prepare and dispatch are the
    NEXT step's and its fetch and emit this step's (ISSUE 28); the first
    call after a break holds prepare and dispatch twice, this step's and
    the next one's, and a call that dispatches nothing ahead holds an
    empty prepare. A call that ends a prefill still holds
    ``engine.prefill`` (its chunk), whichever way its first token comes,
    so a reader that tells a decode-only call by the absence of that name
    (``benchmarks/harness/program_spans.py``) reads as before."""

    __slots__ = ("args", "_open")

    def __init__(self):
        self.args = None
        self._open = None

    def begin(self, name, args=None):
        """``args``: what this phase says beside the step's own."""
        self.end()
        if args is None:
            args = self.args
        elif self.args is not None:
            args = {**self.args, **args}
        self._open = _obs_trace.span(name, cat="engine", args=args)

    def end(self):
        if self._open is not None:
            self._open.end()
            self._open = None


@dataclasses.dataclass(slots=True)
class _DecodeInFlight:
    """A decode step the device has been given and the host has not yet
    fetched (ISSUE 28). ``rows`` is what it was made for, ``[(slot,
    request, position written, the request's admit_seq then)]``; ``logits``
    and ``greedy`` are its result arrays, still on the device; ``sampled``
    says that a row's token is the host sampler's to choose; ``how`` is
    how it was dispatched: ``"ahead"``, or the reason it was not; ``kept``
    is what the model's layers kept for the host (``state.keep``), None
    for a model that keeps nothing."""

    rows: list
    logits: object
    greedy: object
    sampled: bool
    how: str
    kept: object = None

    def standing(self, slots):
        """The rows whose request still sits where the step was made for
        it, as it was: not finished, cancelled, expired or preempted (a
        re-admission takes a new ``admit_seq``), its cache where this step
        wrote."""
        return [row for row in self.rows
                if slots[row[0]] is row[1] and row[1].admit_seq == row[3]
                and not row[1].prefilling and row[1].num_cached == row[2]]


@dataclasses.dataclass
class StepOutput:
    rid: int
    token: int
    finished: bool
    finish_reason: str | None = None


def _default_buckets(block_size, max_model_len):
    """Doubling ladder of prefill lengths, block-aligned: one compiled
    prefill graph per rung."""
    buckets, b = [], block_size
    while b < max_model_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_model_len)
    return buckets


class _IngestThread:
    """Push-based analog of ``io.DevicePrefetcher``'s transfer thread:
    pads each queued prompt to its prefill bucket on the host and starts
    the device transfer, so admission never blocks decode on H2D. Dies
    once, warns once, and the engine degrades to synchronous staging."""

    def __init__(self, stage_fn, name):
        self._stage = stage_fn
        self._q: queue.Queue = queue.Queue()
        self._ready: list = []
        self._cond = threading.Condition()
        self._pending = 0  # submitted but not yet drained
        self._dead = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name=f"{name}-ingest")
        self._thread.start()

    def _worker(self):
        while True:
            req = self._q.get()
            if req is None:
                return
            try:
                self._stage(req)
            except BaseException as e:
                warnings.warn(
                    f"LLMEngine ingest thread died ({e!r}); degrading to "
                    "synchronous request staging", RuntimeWarning)
                with self._cond:
                    # _dead flips and the queue flushes under ONE lock
                    # acquisition: submit() holds the same lock across its
                    # dead-check and enqueue, so a request can never land in
                    # _q after this flush and be stranded there forever
                    self._dead = True
                    # flush EVERYTHING un-staged (the failing request AND
                    # anything still queued behind it) back to the engine —
                    # step() re-stages synchronously; stranding them would
                    # leave has_work() true forever with nothing to drain
                    self._ready.append(req)
                    while True:
                        try:
                            nxt = self._q.get_nowait()
                        except queue.Empty:
                            break
                        if nxt is not None:
                            self._ready.append(nxt)
                    self._cond.notify_all()
                return
            with self._cond:
                self._ready.append(req)
                self._cond.notify_all()

    @property
    def pending(self):
        with self._cond:
            return self._pending

    def submit(self, req):
        # dead-check and enqueue under one lock hold: the worker's death
        # path flips _dead and flushes _q while holding the same lock, so
        # either this put lands before the flush (and gets flushed) or we
        # observe _dead and hand the request straight to _ready
        with self._cond:
            self._pending += 1
            if self._dead:
                self._ready.append(req)
                self._cond.notify_all()
                return
            self._q.put(req)

    def drain(self, wait=False, timeout=1.0):
        """Staged requests since the last drain. ``wait=True`` blocks (up
        to ``timeout``) until at least one lands — the engine uses it when
        it would otherwise spin on an empty scheduler while requests are
        in flight on the ingest thread."""
        with self._cond:
            if wait and not self._ready and self._pending:
                self._cond.wait_for(lambda: self._ready, timeout=timeout)
            out, self._ready = self._ready, []
            self._pending -= len(out)
        return out

    def close(self):
        if not self._dead:
            self._q.put(None)
            self._thread.join(timeout=2.0)


class LLMEngine:
    """Continuous-batching paged-KV serving engine over a llama model."""

    _instance_ids = itertools.count(1)

    def __init__(self, model, *, num_blocks=64, block_size=16,
                 max_batch_size=4, max_model_len=None, prefill_buckets=None,
                 max_prefills_per_step=1, ingest_async=True, plan=None,
                 enable_prefix_cache=False, max_prefill_tokens_per_step=None,
                 draft_model=None, spec_tokens=2, kv_dtype=None,
                 prefill_only=False, kv_host_blocks=0,
                 prefix_store_path=None, prefix_store_autosave_chains=None,
                 capture_logits=False, kv_page_checksums=False,
                 weight_audit=False):
        from ...models.llama import LlamaForCausalLM, sample_next_tokens

        # the serving calls a model exposes (ISSUE 27): the prefill-chunk
        # and decode graphs are built from these and nothing else of it
        missing = [a for a in _SERVING_CALLS if not hasattr(model, a)]
        if missing:
            raise TypeError(
                "LLMEngine serves models that expose the serving calls "
                f"{', '.join(_SERVING_CALLS)}; {type(model).__name__} "
                f"lacks {', '.join(missing)}")
        # draft verify and catch-up keep Llama's layer body written out
        # (or were only ever run with it): another model is refused below,
        # by name
        self._llama = isinstance(model, LlamaForCausalLM)
        self.model = model
        if not self._llama and plan is not None:
            raise ValueError(self._llama_only("plan (a sharded engine)"))
        # sharding plan (distributed.plan.Plan): weights are committed to
        # the plan's layouts (e.g. Megatron tp for pod-scale serving) and
        # both engine executables lower through compile_step_with_plan —
        # the ONE compile layer shared with FusedTrainStep and hapi fit.
        # GSPMD propagates the committed weight placements through the
        # prefill/decode bodies; plan=None keeps the exact single-device
        # program (same entry point, no fork).
        self._plan = plan
        if plan is not None:
            plan.apply_to_model(model)
        # multi-process plan (ISSUE 19): the plan's mesh spans jax
        # processes (one engine rank per process, SPMD lockstep — the
        # fleet's tp replica groups). Host-side control flow stays
        # identical on every rank; device arrays the engine feeds its
        # compiled steps must live REPLICATED on the global mesh (_g),
        # outputs are pinned replicated (_build_jits), and host fetches
        # read the locally addressable shard (_fetch).
        self._mp = False
        if plan is not None and plan.mesh.devices.size > 1:
            import jax
            pi = jax.process_index()
            self._mp = any(d.process_index != pi
                           for d in plan.mesh.devices.flat)
        if self._mp:
            # features whose data path fetches pool pages to the host
            # (or runs a second model) are incompatible with a
            # process-spanning mesh; fail at construction, not mid-burst
            for flag, why in (
                    (int(kv_host_blocks) > 0,
                     "kv_host_blocks > 0 (host KV tier spills pool "
                     "pages to host RAM)"),
                    (prefix_store_path is not None,
                     "prefix_store_path (the store exports pool "
                     "pages)"),
                    (draft_model is not None,
                     "draft_model (speculative decoding)"),
                    (bool(prefill_only),
                     "prefill_only (disaggregated handoff exports "
                     "pool pages)")):
                if flag:
                    raise ValueError(
                        f"a plan whose mesh spans multiple processes "
                        f"does not support {why}; run these features "
                        "on single-process engines")
        self.config = model.config
        was_training = model.training
        model.eval()
        self._was_training = was_training
        limit = self.config.max_position_embeddings
        self.block_size = int(block_size)
        requested_len = min(int(max_model_len or limit), limit)
        # block-alignment invariant: prefill writes whole pages only, so a
        # max_model_len that is not a block multiple would leave the prompt
        # tail out of the pool at the top bucket — silently wrong decodes.
        # Round DOWN to whole pages; the truncated tail was unservable anyway.
        self.max_model_len = (requested_len // self.block_size
                              ) * self.block_size
        if self.max_model_len == 0:
            raise ValueError(
                f"max_model_len={requested_len} is smaller than "
                f"block_size={self.block_size}; nothing fits in one page")
        if self.max_model_len != requested_len:
            warnings.warn(
                f"max_model_len={requested_len} is not a multiple of "
                f"block_size={self.block_size}; rounding down to "
                f"{self.max_model_len} so prefill stays page-aligned",
                RuntimeWarning)
        self.max_pages = self.max_model_len // self.block_size
        dtype = model.serve_dtype()
        # int8 paged-KV quantization (ISSUE 14): pools store codes +
        # per-row scale sidecars, dequantized inside the attention
        # kernels; everything identity-shaped (allocator, prefix cache,
        # COW, tables) is payload-dtype-blind and composes unchanged
        self.kv_dtype = kv_dtype
        # per-layer pool geometry and kinds of pages (ISSUE 27): the model
        # says what each layer caches; a window kind gets a pool, an
        # allocator and a block table of its own, which the cache sizes
        # from the batch (every slot a full ring: it never preempts)
        self.cache = PagedKVCache(self.config, num_blocks, block_size,
                                  dtype=dtype, kv_dtype=kv_dtype,
                                  layout=model.kv_layout(),
                                  max_batch_size=max_batch_size)
        if not self.cache.uniform:
            # what assumes one layout refuses here rather than corrupt
            for flag, what in (
                    (enable_prefix_cache, "enable_prefix_cache (block "
                     "identity is one hash a block of ONE table)"),
                    (int(kv_host_blocks) > 0, "kv_host_blocks > 0 (the "
                     "host tier exports pages)"),
                    (prefix_store_path is not None, "prefix_store_path"),
                    (prefill_only, "prefill_only (the handoff exports "
                     "pages)"),
                    (kv_page_checksums, "kv_page_checksums")):
                if flag:
                    self.cache._require_uniform(what)
        # serving integrity (ISSUE 20): arm per-block CRC sealing of
        # every host-materialized page payload; read-back boundaries
        # (tier revive, page import, prefix-store entries) verify and
        # degrade to re-prefill on mismatch
        self.cache.page_checksums = bool(kv_page_checksums)
        from .paged_attention import chunk_reads_in_a_row
        self._chunks_in_a_row = all(
            chunk_reads_in_a_row(sp, self.cache.quantized)
            for sp in self.cache.layout)
        self._place_cache(self.cache, self.config)
        self._kv_bytes_saved = self.cache.bytes_saved_vs_unquantized(
            self.config)
        # prefix sharing (ISSUE 11): content-hashed block identity over the
        # pool — admission charges only unshared blocks
        self.prefix_cache = (PrefixCache(self.cache.allocator,
                                         self.block_size)
                             if enable_prefix_cache else None)
        # chunked prefill budget: NEW prompt tokens materialized per step.
        # None = whole prompts in one chunk (the PR-7 behavior); a budget
        # bounds decode inter-token latency by the chunk, not the prompt.
        if max_prefill_tokens_per_step is not None:
            max_prefill_tokens_per_step = int(max_prefill_tokens_per_step)
            if max_prefill_tokens_per_step < 1:
                raise ValueError("max_prefill_tokens_per_step must be >= 1")
        self.max_prefill_tokens_per_step = max_prefill_tokens_per_step
        n = next(LLMEngine._instance_ids)
        self._name = f"llm_engine#{n}"
        # KV tiering (ISSUE 16): a host-RAM page tier behind the device
        # pool. Preempted decode-ready requests and reclaimed prefix
        # blocks spill to it instead of being recomputed; revival is
        # import_request_pages — bit-exact by construction.
        kv_host_blocks = int(kv_host_blocks)
        if kv_host_blocks < 0:
            raise ValueError("kv_host_blocks must be >= 0")
        self.kv_tier = (HostKVTier(self.cache, kv_host_blocks,
                                   instance=self._name)
                        if kv_host_blocks > 0 else None)
        if self.kv_tier is not None and self.prefix_cache is not None:
            self.prefix_cache.on_spill = self.kv_tier.spill_blocks
        # persistent prefix store (ISSUE 16): hash chains survive process
        # death as a CRC-framed shard; boot re-imports them into the host
        # tier so the next matching prompt revives instead of re-prefills.
        if prefix_store_path is not None:
            if self.prefix_cache is None:
                raise ValueError(
                    "prefix_store_path requires enable_prefix_cache=True: "
                    "the store persists prefix hash chains")
            if self.kv_tier is None:
                raise ValueError(
                    "prefix_store_path requires kv_host_blocks > 0: "
                    "loaded entries land in the host tier until a "
                    "matching request revives them")
        self._store_path = prefix_store_path
        if prefix_store_autosave_chains is not None:
            prefix_store_autosave_chains = int(prefix_store_autosave_chains)
            if prefix_store_autosave_chains < 1:
                raise ValueError(
                    "prefix_store_autosave_chains must be >= 1")
            if prefix_store_path is None:
                raise ValueError("prefix_store_autosave_chains without "
                                 "prefix_store_path saves nowhere")
        self._store_autosave = prefix_store_autosave_chains
        self._store_fingerprint = None
        self._store_saved_chains = -1  # force the first autosave crossing
        self.scheduler = Scheduler(self.cache.allocator, block_size,
                                   max_batch_size, max_prefills_per_step,
                                   instance=self._name,
                                   prefix_cache=self.prefix_cache,
                                   kv_tier=self.kv_tier,
                                   window_pages=self.cache.window)
        if self.cache.quantized:
            _M_KV_SAVED.inc(self._kv_bytes_saved, instance=self._name)
            _G_QUANT_BLOCKS.set(0, instance=self._name)
        self.max_batch_size = int(max_batch_size)
        buckets = prefill_buckets or _default_buckets(self.block_size,
                                                      self.max_model_len)
        # block-align every rung so prefill writes whole pages
        self.prefill_buckets = sorted({
            min(-(-int(b) // self.block_size) * self.block_size,
                self.max_model_len)
            for b in buckets})
        self._prefill_name = f"llm_engine_prefill#{n}"
        self._decode_name = f"llm_engine_decode#{n}"
        self._params = model._unique_params()
        self._prefill_jit = None
        self._decode_jit = None
        # prefill-only mode (ISSUE 15): the disaggregated prefill worker
        # runs prefills (and samples each request's FIRST token from the
        # final chunk's logits) but never decodes — requests sit
        # decode-ready until the caller exports their pages
        # (export_kv_pages) and cancels them; step() skips the decode
        # phase entirely, so the decode graph never compiles here.
        self.prefill_only = bool(prefill_only)
        if self.prefill_only and draft_model is not None:
            raise ValueError("prefill_only engines never decode; a "
                             "draft_model would be dead weight")
        # speculative decoding (ISSUE 11): the draft llama shares the
        # target's allocator/block tables; its pools are its own shapes
        self.draft_model = draft_model
        self._spec_k = 0
        if draft_model is not None:
            if not self._llama:
                raise ValueError(self._llama_only(
                    "draft_model (speculative verify and draft catch-up)"))
            if not isinstance(draft_model, LlamaForCausalLM):
                raise TypeError("draft_model must be a LlamaForCausalLM; "
                                f"got {type(draft_model).__name__}")
            if draft_model.config.vocab_size != self.config.vocab_size:
                raise ValueError(
                    "draft_model vocab_size "
                    f"{draft_model.config.vocab_size} != target "
                    f"{self.config.vocab_size}: verify compares token ids")
            if int(spec_tokens) < 1:
                raise ValueError("spec_tokens must be >= 1")
            self._spec_k = int(spec_tokens)
            self._draft_was_training = draft_model.training
            draft_model.eval()
            if plan is not None:
                plan.apply_to_model(draft_model)
            ddtype = (draft_model.llama.layers[0].self_attn.k_proj
                      .weight.dtype)
            self.draft_cache = PagedKVCache(
                draft_model.config, num_blocks, block_size, dtype=ddtype,
                allocator=self.cache.allocator, kv_dtype=kv_dtype)
            self._place_cache(self.draft_cache, draft_model.config)
            self._draft_params = draft_model._unique_params()
            self._draft_prefill_name = f"llm_engine_draft_prefill#{n}"
            self._draft_decode_name = f"llm_engine_draft_decode#{n}"
            self._verify_name = f"llm_engine_verify#{n}"
            self._draft_prefill_jit = None
            self._draft_decode_jit = None
            self._verify_jit = None
        #: read at every step, so a caller may switch it off once it has
        #: the rows it wanted: greedy steps then fetch tokens only. While it
        #: is on, what the model's layers keep for the host (``state.keep``,
        #: ``model.serve_keeps``) is fetched too and put on ``Request.kept``
        self.capture_logits = bool(capture_logits)
        # hoisted from _emit (ISSUE 18 satellite): one import at
        # construction instead of one per emitted token
        self._sample_next_tokens = sample_next_tokens
        # device block-table cache (ISSUE 11 satellite): rebuilt only when
        # the scheduler's table version moves, so steady-state decode does
        # ZERO table H2D
        self._tables_version = None
        self._tables_dev = None
        #: with a window kind: the host copy of ``_decode_tables``' array
        #: and how much of each row's block list it holds
        self._tables_host = None
        self._tables_known = None
        self._tables_mask = None
        # device-side counters of the model's layer steps (ISSUE 27): a
        # small int32 array carried through the chunk and decode graphs,
        # fetched (and folded into these host totals) only by metrics()
        self._counter_names = _counter_names(model)
        self._keep_names = _keep_names(model)
        self._counters_dev = None
        self._counter_totals = dict.fromkeys(
            [n + tail for tail in ("_decode", "_prefill")
             for n in self._counter_names], 0)
        # page-steps of either kind, for the live-bytes-vs-one-table ratio
        self._page_steps = [0, 0]
        # slots holding a request, summed a step likewise (a state kind)
        self._state_slot_steps = 0
        self._requests: dict[int, Request] = {}
        self._closed = False
        # fused ragged draft catch-up (ISSUE 16 perf satellite): one
        # fori_loop graph per power-of-two feed-length bucket
        self._catchup_jits = {}
        if self.kv_tier is not None:
            # publish the tier series at zero so metrics() and dashboards
            # see them from boot, not from the first spill
            for m in (_M_SPILLS, _M_REVIVES, _M_SPILL_BYTES,
                      _M_REVIVE_BYTES, _M_HOST_EVICT):
                m.inc(0, instance=self._name)
            _G_HOST_BLOCKS.set(0, instance=self._name)
        if self.cache.page_checksums:
            # publish the verify/reject series at zero from boot
            _M_PAGES_VERIFIED.inc(0, instance=self._name)
            _M_PAGES_REJECTED.inc(0, instance=self._name)
        # weight integrity re-audit (ISSUE 20): capture the live
        # fingerprint at construction; audit_weights() re-hashes and
        # compares — a divergence means the weights changed IN PLACE
        # (silent corruption), not a reload (reload_weights re-captures)
        self._weight_audit = bool(weight_audit)
        self._weight_audits = 0
        self._weight_audit_ref = (weights_fingerprint(model)
                                  if weight_audit else None)
        if weight_audit:
            _M_WEIGHT_AUDIT_FAIL.inc(0, instance=self._name)
        self._store_geometry = None
        if self._store_path is not None:
            self._store_fingerprint = weights_fingerprint(model)
            self._store_geometry = pool_geometry(self.cache, self.config)
            self._load_prefix_store()
        self._ingest = (_IngestThread(self._stage_request, self._name)
                        if ingest_async else None)
        self.stats_extra = {"steps": 0, "prefills": 0, "tokens_out": 0}
        self._phases = _StepPhases()
        # decode dispatch-ahead (ISSUE 28): the decode step in flight, made
        # in the last call for this one (a ``_DecodeInFlight``); why the
        # next step will be dispatched with nothing in flight, if something
        # said so; and the operand a step takes in place of the step
        # before's tokens when every id comes from the host
        self._ahead = None
        self._sync_reason = None
        self._no_prev = None
        # a state kind (ISSUE 33): the slots operand of the last decode
        # step and the live rows it was made for; the outputs of a step
        # ``_drain`` committed outside a call of ``step``, which the next
        # call hands out first
        self._slots_dev = None
        self._slots_live = None
        self._committed = []

    # ------------------------------------------------------------------
    # persistent prefix store (ISSUE 16)
    # ------------------------------------------------------------------
    def _prefix_store_entries(self):
        """Chain entries worth persisting: every device-registered chain
        (exported from the pool) plus every host-tier-resident chain a
        prior boot loaded or a reclaim demoted — deduped by hash, device
        copy wins (it is the one requests are actively sharing)."""
        entries = {}
        for h, b in self.prefix_cache.registered_chains():
            entries[h] = self.cache.export_request_pages([b],
                                                         self.block_size)
        for h, pages in self.kv_tier.prefix_items():
            entries.setdefault(h, pages)
        return list(entries.items())

    def save_prefix_store(self):
        """Serialize the current prefix chains to ``prefix_store_path``
        (atomic publish; the previous store stays intact on any failure).
        Returns the number of entries written."""
        if self._store_path is None:
            raise ValueError(f"{self._name} has no prefix_store_path")
        # beside a step in flight: the chains it exports are full blocks
        # the index names, and no step made ahead writes into one of those
        entries = self._prefix_store_entries()
        save_prefix_store(self._store_path, entries,
                          fingerprint=self._store_fingerprint,
                          geometry=self._store_geometry,
                          instance=self._name)
        self._store_saved_chains = len(self.prefix_cache)
        return len(entries)

    def _load_prefix_store(self):
        """Import the on-disk store into the host tier; any mismatch
        (CRC, fingerprint, geometry) degrades to a clean cold start."""
        try:
            entries = load_prefix_store(
                self._store_path, fingerprint=self._store_fingerprint,
                geometry=self._store_geometry, instance=self._name)
        except PrefixStoreMismatch as e:
            warnings.warn(
                f"{self._name}: rejecting prefix store "
                f"(reason={e.reason}): {e}; cold-starting the prefix "
                "cache", RuntimeWarning)
            return 0
        if entries is None:
            return 0
        loaded = 0
        for h, pages in entries:
            if self.kv_tier.put_prefix_payload(h, pages):
                loaded += 1
        return loaded

    def _maybe_autosave_store(self):
        if self._store_path is None or self._store_autosave is None:
            return
        grown = len(self.prefix_cache) - max(self._store_saved_chains, 0)
        if (grown >= self._store_autosave
                or self._store_saved_chains < 0 and len(self.prefix_cache)):
            try:
                self.save_prefix_store()
            except OSError as e:
                # saving is an optimisation; the serving loop never dies
                # for it (the previous store on disk stays intact)
                warnings.warn(f"{self._name}: prefix store autosave "
                              f"failed: {e}", RuntimeWarning)
                self._store_saved_chains = len(self.prefix_cache)

    def _llama_only(self, what):
        return (f"{what} is built for LlamaForCausalLM only; this engine "
                f"serves {type(self.model).__name__}")

    def _graph_extras(self, window_row=None, slots=None):
        """The operands a cache with a window or a state kind, or a model
        with counters, adds behind a graph's pools (none for Llama, whose
        graphs keep their operands): a chunk's window row, a state kind's
        slots (a chunk's request's, a decode step's a row), the counters.
        The operand of a kind the cache lacks is None, which is no operand
        of the program."""
        if self.cache.window is None and self.cache.state_slots is None \
                and not self._counter_names:
            return ()
        if self._counters_dev is None:
            self._counters_dev = self._g(np.zeros(
                (2, max(2 * len(self._counter_names), 1)), np.int32))
        return (window_row, slots, self._counters_dev)

    def _ensure_open(self):
        if self._closed:
            raise EngineClosedError(
                f"{self._name} is closed; create a new LLMEngine "
                "(close() joined the ingest thread, freed scheduler "
                "blocks and removed this instance's metric series)")

    # ------------------------------------------------------------------
    # multi-process placement helpers (ISSUE 19)
    # ------------------------------------------------------------------
    def _g(self, x):
        """Device placement for a step input: on a single-process mesh
        this is plain ``jnp.asarray`` (byte-identical to the pre-group
        engine); on a process-spanning mesh the value is committed
        REPLICATED over the plan's global mesh — every rank passes the
        same host value (SPMD lockstep), so the commit is collective-free
        and keeps jit from refusing to mix local and global arrays."""
        if not self._mp:
            import jax.numpy as jnp
            return jnp.asarray(x)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            np.asarray(x), NamedSharding(self._plan.mesh,
                                         PartitionSpec()))

    def _fetch(self, arr):
        """Host fetch of a step output. Outputs on a process-spanning
        mesh are pinned replicated (``_build_jits``), so every rank reads
        the SAME value from its locally addressable shard —
        ``np.asarray`` on the global array itself would raise (it spans
        non-addressable devices)."""
        if not self._mp:
            return np.asarray(arr)
        return np.asarray(arr.addressable_data(0))

    def _pool_specs(self, config):
        """``(pool, scale-pool)`` PartitionSpecs of ``config``'s KV pools
        under this engine's plan; ``None`` without a multi-device plan.
        Replicated when the mesh spans processes (ISSUE 19: the
        rebind/donate contract stays rank-agnostic), else kv heads over
        the plan's head axis — the split the paged kernels run on."""
        plan = self._plan
        if plan is None or plan.mesh.devices.size == 1:
            return None
        if self._mp:
            from jax.sharding import PartitionSpec
            return PartitionSpec(), PartitionSpec()
        from .paged_attention import kv_pool_specs
        return kv_pool_specs(plan, config.num_attention_heads,
                             config.num_key_value_heads)

    def _place_cache(self, cache, config):
        """Commit freshly zeroed pool arrays (created on the default
        device) to their plan layout so the compiled steps can donate and
        rebind them without a re-layout."""
        specs = self._pool_specs(config)
        if specs is None:
            return
        import jax
        from jax.sharding import NamedSharding

        pool, scale = (NamedSharding(self._plan.mesh, s) for s in specs)

        def put(x, sharding):
            # a process-spanning mesh takes host values only (every rank
            # passes the same zeros: collective-free)
            return jax.device_put(np.asarray(x) if self._mp else x,
                                  sharding)

        cache.k = [put(x, pool) for x in cache.k]
        cache.v = [put(x, pool) for x in cache.v]
        cache.k_scale = [put(x, scale) for x in cache.k_scale]
        cache.v_scale = [put(x, scale) for x in cache.v_scale]

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------
    def _bucket_for(self, n):
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds the largest "
                         f"prefill bucket {self.prefill_buckets[-1]}")

    def _stage_request(self, req):
        """Pad the request's current prefix to its prefill bucket and start
        the H2D transfer (ingest thread / re-prefill staging)."""
        import jax

        from ...io.prefetch import np_pad_to_bucket
        from ...jit.cache import BucketSpec

        toks = req.tokens
        bucket = self._bucket_for(len(toks))
        spec = BucketSpec({1: (bucket,)})
        ids, _ = np_pad_to_bucket(toks[None].astype(np.int32), spec,
                                  lengths={1: len(toks)})
        ids_dev = self._g(ids) if self._mp else jax.device_put(ids)
        req._staged = (ids_dev, bucket, len(toks))

    def add_request(self, prompt_ids, sampling: SamplingParams | None = None,
                    arrival_t=None, deadline=None, tenant=None, tier=None):
        """Enqueue a prompt; returns the request id. Never blocks on pool
        exhaustion — the request queues until blocks free up.

        ``deadline`` is an absolute ``time.time()`` wall-clock deadline
        (ISSUE 12): an already-expired deadline raises
        :class:`RequestTimeoutError` HERE — before the request is
        registered, staged, or any allocator/scheduler state moves — and
        a deadline expiring later aborts the request at the next step
        (blocks freed, slot recycled, stream finished with reason
        ``"timeout"``).

        ``arrival_t`` is the time the request was DUE, in seconds on the
        ``time.perf_counter()`` clock: an open-loop caller that runs late
        passes the scheduled send time, and TTFT, the ``request.queued``
        span and ``serving_queue_wait_ms`` then count from it, so the wait
        a stall imposes on later requests is not hidden. Without it they
        count from the moment the engine accepted the request.

        ``tenant``/``tier`` (ISSUE 17) attach a QoS identity — defaults
        (``"default"``/latency) keep the exact pre-QoS FIFO behavior."""
        self._ensure_open()
        if deadline is not None and time.time() >= float(deadline):
            raise RequestTimeoutError(
                f"deadline {deadline} already expired at admission "
                f"(now={time.time():.3f}); request rejected before any "
                "block allocation", deadline=deadline)
        req = Request(prompt_ids, sampling, deadline=deadline,
                      tenant=tenant, tier=tier)
        self._check_admissible(req)
        # observability clock zero: TTFT, the queued span and the queue
        # wait measure from the due time if the caller gave one, else from
        # the moment the engine accepted the request
        req.t_submit = req.t_queue_start = (
            time.perf_counter_ns() if arrival_t is None
            else int(float(arrival_t) * 1e9))
        self._requests[req.rid] = req
        if self._ingest is not None:
            self._ingest.submit(req)
        else:
            self._stage_request(req)
            self.scheduler.waiting.append(req)
        return req.rid

    def configure_tenant(self, name, *, weight=1.0, rate_tokens_per_s=None,
                         window_s=1.0, host_blocks=None,
                         prefix_blocks=None):
        """Declare one tenant's QoS envelope (ISSUE 17) in one call:
        fair-share ``weight`` and leaky-bucket token-rate quota land in
        the scheduler, ``host_blocks`` caps its resident host-tier pages
        (requires a KV tier), and ``prefix_blocks`` caps how many
        device-pool prefix blocks it may keep published (over-share
        demotes its own oldest to the host tier, never other tenants').
        Unconfigured tenants serve at weight 1 with no quota — QoS stays
        fully off until the first call."""
        self._ensure_open()
        st = self.scheduler.configure_tenant(
            name, weight=weight, rate_tokens_per_s=rate_tokens_per_s,
            window_s=window_s)
        if host_blocks is not None:
            if self.kv_tier is None:
                raise ValueError(
                    "host_blocks needs a host tier; construct the engine "
                    "with kv_host_blocks=")
            self.kv_tier.set_tenant_share(name, host_blocks)
        if prefix_blocks is not None:
            if self.prefix_cache is None:
                raise ValueError(
                    "prefix_blocks needs prefix sharing; construct the "
                    "engine with enable_prefix_cache=True")
            self.prefix_cache.set_tenant_share(name, prefix_blocks)
        return st

    def _check_admissible(self, req):
        """Admission validation shared by ``add_request`` and
        ``add_request_with_pages`` (ISSUE 15): greedy-only under
        speculation, pool/length caps, re-prefill bucket coverage, sane
        budget — all typed, all BEFORE any request or allocator state
        moves. One copy, so the two admission doors can never drift."""
        if self._spec_k and req.sampling.do_sample:
            raise ValueError(
                "speculative decoding is greedy-only (the verify step "
                "accepts by argmax identity); submit do_sample requests "
                "to an engine without a draft_model")
        total = len(req.prompt) + req.sampling.max_new_tokens
        cap = min(self.max_model_len,
                  (self.cache.num_blocks - 1) * self.block_size)
        # the speculative verify window writes spec_k lookahead positions
        # past the final token — they must fit in the pool too
        if total + self._spec_k > cap:
            raise ValueError(
                f"request needs {total + self._spec_k} tokens (incl. "
                f"{self._spec_k} speculative lookahead) but the engine "
                f"caps at {cap} (max_model_len={self.max_model_len}, pool="
                f"{self.cache.num_blocks - 1} usable blocks x "
                f"{self.block_size})")
        # an evicted request re-prefills from its full prefix (up to
        # total-1 tokens): with custom prefill_buckets the largest rung
        # must cover that, or staging would fail mid-stream
        if total - 1 > self.prefill_buckets[-1]:
            raise ValueError(
                f"request may need a {total - 1}-token prefill (prompt + "
                f"re-prefill after eviction) but the largest prefill "
                f"bucket is {self.prefill_buckets[-1]}")
        if req.sampling.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")

    # -- disaggregated prefill/decode handoff (ISSUE 15) ----------------
    def export_kv_pages(self, rid):
        """Export a request's materialized KV pages (the prefill-worker
        side of the handoff): the pool content of its blocks holding the
        ``num_cached`` tokens written so far, scales included on int8
        pools. The request must have finished prefill (decode-ready) —
        exporting a half-prefilled request would hand off pages the
        first token was never sampled from."""
        if self._mp:
            raise ValueError(
                "export_kv_pages is not supported on a plan whose mesh "
                "spans multiple processes: pool pages cannot be fetched "
                "to one host (sharded disagg handoff is future work)")
        req = self._requests[rid]
        if req.finished or req.prefilling or req.num_cached < 1:
            raise ValueError(
                f"request {rid} is not decode-ready "
                f"(state={req.state}, prefilling={req.prefilling}); only "
                "a completed prefill exports pages")
        # a decode step in flight stays in flight: the gather is enqueued
        # behind it, and what that step wrote for this request lies at
        # ``num_cached``, past what the pages cover
        n_pages = -(-req.num_cached // self.block_size)
        return self.cache.export_request_pages(req.blocks[:n_pages],
                                               req.num_cached)

    def add_request_with_pages(self, prompt_ids, pages,
                               sampling: SamplingParams | None = None,
                               deadline=None, tenant=None, tier=None):
        """Admit a request whose prompt KV pages were computed by a
        prefill worker (the decode side of the disaggregated handoff):
        ``prompt_ids`` is the original prompt PLUS the first token the
        prefill worker sampled, and ``pages`` (an ``export_kv_pages``
        payload) covers every position but the last. Admission allocates
        blocks normally (queues on exhaustion, FIFO); the next ``step``
        imports the payload into them and the request decodes from its
        first step — no prefill graph runs, and greedy continuation is
        bit-identical to a colocated engine because the imported pages
        are byte-identical to what local prefill would have written.

        An expired ``deadline`` raises :class:`RequestTimeoutError` HERE,
        before any request or allocator state moves; a deadline expiring
        while the request waits for admission aborts it with the typed
        reason and the never-imported pages are simply dropped."""
        self._ensure_open()
        if self.prefill_only:
            raise ValueError("prefill_only engines never decode; "
                             "imported pages have nowhere to go")
        if deadline is not None and time.time() >= float(deadline):
            raise RequestTimeoutError(
                f"deadline {deadline} already expired at admission "
                f"(now={time.time():.3f}); imported pages rejected before "
                "any block allocation", deadline=deadline)
        req = Request(prompt_ids, sampling, deadline=deadline,
                      tenant=tenant, tier=tier)
        covered = int(pages["covered"])
        if covered != len(req.prompt) - 1:
            raise ValueError(
                f"pages cover {covered} tokens but the prompt has "
                f"{len(req.prompt)} — the handoff prompt is the original "
                "prompt plus the prefill worker's first sampled token, "
                "so coverage must be len(prompt) - 1")
        # full geometry validation (dtype/block_size/shapes/scale rows)
        # happens HERE, before the request exists — not at import time,
        # when blocks are already allocated and pools about to move
        n_payload = self.cache.validate_request_pages(pages)
        # ISSUE 20 read-back boundary: a sealed payload must verify
        # before admission — typed KVIntegrityError instead of decoding
        # from corrupt transferred pages (unsealed payloads pass)
        _verify_pages(pages, instance=self._name,
                      key=("import", req.rid))
        if n_payload != -(-covered // self.block_size):
            raise ValueError(
                f"pages hold {n_payload} blocks but cover {covered} "
                f"tokens ({-(-covered // self.block_size)} blocks at "
                f"block_size={self.block_size})")
        self._check_admissible(req)
        req.preloaded = pages
        req.t_submit = req.t_queue_start = time.perf_counter_ns()
        self._requests[req.rid] = req
        # no staging needed (nothing to prefill): straight to the queue
        self.scheduler.waiting.append(req)
        return req.rid

    def _adopt_preloaded(self, req):
        """Write a just-admitted preloaded request's imported pages into
        its allocated blocks (host-triggered, before this step's decode)
        and publish their identities to the prefix cache so later
        admissions can share them. One-shot: after this, the request is
        indistinguishable from one prefilled locally — an eviction
        re-prefills through the normal staged path. A decode step in
        flight stays in flight: the import is a program enqueued behind
        it, into blocks none of its standing rows holds."""
        pages = req.preloaded
        req.preloaded = None
        revived = req.revived_from_tier
        req.revived_from_tier = False
        t0 = time.perf_counter()
        self.cache.import_request_pages(req.blocks, pages)
        if revived:
            # tier revival (ISSUE 16): the session came back from host
            # RAM instead of re-prefilling
            _M_REVIVES.inc(instance=self._name)
            _M_REVIVE_BYTES.inc(
                sum(int(v.nbytes) for v in pages.values()
                    if isinstance(v, np.ndarray)), instance=self._name)
            _H_REVIVE_MS.observe((time.perf_counter() - t0) * 1e3,
                                 instance=self._name)
        if self.prefix_cache is not None:
            # sound because imported pages are byte-identical to local
            # prefill output (per-row quantization is pure)
            self.prefix_cache.register(req.tokens, req.blocks,
                                       req.num_cached, tenant=req.tenant)
        req.t_decode_start = time.perf_counter_ns()
        if _obs_trace.enabled():
            _obs_trace.add_complete(
                "request.import",
                getattr(req, "_t_admit", req.t_queue_start),
                req.t_decode_start, cat="request", tid=req.rid,
                args={"rid": req.rid, "engine": self._name,
                      "covered": req.num_cached})

    def request(self, rid):
        return self._requests[rid]

    def output_tokens(self, rid):
        """np prompt+generated tokens for a request."""
        r = self._requests[rid]
        return np.concatenate(
            [r.prompt, np.asarray(r.output_tokens, np.int32)])

    def release(self, rid):
        """Drop a FINISHED request's bookkeeping (prompt + output token
        arrays). A long-lived server must release requests once their
        outputs are delivered or host memory grows without bound —
        ``generate`` releases automatically; ``stream`` consumers that
        read tokens incrementally can release on the finished
        ``StepOutput``."""
        req = self._requests.get(rid)
        if req is None:
            return
        if not req.finished:
            raise ValueError(f"request {rid} is {req.state}; only "
                             "finished requests can be released")
        del self._requests[rid]

    def cancel(self, rid, reason="cancelled"):
        """Abort a live request: blocks freed (decref under sharing), its
        decode slot recycled for the next admission, and the request
        finishes with ``finish_reason() == reason``. No-op on unknown or
        already-finished ids (cancellation races are benign). Returns
        True when a live request was actually aborted."""
        req = self._requests.get(rid)
        if req is None or req.finished:
            return False
        self._abort(req, reason)
        return True

    def _abort(self, req, reason):
        self.scheduler.abort(req, reason)
        if reason == "timeout":
            _M_DEADLINE.inc(instance=self._name)

    def _expire_deadlines(self, outputs):
        """Abort every queued/running request whose deadline has passed
        (checked once per step, BEFORE admission and decode, so an
        expired request never takes blocks it is about to release). Each
        expiry emits a final ``StepOutput`` (token ``-1``, finished,
        reason ``"timeout"``) so stream consumers see the typed end of
        the partial stream."""
        now = time.time()
        for req in (list(self.scheduler.waiting)
                    + list(self.scheduler.running)):
            if req.deadline is not None and now >= req.deadline:
                self._abort(req, "timeout")
                outputs.append(StepOutput(req.rid, -1, True, "timeout"))

    def has_work(self):
        if self._closed:
            return False
        if self._ingest is not None and self._ingest.pending:
            return True
        # outputs of a step committed between two calls wait for the next
        return bool(self._committed) or self.scheduler.has_work()

    # ------------------------------------------------------------------
    # compiled graphs
    # ------------------------------------------------------------------
    def _head_fn(self, model):
        def _head(h):
            from ...nn import functional as F

            if model.lm_head is not None:
                return model.lm_head(h)
            return F.linear(h, model.llama.embed_tokens.weight.t())
        return _head

    @staticmethod
    def _arr(x):
        from ...core.tensor import Tensor

        return x._data if isinstance(x, Tensor) else x

    def _make_chunk_fn(self, model, params):
        """Pure chunk-prefill step over ``model``: ``(param_arrays,
        ids [1, C], start, true_upto, tables_row [max_pages], k_pools,
        v_pools, k_scales, v_scales) -> (logits [1, V] at absolute
        position true_upto-1, pools, scale pools)``. ``start`` is the
        block-aligned absolute offset of the chunk (0 for a whole-prompt
        prefill; the shared-prefix boundary or the previous chunk's end
        otherwise); queries attend causally over the request's pool pages
        [0, true_upto), laid out in a row for the chunk kernel (page by
        page through the multi-query kernel over int8 codes), so one graph
        per chunk-length bucket serves every offset. Quantized caches
        (non-empty scale lists) quantize each page's rows on write and
        store the per-row scales beside the codes (ISSUE 14).

        The layer body is the model's (``serve_layer``); what a layer
        writes and attends over is its ``ChunkAttnState``. A cache with a
        window or a state kind, or a model with counters, adds
        ``_graph_extras``' operands behind the pools — the request's window
        row, its slot (int32 ``[1]``; the state arrays come and go in the
        pools' places), the counter array — and one result, the counters."""
        from ...core.tensor import Tensor

        block_size = self.block_size
        _arr = self._arr
        layout = model.kv_layout()
        counter_names = _counter_names(model)
        keep_names = _keep_names(model)
        n_tail = self.cache.window.n_tail if self.cache.window else 0

        def chunk_pure(param_arrays, ids, start, true_upto, tables_row,
                       k_pools, v_pools, k_scales, v_scales, *extra):
            import jax
            import jax.numpy as jnp

            from .paged_attention import ChunkAttnState

            quantized, ks_in, vs_in = _scales_in(k_pools, v_pools,
                                                 k_scales, v_scales)
            window_row, slot, counters = extra if extra else (None,) * 3
            counts = {} if extra else None
            kept = {} if keep_names else None
            with _params_swapped(params, param_arrays):
                start = jnp.asarray(start, jnp.int32)
                upto = jnp.asarray(true_upto, jnp.int32)
                x = model.serve_embed(ids)
                new_k, new_v, new_ks, new_vs = [], [], [], []
                for i, (spec, kp, vp, ksc, vsc) in enumerate(zip(
                        layout, k_pools, v_pools, ks_in, vs_in)):
                    st = ChunkAttnState(
                        spec, block_size, start, upto, tables_row,
                        kp, vp, ksc, vsc, window_row=window_row,
                        n_tail=n_tail, counters=counts, slot=slot,
                        kept=kept)
                    x = model.serve_layer(i, x, st)
                    new_k.append(st.k_pool)
                    new_v.append(st.v_pool)
                    if quantized:
                        new_ks.append(st.k_scale)
                        new_vs.append(st.v_scale)
                h = model.serve_norm(x)
                h_arr = _arr(h)
                last = jax.lax.dynamic_slice(
                    h_arr, (0, upto - 1 - start, 0),
                    (1, 1, h_arr.shape[-1]))
                logits = model.serve_head(Tensor._wrap(last))
            out = (_arr(logits)[:, 0], new_k, new_v, new_ks, new_vs)
            if extra:
                out += (_add_counts(counters, counts, counter_names, False),)
            if keep_names:
                out += (_stack_kept(kept, keep_names),)
            return out

        return chunk_pure

    def _make_decode_core(self, model):
        """The traced one-token decode body, shared verbatim between the
        plain decode executable and the fused catch-up loop (ISSUE 16):
        the fused path must run the IDENTICAL op sequence per step or
        draft proposals — and therefore acceptance counts — would drift
        between modes. Assumes the caller is inside ``_params_swapped``.
        The layer body is the model's (``serve_layer``) over a
        ``DecodeAttnState`` a layer. ``wtables`` is the window kind's ring
        table, ``counts`` the dict the layers' counters land in, ``slots`` a
        state kind's slot a row."""
        block_size = self.block_size
        _arr = self._arr
        layout = model.kv_layout()

        def core(ids, positions, tables, k_pools, v_pools, ks_in, vs_in,
                 wtables=None, counts=None, slots=None, kept=None):
            from .paged_attention import DecodeAttnState

            quantized = ks_in[0] is not None if ks_in else False
            x = model.serve_embed(ids)
            new_k, new_v, new_ks, new_vs = [], [], [], []
            for i, (spec, kp, vp, ksc, vsc) in enumerate(zip(
                    layout, k_pools, v_pools, ks_in, vs_in)):
                st = DecodeAttnState(
                    spec, block_size, positions,
                    wtables if spec.kind == "window" else tables,
                    kp, vp, ksc, vsc, counters=counts, slots=slots,
                    kept=kept)
                x = model.serve_layer(i, x, st)
                new_k.append(st.k_pool)
                new_v.append(st.v_pool)
                if quantized:
                    new_ks.append(st.k_scale)
                    new_vs.append(st.v_scale)
            h = model.serve_norm(x)
            logits = model.serve_head(h[:, -1:])
            return _arr(logits)[:, 0], new_k, new_v, new_ks, new_vs

        return core

    def _make_decode_fn(self, model, params, feed_back=False):
        """Pure one-token decode over ``model``: ``(param_arrays,
        ids [B, 1], positions [B], tables [B, P], k_pools, v_pools,
        k_scales, v_scales) -> (logits [B, V], greedy tokens [B] int32,
        pools, scale pools)``. Writes each token at ``positions``, attends
        over ``positions+1`` ragged lengths. The greedy tokens are the
        logits' argmax taken in the graph (``greedy_tokens_in_graph``: the
        host sampler's choice, bit for bit), so that a step whose requests
        all decode greedily fetches ``[B]`` int32 and not ``[B, V]``
        float32. Quantized caches quantize the written row and
        store its per-head scale beside the codes (ISSUE 14). The extra
        operands and the extra result are ``_make_chunk_fn``'s, but the
        window row is empty here: with a window kind ``tables`` is
        ``_decode_tables``' ``[B, P + R]``, the rings behind the global
        table, so that a step puts one table and not two. With a state kind
        the slots are int32 ``[B]`` (``_state_slots``): where each row's
        state lies, the null slot for a dead row.

        With ``feed_back`` (the engine's own decode, ISSUE 28) ``ids`` is
        ``[B, 2]`` and one operand comes before the others behind the
        pools: the step before's greedy tokens ``[B]``, still on the
        device. A row whose second column is set takes its id from there
        and not from the first column, so a step can be enqueued before
        the host has seen the tokens it continues from. One executable
        serves both: a step whose ids all come from the host passes zeros."""
        from ...models.llama import greedy_tokens_in_graph

        core = self._make_decode_core(model)
        counter_names = _counter_names(model)
        keep_names = _keep_names(model)
        windowed, P = self.cache.window is not None, self.max_pages

        def decode_pure(param_arrays, ids, positions, tables,
                        k_pools, v_pools, k_scales, v_scales, *extra):
            if feed_back:
                import jax.numpy as jnp

                prev, *extra = extra
                ids = jnp.where(ids[:, 1:] != 0, prev[:, None], ids[:, :1])
            _, ks_in, vs_in = _scales_in(k_pools, v_pools, k_scales,
                                         v_scales)
            _, slots, counters = extra if extra else (None,) * 3
            counts = {} if extra else None
            kept = {} if keep_names else None
            wtables = None
            if windowed:        # ``_decode_tables``: global | rings
                tables, wtables = tables[:, :P], tables[:, P:]
            with _params_swapped(params, param_arrays):
                logits, new_k, new_v, new_ks, new_vs = core(
                    ids, positions, tables, k_pools, v_pools,
                    ks_in, vs_in, wtables=wtables, counts=counts,
                    slots=slots, kept=kept)
            out = (logits, greedy_tokens_in_graph(logits),
                   new_k, new_v, new_ks, new_vs)
            if extra:
                out += (_add_counts(counters, counts, counter_names, True),)
            if keep_names:
                out += (_stack_kept(kept, keep_names),)
            return out

        return decode_pure

    def _make_catchup_fn(self, model, params):
        """Fused ragged draft catch-up (ISSUE 16 perf satellite): one
        ``fori_loop`` graph that replays ``F`` feed tokens through the
        shared decode core — ``(param_arrays, ids [B, F],
        positions [B, F], tables, pools...) -> (last logits [B, V],
        pools...)`` — replacing ``F`` sequential dispatches of the
        single-token draft decode with ONE. Graph size is O(layers),
        independent of ``F``, so the doubling-ladder buckets stay cheap
        to compile. Rows shorter than ``F`` left-pad by repeating their
        first (token, position) feed: rewriting the same token at the
        same position is a deterministic no-op, so a padded replay leaves
        the pools a token-at-a-time replay would."""
        core = self._make_decode_core(model)

        def catchup_pure(param_arrays, ids, positions, tables,
                         k_pools, v_pools, k_scales, v_scales):
            import jax

            quantized, ks_in, vs_in = _scales_in(k_pools, v_pools,
                                                 k_scales, v_scales)
            with _params_swapped(params, param_arrays):
                def one(t, kps, vps, kss, vss):
                    ids_t = jax.lax.dynamic_slice_in_dim(ids, t, 1, axis=1)
                    pos_t = jax.lax.dynamic_slice_in_dim(
                        positions, t, 1, axis=1)[:, 0]
                    return core(ids_t, pos_t, tables, kps, vps, kss, vss)

                # step 0 outside the loop fixes the carry avals
                lg, kps, vps, kss, vss = one(0, k_pools, v_pools,
                                             ks_in, vs_in)
                if not quantized:
                    kss, vss = [], []

                def body(t, carry):
                    kps, vps, kss, vss, _ = carry
                    lg, kps, vps, kss, vss = one(
                        t, kps, vps,
                        kss if quantized else [None] * len(kps),
                        vss if quantized else [None] * len(vps))
                    if not quantized:
                        kss, vss = [], []
                    return (kps, vps, kss, vss, lg)

                kps, vps, kss, vss, lg = jax.lax.fori_loop(
                    1, ids.shape[1], body, (kps, vps, kss, vss, lg))
            return lg, kps, vps, kss, vss

        return catchup_pure

    def _make_verify_fn(self, model, params):
        """Pure speculative verify over ``model``: ``(param_arrays,
        ids [B, K+1], positions [B], tables [B, P], draft_toks [B, K],
        k_pools, v_pools, k_scales, v_scales) -> (accept_counts [B],
        next_tokens [B], pools, scale pools)``. ``ids[:, 0]`` is each
        request's last committed token at absolute position
        ``positions``; one batched multi-query paged-attention step
        scores all K+1 positions, writes their K/V, and counts in-graph
        how many draft tokens match the target's greedy argmax (the
        accept rule that keeps outputs bit-exact)."""
        from ...core.tensor import Tensor

        block_size = self.block_size
        _head = self._head_fn(model)
        _arr = self._arr

        def verify_pure(param_arrays, ids, positions, tables, draft_toks,
                        k_pools, v_pools, k_scales, v_scales):
            import jax
            import jax.numpy as jnp

            from ...models.llama import rope_rotate
            from ...ops import manipulation as M
            from .kv_cache import quantize_kv_rows
            from .paged_attention import paged_multiquery_attention

            quantized, ks_in, vs_in = _scales_in(k_pools, v_pools,
                                                 k_scales, v_scales)
            with _params_swapped(params, param_arrays):
                bsz, t_q = ids.shape
                x = model.llama.embed_tokens(Tensor._wrap(ids))
                cos_t = _arr(model.llama.rope_cos)
                sin_t = _arr(model.llama.rope_sin)
                pos_grid = (positions[:, None]
                            + jnp.arange(t_q, dtype=jnp.int32)[None])
                c = cos_t[pos_grid][:, :, None, :]
                sn = sin_t[pos_grid][:, :, None, :]
                new_k, new_v, new_ks, new_vs = [], [], [], []
                for layer, kp, vp, ksc, vsc in zip(
                        model.llama.layers, k_pools, v_pools, ks_in, vs_in):
                    attn = layer.self_attn
                    h = layer.input_layernorm(x)
                    q = M.reshape(attn.q_proj(h), [
                        bsz, t_q, attn.num_heads, attn.head_dim])
                    k = M.reshape(attn.k_proj(h), [
                        bsz, t_q, attn.num_kv_heads, attn.head_dim])
                    v = M.reshape(attn.v_proj(h), [
                        bsz, t_q, attn.num_kv_heads, attn.head_dim])

                    qa = rope_rotate(_arr(q), c, sn)
                    ka, va = rope_rotate(_arr(k), c, sn), _arr(v)
                    blk = tables[jnp.arange(bsz)[:, None],
                                 pos_grid // block_size]
                    off = pos_grid % block_size
                    if quantized:
                        qk, sk = quantize_kv_rows(ka)  # [B,T,Hkv,D]
                        qv, sv = quantize_kv_rows(va)
                    for i in range(bsz):
                        for t in range(t_q):
                            if quantized:
                                kp = jax.lax.dynamic_update_slice(
                                    kp, qk[i:i + 1, t:t + 1],
                                    (blk[i, t], off[i, t], 0, 0))
                                vp = jax.lax.dynamic_update_slice(
                                    vp, qv[i:i + 1, t:t + 1],
                                    (blk[i, t], off[i, t], 0, 0))
                                ksc = jax.lax.dynamic_update_slice(
                                    ksc, sk[i:i + 1, t:t + 1],
                                    (blk[i, t], off[i, t], 0))
                                vsc = jax.lax.dynamic_update_slice(
                                    vsc, sv[i:i + 1, t:t + 1],
                                    (blk[i, t], off[i, t], 0))
                            else:
                                kp = jax.lax.dynamic_update_slice(
                                    kp, ka[i:i + 1, t:t + 1].astype(kp.dtype),
                                    (blk[i, t], off[i, t], 0, 0))
                                vp = jax.lax.dynamic_update_slice(
                                    vp, va[i:i + 1, t:t + 1].astype(vp.dtype),
                                    (blk[i, t], off[i, t], 0, 0))
                    out = paged_multiquery_attention(
                        qa, kp, vp, tables, positions + t_q, positions,
                        scale=1.0 / math.sqrt(attn.head_dim),
                        k_scale=ksc, v_scale=vsc)
                    attn_out = attn.o_proj(
                        M.reshape(Tensor._wrap(out), [bsz, t_q, -1]))
                    x = x + attn_out
                    x = x + layer.mlp(layer.post_attention_layernorm(x))
                    new_k.append(kp)
                    new_v.append(vp)
                    if quantized:
                        new_ks.append(ksc)
                        new_vs.append(vsc)
                h = model.llama.norm(x)
                logits = _arr(_head(h))          # [B, K+1, V]
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # in-graph accept: 1s until the first draft/target
                # mismatch; next token = target argmax at the first
                # rejected position (or the bonus position on full
                # accept) — exactly sequential greedy, verified at once
                eq = (tgt[:, :t_q - 1] == draft_toks).astype(jnp.int32)
                acc = jnp.cumprod(eq, axis=1)
                counts = jnp.sum(acc, axis=1)
                nxt = jnp.take_along_axis(
                    tgt, counts[:, None], axis=1)[:, 0]
            return counts, nxt, new_k, new_v, new_ks, new_vs

        return verify_pure

    def _build_jits(self):
        import jax

        from ...distributed.plan import compile_step_with_plan

        # a chunk's ids out of the staged prompt with the offset an operand:
        # one executable a (staging bucket, rung), where a slice at a static
        # offset was one for every offset, and an offset no warm-up met
        # compiled inside a measured window
        self._chunk_ids = jax.jit(
            lambda ids, start, size: jax.lax.dynamic_slice_in_dim(
                ids, start, size, axis=1), static_argnames="size")

        # Under a multi-device plan every executable's pool outputs are
        # pinned to the layout the pools were committed to, so a donated
        # round-trip hands back the same layout instead of whatever GSPMD
        # propagated (a drift costs a recompile and a re-layout per
        # step). On a process-spanning mesh that layout is replicated and
        # the leading outputs (logits/tokens) are pinned replicated too:
        # every rank's host fetch must read the same value from its
        # addressable shard.
        pool_out = None
        specs = self._pool_specs(self.config)
        if specs is not None:
            pool, scale = specs
            pool_out = (pool if self._mp else None, pool, pool, scale, scale)
        # scale pools donate beside the payload pools (empty pytrees on
        # the fp path — a zero-leaf donation is a no-op)
        self._prefill_jit = compile_step_with_plan(
            self._make_chunk_fn(self.model, self._params), self._plan,
            name=self._prefill_name, donate_argnums=(5, 6, 7, 8),
            out_specs=pool_out)
        # the greedy tokens go back in as the next step's operand (ISSUE
        # 28): pinned replicated under a plan, where ``_prev_operand``
        # puts the zeros that stand in for them, so that either is the
        # same input layout to the one executable
        greedy_out = None
        if pool_out is not None:
            from jax.sharding import PartitionSpec
            greedy_out = (pool_out[0], PartitionSpec()) + pool_out[1:]
        self._decode_jit = compile_step_with_plan(
            self._make_decode_fn(self.model, self._params, feed_back=True),
            self._plan, name=self._decode_name, donate_argnums=(4, 5, 6, 7),
            out_specs=greedy_out)
        if self.draft_model is not None:
            self._draft_prefill_jit = compile_step_with_plan(
                self._make_chunk_fn(self.draft_model, self._draft_params),
                self._plan, name=self._draft_prefill_name,
                donate_argnums=(5, 6, 7, 8))
            self._draft_decode_jit = compile_step_with_plan(
                self._make_decode_fn(self.draft_model, self._draft_params),
                self._plan, name=self._draft_decode_name,
                donate_argnums=(4, 5, 6, 7))
            self._verify_jit = compile_step_with_plan(
                self._make_verify_fn(self.model, self._params), self._plan,
                name=self._verify_name, donate_argnums=(5, 6, 7, 8))

    def _catchup_jit(self, F):
        """The fused catch-up executable for feed-length bucket ``F``
        (compiled on first use per rung; the fori_loop body makes each
        rung's graph O(layers), so the ladder stays cheap)."""
        jit = self._catchup_jits.get(F)
        if jit is None:
            from ...distributed.plan import compile_step_with_plan
            jit = compile_step_with_plan(
                self._make_catchup_fn(self.draft_model,
                                      self._draft_params),
                self._plan, name=f"{self._draft_decode_name}_catchup{F}",
                donate_argnums=(4, 5, 6, 7))
            self._catchup_jits[F] = jit
        return jit

    # ------------------------------------------------------------------
    # the scheduler tick
    # ------------------------------------------------------------------
    def _tables(self, rows=None):
        """Device block-table array for the decode-ready slots, cached
        against the scheduler's table version + slot readiness (ISSUE 11
        satellite: steady-state decode re-uploads nothing). Slots that are
        empty OR still mid-prefill map to the null block: the decode graph
        writes a K/V row for EVERY batch row, and an inactive row's write
        must land in the null block — pointing it at a prefilling
        request's real blocks would corrupt its just-written pages. So
        does a slot the step leaves out: ``rows`` (``_dispatch_decode``'s
        form) names the slots of a step that does not run every ready
        one, a step dispatched ahead without the rows that finish on the
        step before it."""
        sched = self.scheduler
        if rows is None:
            mask = tuple(r is not None and not r.prefilling
                         for r in sched.slots)
        else:
            live = {row[0] for row in rows}
            mask = tuple(i in live for i in range(len(sched.slots)))
        key = (sched.version, mask)
        if key != self._tables_version:
            lists = [(r.blocks if ok else [])
                     for ok, r in zip(mask, sched.slots)]
            tbl = self.cache.table_array(lists, self.max_pages)
            if self._mp:
                tbl = self._g(np.asarray(tbl))
            self._tables_dev = tbl
            self._tables_version = key
        return self._tables_dev

    def _decode_tables(self, rows):
        """The decode graph's table operand for ``rows`` (``[(slot, request,
        position the step writes, ...)]``). Without a window kind that is
        ``_tables(rows)``. With one it is the global table and the rings
        side by side, ``[B, P + R]`` in ONE put (the graph cuts it in two),
        after turning every row's ring to the page its token lands in: the
        page that fell out of the window goes back to the allocator here
        (a step still in flight may be reading it: whatever writes it next
        is enqueued behind that step). The host copy is kept and only what
        moved is rewritten: a row of the global table grows at its end
        (nothing with a window kind shares or copies a block), a ring turns
        once a page; a change of the rows' slots rewrites it all."""
        window = self.cache.window
        if window is None:
            return self._tables(rows)
        P, bs = self.max_pages, self.block_size
        host, known = self._tables_host, self._tables_known
        if host is None:
            host = self._tables_host = np.zeros(
                (self.max_batch_size, P + window.ring), np.int32)
            known = self._tables_known = [0] * self.max_batch_size
        mask = tuple((row[0], row[1].rid, row[3]) for row in rows)
        fresh = mask != self._tables_mask
        if fresh:
            host[:] = 0
            known[:] = [0] * len(known)
            self._tables_mask = mask
        moved = fresh
        for i, req, pos, _ in rows:
            n = min(len(req.blocks), P)
            if n != known[i]:
                host[i, known[i]:n] = req.blocks[known[i]:n]
                known[i] = n
                moved = True
            if fresh or pos % bs == 0:
                page = pos // bs
                window.ensure(req.rid, page, page)
                host[i, P:] = window.table_row(req.rid)
                moved = True
        if moved:
            # a copy: the CPU backend may keep the host array it is given
            self._tables_dev = self._g(host.copy())
        return self._tables_dev

    def _drain_cow(self):
        """Execute queued copy-on-write page copies (device-side) before
        the next pool write can touch the replaced blocks. No decode step
        is in flight here: ``ensure_decode_room``, which queues them, runs
        only in a call that found none, and a step is dispatched ahead
        only where none of its rows needs a copy (``reserve_ahead``)."""
        for src, dst in self.scheduler.pending_cow:
            self.cache.copy_block(src, dst)
            if self.draft_model is not None:
                self.draft_cache.copy_block(src, dst)
        self.scheduler.pending_cow.clear()

    def _run_chunk(self, req, start, take, outputs, owed):
        """One block-aligned prefill chunk: materialize ``take`` tokens of
        ``req`` starting at ``start`` in the pool(s). The final chunk's
        last-position logits are the request's first token. With no
        decode step of this call's in flight (``owed`` is None) they are
        fetched and the token emitted here, at once, under a phase of
        their own (``engine.prefill.first_token``); beside one, the
        request and the logits, still on the device, go on ``owed`` and
        ``_step`` fetches them behind its decode dispatch
        (``_first_tokens``, ISSUE 34). Until then the request stays
        ``prefilling``, so nothing counts it among the rows to decode."""
        self._phases.begin("engine.prefill")
        staged = getattr(req, "_staged", None)
        if staged is None or staged[2] != req.prefill_upto:
            self._stage_request(req)  # re-prefill after eviction
            staged = req._staged
        ids_dev, bucket, _true_len = staged
        # chunk length: always a LADDER RUNG (one compiled graph per rung
        # — an arbitrary C = bucket - start remainder would compile a
        # fresh executable per distinct prefix-match offset, the
        # recompile-per-shape cliff). When no rung covering ``take`` fits
        # the staged room, cap this chunk at the largest rung that does;
        # the remainder continues next step (progress >= one block).
        room = bucket - start
        C = None
        for b in self.prefill_buckets:
            if b >= take and b <= room:
                C = b
                break
        if C is None:
            C = max(b for b in self.prefill_buckets if b <= room)
            take = min(take, C)
        tables_row = np.zeros(self.max_pages, np.int32)
        nblk = min(len(req.blocks), self.max_pages)
        tables_row[:nblk] = req.blocks[:nblk]
        tables_dev = self._g(tables_row)
        start_a, upto_a = np.int32(start), np.int32(start + take)
        if self._mp:
            # scalars too: a host scalar beside global-mesh arrays would
            # make jit refuse the mixed-device call (the staged ids are
            # already replicated on the global mesh)
            start_a, upto_a = self._g(start_a), self._g(upto_a)
        ids_chunk = self._chunk_ids(ids_dev, start_a, size=C)
        cache = self.cache
        window_row = slot = None
        if cache.window is not None:
            # the ring turns here: the pages behind the chunk's newest go
            # back to the allocator before the chunk is dispatched
            window_row = self._g(cache.window.chunk_row(
                req.rid, start, start + take, C // self.block_size))
        if cache.state_slots is not None:
            # a request's state lies in its decode slot from its first
            # chunk on; a chunk at ``start == 0`` does not read what the
            # slot held (``ChunkAttnState.scan``)
            slot = self._g(np.asarray(
                [self.scheduler.slots.index(req)], np.int32))
        last = start + take >= req.prefill_upto
        # what this chunk does, said where it is known (ISSUE 35): ``take``
        # real tokens from ``start`` in a graph of ``C``
        said = ({"rid": req.rid, "start": start, "tokens": take,
                 "padded": C, "last": int(last)}
                if _obs_trace.live() else None)
        with _obs_trace.span("engine.prefill.chunk", cat="engine",
                             args=said):
            (logits, cache.k, cache.v, cache.k_scale, cache.v_scale,
             *tail) = self._prefill_jit(
                    [p._data for p in self._params], ids_chunk,
                    start_a, upto_a, tables_dev,
                    cache.k, cache.v, cache.k_scale, cache.v_scale,
                    *self._graph_extras(window_row, slot))
        kept = tail.pop() if self._keep_names else None
        if tail:
            self._counters_dev = tail[0]
        if kept is not None and self.capture_logits:
            if start == 0:
                req.kept = {}         # recomputed from its first token
            for name, rows in kept.items():
                req.kept.setdefault(name, []).append(
                    self._fetch(rows)[:, :take])
        if self.draft_model is not None:
            # mirror every target chunk into the draft pools: the draft
            # proposes continuations over the same block tables, so its
            # cache must hold the same prefix
            dc = self.draft_cache
            (_, dc.k, dc.v, dc.k_scale, dc.v_scale) = \
                self._draft_prefill_jit(
                    [p._data for p in self._draft_params], ids_chunk,
                    np.int32(start), np.int32(start + take), tables_dev,
                    dc.k, dc.v, dc.k_scale, dc.v_scale)
            req.draft_cached = start + take
        req.num_cached = start + take
        _M_PREFILL_CHUNKS.inc(instance=self._name)
        if self._chunks_in_a_row:
            _M_PREFILL_IN_A_ROW.inc(instance=self._name)
        _M_PREFILL_TOKENS.inc(take, instance=self._name)
        _M_PREFILL_PADDED.inc(C, instance=self._name)
        # QoS accounting (ISSUE 17): prefill work charges the tenant's
        # quota/vtime as it is SERVED, chunk by chunk
        self.scheduler.note_served(req, take)
        if self.prefix_cache is not None:
            # publish the identity of every full block now materialized so
            # later admissions (and this request's own re-prefill after an
            # eviction) can share them
            self.prefix_cache.register(req.tokens, req.blocks,
                                       req.num_cached, tenant=req.tenant)
        if last:
            self.stats_extra["prefills"] += 1
            _M_PREFILLS.inc(instance=self._name)
            if owed is None:
                self._phases.begin(
                    "engine.prefill.first_token",
                    {"requests": 1, "behind": 0}
                    if _obs_trace.live() else None)
                self._first_token(req, logits, bucket, outputs)
            else:
                owed.append((req, logits, bucket))

    def _first_token(self, req, logits, bucket, outputs):
        """The host's end of a prefill: fetch the last chunk's logits (the
        sync point), emit the first token from them, and the request is
        ready to decode; its prefill span closes here."""
        req.prefilling = False
        outputs.extend(self._emit(req, self._fetch(logits)[0]))
        req.t_decode_start = time.perf_counter_ns()
        if _obs_trace.enabled():
            _obs_trace.add_complete(
                "request.prefill",
                getattr(req, "_t_admit", req.t_queue_start),
                req.t_decode_start, cat="request", tid=req.rid,
                args={"rid": req.rid, "engine": self._name,
                      "bucket": bucket, "true_len": req.prefill_upto})

    def _first_tokens(self, owed, outputs, behind):
        """Empty ``owed``, the last chunks this call ran beside a decode
        step in flight: one ``engine.prefill.first_token`` in the call,
        after its decode dispatch. ``behind`` says that the dispatch
        enqueued a step behind the chunks, so that the device has work
        while the host waits for these logits, emits and prepares the step
        after."""
        self._phases.begin(
            "engine.prefill.first_token",
            {"requests": len(owed), "behind": int(behind)}
            if _obs_trace.live() else None)
        if behind:
            _M_PREFILL_BEHIND.inc(len(owed), instance=self._name)
        for req, logits, bucket in owed:
            self._first_token(req, logits, bucket, outputs)
        owed.clear()

    def step(self):
        """One engine tick: drain ingest, admit, advance chunked prefills
        under the token budget, one decode (or speculative verify) for all
        decode-ready slots. Returns the ``StepOutput`` tokens produced:
        call k returns step k's tokens, also where step k was enqueued by
        call k-1 and step k+1 is enqueued by this one (``_dispatch_ahead``),
        and before them the first token of every request whose last chunk
        ran in this call, also where its logits were fetched behind step
        k+1's dispatch (``_first_tokens``): such a request decodes first in
        step k+2.

        The call is one ``engine.step`` span cut into the phases of
        ``_StepPhases``; each carries the instance's name and, once the
        tick is known to have work, the step's number."""
        self._ensure_open()
        if self._decode_jit is None:
            self._build_jits()
        phases = self._phases
        phases.args = ({"engine": self._name} if _obs_trace.live()
                       else None)
        with _obs_trace.span("engine.step", cat="engine", args=phases.args):
            try:
                outputs = self._step(phases)
            finally:
                phases.end()
        if self._committed:
            # a step ``_drain`` committed between two calls (a state kind)
            outputs, self._committed = self._committed + outputs, []
        return outputs

    def _step(self, phases):
        sched = self.scheduler
        phases.begin("engine.admit")
        if self._ingest is not None:
            # block (briefly) only when the scheduler would otherwise spin
            # empty while requests are in flight on the ingest thread
            for req in self._ingest.drain(wait=not sched.has_work()):
                # a request cancelled/expired while still on the ingest
                # thread is already FINISHED — queueing it would let
                # pick_prefills admit a dead request
                if req.finished:
                    continue
                if not hasattr(req, "_staged"):  # ingest thread died
                    self._stage_request(req)
                sched.waiting.append(req)
        outputs = []
        # deadline scan BEFORE admission/decode: an expired request must
        # never be admitted or decoded one last time, and its freed
        # blocks/slot are available to this very step's admissions
        self._expire_deadlines(outputs)
        if not sched.has_work():
            # a step in flight for requests that are all gone by now
            self._drain("idle")
            return outputs
        self.stats_extra["steps"] += 1
        if phases.args is not None:
            phases.args["step"] = self.stats_extra["steps"]

        # -- admission ---------------------------------------------------
        for slot, req in sched.pick_prefills():
            # queued->running transition: the span closes here, at a point
            # where the host is already doing admission bookkeeping
            req._t_admit = time.perf_counter_ns()
            _H_QUEUE_WAIT.observe((req._t_admit - req.t_queue_start) / 1e6,
                                  instance=self._name)
            if _obs_trace.enabled():
                _obs_trace.add_complete(
                    "request.queued", req.t_queue_start, req._t_admit,
                    cat="request", tid=req.rid,
                    args={"rid": req.rid, "engine": self._name,
                          "evictions": req.evictions})
            if req.preloaded is not None:
                # disaggregated handoff OR tier revival: imported pages
                # land in the freshly allocated blocks before this step
                # decodes
                self._adopt_preloaded(req)
        self._drain_revives()

        # -- chunked prefill (budgeted; interleaves with decode below) ---
        # this call's decode step, where the last call dispatched it ahead
        # and a row of it still stands (no chunk changes that). Beside it a
        # last chunk's first token is owed until the decode dispatch below
        # (ISSUE 34); with none it is fetched where the chunk is enqueued
        cur = self._take_ahead()
        owed = None if cur is None else []
        for req, start, take in sched.prefill_work(
                self.max_prefill_tokens_per_step,
                align=self.prefill_buckets[0]):
            self._run_chunk(req, start, take, outputs, owed)

        if self.prefill_only:
            # disaggregated prefill worker: decode-ready requests wait
            # for export_kv_pages + cancel; nothing decodes here
            phases.begin("engine.bookkeeping")
            self._update_gauges()
            return outputs

        # -- decode ------------------------------------------------------
        # this call's decode step is the one the last call dispatched ahead
        # (``cur``, taken above); else it is made now, as ever. Either way
        # the NEXT one is enqueued behind it before its tokens are fetched,
        # where ``_dispatch_ahead`` finds it can be, so that fetch, emit and
        # the next call's admission run beside the device and not between
        # two of its steps (ISSUE 28). The first
        # tokens this call's last chunks owe are fetched behind that
        # dispatch too (ISSUE 34): the device's queue is then [this step]
        # [the chunks] [the next step] while the host waits for a chunk's
        # logits, and the new requests join the step after the next
        phases.begin("engine.decode.prepare")
        if cur is None:
            sched.ensure_decode_room(extra=self._spec_k)
            self._drain_cow()
            ready = [(i, r) for i, r in enumerate(sched.slots)
                     if r is not None and not r.prefilling]
            if ready and self._spec_k:
                _M_DECODE_SYNC.inc(instance=self._name, reason="path")
                self._spec_step(ready, outputs)
            elif ready:
                cur = self._dispatch_decode(
                    [(i, r, r.num_cached, r.admit_seq) for i, r in ready],
                    (), any(r.sampling.do_sample for _, r in ready),
                    self._sync_reason or "idle")
                self._sync_reason = None
                phases.begin("engine.decode.prepare")
        if cur is not None:
            self._ahead = self._dispatch_ahead(cur)
            if owed:
                self._first_tokens(owed, outputs, self._ahead is not None)
                if self._ahead is None:
                    # nothing could go ahead of that fetch (no row of
                    # ``cur`` goes on, or no room): the order it had
                    # before, the new rows joining the next step now
                    phases.begin("engine.decode.prepare")
                    self._ahead = self._dispatch_ahead(cur)
            self._emit_decode(cur, outputs)
            if self._ahead is not None and not any(sched.slots):
                # every request ended on this step (EOS: not seen ahead)
                self._drain("idle")
        phases.begin("engine.bookkeeping")
        self._maybe_autosave_store()
        self._update_gauges()
        return outputs

    # -- decode dispatch-ahead (ISSUE 28) -------------------------------
    def _drain(self, reason="drain"):
        """Be rid of the decode step in flight, if there is one, so that the
        next call dispatches with nothing in flight.

        Without a state kind the step is FORGOTTEN: its tokens are dropped
        and no ``num_cached`` moves, so the next call makes the same step
        again. What the forgotten step wrote, each row at its request's own
        next position, that step writes again, the same. For a reload of
        the weights (the step ran on the old ones), ``close``, and a call
        that finds nothing left to run.

        With a state kind (ISSUE 33) a forgotten step would be applied
        twice: ``h <- a h + b`` is no write at a position. The step is
        COMMITTED instead: its rows that still stand are fetched and emitted
        as a call of ``step`` would have, here, and the outputs are kept for
        the next call to hand out first (``_committed``). A reload of the
        weights therefore keeps the token the old weights made, as it keeps
        the pages and the states they made; a step whose requests are all
        gone has no row that stands and is dropped as ever.

        Page import and export, tier revival and a store save do NOT
        drain: the pools are arrays handed from program to program, so
        their gathers and scatters are enqueued behind the step in flight,
        and its writes lie past what they cover (``DESIGN_DECISIONS.md``)."""
        if self.cache.state_slots is not None and self._ahead is not None:
            cur = self._take_ahead()
            if cur is not None:
                # between two calls of ``step`` (inside one, ``_drain`` is
                # reached only with no request left, so no row stands): the
                # fetch and the emit are a step's, under a span of its own
                cur.how = "commit"
                phases = self._phases
                phases.args = ({"engine": self._name}
                               if _obs_trace.live() else None)
                with _obs_trace.span("engine.step", cat="engine",
                                     args=phases.args):
                    try:
                        self._emit_decode(cur, self._committed)
                    finally:
                        phases.end()
                self._sync_reason = reason
            return
        a, self._ahead = self._ahead, None
        if a is not None:
            _M_ROWS_DISCARDED.inc(len(a.rows), instance=self._name)
            self._sync_reason = reason

    def _take_ahead(self):
        """The step in flight as THIS call's decode, cut down to the rows
        that still stand (``_DecodeInFlight.standing``); the others are
        discarded here: token dropped, ``num_cached`` as it was. None, and
        the call decodes as ever, if nothing is in flight or nothing of it
        stands."""
        a, self._ahead = self._ahead, None
        if a is None:
            return None
        rows = a.standing(self.scheduler.slots)
        if len(rows) != len(a.rows):
            _M_ROWS_DISCARDED.inc(len(a.rows) - len(rows),
                                  instance=self._name)
            a.rows = rows
        return a if rows else None

    def _prev_operand(self):
        """Zeros in the place of the step before's tokens, for a step
        whose ids all come from the host; laid out as those tokens are."""
        if self._no_prev is None:
            z = np.zeros(self.max_batch_size, np.int32)
            if self._plan is not None and self._plan.mesh.devices.size > 1:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                self._no_prev = jax.device_put(
                    z, NamedSharding(self._plan.mesh, PartitionSpec()))
            else:
                self._no_prev = self._g(z)
        return self._no_prev

    def _decode_args(self, ids, positions, tables, prev=None, slots=None):
        """The decode executable's operands, in its order: ``ids [B, 2]``,
        ``positions [B]``, the tables, the pools, the step before's greedy
        tokens (zeros where there is none), then ``_graph_extras`` (with a
        state kind: ``slots``)."""
        c = self.cache
        return ([p._data for p in self._params], ids, positions, tables,
                c.k, c.v, c.k_scale, c.v_scale,
                self._prev_operand() if prev is None else prev,
                *self._graph_extras(slots=slots))

    def _state_slots(self, rows):
        """With a state kind: where each row of the batch reads and writes
        its state in a decode step for ``rows``, int32 ``[B]``. A live row's
        slot is its own; every other row (an empty slot, a slot mid-prefill,
        a row the step leaves out) is pointed at the null slot, the last, as
        ``_tables`` points its pages at the null block: the decode graph
        updates a state for EVERY row, and a step between two chunks of a
        request's prefill must not advance that request's state. Kept on
        the device while the live rows stay the same."""
        if self.cache.state_slots is None:
            return None
        live = tuple(row[0] for row in rows)
        if live != self._slots_live:
            slots = np.full(self.max_batch_size, self.max_batch_size, np.int32)
            slots[list(live)] = live
            self._slots_dev, self._slots_live = self._g(slots), live
        return self._slots_dev

    def decode_abstract_args(self):
        """``_decode_args`` as ``ShapeDtypeStruct``s, for lowering the decode
        executable without running a step (``chip_smoke.py`` reads its
        compiled text; the live pools are donated). An array on one device
        stays unplaced, as jit treats it at a call."""
        import jax

        if self._decode_jit is None:
            self._build_jits()
        B, window = self.max_batch_size, self.cache.window
        width = self.max_pages + (window.ring if window is not None else 0)
        args = self._decode_args(
            self._g(np.zeros((B, 2), np.int32)),
            self._g(np.zeros(B, np.int32)),
            self._g(np.zeros((B, width), np.int32)),
            slots=self._state_slots(()))
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=(x.sharding if len(x.sharding.device_set) > 1
                          else None)), tuple(args))

    def _dispatch_decode(self, rows, fed, sampled, how, prev=None):
        """Enqueue one decode step for ``rows`` (``[(slot, request,
        position, admit_seq)]``) and return it as a ``_DecodeInFlight``.
        The slots in ``fed`` take their input id from ``prev``, the greedy
        tokens of the step before, on the device; the others from the
        request's last token on the host. Called inside
        ``engine.decode.prepare``; leaves ``engine.decode.dispatch`` open."""
        B = self.max_batch_size
        ids = np.zeros((B, 2), np.int32)
        positions = np.zeros(B, np.int32)
        for i, req, pos, _ in rows:
            if i in fed:
                ids[i, 1] = 1
            else:
                ids[i, 0] = req.last_token
            positions[i] = pos
        c = self.cache
        args = self._decode_args(self._g(ids), self._g(positions),
                                 self._decode_tables(rows), prev,
                                 self._state_slots(rows))
        self._phases.begin("engine.decode.dispatch")
        (logits, greedy, c.k, c.v, c.k_scale, c.v_scale,
         *tail) = self._decode_jit(*args)
        kept = tail.pop() if self._keep_names else None
        if tail:
            self._counters_dev = tail[0]
        return _DecodeInFlight(rows, logits, greedy, sampled, how, kept)

    def _dispatch_ahead(self, cur):
        """Enqueue the decode step AFTER ``cur`` while ``cur`` is still the
        device's, if what the engine can see allows it; returns it, or None
        with the reason kept for the counter. It runs the rows of ``cur``
        that ``cur``'s token does not finish by length, each a position on
        and fed by ``cur``'s greedy tokens without their visiting the
        host, and the rows that became ready since: their first token is
        on the host, fetched by the call before this one behind its own
        dispatch (``_first_tokens``) or, where that call had nothing in
        flight, by this one at its last chunk. A request whose last chunk
        ran in this call beside ``cur`` is still ``prefilling`` here and
        has no row: its pages and its state are the chunk's until the
        step after this one. Not dispatched: beside a row the host samples
        for; where room for it takes an eviction or a copy
        (``Scheduler.reserve_ahead``: the next call's
        ``ensure_decode_room`` does those, with nothing in flight); on a
        mesh that spans processes, whose ranks are held in step call by
        call. Stops and EOS cannot be seen ahead: such a row is run,
        and discarded when the next call finds its request gone."""
        if self._mp:
            self._sync_reason = "path"
            return None
        if cur.sampled:
            self._sync_reason = "sampled"
            return None
        wrote = {row[0]: row[2] for row in cur.rows}
        rows, fed = [], set()
        for i, req in enumerate(self.scheduler.slots):
            if req is None or req.prefilling:
                continue
            s = req.sampling
            if s.do_sample:
                self._sync_reason = "sampled"
                return None
            pos = wrote.get(i)
            if pos is None:
                rows.append((i, req, req.num_cached, req.admit_seq))
            elif len(req.output_tokens) + 1 < s.max_new_tokens:
                rows.append((i, req, pos + 1, req.admit_seq))
                fed.add(i)
        if not rows:
            return None
        if not self.scheduler.reserve_ahead([(r[1], r[2]) for r in rows]):
            self._sync_reason = "evict"
            return None
        self._sync_reason = None    # of a first try in this call (``_step``)
        return self._dispatch_decode(rows, fed, False, "ahead",
                                     prev=cur.greedy)

    def _emit_decode(self, cur, outputs):
        """Fetch ``cur``'s result and commit a token a row. A step whose
        rows all decode greedily fetches its ``[B]`` int32 tokens, and with
        ``capture_logits`` on (read here, so it may be flipped between
        calls) the ``[B, V]`` rows beside them for ``last_logits`` and what
        the layers kept (``Request.kept``): the token is the graph's argmax
        either way. A step with a sampled row fetches the rows and the host
        chooses every token from them."""
        phases = self._phases
        phases.begin("engine.decode.fetch")
        greedy = None if cur.sampled else self._fetch(cur.greedy)
        logits = (self._fetch(cur.logits)
                  if cur.sampled or self.capture_logits else None)
        kept = ({n: self._fetch(a) for n, a in cur.kept.items()}
                if cur.kept is not None and self.capture_logits else {})
        phases.begin("engine.decode.emit")
        inst = self._name
        _M_HOST_SYNCS.inc(instance=inst)
        _M_FETCH_BYTES.inc(
            (0 if greedy is None else greedy.nbytes)
            + (0 if logits is None else logits.nbytes), instance=inst)
        if cur.how == "ahead":
            _M_DECODE_AHEAD.inc(instance=inst)
        else:
            _M_DECODE_SYNC.inc(instance=inst, reason=cur.how)
        for i, req, _, _ in cur.rows:
            req.num_cached += 1
            for name, rows in kept.items():
                req.kept.setdefault(name, []).append(rows[:, i:i + 1])
            if greedy is None:
                outputs.extend(self._emit(req, logits[i]))
                continue
            if logits is not None:
                req.last_logits = logits[i]
            outputs.extend(self._emit_token(req, greedy[i]))

    def _drain_revives(self):
        """Land this step's host-tier prefix hits (queued by the
        scheduler's ``match_with_tier``) in their freshly allocated
        blocks and publish the chain identities so the NEXT admission
        shares them device-side. A hash that vanished from the tier
        between match and drain (LRU pressure from a same-step spill)
        degrades to prefilling that span — and everything after it, since
        a chain with a hole is no chain."""
        sched = self.scheduler
        if not sched.pending_revive:
            return
        # gather each request's revivable span first, then land it as ONE
        # batched import: a functional pool update copies the whole pool,
        # so importing block-by-block would cost O(span * pool) instead
        # of O(pool)
        spans = {}  # rid -> (req, [(block, h, pages), ...])
        dead = set()  # rids whose chain broke mid-revive
        for req, block, h in sched.pending_revive:
            if req.finished:
                # aborted between match and drain (deadline expiry): its
                # blocks are already freed, so indexing them would throw.
                # ``Scheduler.abort`` purges these entries and their tier
                # pins itself; this is belt-and-braces for direct aborts.
                self.kv_tier.pop_prefix(h)
                continue
            idx = req.blocks.index(block)
            if req.rid in dead:
                req.num_cached = min(req.num_cached, idx * self.block_size)
                self.kv_tier.pop_prefix(h)  # unreachable behind the hole
                continue
            pages = self.kv_tier.pop_prefix(h)
            if pages is None:
                dead.add(req.rid)
                req.num_cached = min(req.num_cached, idx * self.block_size)
                continue
            spans.setdefault(req.rid, (req, []))[1].append((block, h,
                                                            pages))
        sched.pending_revive.clear()
        for req, parts in spans.values():
            t0 = time.perf_counter()
            blocks = [b for b, _, _ in parts]
            merged = dict(parts[0][2])
            merged["covered"] = len(parts) * self.block_size
            # each part's seal was verified at pop_prefix; the merged
            # span is a fresh in-memory dict, not a stored payload
            merged.pop("crc", None)
            if len(parts) > 1:
                for key in ("k", "v", "k_scale", "v_scale"):
                    if key in merged:
                        merged[key] = np.concatenate(
                            [p[key] for _, _, p in parts], axis=1)
            self.cache.import_request_pages(blocks, merged)
            for b, h, _ in parts:
                self.prefix_cache.adopt(b, h, tenant=req.tenant)
            nbytes = sum(int(v.nbytes) for v in merged.values()
                         if isinstance(v, np.ndarray))
            _M_REVIVES.inc(len(parts), instance=self._name)
            _M_REVIVE_BYTES.inc(nbytes, instance=self._name)
            _H_REVIVE_MS.observe((time.perf_counter() - t0) * 1e3,
                                 instance=self._name)

    def _update_gauges(self):
        # utilization gauges: free-list arithmetic the host already holds
        usable = max(self.cache.num_blocks - 1, 1)
        self._page_steps[0] += usable - self.cache.allocator.num_free
        if self.cache.window is not None:
            self._page_steps[1] += self.cache.window.blocks_in_use
        if self.cache.state_slots is not None:
            self._state_slot_steps += len(self.scheduler.running)
        _G_KV_UTIL.set(1.0 - self.cache.allocator.num_free / usable,
                       instance=self._name)
        _G_OCCUPANCY.set(len(self.scheduler.running) / self.max_batch_size,
                         instance=self._name)
        if self.cache.quantized:
            _G_QUANT_BLOCKS.set(usable - self.cache.allocator.num_free,
                                instance=self._name)

    # ------------------------------------------------------------------
    # speculative decoding
    # ------------------------------------------------------------------
    def _draft_propose(self, ready, tables):
        """Catch the draft pools up to every request's committed tokens,
        then propose ``spec_k`` greedy draft tokens per request. Returns
        drafts [B, K] (rows of non-ready slots are zeros/ignored)."""
        import jax.numpy as jnp

        B, K = self.max_batch_size, self._spec_k
        toks = {r.rid: r.tokens for _, r in ready}
        feeds = {}
        F = 1
        for _, r in ready:
            lo = min(r.draft_cached, r.num_tokens - 1)
            fs = list(range(lo, r.num_tokens))
            feeds[r.rid] = fs
            F = max(F, len(fs))
        dc = self.draft_cache
        params = [p._data for p in self._draft_params]

        def decode_one(feed):
            """One call of the draft's decode executable; ``feed(i, r)`` is
            a ready row's ``(token, position)``."""
            ids = np.zeros((B, 1), np.int32)
            pos = np.zeros(B, np.int32)
            for i, r in ready:
                ids[i, 0], pos[i] = feed(i, r)
            (logits, _, dc.k, dc.v, dc.k_scale, dc.v_scale) = \
                self._draft_decode_jit(
                    params, jnp.asarray(ids), jnp.asarray(pos), tables,
                    dc.k, dc.v, dc.k_scale, dc.v_scale)
            return logits

        def fetched(logits):
            rows = np.asarray(logits)
            _M_HOST_SYNCS.inc(instance=self._name)
            _M_FETCH_BYTES.inc(rows.nbytes, instance=self._name)
            return rows

        if F > 1:
            # fused catch-up (ISSUE 16 perf satellite): bucket F up to
            # the next power of two — the extra left-pad steps rewrite
            # the first feed in place, a deterministic no-op — and replay
            # the whole ragged window in ONE fori_loop dispatch
            Fb = 1 << (F - 1).bit_length()
            ids = np.zeros((B, Fb), np.int32)
            pos = np.zeros((B, Fb), np.int32)
            for i, r in ready:
                fs = feeds[r.rid]
                for t, j in enumerate([fs[0]] * (Fb - len(fs)) + fs):
                    ids[i, t] = toks[r.rid][j]
                    pos[i, t] = j
            (logits, dc.k, dc.v, dc.k_scale, dc.v_scale) = \
                self._catchup_jit(Fb)(
                    params, jnp.asarray(ids), jnp.asarray(pos), tables,
                    dc.k, dc.v, dc.k_scale, dc.v_scale)
        else:
            # every row is one token behind: the draft's decode step itself
            logits = decode_one(lambda i, r: (toks[r.rid][feeds[r.rid][0]],
                                              feeds[r.rid][0]))
        prev = fetched(logits)
        drafts = np.zeros((B, K), np.int32)
        for kstep in range(K):
            for i, r in ready:
                drafts[i, kstep] = int(prev[i].argmax())
            if kstep + 1 < K:
                prev = fetched(decode_one(
                    lambda i, r: (drafts[i, kstep], r.num_tokens + kstep)))
        for _, r in ready:
            # positions 0 .. num_tokens+K-2 now hold draft K/V
            r.draft_cached = r.num_tokens + K - 1
        return drafts

    def _spec_step(self, ready, outputs):
        """One speculative decode step for the decode-ready slots: draft
        proposes K tokens, one multi-query verify scores K+1 positions,
        accepted tokens emit in order (bit-exact vs sequential greedy),
        rollback rewinds cached lengths and frees over-allocated tail
        blocks on rejection."""
        import jax.numpy as jnp

        B, K = self.max_batch_size, self._spec_k
        phases = self._phases
        tables = self._tables()
        phases.begin("engine.decode.draft")
        drafts = self._draft_propose(ready, tables)
        phases.begin("engine.decode.prepare")
        _M_SPEC_PROPOSED.inc(K * len(ready), instance=self._name)
        ids_v = np.zeros((B, K + 1), np.int32)
        pos_v = np.zeros(B, np.int32)
        n_old = {}
        for i, r in ready:
            ids_v[i, 0] = r.last_token
            ids_v[i, 1:] = drafts[i]
            pos_v[i] = r.num_cached
            n_old[r.rid] = r.num_tokens
        c = self.cache
        inputs = ([p._data for p in self._params], jnp.asarray(ids_v),
                  jnp.asarray(pos_v), tables, jnp.asarray(drafts[:, :K]))
        phases.begin("engine.decode.dispatch")
        (counts, nxt, c.k, c.v, c.k_scale, c.v_scale) = self._verify_jit(
            *inputs, c.k, c.v, c.k_scale, c.v_scale)
        phases.begin("engine.decode.fetch")
        counts = np.asarray(counts)
        nxt = np.asarray(nxt)
        phases.begin("engine.decode.emit")
        _M_HOST_SYNCS.inc(instance=self._name)
        _M_FETCH_BYTES.inc(counts.nbytes + nxt.nbytes,
                           instance=self._name)
        accepted = 0
        for i, r in ready:
            a = int(counts[i])
            emitted = [int(drafts[i, j]) for j in range(a)] + [int(nxt[i])]
            m = 0
            for tok in emitted:
                outputs.extend(self._emit_token(r, tok))
                m += 1
                if r.finished:
                    break
            accepted += min(a, m)
            if r.finished:
                continue
            # rollback: positions past the kept tokens hold rejected-draft
            # K/V — masked by context_lens until overwritten. Rewind the
            # cached lengths and trim lookahead blocks the shorter window
            # no longer needs.
            n0 = n_old[r.rid]
            r.num_cached = r.num_tokens - 1
            r.draft_cached = min(n0 + min(min(a, m), K - 1), r.num_tokens)
            self.scheduler.trim_to_capacity(r, extra=K)
        _M_SPEC_ACCEPTED.inc(accepted, instance=self._name)
        prop = _M_SPEC_PROPOSED.value(instance=self._name)
        if prop:
            _G_SPEC_RATIO.set(
                _M_SPEC_ACCEPTED.value(instance=self._name) / prop,
                instance=self._name)

    # ------------------------------------------------------------------
    # token emission
    # ------------------------------------------------------------------
    def _emit(self, req, row):
        """Sample the next token for ``req`` from logits ``row`` [V] and
        commit it. Returns [StepOutput]."""
        s = req.sampling
        if self.capture_logits:
            # last sampled-from logits row, kept for the quantization
            # tolerance tests (bounded logit delta vs the fp32 engine) and
            # as a logprobs hook; [V] f32, overwritten per emission,
            # dropped with the request at release(). Opt-in (ISSUE 18):
            # the copy is a [V] f32 D2H pinned per live request.
            req.last_logits = np.asarray(row)
        tok = int(self._sample_next_tokens(
            row[None], do_sample=s.do_sample, temperature=s.temperature,
            top_k=s.top_k, top_p=s.top_p, rng=req._rng)[0])
        return self._emit_token(req, tok)

    def _trace_decode(self, req, now):
        """The finished request's ``request.decode`` span, first decode
        step (or first token) to ``now``."""
        _obs_trace.add_complete(
            "request.decode",
            req.t_decode_start or req.t_first_token or now, now,
            cat="request", tid=req.rid,
            args={"rid": req.rid, "engine": self._name,
                  "tokens": len(req.output_tokens),
                  "finish_reason": req.finish_reason()})

    def _emit_token(self, req, tok):
        """Commit one already-chosen token (sampled host-side, or accepted
        by the speculative verify): append it, observe latency metrics,
        finish bookkeeping. Returns [StepOutput]."""
        req.output_tokens.append(int(tok))
        self.stats_extra["tokens_out"] += 1
        # QoS accounting (ISSUE 17): each emitted token charges the
        # tenant's quota and advances its fair-queueing virtual time
        self.scheduler.note_served(req, 1)
        # latency observation at the emission point — the host just
        # fetched logits/verify results anyway, so the clock read is free
        now = time.perf_counter_ns()
        _M_TOKENS.inc(instance=self._name)
        if req.t_first_token is None:
            req.t_first_token = now
            if req.t_submit is not None:
                _H_TTFT.observe((now - req.t_submit) / 1e6,
                                instance=self._name)
        elif req.t_last_token is not None:
            _H_ITL.observe((now - req.t_last_token) / 1e6,
                           instance=self._name)
        req.t_last_token = now
        done = req.should_finish()
        if done:
            self.scheduler.finish(req)
            if _obs_trace.enabled():
                self._trace_decode(req, now)
        return [StepOutput(req.rid, int(tok), done,
                           req.finish_reason() if done else None)]

    def stream(self):
        """Yield ``StepOutput`` s until the engine drains. Raises
        :class:`EngineClosedError` (instead of silently yielding nothing
        or hanging on a joined ingest thread) when the engine is
        closed."""
        self._ensure_open()
        while self.has_work():
            yield from self.step()

    def generate(self, prompts, sampling: SamplingParams | None = None,
                 deadline=None):
        """Convenience batch API: submit every prompt, run to completion,
        return the full token arrays (prompt + generated) in order.
        With ``deadline`` set, a request the deadline kills raises
        :class:`RequestTimeoutError` after the batch drains (partial
        outputs are only reachable through ``stream()``)."""
        self._ensure_open()
        rids = []
        try:
            for p in prompts:
                rids.append(self.add_request(
                    p, dataclasses.replace(sampling) if sampling else None,
                    deadline=deadline))
        except BaseException:
            # a mid-batch admission failure (e.g. the deadline expiring
            # between prompts) must not orphan the already-admitted
            # requests in the queue — they would decode to completion on
            # the NEXT stream() and leak bookkeeping forever
            for r in rids:
                self.cancel(r)
                self.release(r)
            raise
        for _ in self.stream():
            pass
        timed_out = [r for r in rids
                     if self._requests[r].abort_reason == "timeout"]
        if timed_out:
            for r in rids:
                self.release(r)
            raise RequestTimeoutError(
                f"{len(timed_out)} of {len(rids)} requests hit the "
                f"deadline mid-generation: rids {timed_out}",
                rid=timed_out[0], deadline=deadline)
        outs = [self.output_tokens(r) for r in rids]
        for r in rids:
            self.release(r)
        return outs

    # ------------------------------------------------------------------
    # weights + teardown
    # ------------------------------------------------------------------
    def audit_weights(self):
        """Weight integrity re-audit (ISSUE 20): re-hash the live
        parameters and compare against the fingerprint captured at
        construction / last ``reload_weights``. Returns True when they
        match; False — counting
        ``serving_weight_audit_failures_total`` — when the weights
        changed IN PLACE (silent corruption; the caller's degrade is
        ``reload_weights`` from the artifact + a suspicion charge). The
        first call on an engine built without ``weight_audit=True``
        captures the reference instead of comparing."""
        fp = weights_fingerprint(self.model)
        self._weight_audits += 1
        if self._weight_audit_ref is None:
            self._weight_audit_ref = fp
            return True
        if fp != self._weight_audit_ref:
            _M_WEIGHT_AUDIT_FAIL.inc(instance=self._name)
            return False
        return True

    def reload_weights(self, source):
        """Hot-reload weights without recompiling: from a
        ``CheckpointManager`` (prefers ``latest_healthy_step()``, falls
        back to ``latest_valid_step()``), a checkpoint step directory, or
        a state-dict file path. Returns the restored step (or None)."""
        # a step in flight ran on the weights that are about to go: drop
        # it, and the next call makes it again on the new ones (with a
        # state kind it is committed: a state cannot take a step twice)
        self._drain()
        try:
            step = self._reload_weights_impl(source)
        finally:
            if self._plan is not None:
                # restored host arrays must go back to the plan's layouts
                # or the next step would recompile for replicated inputs
                self._plan.apply_to_model(self.model)
        if self._weight_audit_ref is not None or self._weight_audit:
            # a reload legitimately changes the fingerprint: re-anchor
            # the audit reference at the freshly loaded weights
            self._weight_audit_ref = weights_fingerprint(self.model)
        if self._store_path is not None:
            fp = weights_fingerprint(self.model)
            if fp != self._store_fingerprint:
                # different weights: every cached chain (device-registered,
                # host-resident, on disk) would decode garbage — drop them
                # all, then try the store again in case a shard for the NEW
                # fingerprint was published by a peer or a prior run
                self.prefix_cache.invalidate()
                self.kv_tier.drop_prefixes()
                self._store_fingerprint = fp
                self._store_saved_chains = -1
                self._load_prefix_store()
        return step

    def _reload_weights_impl(self, source):
        from ...distributed.checkpoint import load_state_dict
        from ...distributed.checkpoint.manager import CheckpointManager

        if isinstance(source, CheckpointManager):
            step = source.latest_healthy_step()
            if step is None:
                step = source.latest_valid_step()
            if step is None:
                raise FileNotFoundError(
                    "reload_weights: no committed checkpoint in "
                    f"{source.root}")
            if self._plan is not None:
                # group rejoin gate (ISSUE 19): a checkpoint recorded
                # under a DIFFERENT sharding plan must not be committed
                # to this engine's layouts — raise PlanMismatchError
                # (typed) instead of silently serving re-sharded weights
                # the rest of the fleet does not have
                CheckpointManager._check_plan(
                    source.plan_fingerprint(step), self._plan, step)
            load_state_dict(self.model.state_dict(), source.step_dir(step))
            return step
        import os

        from ...framework import io as _fio

        path = str(source)
        if os.path.isdir(path):
            load_state_dict(self.model.state_dict(), path)
            return None
        if is_llama_artifact(path):
            # serving artifact (possibly the ISSUE-14 int8 format):
            # dequantized to the live params' dtype, so the hot-swap
            # never changes an executable's input avals — no recompile
            self.model.set_state_dict(load_llama_state_dict(path))
            return None
        self.model.set_state_dict(_fio.load(path))
        return None

    def stats(self):
        d = dict(self.stats_extra)
        d.update(self.scheduler.stats)
        d["blocks_free"] = self.cache.allocator.num_free
        d["blocks_high_water"] = self.cache.allocator.high_water
        d["waiting"] = len(self.scheduler.waiting)
        d["running"] = len(self.scheduler.running)
        d["prefill_stats_row"] = self._prefill_name
        d["decode_stats_row"] = self._decode_name
        return d

    def metrics(self):
        """Engine-owned observability snapshot (ISSUE 10 public surface):
        lifecycle counters, latency histogram summaries (count/mean/
        p50/p99, ms), prefix-cache/chunk/speculative counters and
        utilization gauges for THIS engine instance, read from
        ``paddle.observability.metrics``. This is what
        ``scripts/bench_serving.py`` reports TTFT / inter-token
        percentiles from — engine-measured, not bench-side timing."""
        inst = self._name
        prop = _M_SPEC_PROPOSED.value(instance=inst)
        store_rejected = self._by_reason(_M_STORE_REJECTED, "corrupt")
        decode_sync = self._by_reason(_M_DECODE_SYNC)
        return {
            "instance": inst,
            **self._kind_metrics(),
            "admitted": int(_M_ADMITTED.value(instance=inst)),
            "evictions": int(_M_EVICTIONS.value(instance=inst)),
            "finished": int(_M_FINISHED.value(instance=inst)),
            "queued_on_exhaustion": int(
                _M_QUEUED_EXH.value(instance=inst)),
            "prefills": int(_M_PREFILLS.value(instance=inst)),
            # of those, the ones whose first token was fetched with the
            # next decode step already enqueued behind the chunk (ISSUE 34)
            "prefill_ends_behind_decode": int(
                _M_PREFILL_BEHIND.value(instance=inst)),
            "prefill_chunks": int(_M_PREFILL_CHUNKS.value(instance=inst)),
            # of those, the ones that read the request's keys in a row
            # through the chunk kernel (ISSUE 36): all, or with int8
            # Llama-form pools none
            "prefill_chunks_in_a_row": int(
                _M_PREFILL_IN_A_ROW.value(instance=inst)),
            # what the chunks were asked for and what their graphs ran:
            # padded / tokens - 1 is the work spent on padding (ISSUE 35)
            "prefill_tokens": int(_M_PREFILL_TOKENS.value(instance=inst)),
            "prefill_padded_tokens": int(
                _M_PREFILL_PADDED.value(instance=inst)),
            "prefix_blocks_reused": int(
                _M_PREFIX_REUSED.value(instance=inst)),
            "cow_copies": int(_M_COW.value(instance=inst)),
            "spec_proposed": int(prop),
            "spec_accepted": int(_M_SPEC_ACCEPTED.value(instance=inst)),
            "spec_accept_ratio": (
                float(_G_SPEC_RATIO.value(instance=inst)) if prop
                else None),
            "deadline_expired": int(_M_DEADLINE.value(instance=inst)),
            "tokens_out": int(_M_TOKENS.value(instance=inst)),
            "ttft_ms": _H_TTFT.summary(instance=inst),
            "itl_ms": _H_ITL.summary(instance=inst),
            "queue_wait_ms": _H_QUEUE_WAIT.summary(instance=inst),
            "kv_block_utilization": _G_KV_UTIL.value(instance=inst),
            "decode_batch_occupancy": _G_OCCUPANCY.value(instance=inst),
            "kv_dtype": self.kv_dtype,
            "kv_bytes_saved": int(_M_KV_SAVED.value(instance=inst)),
            "quantized_blocks_in_use": (
                int(_G_QUANT_BLOCKS.value(instance=inst))
                if self.cache.quantized else None),
            # KV tiering + prefix store (ISSUE 16) — zeros when the tier
            # is off so consumers never need to key-guard
            "kv_spills": int(_M_SPILLS.value(instance=inst)),
            "kv_revives": int(_M_REVIVES.value(instance=inst)),
            "kv_spill_bytes": int(_M_SPILL_BYTES.value(instance=inst)),
            "kv_revive_bytes": int(_M_REVIVE_BYTES.value(instance=inst)),
            "kv_host_evictions": int(_M_HOST_EVICT.value(instance=inst)),
            "kv_host_blocks": int(_G_HOST_BLOCKS.value(instance=inst)),
            "kv_spill_ms": _H_SPILL_MS.summary(instance=inst),
            "kv_revive_ms": _H_REVIVE_MS.summary(instance=inst),
            "prefix_store_saved": int(_M_STORE_SAVED.value(instance=inst)),
            "prefix_store_loaded": int(
                _M_STORE_LOADED.value(instance=inst)),
            # reason-labeled since ISSUE 20: the plain key stays the
            # all-reasons sum so existing consumers keep working
            "prefix_store_rejected": sum(store_rejected.values()),
            "prefix_store_rejected_by_reason": store_rejected,
            # multi-tenant QoS (ISSUE 17) — zeros when QoS is unused
            "quota_throttled": int(_M_THROTTLED.value(instance=inst)),
            "batch_yields": int(_M_BATCH_YIELD.value(instance=inst)),
            "tenant_tokens": self._tenant_token_counts(),
            # device-resident decode (ISSUE 18): decode-loop round-trips
            # and the bytes they pulled (prefill fetches excluded)
            "host_syncs": int(_M_HOST_SYNCS.value(instance=inst)),
            "decode_fetch_bytes": int(_M_FETCH_BYTES.value(instance=inst)),
            # decode dispatch-ahead (ISSUE 28): of the decode steps
            # emitted, how many were enqueued behind the step before them
            # and how many with nothing in flight, by why; rows dropped
            "decode_steps_ahead": int(_M_DECODE_AHEAD.value(instance=inst)),
            "decode_steps_sync": sum(decode_sync.values()),
            "decode_steps_sync_by_reason": decode_sync,
            "decode_rows_discarded": int(
                _M_ROWS_DISCARDED.value(instance=inst)),
            # serving integrity (ISSUE 20) — zeros when checksums / the
            # weight audit are off
            "kv_pages_verified": int(
                _M_PAGES_VERIFIED.value(instance=inst)),
            "kv_pages_rejected": int(
                _M_PAGES_REJECTED.value(instance=inst)),
            "weight_audits": int(self._weight_audits),
            "weight_audit_failures": int(
                _M_WEIGHT_AUDIT_FAIL.value(instance=inst)),
        }

    def _kind_metrics(self):
        """Pages of either kind and the model's own counters (ISSUE 27).
        ``kv_live_byte_steps`` / ``kv_one_table_byte_steps``: K and V bytes
        the live pages hold at the published widths, summed over the
        steps so far, and what ONE table paging every layer alike would
        hold for the same requests. The model's device-side counters are
        fetched here and nowhere else; each comes whole and split by the
        graph that counted it (``_decode``, ``_prefill``). ``state_bytes``:
        what the requests in the slots hold in the state layers now, at the
        published widths; ``state_byte_steps`` the same summed over the
        steps, as the pages' are."""
        cache, bs = self.cache, self.block_size
        window = cache.window
        if self._counters_dev is not None and self._counter_names:
            low, carried = self._fetch(self._counters_dev)
            self._counters_dev = None
            for name, lo, hi in zip(self._counter_totals, low, carried):
                self._counter_totals[name] += int(lo) + _LIMB * int(hi)
        g_steps, w_steps = self._page_steps
        g_bytes = cache.published_bytes_per_token("global")
        w_bytes = cache.published_bytes_per_token("window")
        # a state is held a request, not a token: beside the pages' bytes,
        # never among them (0 without a state kind)
        in_slots = (len(self.scheduler.running)
                    if cache.state_slots is not None else 0)
        s_bytes = cache.state_bytes_per_request()
        return {
            "state_slots_in_use": in_slots,
            "state_bytes": in_slots * s_bytes,
            "state_byte_steps": self._state_slot_steps * s_bytes,
            "global_blocks_in_use":
                cache.num_blocks - 1 - cache.allocator.num_free,
            "window_blocks_in_use": window.blocks_in_use if window else 0,
            "window_blocks_released": window.released if window else 0,
            "kv_live_byte_steps": bs * (g_steps * g_bytes + w_steps * w_bytes),
            "kv_one_table_byte_steps": bs * g_steps * (g_bytes + w_bytes),
            **self._counter_totals,
            **{n: self._counter_totals[n + "_decode"]
               + self._counter_totals[n + "_prefill"]
               for n in self._counter_names},
        }

    def _remove_tenant_series(self):
        """Remove THIS instance's tenant-labeled series. The extra
        ``tenant`` label means the plain ``remove(instance=)`` sweep in
        ``reset_metrics``/``close`` cannot reach them — iterate the live
        label sets instead. The reason-labeled counters (store rejections,
        ISSUE 20; synchronous decode steps, ISSUE 28) need the same
        treatment."""
        for m in (_M_TENANT_TOKENS, _M_STORE_REJECTED, _M_DECODE_SYNC):
            for labels in list(m.labels()):
                d = dict(labels)
                if d.get("instance") == self._name:
                    m.remove(**d)

    def _by_reason(self, metric, unlabeled="none"):
        """Per-reason counts of a reason-labeled counter for THIS
        instance — iterated from live label sets, like the tenant
        tokens."""
        out = {}
        for labels in metric.labels():
            d = dict(labels)
            if d.get("instance") == self._name:
                out[d.get("reason", unlabeled)] = int(metric.value(**d))
        return out

    def _tenant_token_counts(self):
        """Per-tenant served-token counts for THIS instance — iterated
        from live label sets because the ``tenant`` label is only known
        at serve time, not declaration time."""
        out = {}
        for labels in _M_TENANT_TOKENS.labels():
            d = dict(labels)
            if d.get("instance") == self._name:
                out[d.get("tenant", "default")] = int(
                    _M_TENANT_TOKENS.value(**d))
        return out

    def reset_metrics(self):
        """Drop THIS instance's registry series (latency histograms and
        lifecycle counters restart from empty). Benchmarks call it at the
        start of a timed window so warm-phase observations never pollute
        the reported percentiles; a production engine has no reason to."""
        for m in _SERVING_METRICS:
            m.remove(instance=self._name)
        self._remove_tenant_series()
        if self.cache.quantized and not self._closed:
            # bytes saved is a construction-time constant of THIS pool,
            # not window activity — republish it so a benchmark window
            # reset doesn't erase the capacity accounting
            _M_KV_SAVED.inc(self._kv_bytes_saved, instance=self._name)
        if self.kv_tier is not None and not self._closed:
            # host occupancy is current state, not window activity
            _G_HOST_BLOCKS.set(self.kv_tier.host_blocks_in_use,
                               instance=self._name)

    def reset_block_high_water(self):
        """Re-anchor the allocator's high-water mark at the current
        in-use block count — the window-local form benchmarks want
        (replaces reaching into ``cache.allocator`` privates)."""
        alloc = self.cache.allocator
        alloc.high_water = (self.cache.num_blocks - 1) - alloc.num_free

    def close(self):
        """Tear the engine down (ISSUE 12 satellite, mirroring
        ``DevicePrefetcher.close``): join the ingest thread, abort every
        live request so the scheduler's blocks return to the allocator,
        drop request bookkeeping, and remove THIS instance's registry
        series — so a process that constructs engines in a loop (tests,
        notebooks, a supervisor restarting replicas in-process) does not
        grow the metrics registry forever. Idempotent; after close,
        ``add_request``/``step``/``stream``/``generate`` raise
        :class:`EngineClosedError` instead of hanging on the joined
        ingest thread."""
        if self._closed:
            return
        self._drain()
        if self._store_path is not None:
            # persist the warm prefix chains BEFORE teardown frees their
            # blocks; a failed save keeps the previous store intact and
            # never blocks the close
            try:
                self.save_prefix_store()
            except OSError as e:
                warnings.warn(f"{self._name}: prefix store save on close "
                              f"failed: {e}", RuntimeWarning)
        self._closed = True
        if self._ingest is not None:
            self._ingest.close()
            # anything still staged on the (now joined) ingest thread
            # was never admitted — no blocks to free, just bookkeeping
            self._ingest.drain()
        for req in list(self.scheduler.running):
            self.scheduler.abort(req, "closed")
        for req in list(self.scheduler.waiting):
            self.scheduler.abort(req, "closed")
        self._requests.clear()
        if self.kv_tier is not None:
            self.kv_tier.close()
        self.reset_metrics()
        if self._was_training:
            self.model.train()
        if self.draft_model is not None and self._draft_was_training:
            self.draft_model.train()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ----------------------------------------------------------------------
# llama serving artifacts (consumed by inference.create_predictor)
# ----------------------------------------------------------------------

ARTIFACT_QMAX = 127.0


def quantize_state_dict(state_dict, qmax=ARTIFACT_QMAX):
    """Per-channel int8 quantization of a weights state dict (ISSUE 14
    artifact format): every float array with >= 2 dims is packed as int8
    codes + a float32 per-channel scale row (abs-max over all axes
    except the LAST — the output channel of every ``Linear`` here), 1-D
    params (norms, biases) pass through untouched. Returns
    ``(packed, scales)`` where ``scales`` holds ONLY the quantized
    names, each scale being the DEQUANT MULTIPLIER (``absmax / qmax`` —
    dequant is a single ``codes * scale``). The quantization math is
    the quantization package's shared
    :func:`~paddle_tpu.quantization.base.per_channel_int8`, so the
    artifact path and the PTQ convert path can never drift."""
    from ...quantization.base import per_channel_int8

    packed, scales = {}, {}
    for name, val in state_dict.items():
        arr = np.asarray(val.numpy() if hasattr(val, "numpy") else val)
        if arr.ndim >= 2 and arr.dtype.kind == "f":
            codes, absmax = per_channel_int8(arr, qmax=qmax)
            packed[name] = codes
            scales[name] = (absmax / qmax).astype(np.float32)
        else:
            packed[name] = arr
    return packed, scales


def dequantize_state_dict(packed, scales, dtype=np.float32):
    """Inverse of :func:`quantize_state_dict`: codes x scale back to
    ``dtype`` host arrays, passthrough entries untouched."""
    out = {}
    for name, arr in packed.items():
        arr = np.asarray(arr.numpy() if hasattr(arr, "numpy") else arr)
        if name in scales:
            s = np.asarray(scales[name].numpy()
                           if hasattr(scales[name], "numpy")
                           else scales[name])
            out[name] = (arr.astype(np.float32) * s).astype(dtype)
        else:
            out[name] = arr
    return out


def save_llama_artifact(model, path, quantize=None):
    """Persist a llama model as a serving artifact: ``<path>.llamacfg.json``
    (the LlamaConfig) + ``<path>.pdiparams`` (weights). The engine-backed
    predictor (``Config.enable_llm_engine``) detects the sidecar config and
    rebuilds the model around it.

    ``quantize="int8"`` (ISSUE 14) writes the QUANTIZED artifact format:
    ``<path>.pdiparams`` holds packed int8 weight tensors (per-channel
    abs-max, ~4x smaller — the replica-boot / fleet-transfer win), the
    scales live in the ``<path>.qscales.pdiparams`` sidecar, and
    ``<path>.quant.json`` records the scheme. Loaders dequantize back to
    the model dtype, so a running ``LLMEngine.reload_weights`` hot-swap
    sees same-shape/same-dtype arrays and never recompiles."""
    import json
    import os

    from ...framework.io import save as fsave

    if quantize not in (None, "int8"):
        raise ValueError(f"quantize must be None or 'int8'; got "
                         f"{quantize!r}")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".llamacfg.json", "w") as f:
        json.dump(dataclasses.asdict(model.config), f)
    if quantize == "int8":
        packed, scales = quantize_state_dict(model.state_dict())
        fsave(packed, path + ".pdiparams")
        fsave(scales, path + ".qscales.pdiparams")
        with open(path + ".quant.json", "w") as f:
            json.dump({"scheme": "int8_per_channel",
                       "qmax": ARTIFACT_QMAX,
                       "quantized_tensors": sorted(scales)}, f)
    else:
        fsave(model.state_dict(), path + ".pdiparams")
        # a resave over a previously-quantized path must not leave a
        # stale scheme sidecar claiming the fp weights are codes
        for ext in (".quant.json", ".qscales.pdiparams"):
            try:
                os.remove(path + ext)
            except OSError:
                pass


def is_llama_artifact(path):
    import os

    if path.endswith(".pdmodel"):
        path = path[: -len(".pdmodel")]
    return os.path.exists(path + ".llamacfg.json")


def is_quantized_artifact(path):
    import os

    if path.endswith(".pdmodel"):
        path = path[: -len(".pdmodel")]
    return os.path.exists(path + ".quant.json")


def load_llama_state_dict(path):
    """Host-array weights of an artifact, dequantizing the int8 format
    when its ``.quant.json`` sidecar is present (the
    ``LLMEngine.reload_weights`` hot-swap entry: same shapes and dtypes
    as the live params, so nothing recompiles)."""
    import json

    from ...framework.io import load as fload

    if path.endswith(".pdmodel"):
        path = path[: -len(".pdmodel")]
    if is_quantized_artifact(path):
        with open(path + ".quant.json") as f:
            meta = json.load(f)
        if meta.get("scheme") != "int8_per_channel":
            raise ValueError(
                f"unknown quantized-artifact scheme {meta.get('scheme')!r} "
                f"in {path}.quant.json")
        packed = fload(path + ".pdiparams", return_numpy=True)
        scales = fload(path + ".qscales.pdiparams", return_numpy=True)
        return dequantize_state_dict(packed, scales)
    return fload(path + ".pdiparams")


def load_llama_artifact(path):
    """Rebuild the model from :func:`save_llama_artifact` output
    (quantized artifacts are dequantized into the fresh model's
    dtype)."""
    import json

    from ...models.llama import LlamaConfig, LlamaForCausalLM

    if path.endswith(".pdmodel"):
        path = path[: -len(".pdmodel")]
    with open(path + ".llamacfg.json") as f:
        cfg = LlamaConfig(**json.load(f))
    model = LlamaForCausalLM(cfg)
    model.set_state_dict(load_llama_state_dict(path))
    model.eval()
    return model
