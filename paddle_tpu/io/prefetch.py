"""Device prefetcher: double-buffered host→device transfer.

The DataLoader delivers host batches and the device transfer happens at
dispatch time, so a plain training loop pays host batch production + H2D
latency *serially* with every step (PERF.md "Host and device latencies"
has the measured round-trip and dispatch times). ``DevicePrefetcher`` is the
buffered-reader analog of the reference's
``paddle/fluid/operators/reader/buffered_reader.cc`` (which stages batches
onto the device on a side stream): a transfer thread pulls batch N+1 from
the source iterator, pads it to the registered shape buckets ON THE HOST
THREAD (so bucketing costs nothing on the critical path and the staged
shapes hit the same compiled executables — zero extra compiles), and
starts the device transfer with ``jax.device_put`` (async: the copy
overlaps the consumer's compute on batch N). A bounded queue
(``FLAGS_prefetch_depth``, default 2 = classic double buffer) provides
backpressure so a slow consumer cannot pin the whole epoch in device
memory.

Failure containment: if the transfer thread dies (fault site
``io.prefetch``, device OOM on put, a poisoned sample), the consumer warns
ONCE and degrades to synchronous staging on its own thread — the batch the
thread was holding is recovered, nothing is dropped, training continues.

Telemetry flows into ``paddle.jit.cache_stats()`` under this instance's
name: ``host_blocked_ms`` (time the consumer waited for a staged batch —
the residual host-boundness after overlap) and ``avg_queue_depth`` (0
means the host pipeline is the bottleneck, ``depth`` means the device is).
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import warnings

import numpy as np

from ..core.tensor import Tensor
from ..observability import metrics as _obs_metrics
from ..observability import trace as _obs_trace

__all__ = ["DevicePrefetcher", "np_pad_to_bucket"]

# per-INSTANCE overlap telemetry (ISSUE 10 satellite): the legacy
# jit.cache_stats() row is keyed by the caller-chosen stats name, so two
# concurrent loaders built with the same name merge their numbers there.
# These registry series carry an instance label unique per prefetcher
# object, so they never merge; host_blocked is a histogram (p50/p99 of
# the per-get block, not just a total).
_M_HOST_BLOCKED = _obs_metrics.histogram(
    "io_host_blocked_ms",
    "ms the consumer blocked per staged-batch get (residual "
    "host-boundness after overlap)", buckets=_obs_metrics.DEFAULT_MS_BUCKETS)
_G_QUEUE_DEPTH = _obs_metrics.gauge(
    "io_queue_depth",
    "staged-batch queue depth at the last consumer get (0 = host-bound, "
    "prefetch_depth = device-bound)")

# worker -> consumer token kinds
_ITEM = "item"
_DONE = "done"
_ERR_SOURCE = "err_source"   # the source iterator itself raised
_ERR_STAGE = "err_stage"     # staging/transfer failed; raw batch recovered


def _array_leaves(tree, out=None):
    """Tensor/ndarray leaves of a batch tree in call order."""
    if out is None:
        out = []
    if isinstance(tree, (Tensor, np.ndarray)):
        out.append(tree)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            _array_leaves(v, out)
    elif isinstance(tree, dict):
        for v in tree.values():
            _array_leaves(v, out)
    return out


def np_pad_to_bucket(arr, spec, lengths=None):
    """Host-side (numpy) mirror of ``jit.cache.pad_array_to_bucket``:
    zero-pad ``arr`` up to its bucket under ``spec`` on the CALLING thread
    (no device work). Shared by the transfer thread below and the serving
    engine's request-ingest staging (``inference.serving``), so prompt
    padding and batch padding land on identical bucket shapes. Returns
    ``(array, was_padded)``."""
    from ..jit import cache as jit_cache

    if lengths is None:
        lengths = jit_cache.infer_call_lengths([arr], spec)
    target = jit_cache.bucketed_call_shape(arr.shape, spec, lengths)
    if tuple(target) == tuple(arr.shape):
        return arr, False
    widths = [(0, t - s) for s, t in zip(arr.shape, target)]
    return np.pad(arr, widths), True


_np_pad_to_bucket = np_pad_to_bucket  # backward-compatible alias


class DevicePrefetcher:
    """Wrap any batch iterable (``DataLoader``, a list of batches, a
    generator) so host batch production + H2D transfer overlap device
    compute. Iterating yields the same batches, staged: array leaves become
    device Tensors, padded to the active shape buckets.

    Arguments:
        source: the batch iterable. Re-iterable sources (DataLoader) give a
            fresh transfer thread per epoch.
        depth: staged-batch queue bound; default ``FLAGS_prefetch_depth``.
        shape_buckets: pad-up boundaries applied while staging (any form
            ``jit.BucketSpec.normalize`` accepts). ``None`` falls back to
            the process-global ``jit.set_shape_buckets`` spec at stage
            time, so the prefetcher and the jit layer can never disagree.
        bucket_args: like ``FusedTrainStep``'s — positional indices / dict
            keys of the batch fields to pad. Default is the same
            dominant-length rule the fused step uses, so pre-padded shapes
            are exactly the shapes the step would have padded to itself.
        name: the ``jit.cache_stats()`` row this instance reports under.
            Long-lived consumers that build prefetchers repeatedly
            (``FusedTrainStep.drive``, ``hapi.Model.fit``) pass a stable
            name so telemetry accumulates in ONE row instead of leaking a
            new auto-named row per call.
    """

    # itertools.count: atomic next() under CPython, so concurrently built
    # instances never share an auto-generated stats name
    _instance_ids = itertools.count(1)

    def __init__(self, source, depth=None, shape_buckets=None,
                 bucket_args=None, name=None):
        from ..core.flags import flag_value
        from ..jit.cache import BucketSpec

        self.source = source
        if depth is None:
            depth = int(flag_value("prefetch_depth", 2))
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._spec = BucketSpec.normalize(shape_buckets)
        self._bucket_args = (None if bucket_args is None
                             else frozenset(bucket_args))
        uid = next(DevicePrefetcher._instance_ids)
        self._stats_name = name or f"device_prefetcher#{uid}"
        # registry label: unique PER OBJECT even when a stable name= is
        # passed, so two concurrent loaders sharing a legacy stats row
        # keep distinct io_host_blocked_ms / io_queue_depth series
        self._metrics_label = (self._stats_name if name is None
                               else f"{name}#{uid}")
        self._fell_back = False
        self._stats = {"batches": 0, "prefetched": 0, "sync_fallback": 0,
                       "host_blocked_ms": 0.0, "queue_depth_sum": 0,
                       "bucket_pads": 0}
        # live iterations' (stop event, thread, queue) triples — what
        # close() tears down when a consumer abandons iteration mid-epoch
        self._active: list = []

    def __len__(self):
        return len(self.source)

    def stats(self):
        """Instance-level overlap counters (the same numbers also land in
        ``paddle.jit.cache_stats()[<instance name>]``)."""
        d = dict(self._stats)
        d["host_blocked_ms"] = round(d["host_blocked_ms"], 3)
        n = d.pop("queue_depth_sum")
        d["avg_queue_depth"] = (round(n / d["prefetched"], 3)
                                if d["prefetched"] else None)
        d["fallback"] = self._fell_back
        return d

    # -- lifecycle -------------------------------------------------------
    def close(self):
        """Tear down any live staging thread: signal stop, drain the
        bounded queue (unblocking a transfer thread parked on ``put``),
        and join. A consumer that breaks out of iteration mid-epoch — or
        an error path like hapi ``fit``'s — calls this so the daemon
        thread never outlives the loop. Idempotent, and the prefetcher
        itself stays re-iterable (a later ``iter()`` starts a fresh
        thread over a fresh pass of the source)."""
        for stop, _t, _q in list(self._active):
            stop.set()
        for stop, t, q in list(self._active):
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=2.0)
            # a consumer that resumes its abandoned generator afterwards
            # must terminate, not block on an empty queue forever
            try:
                q.put_nowait((_DONE, None, None))
            except queue.Full:
                pass
        self._active = []
        # bound registry growth: the per-OBJECT instance series must not
        # outlive the object's working life (drive() builds a fresh
        # prefetcher per call — leaking one dead histogram + stale gauge
        # per drive would violate the label-cardinality rule). The
        # accumulated totals remain in this object's stats() and in the
        # legacy jit.cache_stats() row; a post-close re-iteration simply
        # re-creates the series.
        _M_HOST_BLOCKED.remove(instance=self._metrics_label)
        _G_QUEUE_DEPTH.remove(instance=self._metrics_label)

    def reset(self, sampler_state=None):
        """Discard every staged (read-ahead) batch and restart from the
        source: tears down live staging threads via :meth:`close`, so the
        next ``iter()`` begins a fresh pass of the source. With
        ``sampler_state`` (a ``BucketedBatchSampler.state_dict()``), the
        source's resumable sampler is first restored to that position —
        this is the divergence-rollback hook: after
        ``CheckpointManager.auto_resume`` rewinds the sampler cursor,
        ``reset`` guarantees no batch staged past the rollback point is
        ever consumed (staged batches were never ``advance()``-d, so the
        cursor and the restarted stream agree exactly)."""
        self.close()
        if sampler_state is not None:
            from . import resolve_resumable

            r = resolve_resumable(self.source)
            if r is None:
                raise TypeError(
                    f"reset(sampler_state=...) needs a resumable source; "
                    f"{type(self.source).__name__} does not expose (or "
                    "wrap something exposing) state_dict/set_state_dict/"
                    "advance")
            r.set_state_dict(sampler_state)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- staging ---------------------------------------------------------
    def _active_spec(self):
        from ..jit import cache as jit_cache

        return (self._spec if self._spec is not None
                else jit_cache.get_shape_buckets())

    def _stage(self, batch):
        """(staged batch, n_padded): pad array leaves up to their bucket
        and start the device transfer. numpy leaves pad on the host
        (np.pad — cheap, on this thread); Tensor leaves pad on device
        (dispatch is async, still off the consumer's critical path)."""
        import jax

        from ..jit import cache as jit_cache

        spec = self._active_spec()
        sel = self._bucket_args
        lengths = None
        if spec is not None and sel is None:
            arrays = [a._data if isinstance(a, Tensor) else a
                      for a in _array_leaves(batch)]
            lengths = jit_cache.infer_call_lengths(arrays, spec)
        n_pads = 0

        def stage_leaf(leaf, pad):
            nonlocal n_pads
            if isinstance(leaf, Tensor):
                arr = leaf._data
                if pad:
                    arr, p = jit_cache.pad_array_to_bucket(arr, spec, lengths)
                    n_pads += int(p)
                t = Tensor._wrap(jax.device_put(arr))
                t.stop_gradient = leaf.stop_gradient
                return t
            if isinstance(leaf, np.ndarray):
                arr = leaf
                if pad:
                    arr, p = _np_pad_to_bucket(arr, spec, lengths)
                    n_pads += int(p)
                return Tensor._wrap(jax.device_put(arr))
            return leaf

        def walk(node, field_id):
            # field selection is by top-level position/key (the step's call
            # convention: batch fields become the call's arguments)
            pad = spec is not None and (sel is None or field_id in sel)
            if isinstance(node, (Tensor, np.ndarray)):
                return stage_leaf(node, pad)
            if isinstance(node, (list, tuple)):
                if field_id is None:
                    staged = [walk(v, i) for i, v in enumerate(node)]
                else:
                    staged = [walk(v, field_id) for v in node]
                return type(node)(staged) if isinstance(node, tuple) \
                    else staged
            if isinstance(node, dict):
                if field_id is None:
                    return {k: walk(v, k) for k, v in node.items()}
                return {k: walk(v, field_id) for k, v in node.items()}
            return node

        return walk(batch, None), n_pads

    def _deliver(self, staged, n_pads, prefetched):
        from ..jit import cache as jit_cache

        if n_pads:
            jit_cache.record_bucket_pads(self._stats_name, n_pads)
            self._stats["bucket_pads"] += n_pads
        self._stats["batches"] += 1
        self._stats["prefetched" if prefetched else "sync_fallback"] += 1
        return staged

    # -- iteration -------------------------------------------------------
    def __iter__(self):
        from ..jit import cache as jit_cache
        from ..utils import fault_injection

        src = iter(self.source)
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def put(token):
            while not stop.is_set():
                try:
                    q.put(token, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            while not stop.is_set():
                try:
                    batch = next(src)
                except StopIteration:
                    put((_DONE, None, None))
                    return
                except BaseException as e:  # the LOADER failed, not us
                    put((_ERR_SOURCE, e, None))
                    return
                try:
                    fault_injection.fire("io.prefetch")
                    # staging runs on the transfer thread — an allowed
                    # span site (the host thread here exists to block)
                    with _obs_trace.span("io.prefetch.stage", cat="io",
                                         args={"instance":
                                               self._metrics_label}):
                        staged, n_pads = self._stage(batch)
                except BaseException as e:
                    # transfer thread dies; hand the un-staged batch back so
                    # the synchronous fallback loses nothing
                    put((_ERR_STAGE, e, batch))
                    return
                if not put((_ITEM, staged, n_pads)):
                    return

        t = threading.Thread(target=worker, daemon=True,
                             name=f"{self._stats_name}-transfer")
        t.start()
        entry = (stop, t, q)
        self._active.append(entry)
        pending = None
        try:
            while True:
                t0 = time.perf_counter()
                kind, payload, extra = q.get()
                blocked_ms = (time.perf_counter() - t0) * 1000.0
                if kind == _ITEM:
                    depth = q.qsize()
                    self._stats["host_blocked_ms"] += blocked_ms
                    self._stats["queue_depth_sum"] += depth
                    jit_cache.record_host_blocked(self._stats_name,
                                                  blocked_ms)
                    jit_cache.record_queue_depth(self._stats_name, depth)
                    _M_HOST_BLOCKED.observe(blocked_ms,
                                            instance=self._metrics_label)
                    _G_QUEUE_DEPTH.set(depth, instance=self._metrics_label)
                    yield self._deliver(payload, extra, prefetched=True)
                    continue
                if kind == _DONE:
                    return
                if kind == _ERR_SOURCE:
                    raise payload  # loader failure: same as synchronous
                # _ERR_STAGE: degrade to the synchronous path, once, loudly
                self._fell_back = True
                pending = extra
                warnings.warn(
                    f"DevicePrefetcher transfer thread died ({payload!r}); "
                    "falling back to synchronous host->device transfers "
                    "for the rest of this iteration",
                    RuntimeWarning, stacklevel=2)
                break
        finally:
            # early break / GeneratorExit / normal end all land here: stop
            # the transfer thread, drain whatever it staged (unconsumed
            # batches are DISCARDED — on a checkpoint resume they are
            # re-staged from the restored sampler cursor, never consumed
            # twice), and join so no thread outlives the iteration
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=2.0)
            try:
                self._active.remove(entry)
            except ValueError:
                pass
        # synchronous fallback: finish the epoch on the consumer thread
        # (no injection probe here — this IS the degraded path)
        if pending is not None:
            staged, n_pads = self._stage(pending)
            yield self._deliver(staged, n_pads, prefetched=False)
        for batch in src:
            staged, n_pads = self._stage(batch)
            yield self._deliver(staged, n_pads, prefetched=False)

    def __call__(self):
        return iter(self)
