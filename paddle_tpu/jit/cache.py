"""Bucket-aware compile cache + telemetry for paddle.jit.

The reference framework absorbs variable-length batches with LoD tensors and
DataFeed (paddle/fluid/framework/data_feed.cc); this XLA-native design pads
instead. Without help, a stream of distinct sequence lengths costs one full
XLA compile *per distinct shape* — the classic recompile-per-shape cliff. The
standard fix in XLA-native stacks (GSPMD/PaLM-style static-shape input
pipelines) is to bucket incoming shapes to a small set of padded sizes so the
compile count is O(buckets), not O(distinct lengths).

This module is the jit-side half of that subsystem (the io-side half is
``paddle.io.BucketedBatchSampler``/``PadToBucket``):

- ``BucketSpec`` / ``set_shape_buckets``: registered bucket boundaries per
  axis. Incoming tensor shapes are padded UP to the nearest boundary before
  the compile-cache lookup, so every length in (prev_boundary, boundary]
  shares one executable. Lengths beyond the largest boundary pass through
  unchanged (and each costs its own compile — the telemetry below makes that
  visible instead of silent).
- per-function cache telemetry: compiles, cache hits, per-shape misses,
  eager-fallback invocations and bucket-pad counts, surfaced via
  ``paddle.jit.cache_stats()``. A ``FLAGS_jit_compile_warn_threshold``-gated
  warning fires when one function's compile count crosses the threshold —
  the actionable symptom of the cliff.

Padding here is zeros. That composes with mask-based variable-length code
(zero mask entries = padding) but is only registered explicitly — bucketing
is opt-in per function (``to_static(fn, shape_buckets=...)``) or global
(``set_shape_buckets``), never inferred.
"""

from __future__ import annotations

import bisect
import os
import threading
import warnings

from ..core.flags import register_flag
from ..observability import metrics as _obs_metrics

register_flag(
    "jit_compile_warn_threshold", 8,
    help="warn when one jitted function has been XLA-compiled more than "
         "this many times (recompile-per-shape cliff); 0 disables. Fix by "
         "registering shape buckets (paddle.jit.set_shape_buckets) or "
         "bucketing the input pipeline (paddle.io.BucketedBatchSampler)")

__all__ = [
    "BucketSpec", "set_shape_buckets", "get_shape_buckets", "cache_stats",
    "reset_cache_stats", "CountingJit", "place_compile_cache",
]


# --------------------------------------------------------------------------
# shape buckets
# --------------------------------------------------------------------------

class BucketSpec:
    """Registered pad-up boundaries per tensor axis.

    ``axes`` maps axis index -> strictly-increasing boundary tuple. The
    normalized forms accepted everywhere a spec is taken:

    - ``[64, 128, 256]``      -> buckets on axis 1 (the batch, seq layout)
    - ``{1: [64, 128]}``      -> explicit per-axis boundaries
    - a ``BucketSpec``        -> passed through
    """

    __slots__ = ("axes",)

    def __init__(self, axes):
        self.axes = {}
        for axis, bounds in axes.items():
            bounds = tuple(sorted(int(b) for b in bounds))
            if not bounds:
                raise ValueError("bucket boundaries must be non-empty")
            if any(b <= 0 for b in bounds):
                raise ValueError(f"bucket boundaries must be positive, got "
                                 f"{bounds}")
            if len(set(bounds)) != len(bounds):
                raise ValueError(f"duplicate bucket boundary in {bounds}")
            self.axes[int(axis)] = bounds

    @classmethod
    def normalize(cls, spec, default_axis=1):
        if spec is None or isinstance(spec, BucketSpec):
            return spec
        if isinstance(spec, dict):
            return cls(spec)
        return cls({default_axis: spec})

    def bucketed_dim(self, axis, size):
        """The boundary ``size`` pads up to on ``axis`` (``size`` itself when
        it exceeds every boundary — overflow stays unbucketed, visibly)."""
        bounds = self.axes.get(axis)
        if bounds is None:
            return size
        i = bisect.bisect_left(bounds, size)
        return bounds[i] if i < len(bounds) else size

    def pad_widths(self, shape):
        """[(lo, hi), ...] zero-pad widths taking ``shape`` to its bucket,
        or None when the shape is already on-bucket."""
        widths = [(0, 0)] * len(shape)
        changed = False
        for axis, size in enumerate(shape):
            target = self.bucketed_dim(axis, size)
            if target != size:
                widths[axis] = (0, target - size)
                changed = True
        return widths if changed else None

    def __repr__(self):
        return f"BucketSpec({self.axes})"


_GLOBAL_SPEC: BucketSpec | None = None


def set_shape_buckets(boundaries=None, axis=1):
    """Register process-global shape buckets for every jitted entry point
    (``to_static`` functions and ``fused_train_step``); ``None`` clears.
    Returns the previous spec. Per-function ``shape_buckets=`` overrides."""
    global _GLOBAL_SPEC
    prev = _GLOBAL_SPEC
    _GLOBAL_SPEC = (None if boundaries is None
                    else BucketSpec.normalize(boundaries, default_axis=axis))
    return prev


def get_shape_buckets():
    return _GLOBAL_SPEC


def infer_call_lengths(arrays, spec):
    """{axis: dominant length} for one call: the FIRST array carrying each
    bucketed axis defines the call's length on that axis (the ids-first
    convention, mirroring ``PadToBucket``'s field-selection rule). Only
    inputs MATCHING the dominant length are padded — fixed-size fields
    ([B, 1] labels, [B, n_features] dense vectors) pass through untouched
    instead of being silently corrupted with fabricated zeros."""
    lengths = {}
    for axis in spec.axes:
        for a in arrays:
            shape = getattr(a, "shape", None)
            if shape is not None and len(shape) > axis:
                lengths[axis] = int(shape[axis])
                break
    return lengths


def bucketed_call_shape(shape, spec, lengths):
    """``shape`` after pad-up under the dominant-length rule — the shape
    the compiled executable sees, computable WITHOUT materializing pads
    (cache-key lookups on the eager-fallback path stay allocation-free)."""
    out = list(shape)
    for axis, size in lengths.items():
        if axis < len(shape) and shape[axis] == size:
            out[axis] = spec.bucketed_dim(axis, size)
    return tuple(out)


def pad_array_to_bucket(arr, spec, lengths=None):
    """(possibly padded array, was_padded) for one jax/numpy array."""
    if lengths is None:
        lengths = infer_call_lengths([arr], spec)
    target = bucketed_call_shape(arr.shape, spec, lengths)
    if target == tuple(arr.shape):
        return arr, False
    import jax.numpy as jnp

    widths = [(0, t - s) for s, t in zip(arr.shape, target)]
    return jnp.pad(arr, widths), True


def tensor_leaves(tree):
    """Tensor leaves of an args/kwargs tree in call order."""
    from ..core.tensor import Tensor

    out = []

    def walk(x):
        if isinstance(x, Tensor):
            out.append(x)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)

    walk(tree)
    return out


def infer_tree_lengths(tree, spec):
    return infer_call_lengths([t._data for t in tensor_leaves(tree)], spec)


def bucketize_tree(tree, spec, lengths=None, per_leaf=False):
    """Pad the padding-safe Tensor leaves of an args/kwargs tree up to
    their bucket. Only ``stop_gradient`` tensors are padded: a
    grad-requiring input must keep its identity so the autograd edge
    reaches the caller's tensor (padding data/ids/masks is the supported
    contract).

    Selection: by default the dominant-length rule (infer_call_lengths)
    decides which leaves pad; ``per_leaf=True`` pads every eligible leaf up
    on every registered axis unconditionally — the mode for subtrees the
    caller EXPLICITLY selected via ``bucket_args``. Returns
    (new_tree, n_padded)."""
    from ..core.tensor import Tensor

    if lengths is None and not per_leaf:
        lengths = infer_tree_lengths(tree, spec)
    n_padded = 0

    def walk(x):
        nonlocal n_padded
        if isinstance(x, Tensor):
            if not x.stop_gradient:
                return x
            arr, padded = pad_array_to_bucket(
                x._data, spec, None if per_leaf else lengths)
            if not padded:
                return x
            n_padded += 1
            t = Tensor._wrap(arr)
            t.stop_gradient = True
            return t
        if isinstance(x, tuple):
            return tuple(walk(v) for v in x)
        if isinstance(x, list):
            return [walk(v) for v in x]
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x

    return walk(tree), n_padded


# --------------------------------------------------------------------------
# compile-cache telemetry
# --------------------------------------------------------------------------

# registry-backed compile-cache counters (ISSUE 10): the numbers live in
# paddle.observability.metrics under a `function` label and cache_stats()
# is a thin backward-compatible view over them, so one Prometheus scrape
# sees the same compile/hit telemetry the dict API reports. Per-shape miss
# breakdowns stay in the local dict below — shape signatures are unbounded
# and the registry's label-cardinality rule forbids them as labels.
_M_COMPILES = _obs_metrics.counter(
    "jit_compiles_total", "XLA compiles per jitted entry point")
_M_HITS = _obs_metrics.counter(
    "jit_cache_hits_total", "compile-cache hits per jitted entry point")
_M_EAGER = _obs_metrics.counter(
    "jit_eager_fallbacks_total",
    "uncompiled per-call executions (the 10-100x cliff)")
_M_PADS = _obs_metrics.counter(
    "jit_bucket_pads_total", "inputs zero-padded up to a shape bucket")
_M_SCALER_FB = _obs_metrics.counter(
    "jit_scaler_fallbacks_total",
    "drive() calls degraded to per-step fetch by an enabled GradScaler")


class FunctionCacheStats:
    """Per-entry-point compile-cache counters (one per function name).

    The counter-valued fields are registry-backed (`jit_*_total{function=
    <name>}`); this object keeps only what the registry must not hold:
    the unbounded per-shape miss map and the one-shot warn latch."""

    __slots__ = ("name", "per_shape_misses", "_warned",
                 "host_blocked_ms", "queue_depth_sum", "queue_depth_n")

    def __init__(self, name):
        self.name = name
        self.per_shape_misses = {}
        self._warned = False
        # host-device overlap telemetry (DevicePrefetcher / drive): how
        # long the consumer blocked waiting on the transfer thread, and the
        # staged-batch queue depth sampled at each get (depth ~0 means the
        # host is the bottleneck, depth ~prefetch_depth means the device
        # is). Kept as the legacy name-keyed row; the authoritative
        # per-instance series are io_host_blocked_ms / io_queue_depth in
        # the registry (two same-named loaders no longer merge there).
        self.host_blocked_ms = 0.0
        self.queue_depth_sum = 0
        self.queue_depth_n = 0

    @property
    def compiles(self):
        return int(_M_COMPILES.value(function=self.name))

    @property
    def hits(self):
        return int(_M_HITS.value(function=self.name))

    @property
    def eager_fallbacks(self):
        return int(_M_EAGER.value(function=self.name))

    @property
    def bucket_pads(self):
        return int(_M_PADS.value(function=self.name))

    @property
    def scaler_fallbacks(self):
        return int(_M_SCALER_FB.value(function=self.name))

    def as_dict(self):
        return {
            "compiles": self.compiles,
            "hits": self.hits,
            "eager_fallbacks": self.eager_fallbacks,
            "bucket_pads": self.bucket_pads,
            "per_shape_misses": dict(self.per_shape_misses),
            "scaler_fallbacks": self.scaler_fallbacks,
            "host_blocked_ms": round(self.host_blocked_ms, 3),
            "avg_queue_depth": (
                round(self.queue_depth_sum / self.queue_depth_n, 3)
                if self.queue_depth_n else None),
        }


_LOCK = threading.RLock()
_STATS: dict[str, FunctionCacheStats] = {}


def _stats_for(name):
    with _LOCK:
        s = _STATS.get(name)
        if s is None:
            s = _STATS[name] = FunctionCacheStats(name)
        return s


def shape_signature(arrays):
    """Compact human-readable signature of a call's dynamic-input shapes,
    the per_shape_misses key."""
    return "|".join(
        f"{tuple(a.shape)}:{a.dtype}".replace(" ", "") for a in arrays)


def record_compile(name, shape_sig=""):
    from ..core.flags import flag_value

    s = _stats_for(name)
    _M_COMPILES.inc(function=name)
    with _LOCK:
        s.per_shape_misses[shape_sig] = \
            s.per_shape_misses.get(shape_sig, 0) + 1
        compiles, warned = s.compiles, s._warned
    threshold = int(flag_value("jit_compile_warn_threshold", 8))
    if threshold > 0 and compiles > threshold and not warned:
        with _LOCK:
            s._warned = True
        warnings.warn(
            f"jit compile cache: `{name}` has been XLA-compiled "
            f"{compiles} times (> FLAGS_jit_compile_warn_threshold="
            f"{threshold}) — a recompile-per-shape cliff. Bucket the "
            "input pipeline (paddle.io.BucketedBatchSampler + PadToBucket) "
            "or register pad-up buckets "
            "(paddle.jit.set_shape_buckets([64, 128, ...])) so the compile "
            "count is O(buckets). See paddle.jit.cache_stats() for the "
            "per-shape miss breakdown.", stacklevel=3)


def record_hit(name):
    _stats_for(name)
    _M_HITS.inc(function=name)


def record_eager_fallback(name):
    """Count one uncompiled (cached-eager) invocation and return a live
    RecordEvent span so the 10-100x per-call cliff is visible in profiler
    timelines — callers ``end()`` it after the eager call returns."""
    from ..profiler.utils import RecordEvent

    _stats_for(name)
    _M_EAGER.inc(function=name)
    return RecordEvent(f"jit::eager_fallback::{name}").begin()


def record_scaler_fallback(name):
    """Count one ``FusedTrainStep.drive`` call that degraded from
    deferred-window metric fetch to per-step fetch because an enabled
    GradScaler was attached (dynamic loss scaling consumes the finite
    flag every step)."""
    _stats_for(name)
    _M_SCALER_FB.inc(function=name)


def record_bucket_pads(name, n):
    if n:
        _stats_for(name)
        _M_PADS.inc(n, function=name)


def record_host_blocked(name, ms):
    """Count milliseconds the consumer spent blocked on the host input
    path (waiting for the prefetch thread to deliver a staged batch)."""
    with _LOCK:
        _stats_for(name).host_blocked_ms += float(ms)


def record_queue_depth(name, depth):
    """Sample the staged-batch queue depth at a consumer get — the direct
    gauge of who is the bottleneck (0 = host-bound, max = device-bound)."""
    with _LOCK:
        s = _stats_for(name)
        s.queue_depth_sum += int(depth)
        s.queue_depth_n += 1


def cache_stats(name=None):
    """Compile-cache telemetry for every jitted entry point.

    Returns ``{function_name: {"compiles", "hits", "eager_fallbacks",
    "bucket_pads", "per_shape_misses"}}`` — or one such dict when ``name``
    is given. ``compiles`` counts traces handed to XLA, ``hits`` are calls
    served by an already-compiled executable, ``eager_fallbacks`` counts
    uncompiled per-call executions (the 10-100x cliff), and
    ``per_shape_misses`` maps each missing input-shape signature to how many
    compiles it caused. ``host_blocked_ms`` / ``avg_queue_depth`` are the
    host-device overlap gauges recorded by ``io.DevicePrefetcher`` (time
    the consumer waited on the transfer thread; staged-queue depth at each
    get — 0 means host-bound, prefetch_depth means device-bound)."""
    with _LOCK:
        if name is not None:
            s = _STATS.get(name)
            return s.as_dict() if s is not None else None
        return {n: s.as_dict() for n, s in _STATS.items()}


def reset_cache_stats():
    """Drop all compile-cache counters (does NOT drop compiled executables).
    The registry-backed series behind cache_stats() are dropped too, so a
    re-registered function name restarts from zero."""
    with _LOCK:
        names = list(_STATS)
        _STATS.clear()
    for m in (_M_COMPILES, _M_HITS, _M_EAGER, _M_PADS, _M_SCALER_FB):
        for n in names:
            m.remove(function=n)


# --------------------------------------------------------------------------
# CountingJit — jax.jit with compile-cache telemetry
# --------------------------------------------------------------------------

class CountingJit:
    """``jax.jit`` wrapper whose compile/hit behavior is visible in
    ``paddle.jit.cache_stats()`` under ``name``.

    The serving engine and the llama decode loop dispatch hand-built pure
    functions (donated KV buffers, no autograd) that bypass
    ``StaticFunction`` — without this wrapper their compiles would be
    invisible and the "zero decode recompiles after warmup" acceptance
    unverifiable. A shape signature (array shapes/dtypes + static-arg
    values) not seen before means jax traces + XLA-compiles a fresh
    executable this dispatch; anything else is a cache hit — the same
    counting contract as ``FusedTrainStep._count_dispatch``.

    ``donate_argnums`` is honored only on the TPU backend: XLA:CPU
    rejects donation with a warning per call, and the tier-1 tests run CPU.
    """

    __slots__ = ("name", "_jit", "_seen", "_static")

    def __init__(self, fn, name, static_argnums=(), donate_argnums=(),
                 jit_kwargs=None):
        import jax

        self.name = name
        self._static = tuple(static_argnums)
        if jax.default_backend() != "tpu":
            donate_argnums = ()
        self._jit = jax.jit(fn, static_argnums=self._static,
                            donate_argnums=donate_argnums,
                            **(jit_kwargs or {}))
        self._seen = set()

    def lower(self, *args):
        return self._jit.lower(*args)

    def _signature(self, args):
        import jax

        arrays = []
        statics = []
        for i, a in enumerate(args):
            if i in self._static:
                statics.append(repr(a))
                continue
            leaves = jax.tree_util.tree_leaves(a)
            arrays.extend(l for l in leaves if hasattr(l, "shape"))
        sig = shape_signature(arrays)
        return sig + ("||" + "|".join(statics) if statics else "")

    def __call__(self, *args):
        # a call that jax's own cache of this function already knew traced
        # and compiled nothing: a hit, told without reading the arguments
        # (a serving step passes some hundred arrays, and their signature
        # was a third of a millisecond of every dispatch: ISSUE 27). Only
        # a call that added to that cache, or a function jax keeps none
        # for, is judged by its signature.
        known = self._jit._cache_size()
        out = self._jit(*args)
        if known and self._jit._cache_size() == known:
            record_hit(self.name)
            return out
        sig = self._signature(args)
        if sig in self._seen:
            record_hit(self.name)
        else:
            self._seen.add(sig)
            record_compile(self.name, sig)
        return out


# --------------------------------------------------------------------------
# persistent compilation cache placement
# --------------------------------------------------------------------------

def place_compile_cache():
    """Decide where JAX's persistent compilation cache lives; returns the
    directory. ``JAX_COMPILATION_CACHE_DIR`` set means the place was chosen
    from outside: JAX reads the variable itself and nothing is touched
    here. Otherwise the cache sits at ``<checkout>/.jax_cache`` — a fixed
    path (the path is part of the cache key, so a directory that moves
    never hits). Every entry point that compiles at scale calls this once
    before its first compile; it initialises no backend."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
