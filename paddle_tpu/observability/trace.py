"""Span tracer: Chrome-trace/Perfetto-compatible JSON, zero added syncs.

The tracer answers "why was step 4017 slow" the way ``jit.cache_stats()``
never could: a timeline of host-side spans — window dispatch/fetch,
guard replay, sentinel verdicts, checkpoint saves, prefetcher staging,
per-request serving lifecycles — exportable as a single
``{"traceEvents": [...]}`` JSON that chrome://tracing and Perfetto open
directly, and that ``scripts/trace_report.py`` aggregates into a text
report.

The cardinal rule (DESIGN_DECISIONS.md "Observability"): spans open and
close ONLY at points where the host already blocks or already holds the
value — window boundaries, metric-fetch points, ingest staging, sampling
(post-fetch), checkpoint IO. A span never forces a device sync, never
wraps an async dispatch mid-flight, and costs one ``perf_counter_ns``
pair plus a dict append when enabled.

``span()`` is the one way the program opens a span, and it is live in two
cases (``live()`` says whether either holds). With the tracer enabled the
event goes to the in-memory buffer that ``export`` writes out. While a
``jax.profiler`` session runs (``TraceAnnotation.is_enabled()``) the span
also enters a ``jax.profiler.TraceAnnotation`` of exactly its name: a
``perf_counter_ns`` stamp cannot be laid over the profile afterwards (the
``.xplane.pb`` counts from the session's start), so a span shares the
device line's clock only by being an event *in* the profile, nested in
whatever span the caller holds. The span's ``args`` ride with it: the
buffer's event keeps them all, the profile's event carries those that are
``int``, ``float``, ``bool`` or ``str`` as its statistics (what a
``TraceAnnotation`` can encode, ``_statistics``; ``None`` and anything else
stay in the buffer only), under the same name, so a reader that finds
spans by name reads as before and one that wants the counts reads
``event.stats``. With both off (the default) ``span()`` returns a shared
no-op context manager and ``add_complete`` returns before taking the lock:
one attribute read and one ``is_enabled()`` call, nothing allocated. A
caller that passes ``args`` to ``span()`` builds them under ``live()``, and
one that passes them to ``add_complete`` (the buffer's alone) under
``enabled()``, so that this stays true of the call site too. ``jax`` is
imported by the first ``span()`` call, not by importing this module.

Timestamps are ``time.perf_counter_ns`` (monotonic), emitted in the
chrome-trace microsecond unit. Complete events use ``ph="X"``; per-request
serving spans ride on ``tid=<request id>`` so each request renders as its
own row (bounded by the live-request count, not an unbounded series —
the metric-label cardinality rule's trace-side analog).
"""

from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["Tracer", "TRACER", "span", "instant", "add_complete", "enable",
           "disable", "enabled", "live", "clear", "events", "drain", "export"]


class _NoopSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self):
        pass


_NOOP = _NoopSpan()

_annotation = None   # jax.profiler.TraceAnnotation, once a span has asked


def _profiling():
    """True while a ``jax.profiler`` session is running in this process."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation.is_enabled()


#: the profile writes an event as ``name#key=value,key=value#``
_SEPARATORS = str.maketrans("#,", "__")


def _statistics(args):
    """The ``args`` a profile's event can carry: ``int``, ``float``, ``bool``
    (an ``int``) and ``str``, a string with ``_`` where it holds one of the
    encoding's own separators (an engine is named ``llm_engine#1``)."""
    out = {}
    for key, value in (args or {}).items():
        if isinstance(value, str):
            out[key] = value.translate(_SEPARATORS)
        elif isinstance(value, (int, float)):
            out[key] = value
    return out


class _Span:
    """A live span: in the profile when ``profiled``, its scalar ``args``
    as the event's statistics, and in the tracer's buffer if the tracer is
    enabled when it ends."""

    __slots__ = ("_tracer", "name", "cat", "tid", "args", "_start", "_ann")

    def __init__(self, tracer, name, cat, tid, args, profiled):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self._ann = None
        if profiled:
            self._ann = _annotation(name, **_statistics(args))
            self._ann.__enter__()
        self._start = time.perf_counter_ns()

    def end(self):
        if self._start is None:
            return
        end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._tracer.add_complete(self.name, self._start, end_ns,
                                  cat=self.cat, tid=self.tid, args=self.args)
        self._start = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Tracer:
    """Thread-safe buffer of chrome-trace events with an on/off switch.

    The buffer is BOUNDED (``max_events``, default 1M): a tracer left
    armed on a long-lived server must not grow host memory without
    limit. On overflow the oldest quarter is dropped, counted in
    ``dropped`` (surfaced in ``export``'s metadata) and warned about
    once — a silently truncated trace reading as complete is the
    no-silent-caps rule's trace-side case."""

    DEFAULT_MAX_EVENTS = 1_000_000

    def __init__(self, max_events=None):
        self.enabled = False
        self.max_events = int(max_events or self.DEFAULT_MAX_EVENTS)
        self.dropped = 0
        self._warned_drop = False
        self._lock = threading.Lock()
        self._events = []

    # -- switches --------------------------------------------------------
    def enable(self):
        self.enabled = True

    def disable(self):
        self.enabled = False

    def clear(self):
        with self._lock:
            self._events = []
            self.dropped = 0
            self._warned_drop = False

    def _append(self, ev):
        with self._lock:
            self._events.append(ev)
            if len(self._events) <= self.max_events:
                return
            cut = max(1, len(self._events) // 4)
            del self._events[:cut]
            self.dropped += cut
            warn = not self._warned_drop
            self._warned_drop = True
        if warn:
            import warnings

            warnings.warn(
                f"observability tracer buffer exceeded max_events="
                f"{self.max_events}; dropping the oldest quarter "
                "(counted in Tracer.dropped / export metadata). Export "
                "or clear() periodically, or raise TRACER.max_events",
                RuntimeWarning, stacklevel=3)

    # -- recording -------------------------------------------------------
    def span(self, name, cat="host", tid=None, args=None):
        """Context manager measuring a host-side region: buffered when
        the tracer is enabled, a ``TraceAnnotation`` of the same name while
        a ``jax.profiler`` session runs, the shared no-op when neither."""
        profiled = _profiling()
        if not (self.enabled or profiled):
            return _NOOP
        return _Span(self, name, cat, tid, args, profiled)

    def add_complete(self, name, start_ns, end_ns, cat="host", tid=None,
                     args=None):
        """Record one complete (``ph="X"``) event from timestamps the
        caller already holds — how the serving engine emits request
        lifecycle spans retroactively at state transitions."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "cat": cat,
              "ts": start_ns / 1e3,
              "dur": max(end_ns - start_ns, 1) / 1e3,
              "pid": os.getpid(),
              "tid": tid if tid is not None else threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    def instant(self, name, cat="host", tid=None, args=None):
        """One ``ph="i"`` marker (e.g. a sentinel verdict)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "cat": cat,
              "ts": time.perf_counter_ns() / 1e3,
              "pid": os.getpid(),
              "tid": tid if tid is not None else threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    # -- readout ---------------------------------------------------------
    def events(self):
        with self._lock:
            return list(self._events)

    def drain(self):
        with self._lock:
            out, self._events = self._events, []
            return out

    def drain_since(self, cutoff_ts_us):
        """Remove and return events with ``ts >= cutoff``, keeping older
        ones — a Profiler RECORD window takes only its own spans and
        leaves a user's earlier buffered history (kept for their own
        ``export``) intact."""
        with self._lock:
            take = [e for e in self._events
                    if e.get("ts", 0.0) >= cutoff_ts_us]
            self._events = [e for e in self._events
                            if e.get("ts", 0.0) < cutoff_ts_us]
            return take

    def export(self, path):
        """Write the buffered events as chrome-trace JSON. The file opens
        directly in chrome://tracing / Perfetto and feeds
        ``scripts/trace_report.py``."""
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms"}
        if self.dropped:
            doc["metadata"] = {"droppedEvents": self.dropped}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


TRACER = Tracer()


# -- module-level facade over the process-wide tracer ----------------------

def span(name, cat="host", tid=None, args=None):
    return TRACER.span(name, cat=cat, tid=tid, args=args)


def instant(name, cat="host", tid=None, args=None):
    return TRACER.instant(name, cat=cat, tid=tid, args=args)


def add_complete(name, start_ns, end_ns, cat="host", tid=None, args=None):
    return TRACER.add_complete(name, start_ns, end_ns, cat=cat, tid=tid,
                               args=args)


def enable():
    TRACER.enable()


def disable():
    TRACER.disable()


def enabled():
    return TRACER.enabled


def live():
    """True if a ``span()`` opened now would be recorded anywhere: the
    tracer is enabled or a ``jax.profiler`` session runs. What a call site
    builds its span's ``args`` under."""
    return TRACER.enabled or _profiling()


def clear():
    TRACER.clear()


def events():
    return TRACER.events()


def drain():
    return TRACER.drain()


def export(path):
    return TRACER.export(path)
