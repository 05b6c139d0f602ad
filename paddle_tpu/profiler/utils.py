"""Host-side event recording.

Reference: python/paddle/profiler/utils.py (RecordEvent) backed by the
C++ HostTracer/HostEventRecorder (paddle/fluid/platform/profiler/
host_tracer.cc, host_event_recorder.h). TPU-native: ``RecordEvent`` is a
thin wrapper over the one span recorder, ``paddle.observability.trace``:
its span goes to the tracer's buffer while a ``Profiler`` RECORD window
(or the user) has the tracer enabled, and into the ``jax.profiler`` trace
while a device trace runs, so host spans show up inside the device trace
timeline too.
"""

from __future__ import annotations

from ..observability import trace as _obs_trace

__all__ = ["RecordEvent", "in_profiler_mode", "wrap_optimizers"]

#: chrome-trace category of ``RecordEvent`` spans: what tells them from the
#: runtime's own spans in ``Profiler.summary()``
RECORD_EVENT_CAT = "record_event"


def in_profiler_mode():
    """True while host spans are being recorded (a ``Profiler`` RECORD
    window arms the tracer)."""
    return _obs_trace.enabled()


class RecordEvent:
    """User-facing span marker (reference utils.py RecordEvent).

    Usage::

        with profiler.RecordEvent("data_loading"):
            batch = next(loader)
    """

    def __init__(self, name, event_type=None):
        self.name = name
        self.event_type = event_type
        self._span = None

    def begin(self):
        self._span = _obs_trace.span(self.name, cat=RECORD_EVENT_CAT)
        return self

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


def wrap_optimizers():
    """Reference hooks optimizer.step into RecordEvent spans; our
    optimizer layer emits ops through the dispatcher, which the device
    trace captures — no wrapping needed."""
