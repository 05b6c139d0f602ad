"""Profiler core.

Reference: python/paddle/profiler/profiler.py — Profiler (:346),
make_scheduler (:117), export_chrome_tracing (:215), ProfilerState /
ProfilerTarget enums.

TPU-native: host spans come from the one span recorder,
``paddle.observability.trace`` (RecordEvent is a wrapper over it, utils.py);
device traces are jax.profiler sessions (libtpu/XLA trace, viewable in
TensorBoard/Perfetto) started and stopped around RECORD windows, and every
span open meanwhile is an event in them too. export_chrome_tracing
writes the host spans as a chrome://tracing JSON next to the device trace
directory.
"""

from __future__ import annotations

import enum
import json
import os
import socket
import time

from ..observability import trace as obs_trace
from .utils import RECORD_EVENT_CAT

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "load_profiler_result"]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last RECORD step of a window


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """reference profiler.py:117 — step-number -> ProfilerState.

    The cycle is [closed]*closed + [ready]*ready + [record]*record,
    repeated `repeat` times (0 = forever), after `skip_first` initial
    CLOSED steps. The last record step of each cycle returns
    RECORD_AND_RETURN (trace handed to on_trace_ready).
    """
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("closed/ready must be >=0 and record >= 1")
    span = closed + ready + record

    def fn(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * span:
            return ProfilerState.CLOSED
        pos = s % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_state_scheduler(step):
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name, worker_name=None):
    """reference profiler.py:215 — returns an on_trace_ready callback
    writing <dir>/<worker>_time.json in chrome trace format."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof):
        worker = worker_name or f"host_{socket.gethostname()}_{os.getpid()}"
        path = os.path.join(dir_name, f"{worker}_time_{int(time.time()*1e3)}"
                            ".paddle_trace.json")
        prof.export(path, format="json")
        return path

    return handler


def load_profiler_result(filename):
    with open(filename) as f:
        return json.load(f)


class Profiler:
    """reference profiler.py:346.

    Usage::

        with profiler.Profiler(
                scheduler=profiler.make_scheduler(closed=1, ready=1,
                                                  record=2),
                on_trace_ready=profiler.export_chrome_tracing("./log"),
        ) as p:
            for batch in loader:
                train_step(batch)
                p.step()
        print(p.summary())
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=max(lo - 1, 0), ready=1 if lo > 0 else 0,
                record=hi - lo, repeat=1)
        else:
            self._scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_tracing = False
        self._trace_dir = None
        # the tracer's events of the RECORD window: RecordEvent spans and
        # the runtime's own (drive/serving/checkpoint) in one chrome trace
        self._obs_spans = []
        self._owns_tracer = False
        self._obs_window_start_ts = 0.0  # chrome-trace us clock
        from .timer import benchmark

        self._benchmark = benchmark()

    # -- device trace (jax.profiler) ------------------------------------
    def _want_device_trace(self):
        return (not self.timer_only
                and any(t in (ProfilerTarget.GPU, ProfilerTarget.TPU,
                              ProfilerTarget.CUSTOM_DEVICE)
                        for t in self.targets))

    def _start_device_trace(self):
        if not self._want_device_trace() or self._device_tracing:
            return
        try:
            import tempfile

            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_trace_")
            jax.profiler.start_trace(self._trace_dir)
            self._device_tracing = True
        except Exception:
            self._trace_dir = None
            self._device_tracing = False

    def _stop_device_trace(self):
        if not self._device_tracing:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:
            pass
        self._device_tracing = False

    # -- state machine ---------------------------------------------------
    def _transit(self, new_state):
        old = self.current_state
        if old == new_state:
            return
        recording_old = old in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN)
        recording_new = new_state in (ProfilerState.RECORD,
                                      ProfilerState.RECORD_AND_RETURN)
        if not recording_old and recording_new:
            # arm the span tracer for the window; if the user already has
            # it on (collecting their own trace), leave it theirs and
            # remember where this window starts so export() takes only
            # in-window spans, not the user's whole history
            self._owns_tracer = not obs_trace.TRACER.enabled
            self._obs_window_start_ts = time.perf_counter_ns() / 1e3
            if self._owns_tracer:
                obs_trace.TRACER.enable()
            self._start_device_trace()
        elif recording_old and not recording_new:
            # a custom scheduler may go RECORD -> CLOSED/READY without ever
            # returning RECORD_AND_RETURN; tear the window down here so the
            # recorder and device trace never leak (reference state machine)
            self._finish_window()
        self.current_state = new_state

    def _finish_window(self):
        # capture ONLY the observability spans recorded during this
        # window (ts cutoff at RECORD start — a user's pre-window
        # history, enabled or disabled-but-buffered, never leaks into
        # the profile). If we armed the tracer, drain our window's
        # events and disarm, leaving any earlier buffered events for the
        # user's own trace.export(); a user-enabled tracer keeps its
        # whole buffer — we only copy.
        if self._owns_tracer:
            self._obs_spans = obs_trace.TRACER.drain_since(
                self._obs_window_start_ts)
            obs_trace.TRACER.disable()
            self._owns_tracer = False
        else:
            self._obs_spans = [
                e for e in obs_trace.TRACER.events()
                if e.get("ts", 0.0) >= self._obs_window_start_ts]
        self._stop_device_trace()
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def start(self):
        self._benchmark.begin()
        self.step_num = 0
        self._transit(self._scheduler(0))
        return self

    def stop(self):
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._finish_window()
        self.current_state = ProfilerState.CLOSED
        self._benchmark.end()

    def step(self, num_samples=1):
        self._benchmark.step(num_samples)
        if self.current_state == ProfilerState.RECORD_AND_RETURN:
            self._finish_window()
            self.current_state = ProfilerState.CLOSED
        self.step_num += 1
        self._transit(self._scheduler(self.step_num))

    def step_info(self, unit=None):
        return self._benchmark.step_info(unit)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- output ----------------------------------------------------------
    def export(self, path, format="json"):
        """Write the captured host spans as a chrome trace: every
        ``paddle.observability.trace`` span recorded in the window
        (RecordEvent spans, drive windows, serving request lifecycles,
        checkpoint IO). The device trace (if any) lives in
        self._trace_dir for TensorBoard."""
        doc = {
            "traceEvents": list(self._obs_spans),
            "metadata": {"device_trace_dir": self._trace_dir},
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        from .profiler_statistic import build_summary

        return build_summary(self.record_events(), time_unit=time_unit)

    def record_events(self):
        """``(name, start_ns, end_ns, tid)`` of the window's RecordEvent
        spans: the rows of ``summary()``."""
        return [(e["name"], e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3,
                 e["tid"])
                for e in self._obs_spans
                if e.get("cat") == RECORD_EVENT_CAT and e.get("ph") == "X"]
