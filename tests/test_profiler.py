"""Profiler tests (reference model: test/legacy_test/test_profiler*.py,
python/paddle/profiler/profiler.py:346)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import profiler
from paddle_tpu.profiler import ProfilerState


class TestScheduler:
    def test_make_scheduler_cycle(self):
        sch = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=1)
        states = [sch(i) for i in range(6)]
        assert states[:4] == [ProfilerState.CLOSED, ProfilerState.READY,
                              ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN]
        # repeat=1 → closed afterwards
        assert states[4] == ProfilerState.CLOSED
        assert states[5] == ProfilerState.CLOSED

    def test_skip_first(self):
        sch = profiler.make_scheduler(closed=0, ready=0, record=1,
                                      skip_first=2)
        assert sch(0) == ProfilerState.CLOSED
        assert sch(1) == ProfilerState.CLOSED
        assert sch(2) == ProfilerState.RECORD_AND_RETURN

    def test_invalid(self):
        with pytest.raises(ValueError):
            profiler.make_scheduler(closed=1, ready=1, record=0)


class TestRecordEvent:
    def test_spans_recorded_only_when_enabled(self):
        from paddle_tpu.observability.trace import TRACER

        TRACER.clear()
        TRACER.disable()
        assert not profiler.in_profiler_mode()
        with profiler.RecordEvent("not_recorded"):
            pass
        assert len(TRACER.events()) == 0
        TRACER.enable()
        try:
            assert profiler.in_profiler_mode()
            with profiler.RecordEvent("recorded"):
                pass
        finally:
            TRACER.disable()
        assert [(e["name"], e["cat"]) for e in TRACER.events()] == [
            ("recorded", "record_event")]
        TRACER.clear()


class TestProfiler:
    def test_profile_train_step_writes_trace(self, tmp_path):
        """The VERDICT acceptance test: profile a train step, get a trace
        file on disk."""
        traces = []

        def on_ready(prof):
            handler = profiler.export_chrome_tracing(str(tmp_path))
            traces.append(handler(prof))

        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        X = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        Y = paddle.to_tensor(np.random.randint(0, 2, (4,)).astype("int64"))

        p = profiler.Profiler(
            targets=[profiler.ProfilerTarget.CPU],
            scheduler=profiler.make_scheduler(closed=1, ready=1, record=2,
                                              repeat=1),
            on_trace_ready=on_ready,
        )
        with p:
            for _ in range(5):
                with profiler.RecordEvent("forward"):
                    loss = nn.CrossEntropyLoss()(model(X), Y)
                with profiler.RecordEvent("backward"):
                    loss.backward()
                with profiler.RecordEvent("optimizer"):
                    opt.step()
                    opt.clear_grad()
                p.step()

        assert len(traces) == 1
        assert os.path.exists(traces[0])
        doc = json.load(open(traces[0]))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"forward", "backward", "optimizer"} <= names
        # every event carries a positive duration
        assert all(e["dur"] > 0 for e in doc["traceEvents"])

    def test_summary_table(self):
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        with p:
            for _ in range(3):
                with profiler.RecordEvent("compute"):
                    pass
        s = p.summary()
        assert "compute" in s
        assert "Calls" in s

    def test_step_info_reports_ips(self):
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                              timer_only=True)
        p.start()
        for _ in range(4):
            p.step(num_samples=32)
        info = p.step_info()
        p.stop()
        assert "avg_samples_per_sec" in info


class TestBenchmark:
    def test_ips_math(self):
        import time

        bm = profiler.Benchmark()
        bm.begin()
        for _ in range(4):
            time.sleep(0.01)
            bm.step(10)
        bm.end()
        # 3 counted steps (skip_first=1) of ~10ms each, 10 items per step
        assert 300 < bm.ips < 3000
        assert bm.batch.count == 3
