"""The readers of the program's own spans (``benchmarks/harness/
program_spans.py``) on hand-made host and device lists, and each of the five
per-layer metrics that use them through ``run.read_layer_metric`` on a
synthetic record. Nothing here needs a chip or a profile on disk."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import program_spans, trace_reduce  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
NEW = ["decode.engine.prepare_ms_p50", "decode.engine.dispatch_ms_p50",
       "decode.engine.fetch_tail_ms_p50", "decode.engine.emit_ms_p50",
       "decode.device.idle_unattributed_share"]


def _step(t, prefill=False, scale=1.0):
    """The host spans of one 50-ms step that starts at ``t``: admit 1 ms,
    (a 10-ms prefill,) prepare 2, dispatch 1, fetch to 44, emit 3,
    bookkeeping 1; ``scale`` stretches the host's parts of a decode-only
    step. The device works from t+4 to t+40 (t+14 on with a prefill)."""
    ms = 1e-3
    s = scale
    host = [("bench.step", t, 50 * ms), ("engine.step", t + 0.1 * ms, 49.8 * ms),
            ("engine.admit", t + 0.2 * ms, 1 * ms * s)]
    at = t + 0.2 * ms + 1 * ms * s
    if prefill:
        host.append(("engine.prefill", at, 10 * ms))
        at += 10 * ms
    host += [("engine.decode.prepare", at, 2 * ms * s),
             ("engine.decode.dispatch", at + 2 * ms * s, 1 * ms * s)]
    at += 3 * ms * s
    fetch_end = t + 44 * ms
    host += [("engine.decode.fetch", at, fetch_end - at),
             ("np.asarray", at + 0.1 * ms, fetch_end - at - 0.2 * ms),
             ("engine.decode.emit", fetch_end, 3 * ms * s),
             ("engine.bookkeeping", fetch_end + 3 * ms * s, 1 * ms * s)]
    d0 = t + (14 if prefill else 4) * ms
    dev = [("fusion.1", d0, 10 * ms, "jit_decode_pure"),
           ("paged_decode_attention.7 custom-call bf16[32,4,8,128]",
            d0 + 10 * ms, t + 40 * ms - d0 - 10 * ms, "jit_decode_pure")]
    return host, dev


def synthetic(scales=(1.0, 1.0, 1.0)):
    """Four steps of 50 ms from t=1: a prefill step, then three that only
    decode; one settle step before the window, under no ``bench.step``."""
    host, dev = [], []
    settle_h, settle_d = _step(0.9)
    host += [h for h in settle_h if h[0] != "bench.step"]
    dev += settle_d
    for k, (prefill, scale) in enumerate([(True, 1.0)] + [(False, s) for s in scales]):
        h, d = _step(1.0 + 0.05 * k, prefill, scale)
        host += h
        dev += d
    return {"device": {"/device:TPU:0": dev}, "host": host}


def record(trace=None, with_host=True):
    trace = trace or synthetic()
    red = trace_reduce.reduce(trace)
    if with_host:
        red["host"] = trace["host"]
    return {"trace": red}


def test_steps_hold_their_phases_and_decode_only_steps_are_chosen():
    steps = program_spans.steps_of(synthetic()["host"])
    assert len(steps) == 5
    assert [program_spans.PREFILL in st for st in steps] == [
        False, True, False, False, False]
    only = program_spans.decode_only(steps)
    assert len(only) == 4 and all(
        len(st["engine.decode.fetch"]) == 1 for st in only)
    # jax's own events and the benchmark's span are no phases
    assert all(set(st) <= {"engine.step", "engine.admit", "engine.prefill",
                           "engine.decode.prepare", "engine.decode.dispatch",
                           "engine.decode.fetch", "engine.decode.emit",
                           "engine.bookkeeping"} for st in steps)
    st = only[1]
    assert program_spans.span_ms(st, program_spans.PREPARE) == pytest.approx(4.0)
    assert program_spans.span_ms(st, ("engine.decode.dispatch",)) == pytest.approx(1.0)
    assert program_spans.span_ms(st, ("engine.decode.draft",)) == 0.0


def test_the_fetch_tail_is_cut_at_the_last_device_operations_end():
    run = record()
    tr = run["trace"]
    gaps = trace_reduce.idle_gaps(tr["events"], tr["t0"], tr["t1"])
    st = program_spans.decode_only(program_spans.steps_of(tr["host"]))[1]
    # the fetch runs from t+4.2 to t+44 ms, the device from t+4 (inside the
    # dispatch) to t+40: 4 ms of transfer after it has ended
    assert program_spans.fetch_tail_ms(st, gaps) == pytest.approx(4.0)
    # a seam between two operations inside the fetch is not the host's
    a, b = st["engine.decode.fetch"][0]
    seam = [(a + 0.010, a + 0.010 + 5e-6)]
    assert program_spans.idle_inside(seam, a, b) == 0.0
    assert program_spans.idle_inside([(a - 1.0, a + 0.001)], a, b) == pytest.approx(0.001)


def test_medians_are_taken_over_the_decode_only_steps_of_the_window():
    run = record(synthetic(scales=(1.0, 1.2, 1.4)))
    # the settle step before the window and the prefill step are left out
    assert program_spans.phase_ms_p50(run, program_spans.PREPARE) == pytest.approx(4.8)
    assert program_spans.phase_ms_p50(run, ("engine.decode.emit",)) == pytest.approx(3.6)
    assert program_spans.phase_ms_p50(run, ("engine.decode.dispatch",)) == pytest.approx(1.2)
    # the device has started by the time any of the three reaches its fetch
    assert program_spans.fetch_tail_ms_p50(run) == pytest.approx(4.0)


def test_the_unattributed_share_is_the_idle_time_under_no_phase():
    run = record()
    tr = run["trace"]
    gaps = trace_reduce.idle_gaps(tr["events"], tr["t0"], tr["t1"])
    idle = sum(b - a for a, b in gaps)
    # the window: 4 steps of 50 ms; busy 26 + 3 x 36 ms
    assert idle == pytest.approx(0.200 - 0.134)
    # under no phase: 0.2 ms at the head of each step and its last 2 ms
    # (the device is idle there in all four)
    assert program_spans.idle_unattributed_share(run) == pytest.approx(
        100 * 4 * 0.0022 / idle, rel=1e-6)
    assert program_spans.unattributed_share([("bench.step", 1.0, 0.1)], gaps) is None
    assert program_spans.unattributed_share(tr["host"], []) is None


def test_a_profile_whose_window_is_not_the_records_is_not_taken(monkeypatch, tmp_path):
    trace = synthetic()
    run = record(trace, with_host=False)
    profile = tmp_path / "benchmarks_out" / "cell" / "trace" / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    loaded = []

    def load(path):
        loaded.append(path)
        return trace

    monkeypatch.setattr(trace_reduce, "load_xplane", load)
    monkeypatch.setattr(program_spans, "_PARSED", {})
    assert program_spans.host_line(run) == trace["host"]
    assert program_spans.phase_ms_p50(run, ("engine.decode.emit",)) == pytest.approx(3.0)
    assert len(loaded) == 1            # parsed once a process
    # another run's record: same file, another window
    other = {"trace": dict(run["trace"], t0=run["trace"]["t0"] + 0.05)}
    assert program_spans.host_line(other) is None
    assert program_spans.fetch_tail_ms_p50(other) is None
    assert program_spans.idle_unattributed_share(other) is None
    # no profile at all, no trace at all
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path / "nowhere"))
    monkeypatch.setattr(program_spans, "_PARSED", {})
    assert program_spans.host_line(run) is None
    assert program_spans.host_line({"trace": None}) is None


def test_a_program_without_the_spans_reads_as_nothing():
    trace = synthetic()
    trace["host"] = [h for h in trace["host"] if not h[0].startswith("engine.")]
    run = record(trace)
    for name in NEW:
        assert bench_run.read_layer_metric(name, run) is None
    assert bench_run.read_layer_metric(NEW[0], {"trace": None}) is None


@pytest.mark.parametrize("name,want", [
    ("decode.engine.prepare_ms_p50", 4.0),
    ("decode.engine.dispatch_ms_p50", 1.0),
    ("decode.engine.fetch_tail_ms_p50", 4.0),
    ("decode.engine.emit_ms_p50", 3.0),
    ("decode.device.idle_unattributed_share", 100 * 0.0088 / 0.066),
])
def test_each_metric_reads_its_spans_from_a_synthetic_record(name, want):
    assert bench_run.read_layer_metric(name, record()) == pytest.approx(want, rel=1e-6)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == ["mistral7b-serve.decode-sat"]
    spec = bench_run.load_json("benchmarks", "layer_metrics", name + ".json")
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])


def test_the_five_are_the_manifests_last_entries_and_the_line_carries_them():
    assert [m["name"] for m in MANIFEST["per_layer"][-5:]] == NEW
    run = dict(record(), correct=True, attempted=1, failed=0, device={},
               series={}, counters={}, work={}, values={})
    run["trace"].setdefault("breakdown", {})
    line = bench_run.result_line(MANIFEST, "mistral7b-serve.decode-sat", run, True)
    assert set(NEW) <= set(line["metrics"])
    assert all(line["metrics"][n]["value"] >= 0 for n in NEW)
