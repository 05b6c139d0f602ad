"""The benchmark's own checks: schedule, arithmetic, trace reduction, shape
functions, the manifest against its files, and a CPU rehearsal of both
runners at a tiny configuration (kernels in interpret mode). No test needs
a chip and nothing here describes a topology."""

import json
import math
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (peaks, readers, schedule, stats,  # noqa: E402
                                trace_reduce)

MANIFEST = bench_run.load_json("BENCHMARK.json")
MISTRAL16 = dict(hidden_size=4096, head_dim=128, num_attention_heads=32,
                 num_key_value_heads=8, intermediate_size=14336,
                 vocab_size=32768, num_hidden_layers=16)


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def traffic_files():
    d = os.path.join(ROOT, "benchmarks", "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


# --- schedule ---------------------------------------------------------------

@pytest.mark.parametrize("name", [t for t in traffic_files()
                                  if bench_run.load_json(
                                      "benchmarks", "traffic", t + ".json"
                                  )["loop"] == "closed"])
def test_schedule_is_the_cells_and_content_is_the_seeds(name):
    traffic = bench_run.load_json("benchmarks", "traffic", name + ".json")
    a, b = schedule.build(traffic), schedule.build(traffic)
    assert a == b and len(a) == traffic["requests"]
    # --seed reaches token ids only: same lengths, other ids
    x = schedule.token_ids(11, a[0].index, a[0].prompt_len, 32768)
    y = schedule.token_ids(4000000007, a[0].index, a[0].prompt_len, 32768)
    assert x.shape == y.shape and (x != y).any()
    assert (x == schedule.token_ids(11, a[0].index, a[0].prompt_len, 32768)).all()
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    assert all(p["min"] <= it.prompt_len <= p["max"] for it in a)
    assert all(o["min"] <= it.output_len <= o["max"] for it in a)
    lens = sorted(it.prompt_len for it in a)
    assert abs(lens[len(lens) // 2] - p["median"]) <= 0.02 * p["median"]


def test_the_list_cycles_on_with_new_indices_and_the_same_lengths():
    items = schedule.build(tiny("tiny-closed.json"))
    it = schedule.cycled(items)
    lap = [next(it) for _ in range(len(items) + 2)]
    assert lap[:len(items)] == items
    assert lap[len(items)].index == len(items)
    assert lap[len(items)].prompt_len == items[0].prompt_len
    assert all(i.due_s == 0.0 for i in lap)
    with pytest.raises(ValueError, match="unknown loop"):
        schedule.build(dict(tiny("tiny-closed.json"), loop="open"))


# --- arithmetic ---------------------------------------------------------------

@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50], 90, 46.0),
    ([5], 95, 5),
    ([1, 2, math.inf], 90, math.inf),      # a failed request sorts last
    ([1, 2, 3, math.inf], 50, 2.5),
    ([], 50, None),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_slices_run_from_step_to_step_and_the_whole_window_counts_all():
    # 3 slices of 2 s from t=100; one stalled slice; a part-slice dropped
    ev = [(99.9, 99), (100.5, 10), (101.9, 10), (102.1, 2), (104.0, 10),
          (105.99, 10), (106.5, 99)]
    rates = stats.slice_rates(ev, 100.0, 7.0, 2.0)
    # [100, 101.9]: 20; (101.9, 102.1]: 2; (102.1, 105.99]: 20
    assert rates == pytest.approx([20 / 1.9, 2 / 0.2, 20 / 3.89])
    assert stats.median(rates) == pytest.approx(10.0)
    assert stats.window_rate(ev, 100.0, 106.0) == pytest.approx(42 / 6.0)
    # steady steps of 0.3 s: every slice reads the same, wherever it is cut
    steady = [(100.0 + 0.3 * k, 32) for k in range(1, 40)]
    assert stats.slice_rates(steady, 100.0, 10.0, 2.0) == pytest.approx(
        [32 / 0.3] * 5)


# --- trace reduction -----------------------------------------------------------

def synthetic_trace():
    dev = [  # (name, start, dur, module)
        ("fusion.1", 1.000, 0.010, "jit_decode_pure"),
        ("decode_pure.7 custom-call bf16[32,4,8,128]", 1.010, 0.020, "jit_decode_pure"),
        ("fusion.2", 1.030, 0.010, "jit_decode_pure"),
        # 20 ms gap while the host fetches
        ("fusion.1", 1.060, 0.010, "jit_decode_pure"),
        ("decode_pure.7 custom-call bf16[32,4,8,128]", 1.070, 0.020, "jit_decode_pure"),
        ("chunk_pure.9 custom-call bf16[1,64,32,128]", 1.090000005, 0.005, "jit_chunk_pure"),
        ("fusion.3", 1.200, 0.010, "jit_other"),      # outside the window
    ]
    host = [("bench.step", 1.000, 0.050), ("np.asarray", 1.041, 0.018),
            ("bench.step", 1.050, 0.050)]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_trace_reduce_idle_share_and_op_shares():
    red = trace_reduce.reduce(synthetic_trace())
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.075, abs=1e-6)
    run = {"trace": red, "work": {"k": 0.004}}
    assert readers.idle_share(run) == pytest.approx(25.0, abs=1e-3)
    # attention = custom calls inside decode_pure: 40 of 75 ms busy
    assert readers.device_share(run, op=" custom-call ", module="decode_pure") \
        == pytest.approx(100 * 40 / 75, abs=1e-3)
    assert readers.roofline(run, work="k", op=" custom-call ",
                            module="decode_pure") == pytest.approx(10.0)
    # everything in decode_pure but the custom calls: 70 - 40 = 30 ms
    assert readers.roofline(run, work="k", not_op=" custom-call ",
                            module="decode_pure") == pytest.approx(
                                100 * 0.004 / 0.030, abs=1e-3)
    assert red["breakdown"]["device_ops"][0] == [
        "jit_decode_pure/decode_pure.7_custom-call_bf16_32_4_8_128_",
        pytest.approx(0.040)]
    assert readers.idle_share({"trace": None}) is None
    assert readers.device_share({"trace": None}, op="x") is None


def test_trace_reduce_splits_each_gap_among_the_innermost_host_spans():
    red = trace_reduce.reduce(synthetic_trace())
    gaps = dict(red["breakdown"]["idle_gaps"])
    # the 20 ms gap [1.040, 1.060]: 1 ms of the first step before the
    # fetch, the fetch's 18 ms, 1 ms of the second step after it
    assert gaps["np.asarray"] == pytest.approx(0.018)
    assert gaps["bench.step"] == pytest.approx(0.002 + 0.005, abs=1e-6)  # + the tail
    assert gaps["seams_between_ops"] == pytest.approx(5e-9, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(0.025, abs=1e-6)
    none = trace_reduce.attribute_gaps([(5.0, 5.1)], synthetic_trace()["host"])
    assert none == [["outside_bench_spans", pytest.approx(0.1)]]
    # a gap that runs past the last span: the rest is outside
    part = dict(trace_reduce.attribute_gaps([(1.09, 1.11)],
                                            synthetic_trace()["host"]))
    assert part["bench.step"] == pytest.approx(0.01)
    assert part["outside_bench_spans"] == pytest.approx(0.01)


def test_hlo_instruction_text_is_cut_to_name_opcode_result():
    f = trace_reduce.op_name
    assert f("%decode_pure.16 = bf16[32,4,8,128]{3,2,1,0:T(8,128)(2,1)} "
             "custom-call(bf16[32,4,8,128]{3,2,1,0} %fusion.2, s32[32]{0} %p)"
             ) == "decode_pure.16 custom-call bf16[32,4,8,128]"
    assert f("%copy-start.23 = (s32[1,32]{1,0:T(1,128)S(1)}, s32[1,32]{1,0}, "
             "u32[]{:S(2)}) copy-start(s32[1,32]{1,0:T(1,128)} %ids.1)"
             ) == "copy-start.23 copy-start s32[1,32]"
    # a fusion that consumes a kernel's result is not a kernel
    assert " custom-call " not in f(
        "%fusion.5 = bf16[32,4096]{1,0} fusion(bf16[32,4,8,128]{3,2,1,0} "
        "%custom-call.3), kind=kLoop, calls=%fused_computation.1")
    assert f("already short") == "already short"


def test_ops_without_a_module_take_the_program_that_holds_them():
    ops = [("a", 1.0, 0.1, None), ("b", 2.05, 0.1, None), ("c", 9.0, 0.1, None)]
    mods = [("jit_x(1)", 0.9, 0.5), ("jit_y(2)", 2.0, 0.5)]
    got = trace_reduce._with_modules(ops, mods)
    assert [m for *_, m in got] == ["jit_x(1)", "jit_y(2)", None]


class _SimulatedTrainer:
    """A device that runs dispatched steps one after another, ``step_s``
    each, and a host that only dispatches: the trainer as the profiler
    sees it, on a clock of its own."""

    def __init__(self, step_s, host_s, run_ahead):
        self.now, self.step_s, self.host_s = 0.0, step_s, host_s
        self.run_ahead, self.device, self.spans = run_ahead, [], []

    def perf_counter(self):
        return self.now

    def one(self):
        self.now += self.host_s
        start = max(self.now, self.device[-1][1] if self.device else 0.0)
        self.device.append((start, start + self.step_s))
        if len(self.device) > self.run_ahead:
            self.now = max(self.now, self.device[-1 - self.run_ahead][1])

    def drain(self):
        self.now = max(self.now, self.device[-1][1])

    def span(self):
        import contextlib

        @contextlib.contextmanager
        def cm():
            t0 = self.now
            yield
            self.spans.append(("bench.step", t0, self.now - t0))
        return cm()


def test_traced_train_steps_count_the_steps_the_window_times(monkeypatch):
    """The host runs two steps ahead of the device. Every step counted as
    work has to finish inside the window cut from the step spans, or the
    roofline counts work whose time it never saw (it read 17/15 too high)."""
    from benchmarks.runners import train

    sim = _SimulatedTrainer(step_s=0.2, host_s=0.001, run_ahead=train.RUN_AHEAD)
    monkeypatch.setattr(train.time, "perf_counter", sim.perf_counter)
    n = train.traced_steps(sim.one, sim.drain, 3.0, sim.span)
    assert n == len(sim.spans) == len(sim.device) >= 15
    t0, t1 = trace_reduce.window_of(sim.spans)
    assert all(t0 <= a and b <= t1 for a, b in sim.device)
    # through the reduction and the reader: kernels take a tenth of each
    # step and do 0.008 s of least work a step
    dev = [("step.1 custom-call bf16[1,4096,32,128]", a, 0.02, "jit_step")
           for a, _ in sim.device]
    dev += [("fusion.1", a + 0.02, 0.18, "jit_step") for a, _ in sim.device]
    red = trace_reduce.reduce({"device": {"/device:TPU:0": dev},
                               "host": sim.spans})
    run = {"trace": red, "work": {"flash_s": n * 0.008}}
    assert readers.roofline(run, work="flash_s", op=" custom-call ") \
        == pytest.approx(40.0)
    assert readers.device_share(run, op=" custom-call ") == pytest.approx(10.0)


# --- shapes ----------------------------------------------------------------------

def test_shape_functions_against_numbers_worked_by_hand():
    m = MISTRAL16
    # q, o: 4096 x 4096; k, v: 4096 x 1024; gate, up, down: 4096 x 14336
    assert peaks.layer_params(m) == 2 * 16777216 + 2 * 4194304 + 3 * 58720256
    assert peaks.layer_params(m) == 218103808
    assert peaks.matmul_params(m) == 16 * 218103808 + 4096 * 32768
    # K and V, 8 heads x 128 x 2 bytes, 16 layers: 64 KiB a token
    assert peaks.kv_bytes_per_token(m) == 65536
    assert peaks.paged_decode_bytes(m, 32 * 1000) == 32000 * 65536
    assert peaks.weight_stream_bytes(m) == 7247757312
    # 4 queries after 0 cached: 1 + 2 + 3 + 4 = 10 (query, key) pairs,
    # x 2 matmuls x 2 flops x 32 heads x 128
    assert peaks.causal_attention_flops(m, 4) == 10 * 4 * 32 * 128
    assert peaks.causal_attention_flops(m, 2, kv_start=10) == (11 + 12) * 16384
    one_layer = dict(m, num_hidden_layers=1)
    s = 4096
    fwd = 4 * 32 * 128 * (s * (s + 1) // 2)
    assert peaks.flash_train_flops(one_layer, 2, s) == 3 * 2 * fwd
    assert peaks.train_flops_per_token(one_layer, s) == pytest.approx(
        6 * (218103808 + 134217728) + 3 * fwd / s)
    pk = peaks.peaks_for("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("TPU v9")
    assert peaks.PEAKS["TPU v5e"] is peaks.PEAKS["TPU v5 lite"]


# --- the manifest against its files ------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_cell_finds_its_files_and_every_name_is_allowed():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for cell in MANIFEST["workloads"]:
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        cfg = bench_run.load_json(configs[cell["config"]]["file"])
        bench_run.load_json("benchmarks", "traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "runners", cfg["kind"] + ".py"))
        assert sorted(cfg["reduced"]) == sorted(
            configs[cell["config"]]["reduced"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in MANIFEST[g]]
    names += [c["name"] for c in MANIFEST["workloads"] + MANIFEST["configs"]]
    names += [c[k] for c in MANIFEST["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names[:len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])])) \
        == len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])
    for g in ("end_to_end", "per_layer"):
        for m in MANIFEST[g]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])


def test_every_layer_metric_has_a_reader_and_moves_what_its_cells_report():
    cells = [c["name"] for c in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        own = os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"])
        spec = bench_run.load_json("benchmarks", "layer_metrics",
                                   m["name"] + ".json")
        assert os.path.exists(own + ".py") or spec["reader"] in readers.READERS
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            m["layer"], m["unit"], m["moves"])
        for cell in m.get("workloads", cells):
            reported = [e["name"] for e in
                        bench_run.metrics_of(MANIFEST, "end_to_end", cell)]
            assert m["moves"] in reported and "setup_s" in reported
    for cell in cells:
        assert len(bench_run.metrics_of(MANIFEST, "end_to_end", cell)) >= 2
        assert bench_run.metrics_of(MANIFEST, "per_layer", cell)


def test_generic_readers_on_a_hand_made_record():
    run = {"series": {"step_ms": [1.0, 2.0, 3.0, 4.0]},
           "counters": {"host_syncs": 5, "tokens_out": 100},
           "values": {"x": 7.5},
           "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 8 * 2 ** 30}}
    assert readers.percentile(run, "step_ms", 50) == 2.5
    assert readers.mean(run, "step_ms") == 2.5
    assert readers.ratio(run, "counters.host_syncs", "counters.tokens_out") == 0.05
    assert readers.value(run, "values.x") == 7.5
    assert readers.peak_hbm_share(run) == 50.0
    # a reader that finds nothing returns nothing
    assert readers.percentile(run, "no_such", 50) is None
    assert readers.value(run, "values.no_such") is None
    assert readers.ratio(run, "counters.host_syncs", "counters.none") is None


# --- reference and command ---------------------------------------------------------------

def test_row_error_on_hand_made_rows():
    import numpy as np

    from benchmarks.harness import reference

    want = np.array([3.0, -4.0, 0.0, 0.0], np.float32)
    got = np.array([3.0, -4.0, 0.5, 0.0], np.float32)
    assert reference.row_error(got, want) == pytest.approx(0.5 / 5.0)
    assert reference.row_error(want, want) == 0.0
    # every entry counts: the same largest difference, spread wider, reads more
    assert reference.row_error(want + 0.5, want) == pytest.approx(1.0 / 5.0)


def _reference_weights(h=64, d=32, f=128, v=96, layers=2):
    import jax.numpy as jnp
    import numpy as np

    shapes = {"llama.embed_tokens.weight": (v, h), "llama.norm.weight": (h,),
              "lm_head.weight": (h, v)}
    for i in range(layers):
        pre = f"llama.layers.{i}."
        shapes.update({
            pre + "input_layernorm.weight": (h,),
            pre + "post_attention_layernorm.weight": (h,),
            pre + "self_attn.q_proj.weight": (h, 2 * d),
            pre + "self_attn.k_proj.weight": (h, d),
            pre + "self_attn.v_proj.weight": (h, d),
            pre + "self_attn.o_proj.weight": (2 * d, h),
            pre + "mlp.gate_proj.weight": (h, f),
            pre + "mlp.up_proj.weight": (h, f),
            pre + "mlp.down_proj.weight": (f, h)})
    rng = np.random.default_rng(0)
    return {k: jnp.asarray(
        np.ones(s) if k.endswith("norm.weight") else rng.normal(0, 0.2, s),
        jnp.bfloat16) for k, s in shapes.items()}


def test_reference_is_causal_and_its_loss_is_its_logits():
    """The reference stands alone (the rehearsal compares the engine with
    it, so a fault shared by both would pass): a later token changes no
    earlier row, and the loss is the cross entropy of those logits."""
    import numpy as np

    from benchmarks.harness import reference

    model = dict(num_hidden_layers=2, num_attention_heads=2,
                 num_key_value_heads=1, rms_norm_eps=1e-5, rope_theta=1e6)
    w = _reference_weights()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 96, (2, 12)).astype(np.int32)
    a = np.asarray(reference.logits(w, ids, model))
    ids2 = ids.copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % 96
    b = np.asarray(reference.logits(w, ids2, model))
    assert a.shape == (2, 12, 96) and a.dtype == np.float32
    assert np.array_equal(a[:, :-1], b[:, :-1]) and (a[:, -1] != b[:, -1]).any()
    labels = rng.integers(0, 96, (2, 12))
    logp = a - np.log(np.exp(a).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, labels[..., None], -1).mean()
    assert reference.loss(w, ids, labels, model) == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_command_hands_the_cell_to_its_runner_and_prints_the_line(
        trace, monkeypatch, capsys):
    """``run.py`` from arguments to last line, with the chip check and the
    runner replaced: the cell's chips, files and seed reach the runner, and
    the line holds the cell's metrics and nothing else."""
    from benchmarks.runners import common, train

    cell = next(c for c in MANIFEST["workloads"]
                if c["name"] == "mistral7b-train.pretrain-4k")
    seen = {}
    red = trace_reduce.reduce(synthetic_trace())

    def fake_run(config, traffic, **kw):
        seen.update(kw, config=config, traffic=traffic)
        return {"correct": True, "attempted": 3, "failed": 0, "setup_s": 1.5,
                "values": {"train_tokens_per_s": 20000.0, "mfu": 60.0},
                "series": {"step_ms": [200.0, 201.0, 199.0]},
                "work": {"flash_s": 0.004}, "compiles_in_window": 0,
                "trace": red if trace else None,
                "device": {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1, "memory_peak_bytes": 8 * 2 ** 30}}

    monkeypatch.setattr(common, "require_tpu", lambda chips: seen.update(need=chips))
    monkeypatch.setattr(common, "place_cache", lambda: "nowhere")
    monkeypatch.setattr(train, "run", fake_run)
    bench_run.main(["--workload", cell["name"], "--seed", "4000000007",
                    "--seconds", "2", "--trace", str(trace)])
    assert seen["need"] == seen["chips"] == cell["chips"]
    assert seen["seed"] == 4000000007 and seen["trace"] is bool(trace)
    assert seen["config"]["kind"] == "train" and seen["traffic"]["seq_len"] == 4096
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {
        m["name"] for m in bench_run.metrics_of(MANIFEST, group, cell["name"])}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["metrics"]["train.device.peak_hbm_share"]["value"] == 50.0
    else:
        assert line["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
        assert "breakdown" not in line and "busy_s" not in line["device"]


# --- rehearsal ------------------------------------------------------------------------

TINY_MANIFEST = {
    "end_to_end": [
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "workloads": ["closed"]},
        {"name": "train_tokens_per_s", "unit": "tokens/s", "workloads": ["packed"]},
        {"name": "setup_s", "unit": "s"}],
}


@pytest.mark.parametrize("kind,config,traffic,metric,trace", [
    ("serve", "tiny-serve.json", "tiny-closed.json", "serve_tokens_per_s", False),
    ("serve", "tiny-serve.json", "tiny-closed.json", "serve_tokens_per_s", True),
    ("train", "tiny-train.json", "tiny-packed.json", "train_tokens_per_s", False),
    ("train", "tiny-train.json", "tiny-packed.json", "train_tokens_per_s", True),
])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(
        kind, config, traffic, metric, trace, monkeypatch, tmp_path):
    """Both runners end to end at a tiny size, kernels in interpret mode,
    with the one argument ``run.py`` never passes (``require_chip=False``).
    What it can show: control flow, the correctness check against the
    reference, zero compiles in the window, the shape of the line."""
    import importlib
    import time

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    runner = importlib.import_module("benchmarks.runners." + kind)
    cell = tiny(traffic)["loop"]
    run = runner.run(tiny(config), tiny(traffic), seed=2 ** 31 + 3,
                     seconds=1.0, trace=trace, out_dir=str(tmp_path),
                     t_start=time.perf_counter(), require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    line = json.loads(json.dumps(
        bench_run.result_line(TINY_MANIFEST, cell, run, trace=False)))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        # the CPU has no device plane, so nothing is reduced; the step
        # spans in the file are the steps the runner counted as work
        import glob

        files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        host = trace_reduce.load_xplane(files[0])["host"]
        n = run["traced_steps"]
        assert sum(1 for name, _, _ in host if name == trace_reduce.STEP_SPAN) \
            == (n if isinstance(n, int) else len(n)) > 0
        assert run["trace"] is None
    if kind == "serve":
        assert run["check"]["rows"] == 9 and run["check"]["worst"] < 1e-4
        assert run["counters"]["evictions"] == 0
    else:
        assert abs(run["check"]["first_loss"]
                   - run["check"]["reference_loss"]) < 1e-4


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", MANIFEST["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert "needs 1 TPU chip" in r.stderr
