"""What a prefill chunk says of itself in the profile, as the benchmark reads
it (``benchmarks/harness/prefill_spans.py``, PR 35): the four per-layer
metrics on a hand-made record against numbers worked by hand, the pairing of
spans and executions at the window's edges, the count of a chunk's attention
for each architecture, a record that has nothing to read, and the profile of
a real (CPU) session as ``load`` parses it."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import prefill_spans as ps  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
NEW = {"decode.engine.prefill_padded_share": ("%", "lower", "program_span",
                                              "serving engine"),
       "decode.engine.first_token_idle_share": ("%", "lower", "program_span",
                                                "serving engine"),
       "decode.device.prefill_ms_per_ktoken": ("ms/ktoken", "lower",
                                               "device_trace", "device"),
       "decode.device.prefill_attention_roofline": ("%", "higher",
                                                    "device_trace",
                                                    "attention kernels")}
SERVING = ["mistral7b-serve.decode-sat", "mimo-v2-flash-serve.mixed-len-decode",
           "joyai-llm-flash-serve.long-ctx-decode",
           "nemotron3-nano-serve.short-chat-decode"]
ATTN = "chunk_attention_global.3 custom-call bf16[1024,4,128]"
MS = 1e-3


def _chunk(at, **said):
    return (ps.CHUNK, at, 2 * MS, said)


def record():
    """A traced window [0, 1] s. Five executions of the chunk program:
    one dispatched before the profiler started (no span), A that the
    window's opening cuts, B and C whole inside it, D that its end cuts.
    Two first-token waits, the device idle for 3 ms inside the first and
    busy all through the second."""
    host_args = [
        _chunk(-0.010, rid=1, start=0, tokens=100, padded=128, last=1),    # A
        _chunk(0.100, rid=2, start=0, tokens=520, padded=1024, last=1),    # B
        (ps.FIRST_TOKEN, 0.150, 10 * MS, {"requests": 1, "behind": 1}),
        _chunk(0.300, rid=3, start=2048, tokens=904, padded=1024, last=0),  # C
        (ps.FIRST_TOKEN, 0.500, 10 * MS, {"requests": 2, "behind": 0}),
        _chunk(0.980, rid=4, start=0, tokens=60, padded=64, last=1),       # D
    ]
    runs = [(-0.100, 0.020), (-0.005, 0.009), (0.110, 0.040), (0.320, 0.050),
            (0.990, 0.030)]
    chunk, step = "jit_chunk_pure", "jit_decode_pure"
    events = [
        ("fusion.9 fusion bf16[128]", 0.0, 0.004, chunk),               # A, cut
        ("fusion.8 fusion bf16[8]", 0.004, 0.106, step),
        ("fusion.1 fusion bf16[1024]", 0.110, 0.010, chunk),            # B
        (ATTN, 0.120, 0.020, chunk),
        ("fusion.2 fusion bf16[1024]", 0.140, 0.010, chunk),
        # idle 0.150-0.152, the first 2 ms of the wait that opens at 0.150,
        # and a 5-us seam at 0.155 that is nobody's
        ("fusion.8 fusion bf16[8]", 0.152, 0.003, step),
        ("fusion.8 fusion bf16[8]", 0.155 + 5e-6, 0.004 - 5e-6, step),
        ("fusion.8 fusion bf16[8]", 0.160, 0.160, step),     # idle 0.159-0.160
        ("fusion.1 fusion bf16[1024]", 0.320, 0.015, chunk),            # C
        (ATTN, 0.335, 0.025, chunk),
        ("fusion.2 fusion bf16[1024]", 0.360, 0.010, chunk),
        ("fusion.8 fusion bf16[8]", 0.370, 0.620, step),
        ("fusion.1 fusion bf16[64]", 0.990, 0.010, chunk),              # D, cut
    ]
    busy = 0.004 + 0.106 + 0.040 + 0.003 + 0.004 - 5e-6 + 0.160 + 0.050 \
        + 0.620 + 0.010
    return {"kind": "serve", "device": {"kind": "TPU v5 lite"},
            "model": {"num_hidden_layers": 2, "num_attention_heads": 4,
                      "head_dim": 128},
            "trace": {"t0": 0.0, "t1": 1.0, "window_s": 1.0, "busy_s": busy,
                      "events": events, "host_args": host_args,
                      "chunk_runs": runs}}


def test_spans_and_executions_pair_in_order_and_the_window_cuts_both_sides():
    tr = record()["trace"]
    chunks = ps.spans(tr["host_args"], ps.CHUNK)
    assert [c[2]["rid"] for c in chunks] == [1, 2, 3, 4]
    paired = ps.pairs(chunks, tr["chunk_runs"], 0.0, 1.0)
    # the first execution ended before any span opened: none of theirs; A's
    # began before the window and D's ends after it: gone with their spans
    assert [(st["rid"], a, b) for st, a, b in paired] == [
        (2, 0.110, pytest.approx(0.150)), (3, 0.320, pytest.approx(0.370))]
    # without the window all four are paired, each with its own
    every = ps.pairs(chunks, tr["chunk_runs"], -1.0, 2.0)
    assert [(st["rid"], a) for st, a, _ in every] == [
        (1, -0.005), (2, 0.110), (3, 0.320), (4, 0.990)]
    # a span whose execution the profile does not hold stays unpaired
    assert len(ps.pairs(chunks, tr["chunk_runs"][:3], -1.0, 2.0)) == 2
    assert ps.pairs([], tr["chunk_runs"], 0.0, 1.0) == []
    # what the paired executions took: their own operations, and no other's
    assert ps.run_seconds(tr["events"], paired) == pytest.approx(0.090)
    assert ps.run_seconds(tr["events"], paired, ps.ATTENTION) \
        == pytest.approx(0.045)


WANT = {
    # the spans that opened inside the window: B, C, D
    "decode.engine.prefill_padded_share":
        100 * ((1024 + 1024 + 64) - (520 + 904 + 60)) / (1024 + 1024 + 64),
    # 2 ms at the head of the first wait and 1 ms at its end; the seam is
    # nobody's and the second wait saw a busy device
    "decode.engine.first_token_idle_share": 100 * 0.003 / 1.0,
    # B and C: 40 + 50 ms for 520 + 904 real tokens
    "decode.device.prefill_ms_per_ktoken": 90.0 / 1.424,
    # B: 520 queries from 0 see 520 x 521 / 2 keys; C: 904 from 2048 see
    # 904 x 2048 + 904 x 905 / 2; two layers of four heads 128 + 128 wide
    "decode.device.prefill_attention_roofline":
        100 * (2 * 2 * 4 * 256 * (135_460 + 2_260_452) / 197e12) / 0.045,
}


@pytest.mark.parametrize("name", list(NEW))
def test_each_metric_reads_the_hand_made_record(name):
    got = bench_run.read_layer_metric(name, record())
    assert got == pytest.approx(WANT[name], rel=1e-9)
    assert 0 < got < 100


@pytest.mark.parametrize("name", list(NEW))
def test_a_record_with_nothing_to_read_reads_as_nothing(name):
    """Without a trace, of a program without the spans (the parent's: the
    profile holds no such event), and (the roofline, whose work is counted
    from it) without the model: None, and nothing raises."""
    read = lambda run: bench_run.read_layer_metric(name, run)  # noqa: E731
    assert read({"trace": None}) is None
    assert read({}) is None
    parent = record()
    parent["trace"]["host_args"] = []
    assert read(parent) is None
    for key in ("model", "kind", "device"):
        run = record()
        del run[key]
        if name.endswith("_roofline"):
            assert read(run) is None
        else:
            assert read(run) == pytest.approx(WANT[name])
    run = record()
    run["device"]["kind"] = "cpu"            # a rehearsal: no peak, no share
    run["trace"]["chunk_runs"] = []          # and no device line
    if name.startswith("decode.device."):
        assert read(run) is None


def test_visible_pairs_against_a_count_by_hand():
    brute = lambda start, n, w=None: sum(  # noqa: E731
        min(p + 1, w) if w else p + 1 for p in range(start, start + n))
    assert ps.visible_pairs(2048, 904) == 904 * 2048 + 904 * 905 // 2 == 2_260_452
    assert ps.visible_pairs(2048, 904, 128) == 904 * 128 == 115_712
    for start, n, w in [(0, 1, None), (0, 520, None), (0, 520, 128),
                        (100, 64, 128), (64, 64, 128), (127, 3, 128),
                        (0, 128, 128), (0, 129, 128), (126, 1, 128)]:
        assert ps.visible_pairs(start, n, w) == brute(start, n, w), (start, n, w)


def _model_of(config_name, runner):
    import importlib

    from benchmarks.runners import common

    mod = importlib.import_module("benchmarks.runners." + runner)
    config = bench_run.load_json("benchmarks", "configs", config_name + ".json")
    sizes = getattr(mod, "model_sizes", common.model_sizes)
    # as the runners' records keep it: what is a list is left out
    return {k: v for k, v in sizes(config).items() if not isinstance(v, list)}


#: one chunk, ``start`` 2048, ``tokens`` 904: causal 2,260,452 pairs a layer,
#: 115,712 under a 128-token window; FLOPs = 2 x heads x (q/k + v) a pair
ONE_CHUNK = {
    # sixteen layers of 32 heads, 128 + 128
    "serve": ("mistral7b-serve", 2 * 16 * 32 * 256 * 2_260_452),
    # layers 0..6 by the pattern: two full, five with the window; 64 heads of
    # 192 + 128 in both
    "serve_mimo_v2": ("mimo-v2-flash-serve",
                      2 * 64 * 320 * (2 * 2_260_452 + 5 * 115_712)),
    # nine layers EXPANDED: 32 heads of (128 + 64) + 128
    "serve_joyai_flash": ("joyai-llm-flash-serve", 2 * 9 * 32 * 320 * 2_260_452),
    # MEMEM*EMEMEM*EMEMEM*: three attention blocks of 32 heads, 128 + 128
    "serve_nemotron_h": ("nemotron3-nano-serve", 2 * 3 * 32 * 256 * 2_260_452),
}


@pytest.mark.parametrize("kind", list(ONE_CHUNK))
def test_a_chunks_attention_is_counted_at_the_published_widths(kind):
    config, want = ONE_CHUNK[kind]
    run = {"kind": kind, "model": _model_of(config, kind)}
    assert "hybrid_layer_pattern" not in run["model"]
    layers = ps.ATTENDING[kind](run)
    assert ps.attention_flops(layers, [{"start": 2048, "tokens": 904}]) == want
    # padding rows count nothing: the rung does not enter
    assert ps.attention_flops(layers, [{"start": 2048, "tokens": 904,
                                        "padded": 1024}]) == want


def test_a_model_whose_pattern_no_configuration_holds_has_no_count():
    run = {"kind": "serve_mimo_v2",
           "model": {**_model_of("mimo-v2-flash-serve", "serve_mimo_v2"),
                     "num_hidden_layers": 3}}
    assert ps.ATTENDING["serve_mimo_v2"](run) is None
    got = record()
    got.update(run)
    assert ps.prefill_attention_roofline(got) is None
    assert "serve_nobody" not in ps.ATTENDING


def test_the_four_are_in_the_manifest_by_name_with_the_serving_cells():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert len(by_name) == len(MANIFEST["per_layer"])
    layers = {m["layer"] for m in MANIFEST["per_layer"] if m["name"] not in NEW}
    for name, (unit, better, source, layer) in NEW.items():
        m = by_name[name]
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "serve_tokens_per_s", "workloads": SERVING}
        assert layer in layers       # a layer the benchmark already names
        spec = bench_run.load_json("benchmarks", "layer_metrics", name + ".json")
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            layer, unit, "serve_tokens_per_s")
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    for cell in SERVING:
        assert set(NEW) <= {m["name"] for m in bench_run.metrics_of(
            MANIFEST, "per_layer", cell)}
    assert not set(NEW) & {m["name"] for m in bench_run.metrics_of(
        MANIFEST, "per_layer", "mistral7b-train.pretrain-4k")}


def test_the_names_the_readers_look_for_are_the_engines():
    import inspect

    from paddle_tpu.inference.serving import engine

    src = inspect.getsource(engine)
    for name in (ps.CHUNK, ps.FIRST_TOKEN):
        assert f'"{name}"' in src
    for arg in ("rid", "start", "tokens", "padded", "last", "requests",
                "behind"):
        assert f'"{arg}":' in src
    # the chunk program is named by its function, as the device line has it
    assert f"def {ps.MODULE}(" in src


def test_load_reads_a_real_profile_and_a_record_finds_it_by_its_window(
        tmp_path, monkeypatch):
    """A CPU session of the tiny engine under the benchmark's own step span:
    ``load`` returns the chunk and first-token spans of the line that holds
    ``bench.step`` with their statistics (no device line here: no
    executions), and ``from_record`` takes the profile only for the record
    whose window it is."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from benchmarks.harness import trace_reduce
    from benchmarks.runners import common
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(7)
    net = LlamaForCausalLM(llama_tiny())
    net.eval()
    out_dir = str(tmp_path / "benchmarks_out" / "cell" / "trace")
    rng = np.random.RandomState(0)
    with LLMEngine(net, num_blocks=64, block_size=8, max_batch_size=4,
                   max_prefill_tokens_per_step=32, ingest_async=False) as eng:
        submit = lambda n: eng.add_request(  # noqa: E731
            rng.randint(0, 100, n).astype(np.int32),
            SamplingParams(max_new_tokens=4))
        submit(5)
        eng.step()
        common.start_trace(out_dir)
        try:
            submit(40)
            while eng.has_work():
                with common.step_span():
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    got = ps.load(path)
    assert got["chunk_runs"] == []
    chunks = ps.spans(got["host_args"], ps.CHUNK)
    assert [(c[2]["start"], c[2]["tokens"], c[2]["padded"], c[2]["last"])
            for c in chunks] == [(0, 32, 32, 0), (32, 8, 8, 1)]
    wait, = ps.spans(got["host_args"], ps.FIRST_TOKEN)
    assert wait[2]["requests"] == 1 and chunks[-1][1] <= wait[0]
    assert got["window"] == trace_reduce.window_of(
        trace_reduce.load_xplane(path)["host"])
    t0, t1 = got["window"]
    assert t0 <= chunks[0][0] and wait[1] <= t1
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    monkeypatch.setattr(ps, "_PARSED", {})
    run = {"trace": {"t0": t0, "t1": t1, "events": []}}
    assert ps.from_record(run) == (got["host_args"], [])
    assert ps.prefill_padded_share(run) == 0.0            # 32 + 8 of 32 + 8
    assert ps.prefill_ms_per_ktoken(run) is None          # nothing timed
    other = {"trace": {"t0": t0 + 1.0, "t1": t1, "events": []}}
    assert ps.from_record(other) is None                  # another run's
    assert ps.prefill_padded_share(other) is None
