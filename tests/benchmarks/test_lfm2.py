"""The benchmark's LFM2-MoE pieces, on the CPU: the configuration
file against the catalog's row, the schedule's rehearsal against the pages
the engine holds, the published sizes the byte functions count, the
per-layer readers on a hand-made record, the warm-up's cover of the
schedule, the reference's own properties, rows compared with the reference
routed as the engine routed, and the runner rehearsed end to end at a toy
configuration."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import peaks_lfm2 as work  # noqa: E402
from benchmarks.harness import reference_lfm2 as reference  # noqa: E402
from benchmarks.harness import schedule, trace_reduce  # noqa: E402

import manifest_checks  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
CELL = "lfm2-8b-a1b-serve.doc-qa-decode"
CONFIG = bench_run.load_json("benchmarks", "configs", "lfm2-8b-a1b-serve.json")
TRAFFIC = bench_run.load_json("benchmarks", "traffic", "doc-qa-decode.json")
NEW = ("lfm2.kernels.h64_decode_roofline",
       "lfm2.kernels.attention_device_share",
       "lfm2.kernels.expert_ffn_roofline",
       "lfm2.xla.weight_stream_roofline",
       "lfm2.prefill.device_share")
#: the serving cells the shared lists held before this one came
BEFORE = ("mistral7b-serve.decode-sat", "mimo-v2-flash-serve.mixed-len-decode",
          "joyai-llm-flash-serve.long-ctx-decode",
          "nemotron3-nano-serve.short-chat-decode",
          "qwen3-next-serve.long-doc-decode")


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


# --- the configuration file against its source ------------------------------

def test_the_file_holds_the_sources_keys_and_states_its_cut():
    assert CONFIG["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    red = CONFIG["reduced"]
    published = red["layer_types"]["published"]
    # 24 layers as published, 18 conv and 6 attention; the cut keeps the first 10
    assert (len(published), published.count("conv")) == (24, 18)
    assert CONFIG["layer_types"] == published[:10]
    assert (red["num_hidden_layers"]["published"],
            red["num_hidden_layers"]["here"]) == (24, 10)
    assert all(v["why"] and "deviceless_compile_bytes" in v for v in red.values())
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "lfm2-8b-a1b-serve")
    assert sorted(entry["reduced"]) == sorted(red)
    assert entry["file"] == "benchmarks/configs/lfm2-8b-a1b-serve.json"
    widths = dict(hidden_size=2048, intermediate_size=7168,
                  moe_intermediate_size=1792, num_attention_heads=32,
                  num_key_value_heads=8, conv_L_cache=3, num_experts=32,
                  num_experts_per_tok=4, vocab_size=65536, num_dense_layers=2)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "first stage" in CONFIG["deployment"].lower()
    # two whole periods after the dense layers: 8 conv and 2 attention
    assert (work.conv_layers(CONFIG), work.attention_layers(CONFIG)) == (8, 2)
    totals = red["num_hidden_layers"]["deviceless_compile_bytes"]
    assert totals["decode_step"]["total"] < totals["prefill_2048"]["total"] \
        < 0.92 * 16 * 2 ** 30
    # over a quarter of the chip by far
    assert totals["decode_step"]["total"] > 0.5 * 16 * 2 ** 30
    # the pools hold 2,048 B a token a layer: no lane padding
    eng = CONFIG["engine"]
    assert totals["of_which_kv_pools"] == 2 * 2 * eng["num_blocks"] \
        * eng["block_size"] * 1024
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["output_tokens"]["max"] \
        == eng["max_model_len"]
    assert (TRAFFIC["clients"], TRAFFIC["requests"]) \
        == (eng["max_batch_size"], 1024)
    assert TRAFFIC["prompt_tokens"] == {"median": 6144, "sigma": 0.7,
                                        "min": 1024, "max": 16384}
    assert TRAFFIC["output_tokens"] == {"median": 1536, "sigma": 0.6,
                                        "min": 384, "max": 4096}
    lens = CONFIG["check"]["prompt_lens"]
    assert lens == [300, 2500, 6000]
    # the longest crosses two chunk boundaries: a tail carried twice
    assert max(lens) > 2 * eng["max_prefill_tokens_per_step"]
    for key in ("tied_head", "expert_bias", "router", "norms", "head_dim",
                "short_convolution", "max_model_len", "weights"):
        assert key in CONFIG["assumed"]


def test_the_pages_hold_the_schedule_and_the_window_opens_after_the_first_wave():
    """The rehearsal of the cell's schedule (``lfm2_deviceless.rehearse``):
    the most pages its requests hold stays under the pool with room, so
    nothing is preempted, and the warm-up outlasts the first prompts and the
    retirement of the shortest third of the first answers."""
    from benchmarks.tools.lfm2_deviceless import rehearse

    eng = CONFIG["engine"]
    got = rehearse(TRAFFIC, eng, steps=3000)
    assert got["peak_pages"] < 0.9 * (eng["num_blocks"] - 1)
    assert got["first_prompts_through"] < got["window_may_open"] \
        <= TRAFFIC["warmup_steps"]
    # ~1.1 M live tokens: ~4.5 GB of pages read a decode step
    assert 0.9e6 < got["mean_live_tokens"] < 1.3e6


def test_the_cell_sends_only_rungs_that_the_warm_up_compiles():
    from benchmarks.runners import serve_joyai_flash as joyai

    eng = CONFIG["engine"]
    rungs, most = set(), 0
    for it in schedule.build(TRAFFIC):
        _, chunks = joyai.chunk_plan(it.prompt_len, eng["prefill_buckets"],
                                     eng["max_prefill_tokens_per_step"])
        rungs |= {c for _, c in chunks}
        most = max(most, len(chunks))
    assert rungs == {256, 512, 1024, 2048} == {
        b for b in eng["prefill_buckets"]
        if b <= eng["max_prefill_tokens_per_step"]}
    assert most == 8                # a tail carried seven times


def test_byte_functions_count_the_published_elements():
    m = CONFIG
    assert work.head_dim(m) == 64
    assert work.kv_bytes_per_token(m) == 2048
    assert work.attention_decode_bytes(m, 1000) == 1000 * 2048
    assert work.expert_bytes(m) == 3 * 2048 * 1792 * 2 == 22_020_096
    assert work.conv_params(m) == 3 * 2048 * 2048 + 3 * 2048 + 2048 * 2048
    assert work.attention_params(m) == 2 * 2048 * 2048 + 2 * 2048 * 512
    fixed = work.fixed_stream_bytes(m)
    assert fixed == 2 * (8 * work.conv_params(m) + 2 * work.attention_params(m)
                         + 2 * 3 * 2048 * 7168 + 2048 * 65536) \
        + 8 * 2048 * 32 * 4
    assert 0.75e9 < fixed < 0.77e9
    # all 32 experts of 8 layers a decode step: 5.64 GB
    assert 5.6e9 < 8 * 32 * work.expert_bytes(m) < 5.7e9


# --- the readers on a hand-made record ---------------------------------------

def _record():
    """Two decode steps of 128 rows: the kernels' and the graphs' time on a
    made-up device line, a chunk beside them."""
    from benchmarks.runners import serve_lfm2 as runner

    ev, t = [], 0.0

    def op(name, dur, module="jit_decode_pure"):
        nonlocal t
        ev.append((name, t, dur, module))
        t += dur

    for step in range(2):
        for layer in range(10):
            if CONFIG["layer_types"][layer] == "full_attention":
                op(f"paged_decode_attention_h64.{layer} custom-call "
                   "bf16[128,32,128]", 0.003)
            if layer >= 2:
                op(f"moe_grouped_swiglu.{layer} custom-call f32[512,16,128]",
                   0.001)
            op(f"fusion.{layer} fusion bf16[128,2048]", 0.0002)
        op("chunk_attention_h64.3 custom-call bf16[2048,32,128]", 0.004,
           "jit_chunk_pure")
        op("fusion.77 fusion bf16[2048,7168]", 0.006, "jit_chunk_pure")
    record = {
        "device_kind": "TPU v5 lite",
        "traced_steps": [(0, 1, 128, [], 128, 1_000_000)] * 2,
        "traced_counters": {"moe_experts_hit_decode": 2 * 8 * 32,
                            "attn_tokens_read_decode": 2 * 2 * 1_000_000},
        "trace": {"events": ev, "busy_s": t, "window_s": 1.25 * t},
        "counters": {"state_byte_steps": 10 ** 9,
                     "kv_live_byte_steps": 3 * 10 ** 9},
    }
    record["work"] = runner._work(record, CONFIG)
    return record


WANT = {
    "lfm2.kernels.h64_decode_roofline":
        lambda busy: 100 * (4_000_000 * 2048 / 819e9) / (4 * 0.003),
    "lfm2.kernels.attention_device_share":
        lambda busy: 100 * 4 * 0.003 / busy,
    "lfm2.kernels.expert_ffn_roofline":
        lambda busy: 100 * (512 * 22_020_096 / 819e9) / (16 * 0.001),
    "lfm2.xla.weight_stream_roofline":
        lambda busy: 100 * (2 * work.fixed_stream_bytes(CONFIG) / 819e9)
        / (20 * 0.0002),
    "lfm2.prefill.device_share": lambda busy: 100 * 2 * 0.01 / busy,
}
#: what a program without the kernels, the counters or a trace reads
ABSENT = {"lfm2.kernels.attention_device_share": 0.0,
          "lfm2.prefill.device_share": 0.0}


def test_the_new_metrics_are_appended_in_the_issues_order():
    names = [m["name"] for m in MANIFEST["per_layer"] if m["name"] in NEW]
    assert names == list(NEW) and set(WANT) == set(NEW)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_reads_its_number_from_a_synthetic_record(name):
    run = _record()
    assert run["work"]["attn_tokens_by_steps"] == 2 * 2 * 1_000_000
    got = bench_run.read_layer_metric(name, run)
    assert got == pytest.approx(WANT[name](run["trace"]["busy_s"]), rel=1e-9)
    assert 0 < got < 100
    # a program that lacks the kernels and the counters (the parent's) reads
    # as nothing, and does not raise
    empty = {"trace": {"events": [("fusion.1 fusion f32[8]", 0.0, 1.0,
                                   "jit_decode_pure")],
                       "busy_s": 1.0, "window_s": 2.0},
             "counters": {}, "work": {}}
    assert bench_run.read_layer_metric(name, empty) == ABSENT.get(name)
    assert bench_run.read_layer_metric(name, {"trace": None}) is None


def check_manifest_lfm2(manifest):
    """The cell with its configuration, traffic and one chip; on every
    shared list (the serve rate, ``decode.engine.*``, ``decode.device.*``,
    the four prefill metrics among them) and on its five own metrics, and on
    none that moves another rate; those five as a cell's own metrics are;
    every shared list still holds the five serving cells it held before."""
    manifest_checks.serving_cell(manifest, CELL, "lfm2-8b-a1b-serve",
                                 "doc-qa-decode", NEW, BEFORE)


def test_the_new_cell_is_on_the_lists_the_issue_names():
    check_manifest_lfm2(MANIFEST)


# --- the reference's own properties -------------------------------------------

def _tiny_weights(seed=0):
    import jax

    from benchmarks.runners import common
    from benchmarks.runners import serve_lfm2 as runner

    model = runner.model_sizes(tiny("tiny-lfm2.json"))
    with jax.default_matmul_precision("highest"):
        net = runner.build_model(model, seed, "float32")
    return net, common.named_weights(net), model


def test_reference_is_causal_blockwise_and_every_layer_kind_moves_it():
    _, w, model = _tiny_weights()
    ids = np.random.default_rng(1).integers(0, 160, size=(1, 30)).astype(np.int32)
    full = np.asarray(reference.logits(w, ids, model))
    cut = np.asarray(reference.logits(w, ids[:, :19], model))
    np.testing.assert_allclose(full[:, :19], cut, atol=1e-5)     # causal
    block = reference.Q_BLOCK
    try:
        reference.Q_BLOCK = 7
        reference._attention.clear_cache()
        again = np.asarray(reference.logits(w, ids, model))
    finally:
        reference.Q_BLOCK = block
        reference._attention.clear_cache()
    np.testing.assert_allclose(again, full, atol=1e-5)
    for part in ("conv.conv_weight", "conv.out_proj.weight",
                 "self_attn.k_proj.weight", "self_attn.k_layernorm.weight",
                 "feed_forward.down_proj.weight", "router.weight",
                 "experts.1.up_proj.weight"):
        moved = {k: (v * 1.5 if k.endswith(part) else v) for k, v in w.items()}
        assert np.abs(np.asarray(reference.logits(moved, ids, model))
                      - full).max() > 1e-5, part
    lg, scores = reference.logits(w, ids, model, with_scores=True)
    assert sorted(scores) == reference.expert_layers(model) == [1, 2, 3, 4]
    sc = np.asarray(scores[1])
    assert sc.shape == (1, 30, 4)
    bias = np.asarray(w["model.layers.1.feed_forward.router.expert_bias"])
    # a sigmoid beside a bias: each score minus the bias lies in (0, 1)
    assert ((sc - bias > 0) & (sc - bias < 1)).all()


def test_rows_are_compared_with_the_reference_routed_as_the_engine_routed():
    from benchmarks.runners import serve_lfm2 as runner

    _, w, model = _tiny_weights()
    prompt = np.random.default_rng(5).integers(0, 160, size=9).astype(np.int32)
    toks = [3, 1, 4, 1]
    n = len(prompt) + len(toks) - 1      # the last token is computed by no step
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None, :n]
    own, scores = reference.logits(w, ids, model, with_scores=True)
    layers = sorted(scores)
    sc = [np.asarray(scores[b][0], np.float64) for b in layers]
    k_top = model["num_experts_per_tok"]
    choice = np.stack([np.argsort(-m, -1, kind="stable")[:, :k_top]
                       for m in sc])
    assert reference.choice_gaps(sc[0], choice[0]) == (0, 0.0)
    # an "engine" that took, at a token of the CONTEXT, the best expert its
    # scores leave out for the worst they take
    pos, k = 5, 0
    out = [e for e in np.argsort(-sc[k][pos]) if e not in choice[k][pos]][0]
    edge = np.sort(sc[k][pos])[-k_top]
    gap = edge - sc[k][pos][out]
    turned = choice.copy()
    turned[k, pos, np.argmin(sc[k][pos][choice[k][pos]])] = out
    handed = {b: turned[i][None] for i, b in enumerate(layers)}
    engine = np.asarray(reference.logits(w, ids, model, choice=handed))[0]
    rows_at = [len(prompt) - 1 + j for j in range(len(toks))]
    taken = {"toks": [toks], "choice": [turned],
             "rows": {(0, j): engine[at].copy() for j, at in enumerate(rows_at)}}
    errors, routing = runner.compare_rows(w, model, [prompt], [taken])
    assert sorted(errors) == [(0, j) for j in range(4)]
    assert max(errors.values()) < 1e-5
    assert routing["turned"] >= 2 and routing["gap"] >= gap - 1e-9
    assert routing["pairs"] == choice.size
    apart = [reference.row_error(engine[at], np.asarray(own)[0, at])
             for at in rows_at]
    assert min(apart) > 20 * max(errors.values())


def test_a_configuration_the_model_does_not_compute_is_refused():
    from benchmarks.runners import serve_lfm2 as runner

    cfg = tiny("tiny-lfm2.json")
    for key, value in (("model_type", "lfm2"), ("conv_bias", True),
                       ("use_expert_bias", False)):
        with pytest.raises(ValueError, match="short convolutions with no bias"):
            runner.model_sizes(dict(cfg, **{key: value}))
    assert runner.model_sizes(CONFIG)["max_position_embeddings"] == 20480
    assert runner.prefill_attending(runner.model_sizes(CONFIG)) \
        == [(2, 32, 64, 64, None)]


# --- the runner, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(trace, monkeypatch,
                                                         tmp_path):
    import glob

    from benchmarks.runners import serve_lfm2 as runner

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    run = runner.run(tiny("tiny-lfm2.json"), tiny("tiny-closed.json"),
                     seed=2 ** 31 + 3, seconds=1.0, trace=trace,
                     out_dir=str(tmp_path), t_start=time.perf_counter(),
                     require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["check"]["rows"] == 9 and run["check"]["worst"] < 1e-4
    c = run["counters"]
    assert c["evictions"] == 0
    # four expert layers a decode step and a chunk; a step may be in flight
    # at one edge of the window and not the other
    assert abs(c["moe_layer_steps"]
               - 4 * (c["host_syncs"] + c["prefill_chunks"])) <= 4
    # four conv layers: a tail a live row a step, a chunk's real tokens
    assert c["conv_tail_rows_updated_decode"] == c["conv_tokens_convolved_decode"] > 0
    assert c["conv_tail_rows_updated_prefill"] == 4 * c["prefill_chunks"]
    assert c["conv_tokens_convolved_prefill"] > 0 == c["attn_tokens_read_prefill"]
    assert c["attn_tokens_read_decode"] > 0
    assert 0 < run["cache"]["state_bytes_share"] < 100
    assert run["attending"] == [(1, 4, 64, 64, None)]
    manifest = {"end_to_end": [
        {"name": "serve_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}]}
    line = json.loads(json.dumps(
        bench_run.result_line(manifest, CELL, run, trace=False)))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    if trace:
        files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        host = trace_reduce.load_xplane(files[0])["host"]
        assert sum(1 for name, _, _ in host if name == trace_reduce.STEP_SPAN) \
            == len(run["traced_steps"]) > 0
        assert run["trace"] is None and run["work"] == {}
        t = run["traced_counters"]
        assert 0 < t["moe_layer_steps_decode"] <= 4 * (len(run["traced_steps"]) + 1)
        # a traced line on a program without a device line reports no metric
        # of this model's, and does not raise
        full = json.loads(json.dumps(
            bench_run.result_line(MANIFEST, CELL, run, trace=True)))
        assert not [n for n in full["metrics"] if n.startswith("lfm2.")]
