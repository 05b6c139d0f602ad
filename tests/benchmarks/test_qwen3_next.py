"""The benchmark's Qwen3-Next pieces (ISSUE 37), on the CPU: the configuration
file against the catalog's row, the published sizes the byte functions count,
the per-layer readers on a hand-made record, the warm-up's cover of the
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

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import peaks_qwen3_next as work  # noqa: E402
from benchmarks.harness import reference_qwen3_next as reference  # noqa: E402
from benchmarks.harness import schedule, trace_reduce  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
CELL = "qwen3-next-serve.long-doc-decode"
CONFIG = bench_run.load_json("benchmarks", "configs", "qwen3-next-serve.json")
TRAFFIC = bench_run.load_json("benchmarks", "traffic", "long-doc-decode.json")
NEW = ("qwen3next.kernels.delta_decode_roofline",
       "qwen3next.kernels.delta_decode_device_share",
       "qwen3next.kernels.gated_attn_decode_roofline",
       "qwen3next.kernels.expert_ffn_roofline",
       "qwen3next.xla.weight_stream_roofline",
       "qwen3next.prefill.device_share",
       "qwen3next.cache.state_bytes_share")
#: the lists ISSUE 37 leaves the cell off (PERF.md 7 (g), (u))
OFF = ("decode.engine.prefill_padded_share",
       "decode.engine.first_token_idle_share",
       "decode.device.prefill_ms_per_ktoken",
       "decode.device.prefill_attention_roofline",
       "decode.device.idle_unattributed_share")


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


# --- the configuration file against its source ------------------------------

def test_the_file_holds_the_sources_keys_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        entry = next(e for e in map(json.loads, open(catalog))
                     if e["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert CONFIG["source"] == entry["source_url"]
        differ = sorted(k for k, v in entry["config"].items()
                        if CONFIG.get(k, "absent") != v)
        assert differ == sorted(CONFIG["reduced"])
    red = CONFIG["reduced"]
    assert {k: (v["published"], v["here"]) for k, v in red.items()} == {
        "num_hidden_layers": (48, 12), "num_experts": (512, 32),
        "vocab_size": (151936, 18992)}
    assert all(v["why"] and "deviceless_compile_bytes" in v for v in red.values())
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "qwen3-next-serve")
    assert sorted(entry["reduced"]) == sorted(red)
    assert entry["file"] == "benchmarks/configs/qwen3-next-serve.json"
    widths = dict(hidden_size=2048, num_attention_heads=16,
                  num_key_value_heads=2, head_dim=256,
                  linear_num_key_heads=16, linear_num_value_heads=32,
                  linear_key_head_dim=128, linear_value_head_dim=128,
                  linear_conv_kernel_dim=4, moe_intermediate_size=512,
                  shared_expert_intermediate_size=512,
                  num_experts_per_tok=10, partial_rotary_factor=0.25,
                  full_attention_interval=4)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "sixteen chips share each layer" in CONFIG["deployment"].lower()
    # three whole periods: 9 delta layers and 3 attention layers
    assert (work.state_layers(CONFIG), work.attention_layers(CONFIG)) == (9, 3)
    totals = red["num_hidden_layers"]["deviceless_compile_bytes"]
    assert totals["decode_step"]["total"] < totals["prefill_2048"]["total"] \
        < 0.92 * 16 * 2 ** 30
    # over the driver's floor of a quarter of the chip by far
    assert totals["decode_step"]["total"] > 0.5 * 16 * 2 ** 30
    eng = CONFIG["engine"]
    # every request of the longest length fits together: nothing is preempted
    assert (eng["num_blocks"] - 1) * eng["block_size"] \
        == eng["max_batch_size"] * eng["max_model_len"]
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["output_tokens"]["max"] \
        == eng["max_model_len"]
    assert (TRAFFIC["clients"], TRAFFIC["requests"], TRAFFIC["schedule_seed"]) \
        == (eng["max_batch_size"], 1024, 37)
    assert TRAFFIC["prompt_tokens"] == {"median": 2048, "sigma": 0.9,
                                        "min": 256, "max": 8192}
    assert TRAFFIC["output_tokens"] == {"median": 768, "sigma": 0.6,
                                        "min": 128, "max": 2048}
    lens = CONFIG["check"]["prompt_lens"]
    assert lens == [300, 1500, 4500]
    # the longest crosses two chunk boundaries: a state carried twice
    assert max(lens) > 2 * eng["max_prefill_tokens_per_step"]
    for key in ("delta_state", "max_model_len", "router", "prediction_module",
                "column_order", "norms", "weights", "chunk_products"):
        assert key in CONFIG["assumed"]


def test_the_cell_sends_only_rungs_that_the_warm_up_compiles():
    from benchmarks.runners import serve_joyai_flash as joyai

    eng = CONFIG["engine"]
    rungs, most = set(), 0
    for it in schedule.build(TRAFFIC):
        _, chunks = joyai.chunk_plan(it.prompt_len, eng["prefill_buckets"],
                                     eng["max_prefill_tokens_per_step"])
        rungs |= {c for _, c in chunks}
        most = max(most, len(chunks))
    # every rung under the budget is met; a staging length never is a chunk
    assert rungs == {256, 512, 1024, 2048} == {
        b for b in eng["prefill_buckets"]
        if b <= eng["max_prefill_tokens_per_step"]}
    assert most == 4                # a state carried three times


def test_byte_functions_count_the_published_elements():
    m = CONFIG
    assert work.delta_state_bytes(m) == 32 * 128 * 128 * 4 == 2_097_152
    assert work.delta_decode_bytes(m, 96 * 9) == 2 * 96 * 9 * 2_097_152
    # 3.6 GB a step, 4.4 ms at the chip's peak (ISSUE 37's arithmetic)
    assert 4.4e-3 < work.delta_decode_bytes(m, 96 * 9) / 819e9 < 4.5e-3
    assert work.gated_attn_decode_bytes(m, 1000) == 1000 * 6144
    assert work.expert_bytes(m) == 3 * 2048 * 512 * 2 == 6_291_456
    assert work.delta_params(m) == 25_165_824 + 131_072 + 32_768 + 8_388_608
    assert work.attention_params(m) == 16_777_216 + 2 * 1_048_576 + 8_388_608
    fixed = work.fixed_stream_bytes(m, 512)
    assert fixed == 2 * (9 * work.delta_params(m) + 3 * work.attention_params(m)
                         + 12 * (3 * 2048 * 512 + 2048) + 2048 * 18992) \
        + 12 * 2048 * 512 * 4
    assert 0.9e9 < fixed < 1.0e9


# --- the readers on a hand-made record ---------------------------------------

def _record():
    """Two decode steps of 96 rows: the kernels' and the graphs' time on a
    made-up device line, a chunk beside them."""
    from benchmarks.runners import serve_qwen3_next as runner

    ev, t = [], 0.0

    def op(name, dur, module="jit_decode_pure"):
        nonlocal t
        ev.append((name, t, dur, module))
        t += dur

    for step in range(2):
        for layer in range(12):
            if work.is_full_attention(CONFIG, layer):
                op(f"paged_decode_attention_global.{layer} custom-call "
                   "bf16[96,16,256]", 0.0009)
            else:
                op(f"gated_delta_decode_update.{layer} custom-call "
                   "f32[96,32,128]", 0.0006)
            op(f"moe_grouped_swiglu.{layer} custom-call f32[960,16,128]",
               0.0003)
            op(f"fusion.{layer} fusion bf16[96,2048]", 0.0002)
        op("chunk_attention_global.3 custom-call bf16[2048,16,256]", 0.004,
           "jit_chunk_pure")
        op("fusion.77 fusion f32[32,16,2,64,64]", 0.016, "jit_chunk_pure")
    record = {
        "device_kind": "TPU v5 lite",
        "traced_steps": [(0, 1, 96, [], 96, 300_000)] * 2,
        "traced_counters": {"moe_experts_hit_decode": 2 * 12 * 30,
                            "delta_state_rows_updated_decode": 2 * 9 * 96},
        "trace": {"events": ev, "busy_s": t, "window_s": 1.25 * t},
        "counters": {"state_byte_steps": 10 ** 9,
                     "kv_live_byte_steps": 3 * 10 ** 9},
    }
    model = runner.model_sizes(CONFIG)
    record["work"] = runner._work(record, CONFIG, model)
    record["cache"] = runner._cache_shares(record["counters"])
    return record


WANT = {
    "qwen3next.kernels.delta_decode_roofline":
        lambda busy: 100 * (2 * 2 * 9 * 96 * 2_097_152 / 819e9) / (18 * 0.0006),
    "qwen3next.kernels.delta_decode_device_share":
        lambda busy: 100 * 18 * 0.0006 / busy,
    "qwen3next.kernels.gated_attn_decode_roofline":
        lambda busy: 100 * (2 * 300_000 * 6144 / 819e9) / (6 * 0.0009),
    "qwen3next.kernels.expert_ffn_roofline":
        lambda busy: 100 * (720 * 6_291_456 / 819e9) / (24 * 0.0003),
    "qwen3next.xla.weight_stream_roofline":
        lambda busy: 100 * (2 * work.fixed_stream_bytes(CONFIG, 512) / 819e9)
        / (24 * 0.0002),
    "qwen3next.prefill.device_share": lambda busy: 100 * 2 * 0.02 / busy,
    "qwen3next.cache.state_bytes_share": lambda busy: 25.0,
}
#: what a program without the kernels, the counters or a trace reads
ABSENT = {"qwen3next.kernels.delta_decode_device_share": 0.0,
          "qwen3next.prefill.device_share": 0.0}


def test_the_new_metrics_are_appended_in_the_issues_order():
    names = [m["name"] for m in MANIFEST["per_layer"]
             if m["name"].startswith("qwen3next.")]
    assert names == list(NEW) and set(WANT) == set(NEW)
    every = [m["name"] for m in MANIFEST["per_layer"]]
    assert every.index(names[0]) \
        > every.index("decode.device.prefill_attention_roofline")
    assert every[every.index(names[0]):][:len(names)] == names


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_reads_its_number_from_a_synthetic_record(name):
    run = _record()
    assert run["work"]["delta_rows_by_steps"] == 2 * 9 * 96
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


def test_the_new_cell_is_on_the_lists_the_issue_names():
    cell = bench_run.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("qwen3-next-serve", "long-doc-decode", 1)
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells.index(CELL) == 5 and len(cell["why"]) <= 200
    with_cell = {m["name"] for g in ("end_to_end", "per_layer")
                 for m in MANIFEST[g] if CELL in m.get("workloads", ())}
    assert {n for n in with_cell if not n.startswith("qwen3next.")} == {
        "serve_tokens_per_s", *(m["name"] for m in MANIFEST["per_layer"]
                                if m["name"].startswith(("decode.engine.",
                                                         "decode.device."))
                                and m["name"] not in OFF)}
    assert {n for n in with_cell if n.startswith("qwen3next.")} == set(NEW)
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["layer"] in layers and m["unit"] == "%"
    # every list the cell joined still opens with what it held
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW:
            assert m["workloads"][4:] == [CELL]
            assert m["workloads"][3] == "nemotron3-nano-serve.short-chat-decode"


# --- the reference's own properties -------------------------------------------

def _tiny_weights(seed=0):
    import jax

    from benchmarks.runners import common
    from benchmarks.runners import serve_qwen3_next as runner

    model = runner.model_sizes(tiny("tiny-qwen3-next.json"))
    with jax.default_matmul_precision("highest"):
        net = runner.build_model(model, seed, "float32")
    return net, common.named_weights(net), model


def test_reference_is_causal_blockwise_and_every_part_moves_it():
    _, w, model = _tiny_weights()
    held = model["experts_held"]
    ids = np.random.default_rng(1).integers(0, 160, size=(1, 30)).astype(np.int32)
    full = np.asarray(reference.logits(w, ids, model, held))
    cut = np.asarray(reference.logits(w, ids[:, :19], model, held))
    np.testing.assert_allclose(full[:, :19], cut, atol=1e-5)     # causal
    # the query blocks are an arrangement, not arithmetic
    block = reference.Q_BLOCK
    try:
        reference.Q_BLOCK = 7
        reference._attention.clear_cache()
        again = np.asarray(reference.logits(w, ids, model, held))
    finally:
        reference.Q_BLOCK = block
        reference._attention.clear_cache()
    np.testing.assert_allclose(again, full, atol=1e-5)
    for part in ("linear_attn.A_log", "linear_attn.conv_weight",
                 "linear_attn.dt_bias", "linear_attn.norm_weight",
                 "linear_attn.in_proj_ba.weight", "self_attn.q_proj.weight",
                 "mlp.shared_expert.down_proj.weight",
                 "mlp.shared_expert_gate.weight"):
        moved = {k: (v * 1.5 if k.endswith(part) else v) for k, v in w.items()}
        assert np.abs(np.asarray(reference.logits(moved, ids, model, held))
                      - full).max() > 1e-5, part
    # a zero-centred norm: w = 0 is the identity scale, so adding 1 doubles
    for part in ("input_layernorm.weight", "self_attn.k_norm.weight"):
        moved = {k: (v + 1.0 if k.endswith(part) else v) for k, v in w.items()}
        assert np.abs(np.asarray(reference.logits(moved, ids, model, held))
                      - full).max() > 1e-5, part
    lg, scores = reference.logits(w, ids, model, held, with_scores=True)
    assert sorted(scores) == list(range(4))
    sc = np.asarray(scores[1])
    assert sc.shape == (1, 30, 16)
    np.testing.assert_allclose(sc.sum(-1), 1.0, atol=1e-5)     # a softmax


def test_rows_are_compared_with_the_reference_routed_as_the_engine_routed():
    from benchmarks.runners import serve_qwen3_next as runner

    _, w, model = _tiny_weights()
    held = model["experts_held"]
    prompt = np.random.default_rng(5).integers(0, 160, size=9).astype(np.int32)
    toks = [3, 1, 4, 1]
    n = len(prompt) + len(toks) - 1      # the last token is computed by no step
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None, :n]
    own, scores = reference.logits(w, ids, model, held, with_scores=True)
    layers = sorted(scores)
    sc = [np.asarray(scores[b][0], np.float64) for b in layers]
    choice = np.stack([np.argsort(-m, -1, kind="stable")[:, :4] for m in sc])
    assert reference.choice_gaps(sc[0], choice[0]) == (0, 0.0)
    # an "engine" that took, at a token of the CONTEXT, the best held expert
    # its scores leave out for the worst they take
    pos, k = 7, 0
    out = [e for e in np.argsort(-sc[k][pos]) if e not in choice[k][pos]
           and e in held][0]
    edge = np.sort(sc[k][pos])[-4]
    gap = (edge - sc[k][pos][out]) / edge           # relative: a softmax
    turned = choice.copy()
    turned[k, pos, np.argmin(sc[k][pos][choice[k][pos]])] = out
    handed = {b: turned[i][None] for i, b in enumerate(layers)}
    engine = np.asarray(reference.logits(w, ids, model, held, choice=handed))[0]
    rows_at = [len(prompt) - 1 + j for j in range(len(toks))]
    taken = {"toks": [toks], "choice": [turned],
             "rows": {(0, j): engine[at].copy() for j, at in enumerate(rows_at)}}
    errors, routing = runner.compare_rows(w, model, [prompt], [taken])
    assert sorted(errors) == [(0, j) for j in range(4)]
    assert max(errors.values()) < 1e-5
    # the turn itself, and whatever it turns in the layers behind it (their
    # handed choices are those of the undisturbed context)
    assert routing["turned"] >= 2 and routing["gap"] >= gap - 1e-9
    assert routing["pairs"] == choice.size
    _, sc_turned = reference.logits(w, ids, model, held, with_scores=True,
                                    choice=handed)
    at_k = reference.choice_gaps(np.asarray(sc_turned[k][0]), turned[k])
    assert at_k[0] == 2 and abs(at_k[1] - gap) < 1e-6
    # left to its own scores the reference reads every row after that token
    # otherwise: the routing of the context is part of what a row is
    apart = [reference.row_error(engine[at], np.asarray(own)[0, at])
             for at in rows_at]
    assert min(apart) > 20 * max(errors.values())


def test_a_configuration_the_model_does_not_compute_is_refused():
    from benchmarks.runners import serve_qwen3_next as runner

    cfg = tiny("tiny-qwen3-next.json")
    for key, value in (("hidden_act", "gelu"), ("mlp_only_layers", [1]),
                       ("decoder_sparse_step", 2),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError, match="SwiGLU experts in every layer"):
            runner.model_sizes(dict(cfg, **{key: value}))
    assert runner.model_sizes(cfg)["num_experts"] == 16
    assert runner.model_sizes(CONFIG)["experts_held"] == list(range(32))
    assert runner.model_sizes(CONFIG)["num_experts"] == 512


# --- the runner, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(trace, monkeypatch,
                                                         tmp_path):
    import glob

    from benchmarks.runners import serve_qwen3_next as runner

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    run = runner.run(tiny("tiny-qwen3-next.json"), tiny("tiny-closed.json"),
                     seed=2 ** 31 + 3, seconds=1.0, trace=trace,
                     out_dir=str(tmp_path), t_start=time.perf_counter(),
                     require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["check"]["rows"] == 9 and run["check"]["worst"] < 1e-4
    c = run["counters"]
    assert c["evictions"] == 0
    # four expert layers a decode step and a chunk; a step may be in flight
    # (counted on the device, not yet fetched) at one edge and not the other
    assert abs(c["moe_layer_steps"]
               - 4 * (c["host_syncs"] + c["prefill_chunks"])) <= 4
    assert 0 < c["moe_experts_hit_decode"] <= 4 * c["moe_layer_steps_decode"]
    assert c["delta_state_rows_updated_decode"] > 0 \
        == c["delta_state_rows_updated_prefill"]
    assert c["delta_tokens_scanned_prefill"] > 0 == c["delta_tokens_scanned_decode"]
    # at most four live rows a step, each in three delta layers
    assert c["delta_state_rows_updated_decode"] <= 3 * 4 * (c["host_syncs"] + 1)
    assert 0 < run["cache"]["state_bytes_share"] < 100
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
        # a traced line on a program without a device line reports only the
        # metric that reads no trace, and does not raise
        full = json.loads(json.dumps(
            bench_run.result_line(MANIFEST, CELL, run, trace=True)))
        assert [n for n in full["metrics"] if n.startswith("qwen3next.")] \
            == ["qwen3next.cache.state_bytes_share"]
