"""The benchmark's Nemotron-H pieces (ISSUE 33), on the CPU: the configuration
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
from benchmarks.harness import peaks_nemotron_h as work  # noqa: E402
from benchmarks.harness import reference_nemotron_h as reference  # noqa: E402
from benchmarks.harness import schedule, trace_reduce  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
CELL = "nemotron3-nano-serve.short-chat-decode"
CONFIG = bench_run.load_json("benchmarks", "configs", "nemotron3-nano-serve.json")
TRAFFIC = bench_run.load_json("benchmarks", "traffic", "short-chat-decode.json")
NEW = ("nemotron.kernels.ssm_decode_roofline",
       "nemotron.kernels.ssm_decode_device_share",
       "nemotron.kernels.expert_ffn_roofline",
       "nemotron.xla.weight_stream_roofline", "nemotron.prefill.device_share",
       "nemotron.cache.state_bytes_share",
       "nemotron.kernels.global_decode_roofline")


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


# --- the configuration file against its source ------------------------------

def test_the_file_holds_the_sources_keys_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        entry = next(e for e in map(json.loads, open(catalog))
                     if e["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert CONFIG["source"] == entry["source_url"]
        differ = sorted(k for k, v in entry["config"].items()
                        if CONFIG.get(k, "absent") != v)
        assert differ == sorted(CONFIG["reduced"])
        assert CONFIG["hybrid_override_pattern"] \
            == entry["config"]["hybrid_override_pattern"][:20]
    red = CONFIG["reduced"]
    assert {k: (v["published"], v["here"]) for k, v in red.items()
            if k != "hybrid_override_pattern"} == {
        "num_hidden_layers": (52, 20), "n_routed_experts": (128, 16),
        "vocab_size": (131072, 16384)}
    assert all(v["why"] and "deviceless_compile_bytes" in v for v in red.values())
    pattern = CONFIG["hybrid_override_pattern"]
    assert pattern == "MEMEM*EMEMEM*EMEMEM*" == red["hybrid_override_pattern"]["here"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (9, 8, 3)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "nemotron3-nano-serve")
    assert sorted(entry["reduced"]) == sorted(red)
    widths = dict(hidden_size=2688, num_attention_heads=32,
                  num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
                  mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                  conv_kernel=4, chunk_size=128, moe_intermediate_size=1856,
                  moe_shared_expert_intermediate_size=3712,
                  num_experts_per_tok=6, routed_scaling_factor=2.5)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "eight chips share each layer" in CONFIG["deployment"].lower()
    totals = red["num_hidden_layers"]["deviceless_compile_bytes"]
    assert totals["decode_step"]["total"] < totals["prefill_2048"]["total"] \
        < 0.92 * 16 * 2 ** 30
    # over the driver's floor of a quarter of the chip by far
    assert totals["decode_step"]["total"] > 0.5 * 16 * 2 ** 30
    eng = CONFIG["engine"]
    # 192 requests of the longest length fit together: nothing is preempted
    assert (eng["num_blocks"] - 1) * eng["block_size"] \
        == eng["max_batch_size"] * eng["max_model_len"]
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["output_tokens"]["max"] \
        == eng["max_model_len"]
    assert (TRAFFIC["clients"], TRAFFIC["requests"], TRAFFIC["schedule_seed"],
            TRAFFIC["warmup_steps"]) == (192, 1024, 33, 800)
    assert TRAFFIC["prompt_tokens"] == {"median": 512, "sigma": 1.0, "min": 64,
                                        "max": 3072}
    assert TRAFFIC["output_tokens"] == {"median": 256, "sigma": 0.8, "min": 32,
                                        "max": 1024}
    lens = CONFIG["check"]["prompt_lens"]
    assert lens == [150, 700, 2200]
    # the longest crosses a chunk boundary: a carried state is compared
    assert max(lens) > eng["max_prefill_tokens_per_step"]
    for key in ("rotary", "ssm_state", "max_model_len", "time_step_limit",
                "experts", "router", "weights"):
        assert key in CONFIG["assumed"]


def test_the_cell_sends_only_rungs_that_the_warm_up_compiles():
    from benchmarks.runners import serve_joyai_flash as joyai

    eng = CONFIG["engine"]
    rungs = set()
    for it in schedule.build(TRAFFIC):
        _, chunks = joyai.chunk_plan(it.prompt_len, eng["prefill_buckets"],
                                     eng["max_prefill_tokens_per_step"])
        rungs |= {c for _, c in chunks}
    # every rung under the budget is met; the staging length never is a chunk
    assert rungs == {256, 512, 1024, 2048} == {
        b for b in eng["prefill_buckets"]
        if b <= eng["max_prefill_tokens_per_step"]}


def test_byte_functions_count_the_published_elements():
    m = CONFIG
    assert work.state_layers(m) == 9
    assert work.ssm_state_bytes(m) == 64 * 64 * 128 * 4 == 2_097_152
    assert work.ssm_decode_bytes(m, 192 * 9) == 2 * 192 * 9 * 2_097_152
    # 7.2 GB a step, 8.9 ms at the chip's peak (ISSUE 33's arithmetic)
    assert 8.8e-3 < work.ssm_decode_bytes(m, 192 * 9) / 819e9 < 8.9e-3
    assert work.expert_bytes(m) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert work.mamba_params(m) == (2688 * (4096 + 6144 + 64) + 6144 * 5
                                    + 4096 * 2688)
    assert 38.7e6 < work.mamba_params(m) < 38.8e6
    assert work.attention_params(m) == 2 * 2688 * 4096 + 2 * 2688 * 256
    fixed = work.fixed_stream_bytes(m, 128)
    assert fixed == 2 * (9 * work.mamba_params(m) + 3 * work.attention_params(m)
                         + 8 * 2 * 2688 * 3712 + 2688 * 16384) \
        + 8 * 2688 * 128 * 4
    assert 1.2e9 < fixed < 1.3e9


# --- the readers on a hand-made record ---------------------------------------

def _record():
    """Two decode steps of 192 rows: the kernels' and the graphs' time on a
    made-up device line, a chunk beside them."""
    from benchmarks.runners import serve_nemotron_h as runner

    ev, t = [], 0.0
    for step in range(2):
        for layer, letter in enumerate(CONFIG["hybrid_override_pattern"]):
            name, dur = {
                "M": ("mamba2_decode_update.%d custom-call f32[192,32,128]", 0.0012),
                "E": ("moe_grouped_relu2.%d custom-call f32[1152,21,128]", 0.00045),
                "*": ("paged_decode_attention_global.%d custom-call "
                      "bf16[192,32,128]", 0.0006)}[letter]
            ev.append((name % layer, t, dur, "jit_decode_pure"))
            t += dur
            ev.append((f"fusion.{layer} fusion bf16[192,2688]", t, 0.0001,
                       "jit_decode_pure"))
            t += 0.0001
        ev.append(("chunk_attention_global.3 custom-call bf16[1024,32,128]",
                   t, 0.002, "jit_chunk_pure"))
        t += 0.002
        ev.append(("fusion.77 fusion bf16[1024,2688]", t, 0.006,
                   "jit_chunk_pure"))
        t += 0.006
    record = {
        "device_kind": "TPU v5 lite",
        "traced_steps": [(0, 1, 192, [], 192, 192_000)] * 2,
        "traced_counters": {"moe_experts_hit_decode": 2 * 8 * 16,
                            "ssm_state_rows_updated_decode": 2 * 9 * 192},
        "trace": {"events": ev, "busy_s": t, "window_s": 1.25 * t},
        "counters": {"state_byte_steps": 3 * 10 ** 9,
                     "kv_live_byte_steps": 10 ** 9},
    }
    model = runner.model_sizes(CONFIG)
    record["work"] = runner._work(record, CONFIG, model)
    record["cache"] = runner._cache_shares(record["counters"])
    return record


def test_every_new_metric_reads_the_record_and_stays_under_100():
    run = _record()
    bw, busy = 819e9, run["trace"]["busy_s"]
    assert run["work"]["ssm_rows_by_steps"] == 2 * 9 * 192
    want = {
        "nemotron.kernels.ssm_decode_roofline":
            100 * (2 * 2 * 9 * 192 * 2_097_152 / bw) / (18 * 0.0012),
        "nemotron.kernels.ssm_decode_device_share": 100 * 18 * 0.0012 / busy,
        "nemotron.kernels.expert_ffn_roofline":
            100 * (256 * 19_955_712 / bw) / (16 * 0.00045),
        "nemotron.xla.weight_stream_roofline":
            100 * (2 * work.fixed_stream_bytes(CONFIG, 128) / bw)
            / (40 * 0.0001),
        "nemotron.prefill.device_share": 100 * 2 * 0.008 / busy,
        "nemotron.cache.state_bytes_share": 75.0,
        "nemotron.kernels.global_decode_roofline":
            100 * (2 * 192_000 * 3 * 1024 / bw) / (6 * 0.0006),
    }
    names = [m["name"] for m in MANIFEST["per_layer"]
             if m["name"].startswith("nemotron.")]
    assert names == list(NEW)
    # appended behind what the benchmark had (a later PR appends behind these:
    # nothing here pins them as the LAST entries)
    every = [m["name"] for m in MANIFEST["per_layer"]]
    assert every.index(names[0]) > every.index("joyai.prefill.device_share")
    assert every[every.index(names[0]):][:len(names)] == names
    for name in names:
        got = bench_run.read_layer_metric(name, run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert 0 < got < 100, name
    # a program that lacks the kernels and the counters (the parent's) reads
    # as nothing, and does not raise
    empty = {"trace": {"events": [("fusion.1 fusion f32[8]", 0.0, 1.0,
                                   "jit_decode_pure")],
                       "busy_s": 1.0, "window_s": 2.0},
             "counters": {}, "work": {}}
    for name in ("nemotron.kernels.ssm_decode_roofline",
                 "nemotron.kernels.expert_ffn_roofline",
                 "nemotron.kernels.global_decode_roofline",
                 "nemotron.xla.weight_stream_roofline",
                 "nemotron.cache.state_bytes_share"):
        assert bench_run.read_layer_metric(name, empty) is None, name
    for name in ("nemotron.kernels.ssm_decode_device_share",
                 "nemotron.prefill.device_share"):
        assert bench_run.read_layer_metric(name, empty) == 0.0
        assert bench_run.read_layer_metric(name, {"trace": None}) is None


def test_the_new_cell_is_on_the_lists_the_issue_names():
    cell = bench_run.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nemotron3-nano-serve", "short-chat-decode", 1)
    cells = [c["name"] for c in MANIFEST["workloads"]]
    assert cells.index(CELL) == 4 and len(cell["why"]) <= 200
    with_cell = {m["name"] for g in ("end_to_end", "per_layer")
                 for m in MANIFEST[g] if CELL in m.get("workloads", ())}
    assert {n for n in with_cell if not n.startswith("nemotron.")} == {
        "serve_tokens_per_s", *(m["name"] for m in MANIFEST["per_layer"]
                                if m["name"].startswith(("decode.engine.",
                                                         "decode.device.")))}
    assert {n for n in with_cell if n.startswith("nemotron.")} == set(NEW)
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["layer"] in layers and m["unit"] == "%"
    # every list the cell joined still opens with what it held
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW:
            assert m["workloads"][3] == CELL
            assert m["workloads"][:3] == [
                "mistral7b-serve.decode-sat",
                "mimo-v2-flash-serve.mixed-len-decode",
                "joyai-llm-flash-serve.long-ctx-decode"]


# --- the reference's own properties -------------------------------------------

def _tiny_weights(seed=0):
    import jax

    from benchmarks.runners import common
    from benchmarks.runners import serve_nemotron_h as runner

    model = runner.model_sizes(tiny("tiny-nemotron-h.json"))
    with jax.default_matmul_precision("highest"):
        net = runner.build_model(model, seed, "float32")
    return net, common.named_weights(net), model


def test_reference_is_causal_blockwise_and_reads_the_published_width():
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
    # an expert's matrices are stored 32 wide for a published 24: what lies
    # past the published width is not read (the program keeps zeros there)
    name = "model.layers.1.mixer.experts.0.up_proj.weight"
    assert w[name].shape == (64, 32) and not np.asarray(w[name])[:, 24:].any()
    junk = dict(w)
    junk[name] = w[name].at[:, 24:].set(7.0)
    np.testing.assert_array_equal(
        np.asarray(reference.logits(junk, ids, model, held)), full)
    # the shared expert is in every expert block with weight 1, the scaling
    # factor multiplies the routed sum, every kind of block moves the result
    doubled = {k: (v * 2 if "shared_experts.down_proj" in k else v)
               for k, v in w.items()}
    assert np.abs(np.asarray(reference.logits(doubled, ids, model, held))
                  - full).max() > 1e-4
    assert np.abs(np.asarray(reference.logits(
        w, ids, dict(model, routed_scaling_factor=1.0), held)) - full).max() > 1e-4
    for part in ("mixer.A_log", "mixer.conv_weight", "mixer.q_proj.weight",
                 "mixer.D", "mixer.dt_bias", "mixer.norm_weight"):
        moved = {k: (v * 1.5 if k.endswith(part) else v) for k, v in w.items()}
        assert np.abs(np.asarray(reference.logits(moved, ids, model, held))
                      - full).max() > 1e-5, part
    lg, scores = reference.logits(w, ids, model, held, with_scores=True)
    assert sorted(scores) == [1, 4] and np.asarray(scores[1]).shape == (1, 30, 32)


def test_rows_are_compared_with_the_reference_routed_as_the_engine_routed():
    from benchmarks.runners import serve_nemotron_h as runner

    _, w, model = _tiny_weights()
    held = model["experts_held"]
    prompt = np.random.default_rng(5).integers(0, 160, size=9).astype(np.int32)
    toks = [3, 1, 4, 1]
    n = len(prompt) + len(toks) - 1      # the last token is computed by no step
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None, :n]
    own, scores = reference.logits(w, ids, model, held, with_scores=True)
    blocks = sorted(scores)
    sc = [np.asarray(scores[b][0], np.float64) for b in blocks]
    choice = np.stack([np.argsort(-m, -1, kind="stable")[:, :4] for m in sc])
    assert reference.choice_gaps(sc[0], choice[0]) == (0, 0.0)
    # an "engine" that took, at a token of the CONTEXT, the best held expert
    # its scores leave out for the worst they take
    pos, k = 7, 0
    out = [e for e in np.argsort(-sc[k][pos]) if e not in choice[k][pos]
           and e in held][0]
    gap = np.sort(sc[k][pos])[-4] - sc[k][pos][out]
    turned = choice.copy()
    turned[k, pos, np.argmin(sc[k][pos][choice[k][pos]])] = out
    handed = {b: turned[i][None] for i, b in enumerate(blocks)}
    engine = np.asarray(reference.logits(w, ids, model, held, choice=handed))[0]
    rows_at = [len(prompt) - 1 + j for j in range(len(toks))]
    taken = {"toks": [toks], "choice": [turned],
             "rows": {(0, j): engine[at].copy() for j, at in enumerate(rows_at)}}
    errors, routing = runner.compare_rows(w, model, [prompt], [taken])
    assert sorted(errors) == [(0, j) for j in range(4)]
    assert max(errors.values()) < 1e-5
    assert routing["turned"] == 2 and abs(routing["gap"] - gap) < 1e-6
    assert routing["pairs"] == choice.size
    # left to its own scores the reference reads every row after that token
    # otherwise: the routing of the context is part of what a row is
    apart = [reference.row_error(engine[at], np.asarray(own)[0, at])
             for at in rows_at]
    assert min(apart) > 20 * max(errors.values())
    # and a choice far from the scores' own reads as far
    far = choice.copy()
    far[k, pos, 0] = np.argsort(sc[k][pos])[0]
    assert reference.choice_gaps(sc[k], far[k])[1] > 0.1


def test_the_verdict_holds_the_worst_row_and_the_widest_turn():
    from benchmarks.runners import serve_nemotron_h as runner

    spec = {"tolerance": 0.02, "margin_limit": 0.01}
    routing = {"pairs": 1000, "turned": 30, "gap": 0.004}
    clean = {(r, j): e for r, errs in enumerate(
        ([0.014, 0.015, 0.016], [0.014, 0.013, 0.015]))
        for j, e in enumerate(errs)}
    v = runner.verdict(clean, routing, True, 6, spec)
    assert v["ok"] and v["worst"] == 0.016 and v["rows"] == 6
    assert v["largest_gap"] == 0.004 and v["pairs_turned"] == 30
    # ONE row over the limit is not correct, whichever request and step made it
    for key in clean:
        assert not runner.verdict({**clean, key: 0.021}, routing, True,
                                  6, spec)["ok"], key
    # a choice the reference's scores do not allow is not correct either
    assert not runner.verdict(clean, dict(routing, gap=0.011), True, 6,
                              spec)["ok"]
    assert not runner.verdict(clean, routing, False, 6, spec)["ok"]
    assert not runner.verdict(clean, routing, True, 7, spec)["ok"]  # a row missing


def test_a_configuration_the_model_does_not_compute_is_refused():
    from benchmarks.runners import serve_nemotron_h as runner

    cfg = tiny("tiny-nemotron-h.json")
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 2),
                       ("use_bias", True), ("use_conv_bias", False)):
        with pytest.raises(ValueError, match="relu\\^2 experts"):
            runner.model_sizes(dict(cfg, **{key: value}))
    assert runner.model_sizes(cfg)["n_routed_experts"] == 32


# --- the runner, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(trace, monkeypatch,
                                                         tmp_path):
    import glob

    from benchmarks.runners import serve_nemotron_h as runner

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    run = runner.run(tiny("tiny-nemotron-h.json"), tiny("tiny-closed.json"),
                     seed=2 ** 31 + 3, seconds=1.0, trace=trace,
                     out_dir=str(tmp_path), t_start=time.perf_counter(),
                     require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["check"]["rows"] == 9 and run["check"]["worst"] < 1e-4
    c = run["counters"]
    assert c["evictions"] == 0
    # two expert blocks and three state blocks a decode step and a chunk; a
    # step may be in flight (counted on the device, not yet fetched) at one
    # edge and not the other
    assert abs(c["moe_layer_steps"]
               - 2 * (c["host_syncs"] + c["prefill_chunks"])) <= 2
    assert 0 < c["moe_experts_hit_decode"] <= 8 * c["moe_layer_steps_decode"]
    assert c["ssm_state_rows_updated_decode"] > 0 == c["ssm_state_rows_updated_prefill"]
    assert c["ssm_tokens_scanned_prefill"] > 0 == c["ssm_tokens_scanned_decode"]
    # at most four live rows a step, each in three state blocks
    assert c["ssm_state_rows_updated_decode"] <= 3 * 4 * (c["host_syncs"] + 1)
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
        assert 0 < t["moe_layer_steps_decode"] <= 2 * (len(run["traced_steps"]) + 1)
        assert {"engine.step", "engine.prefill", "engine.decode.prepare",
                "engine.decode.fetch"} <= {name for name, _, _ in host}
        # a traced line on a program without a device line reports only the
        # metric that reads no trace, and does not raise
        full = json.loads(json.dumps(
            bench_run.result_line(MANIFEST, CELL, run, trace=True)))
        assert [n for n in full["metrics"] if n.startswith("nemotron.")] \
            == ["nemotron.cache.state_bytes_share"]
