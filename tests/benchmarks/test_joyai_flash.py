"""The benchmark's JoyAI-LLM-Flash pieces (ISSUE 31), on the CPU: the
configuration file against the catalog's row, the published sizes the byte
and FLOP functions count, the per-layer readers on a hand-made record, the
warm-up's cover of the schedule, the reference's own properties, a row routed
otherwise, and the runner rehearsed end to end at a toy configuration."""

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
from benchmarks.harness import peaks_joyai_flash as work  # noqa: E402
from benchmarks.harness import reference_joyai_flash as reference  # noqa: E402
from benchmarks.harness import schedule, trace_reduce  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
CELL = "joyai-llm-flash-serve.long-ctx-decode"
CONFIG = bench_run.load_json("benchmarks", "configs", "joyai-llm-flash-serve.json")
TRAFFIC = bench_run.load_json("benchmarks", "traffic", "long-ctx-decode.json")
NEW = ("joyai.kernels.latent_decode_roofline",
       "joyai.kernels.attention_device_share",
       "joyai.xla.weight_stream_roofline", "joyai.prefill.device_share")


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


# --- the configuration file against its source ------------------------------

def test_the_file_holds_the_sources_keys_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        entry = next(e for e in map(json.loads, open(catalog))
                     if e["name"] == "JoyAI-LLM-Flash")
        assert CONFIG["source"] == entry["source_url"]
        differ = sorted(k for k, v in entry["config"].items()
                        if CONFIG.get(k, "absent") != v)
        assert differ == sorted(CONFIG["reduced"])
    red = CONFIG["reduced"]
    assert {k: (v["published"], v["here"]) for k, v in red.items()} == {
        "num_hidden_layers": (40, 9), "n_routed_experts": (256, 16)}
    widths = dict(hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, moe_intermediate_size=768,
                  intermediate_size=7168, num_experts_per_tok=8,
                  routed_scaling_factor=2.5, vocab_size=129280)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "sixteen chips share each layer" in CONFIG["deployment"].lower()
    totals = red["num_hidden_layers"]["deviceless_compile_bytes"]
    assert totals["decode_step"]["total"] < totals["prefill_2048"]["total"] \
        < 0.92 * 16 * 2 ** 30
    eng = CONFIG["engine"]
    # 64 requests of the longest length fit together: nothing is preempted
    assert (eng["num_blocks"] - 1) * eng["block_size"] \
        == eng["max_batch_size"] * eng["max_model_len"]
    assert TRAFFIC["prompt_tokens"]["max"] + TRAFFIC["output_tokens"]["max"] \
        == eng["max_model_len"]
    assert (TRAFFIC["clients"], TRAFFIC["requests"], TRAFFIC["schedule_seed"]) \
        == (64, 512, 31)
    lens = CONFIG["check"]["prompt_lens"]
    assert lens == [300, 1500, 4500]
    assert max(lens) > 2 * eng["max_prefill_tokens_per_step"]
    for key in ("norm", "rotary", "router", "mtp", "max_model_len",
                "latent_rows"):
        assert key in CONFIG["assumed"]


def test_byte_and_flop_functions_count_the_published_elements():
    m = CONFIG
    assert work.latent_row_bytes(m) == 1152
    assert work.latent_row_flops(m) == 69632
    pk = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    least, bound = work.latent_decode_seconds(m, 9 * 64 * 5000, pk)
    assert bound == "bytes" and least == 9 * 64 * 5000 * 1152 / 819e9
    # the bytes' side is the larger by a factor four
    assert 3.9 < (1152 / 819e9) / (69632 / 197e12) < 4.1
    assert work.attention_params(m) == (2048 * 1536 + 1536 * 6144 + 2048 * 576
                                        + 512 * 8192 + 4096 * 2048)
    assert work.expert_params(m) == 4_718_592
    fixed = work.fixed_stream_bytes(m, 256)
    assert fixed == 2 * (9 * work.attention_params(m) + 3 * 2048 * 7168
                         + 8 * 4_718_592 + 2048 * 129280) + 8 * 2048 * 256 * 4
    assert work.weight_stream_bytes(m, 10, 1100, 256) \
        == 10 * fixed + 1100 * 2 * 4_718_592


# --- the readers on a hand-made record ---------------------------------------

def _record():
    """Two decode steps of 64 rows over 320,000 cached rows each (a layer),
    the kernels' and the graphs' time on a made-up device line."""
    from benchmarks.runners import serve_joyai_flash as runner

    ev, t = [], 0.0
    for step in range(2):
        for layer in range(9):
            ev.append((f"paged_decode_attention_latent.{layer} custom-call "
                       "bf16[64,32,512]", t, 0.0008, "jit_decode_pure"))
            t += 0.0008
            ev.append((f"fusion.{layer} fusion bf16[64,2048]", t, 0.0004,
                       "jit_decode_pure"))
            t += 0.0004
        ev.append(("chunk_attention_global.3 custom-call bf16[32,1,2048,128]",
                   t, 0.003, "jit_chunk_pure"))
        t += 0.003
        ev.append(("fusion.77 fusion bf16[2048,2048]", t, 0.005,
                   "jit_chunk_pure"))
        t += 0.005
    record = {
        "device_kind": "TPU v5 lite",
        "traced_steps": [(0, 1, 64, [], 64, 320_000)] * 2,
        "traced_counters": {"moe_experts_hit_decode": 2 * 8 * 14,
                            "mla_latent_tokens_read_decode": 2 * 9 * 320_000},
        "trace": {"events": ev, "busy_s": t, "window_s": 1.25 * t},
    }
    model = runner.model_sizes(CONFIG)
    record["work"] = runner._work(record, CONFIG, model)
    return record


def test_every_new_metric_reads_the_record_and_stays_under_100():
    run = _record()
    bw, busy = 819e9, run["trace"]["busy_s"]
    assert run["work"]["latent_decode_bound"] == "bytes"
    assert run["work"]["latent_rows_by_steps"] == 2 * 9 * 320_000
    want = {
        "joyai.kernels.latent_decode_roofline":
            100 * (2 * 9 * 320_000 * 1152 / bw) / (18 * 0.0008),
        "joyai.kernels.attention_device_share":
            100 * (18 * 0.0008 + 2 * 0.003) / busy,
        "joyai.xla.weight_stream_roofline":
            100 * (work.weight_stream_bytes(CONFIG, 2, 224, 256) / bw)
            / (18 * 0.0004),
        "joyai.prefill.device_share": 100 * 2 * 0.008 / busy,
    }
    names = [m["name"] for m in MANIFEST["per_layer"]
             if m["name"].startswith("joyai.")]
    assert names == list(NEW)
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == names   # appended
    for name in names:
        got = bench_run.read_layer_metric(name, run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert 0 < got < 100, name
    # a program that lacks the kernel and the counters reads as nothing
    empty = {"trace": {"events": [("fusion.1 fusion f32[8]", 0.0, 1.0,
                                   "jit_decode_pure")],
                       "busy_s": 1.0, "window_s": 2.0},
             "counters": {}, "work": {}}
    for name in ("joyai.kernels.latent_decode_roofline",
                 "joyai.xla.weight_stream_roofline"):
        assert bench_run.read_layer_metric(name, empty) is None, name
    for name in ("joyai.kernels.attention_device_share",
                 "joyai.prefill.device_share"):
        assert bench_run.read_layer_metric(name, empty) == 0.0
        assert bench_run.read_layer_metric(name, {"trace": None}) is None


def test_the_new_cell_is_on_the_lists_the_issue_names():
    cell = bench_run.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("joyai-llm-flash-serve", "long-ctx-decode", 1)
    with_cell = {m["name"] for g in ("end_to_end", "per_layer")
                 for m in MANIFEST[g] if CELL in m.get("workloads", ())}
    assert {n for n in with_cell if not n.startswith("joyai.")} == {
        "serve_tokens_per_s", *(m["name"] for m in MANIFEST["per_layer"]
                                if m["name"].startswith(("decode.engine.",
                                                         "decode.device.")))}
    assert {n for n in with_cell if n.startswith("joyai.")} == set(NEW)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    # every list the cell joined still opens with what it held
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW:
            assert m["workloads"][-1] == CELL
            assert m["workloads"][0] == "mistral7b-serve.decode-sat"


# --- the warm-up --------------------------------------------------------------

def test_chunk_plan_cuts_a_prompt_as_the_engine_does():
    from benchmarks.runners import serve_joyai_flash as runner

    buckets, budget = CONFIG["engine"]["prefill_buckets"], 2048
    assert runner.chunk_plan(1024, buckets, budget) == (1024, ((0, 1024),))
    assert runner.chunk_plan(4500, buckets, budget) \
        == (8192, ((0, 2048), (2048, 2048), (4096, 512)))
    assert runner.chunk_plan(10240, buckets, budget)[1][-1] == (8192, 2048)
    # every (staging length, offset, rung) of the cell's schedule is met by
    # one of the prompts the warm-up sends, and they are few
    items = schedule.build(TRAFFIC)

    class Loop:
        live, done, steps = {}, [], []
        sent = []

        def submit(self, item):
            self.sent.append(item.prompt_len)

    lengths = runner.warm_shapes(Loop(), items, CONFIG["engine"])
    assert lengths == Loop.sent and len(lengths) < 40

    def shapes(n):
        bucket, chunks = runner.chunk_plan(n, buckets, budget)
        return {(bucket, s, c) for s, c in chunks}

    warmed = set().union(*(shapes(n) for n in lengths))
    assert all(shapes(it.prompt_len) <= warmed for it in items)


def test_the_warm_up_leaves_nothing_to_compile_for_any_prompt_of_the_schedule():
    """The engine cuts a chunk out of the staged prompt by a slice that is
    an executable of its own for every (staging length, offset, rung)."""
    from benchmarks.runners import common
    from benchmarks.runners import serve, serve_joyai_flash as runner
    from paddle_tpu.inference.serving import LLMEngine

    cfg, traffic = tiny("tiny-joyai-flash.json"), tiny("tiny-closed.json")
    model = runner.model_sizes(cfg)
    net = runner.build_model(model, 5, "float32")
    net.eval()
    counter = common.CompileCounter()
    with LLMEngine(net, **cfg["engine"]) as eng:
        items = schedule.build(traffic)
        loop = serve.Loop(eng, 5, model["vocab_size"])
        runner.warm_shapes(loop, items, cfg["engine"])
        before = counter.compiles
        for it in items:
            loop.submit(it)
        while loop.live:
            loop.step()
        assert counter.compiles == before


# --- the reference's own properties -------------------------------------------

def _tiny_weights(seed=0):
    import jax

    from benchmarks.runners import common
    from benchmarks.runners import serve_joyai_flash as runner

    model = runner.model_sizes(tiny("tiny-joyai-flash.json"))
    with jax.default_matmul_precision("highest"):
        net = runner.build_model(model, seed, "float32")
    return net, common.named_weights(net), model


def test_reference_is_causal_blockwise_and_counts_the_shared_expert_once():
    import jax.numpy as jnp

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
    # the shared expert is in every expert layer, with weight 1
    none = np.asarray(reference.logits(w, ids, dict(model, n_shared_experts=0), held))
    assert np.abs(none - full).max() > 1e-4
    doubled = {k: (v * 2 if "shared_experts.down_proj" in k else v)
               for k, v in w.items()}
    assert np.abs(np.asarray(reference.logits(doubled, ids, model, held))
                  - full).max() > 1e-4
    # the scaling factor multiplies the routed sum
    assert np.abs(np.asarray(reference.logits(
        w, ids, dict(model, routed_scaling_factor=1.0), held)) - full).max() > 1e-4
    lg, scores, hidden = reference.logits(w, ids, model, held, with_scores=True,
                                          with_hidden=True)
    assert sorted(scores) == [1, 2] and hidden.shape == (1, 30, 64)
    assert jnp.asarray(scores[1]).shape == (1, 30, 32)


def test_a_row_routed_otherwise_is_compared_with_the_references_other_routing():
    """An "engine" that turned the held expert nearest the edge at one
    (layer, position), in the trunk and in the prediction module: each row
    reads far over the tolerance against the reference as it routes by
    itself and inside it against the other routing, if that expert is within
    the limit; with no limit the check fails. No row is left out."""
    from benchmarks.runners import serve_joyai_flash as runner

    net, w, model = _tiny_weights()
    held, top_k = model["experts_held"], model["num_experts_per_tok"]
    prompt = np.random.default_rng(5).integers(0, 160, size=9).astype(np.int32)
    toks = [3, 1, 4]
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None]
    lg, scores = reference.logits(w, ids, model, held, with_scores=True)
    lg = np.asarray(lg)[0]
    rows_at = [len(prompt) - 1 + j for j in range(3)]
    gap, layer, pos, e, was_in = min(
        (g, la, p, e, c) for la, sc in scores.items() for p in rows_at
        for g, e, c in runner.uncertain(np.asarray(sc[0, p]), held, top_k, np.inf))
    j = rows_at.index(pos)
    other = np.asarray(reference.logits(
        w, ids, model, held,
        nudge=runner._nudges([(layer, e, was_in)], pos, ids.shape + (32,))))[0]
    rows = {(0, k): (other if k == j else lg)[at].copy()
            for k, at in enumerate(rows_at)}
    spec = {"tolerance": 1e-4, "margin_limit": gap * 1.5 + 1e-9,
            "new_tokens": 3, "mtp_prompt": 0}
    got = runner.compare_rows(w, model, [prompt], [toks], rows, spec)
    assert got[(0, j)]["routed_otherwise"] == [[layer, e]]
    assert abs(got[(0, j)]["margin"] - gap) < 1e-6
    assert max(v["error"] for v in got.values()) < 1e-5
    strict = runner.compare_rows(w, model, [prompt], [toks], rows,
                                 dict(spec, margin_limit=0.0))
    assert strict[(0, j)]["error"] > 1e-3
    # the prediction module's rows go the same way, under ("mtp", position)
    ids_m, hidden = runner.mtp_inputs(w, model, [prompt], [toks], spec)
    mlg, msc = reference.mtp_logits(w, hidden, ids_m[:, 1:], model, held,
                                    with_scores=True)
    at = ids_m.shape[1] - 2
    mgap, me, min_ = min(runner.uncertain(np.asarray(msc[0, at]), held, top_k,
                                          np.inf))
    turned = np.asarray(reference.mtp_logits(
        w, hidden, ids_m[:, 1:], model, held,
        nudge=runner._nudges([(3, me, min_)], at, (1, 11, 32))[3]))[0]
    assert reference.row_error(turned[at], np.asarray(mlg)[0, at]) > 1e-3
    got = runner.compare_rows(
        w, model, [prompt], [toks], {}, dict(spec, margin_limit=mgap * 1.5 + 1e-9),
        mtp_rows={at: turned[at], at - 1: np.asarray(mlg)[0, at - 1]})
    assert got[("mtp", at)]["routed_otherwise"] == [[3, me]]
    assert max(v["error"] for v in got.values()) < 1e-5
    # the program's own module, from the reference's hidden state
    mine = runner.mtp_rows(net, w, model, [prompt], [toks], spec)
    assert sorted(mine) == [at - 2, at - 1, at]
    assert reference.row_error(mine[at], np.asarray(mlg)[0, at]) < 2e-5


def test_the_checks_rows_are_taken_beside_a_full_batch_and_leave_the_engine_empty():
    from benchmarks.runners import serve_joyai_flash as runner
    from paddle_tpu.inference.serving import LLMEngine

    cfg = tiny("tiny-joyai-flash.json")
    model = runner.model_sizes(cfg)
    net = runner.build_model(model, 7, "float32")
    net.eval()
    eng = LLMEngine(net, capture_logits=True, **cfg["engine"])
    try:
        free = eng.cache.allocator.num_free
        beside, step = [], eng.step

        def counting_step():
            outs = step()
            beside.append(sum(1 for r in eng.scheduler.slots
                              if r is not None and not r.prefilling))
            return outs

        eng.step = counting_step
        prompts, toks, rows, agree = runner.engine_rows(eng, model, 7, cfg["check"])
        del eng.step
        assert agree and sorted(rows) == [(i, j) for i in range(3) for j in range(3)]
        assert [len(p) for p in prompts] == cfg["check"]["prompt_lens"]
        assert max(beside) > cfg["engine"]["max_batch_size"] - 3
        assert min(beside) >= cfg["engine"]["max_batch_size"] - 3
        assert not eng.has_work() and not eng._requests
        assert eng.cache.allocator.num_free == free
    finally:
        eng.close()


# --- the runner, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(trace, monkeypatch,
                                                         tmp_path):
    import glob

    from benchmarks.runners import serve_joyai_flash as runner

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    run = runner.run(tiny("tiny-joyai-flash.json"), tiny("tiny-closed.json"),
                     seed=2 ** 31 + 3, seconds=1.0, trace=trace,
                     out_dir=str(tmp_path), t_start=time.perf_counter(),
                     require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    # nine rows of the engine's and three of the prediction module's
    assert run["check"]["rows"] == 12 and run["check"]["worst"] < 1e-4
    assert run["check"]["worst_mtp"] < 1e-4
    c = run["counters"]
    assert c["evictions"] == 0
    # two expert layers a decode step and a chunk; a step may be in flight
    # (counted on the device, not yet fetched) at one edge and not the other
    assert abs(c["moe_layer_steps"]
               - 2 * (c["host_syncs"] + c["prefill_chunks"])) <= 2
    assert 0 < c["moe_experts_hit_decode"] <= 8 * c["moe_layer_steps_decode"]
    assert c["mla_latent_tokens_read_decode"] > 0 == c["mla_latent_tokens_read_prefill"]
    assert c["mla_context_tokens_expanded_prefill"] > 0
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
        # the counters are of the traced steps alone: no more decode steps
        # than step spans (one may be in flight at either edge)
        t = run["traced_counters"]
        assert 0 < t["moe_layer_steps_decode"] <= 2 * (len(run["traced_steps"]) + 1)
        assert {"engine.step", "engine.prefill", "engine.decode.prepare",
                "engine.decode.fetch"} <= {name for name, _, _ in host}
        # a traced line on a program without a device line reports none of
        # the new metrics and does not raise
        full = json.loads(json.dumps(
            bench_run.result_line(MANIFEST, CELL, run, trace=True)))
        assert not [n for n in full["metrics"] if n.startswith("joyai.")]
