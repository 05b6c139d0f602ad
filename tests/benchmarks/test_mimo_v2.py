"""The benchmark's MiMo-V2-Flash pieces (ISSUE 27), on the CPU: the runner
rehearsed end to end at a toy configuration, the published sizes the byte
functions count, the per-layer readers on hand-made records, the reference's
own properties, and how a row that the engine routed otherwise is compared."""

import json
import math
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
from benchmarks.harness import peaks_mimo_v2 as work  # noqa: E402
from benchmarks.harness import reference_mimo_v2 as reference  # noqa: E402
from benchmarks.harness import trace_reduce  # noqa: E402

MANIFEST = bench_run.load_json("BENCHMARK.json")
CELL = "mimo-v2-flash-serve.mixed-len-decode"
CONFIG = bench_run.load_json("benchmarks", "configs", "mimo-v2-flash-serve.json")


def tiny(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


# --- the configuration file against its source ------------------------------

def test_the_file_holds_the_sources_keys_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        entry = next(e for e in map(json.loads, open(catalog))
                     if e["name"] == "MiMo-V2-Flash")
        assert CONFIG["source"] == entry["source_url"]
        differ = sorted(k for k, v in entry["config"].items()
                        if CONFIG.get(k, "absent") != v)
        assert differ == sorted(CONFIG["reduced"])
    red = CONFIG["reduced"]
    assert {k: (v["published"], v["here"]) for k, v in red.items()} == {
        "num_hidden_layers": (48, 7), "n_routed_experts": (256, 16),
        "vocab_size": (152576, 19072)}
    assert CONFIG["vocab_size"] * 8 == 152576     # the guide's floor
    assert CONFIG["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert CONFIG["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    eng = CONFIG["engine"]
    # 64 requests of the longest length fit together; 64 rings and slack
    assert (eng["num_blocks"] - 1) * eng["block_size"] \
        == eng["max_batch_size"] * eng["max_model_len"]
    assert "window_num_blocks" not in eng     # the cache derives it
    traffic = bench_run.load_json("benchmarks", "traffic", "mixed-len-decode.json")
    assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] \
        == eng["max_model_len"]
    assert traffic["prompt_tokens"]["min"] >= CONFIG["sliding_window"]
    lens = CONFIG["check"]["prompt_lens"]
    assert sum(n > 128 + 2 * 16 for n in lens) >= 2
    assert max(lens) > eng["max_prefill_tokens_per_step"]


def test_byte_functions_count_the_published_elements():
    m = CONFIG
    assert work.layers_of(m, False) == [0, 5] and len(work.layers_of(m, True)) == 5
    assert work.kv_bytes_per_token(m, False) == 2560
    assert work.kv_bytes_per_token(m, True) == 5120
    assert work.global_decode_bytes(m, 1000) == 1000 * 5120
    # every context at least a window: 128 tokens a row a layer
    assert work.window_decode_bytes(m, 64, 64 * 3000) == 64 * 128 * 25600
    assert work.window_decode_bytes(m, 2, 100) == 100 * 25600
    assert work.attention_params(m, False) == 89_128_960
    assert work.attention_params(m, True) == 94_371_840
    assert work.expert_params(m) == 25_165_824
    fixed = work.fixed_stream_bytes(m, 256)
    assert fixed == 2 * (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
                         + 4096 * 19072) + 6 * 4096 * 256 * 4
    assert work.weight_stream_bytes(m, 10, 800, 256) \
        == 10 * fixed + 800 * 2 * 25_165_824


# --- the readers on a hand-made record ---------------------------------------

def _record():
    """Two decode steps of 64 rows over 200,000 context tokens each, the
    kernels' and the graph's time on a made-up device line."""
    from benchmarks.runners import serve_mimo_v2 as runner

    ev = []
    t = 0.0
    for step in range(2):
        for layer in range(7):
            kind = "global" if layer in (0, 5) else "window"
            ev.append((f"paged_decode_attention_{kind}.{layer} custom-call "
                       "bf16[64,64,128]", t, 0.0005, "jit_decode_pure"))
            t += 0.0005
            ev.append((f"fusion.{layer} fusion bf16[64,4096]", t, 0.002,
                       "jit_decode_pure"))
            t += 0.002
        ev.append(("chunk_attention_global.3 custom-call bf16[4,16,2048,128]",
                   t, 0.003, "jit_chunk_pure"))
        t += 0.003
    record = {
        "device_kind": "TPU v5 lite",
        "traced_steps": [(0, 1, 64, [], 64, 200_000)] * 2,
        "traced_counters": {"moe_experts_hit_decode": 2 * 6 * 14},
        "counters": {"kv_live_byte_steps": 20, "kv_one_table_byte_steps": 100},
        "trace": {"events": ev, "busy_s": t, "window_s": 2 * t},
    }
    model = runner.model_sizes(CONFIG)
    record["work"] = runner._work(record, CONFIG, model)
    return record


def test_every_new_metric_reads_the_record_and_stays_under_100():
    run = _record()
    bw = 819e9
    want = {
        "mimo.kernels.global_decode_roofline":
            100 * (400_000 * 5120 / bw) / (4 * 0.0005),
        "mimo.kernels.window_decode_roofline":
            100 * (128 * 128 * 25600 / bw) / (10 * 0.0005),
        "mimo.kernels.attention_device_share":
            100 * (14 * 0.0005 + 2 * 0.003) / run["trace"]["busy_s"],
        "mimo.xla.weight_stream_roofline":
            100 * (work.weight_stream_bytes(CONFIG, 2, 168, 256) / bw)
            / (14 * 0.002),
        "mimo.cache.kv_bytes_vs_uniform": 20.0,
    }
    names = [m["name"] for m in MANIFEST["per_layer"]
             if m["name"].startswith("mimo.")]
    assert sorted(names) == sorted(want)
    for name in names:
        got = bench_run.read_layer_metric(name, run)
        assert got == pytest.approx(want[name], rel=1e-9), name
    # a parent that lacks the kernels and the counters reads as nothing
    empty = {"trace": {"events": [("fusion.1 fusion f32[8]", 0.0, 1.0,
                                   "jit_decode_pure")],
                       "busy_s": 1.0, "window_s": 2.0},
             "counters": {}, "work": {}}
    for name in names:
        if name != "mimo.kernels.attention_device_share":
            assert bench_run.read_layer_metric(name, empty) is None, name
    assert bench_run.read_layer_metric(
        "mimo.kernels.attention_device_share", empty) == 0.0


def test_the_new_cell_is_on_the_lists_the_issue_names():
    with_cell = {m["name"] for g in ("end_to_end", "per_layer")
                 for m in MANIFEST[g] if CELL in m.get("workloads", ())}
    assert {n for n in with_cell if not n.startswith("mimo.")} == {
        "serve_tokens_per_s", *(m["name"] for m in MANIFEST["per_layer"]
                                if m["name"].startswith(("decode.engine.",
                                                         "decode.device.")))}
    assert not {n for n in with_cell if n.startswith(("decode.kernels.",
                                                      "decode.xla."))}


# --- the reference's own properties -------------------------------------------

def _tiny_weights(seed=0):
    import jax

    from benchmarks.runners import serve_mimo_v2 as runner
    from benchmarks.runners import common

    cfg = tiny("tiny-mimo-v2.json")
    model = runner.model_sizes(cfg)
    with jax.default_matmul_precision("highest"):
        net = runner.build_model(model, seed, "float32")
    return common.named_weights(net), model


def test_reference_is_causal_windowed_and_uses_its_sinks():
    import jax.numpy as jnp

    w, model = _tiny_weights()
    ids = np.random.default_rng(1).integers(0, 160, size=(1, 30)).astype(np.int32)
    full = np.asarray(reference.logits(w, ids, model, model["experts_held"]))
    cut = np.asarray(reference.logits(w, ids[:, :19], model,
                                      model["experts_held"]))
    np.testing.assert_allclose(full[:, :19], cut, atol=1e-5)     # causal
    # a window-only model forgets what lies further back than its layers'
    # windows reach together (7 tokens a layer, 7 layers)
    only_window = dict(model, hybrid_layer_pattern=[1] * 7,
                       num_key_value_heads=8)
    ww = {k: v for k, v in w.items()}
    for i in (0, 5):                                # give them window shapes
        for n in ("k_proj", "v_proj"):
            src = w[f"model.layers.1.self_attn.{n}.weight"]
            ww[f"model.layers.{i}.self_attn.{n}.weight"] = src
        ww[f"model.layers.{i}.self_attn.attention_sink_bias"] = \
            w["model.layers.1.self_attn.attention_sink_bias"]
    long = np.random.default_rng(2).integers(0, 160, size=(1, 80)).astype(np.int32)
    other = long.copy()
    other[0, :20] = (other[0, :20] + 1) % 160
    a = np.asarray(reference.logits(ww, long, only_window, model["experts_held"]))
    b = np.asarray(reference.logits(ww, other, only_window, model["experts_held"]))
    assert np.abs(a[0, -1] - b[0, -1]).max() < 1e-5
    assert np.abs(a[0, 25] - b[0, 25]).max() > 1e-4
    # the sink changes the result and only through the denominator
    no_sink = {k: (jnp.full_like(v, -1e9) if k.endswith("sink_bias") else v)
               for k, v in w.items()}
    c = np.asarray(reference.logits(no_sink, ids, model, model["experts_held"]))
    assert np.abs(c - full).max() > 1e-4


def test_the_reference_gives_a_share_its_own_part_and_its_scores():
    w, model = _tiny_weights()
    ids = np.random.default_rng(3).integers(0, 160, size=(1, 12)).astype(np.int32)
    lg, scores = reference.logits(w, ids, model, model["experts_held"],
                                  with_scores=True)
    assert lg.shape == (1, 12, 160)
    assert sorted(scores) == [1, 2, 3, 4, 5, 6]       # layer 0 is dense
    for sc in scores.values():
        sc = np.asarray(sc)
        bias = 0.1                # sigmoid scores and a small correction
        assert sc.shape == (1, 12, 32) and -bias < sc.min() and sc.max() < 1 + bias
    # holding other experts than those the weights are of is another model
    shifted = np.asarray(reference.logits(w, ids, model, list(range(8, 16))))
    assert np.abs(shifted - np.asarray(lg)).max() > 1e-4


def _nearest_turn(scores, model, positions, limit=np.inf):
    """(gap, layer, position, expert, was chosen) of the held expert
    nearest the choice's edge over ``positions``."""
    from benchmarks.runners import serve_mimo_v2 as runner

    return min((g, layer, pos, e, c) for layer, sc in scores.items()
               for pos in positions
               for g, e, c in runner.uncertain(
                   np.asarray(sc[0, pos]), model["experts_held"],
                   model["num_experts_per_tok"], limit))


def _nudge(model, ids, turns):
    from benchmarks.runners import serve_mimo_v2 as runner

    out = {}
    for layer, pos, e, was_in in turns:
        a = out.setdefault(layer, np.zeros(
            ids.shape + (model["n_routed_experts"],), np.float32))
        a[0, pos, e] = -runner.NUDGE if was_in else runner.NUDGE
    return out


def test_uncertain_sees_every_held_expert_near_the_edge_not_only_the_8th_and_9th():
    from benchmarks.runners import serve_mimo_v2 as runner

    # top 2 of six; experts 4 and 5 are held. The last chosen (1) and the
    # first unchosen (2) are absent, and a held one lies just behind them:
    # the pair at the edge says nothing, the held expert is what may turn
    scores = np.array([0.9, 0.60, 0.599, 0.2, 0.597, 0.1], np.float32)
    got = runner.uncertain(scores, [4, 5], 2, 0.01)
    assert [(e, c) for _, e, c in got] == [(4, False)]
    assert abs(got[0][0] - 0.003) < 1e-6
    # a held chosen expert reads its distance to the best unchosen one
    got = runner.uncertain(scores, [0, 1], 2, 0.01)
    assert [(e, c) for _, e, c in got] == [(1, True)] and abs(got[0][0] - 0.001) < 1e-6
    assert runner.uncertain(scores, [0, 3, 5], 2, 0.01) == []
    # exact ties go as lax.top_k takes them: the lower index is chosen
    tie = np.array([0.5, 0.5, 0.5, 0.1], np.float32)
    assert runner.uncertain(tie, [1, 2], 2, 0.01) == [(0.0, 1, True), (0.0, 2, False)]


def test_a_nudge_turns_one_expert_there_and_changes_nothing_before():
    w, model = _tiny_weights()
    ids = np.random.default_rng(3).integers(0, 160, size=(1, 12)).astype(np.int32)
    lg, scores = reference.logits(w, ids, model, model["experts_held"],
                                  with_scores=True)
    lg = np.asarray(lg)
    _, layer, pos, e, was_in = _nearest_turn(scores, model, range(2, 11))
    alt, alt_scores = reference.logits(
        w, ids, model, model["experts_held"], with_scores=True,
        nudge=_nudge(model, ids, [(layer, pos, e, was_in)]))
    alt = np.asarray(alt)
    assert np.array_equal(alt[0, :pos], lg[0, :pos])          # causal
    assert reference.row_error(alt[0, pos], lg[0, pos]) > 1e-3
    # the scores handed back are the reference's own, without the nudge
    assert np.array_equal(np.asarray(alt_scores[layer]), np.asarray(scores[layer]))
    none = np.asarray(reference.logits(
        w, ids, model, model["experts_held"],
        nudge={layer: np.zeros(ids.shape + (32,), np.float32)}))
    assert np.array_equal(none, lg)


def test_a_row_routed_otherwise_is_compared_with_the_references_other_routing():
    """An "engine" that turned the held expert nearest the edge at one
    (layer, position): the row reads far over the tolerance against the
    reference as it routes by itself, and inside it against the
    reference's other routing, if that expert is within the limit; with no
    limit, or a wrong row, the check fails. No row is left out."""
    from benchmarks.runners import serve_mimo_v2 as runner

    w, model = _tiny_weights()
    cfg = tiny("tiny-mimo-v2.json")
    prompt = np.random.default_rng(5).integers(0, 160, size=9).astype(np.int32)
    toks = [3, 1, 4]
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None]
    lg, scores = reference.logits(w, ids, model, model["experts_held"],
                                  with_scores=True)
    lg = np.asarray(lg)[0]
    rows_at = [len(prompt) - 1 + j for j in range(3)]
    gap, layer, pos, e, was_in = _nearest_turn(scores, model, rows_at)
    j = rows_at.index(pos)
    other = np.asarray(reference.logits(
        w, ids, model, model["experts_held"],
        nudge=_nudge(model, ids, [(layer, pos, e, was_in)])))[0]
    rows = {(0, k): (other if k == j else lg)[at].copy()
            for k, at in enumerate(rows_at)}
    spec = dict(cfg["check"], tolerance=1e-4, margin_limit=gap * 1.5 + 1e-9)
    got = runner.compare_rows(w, model, [prompt], [toks], rows, spec)
    assert got[(0, j)]["routed_otherwise"] == [[layer, e]]
    assert abs(got[(0, j)]["margin"] - gap) < 1e-6
    assert max(v["error"] for v in got.values()) < 1e-5
    assert all(not v["routed_otherwise"] for k, v in got.items() if k[1] != j)
    checked = ([prompt], [toks], rows, True)
    assert runner.check_logits(w, model, checked, dict(spec, new_tokens=3))["ok"]
    strict = dict(spec, margin_limit=0.0, new_tokens=3)
    got = runner.compare_rows(w, model, [prompt], [toks], rows, strict)
    assert got[(0, j)]["error"] > 1e-3 and not got[(0, j)]["routed_otherwise"]
    assert not runner.check_logits(w, model, checked, strict)["ok"]
    rows[(0, j)] = rows[(0, j)] * 1.5                  # a wrong row
    wrong = runner.compare_rows(w, model, [prompt], [toks], rows, spec)
    assert wrong[(0, j)]["error"] > 0.3


def test_the_search_follows_the_other_routing_into_later_layers():
    """Two experts turned, in two layers: the second is looked for from
    the scores the first turn leaves behind."""
    from benchmarks.runners import serve_mimo_v2 as runner

    w, model = _tiny_weights()
    prompt = np.random.default_rng(6).integers(0, 160, size=10).astype(np.int32)
    toks = [7]
    ids = np.concatenate([prompt, toks]).astype(np.int32)[None]
    pos = len(prompt) - 1
    _, scores = reference.logits(w, ids, model, model["experts_held"],
                                 with_scores=True)
    early = {k: v for k, v in scores.items() if k <= 3}
    g1, a, _, ea, ca = _nearest_turn(early, model, [pos])
    one = _nudge(model, ids, [(a, pos, ea, ca)])
    _, s2 = reference.logits(w, ids, model, model["experts_held"],
                             with_scores=True, nudge=one)
    later = {k: v for k, v in s2.items() if k > a}
    g2, b, _, eb, cb = _nearest_turn(later, model, [pos])
    both = np.asarray(reference.logits(
        w, ids, model, model["experts_held"],
        nudge=_nudge(model, ids, [(a, pos, ea, ca), (b, pos, eb, cb)])))[0]
    spec = {"tolerance": 1e-4, "margin_limit": max(g1, g2) * 1.01 + 1e-9}
    got = runner.compare_rows(w, model, [prompt], [toks],
                              {(0, 0): both[pos]}, spec)
    assert got[(0, 0)]["routed_otherwise"] == [[a, ea], [b, eb]]
    assert got[(0, 0)]["error"] < 1e-5


def test_the_verdict_is_on_the_worst_of_all_rows_and_leaves_none_out():
    from benchmarks.runners import serve_mimo_v2 as runner

    def verdict(errors, margins, agree=True, expected=None):
        got = {(0, j): {"error": e, "margin": m, "routed_otherwise": []}
               for j, (e, m) in enumerate(zip(errors, margins))}
        return runner.verdict(got, agree, expected or len(errors),
                              {"tolerance": 0.02})

    fine, wide = [0.013] * 8, [0.5] * 8
    got = verdict(fine, wide)
    assert got["ok"] and got["worst"] == 0.013 and got["rows"] == 8
    # one row over the tolerance fails the check however near a tie it is
    assert not verdict(fine[:7] + [0.05], wide[:7] + [0.0004])["ok"]
    assert not verdict(fine[:7] + [0.05], wide)["ok"]
    # a row that is missing, or two passes that chose another first token
    assert not verdict(fine, wide, expected=9)["ok"]
    assert not verdict(fine, wide, agree=False)["ok"]
    assert "left_out" not in got
    order = [m for _, m, _, _ in verdict(fine, [0.3, 0.1] * 4)["by_margin"]]
    assert order == sorted(order)


def test_the_checks_rows_are_taken_beside_a_full_batch_and_leave_the_engine_empty():
    from benchmarks.runners import serve_mimo_v2 as runner
    from paddle_tpu.inference.serving import LLMEngine

    cfg = tiny("tiny-mimo-v2.json")
    model = runner.model_sizes(cfg)
    net = runner.build_model(model, 7, "float32")
    net.eval()
    eng = LLMEngine(net, capture_logits=True, **cfg["engine"])
    try:
        free = (eng.cache.allocator.num_free, eng.cache.window.allocator.num_free)
        beside, step = [], eng.step

        def counting_step():
            outs = step()
            beside.append(sum(1 for r in eng.scheduler.slots
                              if r is not None and not r.prefilling))
            return outs

        eng.step = counting_step
        released0 = eng.metrics()["window_blocks_released"]
        prompts, toks, rows, agree = runner.engine_rows(eng, model, 7, cfg["check"])
        del eng.step
        assert agree and sorted(rows) == [(i, j) for i in range(3) for j in range(3)]
        assert [len(p) for p in prompts] == cfg["check"]["prompt_lens"]
        # every slot the three do not need held a request that decoded
        # beside them (they come in a step apart and are short, so they
        # overlap only in part), its ring turning: its context is past the
        # window
        assert max(beside) > cfg["engine"]["max_batch_size"] - 3
        assert min(beside) >= cfg["engine"]["max_batch_size"] - 3
        assert eng.metrics()["window_blocks_released"] - released0 > 10
        # fillers cancelled, everything released: nothing is left behind
        assert not eng.has_work() and not eng._requests
        assert (eng.cache.allocator.num_free,
                eng.cache.window.allocator.num_free) == free
    finally:
        eng.close()


# --- the runner, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_rehearsal_on_the_cpu_ends_in_a_well_formed_line(trace, monkeypatch,
                                                         tmp_path):
    import glob

    from benchmarks.runners import serve_mimo_v2 as runner

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    run = runner.run(tiny("tiny-mimo-v2.json"), tiny("tiny-closed.json"),
                     seed=2 ** 31 + 3, seconds=1.0, trace=trace,
                     out_dir=str(tmp_path), t_start=time.perf_counter(),
                     require_chip=False)
    assert run["correct"] and run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["check"]["rows"] == 9 and run["check"]["worst"] < 1e-4
    c = run["counters"]
    assert c["evictions"] == 0 and c["window_blocks_released"] > 0
    assert c["moe_layer_steps"] == 6 * (c["host_syncs"] + c["prefill_chunks"])
    assert 0 < c["moe_experts_hit_decode"] <= 8 * c["moe_layer_steps_decode"]
    assert 0 < c["kv_live_byte_steps"] < c["kv_one_table_byte_steps"]
    manifest = {"end_to_end": [
        {"name": "serve_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"}]}
    line = json.loads(json.dumps(
        bench_run.result_line(manifest, CELL, run, trace=False)))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    ratio = bench_run.read_layer_metric("mimo.cache.kv_bytes_vs_uniform", run)
    assert 0 < ratio < 100
    if trace:
        files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        host = trace_reduce.load_xplane(files[0])["host"]
        assert sum(1 for name, _, _ in host if name == trace_reduce.STEP_SPAN) \
            == len(run["traced_steps"]) > 0
        assert run["trace"] is None and run["work"] == {}
        assert run["traced_counters"]["moe_layer_steps"] > 0
        # the engine's phase spans are in the profile for the new model too
        assert {"engine.step", "engine.prefill", "engine.decode.prepare",
                "engine.decode.fetch"} <= {name for name, _, _ in host}
