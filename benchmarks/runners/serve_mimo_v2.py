"""Runner of kind "serve_mimo_v2": MiMo-V2-Flash through ``LLMEngine``
under the closed loop of the "serve" runner.

What is generic comes from ``runners.serve`` and ``runners.common`` (the
loop and its clock, the warm-up, the logits rows of the check, the traced
phase, compile counting, the profiler); what is this model's is here: the
model from the seed, its sizes, the check (the engine's rows taken beside a
full batch before the window, compared with the reference after it), the
work of the traced steps, and the engine's counters of pages and experts
around the traced phase and the window (``serve._closed`` takes neither, so
its few lines are repeated in ``_closed`` below).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import peaks_mimo_v2 as work_mod
from ..harness import reference_mimo_v2 as reference
from ..harness import schedule, stats
from . import common, serve

#: the source's keys the model is built from (the file's top level)
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim",
    "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
    "swa_v_head_dim", "sliding_window", "hybrid_layer_pattern",
    "moe_layer_freq", "rope_theta", "swa_rope_theta", "partial_rotary_factor",
    "attention_value_scale", "add_swa_attention_sink_bias",
    "add_full_attention_sink_bias", "layernorm_epsilon",
    "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "tie_word_embeddings")
#: the engine's counters the record keeps, as differences over a stretch
COUNTERS = ("host_syncs", "tokens_out", "prefills", "prefill_chunks",
            "evictions", "admitted", "finished", "window_blocks_released",
            "kv_live_byte_steps", "kv_one_table_byte_steps",
            "moe_pairs_routed_here", "moe_experts_hit", "moe_layer_steps",
            "moe_pairs_routed_here_decode", "moe_experts_hit_decode",
            "moe_layer_steps_decode")


def model_sizes(config):
    """The model as it is run: the source's keys, with the router at its
    published width and ``experts_held`` the experts this chip holds."""
    model = {k: config[k] for k in MODEL_KEYS}
    held = int(config["n_routed_experts"])
    model["n_routed_experts"] = int(
        config["reduced"]["n_routed_experts"]["published"])
    model["experts_held"] = list(range(held))
    if model["n_routed_experts"] < held:
        raise ValueError("more experts held than the router has")
    if config.get("n_shared_experts") or config.get("scoring_func") != "sigmoid" \
            or config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("models/mimo_v2.py routes by sigmoid scores with no "
                         "shared expert and no group limit")
    # rope tables reach as far as the engine serves
    model["max_position_embeddings"] = int(config["engine"]["max_model_len"])
    return model


def build_model(model, seed, dtype="bfloat16"):
    """``MiMoV2ForCausalLM`` with every weight drawn on the device in ONE
    jitted call from the seed (``common.build_model``'s method)."""
    import jax

    from paddle_tpu.core import rng
    from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM

    cfg = MiMoV2Config(**{k: (tuple(v) if isinstance(v, list) else v)
                          for k, v in model.items()})
    gen = rng.default_generator()
    box = {}

    def make(key):
        gen.manual_seed(0)
        with gen.traced_base(key):
            net = MiMoV2ForCausalLM(cfg)
            if dtype == "bfloat16":
                net.bfloat16()
        box["net"] = net
        return [t._data for t in common._leaves(net)]

    arrays = jax.jit(make)(jax.random.key(np.uint32(seed & 0xFFFFFFFF)))
    net = box["net"]
    for t, a in zip(common._leaves(net), arrays):
        t._data = a
    return net


#: reference forwards spent on one row's other routings, at most
MAX_OTHER_ROUTINGS = 12
#: what a nudge adds to a corrected score (a sigmoid and a small bias) to
#: make the choice take or leave that expert whatever the others read
NUDGE = 4.0


def engine_rows(eng, model, seed, spec):
    """``serve.logit_rows`` beside a full batch: the same three requests
    across two prefill buckets on the measured engine itself, the same two
    passes (row 0 comes from a second pass of one-token requests), but
    every other slot of the batch holds a request that is decoding
    meanwhile, its context past the window, so that the rows compared were
    made while rings turned and pages went back on all sides of them (no
    token is dropped, so a request's logits do not depend on its
    neighbours: test (d)). Returns ``(prompts, toks, rows, agree)``."""
    from paddle_tpu.inference.serving import SamplingParams

    prompts = [schedule.token_ids(seed, serve.CHECK_INDEX + i, n,
                                  model["vocab_size"])
               for i, n in enumerate(spec["prompt_lens"])]
    n_new = int(spec["new_tokens"])
    cap = int(eng.max_model_len)
    window = int(model["sliding_window"])
    fill = []
    for i in range(eng.max_batch_size - len(prompts)):
        n = min(window + 2 + (37 * i) % (2 * window), cap // 2)
        ids = schedule.token_ids(seed, serve.CHECK_INDEX + 1000 + i, n,
                                 model["vocab_size"])
        fill.append(eng.add_request(
            ids, SamplingParams(max_new_tokens=cap - n - 1)))
    while any(not eng.request(r).output_tokens for r in fill):
        eng.step()                       # every one of them is decoding
    rows = {}

    def burst(lengths):
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, lengths)]
        seen = {r: 0 for r in rids}
        while not all(eng.request(r).finished for r in rids):
            for out in eng.step():
                if out.rid not in seen:
                    continue
                j = seen[out.rid]
                seen[out.rid] += 1
                if j > 0 or out.finished:
                    rows[(rids.index(out.rid), j)] = \
                        eng.request(out.rid).last_logits.copy()
        toks = [list(eng.request(r).output_tokens) for r in rids]
        for r in rids:
            eng.release(r)
        return toks

    toks = burst([n_new] * len(prompts))
    first = burst([1] * len(prompts))
    for r in fill:
        eng.cancel(r)
        eng.release(r)
    serve._drain(eng, lambda out: None)
    agree = all(f[0] == t[0] for f, t in zip(first, toks))
    return prompts, toks, rows, agree


def uncertain(scores, held, top_k, limit):
    """``[(gap, expert, chosen)]`` for the held experts whose place in or
    out of the ``top_k`` a change of ``limit`` in one corrected score
    would turn, nearest first. ``gap`` is that change: for a chosen expert
    its score over the best unchosen one's, for an unchosen one the worst
    chosen one's over its own. Only a held expert matters: which of two
    absent experts is chosen changes nothing this chip computes but the
    combine weights' sum, by less than the tie itself."""
    order = np.argsort(-scores, kind="stable")     # lax.top_k's order
    chosen = set(order[:top_k].tolist())
    worst_in, best_out = scores[order[top_k - 1]], scores[order[top_k]]
    out = []
    for e in held:
        gap = float(scores[e] - best_out if e in chosen
                    else worst_in - scores[e])
        if gap < limit:
            out.append((gap, int(e), e in chosen))
    return sorted(out)


def compare_rows(weights, model, prompts, toks, rows, spec):
    """``{row: {"error", "margin", "routed_otherwise"}}`` for each of
    ``rows``, from plain forwards over prompt + output, a request at a time.

    A row is first compared with the reference as it routes by itself, from
    its own float32 scores. Where a held expert lies within
    ``spec["margin_limit"]`` of the choice's edge in some layer at the
    row's position (``uncertain``), the engine's bf16 hidden state may put
    it on the other side, which is another valid result. Such a row, IF it
    reads over the tolerance, is compared again with the reference nudged
    to take (or leave) that expert there and nothing else differently, and
    keeps the smallest reading. The search goes on from the routing that
    reads nearest: with one expert turned the later layers see another
    input and may tie anew, so their scores are read again from each
    routing tried; at most ``MAX_OTHER_ROUTINGS`` forwards a row. A row
    with no expert near the edge has one reference only, and no row is
    left out."""
    tol, limit = float(spec["tolerance"]), float(spec.get("margin_limit", 0.0))
    held, top_k = model["experts_held"], model["num_experts_per_tok"]
    out = {}
    for i, (p, t) in enumerate(zip(prompts, toks)):
        ids = np.concatenate([p, t]).astype(np.int32)[None]

        def forward(turns, pos):
            """Logits rows, and {layer: the scores the choice at ``pos`` was
            made from}, with the experts of ``turns`` ((layer, expert, was
            chosen), ...) turned there."""
            nudge = {}
            for layer, e, was_in in turns:
                a = nudge.setdefault(layer, np.zeros(
                    ids.shape + (model["n_routed_experts"],), np.float32))
                a[0, pos, e] = -NUDGE if was_in else NUDGE
            lg, sc = reference.logits(weights, ids, model, experts_held=held,
                                      with_scores=True, nudge=nudge)
            return np.asarray(lg)[0], {
                layer: np.asarray(v[0, pos])
                + (nudge[layer][0, pos] if layer in nudge else 0.0)
                for layer, v in sc.items()}, sc

        lg, _, sc = forward((), 0)
        for (r, j) in sorted(k for k in rows if k[0] == i):
            pos = len(p) - 1 + j
            at = {layer: np.asarray(v[0, pos]) for layer, v in sc.items()}
            near = [g for v in at.values()
                    for g, _, _ in uncertain(v, held, top_k, np.inf)]
            best = [reference.row_error(rows[(r, j)], lg[pos]), ()]
            frontier = [(best[0], (), at)]
            budget = MAX_OTHER_ROUTINGS
            while frontier and best[0] >= tol and budget > 0:
                frontier.sort(key=lambda n: n[0])
                _, turns, scores = frontier.pop(0)
                last = turns[-1][:2] if turns else (-1, -1)
                for gap, layer, e, was_in in sorted(
                        (g, layer, e, c) for layer, v in scores.items()
                        for g, e, c in uncertain(v, held, top_k, limit)
                        if (layer, e) > last):
                    if best[0] < tol or budget <= 0:
                        break
                    budget -= 1
                    more = turns + ((layer, e, was_in),)
                    alt, alt_at, _ = forward(more, pos)
                    err = reference.row_error(rows[(r, j)], alt[pos])
                    if err < best[0]:
                        best[:] = [err, more]
                    frontier.append((err, more, alt_at))
            out[(r, j)] = {"error": best[0], "margin": min(near, default=math.inf),
                           "routed_otherwise": [[layer, e] for layer, e, _
                                                in best[1]]}
    return out


def verdict(compared, agree, expected, spec):
    """The check's record: the worst of ALL rows against the tolerance."""
    tol = float(spec["tolerance"])
    worst = max((v["error"] for v in compared.values()), default=math.inf)
    return {"ok": bool(agree and len(compared) == expected and worst < tol),
            "worst": worst, "rows": len(compared), "tolerance": tol,
            "routed_otherwise": sum(1 for v in compared.values()
                                    if v["routed_otherwise"]),
            "by_margin": [[list(k), v["margin"], v["error"],
                           v["routed_otherwise"]]
                          for k, v in sorted(compared.items(),
                                             key=lambda kv: kv[1]["margin"])]}


def check_logits(weights, model, checked, spec):
    """``engine_rows``' rows (``checked``) against the reference
    (``compare_rows``): every sampled-from logits row has to stay under
    ``spec["tolerance"]``, against the reference as it routes by itself or
    against one of its other routings within ``margin_limit``."""
    prompts, toks, rows, agree = checked
    finite = all(np.isfinite(row).all() for row in rows.values())
    got = compare_rows(weights, model, prompts, toks, rows, spec) \
        if finite else {}
    return verdict(got, agree and finite,
                   len(prompts) * int(spec["new_tokens"]), spec)


def _counters(m0, m1):
    return {k: m1[k] - m0[k] for k in COUNTERS}


def _work(record, config, model):
    """What the traced decode steps had to do, from their shapes and the
    engine's count of the experts their routing hit."""
    if record["device_kind"] not in peaks_mod.PEAKS:   # the CPU rehearsal
        return {}
    bw = peaks_mod.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    steps = record.get("traced_steps") or []
    decode_steps = sum(1 for s in steps if s[4])
    rows, ctx = sum(s[4] for s in steps), sum(s[5] for s in steps)
    hit = (record.get("traced_counters") or {}).get("moe_experts_hit_decode", 0)
    return {
        "global_decode_s": work_mod.global_decode_bytes(config, ctx) / bw,
        "window_decode_s": work_mod.window_decode_bytes(config, rows, ctx) / bw,
        "weight_stream_s": work_mod.weight_stream_bytes(
            config, decode_steps, hit, model["n_routed_experts"]) / bw,
    }


def _closed(loop, items, traffic, seconds, trace, out_dir, t_start, counter,
            record, config, model):
    """``serve._closed``, with the engine's counters read around the traced
    phase as well as around the window."""
    src = schedule.cycled(items)
    loop.on_finish = lambda lv: loop.submit(next(src))
    for _ in range(int(traffic["clients"])):
        loop.submit(next(src))
    for _ in range(int(traffic["warmup_steps"])):
        loop.step()
    if trace:
        before = loop.eng.metrics()
        serve._trace_phase(loop, traffic, trace, out_dir, record)
        record["traced_counters"] = _counters(before, loop.eng.metrics())
    loop.done.clear()
    loop.steps.clear()
    gc.collect()
    m0, compiles0 = loop.eng.metrics(), counter.compiles
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while time.perf_counter() < t_close:
        loop.step()
    t_end = time.perf_counter()
    steps = serve._window(loop, t_open, t_end)
    record.update(
        setup_s=t_open - t_start, seconds=seconds,
        compiles_in_window=counter.compiles - compiles0,
        counters=_counters(m0, loop.eng.metrics()),
        series=serve._series(steps))
    record["work"] = _work(record, config, model)
    tokens = [(s[1], s[2]) for s in steps]
    rates = stats.slice_rates(tokens, t_open, seconds, traffic["slice_seconds"])
    record["slice_rates"] = rates
    record["values"] = {
        "serve_tokens_per_s": stats.window_rate(tokens, t_open, t_end),
        "slice_median_tokens_per_s": stats.median(rates),
    }
    record["attempted"] = len(loop.done)
    record["failed"] = sum(1 for lv in loop.done
                           if len(lv.token_ts) != lv.item.output_len)


def run(config, traffic, *, seed, seconds, trace, out_dir, t_start,
        chips=1, require_chip=True):
    """One run of the cell."""
    import jax

    devs = common.require_tpu(chips) if require_chip else jax.devices()
    counter = common.CompileCounter()
    model = model_sizes(config)
    net = build_model(model, seed, config.get("dtype", "bfloat16"))
    net.eval()

    from paddle_tpu.inference.serving import LLMEngine

    eng = LLMEngine(net, capture_logits=True, **config["engine"])
    try:
        # the engine's rows now, the reference's after the window: its
        # float32 forwards (more of them where a row routed otherwise) are
        # no part of set-up
        checked = engine_rows(eng, model, seed, config["check"])
        # the rows are taken: from here the engine runs as a deployment
        # does, at its default, and a greedy step fetches its tokens only
        eng.capture_logits = False
        items = schedule.build(traffic)
        loop = serve.Loop(eng, seed, model["vocab_size"])
        touched = serve.warm_shapes(loop, items,
                                    config["engine"]["prefill_buckets"])
        print(f"[warm] prefill buckets {touched}; {counter.compiles}"
              f" executables so far", flush=True)
        record = {"kind": "serve_mimo_v2", "loop": traffic["loop"],
                  "model": {k: v for k, v in model.items()
                            if not isinstance(v, list)},
                  "device_kind": devs[0].device_kind, "trace": None}
        if trace:
            loop.span = common.step_span
        _closed(loop, items, traffic, seconds, trace, out_dir, t_start,
                counter, record, config, model)
        check = check_logits(common.named_weights(net), model, checked,
                             config["check"])
        print(f"[check] {check}", flush=True)
        record["correct"] = (bool(check["ok"])
                             and record["compiles_in_window"] == 0)
        record["check"] = check
        record["device"] = common.device_record(devs, chips)
        return record
    finally:
        eng.close()
