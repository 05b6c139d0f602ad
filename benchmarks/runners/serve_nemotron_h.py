"""Runner of kind "serve_nemotron_h": Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B)
through ``LLMEngine`` under the closed loop of the "serve" runner.

What is generic comes from ``runners.serve``, ``runners.common``,
``runners.serve_joyai_flash`` (the warm-up that meets every (staging length,
chunk offset, chunk rung) of the schedule; the way the check's rows are taken
beside a full batch is its too, repeated here because the rows' routing is
taken with them); what is this model's is here: the model from the seed, its
sizes, the comparison with its reference ROUTED AS THE ENGINE ROUTED (a row
reads its context through a state: PERF.md section 4), the work of the traced
steps, and the
engine's counters read around the TRACED steps alone and around the window
(``serve._closed`` and ``serve._trace_phase`` take neither, so their lines are
repeated below as in the two runners before: PERF.md section 7 (f)).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import peaks_nemotron_h as work_mod
from ..harness import reference_nemotron_h as reference
from ..harness import schedule, stats
from . import common, serve
from .serve_joyai_flash import warm_shapes

#: the source's keys the model is built from (the file's top level)
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
    "n_groups", "conv_kernel", "chunk_size", "layer_norm_epsilon",
    "time_step_min", "time_step_max", "time_step_floor",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "tie_word_embeddings")
_MODEL_COUNTERS = ("ssm_state_rows_updated", "ssm_tokens_scanned",
                   "moe_pairs_routed_here", "moe_experts_hit",
                   "moe_layer_steps", "moe_weight_passes")
#: the engine's counters the record keeps, as differences over a stretch
COUNTERS = ("host_syncs", "tokens_out", "prefills", "prefill_chunks",
            "evictions", "admitted", "finished", "kv_live_byte_steps",
            "state_byte_steps") + tuple(
    n + tail for n in _MODEL_COUNTERS for tail in ("", "_decode", "_prefill"))


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
    if config.get("mlp_hidden_act") != "relu2" \
            or config.get("mamba_hidden_act") != "silu" \
            or config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1 \
            or config.get("use_bias") or config.get("mlp_bias") \
            or config.get("attention_bias") or config.get("mamba_proj_bias") \
            or not config.get("use_conv_bias"):
        raise ValueError(
            "models/nemotron_h.py computes relu^2 experts routed by sigmoid "
            "scores with no group limit, silu in the state-space mixer, and "
            "no bias but the convolution's")
    # positions are nowhere in this model (no rotary embedding): the cap
    # only bounds what the engine serves
    model["max_position_embeddings"] = int(config["engine"]["max_model_len"])
    return model


def build_model(model, seed, dtype="bfloat16"):
    """``NemotronHForCausalLM`` with every weight drawn on the device in ONE
    jitted call from the seed (``common.build_model``'s method)."""
    import jax

    from paddle_tpu.core import rng
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    cfg = NemotronHConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                             for k, v in model.items()})
    gen = rng.default_generator()
    box = {}

    def make(key):
        gen.manual_seed(0)
        with gen.traced_base(key):
            net = NemotronHForCausalLM(cfg)
            if dtype == "bfloat16":
                net.bfloat16()
        box["net"] = net
        return [t._data for t in common._leaves(net)]

    arrays = jax.jit(make)(jax.random.key(np.uint32(seed & 0xFFFFFFFF)))
    net = box["net"]
    for t, a in zip(common._leaves(net), arrays):
        t._data = a
    return net


# --- the check -----------------------------------------------------------------

def engine_rows(eng, model, seed, spec):
    """``serve_joyai_flash.engine_rows``' rows, and beside them how the
    engine ROUTED: the same requests across the prefill buckets on the
    measured engine itself beside a full batch that is decoding, in the same
    two passes (row 0 comes from a second pass of one-token requests), and
    for each request of each pass the experts every position took in every
    expert block (``Request.kept["moe_choice"]``, which ``capture_logits``
    fills beside ``last_logits``). Returns ``(prompts, passes, agree)``; a
    pass is ``{"toks": a request's tokens, "rows": {(request, j): logits
    row}, "choice": a request's [blocks, positions, top_k]}``."""
    from paddle_tpu.inference.serving import SamplingParams

    vocab = model["vocab_size"]
    prompts = [schedule.token_ids(seed, serve.CHECK_INDEX + i, n, vocab)
               for i, n in enumerate(spec["prompt_lens"])]
    cap = int(eng.max_model_len)
    fill = []
    for i in range(eng.max_batch_size - len(prompts)):
        n = min(cap // 24 + (37 * i) % (cap // 8), cap // 2)
        ids = schedule.token_ids(seed, serve.CHECK_INDEX + 1000 + i, n, vocab)
        fill.append(eng.add_request(
            ids, SamplingParams(max_new_tokens=cap - n - 1)))
    while any(not eng.request(r).output_tokens for r in fill):
        eng.step()                       # every one of them is decoding

    def burst(n_new):
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=n_new))
                for p in prompts]
        seen, rows = {r: 0 for r in rids}, {}
        while not all(eng.request(r).finished for r in rids):
            for out in eng.step():
                if out.rid not in seen:
                    continue
                j = seen[out.rid]
                seen[out.rid] += 1
                if j > 0 or out.finished:
                    rows[(rids.index(out.rid), j)] = \
                        eng.request(out.rid).last_logits.copy()
        taken = {"toks": [list(eng.request(r).output_tokens) for r in rids],
                 "rows": rows,
                 "choice": [np.concatenate(
                     eng.request(r).kept["moe_choice"], 1) for r in rids]}
        for r in rids:
            eng.release(r)
        return taken

    passes = [burst(int(spec["new_tokens"])), burst(1)]
    for r in fill:
        eng.cancel(r)
        eng.release(r)
    serve._drain(eng, lambda out: None)
    agree = all(f[0] == t[0] for f, t in zip(passes[1]["toks"],
                                             passes[0]["toks"]))
    return prompts, passes, agree


#: the reference's sequences are padded up to a multiple of this, so that a
#: request's passes, a few tokens apart, are ONE shape to compile (it is
#: causal: what lies behind a position moves nothing at it)
PAD_TO = 128


def compare_rows(weights, model, prompts, passes):
    """Every row of ``engine_rows`` against the reference ROUTED AS THE
    ENGINE ROUTED: one plain forward a request, a sequence a pass, over the
    positions the engine computed, each token taking the experts the engine
    chose for it (``reference.logits(choice=)``). Returns ``({row: error},
    {"pairs", "turned", "gap"})``: of all (position, expert block, expert)
    places, how many the engine chose otherwise than the reference's own
    scores there would, and the largest change in one score that such a
    choice needs (``reference.choice_gaps``)."""
    held, top_k = model["experts_held"], model["num_experts_per_tok"]
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    blocks = [i for i, letter in enumerate(pattern) if letter == "E"]
    errors, routing = {}, {"pairs": 0, "turned": 0, "gap": 0.0}
    for i, p in enumerate(prompts):
        # the last token of a pass was computed by no step
        choices = [taken["choice"][i] for taken in passes]
        width = -(-max(c.shape[1] for c in choices) // PAD_TO) * PAD_TO
        ids = np.zeros((len(passes), width), np.int32)
        handed = np.tile(np.arange(top_k, dtype=np.int32),
                         (len(blocks), len(passes), width, 1))
        for q, (taken, c) in enumerate(zip(passes, choices)):
            n = c.shape[1]
            ids[q, :n] = np.concatenate([p, taken["toks"][i]])[:n]
            handed[:, q, :n] = c
        lg, sc = reference.logits(
            weights, ids, model, experts_held=held, with_scores=True,
            choice={b: handed[k] for k, b in enumerate(blocks)})
        lg = np.asarray(lg)
        for q, (taken, c) in enumerate(zip(passes, choices)):
            n = c.shape[1]
            for k, b in enumerate(blocks):
                turned, gap = reference.choice_gaps(sc[b][q, :n], c[k])
                routing["pairs"] += c[k].size
                routing["turned"] += turned
                routing["gap"] = max(routing["gap"], gap)
            for (r, j), row in taken["rows"].items():
                if r == i:
                    errors[(r, j)] = reference.row_error(
                        row, lg[q, len(p) - 1 + j])
    return errors, routing


def verdict(errors, routing, agree, expected, spec):
    """The check's record. The WORST of all rows is held under
    ``tolerance``, as the three configurations before hold it, and the
    engine's routing under ``margin_limit``: no choice it made may lie
    further from the reference's own scores than that."""
    tol, limit = float(spec["tolerance"]), float(spec["margin_limit"])
    worst = max(errors.values(), default=math.inf)
    return {"ok": bool(agree and len(errors) == expected and worst < tol
                       and routing["gap"] < limit),
            "worst": worst, "rows": len(errors), "tolerance": tol,
            "largest_gap": routing["gap"], "margin_limit": limit,
            "pairs_turned": routing["turned"], "pairs": routing["pairs"],
            "errors": [[list(k), v] for k, v in sorted(errors.items())]}


def check_logits(net, model, checked, spec):
    """``engine_rows``' rows (``checked``) against the reference
    (``compare_rows``, ``verdict``)."""
    prompts, passes, agree = checked
    finite = all(np.isfinite(row).all()
                 for taken in passes for row in taken["rows"].values())
    errors, routing = compare_rows(
        common.named_weights(net), model, prompts, passes) if finite \
        else ({}, {"pairs": 0, "turned": 0, "gap": math.inf})
    return verdict(errors, routing, agree and finite,
                   len(prompts) * int(spec["new_tokens"]), spec)


# --- the loop ------------------------------------------------------------------

def _counters(m0, m1):
    return {k: m1[k] - m0[k] for k in COUNTERS}


def _work(record, config, model):
    """What the traced decode steps had to do: the states the engine counted
    the decode update reading and writing, the weights by the experts its
    routing hit."""
    if record["device_kind"] not in peaks_mod.PEAKS:   # the CPU rehearsal
        return {}
    bw = peaks_mod.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    steps = record.get("traced_steps") or []
    counted = record.get("traced_counters") or {}
    decode_steps = sum(1 for s in steps if s[4])
    return {
        "ssm_decode_s": work_mod.ssm_decode_bytes(
            config, counted.get("ssm_state_rows_updated_decode", 0)) / bw,
        # the same rows from the loop's own books, for the record
        "ssm_rows_by_steps": sum(s[4] for s in steps)
        * work_mod.state_layers(config),
        "global_decode_s": work_mod.global_decode_bytes(
            config, sum(s[5] for s in steps)) / bw,
        "expert_ffn_s": work_mod.expert_bytes(config) * counted.get(
            "moe_experts_hit_decode", 0) / bw,
        "weight_stream_s": decode_steps * work_mod.fixed_stream_bytes(
            config, model["n_routed_experts"]) / bw,
    }


def _cache_shares(counters):
    """The state's share of what the requests held over a stretch, from the
    engine's byte-steps (both at the published widths)."""
    state, kv = counters["state_byte_steps"], counters["kv_live_byte_steps"]
    return {"state_bytes_share":
            100.0 * state / (state + kv) if state + kv else None}


def _trace_phase(loop, traffic, out_dir, record):
    """``serve._trace_phase``, with the engine's counters read around the
    traced steps alone (after the settling steps)."""
    common.start_trace(out_dir)
    try:
        span, loop.span = loop.span, None
        for _ in range(serve.SETTLE_STEPS):
            loop.step()
        loop.span = span
        n0, before = len(loop.steps), loop.eng.metrics()
        t_stop = time.perf_counter() + traffic.get("trace_seconds", 3)
        while time.perf_counter() < t_stop:
            loop.step()
        record["traced_counters"] = _counters(before, loop.eng.metrics())
    finally:
        record["trace"] = common.stop_trace(out_dir)
    record["traced_steps"] = loop.steps[n0:]


def _closed(loop, items, traffic, seconds, trace, out_dir, t_start, counter,
            record, config, model):
    """``serve._closed``, with the engine's counters around the window."""
    src = schedule.cycled(items)
    loop.on_finish = lambda lv: loop.submit(next(src))
    for _ in range(int(traffic["clients"])):
        loop.submit(next(src))
    for _ in range(int(traffic["warmup_steps"])):
        loop.step()
    if trace:
        _trace_phase(loop, traffic, out_dir, record)
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
    record["cache"] = _cache_shares(record["counters"])
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
        # float32 forwards are no part of set-up
        checked = engine_rows(eng, model, seed, config["check"])
        # the rows are taken: from here the engine runs as a deployment
        # does, at its default, and a greedy step fetches its tokens only
        eng.capture_logits = False
        items = schedule.build(traffic)
        loop = serve.Loop(eng, seed, model["vocab_size"])
        warmed = warm_shapes(loop, items, config["engine"])
        print(f"[warm] {len(warmed)} prompts {warmed}; {counter.compiles}"
              f" executables so far", flush=True)
        record = {"kind": "serve_nemotron_h", "loop": traffic["loop"],
                  "model": {k: v for k, v in model.items()
                            if not isinstance(v, list)},
                  "device_kind": devs[0].device_kind, "trace": None}
        if trace:
            loop.span = common.step_span
        _closed(loop, items, traffic, seconds, trace, out_dir, t_start,
                counter, record, config, model)
        # the serving peak: what a deployment holds, before the reference's
        # float32 forwards come
        record["device"] = common.device_record(devs, chips)
    finally:
        eng.close()
    # the pools and the states go before the reference comes
    del eng, loop
    gc.collect()
    check = check_logits(net, model, checked, config["check"])
    print(f"[check] {check}", flush=True)
    record["correct"] = (bool(check["ok"])
                         and record["compiles_in_window"] == 0)
    record["check"] = check
    return record
