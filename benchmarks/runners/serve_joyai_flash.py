"""Runner of kind "serve_joyai_flash": JoyAI-LLM-Flash through ``LLMEngine``
under the closed loop of the "serve" runner.

What is generic comes from ``runners.serve``, ``runners.common`` and
``runners.serve_mimo_v2`` (the loop and its clock, compile counting, the
profiler, which held experts lie at the edge of the router's choice, the
verdict); what is this model's is here: the model from the seed, its sizes,
a warm-up that meets every (staging length, chunk offset, chunk rung) the
schedule holds, the check (the engine's rows taken beside a full batch
before the window, compared with the reference after it, the prediction
module's logits with them), the work of the traced steps, and the engine's
counters read around the TRACED steps alone and around the window
(``serve._closed`` and ``serve._trace_phase`` take neither, so their lines
are repeated below: PERF.md section 7 (f)).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import peaks_joyai_flash as work_mod
from ..harness import reference_joyai_flash as reference
from ..harness import schedule, stats
from . import common, serve
from .serve_mimo_v2 import MAX_OTHER_ROUTINGS, NUDGE, uncertain, verdict

#: the source's keys the model is built from (the file's top level)
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_interleave",
    "rope_scaling", "rms_norm_eps", "first_k_dense_replace",
    "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "num_nextn_predict_layers",
    "tie_word_embeddings")
_MODEL_COUNTERS = ("mla_latent_tokens_read", "mla_context_tokens_expanded",
                   "moe_pairs_routed_here", "moe_experts_hit",
                   "moe_layer_steps", "moe_weight_passes")
#: the engine's counters the record keeps, as differences over a stretch
COUNTERS = ("host_syncs", "tokens_out", "prefills", "prefill_chunks",
            "evictions", "admitted", "finished") + tuple(
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
    if config.get("scoring_func") != "sigmoid" \
            or config.get("topk_method") != "noaux_tc" \
            or config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1 \
            or config.get("moe_layer_freq", 1) != 1:
        raise ValueError("models/joyai_flash.py routes by sigmoid scores "
                         "with a choice-only bias, no group limit, experts "
                         "in every layer past the dense ones")
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    if config.get("qk_head_dim", qk) != qk:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    # rope tables reach as far as the engine serves
    model["max_position_embeddings"] = int(config["engine"]["max_model_len"])
    return model


def build_model(model, seed, dtype="bfloat16"):
    """``JoyAIFlashForCausalLM`` with every weight drawn on the device in
    ONE jitted call from the seed (``common.build_model``'s method)."""
    import jax

    from paddle_tpu.core import rng
    from paddle_tpu.models.joyai_flash import (JoyAIFlashConfig,
                                               JoyAIFlashForCausalLM)

    cfg = JoyAIFlashConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                              for k, v in model.items()})
    gen = rng.default_generator()
    box = {}

    def make(key):
        gen.manual_seed(0)
        with gen.traced_base(key):
            net = JoyAIFlashForCausalLM(cfg)
            if dtype == "bfloat16":
                net.bfloat16()
        box["net"] = net
        return [t._data for t in common._leaves(net)]

    arrays = jax.jit(make)(jax.random.key(np.uint32(seed & 0xFFFFFFFF)))
    net = box["net"]
    for t, a in zip(common._leaves(net), arrays):
        t._data = a
    return net


# --- warm-up -------------------------------------------------------------------

def chunk_plan(n, buckets, budget):
    """``(staging length, ((start, rung), ...))`` of a prompt of ``n``
    tokens, as ``LLMEngine._run_chunk`` cuts it: each chunk the smallest
    rung that covers what is taken and fits the staged room."""
    bucket = serve._bucket(buckets, n)
    start, chunks = 0, []
    while start < n:
        take = min(budget, n - start)
        room = bucket - start
        c = next((b for b in sorted(buckets) if take <= b <= room), None)
        if c is None:
            c = max(b for b in buckets if b <= room)
            take = min(take, c)
        chunks.append((start, c))
        start += take
    return bucket, tuple(chunks)


def warm_shapes(loop, items, engine):
    """One request for every distinct (staging length, chunk offset, chunk
    rung) the schedule holds, so that nothing compiles in the window: the
    engine cuts a chunk's ids out of the staged prompt by a slice that is an
    executable of its own for each of them, beside the chunk graph a rung.
    Returns the prompt lengths sent."""
    buckets, budget = engine["prefill_buckets"], \
        engine["max_prefill_tokens_per_step"]
    seen, lengths = set(), []
    for n in sorted({it.prompt_len for it in items}):
        bucket, chunks = chunk_plan(n, buckets, budget)
        new = {(bucket, s, c) for s, c in chunks} - seen
        if new:
            seen |= new
            lengths.append(n)
    for k, n in enumerate(lengths):
        loop.submit(schedule.Item(serve.CHECK_INDEX + 100 + k, 0.0, n, 2))
    while loop.live:
        loop.step()
    loop.done.clear()
    loop.steps.clear()
    return lengths


# --- the check -----------------------------------------------------------------

def engine_rows(eng, model, seed, spec):
    """``serve.logit_rows`` beside a full batch: the same requests across
    the prefill buckets on the measured engine itself, the same two passes
    (row 0 comes from a second pass of one-token requests), but every other
    slot of the batch holds a request that is decoding meanwhile over a
    context of 0.5k-2k tokens, so that the rows compared were made by the
    kernels at the batch they are timed at (no token is dropped, so a
    request's logits do not depend on its neighbours). Returns ``(prompts,
    toks, rows, agree)``."""
    from paddle_tpu.inference.serving import SamplingParams

    vocab = model["vocab_size"]
    prompts = [schedule.token_ids(seed, serve.CHECK_INDEX + i, n, vocab)
               for i, n in enumerate(spec["prompt_lens"])]
    n_new = int(spec["new_tokens"])
    cap = int(eng.max_model_len)
    fill = []
    for i in range(eng.max_batch_size - len(prompts)):
        n = min(cap // 24 + (37 * i) % (cap // 8), cap // 2)
        ids = schedule.token_ids(seed, serve.CHECK_INDEX + 1000 + i, n, vocab)
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


def nearest_routing(row, pos, forward, held, top_k, tol, limit):
    """``{"error", "margin", "routed_otherwise"}`` of one logits ``row``
    against the reference's row at ``pos``, over the routings the reference
    could as well have taken there (``serve_mimo_v2.compare_rows``' search,
    over a ``forward`` of the caller's). ``forward(turns)`` gives ``(logits
    [S, V], {layer: corrected scores [S, E]})`` with the experts of
    ``turns`` ((layer, expert, was chosen), ...) turned at ``pos``. The row
    is first compared as the reference routes by itself; IF it reads over
    ``tol``, each held expert within ``limit`` of the choice's edge in some
    layer at ``pos`` is turned in turn, the search going on from the routing
    that reads nearest (later layers see another input and are read anew),
    at most ``MAX_OTHER_ROUTINGS`` forwards; the smallest reading is kept."""
    lg, sc = forward(())
    at = {layer: np.asarray(v[pos]) for layer, v in sc.items()}
    near = [g for v in at.values() for g, _, _ in uncertain(v, held, top_k, np.inf)]
    best = [reference.row_error(row, lg[pos]), ()]
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
            alt, alt_sc = forward(more)
            err = reference.row_error(row, alt[pos])
            if err < best[0]:
                best[:] = [err, more]
            # the nudged place reads as the choice was made there
            frontier.append((err, more, {
                la: np.asarray(v[pos]) + sum(
                    (-NUDGE if c else NUDGE) * (np.arange(v.shape[-1]) == ex)
                    for lb, ex, c in more if lb == la)
                for la, v in alt_sc.items()}))
    return {"error": best[0], "margin": min(near, default=math.inf),
            "routed_otherwise": [[layer, e] for layer, e, _ in best[1]]}


def _nudges(turns, pos, shape):
    out = {}
    for layer, e, was_in in turns:
        a = out.setdefault(layer, np.zeros(shape, np.float32))
        a[0, pos, e] = -NUDGE if was_in else NUDGE
    return out


def compare_rows(weights, model, prompts, toks, rows, spec, mtp_rows=None):
    """``{row: {"error", "margin", "routed_otherwise"}}`` for each of
    ``rows``, from plain forwards over prompt + output, a request at a time
    (``nearest_routing``); no row is left out. ``mtp_rows`` (``{position:
    logits row}`` of the prediction module over request ``spec
    ["mtp_prompt"]``, made from the reference's own hidden state) are
    compared the same way with the module's reference and come back under
    ``("mtp", position)``."""
    tol, limit = float(spec["tolerance"]), float(spec.get("margin_limit", 0.0))
    held, top_k = model["experts_held"], model["num_experts_per_tok"]
    shape = lambda ids: ids.shape + (model["n_routed_experts"],)  # noqa: E731
    out = {}
    for i, (p, t) in enumerate(zip(prompts, toks)):
        ids = np.concatenate([p, t]).astype(np.int32)[None]
        memo = {}

        def trunk(turns, pos):
            if turns not in memo:
                lg, sc = reference.logits(
                    weights, ids, model, experts_held=held, with_scores=True,
                    nudge=_nudges(turns, pos, shape(ids)))
                memo[turns] = np.asarray(lg)[0], {k: v[0] for k, v in sc.items()}
            return memo[turns]

        for (r, j) in sorted(k for k in rows if k[0] == i):
            pos = len(p) - 1 + j
            for key in [k for k in memo if k]:
                del memo[key]            # turns are a position's
            out[(r, j)] = nearest_routing(
                rows[(r, j)], pos, lambda turns: trunk(turns, pos), held,
                top_k, tol, limit)
    if mtp_rows:
        ids, hidden = mtp_inputs(weights, model, prompts, toks, spec)
        layer = model["num_hidden_layers"]

        def module(turns, pos):
            lg, sc = reference.mtp_logits(
                weights, hidden, ids[:, 1:], model, experts_held=held,
                with_scores=True,
                nudge=_nudges(turns, pos, shape(ids[:, 1:])).get(layer))
            return np.asarray(lg)[0], {layer: sc[0]}

        for pos, row in sorted(mtp_rows.items()):
            out[("mtp", pos)] = nearest_routing(
                row, pos, lambda turns: module(turns, pos), held, top_k, tol,
                limit)
    return out


def mtp_inputs(weights, model, prompts, toks, spec):
    """What the prediction module is checked on: prompt + output of request
    ``spec["mtp_prompt"]`` (``ids`` [1, S]) and the REFERENCE's hidden state
    over all but its last token ([1, S - 1, D], float32): the module's own
    arithmetic is what is compared, not the trunk's again."""
    i = int(spec.get("mtp_prompt", 0))
    ids = np.concatenate([prompts[i], toks[i]]).astype(np.int32)[None]
    _, hidden = reference.logits(weights, ids[:, :-1], model,
                                 experts_held=model["experts_held"],
                                 with_hidden=True)
    return ids, hidden


def mtp_rows(net, weights, model, prompts, toks, spec):
    """The program's prediction module (``mtp_logits``, outside the engine)
    over ``mtp_inputs``, in the dtype the model is served in: ``{position:
    logits row}`` at the last ``new_tokens`` positions."""
    import jax.numpy as jnp

    ids, hidden = mtp_inputs(weights, model, prompts, toks, spec)
    got = np.asarray(net.mtp_logits(
        jnp.asarray(hidden).astype(net.lm_head.weight._data.dtype),
        jnp.asarray(ids[:, 1:]))._data.astype(jnp.float32))
    last = ids.shape[1] - 2
    return {pos: got[0, pos]
            for pos in range(last - int(spec["new_tokens"]) + 1, last + 1)}


def check_logits(net, model, checked, spec):
    """``engine_rows``' rows (``checked``) and the prediction module's
    against the reference: every row has to stay under ``spec
    ["tolerance"]``, against the reference as it routes by itself or against
    one of its other routings within ``margin_limit``."""
    prompts, toks, rows, agree = checked
    weights = common.named_weights(net)
    finite = all(np.isfinite(row).all() for row in rows.values())
    got, expected = {}, len(prompts) * int(spec["new_tokens"])
    if finite:
        module = (mtp_rows(net, weights, model, prompts, toks, spec)
                  if model["num_nextn_predict_layers"] else {})
        expected += len(module)
        got = compare_rows(weights, model, prompts, toks, rows, spec, module)
    out = verdict(got, agree and finite, expected, spec)
    out["worst_mtp"] = max((v["error"] for k, v in got.items()
                            if k[0] == "mtp"), default=None)
    return out


# --- the loop ------------------------------------------------------------------

def _counters(m0, m1):
    return {k: m1[k] - m0[k] for k in COUNTERS}


def _work(record, config, model):
    """What the traced decode steps had to do: the cached rows the engine
    counted the decode kernel walking, the weights by the experts its
    routing hit."""
    if record["device_kind"] not in peaks_mod.PEAKS:   # the CPU rehearsal
        return {}
    pk = peaks_mod.peaks_for(record["device_kind"])
    steps = record.get("traced_steps") or []
    counted = record.get("traced_counters") or {}
    decode_steps = sum(1 for s in steps if s[4])
    least, bound = work_mod.latent_decode_seconds(
        config, counted.get("mla_latent_tokens_read_decode", 0), pk)
    return {
        "latent_decode_s": least, "latent_decode_bound": bound,
        # the same rows from the loop's own books, for the record
        "latent_rows_by_steps": sum(s[5] for s in steps)
        * model["num_hidden_layers"],
        "weight_stream_s": work_mod.weight_stream_bytes(
            config, decode_steps, counted.get("moe_experts_hit_decode", 0),
            model["n_routed_experts"]) / pk["hbm_bytes_per_s"],
    }


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
        record = {"kind": "serve_joyai_flash", "loop": traffic["loop"],
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
    # the pools go before the reference comes (a float32 head alone is 1 GB)
    del eng, loop
    gc.collect()
    check = check_logits(net, model, checked, config["check"])
    print(f"[check] {check}", flush=True)
    record["correct"] = (bool(check["ok"])
                         and record["compiles_in_window"] == 0)
    record["check"] = check
    return record
