"""Runner of kind "serve_lfm2": LFM2-MoE (LFM2-8B-A1B) through ``LLMEngine``
under the closed loop of the "serve" runner.

What is generic comes from ``runners.serve``, ``runners.common``,
``runners.serve_joyai_flash`` (the warm-up that meets every (staging length,
chunk offset, chunk rung) of the schedule) and ``runners.serve_nemotron_h``
(the check's rows taken beside a full batch WITH how the engine routed, the
verdict's two limits, the tails' share of the cache); what is this model's
is here: the model from the seed, drawn in parts as
``runners.serve_qwen3_next`` draws it, its sizes, the comparison with its
reference ROUTED AS THE ENGINE ROUTED (a row reads its context through the
convolution tails and two attention layers, and a token routed the other way
where two scores all but tie moves it by more than the rounding), the work
of the traced steps, and the engine's counters read around the TRACED steps
alone and around the window (``serve._closed`` and ``serve._trace_phase``
take neither, so their lines are repeated below as in the runners before:
PERF.md section 7 (f)).
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import peaks_lfm2 as work_mod
from ..harness import reference_lfm2 as reference
from ..harness import schedule, stats
from . import common, serve
from .serve_joyai_flash import warm_shapes
from .serve_nemotron_h import PAD_TO, _cache_shares, engine_rows, verdict
from .serve_qwen3_next import DRAW_PART

#: the source's keys the model is built from (the file's top level)
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "conv_L_cache", "conv_bias", "norm_eps",
    "rope_theta", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")
_MODEL_COUNTERS = ("conv_tail_rows_updated", "conv_tokens_convolved",
                   "attn_tokens_read", "moe_pairs_routed_here",
                   "moe_experts_hit", "moe_layer_steps", "moe_weight_passes")
#: the engine's counters the record keeps, as differences over a stretch
COUNTERS = ("host_syncs", "tokens_out", "prefills", "prefill_chunks",
            "evictions", "admitted", "finished", "kv_live_byte_steps",
            "state_byte_steps") + tuple(
    n + tail for n in _MODEL_COUNTERS for tail in ("", "_decode", "_prefill"))


def model_sizes(config):
    """The model as it is run: the source's keys, every expert held."""
    model = {k: config[k] for k in MODEL_KEYS}
    if config.get("model_type") != "lfm2_moe" or config.get("conv_bias") \
            or not config.get("use_expert_bias"):
        raise ValueError(
            "models/lfm2_moe.py computes lfm2_moe: short convolutions with "
            "no bias, experts chosen with an expert bias")
    # the rotary tables are as long as what the engine serves
    model["max_position_embeddings"] = int(config["engine"]["max_model_len"])
    return model


def prefill_attending(model):
    """The record's ``attending``, the layers a chunk attends in
    (``prefill_spans.prefill_attention_roofline``): the full-attention layers
    of the kept ones, each query head 64 wide in q/k and in v, causal. The
    short convolutions attend nowhere."""
    d = work_mod.head_dim(model)
    return [(work_mod.attention_layers(model), model["num_attention_heads"],
             d, d, None)]


def build_model(model, seed, dtype="bfloat16"):
    """``Lfm2MoeForCausalLM`` with every weight drawn on the device from the
    seed, the constructor traced once into one program and compiled and run
    in parts of ``DRAW_PART`` outputs (``serve_qwen3_next.build_model``'s
    method: the cell's ~830 arrays in one program would take the compile's
    host memory far past its parts')."""
    import jax
    from jax._src.interpreters import partial_eval as pe
    from jax.extend import core as jcore

    from paddle_tpu.core import rng
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM

    cfg = Lfm2MoeConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                           for k, v in model.items()})
    gen = rng.default_generator()
    box = {}

    def make(key):
        gen.manual_seed(0)
        with gen.traced_base(key):
            net = Lfm2MoeForCausalLM(cfg)
            if dtype == "bfloat16":
                net.bfloat16()
        box["net"] = net
        return [t._data for t in common._leaves(net)]

    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    whole = jax.make_jaxpr(make)(key)
    n = len(whole.out_avals)
    arrays = []
    for lo in range(0, n, DRAW_PART):
        part, used = pe.dce_jaxpr(
            whole.jaxpr, [lo <= i < lo + DRAW_PART for i in range(n)],
            instantiate=True)
        draw = jcore.jaxpr_as_fun(jcore.ClosedJaxpr(part, whole.consts))
        arrays += jax.jit(draw)(*([key] if used[0] else []))
    net = box["net"]
    for t, a in zip(common._leaves(net), arrays):
        t._data = a
    return net


# --- the check -----------------------------------------------------------------

def compare_rows(weights, model, prompts, passes):
    """Every row of ``engine_rows`` against the reference ROUTED AS THE
    ENGINE ROUTED (``serve_nemotron_h.compare_rows``' method over this
    model's expert layers, the ones after the dense). Returns ``({row:
    error}, {"pairs", "turned", "gap"})``."""
    top_k = model["num_experts_per_tok"]
    layers = reference.expert_layers(model)
    errors, routing = {}, {"pairs": 0, "turned": 0, "gap": 0.0}
    for i, p in enumerate(prompts):
        # the last token of a pass was computed by no step
        choices = [taken["choice"][i] for taken in passes]
        width = -(-max(c.shape[1] for c in choices) // PAD_TO) * PAD_TO
        ids = np.zeros((len(passes), width), np.int32)
        handed = np.tile(np.arange(top_k, dtype=np.int32),
                         (len(layers), len(passes), width, 1))
        for q, (taken, c) in enumerate(zip(passes, choices)):
            n = c.shape[1]
            ids[q, :n] = np.concatenate([p, taken["toks"][i]])[:n]
            handed[:, q, :n] = c
        lg, sc = reference.logits(
            weights, ids, model, with_scores=True,
            choice={b: handed[k] for k, b in enumerate(layers)})
        lg = np.asarray(lg)
        for q, (taken, c) in enumerate(zip(passes, choices)):
            n = c.shape[1]
            for k, b in enumerate(layers):
                turned, gap = reference.choice_gaps(sc[b][q, :n], c[k])
                routing["pairs"] += c[k].size
                routing["turned"] += turned
                routing["gap"] = max(routing["gap"], gap)
            for (r, j), row in taken["rows"].items():
                if r == i:
                    errors[(r, j)] = reference.row_error(
                        row, lg[q, len(p) - 1 + j])
    return errors, routing


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


def _work(record, config):
    """What the traced decode steps had to do: the live tokens the head-64
    decode kernel walked (the engine's count), the weights of the experts its
    routing hit, and the weights every step streams outside the two."""
    if record["device_kind"] not in peaks_mod.PEAKS:   # the CPU rehearsal
        return {}
    bw = peaks_mod.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    steps = record.get("traced_steps") or []
    counted = record.get("traced_counters") or {}
    decode_steps = sum(1 for s in steps if s[4])
    return {
        "h64_decode_s": work_mod.attention_decode_bytes(
            config, counted.get("attn_tokens_read_decode", 0)) / bw,
        # the same tokens from the loop's own books, for the record
        "attn_tokens_by_steps": sum(s[5] for s in steps)
        * work_mod.attention_layers(config),
        "expert_ffn_s": work_mod.expert_bytes(config) * counted.get(
            "moe_experts_hit_decode", 0) / bw,
        "weight_stream_s": decode_steps * work_mod.fixed_stream_bytes(
            config) / bw,
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
            record, config):
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
    record["work"] = _work(record, config)
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
        record = {"kind": "serve_lfm2", "loop": traffic["loop"],
                  "model": {k: v for k, v in model.items()
                            if not isinstance(v, list)},
                  "attending": prefill_attending(model),
                  "device_kind": devs[0].device_kind, "trace": None}
        if trace:
            loop.span = common.step_span
        _closed(loop, items, traffic, seconds, trace, out_dir, t_start,
                counter, record, config)
        # the serving peak: what a deployment holds, before the reference's
        # float32 forwards come
        record["device"] = common.device_record(devs, chips)
    finally:
        eng.close()
    # the pools and the tails go before the reference comes
    del eng, loop
    gc.collect()
    check = check_logits(net, model, checked, config["check"])
    print(f"[check] {check}", flush=True)
    record["correct"] = (bool(check["ok"])
                         and record["compiles_in_window"] == 0)
    record["check"] = check
    return record
