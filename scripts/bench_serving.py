"""Serving engine A/B harness (ISSUE 7 tentpole, PERF.md discipline).

Replays ONE seeded Poisson multi-tenant request stream (exponential
inter-arrival times, varied prompt lengths and generation budgets) through
two arms over the SAME model weights:

  naive    batch-of-one FIFO loop: each request waits for its arrival
           time, then runs ``model.generate`` alone — the pre-engine
           serving story (one request on the chip at a time)
  engine   ``inference.serving.LLMEngine``: continuous batching over the
           paged KV pool — arrivals are admitted mid-decode at token
           granularity, up to ``max_batch_size`` requests share every
           fixed-shape decode step

Both arms decode greedily, so outputs must be BIT-EXACT across arms
(asserted in the summary) — batching changes WHO shares a step, never the
math. Compiles are warmed before the timed window in both arms by
replaying the stream's shape set once (the engine acceptance is ZERO
decode-graph compiles inside the timed window, proven from
``paddle.jit.cache_stats()``), so the measured effect is steady-state
batching, not compile amortization.

Metrics per arm: generated tokens/s over the makespan, and per-request
latency (finish − arrival) p50/p99.

ISSUE 11 adds three more seeded A/Bs over the same harness:

  --workload shared-prefix   multi-tenant stream with a common system
           prompt: prefix-cache sharing arm vs charge-everything arm,
           bit-exact outputs asserted, effective (prompt+generated)
           tokens/s and prefix-hit ratio reported
  --workload chunked         long-prompt mix: chunked prefill (budgeted
           tokens/step) vs whole-prompt prefill — decode ITL p99 is the
           engine-owned histogram, the chunk budget bounds it
  --workload spec            speculative decoding arm (draft proposes k,
           one multi-query verify scores k+1) vs plain decode —
           bit-exact greedy asserted, accept ratio reported from
           ``LLMEngine.metrics()``

ISSUE 20 adds the integrity-sentinel overhead A/B:

  --workload audit           ONE warmed subprocess fleet, the same burst
           with ``Router(audit_fraction=0.1)`` off vs on — audit
           replays are batch-tier background work on a different
           replica, so latency-tier TTFT p99 must stay within ~1.1x
           and outputs bit-exact vs the in-process greedy reference

The harness (``default_sizing`` / ``request_stream`` / ``run_naive`` /
``run_engine`` / ``run_shared_prefix_ab`` / ``run_chunked_ab`` /
``run_spec_ab``) is also imported by bench.py's
``serving`` workload and tests/test_serving.py's acceptance tests so the
bench line, the probe and the test can never drift apart.

Usage:
  python scripts/bench_serving.py [--workload poisson|shared-prefix|
      chunked|spec] [--requests 16] [--rate 40]
      [--max-batch 4] [--seed 0] [--tiny]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def default_sizing(tiny):
    """(cfg, stream kwargs, engine kwargs) shared by this probe, bench.py's
    ``serving`` workload and the acceptance test."""
    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:  # CI / CPU smoke
        cfg = llama_tiny()
        stream = dict(n=16, rate=150.0, min_prompt=4, max_prompt=24,
                      min_new=12, max_new=24)
        engine = dict(num_blocks=160, block_size=8, max_batch_size=8,
                      max_prefills_per_step=2)
    else:
        cfg = llama_small()
        stream = dict(n=64, rate=100.0, min_prompt=16, max_prompt=256,
                      min_new=32, max_new=128)
        engine = dict(num_blocks=512, block_size=16, max_batch_size=8)
    return cfg, stream, engine


@dataclasses.dataclass
class _Req:
    arrival: float
    prompt: np.ndarray
    max_new: int


def request_stream(cfg, *, n, rate, min_prompt, max_prompt, min_new,
                   max_new, seed=0):
    """Seeded Poisson request stream: arrival offsets are cumulative
    exponential inter-arrival gaps at ``rate`` req/s; prompt lengths and
    generation budgets are uniform over their ranges."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(gaps)
    out = []
    for t in arrivals:
        plen = int(rng.randint(min_prompt, max_prompt + 1))
        prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
        out.append(_Req(float(t), prompt, int(rng.randint(min_new,
                                                          max_new + 1))))
    return out


def shared_prefix_stream(cfg, *, n, rate, prefix_len, min_suffix,
                         max_suffix, min_new, max_new, seed=0,
                         prefix_seed=None):
    """Seeded multi-tenant stream: every request shares ONE system-prompt
    prefix (drawn from ``prefix_seed``, default ``seed``) followed by a
    unique per-request suffix; Poisson arrivals at ``rate`` req/s. This is
    the production shape prefix caching targets — N tenants of one
    application, one template, distinct questions."""
    rng = np.random.RandomState(seed)
    prefix = np.random.RandomState(
        seed if prefix_seed is None else prefix_seed).randint(
        0, cfg.vocab_size, prefix_len).astype(np.int32)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    out = []
    for t in arrivals:
        slen = int(rng.randint(min_suffix, max_suffix + 1))
        suffix = rng.randint(0, cfg.vocab_size, slen).astype(np.int32)
        out.append(_Req(float(t), np.concatenate([prefix, suffix]),
                        int(rng.randint(min_new, max_new + 1))))
    return out


def _latency_stats(latencies):
    arr = np.asarray(sorted(latencies))
    return {
        "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 2),
    }


def run_naive(model, stream):
    """Batch-of-one FIFO: each request runs ``model.generate`` alone (the
    static-cache path — already O(1) compiles per capacity bucket — so the
    A/B isolates BATCHING, not the old concat-per-token cliff)."""
    import paddle_tpu as paddle

    outs, lat = [], []
    t0 = time.perf_counter()
    for req in stream:
        now = time.perf_counter() - t0
        if now < req.arrival:
            time.sleep(req.arrival - now)
        ids = paddle.to_tensor(req.prompt[None])
        out = model.generate(ids, max_new_tokens=req.max_new)
        outs.append(np.asarray(out.numpy()[0]))
        lat.append((time.perf_counter() - t0) - req.arrival)
    wall = time.perf_counter() - t0
    gen_tokens = sum(r.max_new for r in stream)
    return dict(outputs=outs, wall_s=round(wall, 4),
                tokens_per_sec=round(gen_tokens / wall, 1),
                gen_tokens=gen_tokens, **_latency_stats(lat))


def run_engine(model, stream, engine=None, **engine_kwargs):
    """Continuous batching through ``LLMEngine``; admission respects the
    same arrival clock the naive arm slept on. Pass a warmed ``engine``
    (see :func:`warm_arms`) so the timed window starts with its prefill
    and decode executables already built.

    Serving telemetry is ENGINE-OWNED (ISSUE 10): eviction/admission
    counts and the TTFT / inter-token percentiles come from
    ``LLMEngine.metrics()`` — the observability registry — not from bench
    clocks or engine privates. ``reset_metrics()`` at window start keeps
    warm-phase observations out of the reported numbers."""
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    from paddle_tpu.jit import cache_stats

    eng = engine if engine is not None else LLMEngine(model, **engine_kwargs)
    steps0 = eng.stats_extra["steps"]
    # window-local serving metrics + high-water: warm-phase pressure and
    # latencies must not be attributed to the timed run
    eng.reset_metrics()
    eng.reset_block_high_water()
    try:
        # the zero-compiles-in-window acceptance tracks the decode graph
        jit_name = eng._decode_name
        row = cache_stats().get(jit_name) or {}
        compiles0 = row.get("compiles", 0)
        lat, rids = [], []
        finish_t = {}
        i = 0
        t0 = time.perf_counter()
        while i < len(stream) or eng.has_work():
            now = time.perf_counter() - t0
            while i < len(stream) and stream[i].arrival <= now:
                rids.append(eng.add_request(
                    stream[i].prompt,
                    SamplingParams(max_new_tokens=stream[i].max_new)))
                i += 1
            if not eng.has_work():
                time.sleep(max(0.0, stream[i].arrival - now))
                continue
            for out in eng.step():
                if out.finished:
                    finish_t[out.rid] = time.perf_counter() - t0
        wall = time.perf_counter() - t0
        for req, rid in zip(stream, rids):
            lat.append(finish_t[rid] - req.arrival)
        outs = [eng.output_tokens(rid) for rid in rids]
        row = cache_stats().get(jit_name) or {}
        stats = eng.stats()
        em = eng.metrics()
    finally:
        if engine is None:
            eng.close()
    gen_tokens = sum(r.max_new for r in stream)

    def _r(v):
        return round(v, 2) if v is not None else None

    prompt_tokens = sum(len(r.prompt) for r in stream)
    return dict(outputs=outs, wall_s=round(wall, 4),
                tokens_per_sec=round(gen_tokens / wall, 1),
                # effective throughput counts PROMPT tokens served too —
                # the number prefix sharing moves (shared prefixes are
                # served without recomputing them)
                effective_tokens_per_sec=round(
                    (gen_tokens + prompt_tokens) / wall, 1),
                gen_tokens=gen_tokens, prompt_tokens=prompt_tokens,
                decode_compiles_in_window=row.get("compiles", 0) - compiles0,
                engine_steps=stats["steps"] - steps0,
                evictions=em["evictions"],
                admitted=em["admitted"],
                queued_on_exhaustion=em["queued_on_exhaustion"],
                blocks_high_water=stats["blocks_high_water"],
                prefix_blocks_reused=em["prefix_blocks_reused"],
                prefill_chunks=em["prefill_chunks"],
                spec_accept_ratio=(round(em["spec_accept_ratio"], 4)
                                   if em["spec_accept_ratio"] is not None
                                   else None),
                kv_spills=em["kv_spills"],
                kv_revives=em["kv_revives"],
                kv_host_evictions=em["kv_host_evictions"],
                prefix_store_loaded=em["prefix_store_loaded"],
                host_syncs=em["host_syncs"],
                decode_fetch_bytes=em["decode_fetch_bytes"],
                ttft_p50_ms=_r(em["ttft_ms"]["p50"]),
                ttft_p99_ms=_r(em["ttft_ms"]["p99"]),
                itl_p50_ms=_r(em["itl_ms"]["p50"]),
                itl_p99_ms=_r(em["itl_ms"]["p99"]),
                **_latency_stats(lat))


def warm_arms(model, stream, **engine_kwargs):
    """Compile every shape both arms will hit — the engine's prefill
    buckets + its decode graph, and the naive arm's per-capacity-bucket
    generate executables — untimed. Returns the warmed engine; the timed
    window must run on THE SAME instance (executables live on the
    instance's jit wrappers)."""
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    import paddle_tpu as paddle

    eng = LLMEngine(model, **engine_kwargs)
    for req in stream:
        eng.add_request(req.prompt,
                        SamplingParams(max_new_tokens=req.max_new))
    for _ in eng.stream():
        pass
    caps = set()
    for req in stream:
        b = model.DECODE_CAPACITY_BUCKET
        cap = -(-(len(req.prompt) + req.max_new) // b) * b
        if (len(req.prompt), cap) not in caps:
            caps.add((len(req.prompt), cap))
            model.generate(paddle.to_tensor(req.prompt[None]),
                           max_new_tokens=req.max_new)
    return eng


def run_ab(cfg=None, stream_kwargs=None, engine_kwargs=None, *, tiny=True,
           seed=0, repeat=1):
    """Full A/B: build model, warm, run both arms, cross-check outputs.
    ``repeat`` replays the timed window N times per arm and reports each
    arm's best-throughput run (min-of-N against transient host load)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    if cfg is None:
        cfg, stream_kwargs, engine_kwargs = default_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    eng = warm_arms(model, stream, **engine_kwargs)
    naive_runs, engine_runs = [], []
    try:
        for _ in range(max(int(repeat), 1)):
            naive_runs.append(run_naive(model, stream))
            engine_runs.append(run_engine(model, stream, engine=eng))
    finally:
        eng.close()
    naive = max(naive_runs, key=lambda r: r["tokens_per_sec"])
    engine = max(engine_runs, key=lambda r: r["tokens_per_sec"])
    bit_exact = all(
        len(naive_runs[0]["outputs"]) == len(r["outputs"]) and all(
            a.shape == b.shape and (a == b).all()
            for a, b in zip(naive_runs[0]["outputs"], r["outputs"]))
        for r in naive_runs + engine_runs)
    return dict(
        naive={k: v for k, v in naive.items() if k != "outputs"},
        engine={k: v for k, v in engine.items() if k != "outputs"},
        speedup=round(engine["tokens_per_sec"] / naive["tokens_per_sec"], 3),
        bit_exact=bool(bit_exact),
        repeats=max(int(repeat), 1),
        num_requests=len(stream),
        max_batch_size=engine_kwargs["max_batch_size"],
    )


def _warm_engine(model, stream, **engine_kwargs):
    """Compile every shape one engine arm will hit by replaying a
    DISJOINT warm stream (same shape set, different token content and
    prefix identity) — compiles warm, the prefix cache does NOT: the
    timed window's leader request genuinely prefills its prefix once."""
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams

    eng = LLMEngine(model, **engine_kwargs)
    for req in stream:
        eng.add_request(req.prompt, SamplingParams(max_new_tokens=req.max_new))
    for _ in eng.stream():
        pass
    return eng


def _bit_exact(a_outs, b_outs):
    return (len(a_outs) == len(b_outs) and all(
        x.shape == y.shape and (x == y).all()
        for x, y in zip(a_outs, b_outs)))


def shared_prefix_sizing(tiny):
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        # a deeper/wider tiny so chunk COMPUTE (what sharing avoids)
        # dominates the per-step dispatch overhead even on a loaded CI box
        cfg = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4)
        stream = dict(n=12, rate=400.0, prefix_len=192, min_suffix=2,
                      max_suffix=6, min_new=1, max_new=2)
        engine = dict(num_blocks=320, block_size=8, max_batch_size=8,
                      max_prefills_per_step=2)
    else:
        cfg = llama_small()
        stream = dict(n=48, rate=200.0, prefix_len=512, min_suffix=16,
                      max_suffix=64, min_new=16, max_new=48)
        engine = dict(num_blocks=1024, block_size=16, max_batch_size=8,
                      max_prefills_per_step=2)
    return cfg, stream, engine


def run_shared_prefix_ab(tiny=True, seed=0, repeat=1):
    """Prefix-cache A/B (ISSUE 11): ONE seeded shared-prefix multi-tenant
    stream through two engine arms over the same weights — sharing OFF
    (every request prefills its whole prompt) vs sharing ON (followers
    acquire the leader's full prefix blocks and prefill only their
    suffix). Greedy outputs must be bit-exact across arms; the win is
    reported as EFFECTIVE (prompt+generated) tokens/s, since prompt
    tokens served from shared blocks are exactly the work avoided.
    ``repeat`` replays the window N times per arm and reports each arm's
    best-throughput run (min-of-N against transient host load)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, engine_kwargs = shared_prefix_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = shared_prefix_stream(cfg, seed=seed, **stream_kwargs)
    warm = shared_prefix_stream(cfg, seed=seed + 1, prefix_seed=seed + 2,
                                **stream_kwargs)
    engines = {}
    runs = {"no_sharing": [], "sharing": []}
    try:
        for arm, share in (("no_sharing", False), ("sharing", True)):
            engines[arm] = _warm_engine(model, warm,
                                        enable_prefix_cache=share,
                                        **engine_kwargs)
        for _ in range(max(int(repeat), 1)):
            for arm in ("no_sharing", "sharing"):
                runs[arm].append(
                    run_engine(model, stream, engine=engines[arm]))
    finally:
        for eng in engines.values():
            eng.close()
    res = {arm: max(rs, key=lambda r: r["effective_tokens_per_sec"])
           for arm, rs in runs.items()}
    bit_exact = all(
        _bit_exact(runs["no_sharing"][0]["outputs"], r["outputs"])
        for rs in runs.values() for r in rs)
    bs = engine_kwargs["block_size"]
    full_blocks = sum(len(r.prompt) // bs for r in stream)
    reused = res["sharing"]["prefix_blocks_reused"]
    out = dict(
        no_sharing={k: v for k, v in res["no_sharing"].items()
                    if k != "outputs"},
        sharing={k: v for k, v in res["sharing"].items()
                 if k != "outputs"},
        speedup=round(res["sharing"]["effective_tokens_per_sec"]
                      / res["no_sharing"]["effective_tokens_per_sec"], 3),
        prefix_hit_ratio=round(reused / max(full_blocks, 1), 3),
        repeats=max(int(repeat), 1),
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
        prefix_len=stream_kwargs["prefix_len"],
    )
    return out


def chunked_sizing(tiny):
    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        import dataclasses as _dc

        # long-prompt mix: a background of short decode-heavy requests
        # with long prompts landing mid-stream to stall them. The
        # positions cap is raised so the long prompts are long enough
        # that an unchunked prefill stall dwarfs host-load noise.
        cfg = _dc.replace(llama_tiny(), max_position_embeddings=1024)
        stream = dict(n=12, rate=300.0, min_prompt=4, max_prompt=12,
                      min_new=24, max_new=40)
        long_prompts = dict(every=3, length=768)
        engine = dict(num_blocks=512, block_size=8, max_batch_size=8,
                      max_prefills_per_step=1)
        budget = 128
    else:
        cfg = llama_small()
        stream = dict(n=32, rate=150.0, min_prompt=16, max_prompt=64,
                      min_new=64, max_new=128)
        long_prompts = dict(every=4, length=1024)
        engine = dict(num_blocks=1024, block_size=16, max_batch_size=8,
                      max_prefills_per_step=1)
        budget = 128
    return cfg, stream, long_prompts, engine, budget


def long_prompt_stream(cfg, stream_kwargs, long_prompts, seed=0):
    """Poisson mix where every ``every``-th request carries a
    ``length``-token prompt — the workload whose unchunked prefill stalls
    every in-flight token stream."""
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    rng = np.random.RandomState(seed + 7)
    for i in range(0, len(stream), long_prompts["every"]):
        stream[i] = _Req(stream[i].arrival,
                         rng.randint(0, cfg.vocab_size,
                                     long_prompts["length"]).astype(np.int32),
                         stream[i].max_new)
    return stream


def run_chunked_ab(tiny=True, seed=0, repeat=1):
    """Chunked-prefill A/B (ISSUE 11): the same long-prompt mix through an
    unchunked arm (whole prompts in one step — in-flight decodes stall for
    the full prefill) and a chunked arm (``max_prefill_tokens_per_step``
    budget interleaves prefill chunks with decode steps). Decode ITL p99
    is the ENGINE-OWNED histogram (``serving_itl_ms``), so the comparison
    measures exactly the stall the chunk budget bounds. Outputs must be
    bit-exact across arms. ``repeat`` replays the window N times per arm
    and reports each arm's best-throughput run — the standard min-of-N
    defense against transient host-load spikes polluting one arm."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, long_prompts, engine_kwargs, budget = \
        chunked_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = long_prompt_stream(cfg, stream_kwargs, long_prompts, seed=seed)
    warm = long_prompt_stream(cfg, stream_kwargs, long_prompts,
                              seed=seed + 1)
    engines = {}
    runs = {"unchunked": [], "chunked": []}
    try:
        for arm, b in (("unchunked", None), ("chunked", budget)):
            engines[arm] = _warm_engine(
                model, warm, max_prefill_tokens_per_step=b, **engine_kwargs)
        for _ in range(max(int(repeat), 1)):
            for arm in ("unchunked", "chunked"):
                runs[arm].append(
                    run_engine(model, stream, engine=engines[arm]))
    finally:
        for eng in engines.values():
            eng.close()
    res = {arm: max(rs, key=lambda r: r["tokens_per_sec"])
           for arm, rs in runs.items()}
    # each arm's cleanest (least load-polluted) latency observation: noise
    # only ever INFLATES a p99, so per-arm min across repeats is the
    # honest structural number
    itl = {arm: min(r["itl_p99_ms"] for r in rs if r["itl_p99_ms"])
           for arm, rs in runs.items()}
    bit_exact = all(
        _bit_exact(runs["unchunked"][0]["outputs"], r["outputs"])
        for rs in runs.values() for r in rs)
    return dict(
        unchunked={k: v for k, v in res["unchunked"].items()
                   if k != "outputs"},
        chunked={k: v for k, v in res["chunked"].items()
                 if k != "outputs"},
        itl_p99_ms={"unchunked": itl["unchunked"],
                    "chunked": itl["chunked"]},
        itl_p99_ratio=round(itl["chunked"] / max(itl["unchunked"], 1e-9),
                            3),
        tokens_per_sec_ratio=round(
            res["chunked"]["tokens_per_sec"]
            / res["unchunked"]["tokens_per_sec"], 3),
        chunk_budget=budget,
        repeats=max(int(repeat), 1),
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
    )


def run_spec_ab(tiny=True, seed=0, spec_tokens=3, draft="self"):
    """Speculative-decoding A/B (ISSUE 11): the same Poisson stream
    through a plain greedy arm and a speculative arm (draft proposes
    ``spec_tokens``, one multi-query verify scores them all). Outputs must
    be bit-exact — speculation changes WHEN tokens are produced, never
    WHICH. ``draft='self'`` uses the target model as its own draft
    (accept ratio 1.0 — the machinery's upper bound; a production draft
    is a distilled smaller llama, which only changes the ratio; with
    ``draft='tiny'`` a one-layer draft diverges, so the fused ragged
    catch-up of ISSUE 16 runs)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, engine_kwargs = default_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if draft == "self":
        draft_model = model
    else:
        import dataclasses as _dc

        paddle.seed(seed + 13)
        draft_model = LlamaForCausalLM(
            _dc.replace(cfg, num_hidden_layers=1))
        draft_model.eval()
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    warm = request_stream(cfg, seed=seed + 1, **stream_kwargs)
    res = {}
    for arm, dm in (("plain", None), ("spec", draft_model)):
        kw = dict(engine_kwargs)
        if dm is not None:
            kw.update(draft_model=dm, spec_tokens=spec_tokens)
        eng = _warm_engine(model, warm, **kw)
        try:
            res[arm] = run_engine(model, stream, engine=eng)
        finally:
            eng.close()
    bit_exact = _bit_exact(res["plain"]["outputs"], res["spec"]["outputs"])
    return dict(
        plain={k: v for k, v in res["plain"].items() if k != "outputs"},
        spec={k: v for k, v in res["spec"].items() if k != "outputs"},
        speedup=round(res["spec"]["tokens_per_sec"]
                      / res["plain"]["tokens_per_sec"], 3),
        spec_accept_ratio=res["spec"]["spec_accept_ratio"],
        spec_tokens=spec_tokens,
        draft=draft,
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
    )


def quantized_sizing(tiny):
    """Sizing for the int8-KV capacity A/B (ISSUE 14): the POOL BYTE
    BUDGET is the controlled variable — the fp32 arm gets ``num_blocks``
    blocks in the model dtype, the int8 arm gets however many
    code+scale blocks fit in the SAME bytes (~3.7x at D=64). The burst
    is sized so the fp32 pool saturates (queued admissions / evictions)
    while the quantized pool holds everything resident — the capacity
    win continuous batching converts into throughput."""
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        cfg = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4)
        stream = dict(n=16, rate=1000.0, min_prompt=24, max_prompt=48,
                      min_new=8, max_new=16)
        engine = dict(num_blocks=48, block_size=8, max_batch_size=8,
                      max_prefills_per_step=2)
    else:
        cfg = llama_small()
        stream = dict(n=48, rate=500.0, min_prompt=64, max_prompt=256,
                      min_new=32, max_new=64)
        engine = dict(num_blocks=192, block_size=16, max_batch_size=8,
                      max_prefills_per_step=2)
    return cfg, stream, engine


def quantized_pool_blocks(cfg, engine_kwargs):
    """Blocks the int8 arm gets for the fp32 arm's pool byte budget
    (shared helper: the bench line, the acceptance test and the capacity
    claim all derive from the same arithmetic in
    ``kv_cache.kv_pool_bytes_per_block``)."""
    from paddle_tpu.inference.serving import kv_pool_bytes_per_block

    bs = engine_kwargs["block_size"]
    fp = kv_pool_bytes_per_block(bs, cfg.num_key_value_heads,
                                 cfg.head_dim, kv_dtype=None)
    q8 = kv_pool_bytes_per_block(bs, cfg.num_key_value_heads,
                                 cfg.head_dim, kv_dtype="int8")
    return int(engine_kwargs["num_blocks"] * fp // q8)


def run_quantized_ab(tiny=True, seed=0, repeat=1):
    """Quantized-serving A/B (ISSUE 14 acceptance): ONE seeded Poisson
    burst through an fp32-KV engine and an int8-KV engine holding the
    SAME pool byte budget (so the int8 arm simply has ~3.7x the blocks).
    Reports per-arm tokens/s, saturation telemetry (queued admissions,
    evictions, block high-water), the static ``capacity_ratio``
    (usable int8 blocks / usable fp32 blocks at equal bytes — the >=1.5x
    acceptance number), and the quantized arm's run-to-run greedy
    determinism (the int8 write/dequant path is a pure per-row function,
    so two runs must produce IDENTICAL token ids — asserted). Token
    agreement vs the fp32 arm is reported as quality telemetry; the
    bounded-logit-delta contract is asserted in the slow tier against
    the dense fp32 forward."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, engine_kwargs = quantized_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    warm = request_stream(cfg, seed=seed + 1, **stream_kwargs)
    q_blocks = quantized_pool_blocks(cfg, engine_kwargs)
    capacity_ratio = (q_blocks - 1) / (engine_kwargs["num_blocks"] - 1)
    arms = {
        "fp32": dict(engine_kwargs),
        "int8": dict(engine_kwargs, num_blocks=q_blocks,
                     kv_dtype="int8"),
    }
    engines, runs = {}, {"fp32": [], "int8": []}
    try:
        for arm, kw in arms.items():
            engines[arm] = _warm_engine(model, warm, **kw)
        for _ in range(max(int(repeat), 1)):
            for arm in ("fp32", "int8"):
                runs[arm].append(
                    run_engine(model, stream, engine=engines[arm]))
        # determinism: replay the identical window on the int8 arm —
        # greedy token ids must be IDENTICAL run to run
        rerun = run_engine(model, stream, engine=engines["int8"])
        em_q = engines["int8"].metrics()
    finally:
        for eng in engines.values():
            eng.close()
    deterministic = _bit_exact(runs["int8"][0]["outputs"],
                               rerun["outputs"])
    res = {arm: max(rs, key=lambda r: r["tokens_per_sec"])
           for arm, rs in runs.items()}
    fp_out = runs["fp32"][0]["outputs"]
    q_out = runs["int8"][0]["outputs"]
    gen = [(a[len(r.prompt):], b[len(r.prompt):])
           for a, b, r in zip(fp_out, q_out, stream)]
    agree = float(np.mean([np.mean(a == b) for a, b in gen]))
    return dict(
        fp32={k: v for k, v in res["fp32"].items() if k != "outputs"},
        int8={k: v for k, v in res["int8"].items() if k != "outputs"},
        capacity_ratio=round(capacity_ratio, 3),
        pool_blocks_fp32=engine_kwargs["num_blocks"],
        pool_blocks_int8=q_blocks,
        kv_bytes_saved=em_q["kv_bytes_saved"],
        quantized_blocks_in_use_last=em_q["quantized_blocks_in_use"],
        deterministic=bool(deterministic),
        token_agreement_vs_fp32=round(agree, 4),
        tokens_per_sec_ratio=round(
            res["int8"]["tokens_per_sec"]
            / max(res["fp32"]["tokens_per_sec"], 1e-9), 3),
        repeats=max(int(repeat), 1),
        num_requests=len(stream),
    )


def tiering_sizing(tiny):
    """Sizing for the KV-tiering A/B (ISSUE 16): the live SESSION WORKING
    SET — distinct long per-session prefixes revisited round-robin — is
    deliberately larger than the device pool, so by the time a session
    comes back its prefix blocks have been reclaimed. The recompute arm
    re-prefills them from scratch; the tiered arm revives them from host
    RAM. The deeper/wider tiny makes prefill COMPUTE (what revival
    avoids) dominate dispatch overhead — the shared-prefix-sizing
    trick."""
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        cfg = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4,
                          max_position_embeddings=1024)
        sessions = dict(n_sessions=6, visits=2, rate=400.0,
                        prefix_len=512, min_suffix=2, max_suffix=6,
                        min_new=1, max_new=2)
        # 6 sessions x 32 prefix blocks = 192 blocks of working set
        # against a 72-block pool (holds ~2 sessions): every round-2
        # visit finds its prefix reclaimed. At 512 prefix tokens the
        # recompute arm re-pays a real prefill; the tiered arm pays a
        # host->device page copy
        engine = dict(num_blocks=72, block_size=16, max_batch_size=2,
                      max_prefills_per_step=1)
        host_blocks = 512
        resident_blocks = 512
    else:
        cfg = llama_small()
        sessions = dict(n_sessions=8, visits=2, rate=200.0,
                        prefix_len=512, min_suffix=16, max_suffix=48,
                        min_new=8, max_new=16)
        engine = dict(num_blocks=192, block_size=16, max_batch_size=2,
                      max_prefills_per_step=1)
        host_blocks = 1024
        resident_blocks = 1024
    return cfg, sessions, engine, host_blocks, resident_blocks


def session_stream(cfg, *, n_sessions, visits, rate, prefix_len,
                   min_suffix, max_suffix, min_new, max_new, seed=0,
                   prefix_seed=None):
    """Seeded multi-session stream: ``n_sessions`` distinct long
    prefixes (per-session conversation state), revisited round-robin
    ``visits`` times with a fresh short suffix per visit — the
    more-live-sessions-than-HBM shape KV tiering targets."""
    rng = np.random.RandomState(seed)
    prng = np.random.RandomState(
        seed + 101 if prefix_seed is None else prefix_seed)
    prefixes = [prng.randint(0, cfg.vocab_size, prefix_len).astype(np.int32)
                for _ in range(n_sessions)]
    arrivals = np.cumsum(
        rng.exponential(1.0 / rate, size=n_sessions * visits))
    out, i = [], 0
    for _ in range(visits):
        for s in range(n_sessions):
            slen = int(rng.randint(min_suffix, max_suffix + 1))
            suffix = rng.randint(0, cfg.vocab_size, slen).astype(np.int32)
            out.append(_Req(float(arrivals[i]),
                            np.concatenate([prefixes[s], suffix]),
                            int(rng.randint(min_new, max_new + 1))))
            i += 1
    return out


def run_tiering_ab(tiny=True, seed=0, repeat=1):
    """KV-tiering A/B (ISSUE 16 acceptance): ONE seeded multi-session
    stream whose working set exceeds the device pool, through three arms
    over the same weights:

      resident   an oversized pool that never evicts — the bit-exact
                 greedy reference
      recompute  the small pool with the tier OFF: a reclaimed prefix is
                 gone, every revisit re-prefills it (the pre-16 story)
      tiered     the SAME small pool with ``kv_host_blocks``: reclaimed
                 prefixes spill to host RAM and revisits revive them via
                 ``import_request_pages``

    All arms must be bit-exact (tiering moves pages, never math); the
    headline is tiered/recompute EFFECTIVE (prompt+generated) tokens/s —
    revived prefix tokens are served without recomputing them. The int8
    variant replays the same A/B over int8-KV pools (its own reference;
    int8 vs fp32 token ids may legitimately differ) proving the tier
    composes with quantized pools. ``repeat`` is min-of-N per arm."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    cfg, sess_kwargs, engine_kwargs, host_blocks, resident_blocks = \
        tiering_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = session_stream(cfg, seed=seed, **sess_kwargs)
    warm = session_stream(cfg, seed=seed + 1, prefix_seed=seed + 202,
                          **sess_kwargs)
    arms = {
        "resident": dict(engine_kwargs, num_blocks=resident_blocks),
        "recompute": dict(engine_kwargs),
        "tiered": dict(engine_kwargs, kv_host_blocks=host_blocks),
    }
    engines, runs = {}, {a: [] for a in arms}
    try:
        for arm, kw in arms.items():
            engines[arm] = _warm_engine(model, warm,
                                        enable_prefix_cache=True, **kw)
        for _ in range(max(int(repeat), 1)):
            for arm in arms:
                runs[arm].append(
                    run_engine(model, stream, engine=engines[arm]))
    finally:
        for eng in engines.values():
            eng.close()
    bit_exact = all(
        _bit_exact(runs["resident"][0]["outputs"], r["outputs"])
        for rs in runs.values() for r in rs)
    res = {arm: max(rs, key=lambda r: r["effective_tokens_per_sec"])
           for arm, rs in runs.items()}

    # int8 variant: same stream, int8 pools in all three roles — its own
    # never-evicted reference (int8 vs fp32 ids can differ; int8 arms
    # must agree with EACH OTHER)
    engines8, runs8 = {}, {a: [] for a in arms}
    try:
        for arm, kw in arms.items():
            engines8[arm] = _warm_engine(model, warm,
                                         enable_prefix_cache=True,
                                         kv_dtype="int8", **kw)
        for arm in arms:
            runs8[arm].append(
                run_engine(model, stream, engine=engines8[arm]))
    finally:
        for eng in engines8.values():
            eng.close()
    int8_bit_exact = all(
        _bit_exact(runs8["resident"][0]["outputs"], r["outputs"])
        for rs in runs8.values() for r in rs)

    return dict(
        resident={k: v for k, v in res["resident"].items()
                  if k != "outputs"},
        recompute={k: v for k, v in res["recompute"].items()
                   if k != "outputs"},
        tiered={k: v for k, v in res["tiered"].items()
                if k != "outputs"},
        speedup=round(res["tiered"]["effective_tokens_per_sec"]
                      / res["recompute"]["effective_tokens_per_sec"], 3),
        int8_speedup=round(
            runs8["tiered"][0]["effective_tokens_per_sec"]
            / runs8["recompute"][0]["effective_tokens_per_sec"], 3),
        kv_spills=res["tiered"]["kv_spills"],
        kv_revives=res["tiered"]["kv_revives"],
        bit_exact=bool(bit_exact),
        int8_bit_exact=bool(int8_bit_exact),
        repeats=max(int(repeat), 1),
        num_requests=len(stream),
        n_sessions=sess_kwargs["n_sessions"],
        visits=sess_kwargs["visits"],
        prefix_len=sess_kwargs["prefix_len"],
        pool_blocks=engine_kwargs["num_blocks"],
        host_blocks=host_blocks,
    )


def fleet_sizing(tiny):
    """Stream/engine sizing for the fleet A/B: per-step COMPUTE must
    dominate the per-step RPC/dispatch overhead (a deeper/wider tiny,
    the shared-prefix-sizing trick) and the burst must saturate ONE
    replica's batch, so adding replicas buys real throughput instead of
    just splitting batch occupancy."""
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        cfg = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4)
        stream = dict(n=36, rate=400.0, min_prompt=4, max_prompt=24,
                      min_new=24, max_new=40)
        engine = dict(num_blocks=256, block_size=8, max_batch_size=4,
                      max_prefills_per_step=2)
    else:
        cfg = llama_small()
        stream = dict(n=64, rate=300.0, min_prompt=16, max_prompt=128,
                      min_new=32, max_new=64)
        engine = dict(num_blocks=512, block_size=16, max_batch_size=4)
    return cfg, stream, engine


def run_fleet(artifact, stream, *, n_replicas, engine_kwargs,
              warm_stream=None, log_dir=None, roles=None,
              group_size=1, plan=None):
    """One timed window through a real replica fleet (ISSUE 12):
    ``n_replicas`` worker processes behind the Router, requests admitted
    on the stream's arrival clock. ``warm_stream`` is replayed first so
    every replica's prefill/decode graphs are compiled before timing
    (engine-owned metrics are reset afterwards — the window discipline).
    ``roles`` (ISSUE 15) splits the fleet into dedicated prefill/decode
    workers; decode-worker ITL percentiles are collected per replica
    from the stats RPC, so the disagg A/B compares exactly the latency
    the handoff is supposed to protect. ``group_size``/``plan``
    (ISSUE 19) make every replica a tp-sharded PROCESS GROUP — one
    Router slot, ``group_size`` coordinated workers."""
    from paddle_tpu.inference.serving.fleet import Router

    fleet = Router(artifact=artifact, n_replicas=n_replicas,
                   engine_kwargs=engine_kwargs, log_dir=log_dir,
                   max_queue=1_000_000, roles=roles,
                   group_size=group_size, plan=plan)
    try:
        if warm_stream is not None:
            for r in warm_stream:
                fleet.submit(r.prompt, max_new=r.max_new)
            fleet.join(timeout=600)
            fleet.reset_replica_metrics()
        gids = []
        i = 0
        t0 = time.perf_counter()
        while i < len(stream) or fleet.pending():
            now = time.perf_counter() - t0
            while i < len(stream) and stream[i].arrival <= now:
                gids.append(fleet.submit(stream[i].prompt,
                                         max_new=stream[i].max_new))
                i += 1
            progressed = fleet.step()
            if not progressed:
                if fleet.pending():
                    time.sleep(0.001)
                elif i < len(stream):
                    time.sleep(max(0.0, stream[i].arrival - now))
        fleet.join(timeout=600)
        wall = time.perf_counter() - t0
        outs = [fleet.result(g) for g in gids]
        fm = fleet.metrics()
        # decode-worker ITL: engine-owned histograms read per replica;
        # on a split fleet only decode-capable replicas decode, on a
        # colocated fleet every replica does
        decode_itl = []
        for h in fleet.supervisor.handles:
            if not h.alive or h.retired:
                continue
            if roles is not None and roles[h.id] == "prefill":
                continue
            s = fleet.replica_stats(h.id)
            if s and s.get("itl_p99_ms") is not None:
                decode_itl.append(float(s["itl_p99_ms"]))
    finally:
        fleet.close()
    gen_tokens = sum(r.max_new for r in stream)
    return dict(outputs=outs, wall_s=round(wall, 4),
                tokens_per_sec=round(gen_tokens / wall, 1),
                gen_tokens=gen_tokens, n_replicas=n_replicas,
                redispatches=fm["redispatches"],
                requests_shed=fm["requests_shed"],
                prefill_handoffs=fm["prefill_handoffs"],
                kv_transfer_retries=fm["kv_transfer_retries"],
                decode_itl_p99_ms=(round(max(decode_itl), 2)
                                   if decode_itl else None))


def run_fleet_ab(tiny=True, seed=0, fleet=3):
    """Fleet scaling A/B (ISSUE 12 / ROADMAP item 1 acceptance): ONE
    seeded Poisson burst through a 1-replica fleet and an N-replica
    fleet — both real subprocess fleets behind the same Router/RPC path,
    so the delta is pure replica parallelism, not RPC overhead — plus an
    in-process engine reference that both fleets' greedy outputs must
    match bit-exactly. Reports tokens/s per arm and the scaling factor
    (near-linear on an unloaded box with >= ``fleet`` cores)."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              save_llama_artifact)
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, engine_kwargs = fleet_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    warm = request_stream(cfg, seed=seed + 1, **stream_kwargs)
    import shutil

    tmp = tempfile.mkdtemp(prefix="bench_fleet.")
    try:
        artifact = os.path.join(tmp, "model")
        save_llama_artifact(model, artifact)
        eng = LLMEngine(model, ingest_async=False, **engine_kwargs)
        try:
            rids = [eng.add_request(
                r.prompt, SamplingParams(max_new_tokens=r.max_new))
                for r in stream]
            for _ in eng.stream():
                pass
            refs = [eng.output_tokens(r) for r in rids]
        finally:
            eng.close()
        one = run_fleet(artifact, stream, n_replicas=1,
                        engine_kwargs=engine_kwargs, warm_stream=warm)
        many = run_fleet(artifact, stream, n_replicas=fleet,
                         engine_kwargs=engine_kwargs, warm_stream=warm)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    bit_exact = (_bit_exact(refs, one["outputs"])
                 and _bit_exact(refs, many["outputs"]))
    return dict(
        single={k: v for k, v in one.items() if k != "outputs"},
        fleet={k: v for k, v in many.items() if k != "outputs"},
        scaling=round(many["tokens_per_sec"] / one["tokens_per_sec"], 3),
        n_replicas=fleet,
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
    )


def _llama_weight_bytes(cfg, shards=1):
    """fp32 bytes of ONE device's weight shard under tp=``shards``. The
    default llama tp rules shard every large matrix (vocab-parallel
    embedding, column-parallel lm_head, q/k/v/gate/up on columns,
    o/down on rows); only the RMSNorm vectors replicate."""
    h, inter, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    per_layer = (2 * h * heads * hd      # q_proj + o_proj
                 + 2 * h * kv * hd       # k_proj + v_proj
                 + 3 * h * inter)        # gate/up/down_proj
    sharded = cfg.num_hidden_layers * per_layer + 2 * v * h
    replicated = (2 * cfg.num_hidden_layers + 1) * h
    return 4 * (sharded // shards + replicated)


def _llama_kv_pool_bytes(cfg, engine_kwargs, shards=1):
    """fp32 bytes of one device's share of the paged KV pool: KV heads
    shard across tp, so the resident pool halves with the weights."""
    tokens = engine_kwargs["num_blocks"] * engine_kwargs["block_size"]
    per_token = (2 * cfg.num_hidden_layers
                 * (cfg.num_key_value_heads // shards) * cfg.head_dim)
    return 4 * tokens * per_token


def _llama_device_bytes(cfg, engine_kwargs, shards=1):
    return (_llama_weight_bytes(cfg, shards)
            + _llama_kv_pool_bytes(cfg, engine_kwargs, shards))


def tpfleet_sizing(tiny):
    """Sizing for the model-parallel fleet A/B (ISSUE 19): a per-device
    byte budget that the BIG llama's fp32 weights + KV pool exceed on
    one device but fit once tp=2 shards them, plus a largest-first
    ladder of single-device candidates (same vocab, so one request
    stream serves both arms) from which the baseline is chosen."""
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        # ~13.0 MiB weights + 8.0 MiB KV pool on one device vs a 16 MiB
        # budget; the tp=2 shard is ~10.5 MiB and fits
        big = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4,
                          max_position_embeddings=128)
        ladder = [_dc.replace(llama_tiny(), hidden_size=192,
                              intermediate_size=576, num_hidden_layers=3,
                              max_position_embeddings=128),
                  llama_tiny()]
        budget = 16 * 1024 * 1024
        stream = dict(n=24, rate=400.0, min_prompt=4, max_prompt=24,
                      min_new=24, max_new=40)
        engine = dict(num_blocks=256, block_size=8, max_batch_size=4,
                      max_prefills_per_step=2)
    else:
        # llama_small: ~130 MiB weights + 256 MiB KV vs a 256 MiB budget
        big = llama_small()
        ladder = [_dc.replace(llama_small(), hidden_size=256,
                              intermediate_size=704,
                              num_hidden_layers=4),
                  _dc.replace(llama_small(), hidden_size=128,
                              intermediate_size=384,
                              num_hidden_layers=2,
                              num_attention_heads=4,
                              num_key_value_heads=2)]
        budget = 256 * 1024 * 1024
        stream = dict(n=64, rate=300.0, min_prompt=16, max_prompt=128,
                      min_new=32, max_new=64)
        engine = dict(num_blocks=512, block_size=16, max_batch_size=4)
    return big, ladder, budget, stream, engine


def run_tpfleet_ab(tiny=True, seed=0, groups=2):
    """Model-parallel fleet A/B (ISSUE 19 acceptance): serve a llama
    whose fp32 weights + KV pool EXCEED the per-device byte budget — a
    model NO single-device replica could host — on ``groups`` tp=2
    replica groups (each group is one Router slot backed by two
    coordinated worker processes over jax.distributed), against the
    LARGEST ladder config that does fit one device, served on the same
    device count as plain replicas. Both arms are real subprocess
    fleets behind the same Router/RPC path and each must match its own
    in-process engine greedy reference bit-exactly."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              save_llama_artifact)
    from paddle_tpu.models import LlamaForCausalLM

    big, ladder, budget, stream_kwargs, engine_kwargs = \
        tpfleet_sizing(tiny)
    tp = 2
    one_dev = _llama_device_bytes(big, engine_kwargs)
    per_shard = _llama_device_bytes(big, engine_kwargs, shards=tp)
    assert one_dev > budget, \
        f"big config fits one device ({one_dev} <= {budget}); no tp case"
    assert per_shard <= budget, \
        f"big config does not even fit sharded ({per_shard} > {budget})"
    fits = [c for c in ladder
            if _llama_device_bytes(c, engine_kwargs) <= budget]
    assert fits, "no single-device ladder config fits the budget"
    small = fits[0]
    assert small.vocab_size == big.vocab_size, \
        "arms must share a vocab so one stream serves both"

    n_devices = groups * tp
    stream = request_stream(big, seed=seed, **stream_kwargs)
    warm = request_stream(big, seed=seed + 1, **stream_kwargs)
    tmp = tempfile.mkdtemp(prefix="bench_tpfleet.")

    def arm(cfg, name, n_replicas, group_size, plan):
        paddle.seed(seed)
        np.random.seed(seed)
        model = LlamaForCausalLM(cfg)
        model.eval()
        artifact = os.path.join(tmp, name)
        save_llama_artifact(model, artifact)
        eng = LLMEngine(model, ingest_async=False, **engine_kwargs)
        try:
            rids = [eng.add_request(
                r.prompt, SamplingParams(max_new_tokens=r.max_new))
                for r in stream]
            for _ in eng.stream():
                pass
            refs = [eng.output_tokens(r) for r in rids]
        finally:
            eng.close()
        res = run_fleet(artifact, stream, n_replicas=n_replicas,
                        engine_kwargs=engine_kwargs, warm_stream=warm,
                        group_size=group_size, plan=plan)
        res["bit_exact"] = bool(_bit_exact(refs, res["outputs"]))
        return res

    try:
        sharded = arm(big, "big", groups, tp,
                      {"axes": {"tp": tp}, "strategies": ["tp"]})
        single = arm(small, "small", n_devices, 1, None)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return dict(
        sharded={k: v for k, v in sharded.items() if k != "outputs"},
        single={k: v for k, v in single.items() if k != "outputs"},
        bit_exact=bool(sharded["bit_exact"] and single["bit_exact"]),
        tp=tp, n_groups=groups, n_devices=n_devices,
        device_budget_bytes=budget,
        big_model_device_bytes=one_dev,
        big_model_shard_bytes=per_shard,
        single_model_device_bytes=_llama_device_bytes(
            small, engine_kwargs),
        num_requests=len(stream),
    )


def disagg_sizing(tiny):
    """Long-prompt mix over a replica fleet (ISSUE 15): a background of
    short decode-heavy requests with long prompts landing mid-stream —
    the workload whose colocated prefills stall every in-flight token
    stream, and exactly what shipping prefill to dedicated workers
    protects. The deeper/wider tiny makes chunk compute dominate RPC
    overhead (the fleet_sizing trick)."""
    import dataclasses as _dc

    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        cfg = _dc.replace(llama_tiny(), hidden_size=256,
                          intermediate_size=768, num_hidden_layers=4,
                          max_position_embeddings=1024)
        stream = dict(n=12, rate=300.0, min_prompt=4, max_prompt=12,
                      min_new=24, max_new=40)
        long_prompts = dict(every=3, length=384)
        engine = dict(num_blocks=256, block_size=8, max_batch_size=4,
                      max_prefills_per_step=1)
    else:
        cfg = llama_small()
        stream = dict(n=32, rate=150.0, min_prompt=16, max_prompt=64,
                      min_new=48, max_new=96)
        long_prompts = dict(every=4, length=1024)
        engine = dict(num_blocks=512, block_size=16, max_batch_size=4,
                      max_prefills_per_step=1)
    return cfg, stream, long_prompts, engine


def run_disagg_ab(tiny=True, seed=0, fleet=3):
    """Disaggregated prefill/decode A/B (ISSUE 15 acceptance): ONE
    seeded long-prompt mix through a colocated ``fleet``-replica fleet
    and a role-split fleet of the SAME size (1 prefill + the rest
    decode) — both real subprocess fleets behind the same Router/RPC
    path, both bit-exact against an in-process engine reference. The
    headline number is DECODE-worker ITL p99 (engine-owned histograms):
    colocated replicas stall their decode batches for every long
    prefill, while split decode workers receive finished KV pages and
    never prefill — so the disagg arm's ITL p99 must come in at or
    under the colocated arm's."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              save_llama_artifact)
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, long_prompts, engine_kwargs = disagg_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = long_prompt_stream(cfg, stream_kwargs, long_prompts,
                                seed=seed)
    n = max(int(fleet), 2)
    # warm with an n-times larger stream so EVERY replica sees every
    # prefill bucket: least-loaded placement spreads warm requests
    # nearly evenly, and a bucket compile landing inside the timed
    # window would charge ~10s of XLA time to one arm's ITL p99
    warm = long_prompt_stream(cfg, dict(stream_kwargs,
                                        n=stream_kwargs["n"] * n),
                              long_prompts, seed=seed + 1)
    roles = ["prefill"] + ["decode"] * (n - 1)
    tmp = tempfile.mkdtemp(prefix="bench_disagg.")
    try:
        artifact = os.path.join(tmp, "model")
        save_llama_artifact(model, artifact)
        eng = LLMEngine(model, ingest_async=False, **engine_kwargs)
        try:
            rids = [eng.add_request(
                r.prompt, SamplingParams(max_new_tokens=r.max_new))
                for r in stream]
            for _ in eng.stream():
                pass
            refs = [eng.output_tokens(r) for r in rids]
        finally:
            eng.close()
        colocated = run_fleet(artifact, stream, n_replicas=n,
                              engine_kwargs=engine_kwargs,
                              warm_stream=warm)
        disagg = run_fleet(artifact, stream, n_replicas=n,
                           engine_kwargs=engine_kwargs,
                           warm_stream=warm, roles=roles)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    bit_exact = (_bit_exact(refs, colocated["outputs"])
                 and _bit_exact(refs, disagg["outputs"]))
    co_itl = colocated["decode_itl_p99_ms"]
    dg_itl = disagg["decode_itl_p99_ms"]
    return dict(
        colocated={k: v for k, v in colocated.items() if k != "outputs"},
        disagg={k: v for k, v in disagg.items() if k != "outputs"},
        itl_p99_ratio=(round(dg_itl / co_itl, 3)
                       if co_itl and dg_itl else None),
        tokens_per_sec_ratio=round(
            disagg["tokens_per_sec"]
            / max(colocated["tokens_per_sec"], 1e-9), 3),
        n_replicas=n,
        roles=roles,
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
        long_prompt_len=long_prompts["length"],
    )


def qos_sizing(tiny):
    """Three-tenant mix over ONE engine (ISSUE 17): an interactive
    latency-tier stream, a batch-tier flood sized to fill every decode
    slot with long generations, and an abuser bursting a demand several
    times its token-rate quota. The contended arm must keep the
    interactive TTFT close to the uncontended reference while the
    scheduler paces the abuser at its bucket rate."""
    from paddle_tpu.models import llama_small, llama_tiny

    if tiny:
        cfg = llama_tiny()
        lat = dict(n=16, rate=150.0, min_prompt=4, max_prompt=24,
                   min_new=12, max_new=24)
        bat = dict(n=8, rate=1e6, min_prompt=4, max_prompt=16,
                   min_new=24, max_new=40)
        abu = dict(n=10, rate=1e6, min_prompt=4, max_prompt=12,
                   min_new=8, max_new=12)
        engine = dict(num_blocks=160, block_size=8, max_batch_size=8,
                      max_prefills_per_step=2)
        abuser_rate = 60.0
    else:
        cfg = llama_small()
        lat = dict(n=48, rate=100.0, min_prompt=16, max_prompt=128,
                   min_new=32, max_new=64)
        bat = dict(n=8, rate=1e6, min_prompt=16, max_prompt=64,
                   min_new=64, max_new=128)
        abu = dict(n=24, rate=1e6, min_prompt=16, max_prompt=64,
                   min_new=16, max_new=32)
        engine = dict(num_blocks=512, block_size=16, max_batch_size=8)
        abuser_rate = 200.0
    return cfg, lat, bat, abu, engine, abuser_rate


def _run_qos_arm(eng, jobs):
    """One timed window of tenant/tier-attributed jobs through a warmed
    engine. Per-tenant TTFT is bench-timed (first token seen minus
    arrival) because the engine's TTFT histogram carries no ``tenant``
    label — the cardinality bound is deliberate; scheduler-side QoS
    counters (throttles, yields, per-tenant served tokens) are
    engine-owned, read from the metrics registry after the window."""
    from paddle_tpu.inference.serving import SamplingParams

    eng.reset_metrics()
    jobs = sorted(jobs, key=lambda j: j["arrival"])
    owner = {}
    first_t, finish_t = {}, {}
    i = 0
    t0 = time.perf_counter()
    while i < len(jobs) or eng.has_work():
        now = time.perf_counter() - t0
        while i < len(jobs) and jobs[i]["arrival"] <= now:
            j = jobs[i]
            rid = eng.add_request(
                j["req"].prompt,
                SamplingParams(max_new_tokens=j["req"].max_new),
                tenant=j["tenant"], tier=j["tier"])
            owner[rid] = j
            i += 1
        if not eng.has_work():
            time.sleep(max(0.0, jobs[i]["arrival"] - now))
            continue
        for out in eng.step():
            t = time.perf_counter() - t0
            if out.rid not in first_t:
                first_t[out.rid] = t
            if out.finished:
                finish_t[out.rid] = t
    wall = time.perf_counter() - t0
    outs = {rid: eng.output_tokens(rid) for rid in owner}
    em = eng.metrics()
    stats = eng.stats()

    def bucket_ttfts(bucket):
        return [first_t[rid] - j["req"].arrival
                for rid, j in owner.items() if j["bucket"] == bucket]

    def bucket_span(bucket):
        arr = [(j["req"].arrival, finish_t[rid], j["req"].max_new)
               for rid, j in owner.items() if j["bucket"] == bucket]
        if not arr:
            return 0.0, 0
        return (max(f for _, f, _ in arr) - min(a for a, _, _ in arr),
                sum(g for _, _, g in arr))
    return dict(owner=owner, outputs=outs, wall_s=round(wall, 4),
                ttfts={b: bucket_ttfts(b) for b in ("lat", "bat", "abu")},
                spans={b: bucket_span(b) for b in ("lat", "bat", "abu")},
                quota_throttled=stats["quota_throttled"],
                batch_yields=stats["batch_yields"],
                tenant_tokens=em["tenant_tokens"])


def run_qos_ab(tiny=True, seed=0):
    """Multi-tenant QoS A/B (ISSUE 17): the SAME interactive stream runs
    once uncontended and once under a batch flood + abuser burst, on one
    warmed engine with tenants configured. Reports contended vs
    uncontended latency-tier TTFT percentiles and the abuser's achieved
    throughput against its quota; the interactive outputs of both arms
    must be bit-identical (QoS changes WHEN work runs, never WHICH
    tokens)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import TIER_BATCH
    from paddle_tpu.models import LlamaForCausalLM

    cfg, lat_kw, bat_kw, abu_kw, engine_kwargs, abuser_rate = \
        qos_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    lat = request_stream(cfg, seed=seed, **lat_kw)
    bat = request_stream(cfg, seed=seed + 1, **bat_kw)
    abu = request_stream(cfg, seed=seed + 2, **abu_kw)

    def jobs_from(stream, tenant, tier, bucket):
        return [dict(arrival=r.arrival, req=r, tenant=tenant, tier=tier,
                     bucket=bucket) for r in stream]

    eng = warm_arms(model, lat + bat + abu, **engine_kwargs)
    try:
        eng.configure_tenant("interactive", weight=4.0)
        eng.configure_tenant("batchjobs", weight=1.0)
        eng.configure_tenant("abuser", rate_tokens_per_s=abuser_rate)
        un = _run_qos_arm(
            eng, jobs_from(lat, "interactive", None, "lat"))
        co = _run_qos_arm(
            eng, jobs_from(bat, "batchjobs", TIER_BATCH, "bat")
            + jobs_from(abu, "abuser", None, "abu")
            + jobs_from(lat, "interactive", None, "lat"))
    finally:
        eng.close()

    def lat_outputs(arm):
        ordered = sorted((rid for rid, j in arm["owner"].items()
                          if j["bucket"] == "lat"),
                         key=lambda rid: arm["owner"][rid]["req"].arrival)
        return [arm["outputs"][rid] for rid in ordered]

    bit_exact = _bit_exact(lat_outputs(un), lat_outputs(co))
    abu_span, abu_tokens = co["spans"]["abu"]
    abu_rate = round(abu_tokens / abu_span, 1) if abu_span else None
    u99 = _latency_stats(un["ttfts"]["lat"])
    c99 = _latency_stats(co["ttfts"]["lat"])
    return dict(
        uncontended=dict(wall_s=un["wall_s"],
                         lat_ttft_p50_ms=u99["p50_ms"],
                         lat_ttft_p99_ms=u99["p99_ms"]),
        contended=dict(wall_s=co["wall_s"],
                       lat_ttft_p50_ms=c99["p50_ms"],
                       lat_ttft_p99_ms=c99["p99_ms"],
                       abuser_tokens_per_sec=abu_rate,
                       abuser_quota_tokens_per_sec=abuser_rate,
                       quota_throttled=co["quota_throttled"],
                       batch_yields=co["batch_yields"],
                       tenant_tokens=co["tenant_tokens"]),
        lat_ttft_p99_ratio=round(c99["p99_ms"] / u99["p99_ms"], 3)
        if u99["p99_ms"] else None,
        bit_exact=bool(bit_exact),
        num_requests=len(lat) + len(bat) + len(abu),
    )


def run_audit_ab(tiny=True, seed=0, fleet=3, fraction=0.1):
    """Sampled-output-audit overhead A/B (ISSUE 20): the SAME seeded
    Poisson burst through ONE warmed subprocess fleet, first with
    ``audit_fraction=0.0`` and then with ``audit_fraction=fraction`` —
    audit replays are strictly batch-tier background work on a
    different replica, so the latency-tier TTFT p99 must stay within
    ~1.1x of the audit-off arm, and both arms' outputs must match the
    in-process engine greedy reference bit-exactly (auditing reads
    streams, it never changes them)."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              save_llama_artifact)
    from paddle_tpu.inference.serving.fleet import Router
    from paddle_tpu.models import LlamaForCausalLM

    cfg, stream_kwargs, engine_kwargs = fleet_sizing(tiny)
    paddle.seed(seed)
    np.random.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    stream = request_stream(cfg, seed=seed, **stream_kwargs)
    warm = request_stream(cfg, seed=seed + 1, **stream_kwargs)

    tmp = tempfile.mkdtemp(prefix="bench_audit.")
    fl = None
    try:
        artifact = os.path.join(tmp, "model")
        save_llama_artifact(model, artifact)
        eng = LLMEngine(model, ingest_async=False, **engine_kwargs)
        try:
            rids = [eng.add_request(
                r.prompt, SamplingParams(max_new_tokens=r.max_new))
                for r in stream]
            for _ in eng.stream():
                pass
            refs = [eng.output_tokens(r) for r in rids]
        finally:
            eng.close()

        fl = Router(artifact=artifact, n_replicas=fleet,
                    engine_kwargs=engine_kwargs, max_queue=1_000_000)
        wgids = [fl.submit(r.prompt, max_new=r.max_new) for r in warm]
        fl.join(timeout=600)
        for g in wgids:
            fl.release(g)
        fl.reset_replica_metrics()

        def arm(f):
            # one fleet, both arms: the delta is the auditing, not
            # process boot or compile variance
            fl.audit_fraction = f
            audits_before = fl.metrics()["audits_run"]
            gids = []
            i = 0
            t0 = time.perf_counter()
            while i < len(stream) or fl.pending():
                now = time.perf_counter() - t0
                while i < len(stream) and stream[i].arrival <= now:
                    gids.append(fl.submit(stream[i].prompt,
                                          max_new=stream[i].max_new))
                    i += 1
                if not fl.step():
                    if fl.pending():
                        time.sleep(0.001)
                    elif i < len(stream):
                        time.sleep(max(0.0, stream[i].arrival - now))
            fl.join(timeout=600)
            wall = time.perf_counter() - t0
            outs = [fl.result(g) for g in gids]
            # audits self-release on completion, so the surviving
            # requests (and their TTFTs) are exactly the client burst
            ttfts = fl.ttft_seconds()
            m = fl.metrics()
            for g in gids:
                fl.release(g)
            return dict(outputs=outs, wall_s=round(wall, 4),
                        ttft=_latency_stats(ttfts),
                        audits_run=m["audits_run"] - audits_before,
                        audit_mismatches=m["audit_mismatches"],
                        replicas_quarantined=m["replicas_quarantined"])

        off = arm(0.0)
        on = arm(float(fraction))
    finally:
        if fl is not None:
            fl.close()
        shutil.rmtree(tmp, ignore_errors=True)

    bit_exact = (_bit_exact(refs, off["outputs"])
                 and _bit_exact(refs, on["outputs"]))
    p_off = off["ttft"]["p99_ms"]
    p_on = on["ttft"]["p99_ms"]
    return dict(
        audit_off={k: v for k, v in off.items() if k != "outputs"},
        audit_on={k: v for k, v in on.items() if k != "outputs"},
        audit_fraction=float(fraction),
        ttft_p99_ratio=(round(p_on / p_off, 3) if p_off else None),
        # CI boxes are noisy at millisecond TTFTs: the gate is the
        # 1.1x ratio with a small absolute epsilon, like the qos bound
        ttft_p99_within_bound=bool(p_on <= p_off * 1.1 + 20.0),
        audits_ran=on["audits_run"] > 0 and off["audits_run"] == 0,
        bit_exact=bool(bit_exact),
        num_requests=len(stream),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="poisson",
                    choices=["poisson", "shared-prefix", "chunked", "spec",
                             "fleet", "quantized", "disagg", "tiering",
                             "qos", "tpfleet", "audit"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--spec-tokens", type=int, default=3)
    ap.add_argument("--draft", default="self", choices=["self", "tiny"])
    ap.add_argument("--fleet", type=int, default=3,
                    help="replica count for --workload fleet")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke sizing (llama_tiny)")
    args = ap.parse_args()

    from paddle_tpu.jit.cache import place_compile_cache

    place_compile_cache()
    tiny = args.tiny
    if not tiny:
        try:
            import jax

            tiny = jax.default_backend() in ("cpu",)
        except Exception:
            tiny = True

    if args.workload == "shared-prefix":
        res = run_shared_prefix_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: sharing arm diverges from no-sharing greedy")
        return
    if args.workload == "chunked":
        res = run_chunked_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: chunked arm diverges from unchunked greedy")
        return
    if args.workload == "spec":
        res = run_spec_ab(tiny=tiny, seed=args.seed,
                          spec_tokens=args.spec_tokens, draft=args.draft)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: speculative arm diverges from plain greedy")
        return
    if args.workload == "tiering":
        res = run_tiering_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: tiered/recompute arms diverge from the "
                     "never-evicted greedy reference")
        if not res["int8_bit_exact"]:
            sys.exit("FAIL: int8 tiered arm diverges from its "
                     "never-evicted int8 reference")
        return
    if args.workload == "fleet":
        res = run_fleet_ab(tiny=tiny, seed=args.seed, fleet=args.fleet)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: fleet outputs diverge from the in-process "
                     "engine greedy reference")
        return
    if args.workload == "tpfleet":
        res = run_tpfleet_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: tp-sharded or single-device fleet outputs "
                     "diverge from their in-process engine greedy "
                     "references")
        return
    if args.workload == "quantized":
        res = run_quantized_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["deterministic"]:
            sys.exit("FAIL: int8-KV greedy decode was not deterministic "
                     "run-to-run")
        return
    if args.workload == "disagg":
        res = run_disagg_ab(tiny=tiny, seed=args.seed, fleet=args.fleet)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: disaggregated fleet outputs diverge from the "
                     "in-process engine greedy reference")
        return
    if args.workload == "qos":
        res = run_qos_ab(tiny=tiny, seed=args.seed)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: contended interactive outputs diverge from "
                     "the uncontended run — QoS must only change WHEN "
                     "work runs, never WHICH tokens")
        return
    if args.workload == "audit":
        res = run_audit_ab(tiny=tiny, seed=args.seed, fleet=args.fleet)
        print(json.dumps(res, indent=2))
        if not res["bit_exact"]:
            sys.exit("FAIL: audited fleet outputs diverge from the "
                     "in-process engine greedy reference — auditing "
                     "must never change a served token")
        if not res["audits_ran"]:
            sys.exit("FAIL: the audit-on arm ran no audits (or the "
                     "audit-off arm ran some)")
        if not res["ttft_p99_within_bound"]:
            sys.exit("FAIL: audit_fraction=%s pushed latency-tier TTFT "
                     "p99 past 1.1x the audit-off arm (%s)"
                     % (res["audit_fraction"], res["ttft_p99_ratio"]))
        return

    cfg, stream_kwargs, engine_kwargs = default_sizing(tiny)
    if args.requests is not None:
        stream_kwargs["n"] = args.requests
    if args.rate is not None:
        stream_kwargs["rate"] = args.rate
    if args.max_batch is not None:
        engine_kwargs["max_batch_size"] = args.max_batch

    res = run_ab(cfg, stream_kwargs, engine_kwargs, seed=args.seed)
    print(json.dumps(res, indent=2))
    if not res["bit_exact"]:
        sys.exit("FAIL: engine outputs diverge from batch-of-one greedy")
    if res["engine"]["decode_compiles_in_window"]:
        sys.exit("FAIL: decode graph recompiled inside the timed window")


if __name__ == "__main__":
    main()
