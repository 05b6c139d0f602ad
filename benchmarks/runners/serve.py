"""Runner of kind "serve": ``LLMEngine`` under a closed loop.

One process, one thread of load: the loop below submits what is due, calls
``engine.step()`` and stamps what came out on its own clock. Everything a
per-layer reader needs goes into the record this returns; nothing is read
from the engine but its public ``metrics()`` counters and its outputs.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import reference, schedule, stats
from . import common

# ``correct`` compares logits rows by ``reference.row_error`` against the
# configuration's own ``check.tolerance``: a tolerance belongs to what the
# configuration states (a bf16 engine and an int8 KV cache differ from the
# float32 reference by different amounts, both rightly), so it is data, and
# the file says what was measured on either side of it (mistral7b-serve,
# v5e, PR 24: 0.0432-0.0472 as stated, 0.0695-0.0763 with an int8 KV cache,
# tolerance 0.057). A wrong page, mask, head or rope moves a row by
# 0.3-1.0, far over any of them.

#: token-id streams of the check's and the warm-up's prompts: far from any
#: index a schedule reaches
CHECK_INDEX = 2 ** 30
#: loop iterations between the profiler's start and the first traced step
SETTLE_STEPS = 3


def _bucket(buckets, n):
    return next(b for b in sorted(buckets) if b >= n)


def _drain(eng, on_output):
    while eng.has_work():
        for out in eng.step():
            on_output(out)


def logit_rows(eng, model, seed, spec):
    """Three short requests across two prefill buckets on the measured
    engine itself: ``{(request, j): the logits row token j was sampled
    from}``, with the prompts and the tokens that came out. A step that
    finishes a prefill also decodes that request once and ``last_logits``
    keeps the newer row, so row 0 comes from a second pass of one-token
    requests (chip_smoke.py's method); ``agree`` says that both passes
    chose the same first token."""
    from paddle_tpu.inference.serving import SamplingParams

    prompts = [schedule.token_ids(seed, CHECK_INDEX + i, n, model["vocab_size"])
               for i, n in enumerate(spec["prompt_lens"])]
    n_new = int(spec["new_tokens"])
    rows = {}

    def burst(lengths):
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, lengths)]
        seen = {r: 0 for r in rids}

        def on(out):
            j = seen[out.rid]
            seen[out.rid] += 1
            if j > 0 or out.finished:
                rows[(rids.index(out.rid), j)] = \
                    eng.request(out.rid).last_logits.copy()

        _drain(eng, on)
        toks = [list(eng.request(r).output_tokens) for r in rids]
        for r in rids:
            eng.release(r)
        return toks

    toks = burst([n_new] * len(prompts))
    first = burst([1] * len(prompts))
    agree = all(f[0] == t[0] for f, t in zip(first, toks))
    return prompts, toks, rows, agree


def reference_rows(net, model, prompts, toks, rows):
    """The reference's row for each of ``rows``, from one plain forward
    over prompt + output of every request."""
    width = max(len(p) + len(t) for p, t in zip(prompts, toks))
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, t) in enumerate(zip(prompts, toks)):
        ids[i, :len(p) + len(t)] = np.concatenate([p, t])
    ref = np.asarray(reference.logits(common.named_weights(net), ids, model))
    return {(i, j): ref[i, len(prompts[i]) - 1 + j] for i, j in rows}


def check_logits(eng, net, model, seed, spec):
    """Every sampled-from logits row of ``logit_rows`` against the
    reference; the worst row has to stay under ``spec["tolerance"]``."""
    prompts, toks, rows, agree = logit_rows(eng, model, seed, spec)
    want = reference_rows(net, model, prompts, toks, rows)
    tol = float(spec["tolerance"])
    finite = all(np.isfinite(row).all() for row in rows.values())
    worst = max((reference.row_error(rows[k], want[k]) for k in rows),
                default=math.inf) if finite else math.inf
    expected = len(prompts) * int(spec["new_tokens"])
    return {"ok": agree and len(rows) == expected and worst < tol,
            "worst": worst, "rows": len(rows), "tolerance": tol}


class _Live:
    __slots__ = ("item", "token_ts")

    def __init__(self, item):
        self.item = item
        self.token_ts = []


class Loop:
    """The load generator and the clock. ``step()`` runs one engine step
    and books what it emitted."""

    def __init__(self, eng, seed, vocab_size):
        self.eng, self.seed, self.vocab = eng, seed, vocab_size
        self.live, self.done, self.steps = {}, [], []
        self.on_finish = lambda live: None
        self.span = None

    def submit(self, item):
        from paddle_tpu.inference.serving import SamplingParams

        ids = schedule.token_ids(self.seed, item.index, item.prompt_len,
                                 self.vocab)
        rid = self.eng.add_request(
            ids, SamplingParams(max_new_tokens=item.output_len))
        self.live[rid] = _Live(item)

    def step(self):
        t0 = time.perf_counter()
        if self.span is not None:
            with self.span():
                outs = self.eng.step()
        else:
            outs = self.eng.step()
        t1 = time.perf_counter()
        decodes = ctx = 0
        prefills, finished = [], []
        for out in outs:
            lv = self.live[out.rid]
            j = len(lv.token_ts)
            lv.token_ts.append(t1)
            if j == 0:
                prefills.append(lv.item.prompt_len)
            else:
                # token j was decoded against prompt + j cached tokens
                decodes += 1
                ctx += lv.item.prompt_len + j
            if out.finished:
                finished.append(out.rid)
        # (start, end, tokens out, prompt lengths prefilled, rows decoded,
        #  sum of their context lengths)
        self.steps.append((t0, t1, len(outs), prefills, decodes, ctx))
        for rid in finished:
            lv = self.live.pop(rid)
            self.eng.release(rid)
            self.done.append(lv)
            self.on_finish(lv)


def warm_shapes(loop, items, buckets):
    """One request at each prefill bucket the schedule touches (the decode
    step has one shape), so that nothing compiles in the window."""
    from ..harness.schedule import Item

    touched = sorted({_bucket(buckets, it.prompt_len) for it in items})
    for k, b in enumerate(touched):
        loop.submit(Item(CHECK_INDEX + 100 + k, 0.0, b, 2))
    while loop.live:
        loop.step()
    loop.done.clear()
    loop.steps.clear()
    return touched


def run(config, traffic, *, seed, seconds, trace, out_dir, t_start,
        chips=1, require_chip=True):
    """One run of a serving cell."""
    import jax

    devs = common.require_tpu(chips) if require_chip else jax.devices()
    counter = common.CompileCounter()
    model = common.model_sizes(config)
    net = common.build_model(model, seed, config.get("dtype", "bfloat16"))
    net.eval()

    from paddle_tpu.inference.serving import LLMEngine

    eng = LLMEngine(net, capture_logits=True, **config["engine"])
    try:
        check = check_logits(eng, net, model, seed, config["check"])
        print(f"[check] {check}", flush=True)
        items = schedule.build(traffic)
        loop = Loop(eng, seed, model["vocab_size"])
        touched = warm_shapes(loop, items, config["engine"]["prefill_buckets"])
        print(f"[warm] prefill buckets {touched}; {counter.compiles}"
              f" executables so far", flush=True)
        record = {"kind": "serve", "loop": traffic["loop"], "model": model,
                  "device_kind": devs[0].device_kind, "trace": None}
        if trace:
            loop.span = common.step_span
        # schedule.build has refused every loop but the closed one
        _closed(loop, items, traffic, seconds, trace, out_dir, t_start,
                counter, record)
        record["correct"] = (bool(check["ok"])
                             and record["compiles_in_window"] == 0)
        record["check"] = check
        record["device"] = common.device_record(devs, chips)
        return record
    finally:
        eng.close()


def _window(loop, t_open, t_close):
    return [s for s in loop.steps if t_open <= s[1] < t_close]


def _trace_phase(loop, traffic, trace, out_dir, record):
    """With ``--trace 1``: step the loop under the profiler for the
    traffic's ``trace_seconds`` BEFORE the window opens, so that starting
    and stopping the profiler cost the window nothing. The steps made
    meanwhile are kept apart for the readers. ``engine.step()`` ends by
    fetching the step's logits, so each step's device time lies inside
    its own span: the work counted is the work timed."""
    if not trace:
        return
    n0 = len(loop.steps)
    common.start_trace(out_dir)
    try:
        # the profiler's start can hold the device for seconds (10 s in one
        # of this PR's traced runs): let it pass outside the step spans,
        # which are what bounds the traced window
        span, loop.span = loop.span, None
        for _ in range(SETTLE_STEPS):
            loop.step()
        loop.span = span
        n0 = len(loop.steps)
        t_stop = time.perf_counter() + traffic.get("trace_seconds", 3)
        while time.perf_counter() < t_stop:
            loop.step()
    finally:
        record["trace"] = common.stop_trace(out_dir)
    record["traced_steps"] = loop.steps[n0:]


def _series(steps):
    ms = lambda sel: [(s[1] - s[0]) * 1e3 for s in steps if sel(s)]  # noqa: E731
    return {"step_ms": ms(lambda s: True),
            "decode_step_ms": ms(lambda s: not s[3]),
            "prefill_step_ms": ms(lambda s: bool(s[3])),
            "decode_batch": [float(s[4]) for s in steps if s[4]]}


def _work(record, model):
    """What the traced steps had to do, from their shapes."""
    if record["device_kind"] not in peaks_mod.PEAKS:   # the CPU rehearsal
        return {}
    pk = peaks_mod.peaks_for(record["device_kind"])
    steps = record.get("traced_steps") or []
    decode_steps = sum(1 for s in steps if s[4])
    return {
        "paged_decode_s": peaks_mod.paged_decode_bytes(
            model, sum(s[5] for s in steps)) / pk["hbm_bytes_per_s"],
        "weight_stream_s": decode_steps * peaks_mod.weight_stream_bytes(model)
        / pk["hbm_bytes_per_s"],
    }


def _finish(loop, record, t_open, t_close, seconds, t_start, counter,
            compiles0, m0):
    steps = _window(loop, t_open, t_close)
    m1 = loop.eng.metrics()
    record.update(
        setup_s=t_open - t_start, seconds=seconds,
        compiles_in_window=counter.compiles - compiles0,
        counters={k: m1[k] - m0[k] for k in (
            "host_syncs", "tokens_out", "prefills", "prefill_chunks",
            "evictions", "admitted", "finished")},
        series=_series(steps))
    record["work"] = _work(record, record["model"])
    return steps


def _closed(loop, items, traffic, seconds, trace, out_dir, t_start, counter,
            record):
    """``clients`` callers, each sending its next request the moment its
    last one finishes. The window opens after the traffic's
    ``warmup_steps`` engine steps: the batch is full by then and holds
    requests of many ages. Steps, not seconds: the same requests are in
    flight when every run's window opens."""
    src = schedule.cycled(items)
    loop.on_finish = lambda lv: loop.submit(next(src))
    for _ in range(int(traffic["clients"])):
        loop.submit(next(src))
    for _ in range(int(traffic["warmup_steps"])):
        loop.step()
    _trace_phase(loop, traffic, trace, out_dir, record)
    loop.done.clear()
    loop.steps.clear()
    gc.collect()
    m0, compiles0 = loop.eng.metrics(), counter.compiles
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while time.perf_counter() < t_close:
        loop.step()
    t_end = time.perf_counter()
    steps = _finish(loop, record, t_open, t_end, seconds, t_start, counter,
                    compiles0, m0)
    tokens = [(s[1], s[2]) for s in steps]
    rates = stats.slice_rates(tokens, t_open, seconds, traffic["slice_seconds"])
    record["slice_rates"] = rates
    # the window runs from a step's end to a step's end: all its work over
    # all its time
    record["values"] = {
        "serve_tokens_per_s": stats.window_rate(tokens, t_open, t_end),
        "slice_median_tokens_per_s": stats.median(rates),
    }
    record["attempted"] = len(loop.done)
    record["failed"] = sum(1 for lv in loop.done
                           if len(lv.token_ts) != lv.item.output_len)
