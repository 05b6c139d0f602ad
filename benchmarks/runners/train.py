"""Runner of kind "train": ``FusedTrainStep`` + AdamW over packed
sequences of seeded token ids."""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from ..harness import peaks as peaks_mod
from ..harness import reference, schedule
from . import common

# |first step's loss - reference loss| on the same batch and weights. The
# step computes in bf16, the reference in float32 at "highest"; a mean over
# 16k tokens averages the rounding of single logits (0.4% each) down to a
# few 1e-3. chip_smoke.py allows 0.02 between two bf16 runs that differ in
# reduction order only. A wrong mask, label shift or rope moves the loss of
# random tokens by 0.05 and more away from the reference.
LOSS_TOL = 0.02
#: steps the host may run ahead of the device: keeps the device fed while
#: the host still learns, within a step's time, when each step ended
RUN_AHEAD = 2
#: steps between the profiler's start and the first traced step: the start
#: can hold the device for seconds (serve.py saw 10), outside the spans
SETTLE_STEPS = 3


def make_batches(seed, traffic, batch, vocab_size):
    """``distinct_batches`` batches of ``batch`` packed sequences: ids and
    the next-token labels, cut from sequences one token longer."""
    seq = traffic["seq_len"]
    out = []
    for k in range(traffic["distinct_batches"]):
        toks = schedule.token_ids(seed, k, batch * (seq + 1),
                                  vocab_size).reshape(batch, seq + 1)
        out.append((np.ascontiguousarray(toks[:, :-1]),
                    np.ascontiguousarray(toks[:, 1:])))
    return out


def traced_steps(one, drain, seconds, span):
    """Call ``one()`` under a step span each, for ``seconds``, and return
    how many steps were made. ``one`` only dispatches (the host
    runs ahead of the device), and the traced window is cut from the first
    span's start to the last span's end, so the last span is closed only
    after ``drain()`` has seen the last step finish: the device time of
    every step counted lies inside the window that times it. The device is
    idle when this starts."""
    t_stop = time.perf_counter() + seconds
    n, last = 0, False
    while not last:
        with span():
            one()
            n += 1
            last = time.perf_counter() >= t_stop
            if last:
                drain()
    return n


def run(config, traffic, *, seed, seconds, trace, out_dir, t_start,
        chips=1, require_chip=True):
    import jax
    import paddle_tpu as paddle

    devs = common.require_tpu(chips) if require_chip else jax.devices()
    counter = common.CompileCounter()
    model, tr = common.model_sizes(config), config["trainer"]
    net = common.build_model(model, seed, config.get("dtype", "bfloat16"))
    batches = make_batches(seed, traffic, tr["batch_sequences"],
                           model["vocab_size"])
    ref_loss = reference.loss(common.named_weights(net), *batches[0], model)

    net.train()
    opt = paddle.optimizer.AdamW(learning_rate=tr["learning_rate"],
                                 parameters=net.parameters())
    step = paddle.incubate.fused_train_step(
        net, opt, loss_fn=lambda out: out[0])
    losses, ends = [], []

    def one():
        ids, labels = batches[len(losses) % len(batches)]
        loss = step(paddle.to_tensor(ids), paddle.to_tensor(labels))
        losses.append(loss._data)
        if len(losses) > RUN_AHEAD:
            losses[-1 - RUN_AHEAD].block_until_ready()
            ends.append(time.perf_counter())

    def drain():
        losses[-1].block_until_ready()

    # warm-up: the one shape, and every distinct batch once
    for _ in range(max(len(batches), RUN_AHEAD + 1)):
        one()
    drain()
    first_loss = float(np.asarray(losses[0]))
    record = {"kind": "train", "model": model, "trace": None,
              "device_kind": devs[0].device_kind}
    if trace:
        # traced steps come before the window, so that starting and
        # stopping the profiler cost the window nothing
        common.start_trace(out_dir)
        try:
            for _ in range(SETTLE_STEPS):
                one()
            drain()
            record["traced_steps"] = traced_steps(
                one, drain, traffic.get("trace_seconds", 3),
                common.step_span)
        finally:
            record["trace"] = common.stop_trace(out_dir)
    n_warm = len(losses)
    gc.collect()
    compiles0 = counter.compiles
    t_open = time.perf_counter()
    t_close = t_open + seconds
    ends.clear()
    while time.perf_counter() < t_close:
        one()
    drain()
    t_end = time.perf_counter()

    n_steps = len(losses) - n_warm
    tokens = tr["batch_sequences"] * traffic["seq_len"]
    rate = n_steps * tokens / (t_end - t_open)
    host = [float(np.asarray(x)) for x in losses]
    lap = len(batches)
    falling = (len(host) >= 2 * lap
               and sum(host[-lap:]) / lap < sum(host[:lap]) / lap)
    check = {"first_loss": first_loss, "reference_loss": ref_loss,
             "tolerance": LOSS_TOL, "last_loss": host[-1],
             "finite": all(math.isfinite(x) for x in host),
             "falling": falling}
    print(f"[check] {check}", flush=True)
    pk = peaks_mod.peaks_for(devs[0].device_kind) if require_chip else None
    flops_tok = peaks_mod.train_flops_per_token(model, traffic["seq_len"])
    record.update(
        setup_s=t_open - t_start, seconds=seconds, check=check,
        compiles_in_window=counter.compiles - compiles0,
        counters={"steps": n_steps, "tokens": n_steps * tokens},
        series={"step_ms": [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]},
        values={"train_tokens_per_s": rate,
                "mfu": (100.0 * rate * flops_tok / pk["bf16_flops"]
                        if pk else None)},
        work={"flash_s": (record.get("traced_steps", 0)
                          * peaks_mod.flash_train_flops(
                              model, tr["batch_sequences"], traffic["seq_len"])
                          / pk["bf16_flops"]) if pk else None},
        attempted=n_steps, failed=0,
        correct=(abs(first_loss - ref_loss) < LOSS_TOL and check["finite"]
                 and falling and counter.compiles == compiles0),
        device=common.device_record(devs, chips))
    return record
