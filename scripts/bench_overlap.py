"""Host–device overlap A/B harness (ISSUE 3 tentpole, PERF.md discipline).

Drives ONE fixed-shape token stream with a deliberately slow host loader
(per-item delay simulating tokenization / augmentation / storage reads)
through an identically-seeded fused BERT train step twice:

  sync       inline loader iteration + ``float(loss)`` after every step —
             each step pays host batch production, H2D transfer AND a
             device→host metric round-trip (measured in PERF.md)
             serially
  pipelined  ``DevicePrefetcher`` (depth ``FLAGS_prefetch_depth``) +
             ``FusedTrainStep.drive(log_every=...)``: the transfer thread
             stages batch N+1 while the device runs batch N, and the
             loss/guard fetch is amortized over the log window

The XLA compile is identical in both arms and NOT the effect under test
(unlike bench_bucketing), so one same-shape warmup step runs before the
timed window in each arm. tokens/s counts the fixed-shape stream's real
tokens; both arms must produce bit-identical per-step losses (asserted in
the summary) — deferral changes WHEN metrics are read, never the math.

The harness (``default_sizing`` / ``slow_loader`` / ``build_step`` /
``run_arm``) is also imported by bench.py's ``overlap`` workload and the
slow-tier acceptance test so the bench line, the probe and the test can
never drift apart.

Usage:
  python scripts/bench_overlap.py [--delay 0.004] [--steps 32]
      [--batch-size 8] [--seq 32] [--log-every 10] [--depth 2] [--tiny]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def default_sizing(tiny):
    """(cfg, bs, seq, steps, per_item_delay_s) shared by this probe,
    bench.py's overlap workload and the slow-tier acceptance test."""
    from paddle_tpu.models import bert_base, bert_tiny

    cfg = bert_tiny() if tiny else bert_base()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    if tiny:
        bs, seq, steps, delay = 4, 24, 24, 0.004
    else:
        bs, seq, steps, delay = 16, 128, 40, 0.002
    return cfg, bs, seq, steps, delay


def slow_loader(cfg, n_samples, bs, seq, delay, seed=0):
    """Map-style (ids[seq], label) dataset whose __getitem__ sleeps
    ``delay`` seconds — the simulated per-item host cost."""
    from paddle_tpu import io

    rng = np.random.RandomState(seed)
    xs = rng.randint(1, cfg.vocab_size, (n_samples, seq)).astype(np.int32)
    ys = rng.randint(0, cfg.num_labels, (n_samples,)).astype(np.int64)

    class SlowDS(io.Dataset):
        def __getitem__(self, i):
            time.sleep(delay)
            return xs[i], ys[i]

        def __len__(self):
            return n_samples

    return io.DataLoader(SlowDS(), batch_size=bs, shuffle=False,
                         drop_last=True)


def build_step(cfg, on_tpu):
    """Identically-seeded fused BERT fine-tune step; labels are positional
    so ``drive`` can splat loader batches directly."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models import BertForSequenceClassification

    paddle.seed(0)

    class WithLoss(nn.Layer):
        def __init__(self):
            super().__init__()
            self.inner = BertForSequenceClassification(cfg)

        def forward(self, ids, labels):
            return self.inner(ids, labels=labels)[0]

    m = WithLoss()
    if on_tpu:
        m.bfloat16()
    m.train()
    opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                 parameters=m.parameters())
    return paddle.incubate.fused_train_step(m, opt)


def run_arm(arm, cfg, on_tpu, bs, seq, steps, delay, log_every=10,
            depth=None, seed=0):
    """One full A/B arm: fresh identically-seeded step + fresh stream."""
    import paddle_tpu as paddle
    from paddle_tpu import jit

    step = build_step(cfg, on_tpu)
    loader = slow_loader(cfg, steps * bs, bs, seq, delay, seed=seed)
    # same-shape warmup: compile (identical across arms) stays out of the
    # timed window; it advances the optimizer one step in BOTH arms, so
    # loss parity is preserved
    wx = paddle.to_tensor(np.ones((bs, seq), np.int32))
    wy = paddle.to_tensor(np.zeros((bs,), np.int64))
    float(step(wx, wy).numpy())

    t0 = time.perf_counter()
    if arm == "sync":
        losses, n = [], 0
        for batch in loader:
            if n >= steps:
                break
            ids, labels = batch
            loss = step(ids, labels)
            losses.append(float(loss.numpy()))  # per-step host fetch
            n += 1
        host_syncs = n
        prefetch_stats = None
    elif arm == "pipelined":
        hist = step.drive(loader, steps=steps, log_every=log_every,
                          prefetch_depth=depth)
        losses, n = hist["loss"], hist["steps"]
        host_syncs = hist["host_syncs"]
        prefetch_stats = hist["prefetch"]
    else:
        raise ValueError(f"unknown arm {arm!r}")
    wall = time.perf_counter() - t0

    stats = jit.cache_stats(step._stats_name) or {}
    rec = {
        "arm": arm,
        "tokens_per_sec": round(n * bs * seq / wall, 1),
        "wall_s": round(wall, 2),
        "steps": n,
        "host_syncs": host_syncs,
        "compiles": stats.get("compiles", 0),
        "loss": losses,
    }
    if prefetch_stats is not None:
        rec["prefetch"] = prefetch_stats
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--delay", type=float, default=None,
                   help="per-item host delay in seconds")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--depth", type=int, default=None,
                   help="prefetch depth (default FLAGS_prefetch_depth)")
    p.add_argument("--tiny", action="store_true",
                   help="force bert_tiny sizing (default on CPU)")
    args = p.parse_args()

    on_tpu = True
    try:
        import jax

        on_tpu = jax.default_backend() not in ("cpu",)
    except Exception:
        pass
    tiny = args.tiny or not on_tpu

    cfg, bs, seq, steps, delay = default_sizing(tiny)
    bs = args.batch_size or bs
    seq = args.seq or seq
    steps = args.steps or steps
    delay = args.delay if args.delay is not None else delay

    print(json.dumps({
        "config": {"model": "bert_tiny" if tiny else "bert_base",
                   "batch_size": bs, "seq": seq, "steps": steps,
                   "per_item_delay_s": delay,
                   "log_every": args.log_every}}))
    arms = {}
    for arm in ("sync", "pipelined"):
        arms[arm] = run_arm(arm, cfg, on_tpu, bs, seq, steps, delay,
                            log_every=args.log_every, depth=args.depth)
        printable = {k: v for k, v in arms[arm].items() if k != "loss"}
        print(json.dumps(printable))
    bit_equal = arms["sync"]["loss"] == arms["pipelined"]["loss"]
    print(json.dumps({
        "summary": {
            "overlap_speedup": round(arms["pipelined"]["tokens_per_sec"]
                                     / arms["sync"]["tokens_per_sec"], 3),
            "loss_bit_equal": bit_equal,
            "host_syncs": {a: arms[a]["host_syncs"] for a in arms},
        }}))
    if not bit_equal:
        sys.exit("FAIL: deferred-fetch losses diverged from per-step fetch")


if __name__ == "__main__":
    main()
