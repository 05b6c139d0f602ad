"""Benchmark entry — prints ONE JSON line.

Default workload: Llama-125M-class causal-LM training step (BASELINE.md
configs 2/5 scaled to one chip): bf16 params, seq 1024, full fused
fwd+bwd+AdamW in a single donated XLA executable
(paddle.incubate.fused_train_step — the framework's perf path; the
reference's analog is its fused CUDA optimizer + multi-stream executor).

Extra workloads (BASELINE configs 1 and 4), selected by argv[1] or
BENCH_WORKLOAD env: ``resnet50`` (images/sec) and ``deepfm`` (examples/sec).
The driver's default invocation still prints the flagship llama line.

Metrics: steady-state training tokens/sec AND model-FLOPs-utilisation
(MFU = model TFLOPs / chip peak bf16 TFLOPs; FLOPs/token = 6N + 12*L*h*s,
the PaLM-appendix accounting).

vs_baseline: the reference publishes no in-tree numbers (BASELINE.md —
"published": {}), so vs_baseline is measured against this framework's own
round-1 result (78,701.7 tokens/s; earlier installation, not reproduced)
— a self-referential trend, not a fabricated reference ratio.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

ROUND1_TOKENS_PER_SEC = 78701.7

# per-workload MFU floors (ROADMAP item 2 tripwire, PERF.md round-6
# promise): 0.95x the round-5 measurement (earlier installation, not
# reproduced on this one). Every bench line carries its
# floor so scripts/check_bench_regression.py can fail a round that
# regresses a workload — wins must stick. Raise a floor when a campaign
# lands a durable improvement.
MFU_FLOORS = {
    "llama125m_train_tokens_per_sec": round(0.5829 * 0.95, 4),
    "resnet50_train_images_per_sec": round(0.2509 * 0.95, 4),
    "deepfm_train_examples_per_sec": round(0.0036 * 0.95, 4),
    "bert_base_finetune_tokens_per_sec": round(0.3932 * 0.95, 4),
    "ppyoloe_s_train_images_per_sec": round(0.0763 * 0.95, 4),
}

# peak dense bf16 TFLOP/s per chip by generation
_PEAK_BF16 = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,  # v6e / Trillium
    "v6e": 918e12,
}


def _chip_peak_flops():
    """Peak bf16 FLOP/s of the current chip; ``None`` on the CPU backend
    (no MFU there). An accelerator missing from the table is an error,
    not a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    for key in sorted(_PEAK_BF16, key=len, reverse=True):
        if key in kind:
            return _PEAK_BF16[key]
    raise RuntimeError(
        f"no peak-FLOP/s entry for device_kind {dev.device_kind!r}; add it "
        "to bench._PEAK_BF16 with its source")


def _train_flops_per_token(cfg, n_params, seq):
    """PaLM-appendix accounting: 6*N (fwd+bwd matmuls) plus attention
    score/value FLOPs 12*L*h*s per token."""
    return 6.0 * n_params + 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq


def _round_history(metric):
    """{round_n: value} for a metric across past BENCH_r*.json artifacts
    (each stores the run's stdout tail: one JSON line per workload)."""
    import glob
    import re

    vals = {}
    here = os.path.dirname(os.path.abspath(__file__))
    for p in sorted(glob.glob(os.path.join(here, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)", p)
        if not m:
            continue
        try:
            data = json.load(open(p))
        except Exception:
            continue
        for line in str(data.get("tail", "")).splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except Exception:
                continue
            if rec.get("metric") == metric and rec.get("value"):
                vals[int(m.group(1))] = rec["value"]
    return vals


def _emit(rec, step=None, batch=None, items_per_batch=None):
    """Print one bench JSON line, enriched with:

    - ``mfu`` + ``model_tflops_per_sec`` from XLA HLO cost analysis of the
      fused step (when ``step``/``batch`` given and the record has no
      hand-accounted mfu already) — VERDICT r4 weak-2;
    - ``vs_prev_round`` / ``vs_baseline`` ratios against this framework's
      own BENCH_r*.json history (the reference publishes no numbers, so the
      trend is self-referential and says so).
    """
    if rec.get("mfu_floor") is None:
        rec["mfu_floor"] = MFU_FLOORS.get(rec.get("metric"))
    if step is not None and rec.get("mfu") is None:
        try:
            flops = step.lowered_flops(*batch)
        except Exception:
            flops = None
        peak = _chip_peak_flops()
        if flops and peak:
            per_item = flops / (items_per_batch or 1)
            achieved = rec["value"] * per_item
            rec["mfu"] = round(achieved / peak, 4)
            rec["model_tflops_per_sec"] = round(achieved / 1e12, 1)
            rec["mfu_accounting"] = "xla_hlo_cost_analysis"
    hist = _round_history(rec["metric"])
    rec["vs_prev_round"] = (round(rec["value"] / hist[max(hist)], 3)
                            if hist else None)
    if rec.get("vs_baseline") is None and hist:
        first_round = min(hist)
        rec["vs_baseline"] = round(rec["value"] / hist[first_round], 3)
        note = (
            f"vs_baseline is vs round-{first_round} self-measurement "
            f"({hist[first_round]}); reference publishes no in-tree numbers")
        # keep any workload-specific methodology note (e.g. bert_varlen's
        # compiles-included accounting) instead of clobbering it
        prior = rec.get("baseline_note")
        rec["baseline_note"] = (
            note if not prior or prior.startswith("reference publishes")
            else f"{prior}; {note}")
    if "metrics_snapshot" not in rec:
        # observability registry riding on every line (ISSUE 10): the
        # compact form (counters/gauges + histogram count/sum/p50/p99) so
        # check_bench_regression can later floor e.g. serving p99 the way
        # it floors MFU. Best-effort: a bench line must never fail on its
        # own telemetry.
        try:
            from paddle_tpu.observability import metrics as _obs_metrics

            rec["metrics_snapshot"] = _obs_metrics.compact_snapshot()
        except Exception:
            rec["metrics_snapshot"] = None
    print(json.dumps(rec))


def _bench_loop(step, make_batch, batch_sizes, steps, warmup, rebuild):
    """Shared sweep-then-measure loop; returns (items/sec, batch_size)."""
    import time

    def measure(bs, n_steps, n_warmup):
        batch = make_batch(bs)
        loss = None
        for _ in range(n_warmup):
            loss = step(*batch)
        if loss is not None:  # sync: drain compile + warmup steps
            float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(n_steps):
            loss = step(*batch)
        float(loss.numpy())
        return bs * n_steps / (time.perf_counter() - t0)

    best_bs, best_ips = None, 0.0
    for bs in batch_sizes:
        try:
            ips = measure(bs, max(steps // 3, 2), warmup)
        except Exception:
            step = rebuild()
            break
        if ips > best_ips:
            best_bs, best_ips = bs, ips
    if best_bs is None:
        best_bs = max(batch_sizes[0] // 2, 1)
    # best-of-3 on the final timed window: the steady-state loop is
    # sub-second at the CPU sizings, where a single-shot number swings
    # +/-25% with scheduler noise on a shared one-core host — enough to
    # trip the 0.95x round-over-round floor on an UNCHANGED workload.
    # max-of-N estimates the noise-free capability; the batch-size sweep
    # above stays single-shot (it only picks the shape).
    return max(measure(best_bs, steps, 1) for _ in range(3)), best_bs


def make_resnet(on_tpu):
    """ResNet workload builder (BASELINE config 1), shared by the bench
    loop and scripts/audit_hlo.py: returns (build, make_batch, sizing)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision import models

    if on_tpu:
        depth, img, steps, warmup, batch_sizes = 50, 224, 12, 2, [64, 128, 256]
    else:
        depth, img, steps, warmup, batch_sizes = 18, 32, 3, 1, [4]

    class WithLoss(paddle.nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, x, y):
            return F.cross_entropy(self.inner(x), y)

    def build():
        m = models.ResNet(models.BottleneckBlock if depth == 50
                          else models.BasicBlock, depth, num_classes=1000)
        m.bfloat16()
        m.train()
        opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                        parameters=m.parameters())
        return paddle.incubate.fused_train_step(WithLoss(m), opt)

    def make_batch(bs):
        x = paddle.to_tensor(
            np.random.randn(bs, 3, img, img).astype(np.float32)
        ).astype("bfloat16")
        y = paddle.to_tensor(np.random.randint(0, 1000, (bs,)))
        return x, y

    return build, make_batch, dict(steps=steps, warmup=warmup,
                                   batch_sizes=batch_sizes, img=img)


def bench_resnet50(on_tpu):
    """BASELINE config 1: ResNet-50 training images/sec, bf16, fused step."""
    import paddle_tpu as paddle

    paddle.seed(0)
    np.random.seed(0)
    build, make_batch, sz = make_resnet(on_tpu)
    step = build()
    img = sz["img"]
    ips, bs = _bench_loop(step, make_batch, sz["batch_sizes"], sz["steps"],
                          sz["warmup"], build)
    _emit({
        "metric": "resnet50_train_images_per_sec" if on_tpu
                  else "resnet18_cpu_train_images_per_sec",
        "value": round(ips, 1), "unit": "images/s", "vs_baseline": None,
        "batch_size": bs, "image_size": img,
        "baseline_note": "reference publishes no in-tree numbers",
    }, step=step, batch=make_batch(bs), items_per_batch=bs)


def make_deepfm(on_tpu, sparse_path="lazy"):
    """DeepFM workload builder (BASELINE config 4), shared by the bench
    loop, scripts/audit_hlo.py and scripts/bench_sparse_embedding.py.
    ``sparse_path``: "lazy" (Adam lazy_mode=True — row-sparse embedding
    grads + gather/update/scatter moments, the ISSUE 6 fast path) or
    "dense" (the pre-round-7 full-table path, kept for A/B)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models import DeepFM

    vocab, nfield, dense_dim = (1000001, 26, 13)
    if on_tpu:
        steps, warmup, batch_sizes = 20, 3, [4096, 8192, 16384]
    else:
        vocab, steps, warmup, batch_sizes = 10001, 4, 1, [256]

    class WithLoss(paddle.nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, ids, dense, label):
            return F.binary_cross_entropy(self.inner(ids, dense), label)

    def build():
        m = DeepFM(vocab, 9, dense_dim, nfield, layer_sizes=(512, 256, 128))
        m.train()
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=m.parameters(),
                                    lazy_mode=(sparse_path == "lazy"))
        return paddle.incubate.fused_train_step(WithLoss(m), opt)

    def make_batch(bs):
        ids = paddle.to_tensor(
            np.random.randint(0, vocab, (bs, nfield)).astype(np.int32))
        dense = paddle.to_tensor(
            np.random.randn(bs, dense_dim).astype(np.float32))
        label = paddle.to_tensor(
            np.random.randint(0, 2, (bs, 1)).astype(np.float32))
        return ids, dense, label

    return build, make_batch, dict(steps=steps, warmup=warmup,
                                   batch_sizes=batch_sizes, vocab=vocab,
                                   nfield=nfield)


def bench_deepfm(on_tpu):
    """BASELINE config 4: DeepFM (criteo config) training examples/sec.
    Default path is the round-7 lazy (row-sparse) one; set
    BENCH_DEEPFM_SPARSE=dense for the old full-table arm (the full A/B
    lives in scripts/bench_sparse_embedding.py)."""
    import paddle_tpu as paddle

    paddle.seed(0)
    np.random.seed(0)
    sparse_path = os.environ.get("BENCH_DEEPFM_SPARSE", "lazy")
    if sparse_path not in ("lazy", "dense"):
        raise SystemExit(
            f"BENCH_DEEPFM_SPARSE={sparse_path!r}: expected 'lazy' or "
            "'dense'")
    build, make_batch, sz = make_deepfm(on_tpu, sparse_path=sparse_path)
    step = build()
    ips, bs = _bench_loop(step, make_batch, sz["batch_sizes"], sz["steps"],
                          sz["warmup"], build)
    _emit({
        # the CPU smoke runs a 10k vocab (vs the real 1M) — its numbers
        # are not comparable to the TPU rounds, so it gets its own metric
        # name like every other workload's cpu variant
        "metric": "deepfm_train_examples_per_sec" if on_tpu
                  else "deepfm_cpu_train_examples_per_sec",
        "value": round(ips, 1), "unit": "examples/s", "vs_baseline": None,
        "batch_size": bs, "vocab": sz["vocab"],
        "sparse_path": sparse_path,
        "baseline_note": "reference publishes no in-tree numbers; MFU is "
                         "expected tiny (embedding-bound workload)",
    }, step=step, batch=make_batch(bs), items_per_batch=bs)


def make_ppyoloe(on_tpu):
    """PP-YOLOE workload builder (BASELINE config 3), shared by the bench
    loop and scripts/audit_hlo.py."""
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import PPYOLOE, PPYOLOEConfig

    if on_tpu:
        cfg = PPYOLOEConfig(depth_mult=0.33, width_mult=0.50, max_boxes=16)
        img, steps, warmup, batch_sizes = 640, 10, 2, [16, 32]
    else:
        cfg = PPYOLOEConfig(num_classes=4, depth_mult=0.33, width_mult=0.25,
                            max_boxes=4)
        img, steps, warmup, batch_sizes = 64, 3, 1, [2]

    def build():
        m = PPYOLOE(cfg)
        m.bfloat16()
        m.train()
        opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                        parameters=m.parameters())
        return paddle.incubate.fused_train_step(m, opt,
                                                loss_fn=lambda o: o[0])

    def make_batch(bs):
        x = paddle.to_tensor(
            np.random.randn(bs, 3, img, img).astype(np.float32)
        ).astype("bfloat16")
        g = cfg.max_boxes
        wh = np.random.uniform(img * 0.1, img * 0.5, (bs, g, 2))
        xy = np.random.uniform(0, img * 0.5, (bs, g, 2))
        gt_b = paddle.to_tensor(
            np.concatenate([xy, xy + wh], -1).astype(np.float32))
        gt_l = paddle.to_tensor(
            np.random.randint(0, cfg.num_classes, (bs, g)).astype(np.int64))
        return x, gt_b, gt_l

    return build, make_batch, dict(steps=steps, warmup=warmup,
                                   batch_sizes=batch_sizes, img=img)


def bench_ppyoloe(on_tpu):
    """BASELINE config 3: PP-YOLOE-s training images/sec (conv-heavy,
    640x640, full TAL/VFL/GIoU/DFL loss)."""
    import paddle_tpu as paddle

    paddle.seed(0)
    np.random.seed(0)
    build, make_batch, sz = make_ppyoloe(on_tpu)
    step = build()
    img = sz["img"]
    ips, bs = _bench_loop(step, make_batch, sz["batch_sizes"], sz["steps"],
                          sz["warmup"], build)
    _emit({
        "metric": "ppyoloe_s_train_images_per_sec" if on_tpu
                  else "ppyoloe_tiny_cpu_train_images_per_sec",
        "value": round(ips, 1), "unit": "images/s", "vs_baseline": None,
        "batch_size": bs, "image_size": img,
        "baseline_note": "reference publishes no in-tree numbers",
    }, step=step, batch=make_batch(bs), items_per_batch=bs)


def make_bert(on_tpu):
    """BERT fine-tune workload builder (BASELINE config 2), shared by the
    bench loop and scripts/audit_hlo.py."""
    import paddle_tpu as paddle
    from paddle_tpu.models import BertForSequenceClassification, bert_base, \
        bert_tiny

    if on_tpu:
        cfg = bert_base()
        seq, steps, warmup, batch_sizes = 128, 15, 3, [64, 128]
    else:
        cfg = bert_tiny()
        seq, steps, warmup, batch_sizes = 32, 3, 1, [4]
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0

    def build():
        m = BertForSequenceClassification(cfg)
        m.bfloat16()
        m.train()
        opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                     parameters=m.parameters())
        raw = paddle.incubate.fused_train_step(m, opt,
                                               loss_fn=lambda o: o[0])

        # labels must travel by keyword (position 2 is token_type_ids)
        def wrapped(ids, labels):
            return raw(ids, labels=labels)

        wrapped.lowered_flops = (
            lambda ids, labels: raw.lowered_flops(ids, labels=labels))
        wrapped.hlo_cost_report = (
            lambda ids, labels, **kw: raw.hlo_cost_report(
                ids, labels=labels, **kw))
        return wrapped

    def make_batch(bs):
        ids = paddle.to_tensor(
            np.random.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))
        labels = paddle.to_tensor(
            np.random.randint(0, cfg.num_labels, (bs,)).astype(np.int64))
        return ids, labels

    return build, make_batch, dict(steps=steps, warmup=warmup,
                                   batch_sizes=batch_sizes, seq=seq)


def bench_bert(on_tpu):
    """BASELINE config 2: BERT-base fine-tune (seq classification),
    tokens/sec — the ERNIE-3.0 / BERT fine-tune workload."""
    import paddle_tpu as paddle

    paddle.seed(0)
    np.random.seed(0)
    build, make_batch, sz = make_bert(on_tpu)
    step = build()
    seq = sz["seq"]
    ips, bs = _bench_loop(step, make_batch, sz["batch_sizes"], sz["steps"],
                          sz["warmup"], build)
    _emit({
        "metric": "bert_base_finetune_tokens_per_sec" if on_tpu
                  else "bert_tiny_cpu_finetune_tokens_per_sec",
        "value": round(ips * seq, 1), "unit": "tokens/s",
        "vs_baseline": None, "batch_size": bs, "seq_len": seq,
        "baseline_note": "reference publishes no in-tree numbers",
    }, step=step, batch=make_batch(bs), items_per_batch=bs * seq)


def bench_bert_varlen(on_tpu):
    """Variable-length BERT fine-tune stream, bucketing A/B (ISSUE 1
    tentpole): the SAME stream of distinct sequence lengths is driven
    through the fused train step twice — naive exact-length padding
    (one XLA compile per distinct batch shape) vs the shape-bucketed
    pipeline (BucketedBatchSampler + PadToBucket, compile count =
    O(buckets)). The dataset/arm harness lives in
    scripts/bench_bucketing.py (single source, also the 3-arm probe);
    wall time includes compiles on both arms — the compile cliff IS the
    measured effect — and tokens/s counts REAL (unpadded) tokens actually
    dispatched, so bucket padding waste and drop_last both show up
    honestly."""
    import sys

    import paddle_tpu as paddle

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import bench_bucketing as bb

    paddle.seed(0)
    np.random.seed(0)
    cfg, bs, lengths, boundaries, samples_per_len = \
        bb.default_sizing(tiny=not on_tpu)
    epochs = 2
    ds = bb.varlen_dataset(cfg, lengths, samples_per_len)

    def run_arm(arm):
        raw = bb.build_step(cfg, on_tpu)
        return bb.run_stream(raw, ds, bs, boundaries, arm, epochs)

    naive = run_arm("naive")
    pipe = run_arm("pipeline")
    _emit({
        "metric": "bert_varlen_bucketed_tokens_per_sec" if on_tpu
                  else "bert_varlen_cpu_bucketed_tokens_per_sec",
        "value": pipe["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_unbucketed": naive["tokens_per_sec"],
        "bucketing_speedup": round(pipe["tokens_per_sec"]
                                   / naive["tokens_per_sec"], 3),
        "compiles_bucketed": pipe["compiles"],
        "compiles_unbucketed": naive["compiles"],
        "pad_waste_bucketed": pipe["pad_waste"],
        "pad_waste_unbucketed": naive["pad_waste"],
        "num_buckets": len(boundaries),
        "distinct_lengths": len(lengths),
        "batch_size": bs,
        "baseline_note": "A/B over one varying-length stream; wall time "
                         "includes XLA compiles (the measured cliff); "
                         "tokens/s counts real (unpadded) tokens",
    })


def bench_overlap(on_tpu):
    """Host–device overlap A/B (ISSUE 3 tentpole): the SAME slow-host-
    loader token stream (per-item delay simulating tokenize/augment/IO)
    driven through identically-seeded fused BERT steps twice — inline
    iteration + per-step float(loss) fetch vs DevicePrefetcher +
    FusedTrainStep.drive deferred fetch. The harness lives in
    scripts/bench_overlap.py (single source, also the standalone probe and
    the slow-tier acceptance test). Compile time is excluded via one
    warmup step per arm (identical executables in both arms — the overlap,
    not the compile, is the effect under test); per-step losses must be
    bit-identical across arms."""
    import sys

    import paddle_tpu as paddle
    from paddle_tpu.core.flags import flag_value

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import bench_overlap as bo

    paddle.seed(0)
    np.random.seed(0)
    cfg, bs, seq, steps, delay = bo.default_sizing(tiny=not on_tpu)
    sync = bo.run_arm("sync", cfg, on_tpu, bs, seq, steps, delay)
    pipe = bo.run_arm("pipelined", cfg, on_tpu, bs, seq, steps, delay)
    pf = pipe.get("prefetch") or {}
    _emit({
        "metric": "overlap_pipelined_tokens_per_sec" if on_tpu
                  else "overlap_cpu_pipelined_tokens_per_sec",
        "value": pipe["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_sync": sync["tokens_per_sec"],
        "overlap_speedup": round(pipe["tokens_per_sec"]
                                 / sync["tokens_per_sec"], 3),
        "loss_bit_equal": sync["loss"] == pipe["loss"],
        "host_syncs_sync": sync["host_syncs"],
        "host_syncs_pipelined": pipe["host_syncs"],
        "avg_queue_depth": pf.get("avg_queue_depth"),
        "host_blocked_ms": pf.get("host_blocked_ms"),
        "prefetch_depth": int(flag_value("prefetch_depth", 2)),
        "batch_size": bs, "seq_len": seq, "steps": steps,
        "per_item_delay_s": delay,
        "baseline_note": "A/B over one slow-host-loader stream; warmup "
                         "compile excluded (identical in both arms); "
                         "deferred-fetch losses must be bit-equal to "
                         "per-step fetch",
    })


def bench_streaming(on_tpu):
    """Streaming data-plane A/B (ISSUE 13): the SAME deterministic record
    stream driven through an identically-seeded fused step from memory vs
    from atomic ``*.pdstream`` shards with per-record decode cost, a host
    thread pool, and an injected-flaky filesystem ("io.stream.read"
    transients riding the retry budget). The tracked value is the
    device-utilization RATIO (stream/mem), each util read off the PR-10
    ``io_host_blocked_ms`` backpressure telemetry — the ROADMAP item 3
    acceptance is >= 0.9x at CPU smoke scale. Per-step losses must be
    bit-identical across arms. Harness: scripts/bench_streaming.py."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import bench_streaming as bst

    res = bst.run_ab(tiny=not on_tpu)
    assert res["bit_exact"], "streaming arm diverged from in-memory arm"
    _emit({
        "metric": "ingest_stream_device_util_ratio" if on_tpu
                  else "ingest_cpu_stream_device_util_ratio",
        "value": res["util_ratio"], "unit": "ratio (stream/mem)",
        "vs_baseline": None,
        "device_util_stream": res["stream"]["device_util"],
        "device_util_mem": res["mem"]["device_util"],
        "examples_per_sec_stream": res["stream"]["examples_per_sec"],
        "examples_per_sec_mem": res["mem"]["examples_per_sec"],
        "host_blocked_ms_stream": res["stream"]["host_blocked_ms"],
        "avg_queue_depth_stream": res["stream"]["avg_queue_depth"],
        "bit_exact": res["bit_exact"],
        "n_records": res["n_records"],
        "batch_size": res["batch_size"],
        "decode_delay_s": res["decode_delay_s"],
        "flaky_read_period": res["flaky_read_period"],
        "baseline_note": "A/B over one deterministic record stream; util "
                         "= 1 - io_host_blocked_ms/wall per arm (the "
                         "PR-10 backpressure telemetry); losses bit-equal "
                         "across arms; stream arm includes injected "
                         "transient read failures absorbed by the retry "
                         "budget",
    })


def bench_serving(on_tpu):
    """LLM serving A/B (ISSUE 7 tentpole): one seeded Poisson multi-tenant
    request stream replayed through a naive batch-of-one ``model.generate``
    loop vs the paged-KV continuous-batching ``LLMEngine``. Greedy outputs
    must be bit-exact across arms and the engine's decode graph must not
    recompile inside the timed window (both asserted here — a serving win
    that breaks either is a broken win). The harness lives in
    scripts/bench_serving.py (single source, also the standalone probe and
    the acceptance test)."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import bench_serving as bsv

    # CPU-smoke timed windows are sub-second and single-shot numbers swing
    # +/-30% with scheduler noise on a shared one-core host (ISSUE 18: the
    # same reason _bench_loop takes best-of-3); replay each arm's window
    # and report its best run. TPU windows are long enough to stay at 1.
    rep = 1 if on_tpu else 3
    res = bsv.run_ab(tiny=not on_tpu, repeat=rep)
    assert res["bit_exact"], "engine diverged from batch-of-one greedy"
    assert res["engine"]["decode_compiles_in_window"] == 0, \
        "decode graph recompiled inside the timed window"
    _emit({
        "metric": "serving_engine_tokens_per_sec" if on_tpu
                  else "serving_cpu_engine_tokens_per_sec",
        "value": res["engine"]["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_naive": res["naive"]["tokens_per_sec"],
        "serving_speedup": res["speedup"],
        "p50_ms": res["engine"]["p50_ms"],
        "p99_ms": res["engine"]["p99_ms"],
        "p50_ms_naive": res["naive"]["p50_ms"],
        "p99_ms_naive": res["naive"]["p99_ms"],
        # engine-owned latency histograms (ISSUE 10): measured at the
        # engine's own sampling points, not by the bench clock
        "ttft_p50_ms": res["engine"]["ttft_p50_ms"],
        "ttft_p99_ms": res["engine"]["ttft_p99_ms"],
        "itl_p50_ms": res["engine"]["itl_p50_ms"],
        "itl_p99_ms": res["engine"]["itl_p99_ms"],
        "bit_exact": res["bit_exact"],
        "decode_compiles_in_window": res["engine"]["decode_compiles_in_window"],
        "evictions": res["engine"]["evictions"],
        "num_requests": res["num_requests"],
        "max_batch_size": res["max_batch_size"],
        "baseline_note": "A/B over one seeded Poisson request stream; "
                         "compiles warmed in both arms (steady-state "
                         "batching is the effect); greedy outputs "
                         "bit-exact across arms",
    })
    # prefix-cache sharing A/B (ISSUE 11): its own tracked metric line so
    # the r06+ regression tripwire guards the sharing win round over round
    sp = bsv.run_shared_prefix_ab(tiny=not on_tpu, repeat=rep)
    assert sp["bit_exact"], "sharing arm diverged from no-sharing greedy"
    _emit({
        "metric": "serving_shared_prefix_tokens_per_sec" if on_tpu
                  else "serving_cpu_shared_prefix_tokens_per_sec",
        "value": sp["sharing"]["effective_tokens_per_sec"],
        "unit": "tokens/s (prompt+generated)",
        "vs_baseline": None,
        "effective_tokens_per_sec_no_sharing":
            sp["no_sharing"]["effective_tokens_per_sec"],
        "sharing_speedup": sp["speedup"],
        "prefix_hit_ratio": sp["prefix_hit_ratio"],
        "prefix_blocks_reused": sp["sharing"]["prefix_blocks_reused"],
        "itl_p99_ms": sp["sharing"]["itl_p99_ms"],
        "bit_exact": sp["bit_exact"],
        "num_requests": sp["num_requests"],
        "prefix_len": sp["prefix_len"],
        "baseline_note": "A/B over one seeded shared-prefix multi-tenant "
                         "stream; effective tokens/s counts prompt tokens "
                         "served (shared blocks are the avoided work); "
                         "greedy outputs bit-exact across arms",
    })
    # quantized-serving A/B (ISSUE 14): int8 paged-KV pools at the SAME
    # pool byte budget as the fp32 arm — the tracked line is the int8
    # arm's tokens/s, plus a second line pinning the capacity ratio
    # (usable int8 blocks per fp32 block at equal bytes; deterministic
    # arithmetic, so the tripwire holds it exactly round over round)
    qz = bsv.run_quantized_ab(tiny=not on_tpu, repeat=rep)
    assert qz["deterministic"], \
        "int8-KV greedy decode was not deterministic run-to-run"
    _emit({
        "metric": "serving_quantized_tokens_per_sec" if on_tpu
                  else "serving_cpu_quantized_tokens_per_sec",
        "value": qz["int8"]["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_fp32": qz["fp32"]["tokens_per_sec"],
        "tokens_per_sec_ratio": qz["tokens_per_sec_ratio"],
        "capacity_ratio": qz["capacity_ratio"],
        "pool_blocks_fp32": qz["pool_blocks_fp32"],
        "pool_blocks_int8": qz["pool_blocks_int8"],
        "kv_bytes_saved": qz["kv_bytes_saved"],
        "queued_on_exhaustion_fp32": qz["fp32"]["queued_on_exhaustion"],
        "queued_on_exhaustion_int8": qz["int8"]["queued_on_exhaustion"],
        "evictions_fp32": qz["fp32"]["evictions"],
        "evictions_int8": qz["int8"]["evictions"],
        "deterministic": qz["deterministic"],
        "token_agreement_vs_fp32": qz["token_agreement_vs_fp32"],
        "num_requests": qz["num_requests"],
        "baseline_note": "A/B over one seeded Poisson burst; both arms "
                         "hold the SAME pool byte budget (int8 codes + "
                         "f32 scale sidecars vs fp32 payload); int8 "
                         "greedy token ids asserted identical "
                         "run-to-run",
    })
    _emit({
        "metric": "serving_quantized_capacity_ratio" if on_tpu
                  else "serving_cpu_quantized_capacity_ratio",
        "value": qz["capacity_ratio"],
        "unit": "ratio (int8 blocks / fp32 blocks at equal bytes)",
        "vs_baseline": None,
        "pool_blocks_fp32": qz["pool_blocks_fp32"],
        "pool_blocks_int8": qz["pool_blocks_int8"],
        "kv_bytes_saved": qz["kv_bytes_saved"],
        "baseline_note": "static pool arithmetic "
                         "(kv_pool_bytes_per_block) — the >=1.5x "
                         "concurrent-capacity acceptance, held exactly "
                         "by the regression tripwire",
    })
    # KV-tiering A/B (ISSUE 16): one seeded multi-session stream whose
    # prefix working set exceeds the device pool, replayed through a
    # never-evicted reference, a recompute-eviction arm (tier off) and a
    # host-RAM-tiered arm. The tracked line is the tiered arm's
    # EFFECTIVE tokens/s; the >=1.5x-vs-recompute acceptance and greedy
    # bit-exactness across all arms (incl. the int8-KV replay) are
    # asserted — tiering moves pages, never math.
    tr = bsv.run_tiering_ab(tiny=not on_tpu)
    assert tr["bit_exact"], \
        "tiered/recompute arm diverged from the never-evicted greedy " \
        "reference"
    assert tr["int8_bit_exact"], \
        "int8 tiered arm diverged from the int8 never-evicted reference"
    _emit({
        "metric": "serving_tiering_tokens_per_sec" if on_tpu
                  else "serving_cpu_tiering_tokens_per_sec",
        "value": tr["tiered"]["effective_tokens_per_sec"],
        "unit": "tokens/s (prompt+generated)",
        "vs_baseline": None,
        "effective_tokens_per_sec_recompute":
            tr["recompute"]["effective_tokens_per_sec"],
        "effective_tokens_per_sec_resident":
            tr["resident"]["effective_tokens_per_sec"],
        "tiering_speedup": tr["speedup"],
        "int8_tiering_speedup": tr["int8_speedup"],
        "kv_spills": tr["kv_spills"],
        "kv_revives": tr["kv_revives"],
        "bit_exact": tr["bit_exact"],
        "int8_bit_exact": tr["int8_bit_exact"],
        "num_requests": tr["num_requests"],
        "n_sessions": tr["n_sessions"],
        "prefix_len": tr["prefix_len"],
        "pool_blocks": tr["pool_blocks"],
        "host_blocks": tr["host_blocks"],
        "baseline_note": "one seeded multi-session stream (working set "
                         "> device pool) through never-evicted vs "
                         "recompute-eviction vs host-RAM-tiered pools; "
                         "effective tokens/s counts revived prefix "
                         "tokens as served; greedy outputs bit-exact "
                         "across arms in both the fp32 and int8-KV "
                         "replays",
    })
    # fleet scaling A/B (ISSUE 12): 1-replica vs N-replica subprocess
    # fleets behind the same Router/RPC path, so the tracked line is pure
    # replica parallelism — the ROADMAP item 1 tokens/s-scaling evidence,
    # guarded by the per-platform regression tripwire from the next round.
    # ALWAYS the CPU smoke, even on a TPU box: ReplicaSupervisor pins
    # replica subprocesses to the CPU backend (N processes cannot share
    # one accelerator), so the reference engine must run on CPU too —
    # bit-exactness is a within-backend guarantee — and labeling the line
    # as a TPU metric would misrepresent CPU throughput. A TPU-replica
    # fleet line lands with the sharded-replica work (ROADMAP item 1
    # remainder). The A/B runs in a CPU SUBPROCESS: this process may
    # already hold the TPU backend, and jax backends are process-wide.
    import json as _json
    import subprocess
    import sys as _sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [_sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts", "bench_serving.py"),
         "--workload", "fleet", "--fleet", "3", "--tiny"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, f"fleet A/B failed: {r.stderr[-2000:]}"
    fl = _json.loads(r.stdout)
    assert fl["bit_exact"], \
        "fleet diverged from the in-process engine greedy reference"
    _emit({
        "metric": "serving_cpu_fleet_tokens_per_sec",
        "value": fl["fleet"]["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_single_replica": fl["single"]["tokens_per_sec"],
        "fleet_scaling": fl["scaling"],
        "n_replicas": fl["n_replicas"],
        "redispatches": fl["fleet"]["redispatches"],
        "bit_exact": fl["bit_exact"],
        "num_requests": fl["num_requests"],
        "baseline_note": "one seeded Poisson burst through 1-replica vs "
                         "N-replica subprocess fleets (same Router/RPC "
                         "path in both arms, CPU replicas by design); "
                         "outputs bit-exact vs the in-process CPU "
                         "engine",
    })
    # model-parallel fleet A/B (ISSUE 19): a llama whose fp32 weights +
    # KV pool exceed the per-device byte budget — unservable on any
    # single-device replica — runs on tp=2 replica GROUPS (one Router
    # slot = two coordinated worker processes over jax.distributed),
    # against the largest ladder config that does fit one device on the
    # same device count. The tracked line is the sharded arm's tokens/s:
    # fleet-scale serving of a model that does not fit one device. CPU
    # subprocess for the same backend reasons as the fleet line.
    r = subprocess.run(
        [_sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts", "bench_serving.py"),
         "--workload", "tpfleet", "--tiny"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, f"tpfleet A/B failed: {r.stderr[-2000:]}"
    tpf = _json.loads(r.stdout)
    assert tpf["bit_exact"], \
        "tp-sharded fleet diverged from the in-process engine reference"
    _emit({
        "metric": "serving_cpu_tpfleet_tokens_per_sec",
        "value": tpf["sharded"]["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_single_device_config":
            tpf["single"]["tokens_per_sec"],
        "tp": tpf["tp"],
        "n_groups": tpf["n_groups"],
        "n_devices": tpf["n_devices"],
        "device_budget_bytes": tpf["device_budget_bytes"],
        "big_model_device_bytes": tpf["big_model_device_bytes"],
        "big_model_shard_bytes": tpf["big_model_shard_bytes"],
        "bit_exact": tpf["bit_exact"],
        "num_requests": tpf["num_requests"],
        "baseline_note": "one seeded burst through 2 tp=2 replica "
                         "groups serving a llama whose weights + KV "
                         "pool exceed the per-device budget, vs the "
                         "largest single-device config that fits on "
                         "the same device count; each arm bit-exact "
                         "vs its in-process CPU engine reference",
    })
    # disaggregated prefill/decode A/B (ISSUE 15): colocated vs
    # role-split fleets of the SAME size on the long-prompt mix. The
    # tracked line is the split arm's tokens/s; the headline contract —
    # decode-worker ITL p99 at or under the colocated arm's — rides the
    # line as fields (engine-owned histograms via the stats RPC). CPU
    # subprocess for the same backend reasons as the fleet line.
    r = subprocess.run(
        [_sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts", "bench_serving.py"),
         "--workload", "disagg", "--fleet", "3", "--tiny"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, f"disagg A/B failed: {r.stderr[-2000:]}"
    dg = _json.loads(r.stdout)
    assert dg["bit_exact"], \
        "disagg fleet diverged from the in-process engine reference"
    _emit({
        "metric": "serving_cpu_disagg_tokens_per_sec",
        "value": dg["disagg"]["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": None,
        "tokens_per_sec_colocated": dg["colocated"]["tokens_per_sec"],
        "decode_itl_p99_ms_disagg": dg["disagg"]["decode_itl_p99_ms"],
        "decode_itl_p99_ms_colocated":
            dg["colocated"]["decode_itl_p99_ms"],
        "itl_p99_ratio": dg["itl_p99_ratio"],
        "prefill_handoffs": dg["disagg"]["prefill_handoffs"],
        "kv_transfer_retries": dg["disagg"]["kv_transfer_retries"],
        "n_replicas": dg["n_replicas"],
        "roles": dg["roles"],
        "bit_exact": dg["bit_exact"],
        "num_requests": dg["num_requests"],
        "long_prompt_len": dg["long_prompt_len"],
        "baseline_note": "one seeded long-prompt mix through colocated "
                         "vs 1-prefill+2-decode subprocess fleets of "
                         "equal size; decode-worker ITL p99 is "
                         "engine-owned (stats RPC after a post-warm "
                         "metrics reset); outputs bit-exact vs the "
                         "in-process CPU engine",
    })
    # multi-tenant QoS A/B (ISSUE 17): the SAME interactive stream runs
    # uncontended and under a batch-tier flood + abuser burst on one
    # engine with tenants configured. The tracked line is the contended
    # latency-tier p99 TTFT; the uncontended reference, the ratio and
    # the abuser's quota-paced throughput ride the line as fields, and
    # interactive outputs must be bit-exact across arms (QoS changes
    # WHEN work runs, never WHICH tokens).
    qs = bsv.run_qos_ab(tiny=not on_tpu)
    assert qs["bit_exact"], \
        "contended interactive outputs diverged from the uncontended run"
    _emit({
        "metric": "serving_qos_lat_ttft_p99_ms" if on_tpu
                  else "serving_cpu_qos_lat_ttft_p99_ms",
        "value": qs["contended"]["lat_ttft_p99_ms"], "unit": "ms",
        "vs_baseline": None,
        "lat_ttft_p99_ms_uncontended":
            qs["uncontended"]["lat_ttft_p99_ms"],
        "lat_ttft_p99_ratio": qs["lat_ttft_p99_ratio"],
        "abuser_tokens_per_sec":
            qs["contended"]["abuser_tokens_per_sec"],
        "abuser_quota_tokens_per_sec":
            qs["contended"]["abuser_quota_tokens_per_sec"],
        "quota_throttled": qs["contended"]["quota_throttled"],
        "batch_yields": qs["contended"]["batch_yields"],
        "tenant_tokens": qs["contended"]["tenant_tokens"],
        "bit_exact": qs["bit_exact"],
        "num_requests": qs["num_requests"],
        "baseline_note": "one warmed engine, tenants configured "
                         "(interactive w=4, batch tier, abuser behind a "
                         "token-rate bucket); latency-tier TTFT is "
                         "bench-timed per tenant (the engine histogram "
                         "deliberately carries no tenant label); "
                         "interactive outputs bit-exact across arms",
    })
    # integrity-sentinel audit overhead A/B (ISSUE 20): the same burst
    # through ONE warmed subprocess fleet with audit_fraction 0.0 vs
    # 0.1. The tracked line is the audited arm's latency-tier TTFT p99;
    # the audit-off reference, the ratio (gated at ~1.1x in the
    # workload itself) and the audits-run count ride as fields, and
    # both arms must match the in-process greedy reference bit-exactly
    # (auditing reads streams, never changes them). CPU subprocess for
    # the same backend reasons as the fleet line.
    r = subprocess.run(
        [_sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts", "bench_serving.py"),
         "--workload", "audit", "--fleet", "3", "--tiny"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, f"audit A/B failed: {r.stderr[-2000:]}"
    au = _json.loads(r.stdout)
    assert au["bit_exact"], \
        "audited fleet diverged from the in-process engine reference"
    _emit({
        "metric": "serving_cpu_audit_ttft_p99_ms",
        "value": au["audit_on"]["ttft"]["p99_ms"], "unit": "ms",
        "vs_baseline": None,
        "ttft_p99_ms_audit_off": au["audit_off"]["ttft"]["p99_ms"],
        "ttft_p99_ratio": au["ttft_p99_ratio"],
        "ttft_p99_within_bound": au["ttft_p99_within_bound"],
        "audit_fraction": au["audit_fraction"],
        "audits_run": au["audit_on"]["audits_run"],
        "audit_mismatches": au["audit_on"]["audit_mismatches"],
        "bit_exact": au["bit_exact"],
        "num_requests": au["num_requests"],
        "baseline_note": "one warmed 3-replica subprocess fleet, same "
                         "seeded burst with sampled output audits off "
                         "vs on (fraction 0.1, batch-tier replays on a "
                         "different replica); outputs bit-exact vs the "
                         "in-process CPU engine in both arms",
    })


def make_llama(on_tpu):
    """Flagship llama workload builder, shared by main() and
    scripts/audit_hlo.py: ``build()`` returns ``(step, n_params)``."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_125m

    if on_tpu:
        cfg = llama_125m()
        seq, steps, warmup = 1024, 15, 3
        batch_sizes = [8, 16, 32]  # 64 OOMs on v5e and poisons the run
    else:  # CI / CPU smoke sizing
        from paddle_tpu.models import llama_tiny

        cfg = llama_tiny()
        seq, steps, warmup = 64, 4, 1
        batch_sizes = [2]

    def loss_of(out):
        return out[0] if isinstance(out, (tuple, list)) else out

    def build():
        model = LlamaForCausalLM(cfg)
        model.bfloat16()
        model.train()
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        n = sum(int(np.prod(p.shape)) for p in model.parameters())
        return paddle.incubate.fused_train_step(model, opt,
                                                loss_fn=loss_of), n

    def make_batch(bs):
        ids = paddle.to_tensor(
            np.random.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))
        labels = paddle.to_tensor(
            np.random.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))
        return ids, labels

    return build, make_batch, dict(steps=steps, warmup=warmup,
                                   batch_sizes=batch_sizes, seq=seq,
                                   cfg=cfg)


def main():
    import paddle_tpu as paddle

    paddle.seed(0)
    np.random.seed(0)

    on_tpu = True
    try:
        import jax

        on_tpu = jax.default_backend() not in ("cpu",)
    except Exception:
        pass

    build, make_batch, sz = make_llama(on_tpu)
    cfg, seq = sz["cfg"], sz["seq"]
    steps, warmup, batch_sizes = sz["steps"], sz["warmup"], sz["batch_sizes"]
    step, n_params = build()
    build_step = build

    def rebuild():
        # OOM invalidates the donated param buffers — rebuild fresh
        nonlocal n_params
        s, n_params = build_step()
        return s

    seqs_per_sec, best_bs = _bench_loop(step, make_batch, batch_sizes, steps,
                                        warmup, rebuild)
    tokens_per_sec = seqs_per_sec * seq

    # which attention kernel the traced step chose (shapes + backend
    # decide; a kernel that fails to build raises, it does not reroute)
    import importlib

    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    attn_path = fa.LAST_PATH

    flops_per_token = _train_flops_per_token(cfg, n_params, seq)
    achieved = tokens_per_sec * flops_per_token
    peak = _chip_peak_flops()
    mfu = round(achieved / peak, 4) if peak else None

    _emit({
        "metric": "llama125m_train_tokens_per_sec" if on_tpu
                  else "llama_tiny_cpu_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / ROUND1_TOKENS_PER_SEC, 3)
                       if on_tpu else 1.0,
        "mfu": mfu,
        "model_tflops_per_sec": round(achieved / 1e12, 1),
        "mfu_accounting": "palm_6N_plus_attention",
        "batch_size": best_bs,
        "seq_len": seq,
        "attn_path": attn_path,
        "baseline_note": "vs_baseline is vs round-1 self-measurement "
                         "(78701.7 tok/s); reference publishes no numbers",
    })


if __name__ == "__main__":
    import sys
    import traceback

    import jax
    from paddle_tpu.jit.cache import place_compile_cache

    place_compile_cache()
    workload = (sys.argv[1] if len(sys.argv) > 1
                else os.environ.get("BENCH_WORKLOAD", "all"))
    _on_tpu = jax.default_backend() != "cpu"
    _workloads = {
        "resnet50": lambda: bench_resnet50(_on_tpu),
        "deepfm": lambda: bench_deepfm(_on_tpu),
        "bert": lambda: bench_bert(_on_tpu),
        "bert_varlen": lambda: bench_bert_varlen(_on_tpu),
        "overlap": lambda: bench_overlap(_on_tpu),
        "streaming": lambda: bench_streaming(_on_tpu),
        "serving": lambda: bench_serving(_on_tpu),
        "ppyoloe": lambda: bench_ppyoloe(_on_tpu),
        # the flagship llama line prints LAST (the driver parses the tail)
        "llama": main,
    }
    if workload in _workloads:
        _workloads[workload]()
    elif workload == "all":
        # ALL BASELINE workloads, one JSON line each. A workload that
        # raises is printed and the rest still run, but the run exits
        # non-zero: a missing line must not read as a pass.
        failed = []
        for name, fn in _workloads.items():
            if _on_tpu:
                # one process: re-initializing the chip runtime per
                # workload is minutes of dead time, and the device is
                # exclusive anyway
                try:
                    fn()
                except Exception:
                    traceback.print_exc()
                    failed.append(name)
            else:
                # CPU smoke: one FRESH SUBPROCESS per workload (ISSUE
                # 18). In-process, a late workload measures 15-25% below
                # what the same code reports solo (shared_prefix: ~16.1k
                # tok/s solo vs ~12.7k after seven workloads' heaps and
                # jit caches pile up in the parent, on an idle host) —
                # enough to false-trip the 0.95x round-over-round floor
                # on UNCHANGED code. Isolation makes every line measure
                # what its solo run measures, independent of run order;
                # import overhead is seconds per workload and never
                # inside a timed window.
                import subprocess

                if subprocess.run([sys.executable,
                                   os.path.abspath(__file__),
                                   name]).returncode:
                    failed.append(name)
        if failed:
            sys.exit(f"bench workloads failed: {', '.join(failed)}")
    else:
        sys.exit(f"unknown workload {workload!r}; expected "
                 f"{' | '.join(_workloads)} | all")
