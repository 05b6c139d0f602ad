#!/usr/bin/env python
"""Chaos drill for the elastic supervision layer: kill, preempt, and hang
a REAL 2-worker launcher job and prove bit-exact end-to-end recovery —
plus the divergence drill (``--drill spike``): poison a batch window
mid-run and prove the sentinel detects, rolls back, skips, and recovers.

Orchestrator mode (default — run it directly)::

    python scripts/chaos_train.py [--out DIR] [--scenarios kill,preempt,hang]
    python scripts/chaos_train.py --drill spike
    python scripts/chaos_train.py --drill plan
    python scripts/chaos_train.py --drill stream

``--drill stream`` (ISSUE 13) reruns kill/preempt with the workers
training over a slow+flaky SHARDED RECORD STREAM (``io.StreamingDataset``
over atomic ``*.pdstream`` shards, per-rank shard assignment, thread-pool
decode, injected ``io.stream.read`` transients riding the retry budget)
with per-rank cursor checkpoints — recovery must be bit-exact on BOTH
ranks — plus a corrupt-shard arm that must finish via the quarantine
skip budget (``io_records_quarantined_total`` counted) instead of
crashing.

``--drill plan`` reruns the kill/preempt/hang scenarios with the worker
training under a dp=2 x tp=2 **sharded plan** (column/row tp split,
zero1 moments over dp, a virtual 8-device CPU mesh inside a single
worker process): every step compiles through ``compile_step_with_plan``,
every checkpoint records the plan fingerprint, ``auto_resume(plan=...)``
re-validates it on restart, and the recovered loss sequence must be
bit-identical to the uninterrupted sharded baseline (ROADMAP item 3
acceptance).

``--drill spike`` runs three single-process jobs: an uninterrupted clean
**baseline**; a **control** with fault site ``train.spike`` poisoning one
metric-fetch window (inputs scaled 1e3 — finite-but-huge loss, invisible
to the NaN guard) and ``FLAGS_sentinel_action=none``; and a **sentinel**
job with the same poison and ``FLAGS_sentinel_action=rollback``. The
drill asserts the control visibly diverges, while the sentinel job
detects the spike at the window boundary, rolls back to
``latest_healthy_step()``, skips the poisoned window's batches, and
finishes with a final loss within tolerance of the clean baseline.

runs an uninterrupted 2-worker baseline job, then one chaos job per
scenario, each under ``python -m paddle_tpu.distributed.launch``:

- ``kill``:    rank 1 SIGKILLs itself mid-epoch (fault site ``proc.kill``)
               — the supervisor sees the -9 exit, kills the group, and
               restarts it (consumes restart budget).
- ``preempt``: every rank receives SIGTERM at a window boundary; drive()
               finishes the window, writes a committed checkpoint, and
               exits 123 — the supervisor relaunches WITHOUT consuming
               restart budget.
- ``hang``:    rank 1 wedges (fault site ``train.stall``) with the
               in-process stall guard off; its heartbeats go stale past
               FLAGS_worker_hang_timeout_s, the watchdog SIGTERM→SIGKILLs
               the group, and the budgeted restart resumes it.

Every job writes a per-step loss log keyed by GLOBAL step (steps retrained
after a restart are logged again). The drill asserts, per scenario:

1. the job completes (exit 0) within its restart budget;
2. every global step's loss is single-valued across incarnations — i.e.
   replayed steps reproduced bit-identical losses;
3. the full per-step loss sequence equals the uninterrupted baseline's
   bit-for-bit;
4. for ``preempt``: the launcher reported the relaunch as budget-free.

Worker mode is selected automatically when the launcher's env
(``PADDLE_TRAINER_ID`` + ``CHAOS_OUT``) is present: a deterministic
bucketed varlen regression trained through ``FusedTrainStep.drive`` with
checkpoint+sampler persistence at every metric-fetch window.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EPOCHS = 2
WINDOW = 3          # log_every: checkpoint / loss-log cadence
BATCH = 4
N_SAMPLES = 48      # -> 12 batches/epoch, 24 global steps
FEATS = 4
BOUNDARIES = [8, 16, 32]


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def worker_main():
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.io as io
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.launch import heartbeat
    from paddle_tpu.incubate.fused_train_step import FusedTrainStep
    from paddle_tpu.utils import fault_injection as fi

    # the gap between the bootstrap heartbeat and drive()'s first window
    # spans the framework import + first XLA compile — beat once here so a
    # tight watchdog timeout cannot mistake setup for a hang
    heartbeat.write(step=None)

    out = os.environ["CHAOS_OUT"]
    scenario = os.environ.get("CHAOS_SCENARIO", "none")
    chaos_step = int(os.environ.get("CHAOS_STEP", "0"))
    chaos_rank = int(os.environ.get("CHAOS_RANK", "-1"))
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    with_plan = bool(os.environ.get("CHAOS_PLAN"))
    stream_dir = os.environ.get("CHAOS_STREAM")

    paddle.seed(0)
    np.random.seed(0)

    # deterministic varlen dataset (same on every rank / incarnation)
    rng = np.random.RandomState(5)
    lengths = rng.randint(3, 25, size=N_SAMPLES)
    xs = [rng.randn(int(n), FEATS).astype("float32") for n in lengths]
    ys = rng.randn(N_SAMPLES).astype("float32")

    class VarLen(io.Dataset):
        def __len__(self):
            return N_SAMPLES

        def __getitem__(self, i):
            return xs[i], ys[i]

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(FEATS, 1)

        def forward(self, x, y, mask):
            tok = self.proj(x)[:, :, 0] * mask          # [B, L]
            pred = tok.sum(axis=1) / mask.sum(axis=1)   # masked mean
            d = pred - y
            return (d * d).mean()

    class PlanNet(nn.Layer):
        """Two Linears so the drill's tp axis has a real column/row split
        (the 1-wide proj of Net gives tp nothing to shard)."""

        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(FEATS, 8)
            self.fc2 = nn.Linear(8, 1)

        def forward(self, x, y, mask):
            tok = self.fc2(paddle.tanh(self.fc1(x)))[:, :, 0] * mask
            pred = tok.sum(axis=1) / mask.sum(axis=1)   # masked mean
            d = pred - y
            return (d * d).mean()

    plan = None
    if with_plan:
        # the --plan drill: a dp x tp sharded plan (zero1 moments over
        # dp) on a virtual CPU mesh — kill/preempt/hang restarts must be
        # bit-exact THROUGH the sharded layouts, and the checkpoint's
        # plan fingerprint must admit the (identical) restore plan
        from paddle_tpu.distributed.plan import Plan

        plan = Plan.build(
            {"dp": 2, "tp": 2},
            ["dp",
             ("tp", {"rules": (("*fc1*", {1: "tp"}),
                               ("*fc2*", {0: "tp"}))}),
             ("zero1", {"axis": "dp"})])

    model = PlanNet() if with_plan else Net()
    if with_plan:
        # AdamW so the zero1 arm has REAL moment buffers to shard, save
        # and restore — with momentum-less SGD the zero1 layout would be
        # applied to nothing and the drill would never exercise sharded
        # optimizer-state round-trips
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     parameters=model.parameters())
    else:
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
    fstep = FusedTrainStep(model, opt, plan=plan)
    if stream_dir:
        # the --drill stream data plane: a slow+flaky sharded record
        # stream read through StreamingDataset instead of in-memory
        # arrays. Each rank owns its shard slice (sorted-manifest
        # round-robin), decodes on the host thread pool (the sleep is
        # the simulated tokenize cost), pads through the SAME
        # PadToBucket collate as the base drill, and checkpoints its
        # cursor per rank. Workers run coordination-free
        # (PADDLE_SKIP_DIST_INIT): ranks train DIFFERENT data, so their
        # model replicas diverge by design and each rank owns a private
        # checkpoint directory — the supervision layer (heartbeats,
        # watchdog, restart budget) still covers the whole group.
        import time as _time_mod

        def slow_decode(payload):
            _time_mod.sleep(0.002)
            return io.unpack_arrays(payload)

        loader = io.StreamingDataset(
            stream_dir, batch_size=BATCH, num_workers=2,
            decode_fn=slow_decode,
            collate_fn=io.PadToBucket(BOUNDARIES, as_tensor=False),
            max_skips_per_epoch=int(
                os.environ.get("CHAOS_STREAM_SKIPS", "0")),
            name=f"chaos_stream.rank{rank}")
        ckpt_dir = os.path.join(out, f"ckpt.rank{rank}")
    else:
        sampler = io.BucketedBatchSampler(
            VarLen(), batch_size=BATCH, boundaries=BOUNDARIES, shuffle=True,
            seed=11, lengths=lengths.tolist(), drop_last=True)
        loader = io.DataLoader(VarLen(), batch_sampler=sampler,
                               collate_fn=io.PadToBucket(BOUNDARIES))
        ckpt_dir = os.path.join(out, "ckpt")

    mgr = paddle.CheckpointManager(ckpt_dir, keep_last_n=3)
    # plan= arms the fingerprint gate: a restore under a DIFFERENT mesh /
    # rule table raises PlanMismatchError instead of mis-sharding
    resumed = mgr.auto_resume(model, fstep, sampler=loader, plan=plan)
    base = 0 if resumed is None else int(resumed)
    start_epoch = loader.state_dict()["epoch"]

    log = open(os.path.join(out, f"loss.rank{rank}.log"), "a")
    marker = os.path.join(out, f"fired.{scenario}.{rank}")

    def on_window(win):
        gstep_end = base + win["step"]
        for i, l in enumerate(win["losses"]):
            gs = gstep_end - len(win["losses"]) + i + 1
            log.write(f"{gs} {float(l)!r}\n")
        log.flush()
        os.fsync(log.fileno())
        # plan= records the fingerprint on EVERY window checkpoint (not
        # just preemption saves), so kill/hang restarts re-validate it
        # through auto_resume(plan=) rather than passing trivially on a
        # fingerprint-less checkpoint (plan is None on the base drill)
        mgr.save(int(fstep.device_metrics()["step_count"]), model=model,
                 optimizer=fstep, sampler=loader, plan=plan)
        if (scenario == "preempt" and gstep_end >= chaos_step
                and not os.path.exists(marker)):
            open(marker, "w").write("x")
            # a real scheduler would deliver SIGTERM asynchronously; at a
            # window boundary every rank is at the same global step, so
            # the group's preemption checkpoints agree
            signal.raise_signal(signal.SIGTERM)

    import contextlib

    with contextlib.ExitStack() as stack:
        flaky_n = int(os.environ.get("CHAOS_STREAM_FLAKY", "0"))
        if stream_dir and flaky_n > 0:
            # the FLAKY filesystem: every Nth positioned shard read
            # fails transiently (InjectedFault is an OSError, so the
            # shared retry/backoff path absorbs it) — armed in baseline
            # and chaos arms alike so every arm trains over the same
            # flaky stream and recovery is invisible to the data
            stack.enter_context(
                fi.inject("io.stream.read", every_n=flaky_n))
        hit = (scenario in ("kill", "hang") and rank == chaos_rank
               and chaos_step > base and not os.path.exists(marker))
        if hit:
            # marker first: the fault below ends this incarnation, and the
            # restarted worker must not re-arm it
            open(marker, "w").write("x")
            site = "proc.kill" if scenario == "kill" else "train.stall"
            stack.enter_context(
                fi.inject(site, every_n=chaos_step - base))
        for epoch in range(start_epoch, EPOCHS):
            loader.set_epoch(epoch)  # resets cursor unless resuming into it
            res = fstep.drive(loader, log_every=WINDOW, on_window=on_window,
                              checkpoint=mgr, sampler=loader)
            base += res["steps"]

    if stream_dir:
        import json

        with open(os.path.join(out, f"stream_stats.rank{rank}.json"),
                  "w") as f:
            st = loader.stats()
            st.pop("quarantine_log", None)
            json.dump(st, f)
    open(os.path.join(out, f"done.rank{rank}"), "w").write(str(base))
    return 0


# ---------------------------------------------------------------------------
# spike drill (single-process divergence sentinel)
# ---------------------------------------------------------------------------

SPIKE_WINDOW = 3        # log_every for the spike drill
SPIKE_EPOCHS = 3
# poison the window AFTER this many boundaries have passed: late enough
# that the sentinel's EMA warmup is over and at least one checkpoint has
# earned its HEALTHY tag, early enough to leave recovery room
SPIKE_POISON_AT = 5


def spike_worker_main():
    """One spike-drill job: mode ``baseline`` (clean), ``control``
    (poisoned window, sentinel off) or ``sentinel`` (poisoned window,
    rollback response). Deterministic data/model; writes per-step losses
    and the sentinel stats for the orchestrator's assertions."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.io as io
    import paddle_tpu.nn as nn
    from paddle_tpu.incubate.fused_train_step import FusedTrainStep
    from paddle_tpu.utils import fault_injection as fi

    out = os.environ["CHAOS_OUT"]
    mode = os.environ["CHAOS_SPIKE_MODE"]

    paddle.seed(0)
    np.random.seed(0)
    rng = np.random.RandomState(5)
    lengths = rng.randint(3, 25, size=N_SAMPLES)
    xs = [rng.randn(int(n), FEATS).astype("float32") for n in lengths]
    # learnable target so the clean loss actually descends (the drill
    # compares final losses, not just survival)
    w_true = rng.randn(FEATS).astype("float32")
    ys = np.array([x.mean(axis=0) @ w_true for x in xs], dtype="float32")

    class VarLen(io.Dataset):
        def __len__(self):
            return N_SAMPLES

        def __getitem__(self, i):
            return xs[i], ys[i]

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(FEATS, 1)

        def forward(self, x, y, mask):
            tok = self.proj(x)[:, :, 0] * mask          # [B, L]
            pred = tok.sum(axis=1) / mask.sum(axis=1)   # masked mean
            d = pred - y
            return (d * d).mean()

    model = Net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    fstep = FusedTrainStep(model, opt)
    sampler = io.BucketedBatchSampler(
        VarLen(), batch_size=BATCH, boundaries=BOUNDARIES, shuffle=True,
        seed=11, lengths=lengths.tolist(), drop_last=True)
    loader = io.DataLoader(VarLen(), batch_sampler=sampler,
                           collate_fn=io.PadToBucket(BOUNDARIES))
    mgr = paddle.CheckpointManager(os.path.join(out, "ckpt"), keep_last_n=3)

    sentinel = None
    if mode == "sentinel":
        from paddle_tpu.incubate.sentinel import TrainingSentinel

        sentinel = TrainingSentinel(
            action="rollback", zscore=4.0, warmup_windows=3, ema_beta=0.8,
            healthy_windows=1)

    poison = {"cm": None, "windows": 0}

    def on_window(win):
        for loss in win["losses"]:
            log.write(f"{float(loss)!r}\n")
        log.flush()
        mgr.save(int(fstep.device_metrics()["step_count"]), model=model,
                 optimizer=fstep, sampler=loader)
        # arm the poison for exactly one window of dispatches
        # (boundary-to-boundary), in control and sentinel modes alike
        poison["windows"] += 1
        if mode != "baseline":
            if poison["windows"] == SPIKE_POISON_AT:
                poison["cm"] = fi.inject("train.spike")
                poison["cm"].__enter__()
            elif poison["cm"] is not None:
                poison["cm"].__exit__(None, None, None)
                poison["cm"] = None

    import warnings

    losses = []
    with open(os.path.join(out, "loss.log"), "a") as log:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for epoch in range(SPIKE_EPOCHS):
                loader.set_epoch(epoch)
                hist = fstep.drive(loader, log_every=SPIKE_WINDOW,
                                   on_window=on_window, checkpoint=mgr,
                                   sampler=loader, sentinel=sentinel)
                losses.extend(hist["loss"])
    if poison["cm"] is not None:
        poison["cm"].__exit__(None, None, None)
    summary = {
        "mode": mode, "steps": len(losses),
        # applied updates in the FINAL trajectory: a rollback rewinds this
        # to the healthy step, so skipped windows never count
        "device_steps": int(fstep.device_metrics()["step_count"]),
        "final_loss": float(np.mean(losses[-SPIKE_WINDOW:])),
        "sentinel": hist["sentinel"],
        "healthy_step": mgr.latest_healthy_step(),
    }
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f)
    return 0


def run_spike_job(out, mode, timeout=600):
    os.makedirs(out, exist_ok=True)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "CHAOS_OUT": out,
        "CHAOS_SPIKE_MODE": mode,
    })
    if mode == "sentinel":
        env["FLAGS_sentinel_action"] = "rollback"
    else:
        env["FLAGS_sentinel_action"] = "none"
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=timeout)
    return r


def spike_drill(out_root):
    """baseline vs control vs sentinel; see the module docstring."""
    import json

    print(f"[chaos] spike drill, scratch: {out_root}")
    summaries = {}
    for mode in ("baseline", "control", "sentinel"):
        out = os.path.join(out_root, f"spike_{mode}")
        print(f"[chaos] spike job {mode!r}...")
        t0 = time.time()
        r = run_spike_job(out, mode)
        check(r.returncode == 0,
              f"{mode}: job exits 0 (got {r.returncode}): "
              f"{r.stderr[-800:]}")
        with open(os.path.join(out, "summary.json")) as f:
            summaries[mode] = json.load(f)
        print(f"  done in {time.time() - t0:.1f}s "
              f"(final loss {summaries[mode]['final_loss']:.6g})")

    base = summaries["baseline"]["final_loss"]
    ctrl = summaries["control"]["final_loss"]
    sent = summaries["sentinel"]["final_loss"]
    st = summaries["sentinel"]["sentinel"]
    check(st and st["spikes"] >= 1,
          f"sentinel detected the poisoned window ({st and st['spikes']} "
          "spike verdicts)")
    check(st["rollbacks"] >= 1,
          f"sentinel rolled back ({st['rollbacks']}x) to the last "
          f"healthy step")
    check(summaries["sentinel"]["healthy_step"] is not None,
          "healthy-step tagging produced a rollback target")
    check(not (ctrl <= 10 * max(base, 1e-6)) or ctrl != ctrl,
          f"control visibly diverges: {ctrl:.6g} vs baseline {base:.6g}")
    # the sentinel run trains fewer steps (the poisoned window's batches
    # are skipped, not replayed), so "recovered" means the same loss
    # regime as the clean baseline — not bit-equality
    tol = 0.5 * max(base, 1e-3) + 0.05
    check(abs(sent - base) <= tol,
          f"sentinel run recovers: final {sent:.6g} within ±{tol:.3g} of "
          f"baseline {base:.6g} (control: {ctrl:.6g})")
    check(summaries["sentinel"]["device_steps"]
          < summaries["baseline"]["device_steps"],
          "poisoned window was skipped, not replayed: fewer applied "
          f"updates ({summaries['sentinel']['device_steps']} vs "
          f"{summaries['baseline']['device_steps']}) in the final "
          "trajectory")
    print("[chaos] SPIKE DRILL PASSED")
    return 0


# ---------------------------------------------------------------------------
# plan drill (sharded-plan restart bit-exactness — ROADMAP item 3)
# ---------------------------------------------------------------------------

# one worker process carrying a virtual 8-device CPU mesh; the dp=2 x tp=2
# plan shards the drill net column/row over tp with zero1 moments over dp
_PLAN_ENV = {
    "CHAOS_PLAN": "dp2xtp2",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def plan_drill(out_root, scenarios=("kill", "preempt", "hang")):
    """kill -9 / preempt / hang under a dp x tp SHARDED PLAN, restart
    bit-exact: the launcher scenarios, single-process (the virtual mesh
    lives inside the worker), with every step compiled through
    ``compile_step_with_plan`` and every checkpoint carrying the plan
    fingerprint that ``auto_resume(plan=...)`` re-validates on restart."""
    print(f"[chaos] plan drill (dp=2 x tp=2 zero1), scratch: {out_root}")
    print("[chaos] plan baseline (uninterrupted sharded run)...")
    base_out = os.path.join(out_root, "plan_baseline")
    r = run_job(base_out, "none", extra_env=_PLAN_ENV, nproc=1)
    check(r.returncode == 0,
          f"plan baseline exits 0 (got {r.returncode}): {r.stderr[-800:]}")
    baseline = read_losses(base_out)
    check(baseline and sorted(baseline) == list(range(1, len(baseline) + 1)),
          f"plan baseline logged a contiguous {len(baseline)}-step "
          "sequence")

    results = {}
    for sc in scenarios:
        out = os.path.join(out_root, f"plan_{sc}")
        print(f"[chaos] plan scenario {sc!r}...")
        if sc == "kill":
            r = run_job(out, "kill", chaos_step=8, chaos_rank=0,
                        max_restart=2, extra_env=_PLAN_ENV, nproc=1)
        elif sc == "preempt":
            r = run_job(out, "preempt", chaos_step=2 * WINDOW,
                        max_restart=0, extra_env=_PLAN_ENV, nproc=1)
        elif sc == "hang":
            # the sharded step's first compile is slower than the plain
            # drill's — the timeout must not mistake compile for a hang
            r = run_job(out, "hang", chaos_step=7, chaos_rank=0,
                        max_restart=2, nproc=1,
                        extra_env=dict(_PLAN_ENV,
                                       FLAGS_worker_hang_timeout_s="20",
                                       FLAGS_worker_term_grace_s="2"))
        else:
            raise SystemExit(f"unknown plan scenario {sc!r}")
        check(r.returncode == 0,
              f"plan {sc}: job completes within budget "
              f"(rc={r.returncode}): {r.stderr[-800:]}")
        losses = read_losses(out)
        check(losses == baseline,
              f"plan {sc}: loss sequence bit-identical to the sharded "
              f"baseline ({len(losses)} steps)")
        if sc == "preempt":
            check("restart budget untouched" in r.stderr,
                  "plan preempt: relaunch consumed zero restart budget")
        if sc == "kill":
            check("restart 1/" in r.stderr,
                  "plan kill: consumed restart budget")
        if sc == "hang":
            check("heartbeats stale" in r.stderr,
                  "plan hang: watchdog detected the stall")
        results[sc] = r.elapsed
        print(f"  done in {r.elapsed:.1f}s")
    print("[chaos] PLAN DRILL PASSED:",
          ", ".join(f"{k}={v:.1f}s" for k, v in results.items()))
    return 0


# ---------------------------------------------------------------------------
# stream drill (fault-tolerant streaming data plane — ISSUE 13)
# ---------------------------------------------------------------------------

N_STREAM_SHARDS = 6     # 48 samples -> 8 records/shard; world 2 -> 3/rank


def stream_make_main():
    """Shard-maker worker mode (``CHAOS_STREAM_MAKE=<dest>``): writes the
    drill's deterministic varlen dataset as ``N_STREAM_SHARDS`` atomic
    ``*.pdstream`` shards. Runs as a subprocess so the orchestrator never
    imports jax."""
    import numpy as np

    import paddle_tpu.io as io

    dest = os.environ["CHAOS_STREAM_MAKE"]
    os.makedirs(dest, exist_ok=True)
    rng = np.random.RandomState(5)
    lengths = rng.randint(3, 25, size=N_SAMPLES)
    xs = [rng.randn(int(n), FEATS).astype("float32") for n in lengths]
    ys = rng.randn(N_SAMPLES).astype("float32")
    per = N_SAMPLES // N_STREAM_SHARDS
    for s in range(N_STREAM_SHARDS):
        recs = [(xs[i], np.float32(ys[i]))
                for i in range(s * per, (s + 1) * per)]
        io.write_stream_shard(
            os.path.join(dest, f"shard-{s:02d}.pdstream"), recs)
    return 0


def make_stream_shards(dest):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "CHAOS_STREAM_MAKE": dest,
    })
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    if r.returncode != 0:
        raise AssertionError(f"shard maker failed: {r.stderr[-800:]}")


def corrupt_one_record(shards_dir, shard_name="shard-02.pdstream",
                       byte_offset=40):
    """Flip one byte inside a record payload (past the 8-byte magic and
    the first 8-byte frame header), so the record's CRC no longer
    matches — the quarantine path's on-disk trigger."""
    p = os.path.join(shards_dir, shard_name)
    raw = bytearray(open(p, "rb").read())
    raw[byte_offset] ^= 0xFF
    with open(p, "wb") as f:
        f.write(bytes(raw))


def read_stream_stats(out, rank=0):
    import json

    with open(os.path.join(out, f"stream_stats.rank{rank}.json")) as f:
        return json.load(f)


def stream_drill(out_root, scenarios=("kill", "preempt")):
    """The ISSUE-13 acceptance drill: a 2-worker launcher job trains over
    a slow (thread-pool decode with per-record cost) + flaky (injected
    ``io.stream.read`` transients, absorbed by the retry budget) sharded
    record stream, with per-rank shard assignment and per-rank cursor
    checkpoints. SIGKILL and graceful preemption mid-epoch must resume to
    per-step loss sequences bit-identical to the undisturbed baseline —
    on BOTH ranks (they train different shards). A separate corrupt-shard
    arm flips a byte on disk and must FINISH via quarantine (counted)
    under the skip budget instead of crashing."""
    print(f"[chaos] stream drill, scratch: {out_root}")
    shards = os.path.join(out_root, "shards")
    make_stream_shards(shards)
    stream_env = {
        "CHAOS_STREAM": shards,
        "CHAOS_STREAM_FLAKY": "17",
        # ranks shard the DATA and keep private model replicas/ckpt dirs;
        # no cross-rank collectives -> no coordination service
        "PADDLE_SKIP_DIST_INIT": "1",
    }

    print("[chaos] stream baseline (uninterrupted 2-worker run)...")
    base_out = os.path.join(out_root, "stream_baseline")
    r = run_job(base_out, "none", extra_env=stream_env)
    check(r.returncode == 0,
          f"stream baseline exits 0 (got {r.returncode}): "
          f"{r.stderr[-800:]}")
    baseline = {rk: read_losses(base_out, rank=rk) for rk in (0, 1)}
    for rk in (0, 1):
        check(baseline[rk] and sorted(baseline[rk])
              == list(range(1, len(baseline[rk]) + 1)),
              f"stream baseline rank{rk} logged a contiguous "
              f"{len(baseline[rk])}-step sequence")
    stats = read_stream_stats(base_out)
    check(stats["retries"] >= 1 and stats["quarantined"] == 0,
          f"baseline stream was flaky-but-clean: {stats['retries']} "
          "transient read failures retried, 0 records quarantined")

    results = {}
    for sc in scenarios:
        out = os.path.join(out_root, f"stream_{sc}")
        print(f"[chaos] stream scenario {sc!r}...")
        if sc == "kill":
            r = run_job(out, "kill", chaos_step=5, chaos_rank=1,
                        max_restart=2, extra_env=stream_env)
        elif sc == "preempt":
            r = run_job(out, "preempt", chaos_step=WINDOW,
                        max_restart=0, extra_env=stream_env)
        else:
            raise SystemExit(f"unknown stream scenario {sc!r}")
        check(r.returncode == 0,
              f"stream {sc}: job completes within budget "
              f"(rc={r.returncode}): {r.stderr[-800:]}")
        for rk in (0, 1):
            losses = read_losses(out, rank=rk)
            check(losses == baseline[rk],
                  f"stream {sc} rank{rk}: loss sequence bit-identical to "
                  f"baseline ({len(losses)} steps)")
        if sc == "kill":
            check("restart 1/" in r.stderr,
                  "stream kill: consumed restart budget")
        if sc == "preempt":
            check("restart budget untouched" in r.stderr,
                  "stream preempt: relaunch consumed zero restart budget")
        results[sc] = r.elapsed
        print(f"  done in {r.elapsed:.1f}s")

    # corrupt-shard arm: single worker, one flipped byte on disk, a skip
    # budget that admits it — the job must FINISH (quarantine, counted),
    # not crash, and train strictly fewer records than the clean stream
    print("[chaos] stream scenario 'corrupt'...")
    cshards = os.path.join(out_root, "shards_corrupt")
    import shutil as _shutil

    _shutil.copytree(shards, cshards)
    corrupt_one_record(cshards)
    out = os.path.join(out_root, "stream_corrupt")
    r = run_job(out, "none", nproc=1,
                extra_env=dict(stream_env, CHAOS_STREAM=cshards,
                               CHAOS_STREAM_SKIPS="4"))
    check(r.returncode == 0,
          f"corrupt arm finishes via quarantine (rc={r.returncode}): "
          f"{r.stderr[-800:]}")
    cstats = read_stream_stats(out)
    check(cstats["quarantined"] >= 1,
          f"corrupt record was quarantined and counted "
          f"({cstats['quarantined']}x, io_records_quarantined_total)")
    total = EPOCHS * N_SAMPLES
    check(cstats["records"] + cstats["quarantined"] == total
          and cstats["records"] < total,
          f"quarantined records were SKIPPED, not trained: "
          f"{cstats['records']} delivered + {cstats['quarantined']} "
          f"quarantined == {total} read")
    results["corrupt"] = r.elapsed
    print(f"  done in {r.elapsed:.1f}s")

    print("[chaos] STREAM DRILL PASSED:",
          ", ".join(f"{k}={v:.1f}s" for k, v in results.items()))
    return 0


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def _job_env(out, scenario, chaos_step=0, chaos_rank=-1, extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "CHAOS_OUT": out,
        "CHAOS_SCENARIO": scenario,
        "CHAOS_STEP": str(chaos_step),
        "CHAOS_RANK": str(chaos_rank),
        "FLAGS_restart_backoff_s": "0.1",
    })
    env.update(extra or {})
    return env


def run_job(out, scenario, chaos_step=0, chaos_rank=-1, max_restart=0,
            extra_env=None, timeout=600, nproc=2):
    os.makedirs(out, exist_ok=True)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           f"--nproc_per_node={nproc}", f"--max_restart={max_restart}",
           f"--log_dir={os.path.join(out, 'logs')}",
           os.path.abspath(__file__)]
    t0 = time.time()
    r = subprocess.run(cmd, env=_job_env(out, scenario, chaos_step,
                                         chaos_rank, extra_env),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=timeout)
    r.elapsed = time.time() - t0
    return r


def read_losses(out, rank=0):
    """{global_step: loss_repr}; raises if any step was re-trained with a
    DIFFERENT loss (the bit-exactness the recovery path guarantees)."""
    seen = {}
    path = os.path.join(out, f"loss.rank{rank}.log")
    with open(path) as f:
        for line in f:
            step_s, val = line.split(" ", 1)
            step, val = int(step_s), val.strip()
            if step in seen and seen[step] != val:
                raise AssertionError(
                    f"step {step} retrained with a DIFFERENT loss: "
                    f"{seen[step]} vs {val} (not bit-exact)")
            seen[step] = val
    return dict(sorted(seen.items()))


def read_liveness(out):
    """The launch_live_ranks transition sequence the supervisor appended
    to ``<out>/logs/liveness.log`` (one ``<time> <count>`` line per gauge
    change)."""
    path = os.path.join(out, "logs", "liveness.log")
    vals = []
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) == 2:
                vals.append(int(parts[1]))
    return vals


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)
    print(f"  ok: {msg}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="scratch dir (default: a fresh tempdir)")
    ap.add_argument("--scenarios", default="kill,preempt,hang")
    ap.add_argument("--drill", default=None,
                    choices=["spike", "plan", "stream"],
                    help="run one named drill instead of the launcher "
                         "scenarios (spike: divergence-sentinel "
                         "detect/rollback/skip/recover; plan: kill/"
                         "preempt/hang under a dp x tp sharded plan, "
                         "restart bit-exact; stream: kill/preempt over a "
                         "slow+flaky sharded record stream, per-rank "
                         "cursors resume bit-exact + corrupt-shard "
                         "quarantine arm)")
    args = ap.parse_args(argv)
    out_root = args.out or tempfile.mkdtemp(prefix="chaos_train.")
    if args.drill == "spike":
        return spike_drill(out_root)
    if args.drill == "stream":
        return stream_drill(out_root)
    if args.drill == "plan":
        return plan_drill(
            out_root, tuple(s for s in args.scenarios.split(",") if s))
    scenarios = [s for s in args.scenarios.split(",") if s]

    print(f"[chaos] scratch: {out_root}")
    print("[chaos] baseline (uninterrupted 2-worker run)...")
    base_out = os.path.join(out_root, "baseline")
    r = run_job(base_out, "none")
    check(r.returncode == 0,
          f"baseline exits 0 (got {r.returncode}): {r.stderr[-800:]}")
    baseline = read_losses(base_out)
    check(baseline and sorted(baseline) == list(range(1, len(baseline) + 1)),
          f"baseline logged a contiguous {len(baseline)}-step sequence")

    results = {}
    for sc in scenarios:
        out = os.path.join(out_root, sc)
        print(f"[chaos] scenario {sc!r}...")
        if sc == "kill":
            r = run_job(out, "kill", chaos_step=8, chaos_rank=1,
                        max_restart=2)
        elif sc == "preempt":
            r = run_job(out, "preempt", chaos_step=2 * WINDOW,
                        max_restart=0)
        elif sc == "hang":
            # timeout must exceed (model build + first XLA compile +
            # auto_resume) between heartbeats on a loaded CI box, while
            # staying far below the 3600s stall itself
            r = run_job(out, "hang", chaos_step=7, chaos_rank=1,
                        max_restart=2,
                        extra_env={"FLAGS_worker_hang_timeout_s": "12",
                                   "FLAGS_worker_term_grace_s": "2"})
        else:
            raise SystemExit(f"unknown scenario {sc!r}")
        check(r.returncode == 0,
              f"{sc}: job completes within budget (rc={r.returncode}): "
              f"{r.stderr[-800:]}")
        losses = read_losses(out)
        check(losses == baseline,
              f"{sc}: loss sequence bit-identical to baseline "
              f"({len(losses)} steps)")
        if sc == "preempt":
            check("restart budget untouched" in r.stderr,
                  "preempt: relaunch consumed zero restart budget")
            check("worker failed" not in r.stderr,
                  "preempt: no crash restarts")
        if sc == "kill":
            check("restart 1/" in r.stderr, "kill: consumed restart budget")
            # rank-liveness gauge (ISSUE 10): the launcher publishes
            # launch_live_ranks every supervision tick and appends value
            # transitions to logs/liveness.log — the kill must show the
            # gauge dipping below the full rank count and recovering to
            # full after the budgeted restart
            vals = read_liveness(out)
            check(any(v < 2 for v in vals),
                  "kill: rank-liveness gauge dipped below nproc "
                  f"(transitions: {vals})")
            first_dip = next(i for i, v in enumerate(vals) if v < 2)
            check(any(v == 2 for v in vals[first_dip:]),
                  "kill: rank-liveness gauge recovered to full after the "
                  f"restart (transitions: {vals})")
        if sc == "hang":
            check("heartbeats stale" in r.stderr,
                  "hang: watchdog detected the stall")
        results[sc] = r.elapsed
        print(f"  done in {r.elapsed:.1f}s")

    print("[chaos] ALL SCENARIOS PASSED:",
          ", ".join(f"{k}={v:.1f}s" for k, v in results.items()))
    return 0


if __name__ == "__main__":
    if os.environ.get("CHAOS_STREAM_MAKE"):
        sys.exit(stream_make_main())
    if os.environ.get("CHAOS_OUT") and os.environ.get("CHAOS_SPIKE_MODE"):
        sys.exit(spike_worker_main())
    if os.environ.get("CHAOS_OUT") and os.environ.get("PADDLE_TRAINER_ID"):
        sys.exit(worker_main())
    sys.exit(main())
