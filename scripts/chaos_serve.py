#!/usr/bin/env python
"""Chaos drill for the fault-tolerant serving fleet (ISSUE 12): SIGKILL
a replica, hang another, drain a third mid-burst — and prove NOTHING is
lost: every accepted request completes with greedy output bit-identical
to an undisturbed single-engine baseline, every rejected request gets a
typed error (completed + typed-error counts == submitted), and the
fleet liveness gauge dips and recovers.

Usage::

    python scripts/chaos_serve.py [--drill kill|hang|drain|shed|all]
        [--fleet 3] [--out DIR]

Drills (each runs against a fresh fleet of ``--fleet`` replica worker
processes over one shared model artifact + checkpoint root):

- ``kill``:  the acceptance storm — one replica is SIGKILLed from
  outside (picked by in-flight load, the OOM-killer shape) AND, with
  >= 3 replicas, another is armed to wedge mid-serve (fault site
  ``serve.replica_hang`` via env, the stuck-collective shape). The
  supervisor detects both (exit code; stale heartbeats →
  SIGTERM→SIGKILL), respawns them under the restart budget — the
  respawned workers rejoin via ``reload_weights(latest_healthy_step())``
  — and the router replays their in-flight requests from prompt +
  already-emitted tokens on healthy peers. Asserts: all requests
  complete bit-exact, redispatches happened, liveness dipped and
  recovered, restarted replicas report the rejoin checkpoint step,
  p99 TTFT stays bounded.
- ``hang``:  hang-only variant (fault site ``serve.replica_hang``).
- ``drain``: graceful drain mid-burst — ``drain(replica,
  then='reload')`` stops admission, lets in-flight requests finish,
  hot-swaps weights from the checkpoint root, rejoins. Asserts: zero
  drops, zero typed errors, the drain completed with the expected
  checkpoint step (the zero-drop rolling-update primitive).
- ``shed``:  overload + deadline typed-error accounting — a tiny
  admission queue sheds a fast burst with FleetOverloadedError, an
  expired deadline is rejected at admission and a too-tight one dies
  queued, both with RequestTimeoutError; afterwards every replica's
  allocator is PROVEN clean (all blocks free, nothing waiting/running).
- ``quant``: the kill drill over a QUANTIZED fleet (ISSUE 14): replicas
  boot from an int8 per-channel weight artifact and serve with
  ``kv_dtype="int8"`` paged-KV pools. int8-KV greedy decode is
  deterministic (per-row quantization is a pure function of the row),
  so redispatching an in-flight request off the killed replica and
  replaying prompt + emitted tokens on a survivor must reproduce
  IDENTICAL token ids — asserted against an undisturbed quantized
  single-engine baseline, like the fp32 kill drill asserts against its
  fp32 baseline.
- ``disagg``: the ISSUE-15 storm over a ROLE-SPLIT fleet (2 prefill +
  2 decode workers): one prefill worker SIGKILLs itself MID-TRANSFER
  (fault site ``serve.prefill_crash``, fired between KV-page frames,
  with tiny frames forced so every handoff spans several) AND one
  decode worker wedges mid-stream (``serve.replica_hang``). The router
  must discard the partial pages atomically, re-drive the prefill on
  the surviving prefill worker (``fleet_handoff_failovers_total`` > 0),
  and replay the hung decode worker's requests through a fresh
  two-stage handoff — every output bit-identical to a COLOCATED
  single-engine baseline, allocators clean on every replica. A second
  burst arms ``serve.kv_transfer_corrupt`` (frames corrupted after
  their CRC was computed): the router's CRC check must catch it and
  re-drive under the transfer retry budget
  (``fleet_kv_transfer_retries_total`` > 0), still bit-exact.

- ``warmstore``: the ISSUE-16 persistent-prefix-store drill
  (single-engine — no fleet). A cold engine serves a session-revisit
  stream and publishes the prefix store at ``close()``; a warm boot
  must re-import it (``prefix_store_loaded`` > 0), REVIVE the shared
  prefixes instead of re-prefilling (``kv_revives`` > 0) and produce
  bit-identical outputs. Crash arms: a victim process SIGKILLed from
  inside the armed ``serve.store_write`` window must never publish a
  torn store (the previous bytes survive exactly and still load); a
  corrupt store byte and a weight-fingerprint mismatch must each be
  rejected WHOLE and degrade to a clean, still-bit-exact cold start.

- ``qos``: the ISSUE-17 multi-tenant QoS drill. An uncontended
  interactive-only burst sets the TTFT reference; then a flood — batch
  tier filling every decode slot plus an abuser bursting past its
  40 tok/s admission quota — must leave the interactive p99 TTFT
  within ~1.2x, rate-limit the abuser with typed
  TenantQuotaExceededError + ``retry_after_s``, and complete every
  batch request bit-exact (slots YIELDED — ``batch_yields`` > 0 —
  never dropped). A final burst scales the fleet DOWN mid-flood with
  ``serve.scale_down_kill`` armed: the draining replica is SIGKILLed,
  its in-flight requests ride crash-redispatch, a clean retry retires
  the slot — zero requests dropped end to end.

- ``tpgroup``: the ISSUE-19 model-parallel replica-group drill. Two
  slots, each a 2-process tp=2 GROUP (one plan-sharded engine in SPMD
  lockstep, rank 0 owning the RPC stream). Mid-burst, group 0's rank 1
  SIGKILLs itself (``serve.group_member_crash``) and group 1's rank 1
  wedges (``serve.group_member_hang``) — both failures start as
  half-dead groups whose rank 0 still answers. The supervisor must fell
  each group WHOLE (survivors SIGTERM→SIGKILL — a partial tp group must
  never serve), charge one restart-budget slot per group, respawn on a
  fresh coordination port, rejoin from the checkpoint root, and the
  router replays everything bit-exact; allocators proven clean over the
  rank-0 stats RPC.

- ``sdc``: the ISSUE-20 silent-data-corruption drill (fault site
  ``serve.bit_flip``). Three arms: a host-tier spill entry gets one
  payload byte flipped after its CRC seal — the read-back verification
  at revive must reject it, degrade to re-prefill, and deliver
  bit-exact output anyway; a weight flip on an idle fleet replica is
  caught by the sampled output audit (``audit_fraction=1.0``) — the
  corrupt replay mismatches, a third-replica referee votes the auditor
  corrupt, and it is QUARANTINED through one restart-budget slot
  (liveness dip + recover, in-flight redispatch, both waves bit-exact);
  a single-engine weight flip is caught by the periodic fingerprint
  re-audit and healed by ``reload_weights``.

``--drill all`` (the default) runs kill, hang, drain, shed, quant,
disagg, warmstore, qos, tpgroup, sdc in order.
Wired into the slow tier of tests/test_serving.py, the chaos_train.py
discipline applied to serving. Everything runs on CPU
(JAX_PLATFORMS=cpu is forced for the replicas by the supervisor).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_REQUESTS = 18
RATE = 60.0            # req/s Poisson arrivals — the whole burst in ~0.3s
ENGINE_KW = dict(num_blocks=64, block_size=8, max_batch_size=4,
                 max_prefills_per_step=2)


#: busy ticks before the armed replica wedges: mid-burst, not after the
#: work is done
HANG_AFTER_STEPS = 12
#: the watchdog's staleness bound
HANG_TIMEOUT_S = 3.0


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)
    print(f"  ok: {msg}")


def request_stream(cfg, seed=0, n=N_REQUESTS, rate=RATE):
    """The bench_serving seeded Poisson generator (ONE workload source —
    the drill and the fleet A/B must never drift apart), drill-sized."""
    import bench_serving as bsv

    return bsv.request_stream(cfg, n=n, rate=rate, min_prompt=4,
                              max_prompt=16, min_new=6, max_new=12,
                              seed=seed)


def build_fixture(out):
    """Deterministic tiny llama + serving artifact + a committed
    checkpoint (step 1) replicas rejoin/reload from."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.checkpoint.manager import CheckpointManager
    from paddle_tpu.inference.serving import save_llama_artifact
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    np.random.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    artifact = os.path.join(out, "model")
    save_llama_artifact(model, artifact)
    ckpt_root = os.path.join(out, "ckpt")
    CheckpointManager(ckpt_root, keep_last_n=2).save(1, model=model)
    return model, artifact, ckpt_root


def baseline_outputs(model, stream, engine_kw=None):
    """Undisturbed single-engine greedy outputs, one per request index —
    the bit-exactness reference for every drill."""
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams

    eng = LLMEngine(model, ingest_async=False, **(engine_kw or ENGINE_KW))
    try:
        rids = [eng.add_request(r.prompt,
                                SamplingParams(max_new_tokens=r.max_new))
                for r in stream]
        for _ in eng.stream():
            pass
        return [eng.output_tokens(r) for r in rids]
    finally:
        eng.close()


def run_burst(fleet, stream, chaos=None):
    """Submit the seeded Poisson burst through the fleet, firing the
    ``chaos(fleet)`` callback mid-burst (re-tried until it reports
    success by returning truthy); pump to completion. Returns
    ({idx: gid}, [(idx, error)] shed, wall seconds)."""
    gids, shed = {}, []
    fired = False
    t0 = time.perf_counter()
    i = 0
    while i < len(stream) or fleet.pending():
        now = time.perf_counter() - t0
        while i < len(stream) and stream[i].arrival <= now:
            try:
                gids[i] = fleet.submit(stream[i].prompt,
                                       max_new=stream[i].max_new)
            except Exception as e:
                shed.append((i, e))
            i += 1
        progressed = fleet.step()
        if chaos is not None and not fired and i >= len(stream) // 2:
            fired = bool(chaos(fleet))
        if not fleet.pending() and i < len(stream):
            time.sleep(max(0.0, stream[i].arrival - now))
        elif not progressed:
            # don't busy-spin the pump while the replica processes do
            # the actual decoding — on a shared box the spinning parent
            # steals their cycles
            time.sleep(0.001)
    fleet.join(timeout=300)
    return gids, shed, time.perf_counter() - t0


def wait_all_ready(fleet, timeout=120.0):
    """Pump until every live replica (including just-restarted ones)
    reported ready — restart assertions and stats RPCs need them up.
    Also waits out scheduled (backoff-delayed) respawns."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        fleet.step()
        pending = getattr(fleet.supervisor, "_pending_respawn", {})
        if not pending and all(h.ready for h in fleet.supervisor.handles
                               if h.alive and not h.retired):
            return
        time.sleep(0.05)
    raise AssertionError("restarted replicas never became ready")


def read_liveness(out):
    vals = []
    try:
        with open(os.path.join(out, "fleet_liveness.log")) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 2:
                    vals.append(int(parts[1]))
    except OSError:
        pass
    return vals


def assert_complete_bitexact(fleet, gids, baseline):
    done = 0
    for idx, gid in gids.items():
        out = fleet.result(gid)  # raises the typed error if any
        ref = baseline[idx]
        check_quiet = np.array_equal(out, ref)
        if not check_quiet:
            raise AssertionError(
                f"request {idx} diverged from the undisturbed baseline: "
                f"{out.tolist()} vs {ref.tolist()}")
        done += 1
    print(f"  ok: all {done} accepted requests completed bit-identical "
          "to the undisturbed single-engine baseline")
    return done


def assert_replicas_clean(fleet):
    for h in fleet.supervisor.handles:
        if h.retired or not h.alive:
            continue
        s = fleet.replica_stats(h.id)
        check(s is not None, f"replica {h.id} answers the stats RPC")
        usable = ENGINE_KW["num_blocks"] - 1
        check(s["blocks_free"] == usable and s["waiting"] == 0
              and s["running"] == 0,
              f"replica {h.id} allocator/scheduler clean after the burst "
              f"({s['blocks_free']}/{usable} blocks free, "
              f"waiting={s['waiting']}, running={s['running']})")


def _fleet(out, n, engine_kw=None, **kw):
    from paddle_tpu.inference.serving.fleet import Router

    args = dict(artifact=os.path.join(out, "model"),
                n_replicas=n, engine_kwargs=engine_kw or ENGINE_KW,
                ckpt_root=os.path.join(out, "ckpt"),
                log_dir=out, max_queue=100, hang_timeout_s=0.0,
                max_restarts=3)
    args.update(kw)
    return Router(**args)


def drill_kill(out, model, n, hang_too=True):
    """The acceptance storm: SIGKILL the busiest replica mid-burst and
    (with >= 3 replicas) wedge another via ``serve.replica_hang``."""
    stream = request_stream(_cfg(model))
    baseline = baseline_outputs(model, stream)
    env = {}
    arm_hang = hang_too and n >= 3
    if arm_hang:
        env = {"CHAOS_SERVE_SITE": "serve.replica_hang",
               "CHAOS_SERVE_REPLICA": str(n - 1),
               "CHAOS_SERVE_AFTER_STEPS": str(HANG_AFTER_STEPS)}
    fleet = _fleet(out, n, hang_timeout_s=HANG_TIMEOUT_S, env_extra=env)
    try:
        victim = {}

        def chaos(fl):
            # the OOM-killer shape: kill the replica carrying the most
            # in-flight requests (never the one armed to hang). Retried
            # (return False) until somebody actually holds requests, so
            # the redispatch path is guaranteed to be exercised.
            cand = [h for h in fl.supervisor.handles
                    if h.alive and (not arm_hang or h.id != n - 1)]
            if not cand:
                # every candidate is mid-respawn (watchdog churn under
                # contention) — retry once somebody is back up and busy
                return False
            h = max(cand, key=lambda h: len(fl.inflight(h.id)))
            if not fl.inflight(h.id):
                return False
            victim["id"], victim["load"] = h.id, len(fl.inflight(h.id))
            print(f"[chaos] SIGKILL replica {h.id} "
                  f"({victim['load']} requests in flight)")
            os.kill(h.pid, signal.SIGKILL)
            return True

        gids, shed, wall = run_burst(fleet, stream, chaos)
        wait_all_ready(fleet)
        check(not shed, f"no request shed (queue bound ample): {shed}")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream),
              f"completed == submitted ({done}/{len(stream)}): nothing "
              "dropped silently")
        m = fleet.metrics()
        check(m["redispatches"] >= 1,
              f"in-flight requests were redispatched "
              f"({m['redispatches']}x) off the killed"
              + ("/hung" if arm_hang else "") + " replica")
        check(m["replica_restarts"] >= (2 if arm_hang else 1),
              f"supervisor restarted the dead replica(s) "
              f"({m['replica_restarts']} restarts)")
        vals = read_liveness(out)
        check(any(v < n for v in vals),
              f"fleet liveness gauge dipped below {n} (transitions: "
              f"{vals})")
        first_dip = next(i for i, v in enumerate(vals) if v < n)
        check(any(v == n for v in vals[first_dip:]),
              f"fleet liveness gauge recovered to {n} (transitions: "
              f"{vals})")
        h = fleet.supervisor.handles[victim["id"]]
        check(h.incarnation >= 1
              and h.ready_info.get("reloaded_step") == 1,
              "restarted replica rejoined via reload_weights("
              "latest_healthy_step()) at checkpoint step 1")
        ttfts = sorted(fleet.ttft_seconds())
        p99 = ttfts[min(len(ttfts) - 1,
                        int(0.99 * len(ttfts)))] if ttfts else 0.0
        check(p99 < 60.0, f"p99 TTFT bounded under chaos ({p99:.2f}s)")
        toks = sum(len(fleet.tokens(g)) for g in gids.values())
        print(f"  [report] {toks} tokens in {wall:.1f}s "
              f"({toks / wall:.1f} tok/s, fleet={n}, one killed"
              + (", one hung" if arm_hang else "") + ")")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_hang(out, model, n):
    """Hang-only: replica ``n-1`` wedges mid-serve; the heartbeat
    watchdog SIGTERM→SIGKILLs it and the burst still completes."""
    stream = request_stream(_cfg(model))
    baseline = baseline_outputs(model, stream)
    env = {"CHAOS_SERVE_SITE": "serve.replica_hang",
           "CHAOS_SERVE_REPLICA": str(n - 1),
           "CHAOS_SERVE_AFTER_STEPS": str(HANG_AFTER_STEPS)}
    fleet = _fleet(out, n, hang_timeout_s=HANG_TIMEOUT_S, env_extra=env)
    try:
        gids, shed, wall = run_burst(fleet, stream)
        wait_all_ready(fleet)
        check(not shed, "no request shed")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream), "completed == submitted")
        m = fleet.metrics()
        check(m["replica_restarts"] >= 1,
              f"watchdog killed + restarted the hung replica "
              f"({m['replica_restarts']} restarts)")
        vals = read_liveness(out)
        check(any(v < n for v in vals) and vals and vals[-1] == n,
              f"liveness dipped and recovered (transitions: {vals})")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_drain(out, model, n):
    """Graceful drain mid-burst: zero drops, zero typed errors, weight
    hot-swap from the checkpoint root."""
    stream = request_stream(_cfg(model))
    baseline = baseline_outputs(model, stream)
    fleet = _fleet(out, n)
    try:
        def chaos(fl):
            print("[chaos] draining replica 0 (then=reload)")
            fl.drain(0, then="reload")
            return True

        gids, shed, wall = run_burst(fleet, stream, chaos)
        fleet.join(timeout=120)
        deadline = time.time() + 60
        while fleet.metrics()["replicas_draining"] and \
                time.time() < deadline:
            fleet.step()
            time.sleep(0.005)
        check(not shed, "no request shed during the drain")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream),
              "zero-drop rolling update: completed == submitted")
        check(fleet.drains_completed == 1
              and fleet.metrics()["replicas_draining"] == 0,
              "drain completed and the replica rejoined")
        check((0, 1) in fleet.reloads,
              f"drained replica hot-swapped weights from checkpoint "
              f"step 1 (reloads: {fleet.reloads})")
        check(fleet.metrics()["deadline_expired"] == 0
              and fleet.metrics()["redispatches"] == 0,
              "no typed errors, no redispatches — the drain was "
              "invisible to clients")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_shed(out, model, n):
    """Overload + deadline accounting: a tiny queue sheds with
    FleetOverloadedError, deadlines reject/expire with
    RequestTimeoutError, and afterwards the allocators are clean."""
    from paddle_tpu.inference.serving import (FleetOverloadedError,
                                              RequestTimeoutError)

    cfg = _cfg(model)
    stream = request_stream(cfg, n=30, rate=1e6)  # instant burst
    baseline = baseline_outputs(model, stream)
    fleet = _fleet(out, min(n, 2), max_queue=4,
                   max_inflight_per_replica=2)
    try:
        check(fleet.submit(stream[0].prompt, max_new=4,
                           deadline_s=30) is not None or True,
              "sanity: a generous deadline admits")
        try:
            fleet.submit(stream[0].prompt, max_new=4, deadline_s=0.0)
            raise AssertionError("expired deadline was admitted")
        except RequestTimeoutError:
            print("  ok: already-expired deadline rejected at admission "
                  "with RequestTimeoutError")
        doomed = fleet.submit(stream[1].prompt, max_new=4,
                              deadline_s=0.01)
        time.sleep(0.05)
        fleet.step()
        try:
            fleet.result(doomed)
            raise AssertionError("queued past-deadline request returned")
        except RequestTimeoutError:
            print("  ok: deadline expiring in the queue surfaced as "
                  "RequestTimeoutError at the next tick")
        fleet.join(timeout=120)
        gids, shed = {}, []
        for i, req in enumerate(stream):
            try:
                gids[i] = fleet.submit(req.prompt, max_new=req.max_new)
            except FleetOverloadedError:
                shed.append(i)
            fleet.step()
        fleet.join(timeout=300)
        check(shed, f"the instant burst shed {len(shed)} requests with "
              "FleetOverloadedError (bounded queue, typed error)")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done + len(shed) == len(stream),
              f"completed ({done}) + typed-error ({len(shed)}) == "
              f"submitted ({len(stream)}): nothing dropped silently")
        m = fleet.metrics()
        check(m["requests_shed"] == len(shed)
              and m["deadline_expired"] >= 2,
              f"fleet metrics account for every rejection "
              f"(shed={m['requests_shed']}, "
              f"deadline={m['deadline_expired']})")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_quant(out, model, n):
    """Kill drill over an int8 fleet (ISSUE 14 satellite): quantized
    weight artifact + int8 paged-KV replicas; redispatch replay after
    the SIGKILL must reproduce token ids IDENTICAL to the undisturbed
    quantized single-engine baseline (int8-KV greedy is deterministic —
    per-row quantization is write-order-independent)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.checkpoint.manager import CheckpointManager
    from paddle_tpu.inference.serving import (
        is_quantized_artifact, load_llama_artifact, save_llama_artifact)

    engine_kw = dict(ENGINE_KW, kv_dtype="int8")
    # re-publish the artifact QUANTIZED and rebuild the fixture around
    # the DEQUANTIZED weights: replicas boot from the artifact, the
    # rejoin checkpoint must hold the same weights or a restarted
    # replica would serve a different model than the baseline
    artifact = os.path.join(out, "model")
    save_llama_artifact(model, artifact, quantize="int8")
    check(is_quantized_artifact(artifact),
          "artifact re-published in the int8 per-channel format")
    model_q = load_llama_artifact(artifact)
    CheckpointManager(os.path.join(out, "ckpt"), keep_last_n=2).save(
        1, model=model_q)
    stream = request_stream(_cfg(model_q))
    baseline = baseline_outputs(model_q, stream, engine_kw=engine_kw)
    fleet = _fleet(out, n, engine_kw=engine_kw, hang_timeout_s=HANG_TIMEOUT_S)
    try:
        victim = {}

        def chaos(fl):
            cand = [h for h in fl.supervisor.handles if h.alive]
            h = max(cand, key=lambda h: len(fl.inflight(h.id)))
            if not fl.inflight(h.id):
                return False
            victim["id"] = h.id
            print(f"[chaos] SIGKILL quantized replica {h.id} "
                  f"({len(fl.inflight(h.id))} requests in flight)")
            os.kill(h.pid, signal.SIGKILL)
            return True

        gids, shed, wall = run_burst(fleet, stream, chaos)
        wait_all_ready(fleet)
        check(not shed, f"no request shed: {shed}")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream),
              f"completed == submitted ({done}/{len(stream)})")
        m = fleet.metrics()
        check(m["redispatches"] >= 1,
              f"in-flight requests were redispatched "
              f"({m['redispatches']}x) — int8-KV replay reproduced "
              "identical token ids on the surviving replica")
        check(m["replica_restarts"] >= 1,
              f"supervisor restarted the killed replica "
              f"({m['replica_restarts']} restarts)")
        h = fleet.supervisor.handles[victim["id"]]
        check(h.incarnation >= 1
              and h.ready_info.get("reloaded_step") == 1,
              "restarted quantized replica rejoined at checkpoint step 1")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_disagg(out, model, n):
    """ISSUE 15 acceptance: prefill-worker SIGKILL mid-transfer + decode
    worker hang mid-stream over a role-split fleet, all outputs
    bit-identical to a COLOCATED single-engine baseline; then a
    corrupt-transfer burst that must complete through the retry budget.
    """
    import json as _json

    n_prefill = 2
    n_decode = max(2, n - n_prefill)
    roles = ["prefill"] * n_prefill + ["decode"] * n_decode
    total = len(roles)
    stream = request_stream(_cfg(model))
    baseline = baseline_outputs(model, stream)
    # tiny frames force multi-frame transfers on the tiny model, so the
    # mid-transfer kill genuinely interrupts a handoff; replica 0
    # (prefill) dies between frames, the LAST replica (decode) wedges
    env = {"PADDLE_KV_FRAME_BYTES": "2048",
           "CHAOS_SERVE_SITES": _json.dumps([
               {"site": "serve.prefill_crash", "replica": 0,
                "after": 11},
               {"site": "serve.replica_hang", "replica": total - 1,
                "after": 12},
           ])}
    fleet = _fleet(out, total, roles=roles, hang_timeout_s=HANG_TIMEOUT_S,
                   env_extra=env)
    try:
        gids, shed, wall = run_burst(fleet, stream)
        wait_all_ready(fleet)
        check(not shed, f"no request shed: {shed}")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream),
              f"completed == submitted ({done}/{len(stream)}): the "
              "disaggregated fleet dropped nothing")
        m = fleet.metrics()
        check(m["prefill_handoffs"] >= 1 and
              m["kv_pages_transferred"] >= 1,
              f"KV pages flowed prefill->decode "
              f"({m['prefill_handoffs']} handoffs, "
              f"{m['kv_pages_transferred']} frames)")
        check(m["handoff_failovers"] >= 1,
              f"the mid-transfer SIGKILL was recovered by re-driving "
              f"the prefill elsewhere ({m['handoff_failovers']} "
              "failovers, partial pages discarded atomically)")
        check(m["replica_restarts"] >= 2,
              f"supervisor restarted the crashed prefill worker AND the "
              f"hung decode worker ({m['replica_restarts']} restarts)")
        vals = read_liveness(out)
        check(any(v < total for v in vals) and vals and vals[-1] == total,
              f"liveness dipped and recovered (transitions: {vals})")
        for h in fleet.supervisor.handles:
            s = fleet.replica_stats(h.id)
            check(s is not None and s.get("role") == roles[h.id],
                  f"replica {h.id} reports role={roles[h.id]} after "
                  "restart (role survives respawn)")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()

    # corrupt-transfer burst (fresh fleet, clean incarnations): frames
    # corrupted AFTER their CRC was computed must be caught by the
    # router and re-driven under the retry budget — never decoded
    stream2 = request_stream(_cfg(model), seed=1)
    baseline2 = baseline_outputs(model, stream2)
    out2 = os.path.join(out, "corrupt")
    os.makedirs(out2, exist_ok=True)
    env2 = {"PADDLE_KV_FRAME_BYTES": "2048",
            "CHAOS_SERVE_SITES": _json.dumps([
                {"site": "serve.kv_transfer_corrupt", "replica": 0,
                 "after": 7, "max_fires": 2},
            ])}
    fleet = _fleet(out, total, roles=roles, env_extra=env2,
                   log_dir=out2)
    try:
        gids, shed, wall = run_burst(fleet, stream2)
        check(not shed, f"no request shed in the corrupt burst: {shed}")
        done = assert_complete_bitexact(fleet, gids, baseline2)
        check(done == len(stream2),
              "corrupt burst: completed == submitted")
        m = fleet.metrics()
        check(m["kv_transfer_retries"] >= 1,
              f"corrupt frames were caught by CRC and the prefill "
              f"re-driven ({m['kv_transfer_retries']} transfer retries, "
              "zero garbage decoded)")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


_VICTIM_SRC = r'''
import os, sys, numpy as np
sys.path.insert(0, sys.argv[1])
from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                          load_llama_artifact)
from paddle_tpu.utils import fault_injection as fi

class Kill9(OSError):
    """SIGKILLs the process from inside the armed serve.store_write
    window — data written to the tmp file, nothing published yet."""
    def __init__(self, *a):
        os.kill(os.getpid(), 9)

model = load_llama_artifact(sys.argv[2])
rng = np.random.RandomState(66)
prefix = rng.randint(0, model.config.vocab_size, 12).astype(np.int32)
prompts = [np.concatenate([prefix, rng.randint(
    0, model.config.vocab_size, s).astype(np.int32)]) for s in (4, 6)]
eng = LLMEngine(model, num_blocks=24, block_size=4, max_batch_size=3,
                enable_prefix_cache=True, kv_host_blocks=64,
                prefix_store_path=sys.argv[3])
eng.generate(prompts, SamplingParams(max_new_tokens=4))
with fi.inject("serve.store_write", exc=Kill9):
    eng.save_prefix_store()       # dies HERE, mid-write
raise SystemExit("unreachable: the armed save did not kill us")
'''


def drill_warmstore(out, model, n):
    """ISSUE 16 acceptance: the persistent prefix store across engine
    restarts. A cold engine serves a session-revisit stream and
    publishes the store at close(); a warm engine re-imports it and
    REVIVES prefixes instead of re-prefilling, bit-exact. Then the
    crash arms: a victim process SIGKILLed from inside the
    ``serve.store_write`` window must never publish a torn store (the
    previous bytes survive exactly); a corrupt store and a
    weight-fingerprint mismatch must each cold-start CLEAN — wrong
    pages are never imported."""
    import subprocess

    from paddle_tpu.inference.serving import LLMEngine, SamplingParams

    cfg = _cfg(model)
    rng = np.random.RandomState(66)
    prefix = rng.randint(0, cfg.vocab_size, 12).astype(np.int32)

    def wave(suffixes, seed):
        r = np.random.RandomState(seed)
        return [np.concatenate([prefix, r.randint(
            0, cfg.vocab_size, s).astype(np.int32)]) for s in suffixes]

    waves = [wave((4, 6, 5), 1),
             [rng.randint(0, cfg.vocab_size, 40).astype(np.int32)],
             wave((3, 7), 2)]
    store = os.path.join(out, "prefix.pdstream")
    kw = dict(num_blocks=14, block_size=4, max_batch_size=3,
              enable_prefix_cache=True, kv_host_blocks=64,
              prefix_store_path=store)

    def serve(**extra):
        outs = []
        with LLMEngine(model, **dict(kw, **extra)) as eng:
            boot = eng.metrics()
            for w in waves:
                outs.extend(eng.generate(
                    w, SamplingParams(max_new_tokens=6)))
            return outs, boot, eng.metrics()

    cold, boot0, _ = serve()
    check(boot0["prefix_store_loaded"] == 0,
          "first boot found no store (clean cold start)")
    check(os.path.exists(store), "close() published the prefix store")
    good = open(store, "rb").read()

    warm, boot1, em1 = serve()
    check(boot1["prefix_store_loaded"] > 0,
          f"warm boot re-imported {int(boot1['prefix_store_loaded'])} "
          "stored chains")
    check(em1["kv_revives"] > 0,
          f"stored chains were REVIVED, not re-prefilled "
          f"({int(em1['kv_revives'])} revives)")
    check(all(np.array_equal(a, b) for a, b in zip(warm, cold)),
          "warm-restart outputs bit-identical to the cold run")

    # SIGKILL from inside the store-write window: tmp data written,
    # rename not reached — the PREVIOUS store must survive exactly
    victim = os.path.join(out, "victim.py")
    with open(victim, "w") as f:
        f.write(_VICTIM_SRC)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, victim, REPO, os.path.join(out, "model"), store],
        env=env, capture_output=True, text=True, timeout=300)
    check(r.returncode == -signal.SIGKILL,
          f"victim died by SIGKILL mid-store-write (rc={r.returncode})")
    check(open(store, "rb").read() == good,
          "previous store intact byte-for-byte (no torn publish)")
    _, boot2, _ = serve()
    check(boot2["prefix_store_loaded"] > 0,
          "store still loads after the crashed writer")

    # corrupt store: rejected WHOLE, clean cold start, still bit-exact
    blob = bytearray(good)
    blob[len(blob) // 2] ^= 0xFF
    with open(store, "wb") as f:
        f.write(bytes(blob))
    got3, boot3, _ = serve()
    check(boot3["prefix_store_loaded"] == 0 and
          boot3["prefix_store_rejected"] >= 1,
          "corrupt store rejected whole (nothing partially imported)")
    check(all(np.array_equal(a, b) for a, b in zip(got3, cold)),
          "cold start after rejection still bit-exact")
    with open(store, "wb") as f:
        f.write(good)

    # fingerprint mismatch: same store, DIFFERENT weights — pages from
    # other weights would decode garbage; must cold-start clean
    import copy

    m2 = copy.deepcopy(model)
    sd = m2.state_dict()
    _, val = next(iter(sd.items()))
    val.set_value(val.numpy() + 0.25)
    with LLMEngine(m2, **kw) as eng:
        boot4 = eng.metrics()
        outs4 = eng.generate(waves[0], SamplingParams(max_new_tokens=4))
        check(boot4["prefix_store_loaded"] == 0 and
              boot4["prefix_store_rejected"] >= 1,
              "weight-fingerprint mismatch rejected the store")
        check(len(outs4) == len(waves[0]),
              "mismatched-store engine still serves (clean cold start)")


def drill_qos(out, model, n):
    """ISSUE 17 acceptance: multi-tenant QoS under a flood. Three
    tenants share one fleet — ``interactive`` (latency tier, weight 4),
    ``batchjobs`` (batch tier) and ``abuser`` (latency tier behind a
    40 tok/s admission quota). Batch work fills EVERY decode slot, then
    the interactive stream and an instant abuser burst land on top.
    Asserts: the abuser is rate-limited at the router with typed
    TenantQuotaExceededError + retry_after_s while other tenants are
    untouched; batch requests YIELD slots (batch_yields > 0 fleet-wide)
    but ALL complete bit-exact — deprioritised, never dropped; the
    interactive p99 TTFT under the flood stays within ~1.2x of an
    uncontended run of the SAME stream. Then a scale-down-during-flood
    burst: autoscale nominates the top slot mid-burst with
    ``serve.scale_down_kill`` armed — the draining replica is SIGKILLed
    mid-drain, its in-flight requests ride crash-redispatch (the drain
    is cancelled; recovery owns them), a later calm tick retires the
    slot cleanly to the new floor, and completed == submitted: the
    whole manoeuvre drops zero requests."""
    import bench_serving as bsv
    from paddle_tpu.inference.serving import (TIER_BATCH,
                                              TenantQuotaExceededError)
    from paddle_tpu.utils import fault_injection as fi

    cfg = _cfg(model)
    n = max(2, n)
    slots = n * ENGINE_KW["max_batch_size"]
    abuser_rate = 40.0  # tok/s bucket; the instant burst demands ~4x

    def jobs_from(stream, tenant, tier, bucket):
        return [dict(arrival=r.arrival, req=r, tenant=tenant, tier=tier,
                     bucket=bucket, idx=i) for i, r in enumerate(stream)]

    def configure(fleet):
        fleet.configure_tenant("interactive", weight=4.0)
        fleet.configure_tenant("batchjobs", weight=1.0)
        fleet.configure_tenant("abuser", rate_tokens_per_s=abuser_rate)

    def qos_burst(fleet, jobs, chaos=None):
        """run_burst with tenant/tier attribution: jobs merge several
        streams on one arrival clock; rejections come back typed."""
        jobs = sorted(jobs, key=lambda j: j["arrival"])
        gids = {"lat": {}, "bat": {}, "abu": {}}
        rejected = []
        fired = False
        t0 = time.perf_counter()
        i = 0
        while i < len(jobs) or fleet.pending():
            now = time.perf_counter() - t0
            while i < len(jobs) and jobs[i]["arrival"] <= now:
                j = jobs[i]
                try:
                    gids[j["bucket"]][j["idx"]] = fleet.submit(
                        j["req"].prompt, max_new=j["req"].max_new,
                        tenant=j["tenant"], tier=j["tier"])
                except Exception as e:
                    rejected.append((j["bucket"], j["idx"], e))
                i += 1
            progressed = fleet.step()
            if chaos is not None and not fired and i >= len(jobs) // 2:
                fired = bool(chaos(fleet))
            if i < len(jobs) and not fleet.pending():
                time.sleep(max(0.0, jobs[i]["arrival"]
                               - (time.perf_counter() - t0)))
            elif not progressed:
                time.sleep(0.001)
        fleet.join(timeout=300)
        return gids, rejected

    def lat_p99(fleet, gids):
        ttfts = sorted(fleet.request(g).t_first - fleet.request(g).t_submit
                       for g in gids["lat"].values()
                       if fleet.request(g).t_first is not None)
        return ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]

    def warm(fleet):
        """Replay a disjoint same-shape stream untimed so every replica
        has booted and compiled its prefill/decode graphs — the TTFT
        comparison must measure CONTENTION, not first-burst compiles."""
        wait_all_ready(fleet)
        for seed in (7, 8):  # two rounds: every replica sees every bucket
            for r in request_stream(cfg, seed=seed, rate=1e6):
                fleet.submit(r.prompt, max_new=r.max_new)
            fleet.join(timeout=300)

    # arm 1: the interactive stream ALONE — the uncontended reference
    lat_stream = request_stream(cfg, seed=0)
    lat_base = baseline_outputs(model, lat_stream)
    fleet = _fleet(out, n)
    try:
        configure(fleet)
        warm(fleet)
        gids, rejected = qos_burst(
            fleet, jobs_from(lat_stream, "interactive", None, "lat"))
        check(not rejected,
              f"uncontended arm admitted everything: {rejected}")
        assert_complete_bitexact(fleet, gids["lat"], lat_base)
        p99_u = lat_p99(fleet, gids)
        print(f"  [report] uncontended interactive p99 TTFT "
              f"{p99_u * 1e3:.0f}ms")
    finally:
        fleet.close()

    # arm 2: the flood — batch fills every slot, abuser bursts past its
    # quota, the SAME interactive stream must barely notice
    bat_stream = bsv.request_stream(cfg, n=slots, rate=1e6, min_prompt=4,
                                    max_prompt=12, min_new=16, max_new=24,
                                    seed=1)
    abu_stream = bsv.request_stream(cfg, n=12, rate=1e6, min_prompt=4,
                                    max_prompt=12, min_new=6, max_new=8,
                                    seed=2)
    bat_base = baseline_outputs(model, bat_stream)
    abu_base = baseline_outputs(model, abu_stream)
    out2 = os.path.join(out, "flood")
    os.makedirs(out2, exist_ok=True)
    fleet = _fleet(out, n, log_dir=out2)
    try:
        configure(fleet)
        warm(fleet)
        jobs = (jobs_from(bat_stream, "batchjobs", TIER_BATCH, "bat")
                + jobs_from(abu_stream, "abuser", None, "abu")
                + jobs_from(lat_stream, "interactive", None, "lat"))
        gids, rejected = qos_burst(fleet, jobs)
        check(rejected and all(b == "abu" for b, _, _ in rejected),
              f"only the abuser was rejected ({len(rejected)} rejections)")
        check(all(isinstance(e, TenantQuotaExceededError)
                  and getattr(e, "retry_after_s", 0) > 0
                  for _, _, e in rejected),
              f"{len(rejected)} abuser submits rejected with typed "
              "TenantQuotaExceededError + retry_after_s backoff hint")
        admitted = sum(len(abu_stream[i].prompt) + abu_stream[i].max_new
                       for i in gids["abu"])
        worst = max(len(r.prompt) + r.max_new for r in abu_stream)
        check(admitted <= abuser_rate + worst,
              f"abuser throughput capped at its quota ({admitted} token "
              f"demand admitted vs the {abuser_rate:.0f} tok/s bucket)")
        check(len(gids["bat"]) == len(bat_stream),
              "every batch-tier request was ADMITTED (deprioritised, "
              "never shed)")
        assert_complete_bitexact(fleet, gids["lat"], lat_base)
        assert_complete_bitexact(fleet, gids["bat"], bat_base)
        assert_complete_bitexact(fleet, gids["abu"], abu_base)
        yields = sum(
            int((fleet.replica_stats(h.id) or {}).get("batch_yields", 0))
            for h in fleet.supervisor.handles
            if h.alive and not h.retired)
        check(yields >= 1,
              f"batch-tier work YIELDED decode slots to latency traffic "
              f"({yields} yields fleet-wide) and still completed")
        m = fleet.metrics()
        check(m["quota_rejections"] == len(rejected),
              f"router accounted every quota rejection "
              f"({m['quota_rejections']})")
        p99_c = lat_p99(fleet, gids)
        # ~1.2x, with an absolute grace floor: on a shared CPU box a
        # handful of scheduler steps of added queueing dwarfs a tiny
        # uncontended p99 without meaning the QoS isolation failed
        bound = max(1.2 * p99_u, p99_u + 0.75)
        check(p99_c <= bound,
              f"interactive p99 TTFT under the flood "
              f"({p99_c * 1e3:.0f}ms) within ~1.2x of uncontended "
              f"({p99_u * 1e3:.0f}ms)")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()

    # arm 3: scale-down DURING a flood, with the retiring replica
    # SIGKILLed mid-drain — still zero-drop
    lat3 = request_stream(cfg, seed=3)
    bat3 = bsv.request_stream(cfg, n=slots, rate=1e6, min_prompt=4,
                              max_prompt=12, min_new=16, max_new=24,
                              seed=4)
    lat3_base = baseline_outputs(model, lat3)
    bat3_base = baseline_outputs(model, bat3)
    out3 = os.path.join(out, "scaledown")
    os.makedirs(out3, exist_ok=True)
    fleet = _fleet(out, n, log_dir=out3)
    try:
        configure(fleet)

        def chaos(fl):
            print(f"[chaos] autoscale armed mid-flood (floor {n - 1}): "
                  "the next calm tick drains the top slot with "
                  "serve.scale_down_kill armed")
            fl.enable_autoscale(n - 1, n, low_water=1.0, high_water=1.01,
                                cooldown_s=1.0, max_events=4)
            return True

        jobs = (jobs_from(bat3, "batchjobs", TIER_BATCH, "bat")
                + jobs_from(lat3, "interactive", None, "lat"))
        with fi.inject("serve.scale_down_kill", max_fires=1) as inj:
            gids, rejected = qos_burst(fleet, jobs, chaos=chaos)
            # keep ticking until a clean retry retires the slot (the
            # killed drain was cancelled — recovery owned its requests)
            deadline = time.time() + 90
            while time.time() < deadline and (
                    fleet.supervisor.n_active > n - 1
                    or fleet.metrics()["replicas_draining"]):
                fleet.step()
                time.sleep(0.005)
        fleet.disable_autoscale()
        check(not rejected, f"nothing shed during scale-down: {rejected}")
        check(inj.fires == 1,
              "the first scale-down decision SIGKILLed the draining "
              "replica mid-drain (serve.scale_down_kill fired)")
        m = fleet.metrics()
        check(m["redispatches"] >= 1,
              f"the killed replica's in-flight requests rode "
              f"crash-redispatch ({m['redispatches']}x)")
        check(m["replica_restarts"] >= 1,
              f"supervisor respawned the killed slot "
              f"({m['replica_restarts']} restarts)")
        check(fleet.scale_downs >= 2 and fleet.drains_completed >= 1
              and fleet.supervisor.n_active == n - 1,
              f"a clean retry retired the slot to the new floor "
              f"({fleet.scale_downs} down decisions, "
              f"n_active={fleet.supervisor.n_active})")
        assert_complete_bitexact(fleet, gids["lat"], lat3_base)
        assert_complete_bitexact(fleet, gids["bat"], bat3_base)
        done = len(gids["lat"]) + len(gids["bat"])
        check(done == len(lat3) + len(bat3),
              f"scale-down during the flood dropped ZERO requests "
              f"({done}/{len(lat3) + len(bat3)})")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_tpgroup(out, model, n):
    """ISSUE 19 acceptance: model-parallel replica GROUPS under partial
    failure. Two slots, each a 2-process tp=2 group (4 worker processes,
    one plan-sharded engine per group in SPMD lockstep). Mid-burst, the
    fault sites fire on NON-ZERO ranks only: group 0's rank 1 SIGKILLs
    itself (``serve.group_member_crash``) while group 1's rank 1 wedges
    (``serve.group_member_hang``) — so every failure starts as a
    HALF-DEAD group whose rank 0 still owns a live RPC stream. The
    supervisor must fell each whole group atomically (survivors
    SIGTERM→SIGKILL), charge ONE restart-budget slot per group, respawn
    on fresh coordination ports, rejoin from the checkpoint root, and
    the router must replay the in-flight requests bit-exact."""
    import json

    from paddle_tpu.observability import metrics as om

    n = 2  # two groups of two processes — the drill's fixed topology
    stream = request_stream(_cfg(model))
    baseline = baseline_outputs(model, stream)
    env = {"CHAOS_SERVE_SITES": json.dumps([
        {"site": "serve.group_member_crash", "replica": 0, "rank": 1,
         "after": HANG_AFTER_STEPS},
        {"site": "serve.group_member_hang", "replica": 1, "rank": 1,
         "after": HANG_AFTER_STEPS},
    ])}
    fleet = _fleet(out, n, hang_timeout_s=HANG_TIMEOUT_S,
                   env_extra=env, group_size=2,
                   plan={"axes": {"tp": 2}, "strategies": ["tp"]})
    try:
        for h in fleet.supervisor.handles:
            check(h.ready_info.get("group_size") == 2,
                  f"group {h.id} reported ready only after BOTH ranks "
                  "acked warm-up")
        ports0 = [h.coord_port for h in fleet.supervisor.handles]
        gids, shed, wall = run_burst(fleet, stream)
        wait_all_ready(fleet)
        check(not shed, f"no request shed (queue bound ample): {shed}")
        done = assert_complete_bitexact(fleet, gids, baseline)
        check(done == len(stream),
              f"completed == submitted ({done}/{len(stream)}): nothing "
              "dropped silently")
        m = fleet.metrics()
        check(m["redispatches"] >= 1,
              f"in-flight requests were redispatched "
              f"({m['redispatches']}x) off the felled groups")
        check(m["replica_restarts"] >= 2,
              f"both half-dead groups were felled WHOLE and restarted "
              f"({m['replica_restarts']} group restarts)")
        g_restarts = om.REGISTRY.get("fleet_group_restarts_total").value(
            instance=fleet._name)
        check(g_restarts >= 2,
              f"fleet_group_restarts_total counted them ({g_restarts})")
        check(g_restarts <= 2 * 3,
              f"group restarts stayed within the leaky-bucket budget "
              f"({g_restarts} <= 3 per slot)")
        for h in fleet.supervisor.handles:
            check(h.incarnation >= 1, f"group {h.id} was respawned")
            check(h.coord_port != ports0[h.id],
                  f"group {h.id} respawned on a FRESH coordination port "
                  f"({ports0[h.id]} -> {h.coord_port})")
            check(h.ready_info.get("reloaded_step") == 1,
                  f"group {h.id} rejoined via reload_weights("
                  "latest_healthy_step()) at checkpoint step 1")
            live = om.REGISTRY.get("fleet_group_members_live").value(
                instance=fleet._name, replica=h.id)
            check(live == 2,
                  f"fleet_group_members_live recovered to 2 for group "
                  f"{h.id} ({live})")
        vals = read_liveness(out)
        check(any(v < n for v in vals),
              f"fleet liveness gauge dipped below {n} (transitions: "
              f"{vals})")
        first_dip = next(i for i, v in enumerate(vals) if v < n)
        check(any(v == n for v in vals[first_dip:]),
              f"fleet liveness gauge recovered to {n} (transitions: "
              f"{vals})")
        toks = sum(len(fleet.tokens(g)) for g in gids.values())
        print(f"  [report] {toks} tokens in {wall:.1f}s "
              f"({toks / wall:.1f} tok/s, 2 tp=2 groups, one member "
              "killed, one member hung)")
        assert_replicas_clean(fleet)
    finally:
        fleet.close()


def drill_sdc(out, model, n):
    """ISSUE 20 acceptance: silent-data-corruption defense, end to end.
    Three arms, each a different ``serve.bit_flip`` target:

    A. host-tier flip: a spilled request's resident host entry gets one
       payload byte flipped AFTER its CRC seal was computed — the
       read-back verification at revive must reject the entry
       (``serving_kv_pages_rejected_total``), degrade to re-prefill
       (scheduler ``revive_misses``), and the output must still be
       bit-identical to an undisturbed reference.
    B. weight flip on an idle fleet replica: wave-1 traffic is
       session-pinned to replicas 0/1, so replica 2's FIRST busy tick —
       the first sampled output audit placed on it — fires the armed
       flip. The corrupt audit stream mismatches the served one, the
       third-replica referee votes the auditor corrupt, and replica 2
       is QUARANTINED: one restart-budget slot, liveness dips and
       recovers, its in-flight audits redispatch, and every DELIVERED
       output (both waves) matches the single-engine baseline.
    C. single-engine weight re-audit: a weight flip is detected by
       ``audit_weights()`` (fingerprint drift,
       ``serving_weight_audit_failures_total``), ``reload_weights``
       from the artifact re-anchors the reference, and serving is
       bit-exact again."""
    import json as _json

    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              load_llama_artifact)
    from paddle_tpu.inference.serving import integrity
    from paddle_tpu.utils import fault_injection as fi

    cfg = _cfg(model)
    artifact = os.path.join(out, "model")

    # ---- arm A: host-tier entry flip, caught at revive by CRC --------
    rng = np.random.RandomState(20)
    prompts = [rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=3,
                   ingest_async=False) as ref_eng:
        refs = ref_eng.generate(prompts,
                                SamplingParams(max_new_tokens=20))
    # tiny pool forces decode-pressure eviction -> spill to host tier
    eng = LLMEngine(model, num_blocks=5, block_size=8, max_batch_size=2,
                    kv_host_blocks=32, kv_page_checksums=True,
                    ingest_async=False)
    try:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=20))
                for p in prompts]
        flipped = None
        with fi.inject("serve.bit_flip", max_fires=1):
            while eng.has_work():
                eng.step()
                if (flipped is None and eng.kv_tier is not None
                        and eng.kv_tier._entries
                        and fi.should_fire("serve.bit_flip")):
                    # flip one byte of the resident spill AFTER its
                    # seal — exactly what a bad DIMM would do
                    flipped = integrity.flip_bit(eng, "host_entry")
        outs = [eng.output_tokens(r) for r in rids]
        em, st = eng.metrics(), eng.stats()
    finally:
        eng.close()
    check(flipped is not None,
          f"the bit flip landed on a resident host-tier entry "
          f"({flipped})")
    check(em["kv_pages_rejected"] >= 1,
          f"read-back CRC caught the flipped entry "
          f"({int(em['kv_pages_rejected'])} rejections) — the corrupt "
          "page was never served")
    check(st["revive_misses"] >= 1,
          f"the rejected revive degraded to re-prefill "
          f"({st['revive_misses']} revive misses)")
    for got, ref in zip(outs, refs):
        if not np.array_equal(got, ref):
            raise AssertionError(
                f"corrupted-then-reprefilled output diverged: "
                f"{got.tolist()} vs {ref.tolist()}")
    print("  ok: outputs bit-identical to the undisturbed reference "
          "despite the flipped spill")

    # ---- arm B: weight flip on a fleet replica, caught by the audit --
    stream = request_stream(cfg, n=10)
    baseline = baseline_outputs(model, stream)
    stream2 = request_stream(cfg, seed=1, n=6)
    baseline2 = baseline_outputs(model, stream2)
    env = {"CHAOS_SERVE_SITES": _json.dumps([
               {"site": "serve.bit_flip", "replica": 2, "after": 1,
                "max_fires": 1}]),
           "CHAOS_SERVE_BIT_FLIP_TARGET": "weights"}
    fleet = _fleet(out, 3, env_extra=env, audit_fraction=1.0,
                   max_inflight_per_replica=64)
    try:
        # session-pin wave 1 to replicas 0/1: replica 2 stays idle, so
        # its first busy tick — the first AUDIT placed there — fires
        # the flip. No corrupt token is ever DELIVERED: the flip can
        # only touch background audit replays.
        gids = {}
        for i, r in enumerate(stream):
            gids[i] = fleet.submit(r.prompt, max_new=r.max_new,
                                   session=f"s{i % 2}")
        fleet.join(timeout=300)
        wait_all_ready(fleet)
        m = fleet.metrics()
        check(m["audits_run"] >= 1,
              f"sampled output audits ran ({m['audits_run']})")
        check(m["audit_mismatches"] >= 1,
              f"the corrupt replica's replay mismatched the served "
              f"stream ({m['audit_mismatches']} mismatches)")
        check(m["replicas_quarantined"] == 1,
              f"referee vote quarantined exactly the corrupt replica "
              f"({m['replicas_quarantined']} quarantines)")
        check(m["replica_restarts"] == 1,
              f"quarantine charged exactly ONE restart-budget slot "
              f"({m['replica_restarts']} restarts)")
        check(any(e.get("stage") == "quarantine" and e.get("replica") == 2
                  for e in fleet.audit_log),
              "the quarantined replica is the one the flip was armed on")
        # whether the auditor still holds in-flight audits when the
        # referee verdict lands is timing-dependent (the verdict races
        # the auditor draining its queue); the deterministic
        # requeue + bit-exact-replay property is pinned by
        # tests/test_integrity.py. When the race does leave work in
        # flight, the bit-exact checks below cover the redispatches.
        print(f"  note: {int(m['redispatches'])} in-flight request(s) "
              f"redispatched at the quarantine")
        vals = read_liveness(out)
        check(any(v < 3 for v in vals),
              f"fleet liveness dipped at the quarantine (transitions: "
              f"{vals})")
        first_dip = next(i for i, v in enumerate(vals) if v < 3)
        check(any(v == 3 for v in vals[first_dip:]),
              f"fleet liveness recovered after the respawn "
              f"(transitions: {vals})")
        assert_complete_bitexact(fleet, gids, baseline)
        print("  ok: the flip never reached a client — every DELIVERED "
              "wave-1 output matched the baseline")
        # wave 2 over the healed fleet (respawned replica serves again)
        gids2 = {i: fleet.submit(r.prompt, max_new=r.max_new)
                 for i, r in enumerate(stream2)}
        fleet.join(timeout=300)
        assert_complete_bitexact(fleet, gids2, baseline2)
        print("  ok: wave 2 bit-exact after the heal")
        assert_replicas_clean(fleet)
        st = fleet.stats()
        check(st["fleet"]["audits_run"] >= m["audits_run"]
              and st["fleet"]["replicas_quarantined"] == 1,
              "Router.stats() carries the fleet integrity counters")
        for rid, s in sorted(st["replicas"].items()):
            check(s is not None and "kv_pages_verified" in s
                  and "kv_pages_rejected" in s and "weight_audits" in s
                  and "weight_audit_failures" in s,
                  f"replica {rid} stats RPC exposes its integrity "
                  "counters")
    finally:
        fleet.close()

    # ---- arm C: weight flip caught by the periodic re-audit ----------
    m2 = load_llama_artifact(artifact)
    with LLMEngine(m2, num_blocks=32, block_size=8, max_batch_size=2,
                   ingest_async=False, weight_audit=True) as eng:
        p = prompts[0]
        before = eng.generate([p], SamplingParams(max_new_tokens=8))[0]
        check(eng.audit_weights(), "clean weights pass the re-audit")
        flip = integrity.flip_bit(eng, "weights")
        check(flip is not None and flip["flips"] >= 1,
              f"weight flip landed ({flip})")
        check(not eng.audit_weights(),
              "fingerprint drift detected by the re-audit")
        em = eng.metrics()
        check(em["weight_audit_failures"] >= 1,
              f"serving_weight_audit_failures_total counted it "
              f"({int(em['weight_audit_failures'])})")
        eng.reload_weights(artifact)
        check(eng.audit_weights(),
              "reload_weights re-anchored the audit reference")
        after = eng.generate([p], SamplingParams(max_new_tokens=8))[0]
        check(np.array_equal(before, after),
              "serving bit-exact again after the reload")


def _cfg(model):
    return model.config


DRILLS = {"kill": drill_kill, "hang": drill_hang, "drain": drill_drain,
          "shed": drill_shed, "quant": drill_quant,
          "disagg": drill_disagg, "warmstore": drill_warmstore,
          "qos": drill_qos, "tpgroup": drill_tpgroup, "sdc": drill_sdc}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--drill", default="all",
                    choices=["kill", "hang", "drain", "shed", "quant",
                             "disagg", "warmstore", "qos", "tpgroup",
                             "sdc", "all"])
    ap.add_argument("--fleet", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    out_root = args.out or tempfile.mkdtemp(prefix="chaos_serve.")
    print(f"[chaos] serving fleet drill, scratch: {out_root}, "
          f"fleet={args.fleet}")
    drills = (["kill", "hang", "drain", "shed", "quant", "disagg",
               "warmstore", "qos", "tpgroup", "sdc"]
              if args.drill == "all" else [args.drill])
    model = None
    for name in drills:
        out = os.path.join(out_root, name)
        os.makedirs(out, exist_ok=True)
        model, _, _ = build_fixture(out)
        print(f"[chaos] drill {name!r} (fleet of {args.fleet})...")
        t0 = time.time()
        DRILLS[name](out, model, args.fleet)
        print(f"  done in {time.time() - t0:.1f}s")
    print(f"[chaos] SERVE DRILL PASSED ({', '.join(drills)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
