"""Serving fleet tests (ISSUE 12): typed errors, router placement /
deadlines / shedding / redispatch-dedup / drain against fake replica
handles (fast, no subprocesses), plus one real single-replica
end-to-end smoke. The full chaos matrix (SIGKILL + hang + drain over a
real 3-replica fleet) lives in scripts/chaos_serve.py, wired slow-tier
in tests/test_serving.py."""

from __future__ import annotations

import time

import numpy as np
import pytest

from paddle_tpu.inference.serving import (
    EngineClosedError, FleetOverloadedError, ReplicaCrashLoopError,
    RequestTimeoutError,
)
from paddle_tpu.inference.serving.fleet import Router
from paddle_tpu.observability import metrics as om
from paddle_tpu.utils import fault_injection as fi


# ---------------------------------------------------------------------------
# fakes: the Router's supervisor/handle contract, no processes
# ---------------------------------------------------------------------------

class FakeHandle:
    def __init__(self, hid):
        self.id = hid
        self.ready = True
        self.ready_info = {"e": "ready", "replica": hid}
        self.alive = True
        self.retired = False
        self.sent = []
        self.inbox = []

    def send(self, obj):
        if not self.alive:
            return False
        self.sent.append(obj)
        return True

    def events(self):
        out, self.inbox = self.inbox, []
        for ev in out:
            if ev.get("e") == "ready":
                self.ready = True
                self.ready_info = ev
        return out

    def submits(self):
        return [s for s in self.sent if s.get("op") == "submit"]


class FakeSupervisor:
    def __init__(self, n):
        self.handles = [FakeHandle(i) for i in range(n)]
        self.deaths = []
        self.shut = False
        self.crash_loop = None

    def check(self, now=None):
        if self.crash_loop is not None:
            raise self.crash_loop
        out, self.deaths = self.deaths, []
        return out

    def retire(self, i):
        h = self.handles[i]
        h.retired = True
        h.alive = False

    def shutdown(self):
        self.shut = True

    # test helpers -----------------------------------------------------
    def die(self, i, leftover=()):
        h = self.handles[i]
        h.alive = False
        self.deaths.append({"replica": i, "reason": "crash", "rc": -9,
                            "events": list(leftover)})
        self.handles[i] = FakeHandle(i)
        # a real respawn is NOT ready until its boot finishes — placement
        # must route the replay to a healthy peer, not the empty slot
        self.handles[i].ready = False

    def feed(self, i, ev):
        self.handles[i].inbox.append(ev)


def make_fleet(n=2, **kw):
    kw.setdefault("engine_kwargs", {"max_batch_size": 4})
    sup = FakeSupervisor(n)
    fleet = Router(supervisor=sup, **kw)
    return fleet, sup


def tok_ev(gid, gen, toks, fin=False, reason=None):
    return {"e": "tok", "gid": gid, "gen": gen, "toks": list(toks),
            "fin": fin, "reason": reason if fin else None}


PROMPT = np.arange(1, 7, dtype=np.int32)


# ---------------------------------------------------------------------------
# typed errors
# ---------------------------------------------------------------------------

class TestOneProcessPerChip:
    def test_imports_initialise_no_backend(self):
        """A chip belongs to one process: the parent that only routes or
        launches must be able to import the package, the fleet router and
        the launcher without touching a JAX backend (ISSUE 21)."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            f"import sys; sys.path.insert(0, {repo!r})\n"
            "import paddle_tpu\n"
            "import paddle_tpu.inference.serving.fleet.router\n"
            "import paddle_tpu.inference.serving.fleet.supervisor\n"
            "import paddle_tpu.distributed.launch\n"
            "import paddle_tpu.distributed.launch.bootstrap\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]


class TestTypedErrors:
    def test_hierarchy_and_exports(self):
        from paddle_tpu.distributed.launch import (CrashLoopError,
                                                   RestartBudget)
        from paddle_tpu.inference.serving import fleet as fleet_mod

        assert issubclass(ReplicaCrashLoopError, CrashLoopError)
        assert issubclass(RequestTimeoutError, TimeoutError)
        assert issubclass(FleetOverloadedError, RuntimeError)
        assert issubclass(EngineClosedError, RuntimeError)
        for name in ("Router", "ReplicaSupervisor", "RequestTimeoutError",
                     "FleetOverloadedError", "ReplicaCrashLoopError"):
            assert hasattr(fleet_mod, name)
        # the serving supervisor reuses the launcher's leaky bucket
        b = RestartBudget(2, window_s=100.0, backoff_base_s=0.0)
        assert b.try_acquire() and b.try_acquire() and not b.try_acquire()

    def test_crash_loop_error_fields(self):
        e = ReplicaCrashLoopError("boom", replica=3, exit_code=-9,
                                  restarts=4)
        assert e.replica == 3 and e.exit_code == -9 and e.restarts == 4


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

class TestPlacement:
    def test_least_loaded_spreads(self):
        fleet, sup = make_fleet(3)
        try:
            for _ in range(6):
                fleet.submit(PROMPT, max_new=4)
            fleet.step()
            counts = [len(h.submits()) for h in sup.handles]
            assert counts == [2, 2, 2]
        finally:
            fleet.close()

    def test_session_affinity_prefers_last_replica(self):
        fleet, sup = make_fleet(2)
        try:
            fleet.submit(PROMPT, max_new=4, session="tenant-a")
            fleet.step()
            first = next(i for i, h in enumerate(sup.handles)
                         if h.submits())
            # load the other replica less, then submit the session again:
            # affinity must beat least-loaded
            fleet.submit(PROMPT, max_new=4, session="tenant-a")
            fleet.step()
            assert len(sup.handles[first].submits()) == 2
        finally:
            fleet.close()

    def test_load_reports_break_ties(self):
        fleet, sup = make_fleet(2)
        try:
            # replica 0 reports hot gauges; equal inflight -> pick 1
            sup.feed(0, {"e": "load", "kv": 0.9, "occ": 0.9})
            fleet.step()
            fleet.submit(PROMPT, max_new=4)
            fleet.step()
            assert len(sup.handles[1].submits()) == 1
        finally:
            fleet.close()

    def test_inflight_cap_queues_then_shed_at_bound(self):
        fleet, sup = make_fleet(1, max_queue=2,
                                max_inflight_per_replica=1)
        try:
            fleet.submit(PROMPT, max_new=4)
            fleet.step()                      # placed (cap 1 reached)
            fleet.submit(PROMPT, max_new=4)   # queued 1
            fleet.submit(PROMPT, max_new=4)   # queued 2 = bound
            with pytest.raises(FleetOverloadedError) as ei:
                fleet.submit(PROMPT, max_new=4)
            assert ei.value.queue_depth == 2
            # registry truth: fleet_requests_shed_total + queue gauge
            inst = fleet._name
            assert om.REGISTRY.get("fleet_requests_shed_total").value(
                instance=inst) == 1
            fleet.step()
            assert om.REGISTRY.get("fleet_queue_depth").value(
                instance=inst) == 2
            assert fleet.metrics()["requests_shed"] == 1
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# deadlines (ISSUE 12 satellite: the edge matrix)
# ---------------------------------------------------------------------------

class TestRouterDeadlines:
    def test_expired_at_submit_rejected_before_queueing(self):
        fleet, _ = make_fleet(1)
        try:
            with pytest.raises(RequestTimeoutError):
                fleet.submit(PROMPT, max_new=4, deadline_s=0.0)
            assert fleet.pending() == []
            assert fleet.metrics()["deadline_expired"] == 1
        finally:
            fleet.close()

    def test_queued_expiry_surfaces_at_tick(self):
        fleet, sup = make_fleet(1)
        sup.handles[0].ready = False  # nothing placeable: stays queued
        try:
            gid = fleet.submit(PROMPT, max_new=4, deadline_s=0.01)
            time.sleep(0.03)
            fleet.step()
            with pytest.raises(RequestTimeoutError):
                fleet.result(gid)
            assert fleet.tokens(gid) == []
            # fleet_deadline_expired_total counts it
            assert om.REGISTRY.get("fleet_deadline_expired_total").value(
                instance=fleet._name) == 1
        finally:
            fleet.close()

    def test_placed_expiry_cancels_on_replica(self):
        fleet, sup = make_fleet(1)
        try:
            gid = fleet.submit(PROMPT, max_new=8, deadline_s=0.02)
            fleet.step()
            sup.feed(0, tok_ev(gid, 1, [7]))
            fleet.step()
            time.sleep(0.04)
            fleet.step()
            with pytest.raises(RequestTimeoutError):
                fleet.result(gid)
            # the partial stream survives; the replica was told to free
            assert fleet.tokens(gid) == [7]
            assert any(s.get("op") == "cancel" and s["gid"] == gid
                       for s in sup.handles[0].sent)
        finally:
            fleet.close()

    def test_deadline_survives_redispatch(self):
        """The replay inherits the ORIGINAL absolute deadline, not a
        fresh one (ISSUE 12 satellite)."""
        fleet, sup = make_fleet(2)
        try:
            gid = fleet.submit(PROMPT, max_new=8, deadline_s=30.0)
            fleet.step()
            original = fleet.request(gid).deadline
            src = next(i for i, h in enumerate(sup.handles)
                       if h.submits())
            first_payload = sup.handles[src].submits()[0]
            assert first_payload["deadline"] == pytest.approx(original)
            sup.feed(src, tok_ev(gid, 1, [9, 11]))
            fleet.step()
            sup.die(src)
            fleet.step()
            other = 1 - src
            replay = sup.handles[other].submits()[0]
            assert replay["deadline"] == pytest.approx(original)
            assert fleet.request(gid).deadline == original
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# redispatch + dedup
# ---------------------------------------------------------------------------

class TestRedispatch:
    def test_replay_resumes_from_emitted_tokens(self):
        fleet, sup = make_fleet(2)
        try:
            gid = fleet.submit(PROMPT, max_new=6)
            fleet.step()
            src = next(i for i, h in enumerate(sup.handles)
                       if h.submits())
            # 2 tokens emitted, then the replica dies with one more token
            # stranded in its final (post-mortem drained) events
            sup.feed(src, tok_ev(gid, 1, [101, 102]))
            fleet.step()
            sup.die(src, leftover=[tok_ev(gid, 1, [103])])
            fleet.step()
            other = 1 - src
            replay = sup.handles[other].submits()[0]
            # replay = original prompt + ALL emitted (incl. the stranded
            # token) with the remaining budget
            assert replay["prompt"] == PROMPT.tolist() + [101, 102, 103]
            assert replay["max_new"] == 3
            assert replay["gen"] == 2
            assert fleet.metrics()["redispatches"] == 1
            assert om.REGISTRY.get("fleet_redispatches_total").value(
                instance=fleet._name) == 1
            # finish on the new replica; full stream = old + new tokens
            sup.feed(other, tok_ev(gid, 2, [104, 105, 106], fin=True,
                                   reason="length"))
            fleet.step()
            out = fleet.result(gid)
            assert out.tolist() == (PROMPT.tolist()
                                    + [101, 102, 103, 104, 105, 106])
        finally:
            fleet.close()

    def test_superseded_assignment_cannot_double_emit(self):
        fleet, sup = make_fleet(2)
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            src = next(i for i, h in enumerate(sup.handles)
                       if h.submits())
            sup.feed(src, tok_ev(gid, 1, [7]))
            fleet.step()
            sup.die(src)  # presumed dead -> replay on the other replica
            fleet.step()
            other = 1 - src
            # the "dead" replica's zombie incarnation keeps emitting with
            # the OLD generation — every token must be dropped
            sup.feed(src, tok_ev(gid, 1, [8, 9], fin=True,
                                 reason="length"))
            fleet.step()
            assert fleet.tokens(gid) == [7]
            assert not fleet.request(gid).finished
            sup.feed(other, tok_ev(gid, 2, [8, 9, 10], fin=True,
                                   reason="length"))
            fleet.step()
            assert fleet.result(gid).tolist() == (PROMPT.tolist()
                                                  + [7, 8, 9, 10])
        finally:
            fleet.close()

    def test_dispatch_fault_requeues_with_bumped_generation(self):
        fleet, sup = make_fleet(2)
        try:
            with fi.inject("serve.dispatch", max_fires=1):
                gid = fleet.submit(PROMPT, max_new=4)
                fleet.step()   # first dispatch attempt fails, requeued
                fleet.step()   # second attempt lands
            subs = [s for h in sup.handles for s in h.submits()]
            assert len(subs) == 1 and subs[0]["gen"] == 2
            assert fleet.metrics()["redispatches"] == 1
            assert fleet.request(gid).state == "placed"
        finally:
            fleet.close()

    def test_fully_emitted_request_finishes_without_replay(self):
        """max_new tokens already emitted when the replica died — only
        the fin event was lost; the router completes it locally."""
        fleet, sup = make_fleet(2)
        try:
            gid = fleet.submit(PROMPT, max_new=2)
            fleet.step()
            src = next(i for i, h in enumerate(sup.handles)
                       if h.submits())
            sup.die(src, leftover=[tok_ev(gid, 1, [5, 6])])
            fleet.step()
            assert fleet.result(gid).tolist() == PROMPT.tolist() + [5, 6]
            assert fleet.metrics()["redispatches"] == 0
        finally:
            fleet.close()

    def test_crash_loop_propagates(self):
        fleet, sup = make_fleet(1)
        sup.crash_loop = ReplicaCrashLoopError("gone", replica=0)
        with pytest.raises(ReplicaCrashLoopError):
            fleet.step()
        fleet.close()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

class TestDrain:
    def test_drain_blocks_placement_until_resumed(self):
        fleet, sup = make_fleet(2)
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            src = next(i for i, h in enumerate(sup.handles)
                       if h.submits())
            fleet.drain(src, then="resume")
            # draining replica takes nothing new (session affinity too)
            fleet.submit(PROMPT, max_new=4, session="s")
            fleet.step()
            assert len(sup.handles[src].submits()) == 1
            assert om.REGISTRY.get("fleet_replicas_draining").value(
                instance=fleet._name) == 1
            # in-flight request finishes -> drain completes
            sup.feed(src, tok_ev(gid, 1, [1, 2, 3, 4], fin=True,
                                 reason="length"))
            fleet.step()
            assert fleet.drains_completed == 1
            assert fleet.metrics()["replicas_draining"] == 0
            fleet.submit(PROMPT, max_new=4)
            fleet.step()  # replica is placeable again
            assert sum(len(h.submits()) for h in sup.handles) == 3
        finally:
            fleet.close()

    def test_drain_reload_hot_swaps_weights(self):
        fleet, sup = make_fleet(1)
        try:
            gid = fleet.submit(PROMPT, max_new=2)
            fleet.step()
            fleet.drain(0, then="reload", ckpt_root="/ckpt/root")
            sup.feed(0, tok_ev(gid, 1, [1, 2], fin=True, reason="length"))
            fleet.step()
            reloads = [s for s in sup.handles[0].sent
                       if s.get("op") == "reload"]
            assert reloads == [{"op": "reload", "root": "/ckpt/root"}]
            assert fleet.metrics()["replicas_draining"] == 1  # awaiting ack
            sup.feed(0, {"e": "reloaded", "replica": 0, "step": 7})
            fleet.step()
            assert fleet.reloads == [(0, 7)]
            assert fleet.drains_completed == 1
        finally:
            fleet.close()

    def test_drain_retire_stops_the_replica(self):
        fleet, sup = make_fleet(2)
        try:
            fleet.drain(1, then="retire")
            fleet.step()
            assert sup.handles[1].retired
            fleet.submit(PROMPT, max_new=4)
            fleet.step()
            assert len(sup.handles[0].submits()) == 1
        finally:
            fleet.close()

    def test_drain_validates_arguments(self):
        fleet, _ = make_fleet(1)
        try:
            with pytest.raises(ValueError):
                fleet.drain(0, then="explode")
            with pytest.raises(ValueError):
                fleet.drain(99)
            with pytest.raises(ValueError):
                fleet.drain(0, then="reload")  # no ckpt_root anywhere
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# router lifecycle
# ---------------------------------------------------------------------------

class TestRouterLifecycle:
    def test_close_removes_registry_series_and_guards(self):
        fleet, sup = make_fleet(1)
        name = fleet._name
        fleet.submit(PROMPT, max_new=4)
        fleet.close()
        assert sup.shut
        for metric in ("fleet_redispatches_total",
                       "fleet_requests_shed_total",
                       "fleet_deadline_expired_total",
                       "fleet_queue_depth", "fleet_replicas_draining"):
            snap = om.REGISTRY.snapshot().get(metric, {"series": {}})
            assert not any(name in k for k in snap["series"]), metric
        with pytest.raises(EngineClosedError):
            fleet.submit(PROMPT, max_new=4)
        with pytest.raises(EngineClosedError):
            fleet.step()
        fleet.close()  # idempotent

    def test_replica_stats_routes_surrounding_events(self):
        """Events drained in the same batch as the stats reply must go
        through the normal pump — ``events()`` is destructive, so
        returning mid-batch used to drop live tokens forever."""
        fleet, sup = make_fleet(1)
        try:
            gid = fleet.submit(PROMPT, max_new=2)
            fleet.step()
            sup.feed(0, tok_ev(gid, 1, [5]))
            sup.feed(0, {"e": "stats", "replica": 0, "blocks_free": 47})
            sup.feed(0, tok_ev(gid, 1, [6], fin=True, reason="length"))
            stats = fleet.replica_stats(0)
            assert stats["blocks_free"] == 47
            assert fleet.result(gid).tolist() == PROMPT.tolist() + [5, 6]
        finally:
            fleet.close()

    def test_metrics_reads_injected_supervisors_instance(self):
        """Supervisor-owned gauges live under the SUPERVISOR's instance
        label; an injected supervisor keeps its own name."""
        from paddle_tpu.inference.serving.fleet.supervisor import _G_LIVE

        sup = FakeSupervisor(2)
        sup.instance = "external-fleet"
        fleet = Router(supervisor=sup, engine_kwargs={"max_batch_size": 4})
        try:
            _G_LIVE.set(2, instance="external-fleet")
            assert fleet.metrics()["replicas_live"] == 2
        finally:
            _G_LIVE.remove(instance="external-fleet")
            fleet.close()

    def test_result_and_release_contract(self):
        fleet, sup = make_fleet(1)
        try:
            gid = fleet.submit(PROMPT, max_new=2)
            with pytest.raises(ValueError):
                fleet.release(gid)  # unfinished
            fleet.step()
            with pytest.raises(RuntimeError):
                fleet.result(gid)   # still running
            sup.feed(0, tok_ev(gid, 1, [3, 4], fin=True, reason="length"))
            fleet.step()
            assert fleet.result(gid).tolist() == PROMPT.tolist() + [3, 4]
            fleet.release(gid)
            assert fleet.pending() == []
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# real single-replica end-to-end (subprocess; the chaos matrix is slow-tier)
# ---------------------------------------------------------------------------

class TestRealFleetSmoke:
    def test_single_replica_bit_exact_and_liveness(self, tmp_path):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import (LLMEngine,
                                                  SamplingParams,
                                                  save_llama_artifact)
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        artifact = str(tmp_path / "model")
        save_llama_artifact(model, artifact)
        kw = dict(num_blocks=48, block_size=8, max_batch_size=2)
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, model.config.vocab_size, n)
                   .astype(np.int32) for n in (5, 11)]
        with LLMEngine(model, ingest_async=False, **kw) as eng:
            refs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        fleet = Router(artifact=artifact, n_replicas=1, engine_kwargs=kw,
                       log_dir=str(tmp_path / "logs"))
        try:
            # fleet_replicas_live / fleet_replica_restarts_total are the
            # supervisor-owned registry series
            assert om.REGISTRY.get("fleet_replicas_live").value(
                instance=fleet._name) == 1
            assert om.REGISTRY.get("fleet_replica_restarts_total").value(
                instance=fleet._name) == 0
            gids = [fleet.submit(p, max_new=6) for p in prompts]
            fleet.join(timeout=120)
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
            stats = fleet.replica_stats(0)
            assert stats["blocks_free"] == kw["num_blocks"] - 1
            assert stats["running"] == 0 and stats["waiting"] == 0
        finally:
            fleet.close()
        snap = om.REGISTRY.snapshot().get("fleet_replicas_live",
                                          {"series": {}})
        assert not any(fleet._name in k for k in snap["series"])

    def test_replica_crash_site_respawn_and_replay(self, tmp_path):
        """Fault site ``serve.replica_crash``: the replica SIGKILLs
        itself mid-serve (armed via env, incarnation 0 only); the
        supervisor respawns it and the router replays its in-flight
        requests — outputs stay bit-identical to an undisturbed
        engine."""
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import (LLMEngine,
                                                  SamplingParams,
                                                  save_llama_artifact)
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        artifact = str(tmp_path / "model")
        save_llama_artifact(model, artifact)
        kw = dict(num_blocks=48, block_size=8, max_batch_size=4)
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, model.config.vocab_size, n)
                   .astype(np.int32) for n in (6, 9, 5)]
        with LLMEngine(model, ingest_async=False, **kw) as eng:
            refs = eng.generate(prompts,
                                SamplingParams(max_new_tokens=8))
        fleet = Router(
            artifact=artifact, n_replicas=1, engine_kwargs=kw,
            log_dir=str(tmp_path / "logs"), max_restarts=2,
            env_extra={"CHAOS_SERVE_SITE": "serve.replica_crash",
                       "CHAOS_SERVE_REPLICA": "0",
                       "CHAOS_SERVE_AFTER_STEPS": "3"})
        try:
            gids = [fleet.submit(p, max_new=8) for p in prompts]
            fleet.join(timeout=180)
            m = fleet.metrics()
            assert m["replica_restarts"] >= 1
            assert m["redispatches"] >= 1
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# model-parallel replica groups (ISSUE 19) — supervisor unit matrix.
# GroupFakeHandle implements the full group-handle contract (members_live /
# dead_member / atomic kill) so the watchdog, budget and metrics logic run
# without subprocesses; the real multi-process lifecycle is slow-tier below.
# ---------------------------------------------------------------------------

class GroupFakeHandle:
    class _Proc:
        def poll(self):
            return None

    def __init__(self, hid, incarnation=0, group_size=2):
        self.id = int(hid)
        self.incarnation = int(incarnation)
        self.group_size = int(group_size)
        self.ready = True
        self.ready_info = {"e": "ready", "replica": hid}
        self.retired = False
        self.spawn_time = time.time()
        self.killed = False
        self.dead = None  # (rank, rc) set by tests
        self.proc = self._Proc()
        self.role = "both"

    @property
    def alive(self):
        return not self.retired and not self.killed and self.dead is None

    @property
    def members_live(self):
        if self.killed:
            return 0
        return self.group_size - (1 if self.dead is not None else 0)

    def dead_member(self):
        return self.dead

    def kill(self, grace_s=0.0):
        self.killed = True

    def final_events(self, timeout=2.0):
        return []

    def send(self, obj):
        return not self.killed

    def events(self):
        return []

    def close(self):
        self.killed = True


def make_group_supervisor(monkeypatch, n=1, group_size=2, **kw):
    from paddle_tpu.inference.serving.fleet.supervisor import \
        ReplicaSupervisor

    monkeypatch.setattr(
        ReplicaSupervisor, "_spawn",
        lambda self, i, inc: GroupFakeHandle(i, inc, self.group_size))
    kw.setdefault("instance", f"grouptest#{time.monotonic_ns()}")
    return ReplicaSupervisor(n, {"artifact": "unused"},
                             group_size=group_size, **kw)


class TestGroupSupervisor:
    def test_validates_group_size_and_prefill_roles(self):
        from paddle_tpu.inference.serving.fleet.supervisor import \
            ReplicaSupervisor

        with pytest.raises(ValueError, match="group_size"):
            ReplicaSupervisor(1, {}, group_size=0)
        # disaggregated prefill slots cannot be groups: the KV handoff
        # exports pages to one host, which a process-spanning plan
        # cannot satisfy yet — typed rejection at construction
        with pytest.raises(ValueError, match="prefill"):
            ReplicaSupervisor(2, {}, group_size=2,
                              roles=["prefill", "decode"])

    def test_boot_grace_scales_with_group_size(self, monkeypatch):
        # groups boot slower (rendezvous + sharded weight commit + the
        # all-ranks warmup barrier): the grace scales with the group
        # size so phantom boot hangs never drain the restart budget
        sup = make_group_supervisor(monkeypatch, group_size=2,
                                    boot_grace_s=10.0, hang_timeout_s=5.0)
        try:
            assert sup.boot_grace_s == 20.0
            h = sup.handles[0]
            h.ready = False
            now = time.time()
            h.spawn_time = now - 15.0  # inside the SCALED grace
            assert not sup._hung(h, {}, now)
            h.spawn_time = now - 25.0  # past it: condemned
            assert sup._hung(h, {}, now)
        finally:
            sup.shutdown()
        sup1 = make_group_supervisor(monkeypatch, group_size=1,
                                     boot_grace_s=10.0, hang_timeout_s=5.0)
        try:
            assert sup1.boot_grace_s == 10.0
        finally:
            sup1.shutdown()

    def test_hang_judged_by_stalest_member_heartbeat(self, monkeypatch):
        # one wedged rank stalls every member's next collective, so the
        # group is condemned by its STALEST hb.<replica>.<rank> — a
        # fresh rank-0 beat must not vouch for a wedged rank 1
        sup = make_group_supervisor(monkeypatch, group_size=2,
                                    hang_timeout_s=5.0)
        try:
            h = sup.handles[0]
            now = time.time()
            fresh = {"0.0": {"time": now}, "0.1": {"time": now}}
            assert not sup._hung(h, fresh, now)
            stale1 = {"0.0": {"time": now}, "0.1": {"time": now - 10.0}}
            assert sup._hung(h, stale1, now)
            # a member that never beat is judged from spawn_time
            h.spawn_time = now - 10.0
            assert sup._hung(h, {"0.0": {"time": now}}, now)
        finally:
            sup.shutdown()

    def test_member_crash_fells_group_one_budget_slot(self, monkeypatch):
        sup = make_group_supervisor(monkeypatch, group_size=2,
                                    max_restarts=3)
        try:
            h = sup.handles[0]
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=sup.instance, replica=0) == 2
            h.dead = (1, -9)  # non-zero rank SIGKILLed
            now = time.time()
            deaths = sup.check(now=now)
            # the death names the failing rank and the survivors were
            # felled atomically (a half-dead tp group must never answer)
            assert deaths == [{"replica": 0, "reason": "crash", "rc": -9,
                               "rank": 1, "events": []}]
            assert h.killed
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=sup.instance, replica=0) == 0
            # the whole-group restart charges exactly ONE budget slot
            assert sup._budgets[0].used == 1
            # backoff lapse -> respawn: gauge recovers, group restart
            # counter ticks once
            deaths = sup.check(now=now + 120.0)
            assert deaths == []
            assert sup.handles[0] is not h
            assert sup.handles[0].incarnation == 1
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=sup.instance, replica=0) == 2
            assert om.REGISTRY.get("fleet_group_restarts_total").value(
                instance=sup.instance) == 1
            assert om.REGISTRY.get("fleet_replica_restarts_total").value(
                instance=sup.instance) == 1
        finally:
            sup.shutdown()
        # shutdown removes the per-replica member gauge series
        snap = om.REGISTRY.snapshot().get("fleet_group_members_live",
                                          {"series": {}})
        assert not any(sup.instance in k for k in snap["series"])

    def test_crash_loop_error_names_failing_rank(self, monkeypatch):
        sup = make_group_supervisor(monkeypatch, group_size=2,
                                    max_restarts=0)
        with pytest.raises(ReplicaCrashLoopError,
                           match="at group rank 1"):
            sup.handles[0].dead = (1, -9)
            sup.check()

    def test_group_retire_zeroes_member_gauge(self, monkeypatch):
        sup = make_group_supervisor(monkeypatch, n=2, group_size=2)
        try:
            sup.retire(1)
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=sup.instance, replica=1) == 0
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=sup.instance, replica=0) == 2
        finally:
            sup.shutdown()


class TestGroupRejoinGate:
    def test_reload_rejects_stale_plan_fingerprint(self, tmp_path):
        """Group rejoin gate: a restarted group member reloading from the
        fleet checkpoint root must refuse a checkpoint recorded under a
        DIFFERENT sharding plan (typed PlanMismatchError) — silently
        re-sharding would hand the group weights its peers don't have."""
        import paddle_tpu as paddle
        from paddle_tpu.distributed.checkpoint.manager import \
            CheckpointManager
        from paddle_tpu.distributed.plan import Plan
        from paddle_tpu.inference.serving import LLMEngine
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        mgr.save(100, model=model, plan=Plan.build({"tp": 4}, ["tp"]))
        with LLMEngine(model, num_blocks=8, block_size=8,
                       max_batch_size=2, ingest_async=False,
                       plan=Plan.build({"tp": 2}, ["tp"])) as eng:
            with pytest.raises(paddle.PlanMismatchError, match="mesh"):
                eng.reload_weights(mgr)


# ---------------------------------------------------------------------------
# real multi-process replica groups (ISSUE 19, slow tier): each slot is a
# 2-process tp=2 group over the gloo-backed jax coordination service
# ---------------------------------------------------------------------------

def _group_refs(tmp_path, lens=(5, 9, 12), max_new=8, seed=7):
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (LLMEngine, SamplingParams,
                                              save_llama_artifact)
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    artifact = str(tmp_path / "model")
    save_llama_artifact(model, artifact)
    kw = dict(num_blocks=32, block_size=8, max_batch_size=4)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model.config.vocab_size, n)
               .astype(np.int32) for n in lens]
    with LLMEngine(model, ingest_async=False, **kw) as eng:
        refs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=max_new))
    return artifact, kw, prompts, refs


TP2_PLAN = {"axes": {"tp": 2}, "strategies": ["tp"]}


@pytest.mark.slow
class TestRealGroupFleet:
    def test_group_bit_exact_stats_and_retire(self, tmp_path):
        """A tp=2 group serves bit-identically to the single-process
        engine; stats aggregate through rank 0 (the group's one mouth);
        drain-then-retire fells every member process."""
        artifact, kw, prompts, refs = _group_refs(tmp_path)
        fleet = Router(artifact=artifact, n_replicas=1, engine_kwargs=kw,
                       group_size=2, plan=TP2_PLAN,
                       log_dir=str(tmp_path / "logs"))
        try:
            assert fleet.supervisor.handles[0].ready_info[
                "group_size"] == 2
            gids = [fleet.submit(p, max_new=8) for p in prompts]
            fleet.join(timeout=300)
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
            # engine-owned stats flow through rank 0's RPC stream
            stats = fleet.replica_stats(0)
            assert stats["blocks_free"] == kw["num_blocks"] - 1
            assert stats["running"] == 0 and stats["waiting"] == 0
            assert om.REGISTRY.get("fleet_group_members_live").value(
                instance=fleet._name, replica=0) == 2
            h = fleet.supervisor.handles[0]
            fleet.drain(0, then="retire", wait=True)
            assert h.retired
            assert h.proc.poll() is not None
            assert all(m.poll() is not None for m in h.members)
        finally:
            fleet.close()

    def test_group_member_crash_fells_group_and_replays(self, tmp_path):
        """SIGKILL of a NON-ZERO rank mid-burst: the supervisor fells
        the whole group, respawns it on a fresh coordination port, and
        the redispatched requests replay bit-exactly."""
        import json as _json

        artifact, kw, prompts, refs = _group_refs(tmp_path, seed=9)
        fleet = Router(
            artifact=artifact, n_replicas=1, engine_kwargs=kw,
            group_size=2, plan=TP2_PLAN, max_restarts=2,
            log_dir=str(tmp_path / "logs"),
            env_extra={"CHAOS_SERVE_SITES": _json.dumps(
                [{"site": "serve.group_member_crash", "replica": 0,
                  "rank": 1, "after": 3}])})
        try:
            port0 = fleet.supervisor.handles[0].coord_port
            gids = [fleet.submit(p, max_new=8) for p in prompts]
            fleet.join(timeout=600)
            m = fleet.metrics()
            assert m["replica_restarts"] >= 1
            assert m["redispatches"] >= 1
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
            h = fleet.supervisor.handles[0]
            assert h.incarnation >= 1
            assert h.coord_port != port0  # fresh rendezvous port
            assert om.REGISTRY.get("fleet_group_restarts_total").value(
                instance=fleet._name) >= 1
        finally:
            fleet.close()

    def test_group_member_hang_watchdog_escalation(self, tmp_path):
        """A wedged rank 1 stalls the group's collectives WITHOUT any
        process exiting: only the hang watchdog (stale member
        heartbeats) can fell the group; the respawn then replays
        bit-exactly."""
        import json as _json

        artifact, kw, prompts, refs = _group_refs(tmp_path, seed=11)
        fleet = Router(
            artifact=artifact, n_replicas=1, engine_kwargs=kw,
            group_size=2, plan=TP2_PLAN, max_restarts=2,
            hang_timeout_s=4.0, log_dir=str(tmp_path / "logs"),
            env_extra={"CHAOS_SERVE_SITES": _json.dumps(
                [{"site": "serve.group_member_hang", "replica": 0,
                  "rank": 1, "after": 3}])})
        try:
            gids = [fleet.submit(p, max_new=8) for p in prompts]
            fleet.join(timeout=600)
            m = fleet.metrics()
            assert m["replica_restarts"] >= 1
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
        finally:
            fleet.close()
