"""Replica process supervision (ISSUE 12 tentpole, part a).

``ReplicaSupervisor`` is the serving-side twin of the training
launcher's ``CollectiveController`` (PR 4): it spawns N replica worker
processes (``fleet.replica``, each owning one ``LLMEngine`` over a
shared model artifact) and keeps them alive:

* **Crash**: a replica exiting for any reason (SIGKILL'd by the OOM
  killer, a real crash, a chaos drill) is detected by ``check()`` and
  respawned under a per-replica leaky-bucket
  :class:`~paddle_tpu.distributed.launch.controllers.collective.RestartBudget`
  — the SAME budget/backoff machinery the training launcher uses, with
  a typed :class:`~..errors.ReplicaCrashLoopError` once a slot's budget
  is exhausted (a poisoned replica must not flap forever).
* **Hang**: replicas heartbeat through ``distributed.launch.heartbeat``
  (atomic ``hb.<replica>`` files, written at every engine ``step()``
  boundary and on idle ticks); a heartbeat older than
  ``hang_timeout_s`` triggers the SIGTERM→SIGKILL escalation and the
  replica is restarted like a crash — a worker wedged in a compile or a
  device call cannot silently hold its share of the fleet.
* **Rejoin**: a restarted replica reloads weights from the fleet's
  checkpoint root (``reload_weights(latest_healthy_step())`` inside the
  worker) before reporting ready, so a crash during a rolling weight
  update cannot resurrect stale weights.

The supervisor only manages processes; request-level recovery
(redispatching the dead replica's in-flight requests) is the Router's
job — ``check()`` hands it the death events WITH the dying process's
final token events (drained to EOF first), so tokens emitted before the
crash are never lost and never double-counted.

ISSUE 17 adds **fleet autoscaling**: :meth:`ReplicaSupervisor.autoscale`
is a pure decision tick driven by the router's ``fleet_queue_depth`` and
occupancy gauges — sustained pressure above the high watermark grows the
fleet by one slot (:meth:`add_replica`), calm below the low watermark
nominates the highest live slot for the caller to drain-then-retire
(riding the PR-12 zero-drop drain; the supervisor never kills a slot
that may hold in-flight work). Hysteresis (distinct watermarks + a
cooldown between events) and a leaky-bucket scale-event budget (the
:class:`RestartBudget` machinery again) keep flapping load from
crash-looping the fleet through churn.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import warnings

from ....distributed.launch import heartbeat as _hb
from ....distributed.launch.controllers.collective import RestartBudget
from ....observability import metrics as _obs_metrics
from ..errors import ReplicaCrashLoopError

__all__ = ["ReplicaHandle", "ReplicaSupervisor"]

# fleet liveness (ISSUE 12): how many replicas look alive RIGHT NOW —
# process running and (when the hang watchdog is armed) heartbeat fresh.
# Transitions are appended to <log_dir>/fleet_liveness.log so the chaos
# drill can assert the gauge dipped during a kill/hang and recovered.
_G_LIVE = _obs_metrics.gauge(
    "fleet_replicas_live",
    "replicas currently alive (process running + heartbeat fresh when "
    "the hang watchdog is armed)")
_M_RESTARTS = _obs_metrics.counter(
    "fleet_replica_restarts_total",
    "replica respawns performed by the supervisor (crash or hang)")
_M_SCALE_UP = _obs_metrics.counter(
    "fleet_scale_up_total",
    "replicas added by autoscale (queue pressure above the high "
    "watermark past the cooldown)")
_M_SCALE_DOWN = _obs_metrics.counter(
    "fleet_scale_down_total",
    "replicas nominated for drain-then-retire by autoscale (fleet calm "
    "below the low watermark past the cooldown)")
# model-parallel replica groups (ISSUE 19): per-replica member liveness
# and whole-group restarts. A group is atomic — members_live < group_size
# is a transient state the supervisor resolves by felling the whole
# group, never a serving state.
_G_GROUP_MEMBERS = _obs_metrics.gauge(
    "fleet_group_members_live",
    "processes of this replica group currently running (a value below "
    "the group size means the group is being felled or respawned — a "
    "partial group never serves)")
_M_GROUP_RESTARTS = _obs_metrics.counter(
    "fleet_group_restarts_total",
    "whole-group respawns performed by the supervisor (any member "
    "crash/hang fells and restarts the entire group, charging ONE "
    "restart-budget slot)")

# repo root (five levels up: fleet/serving/inference/paddle_tpu/<repo>)
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

ENV_ID = "PADDLE_REPLICA_ID"
ENV_CONFIG = "PADDLE_REPLICA_CONFIG"
ENV_INCARNATION = "PADDLE_REPLICA_INCARNATION"
# model-parallel replica groups (ISSUE 19)
ENV_GROUP_SIZE = "PADDLE_REPLICA_GROUP_SIZE"
ENV_GROUP_RANK = "PADDLE_REPLICA_GROUP_RANK"
ENV_COORD_PORT = "PADDLE_REPLICA_COORD_PORT"


def _free_port():
    """A currently free TCP port for an incarnation's private
    coordination service (racy-but-fine: the group binds it within
    milliseconds, and a collision just fails the boot — which the
    watchdog turns into an ordinary group restart on a NEW port)."""
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


class ReplicaHandle:
    """One replica worker process + its line-JSON RPC plumbing.

    Commands go down the child's stdin (one JSON object per line);
    events come back on stdout, pumped by a daemon reader thread into an
    internal queue that :meth:`events` drains. stderr goes to a per-
    replica log file (jax chatter must never corrupt the RPC stream).

    ``group_size > 1`` (ISSUE 19) makes the handle a multi-process
    GROUP: rank 0 keeps the RPC pipes (``proc``/``pid`` stay rank 0, so
    the router's one-handle-one-target view is unchanged) and ranks 1+
    are spawned headless (stdin ``/dev/null``, stdout+stderr to their
    own log). The group is ATOMIC: :attr:`alive` demands every member
    running, and :meth:`kill` fells them all — a half-dead tp group must
    never answer.
    """

    def __init__(self, replica_id, config, *, env=None, log_path=None,
                 incarnation=0, group_size=1, coord_port=None):
        self.id = int(replica_id)
        self.incarnation = int(incarnation)
        self.group_size = int(group_size)
        self.coord_port = coord_port
        self.spawn_time = time.time()
        self.ready = False
        self.ready_info = None
        self.retired = False
        self._lock = threading.Lock()
        self._events: list = []
        self._log_file = open(log_path, "ab") if log_path else None
        self._member_logs = []
        child_env = dict(env if env is not None else os.environ)
        child_env[ENV_ID] = str(self.id)
        child_env[ENV_CONFIG] = json.dumps(config)
        child_env[ENV_INCARNATION] = str(self.incarnation)
        child_env["PYTHONPATH"] = (_REPO + os.pathsep
                                   + child_env.get("PYTHONPATH", ""))
        if self.group_size > 1:
            child_env[ENV_GROUP_SIZE] = str(self.group_size)
            child_env[ENV_COORD_PORT] = str(coord_port)
            child_env[ENV_GROUP_RANK] = "0"
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m",
             "paddle_tpu.inference.serving.fleet.replica"],
            env=child_env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=(self._log_file or subprocess.DEVNULL), text=True,
            bufsize=1)
        # ranks 1+: same engine in SPMD lockstep, no RPC stream — their
        # stdout would corrupt nothing, but it belongs in a log
        self.members = []
        for rank in range(1, self.group_size):
            member_env = dict(child_env)
            member_env[ENV_GROUP_RANK] = str(rank)
            mlog = (open(f"{log_path}.r{rank}", "ab") if log_path
                    else None)
            self._member_logs.append(mlog)
            self.members.append(subprocess.Popen(
                [sys.executable, "-u", "-m",
                 "paddle_tpu.inference.serving.fleet.replica"],
                env=member_env, stdin=subprocess.DEVNULL,
                stdout=(mlog or subprocess.DEVNULL),
                stderr=(mlog or subprocess.DEVNULL)))
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name=f"replica{self.id}-reader")
        self._reader.start()

    def _read(self):
        try:
            for line in self.proc.stdout:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue  # stray non-RPC print; never kill the reader
                with self._lock:
                    self._events.append(ev)
        except (OSError, ValueError):
            pass

    @property
    def alive(self):
        """Every member running (group-atomic: a group missing ANY
        member must not look placeable)."""
        return (not self.retired and self.proc.poll() is None
                and all(m.poll() is None for m in self.members))

    @property
    def pid(self):
        return self.proc.pid

    @property
    def members_live(self):
        """Running member processes (rank 0 included) — the
        ``fleet_group_members_live`` gauge."""
        n = 1 if self.proc.poll() is None else 0
        return n + sum(1 for m in self.members if m.poll() is None)

    def dead_member(self):
        """``(rank, rc)`` of the first exited member, or ``None`` when
        all are running — the supervisor's group-crash probe, naming the
        failing rank for the crash-loop error."""
        if self.proc.poll() is not None:
            return 0, self.proc.poll()
        for rank, m in enumerate(self.members, start=1):
            if m.poll() is not None:
                return rank, m.poll()
        return None

    def send(self, obj):
        """Write one command line; False when the pipe is gone (the
        caller treats it as a dead replica and redispatches)."""
        try:
            with self._lock:
                self.proc.stdin.write(json.dumps(obj) + "\n")
                self.proc.stdin.flush()
            return True
        except (OSError, ValueError, AttributeError):
            return False

    def events(self):
        """Drain queued events (ready events also flip :attr:`ready`)."""
        with self._lock:
            out, self._events = self._events, []
        for ev in out:
            if ev.get("e") == "ready":
                self.ready = True
                self.ready_info = ev
        return out

    def push_back(self, evs):
        """Requeue events at the front (``wait_ready`` peeks without
        consuming the router's view of the stream)."""
        with self._lock:
            self._events = list(evs) + self._events

    def final_events(self, timeout=2.0):
        """Join the reader (EOF after death) and drain what's left —
        tokens the replica emitted before dying must reach the router."""
        self._reader.join(timeout=timeout)
        return self.events()

    def kill(self, grace_s=5.0):
        """SIGTERM → wait ``grace_s`` → SIGKILL (the launcher's
        escalation) — applied to EVERY group member: survivors of a
        partial failure are felled, never left to answer. SIGTERM goes
        to all members first so the grace window is shared, not
        per-process."""
        procs = [self.proc] + list(self.members)
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.time() + float(grace_s)
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(deadline - time.time(), 0.0))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
        for f in [self._log_file] + self._member_logs:
            if f is not None:
                try:
                    f.close()
                except OSError:
                    pass
        self._log_file = None
        self._member_logs = []

    def close(self):
        """Polite shutdown: ask, wait briefly, then escalate."""
        self.send({"op": "shutdown"})
        try:
            self.proc.wait(timeout=3.0)
        except subprocess.TimeoutExpired:
            pass
        self.kill(grace_s=1.0)


class ReplicaSupervisor:
    """Spawn + watch ``n_replicas`` replica workers (see module doc)."""

    def __init__(self, n_replicas, config, *, hang_timeout_s=0.0,
                 max_restarts=3, term_grace_s=5.0, boot_grace_s=120.0,
                 log_dir=None, env_extra=None, instance="fleet",
                 roles=None, group_size=1):
        if int(n_replicas) < 1:
            raise ValueError("n_replicas must be >= 1")
        # model-parallel replica groups (ISSUE 19): every slot is a
        # group of `group_size` processes serving ONE plan-sharded
        # engine in SPMD lockstep (group_size=1 is the exact PR-12
        # single-process replica, byte-for-byte)
        self.group_size = int(group_size)
        if self.group_size < 1:
            raise ValueError("group_size must be >= 1")
        if self.group_size > 1 and roles is not None \
                and any(r == "prefill" for r in roles):
            raise ValueError(
                "prefill-role slots cannot be multi-process groups: the "
                "disaggregated handoff exports KV pages to one host, "
                "which a process-spanning plan does not support yet")
        # role-disaggregated serving (ISSUE 15): each slot is "prefill",
        # "decode" or "both" (the colocated default). The role is part of
        # the SLOT, not the incarnation — a restarted replica respawns
        # with the same role, so a crash can never silently turn a
        # prefill worker into a decode worker.
        if roles is not None:
            roles = [str(r) for r in roles]
            if len(roles) != int(n_replicas):
                raise ValueError(
                    f"roles has {len(roles)} entries for {n_replicas} "
                    "replicas")
            bad = [r for r in roles if r not in ("prefill", "decode",
                                                 "both")]
            if bad:
                raise ValueError(f"unknown replica roles {bad}; expected "
                                 "'prefill', 'decode' or 'both'")
        self._roles = roles
        self.instance = instance
        self.hang_timeout_s = float(hang_timeout_s or 0.0)
        self.term_grace_s = float(term_grace_s)
        # a replica writes its first heartbeat only after the framework
        # import + engine build, so a booting (not-yet-ready) replica is
        # judged against this LONGER grace — otherwise a tight watchdog
        # condemns every restart before it can possibly beat, and the
        # budget drains on phantom hangs (the launch bootstrap solves
        # this with a pre-jax heartbeat; here the import IS the boot).
        # Groups boot slower still — collective jax.distributed
        # rendezvous + plan-sharded weight commit + an all-ranks warmup
        # barrier — so the grace SCALES with the group size (the PR-12
        # boot_grace_s lesson, re-proven for groups: a phantom boot hang
        # must never drain the restart budget)
        self.boot_grace_s = (max(float(boot_grace_s), self.hang_timeout_s)
                             * max(1, self.group_size))
        self.log_dir = log_dir
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            self._hb_dir = os.path.join(log_dir, "heartbeats")
        else:
            self._hb_dir = tempfile.mkdtemp(prefix="paddle_fleet_hb.")
        os.makedirs(self._hb_dir, exist_ok=True)
        self._config = dict(config)
        self._config["hb_dir"] = self._hb_dir
        self._env = dict(os.environ)
        # replicas default to the CPU backend: N extra processes fighting
        # over one accelerator is never what a test/drill wants; a real
        # deployment overrides via env_extra
        self._env.setdefault("JAX_PLATFORMS", "cpu")
        self._env.update(env_extra or {})
        # sleep=no-op: backoff() only COMPUTES the delay — the supervisor
        # schedules the respawn at now+delay instead of sleeping inside
        # the router's single-threaded pump (a synchronous backoff sleep
        # would freeze token events, placements and the redispatch the
        # death just triggered, for every healthy replica too)
        self._max_restarts = int(max_restarts)
        self._budgets = [RestartBudget(max_restarts, sleep=lambda s: None)
                         for _ in range(int(n_replicas))]
        self._pending_respawn: dict[int, float] = {}
        self.handles = [self._spawn(i, 0) for i in range(int(n_replicas))]
        self._last_live = None
        # autoscale state (ISSUE 17): budget created lazily at the first
        # autoscale() tick (its shape is a caller decision)
        self._scale_budget = None
        self._last_scale_t = None
        self._scale_warned = False
        self._note_liveness()

    # -- lifecycle -------------------------------------------------------
    def role(self, i):
        """The slot's serving role ("both" when undeclared)."""
        return self._roles[i] if self._roles else "both"

    def _spawn(self, i, incarnation):
        log_path = (os.path.join(self.log_dir, f"replica.{i}.log")
                    if self.log_dir else None)
        config = self._config
        if self._roles is not None:
            config = dict(config, role=self._roles[i])
        # fresh coordination port per incarnation: a respawned group's
        # rendezvous must never reach a predecessor's half-dead service
        port = _free_port() if self.group_size > 1 else None
        h = ReplicaHandle(i, config, env=self._env,
                          log_path=log_path, incarnation=incarnation,
                          group_size=self.group_size, coord_port=port)
        h.role = self.role(i)
        return h

    def wait_ready(self, timeout=180.0):
        """Block until every live replica reported ``ready`` (engine
        built, weights loaded/reloaded). Peeked events are pushed back
        for the router's pump."""
        deadline = time.time() + float(timeout)
        for h in self.handles:
            while not h.ready and not h.retired:
                evs = h.events()
                if evs:
                    h.push_back(evs)
                if h.ready:
                    break
                dead = h.dead_member()
                if dead is not None:
                    rank, rc = dead
                    raise RuntimeError(
                        f"replica {h.id} (group rank {rank}) died "
                        f"during startup (rc={rc}); see its log"
                        + (f" in {self.log_dir}" if self.log_dir else ""))
                if time.time() > deadline:
                    raise TimeoutError(
                        f"replica {h.id} not ready within {timeout}s")
                time.sleep(0.05)

    def retire(self, i):
        """Permanently stop replica ``i`` (the drain-then-retire path) —
        no restart, excluded from liveness."""
        h = self.handles[i]
        h.retired = True
        h.close()
        self._note_liveness()

    def shutdown(self):
        for h in self.handles:
            if not h.retired:
                h.close()
        _G_LIVE.remove(instance=self.instance)
        _M_RESTARTS.remove(instance=self.instance)
        _M_SCALE_UP.remove(instance=self.instance)
        _M_SCALE_DOWN.remove(instance=self.instance)
        if self.group_size > 1:
            _M_GROUP_RESTARTS.remove(instance=self.instance)
            for h in self.handles:
                _G_GROUP_MEMBERS.remove(instance=self.instance,
                                        replica=h.id)

    # -- fleet autoscaling (ISSUE 17) -----------------------------------
    @property
    def n_active(self):
        """Slots not retired (live, booting, or pending respawn) — the
        fleet size autoscale reasons about."""
        return sum(1 for h in self.handles if not h.retired)

    def add_replica(self, role="both"):
        """Grow the fleet by one slot (the autoscale-up action). The new
        slot appends at the end — slot id == handles index stays true for
        every existing slot — with a fresh restart budget and incarnation
        0. Returns the new slot id."""
        i = len(self.handles)
        if self._roles is not None:
            role = str(role)
            if role not in ("prefill", "decode", "both"):
                raise ValueError(f"unknown replica role {role!r}")
            self._roles.append(role)
        self._budgets.append(
            RestartBudget(self._max_restarts, sleep=lambda s: None))
        self.handles.append(self._spawn(i, 0))
        _M_SCALE_UP.inc(instance=self.instance)
        self._note_liveness()
        return i

    def autoscale(self, min_replicas, max_replicas, *, queue_depth,
                  occupancy, high_water=0.75, low_water=0.25,
                  cooldown_s=5.0, max_events=8, window_s=60.0, now=None):
        """One autoscale decision tick, driven by the router's gauges:
        ``queue_depth`` (requests waiting at the router) and
        ``occupancy`` (mean decode-slot occupancy across live replicas,
        0..1).

        * **Up** — work is queued AND the fleet is busy (``occupancy >=
          high_water``) with room to grow: spawn one replica
          (:meth:`add_replica`) and return ``("up", new_id)``.
        * **Down** — nothing queued AND the fleet is idle (``occupancy
          <= low_water``) above the floor: return ``("down",
          victim_id)`` nominating the highest live slot; the CALLER
          drains it (zero-drop) and calls :meth:`retire` — the
          supervisor never kills a slot that may hold in-flight work.
        * Otherwise (or inside the hysteresis band / cooldown / an
          exhausted scale-event budget) return ``None``.

        Hysteresis is the gap between the watermarks plus ``cooldown_s``
        between events; the leaky-bucket scale-event budget
        (``max_events`` per rolling ``window_s``, fixed at the first
        tick) stops flapping load from churning replicas forever — past
        it, autoscale goes quiet (one warning) instead of crash-looping
        the fleet."""
        min_replicas, max_replicas = int(min_replicas), int(max_replicas)
        if not 1 <= min_replicas <= max_replicas:
            raise ValueError(
                f"need 1 <= min ({min_replicas}) <= max ({max_replicas})")
        if not low_water < high_water:
            raise ValueError(
                f"need low_water ({low_water}) < high_water "
                f"({high_water}) — the gap IS the hysteresis band")
        now = time.time() if now is None else now
        n = self.n_active
        want_up = (queue_depth > 0 and occupancy >= high_water
                   and n < max_replicas)
        want_down = (queue_depth == 0 and occupancy <= low_water
                     and n > min_replicas)
        if not (want_up or want_down):
            return None
        if (self._last_scale_t is not None
                and now - self._last_scale_t < cooldown_s):
            return None
        if self._scale_budget is None:
            self._scale_budget = RestartBudget(
                int(max_events), window_s=float(window_s),
                sleep=lambda s: None)
        if not self._scale_budget.try_acquire():
            if not self._scale_warned:
                self._scale_warned = True
                warnings.warn(
                    f"{self.instance}: scale-event budget exhausted "
                    f"({self._scale_budget.max_restarts} per "
                    f"{self._scale_budget.window_s:.0f}s); autoscale "
                    "pausing — flapping load, widen the watermarks",
                    RuntimeWarning)
            return None
        self._last_scale_t = now
        if want_up:
            return ("up", self.add_replica())
        victim = max(h.id for h in self.handles if not h.retired)
        _M_SCALE_DOWN.inc(instance=self.instance)
        return ("down", victim)

    # -- the watchdog tick ----------------------------------------------
    def _hung(self, h, beats, now):
        if self.hang_timeout_s <= 0 or not h.alive:
            return False
        if not h.ready:
            # still booting: only the boot grace can condemn it
            return (now - h.spawn_time) > self.boot_grace_s
        if getattr(h, "group_size", 1) > 1:
            # groups run in SPMD lockstep, so ONE wedged rank stalls
            # every member's next collective: judge the group by its
            # STALEST member's hb.<replica>.<rank> heartbeat
            ts = []
            for r in range(h.group_size):
                t = beats.get(f"{h.id}.{r}", {}).get("time")
                ts.append(h.spawn_time if t is None else float(t))
            return (now - min(ts)) > self.hang_timeout_s
        t = beats.get(str(h.id), {}).get("time")
        if t is None:
            t = h.spawn_time  # not-yet-written grace, like launch.stale
        return (now - float(t)) > self.hang_timeout_s

    def check(self, now=None):
        """One supervision tick. Detects dead and hung replicas, kills
        the hung ones, respawns both under the per-replica restart
        budget, and returns the death events for the router::

            [{"replica": i, "reason": "crash"|"hang", "rc": rc,
              "events": [<final events drained after EOF>]}]

        Raises :class:`ReplicaCrashLoopError` when a slot's budget is
        exhausted. Also refreshes the ``fleet_replicas_live`` gauge
        (transition log: ``<log_dir>/fleet_liveness.log``)."""
        now = time.time() if now is None else now
        beats = _hb.read_all(self._hb_dir)
        deaths = []
        for i, h in enumerate(self.handles):
            if h.retired:
                continue
            if i in self._pending_respawn:
                # death already reported; respawn when the backoff lapses
                if now >= self._pending_respawn[i]:
                    del self._pending_respawn[i]
                    # stale heartbeats must not re-condemn the new life
                    # (hb.<i> and every group member's hb.<i>.<rank>)
                    self._clear_heartbeats(i)
                    self.handles[i] = self._spawn(i, h.incarnation + 1)
                    _M_RESTARTS.inc(instance=self.instance)
                    if self.group_size > 1:
                        _M_GROUP_RESTARTS.inc(instance=self.instance)
                continue
            reason = None
            rank = None
            dead = (h.dead_member() if hasattr(h, "dead_member")
                    else ((0, h.proc.poll())
                          if h.proc.poll() is not None else None))
            if dead is not None:
                # ANY member exiting fells the WHOLE group atomically: a
                # half-dead tp group must never answer — survivors are
                # SIGTERM→SIGKILL'd before the death is even reported
                reason = "crash"
                rank, _ = dead
                h.kill(grace_s=self.term_grace_s)
            elif self._hung(h, beats, now):
                reason = "hang"
                h.kill(grace_s=self.term_grace_s)
            if reason is None:
                continue
            rc = dead[1] if dead is not None else h.proc.poll()
            leftovers = h.final_events()
            # the dip must be visible BEFORE the respawn restores it
            self._note_liveness()
            budget = self._budgets[i]
            if not budget.try_acquire():
                self.shutdown()
                at_rank = f" at group rank {rank}" if rank else ""
                raise ReplicaCrashLoopError(
                    f"replica {i} crash loop ({reason}{at_rank}, "
                    f"rc={rc}): restart budget exhausted "
                    f"({budget.max_restarts} per "
                    f"{budget.window_s:.0f}s window, "
                    f"{budget.total_restarts} performed)",
                    replica=i, exit_code=rc if rc is not None else 1,
                    restarts=budget.total_restarts)
            # schedule (never sleep in the pump): the death event returns
            # NOW so the router redispatches immediately; the slot stays
            # un-placeable (dead handle) until the delayed respawn
            self._pending_respawn[i] = now + budget.backoff()
            deaths.append({"replica": i, "reason": reason, "rc": rc,
                           "rank": rank, "events": leftovers})
        self._note_liveness(beats=beats, now=now)
        return deaths

    def quarantine(self, i, now=None):
        """Integrity quarantine (ISSUE 20): kill replica ``i`` NOW —
        group-atomic, exactly like a watchdog kill — charge ONE restart-
        budget slot and schedule the respawn through the normal
        ``_pending_respawn`` path (so a supervision tick racing this
        call can never double-restart the slot: ``check`` skips slots
        already pending). Returns the death dict (``reason:
        "quarantine"``) for the router to replay/redispatch from, or
        ``None`` when the slot is retired / already dying. Raises
        :class:`ReplicaCrashLoopError` when the budget is exhausted —
        a replica that keeps corrupting after restarts is poisoned
        hardware, not bad luck."""
        now = time.time() if now is None else now
        h = self.handles[i]
        if h.retired or i in self._pending_respawn:
            return None
        # no SIGTERM grace: a corrupt replica must stop emitting tokens
        # immediately, not drain them
        h.kill(grace_s=0.0)
        rc = h.proc.poll()
        leftovers = h.final_events()
        self._note_liveness()  # the dip precedes the respawn
        budget = self._budgets[i]
        if not budget.try_acquire():
            self.shutdown()
            raise ReplicaCrashLoopError(
                f"replica {i} quarantine loop: restart budget exhausted "
                f"({budget.max_restarts} per {budget.window_s:.0f}s "
                f"window, {budget.total_restarts} performed) — the slot "
                "keeps serving corrupt output; suspect the hardware",
                replica=i, exit_code=rc if rc is not None else 1,
                restarts=budget.total_restarts)
        self._pending_respawn[i] = now + budget.backoff()
        return {"replica": i, "reason": "quarantine", "rc": rc,
                "rank": None, "events": leftovers}

    def _clear_heartbeats(self, i):
        """Remove slot ``i``'s heartbeat files — the bare ``hb.<i>`` and
        every group member's ``hb.<i>.<rank>``."""
        for r in [None] + list(range(self.group_size)):
            fn = f"hb.{i}" if r is None else f"hb.{i}.{r}"
            try:
                os.remove(os.path.join(self._hb_dir, fn))
            except OSError:
                pass

    def _note_liveness(self, beats=None, now=None):
        now = time.time() if now is None else now
        if beats is None:
            beats = _hb.read_all(self._hb_dir)
        n = sum(1 for h in self.handles
                if h.alive and not self._hung(h, beats, now))
        _G_LIVE.set(n, instance=self.instance)
        if self.group_size > 1:
            for h in self.handles:
                _G_GROUP_MEMBERS.set(
                    0 if h.retired else h.members_live,
                    instance=self.instance, replica=h.id)
        if n != self._last_live:
            self._last_live = n
            if self.log_dir:
                try:
                    with open(os.path.join(self.log_dir,
                                           "fleet_liveness.log"), "a") as f:
                        f.write(f"{now:.3f} {n}\n")
                except OSError:
                    pass
        return n
