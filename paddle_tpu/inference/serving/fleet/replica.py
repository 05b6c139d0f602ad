"""Replica worker process (ISSUE 12): one ``LLMEngine`` behind a
line-JSON RPC loop, runnable as
``python -m paddle_tpu.inference.serving.fleet.replica``.

Config arrives in ``PADDLE_REPLICA_CONFIG`` (JSON: ``artifact`` path
from :func:`~..engine.save_llama_artifact`, ``engine`` kwargs,
``hb_dir`` heartbeat directory, optional ``ckpt_root``, optional
``role`` — ``"both"``/``"prefill"``/``"decode"``, ISSUE 15). Protocol
(stdin commands → stdout events, one JSON object per line):

  {"op":"submit","gid":g,"gen":k,"prompt":[...],"max_new":n,
   "eos":t|null,"deadline":s|null}      -> tok events as tokens emerge
  {"op":"prefill","gid":g,"gen":k,"hid":h,...}  -> kvpage* + kvdone
  {"op":"kvpage","gid":g,"seq":i,"total":T,"crc":c,"data":b64}
  {"op":"submit_pages","gid":g,"gen":k,"prompt":[...],"frames":T,
   "crc":c,...}                         -> import pages, then tok events
  {"op":"cancel","gid":g}               -> blocks freed, slot recycled
  {"op":"reload","root":path}           -> {"e":"reloaded","step":s}
  {"op":"stats"}                        -> {"e":"stats",...}
  {"op":"shutdown"}                     -> drain in-flight, {"e":"bye"}

Events: ``ready`` (engine built, weights loaded — with the checkpoint
step it rejoined from, when a ``ckpt_root`` was given, and the slot's
``role``), ``tok`` (``{"gid","gen","toks":[...],"fin","reason"}``;
``gen`` echoes the dispatch generation so the router can drop emissions
from a superseded assignment), ``load`` (kv-utilization /
decode-occupancy after each step — the router's least-loaded signal),
``stats``, ``reloaded``, ``bye``.

Disaggregated handoff (ISSUE 15): a ``prefill``-role worker runs its
engine in ``prefill_only`` mode. On ``{"op":"prefill"}`` it admits the
request, and the moment the engine samples the request's FIRST token
(prefill complete) it exports the KV pages, streams them up as
CRC-framed ``kvpage`` events (``crc`` = zlib.crc32 of the raw chunk;
``hid`` echoes the dispatch's handoff id so the router can drop a
zombie's stale frames) followed by a ``kvdone`` carrying the first
token and the whole-payload CRC, then frees the request's blocks. A
``decode``-capable worker buffers ``kvpage`` command frames, verifies
each CRC, and on ``submit_pages`` imports the payload via
``LLMEngine.add_request_with_pages`` — a corrupt or incomplete buffer
is rejected with a typed ``err`` event (kind ``KVTransferError``) so
the router re-drives the prefill instead of decoding on garbage.
stdout carries ONLY protocol lines; everything chatty goes to stderr
(the supervisor routes it to a per-replica log file).

Heartbeats (``distributed.launch.heartbeat.write`` — the PR-4 files)
are written at every loop tick, engine-stepping or idle; the two chaos
sites fire at the loop head:

* ``serve.replica_crash`` — SIGKILL self (the OOM-killer/node-loss
  shape; nothing is flushed, the supervisor must recover everything);
* ``serve.replica_hang``  — wedge forever without heartbeating (the
  stuck-collective shape; only the supervisor's watchdog can end it);
* ``serve.prefill_crash`` — fired between kvpage frame emissions:
  SIGKILL self MID-TRANSFER, the partial-pages recovery shape;
* ``serve.kv_transfer_corrupt`` — fired per kvpage frame: the frame's
  payload is corrupted after its CRC was computed, so the receiver's
  CRC check must catch it.
* ``serve.bit_flip`` — silent data corruption (ISSUE 20): flips bits in
  a weight buffer, a host-tier KV entry, or a KV pool page
  (``CHAOS_SERVE_BIT_FLIP_TARGET`` = ``weights`` | ``host_entry`` |
  ``kv_page``). Nothing crashes and nothing raises — the integrity
  sentinel (page CRCs / sampled output audit / weight re-audit) must
  catch it.

The periodic weight re-audit (ISSUE 20) is armed by
``PADDLE_SERVE_WEIGHT_AUDIT_TICKS=N``: every N loop ticks the worker
re-hashes the live weights against the fingerprint captured at load; a
mismatch emits ``{"e":"integrity","kind":"weight_audit"}`` (a suspicion
charge at the router) and hot-reloads the artifact's clean weights.

Chaos arming is env-driven so drills can poison exactly one replica:
``CHAOS_SERVE_SITE`` + ``CHAOS_SERVE_REPLICA`` + optional
``CHAOS_SERVE_AFTER_STEPS`` — armed only in incarnation 0, so the
respawned replica runs clean (the marker-file discipline of
``chaos_train.py``, enforced by the incarnation counter instead). A
drill that poisons SEVERAL replicas at once (the disagg storm) sets
``CHAOS_SERVE_SITES`` instead: a JSON list of
``{"site","replica","after"}`` specs.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import sys
import threading
import time
import zlib

from .framing import decode_frame, encode_frame, join_frames, split_frames
from .supervisor import (ENV_CONFIG, ENV_COORD_PORT, ENV_GROUP_RANK,
                         ENV_GROUP_SIZE, ENV_ID, ENV_INCARNATION)

__all__ = ["replica_worker_main"]

# non-zero group ranks run the SAME engine in SPMD lockstep but own no
# RPC stream — rank 0 is the one mouth of the group, so everyone else's
# protocol emissions are suppressed (their stdout is a log file)
_SILENT = [False]


def _emit(obj):
    if _SILENT[0]:
        return
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


class _GroupChannel:
    """Rank-0 → member command broadcast for a multi-process replica
    group, over the group's own jax coordination service KV store (the
    PR-4 transport — no second socket layer). The contract is SPMD
    lockstep: rank 0 publishes one ``fleet.tick.<seq>`` entry per busy
    loop iteration carrying exactly the commands it is about to apply;
    every member applies the same commands to an identical engine and
    then steps — so the collectives inside the compiled step line up by
    construction. Idle iterations publish nothing (no collectives run);
    members poll with a timeout so their heartbeats stay fresh while
    idle. Consumed entries are garbage-collected ``_GC_LAG`` ticks
    behind the publisher — members can never lag further than one
    in-flight collective."""

    _GC_LAG = 512

    def __init__(self):
        from jax._src import distributed as jdist

        self._client = jdist.global_state.client
        self._seq = 0

    def publish(self, cmds):
        self._client.key_value_set(f"fleet.tick.{self._seq}",
                                   json.dumps(cmds))
        old = self._seq - self._GC_LAG
        if old >= 0:
            try:
                self._client.key_value_delete(f"fleet.tick.{old}")
            except Exception:
                pass
        self._seq += 1

    def fetch(self, timeout_ms=250):
        """The next tick's commands, or ``None`` on timeout (idle)."""
        try:
            raw = self._client.blocking_key_value_get(
                f"fleet.tick.{self._seq}", int(timeout_ms))
        except Exception:
            return None
        self._seq += 1
        return json.loads(raw)


# the armed inject() context managers must outlive _arm_chaos: a GC'd
# contextmanager generator runs its finally block, silently DISARMING
# the site — module-global keeps them alive for the process lifetime
_CHAOS_CMS: list = []


def _chaos_specs(replica_id, group_rank=0):
    """Armed (site, after, max_fires) specs for THIS process. Specs may
    carry a ``"rank"`` (default 0) so a group drill can poison exactly
    one member — e.g. ``serve.group_member_crash`` on rank 1 while rank
    0 keeps answering the router until the supervisor fells the group."""
    multi = os.environ.get("CHAOS_SERVE_SITES")
    if multi:
        try:
            specs = json.loads(multi)
        except ValueError:
            return []
        return [(s["site"], int(s.get("after", 1) or 1),
                 s.get("max_fires")) for s in specs
                if str(s.get("replica")) == str(replica_id)
                and int(s.get("rank", 0) or 0) == int(group_rank)]
    site = os.environ.get("CHAOS_SERVE_SITE")
    if site and os.environ.get("CHAOS_SERVE_REPLICA") == str(replica_id) \
            and int(os.environ.get("CHAOS_SERVE_RANK", "0")
                    or 0) == int(group_rank):
        return [(site,
                 int(os.environ.get("CHAOS_SERVE_AFTER_STEPS", "1") or 1),
                 None)]
    return []


def _arm_chaos(replica_id, group_rank=0):
    if int(os.environ.get(ENV_INCARNATION, "0") or 0) != 0:
        return  # restarted incarnations run clean
    from ....utils import fault_injection as fi

    for site, after, max_fires in _chaos_specs(replica_id, group_rank):
        # armed for the process lifetime (the fault ends or taints only
        # this incarnation)
        cm = fi.inject(site, every_n=after, max_fires=max_fires)
        cm.__enter__()
        _CHAOS_CMS.append(cm)


def replica_worker_main():
    replica_id = int(os.environ[ENV_ID])
    group_size = int(os.environ.get(ENV_GROUP_SIZE, "1") or 1)
    group_rank = int(os.environ.get(ENV_GROUP_RANK, "0") or 0)
    _SILENT[0] = group_rank != 0
    cfg = json.loads(os.environ[ENV_CONFIG])
    _arm_chaos(replica_id, group_rank)

    if group_size > 1:
        # multi-process replica group (ISSUE 19): rendezvous on the
        # incarnation's PRIVATE coordination service (fresh port per
        # incarnation — a respawned group must never rendezvous with a
        # half-dead predecessor) before any backend work. gloo backs the
        # CPU cross-process collectives; real TPU pods override the
        # platform via env and ride the default backend.
        if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
            # CPU simulation: each member owns an EQUAL share of the
            # plan's devices, so the group's global mesh is exactly the
            # plan — regardless of any device count the parent baked
            # into XLA_FLAGS (the test harness forces 8 virtual devices
            # per process, which would hand a 2-process tp=2 group 16
            # global devices and a mesh living entirely on rank 0).
            # XLA_FLAGS is still honored here: backends init lazily and
            # no array op has run yet.
            import re

            spec = cfg.get("plan") or {}
            total = 1
            for v in (spec.get("axes") or {}).values():
                total *= int(v)
            per = max(total // group_size, 1)
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                           "", os.environ.get("XLA_FLAGS", ""))
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={per}"
            ).strip()
        import jax

        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            f"127.0.0.1:{os.environ[ENV_COORD_PORT]}",
            num_processes=group_size, process_id=group_rank)

    import numpy as np

    from ....distributed.launch import heartbeat as hb
    from ....jit.cache import place_compile_cache
    from ....utils import fault_injection as fi
    from .. import integrity as _integrity
    from ..engine import LLMEngine, load_llama_artifact
    from ..errors import RequestTimeoutError
    from ..kv_cache import pack_kv_pages, unpack_kv_pages
    from ..scheduler import SamplingParams

    place_compile_cache()
    model = load_llama_artifact(cfg["artifact"])
    role = cfg.get("role") or "both"
    engine_kw = dict(cfg.get("engine") or {})
    plan_spec = cfg.get("plan")
    if plan_spec:
        # sharding plan from its JSON spec ({"axes": {...}, "strategies":
        # [...]}): the mesh is built over jax.devices() — the group's
        # GLOBAL device set after the rendezvous above, or this process's
        # virtual devices for in-process tp (XLA_FLAGS via env_extra)
        from ....distributed.plan import Plan

        engine_kw["plan"] = Plan.build(
            dict(plan_spec["axes"]),
            list(plan_spec.get("strategies") or ()))
    if engine_kw.get("prefix_store_path"):
        # each replica persists its own prefix-store shard — a literal
        # shared path would have every worker clobbering one store file
        # at close(), so the fleet API takes a ``{replica}`` template
        engine_kw["prefix_store_path"] = str(
            engine_kw["prefix_store_path"]).replace(
                "{replica}", str(replica_id))
    eng = LLMEngine(model, ingest_async=False,
                    prefill_only=(role == "prefill"),
                    **engine_kw)
    reloaded = None
    root = cfg.get("ckpt_root")
    if root:
        # rejoin contract: a (re)started replica serves the newest
        # healthy checkpoint, never the artifact's possibly-stale weights
        from ....distributed.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(root)
        if (mgr.latest_healthy_step() is not None
                or mgr.latest_valid_step() is not None):
            reloaded = eng.reload_weights(mgr)
    hb_dir = cfg.get("hb_dir")
    # group members heartbeat under hb.<replica>.<rank> — EVERY member
    # beats, so the watchdog condemns the group when ANY member wedges
    # (single-process replicas keep the bare hb.<replica> name)
    hb_rank = (f"{replica_id}.{group_rank}" if group_size > 1
               else replica_id)
    hb.write(step=0, dir=hb_dir, rank=hb_rank)

    # Replica groups pre-compile EVERY admissible prefill bucket, and the
    # decode step, BEFORE reporting ready: a post-ready first-touch
    # compile stalls the whole group's collectives with every heartbeat
    # silent, long enough to read as a hang, and boot (covered by the
    # group-scaled boot grace, not the heartbeat) is the only place
    # one-time work belongs. Both ranks run this identical warmup, so the
    # compile-time collectives line up by construction. Single-process
    # replicas keep the lazy first-call compile.
    if group_size > 1 and role != "prefill":
        cap = min(eng.max_model_len,
                  (eng.cache.num_blocks - 1) * eng.block_size)
        lens, prev = [], 0
        for b in eng.prefill_buckets:
            ln = min(b - 1, cap - 1)
            if ln > prev:
                lens.append(ln)
            prev = b
        for k, ln in enumerate(lens):
            wid = eng.add_request(
                np.zeros(ln, dtype=np.int64),
                SamplingParams(max_new_tokens=2 if k == 0 else 1))
            while not any(o.rid == wid and o.finished
                          for o in eng.step()):
                pass
            eng.release(wid)
        eng.reset_metrics()
        eng.reset_block_high_water()
        # the warmup compiles ran long past the boot-time heartbeat:
        # refresh it BEFORE ready flips, or the watchdog reads the whole
        # warmup as staleness the moment boot grace stops protecting us
        hb.write(step=0, dir=hb_dir, rank=hb_rank)

    chan = None
    if group_size > 1:
        # ready only after ALL ranks ack warm-up (ISSUE 19 satellite):
        # the barrier proves every member built its engine, committed
        # the plan-sharded weights and warmed its executables — a group
        # where one rank is still compiling must not take traffic
        from ....distributed.checkpoint import sync_processes

        sync_processes("fleet.group.warmup")
        # ranks can skew by whole compiles at the barrier; every member
        # re-beats on release so nobody's wait reads as a wedge
        hb.write(step=0, dir=hb_dir, rank=hb_rank)
        chan = _GroupChannel()

    _emit({"e": "ready", "replica": replica_id, "role": role,
           "incarnation": int(os.environ.get(ENV_INCARNATION, "0") or 0),
           "reloaded_step": reloaded, "group_size": group_size})

    cmd_q: queue.Queue = queue.Queue()

    def _reader():
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            try:
                cmd_q.put(json.loads(line))
            except ValueError:
                continue
        cmd_q.put({"op": "shutdown"})  # EOF: the router is gone

    if group_rank == 0:
        # only rank 0 owns an RPC stream; a member's stdin is /dev/null
        # and its EOF must not shut the group down at boot
        threading.Thread(target=_reader, daemon=True).start()

    rid_of = {}    # gid -> engine rid
    meta = {}      # gid -> {"gen": k}
    handoff = {}   # gid -> {"gen","hid"}: op=prefill requests (ISSUE 15)
    page_buf = {}  # gid -> {"frames": {seq: bytes}, "bad": reason|None}
    steps = 0
    shutting = False
    # periodic weight re-audit (ISSUE 20): every N loop ticks, re-hash
    # the live weights against the load-time fingerprint. Single-process
    # replicas only — a group rank's params are plan-sharded device
    # arrays, and the group's SPMD lockstep must not fork on a
    # host-side reload.
    audit_every = int(os.environ.get("PADDLE_SERVE_WEIGHT_AUDIT_TICKS",
                                     "0") or 0)
    if group_size > 1:
        audit_every = 0

    def _stream_pages(gid, out):
        """Prefill finished for a handed-off request: export its pages,
        stream CRC-framed ``kvpage`` events (the mid-transfer chaos
        probes fire between frames), emit ``kvdone`` with the first
        sampled token, then free the request's blocks — the decode
        worker owns it from here."""
        hm = handoff.pop(gid)
        rid = rid_of.pop(gid)
        if out.token < 0:
            # aborted before/without a first token (deadline expiry):
            # typed end, no pages, nothing held
            _emit({"e": "kvdone", "gid": gid, "hid": hm["hid"],
                   "first_tok": None, "fin": True,
                   "reason": out.finish_reason, "frames": 0, "crc": 0})
            eng.release(rid)
            return
        if out.finished:
            # the first token already ends the request (max_new=1 or
            # EOS): nothing left to decode, nothing to transfer
            _emit({"e": "kvdone", "gid": gid, "hid": hm["hid"],
                   "first_tok": int(out.token), "fin": True,
                   "reason": out.finish_reason, "frames": 0, "crc": 0})
            eng.release(rid)
            return
        pages = eng.export_kv_pages(rid)
        blob = pack_kv_pages(pages)
        frames = split_frames(blob)
        for seq, chunk in enumerate(frames):
            if fi.should_fire("serve.prefill_crash"):
                os.kill(os.getpid(), signal.SIGKILL)  # mid-transfer
            fr = encode_frame(
                chunk,
                corrupt=fi.should_fire("serve.kv_transfer_corrupt"))
            _emit({"e": "kvpage", "gid": gid, "hid": hm["hid"],
                   "seq": seq, "total": len(frames), **fr})
        _emit({"e": "kvdone", "gid": gid, "hid": hm["hid"],
               "first_tok": int(out.token), "fin": False, "reason": None,
               "frames": len(frames), "crc": zlib.crc32(blob),
               "nbytes": len(blob), "covered": int(pages["covered"])})
        # handoff delivered: this worker's part is done — free the blocks
        eng.cancel(rid, reason="handoff")
        eng.release(rid)

    def _handle(cmd):
        nonlocal shutting
        op = cmd.get("op")
        if op == "submit":
            gid = cmd["gid"]
            try:
                rid = eng.add_request(
                    np.asarray(cmd["prompt"], np.int32),
                    SamplingParams(max_new_tokens=int(cmd["max_new"]),
                                   eos_token_id=cmd.get("eos")),
                    deadline=cmd.get("deadline"),
                    tenant=cmd.get("tenant"), tier=cmd.get("tier"))
            except RequestTimeoutError:
                _emit({"e": "tok", "gid": gid, "gen": cmd.get("gen", 0),
                       "toks": [], "fin": True, "reason": "timeout"})
                return
            except Exception as ex:  # typed errors -> router surfaces
                _emit({"e": "err", "gid": gid,
                       "kind": type(ex).__name__, "msg": str(ex)})
                return
            rid_of[gid] = rid
            meta[gid] = {"gen": cmd.get("gen", 0)}
        elif op == "prefill":
            # disaggregated stage 1 (ISSUE 15): admit normally; the
            # output loop intercepts the first sampled token and streams
            # the KV pages up instead of emitting it as a tok event
            gid = cmd["gid"]
            try:
                rid = eng.add_request(
                    np.asarray(cmd["prompt"], np.int32),
                    SamplingParams(max_new_tokens=int(cmd["max_new"]),
                                   eos_token_id=cmd.get("eos")),
                    deadline=cmd.get("deadline"),
                    tenant=cmd.get("tenant"), tier=cmd.get("tier"))
            except RequestTimeoutError:
                _emit({"e": "kvdone", "gid": gid,
                       "hid": cmd.get("hid", 0), "first_tok": None,
                       "fin": True, "reason": "timeout", "frames": 0,
                       "crc": 0})
                return
            except Exception as ex:
                _emit({"e": "err", "gid": gid,
                       "kind": type(ex).__name__, "msg": str(ex)})
                return
            rid_of[gid] = rid
            handoff[gid] = {"gen": cmd.get("gen", 0),
                            "hid": cmd.get("hid", 0)}
        elif op == "kvpage":
            # disaggregated stage 2, inbound frame: buffer + verify CRC
            gid = cmd["gid"]
            buf = page_buf.setdefault(gid, {"frames": {}, "bad": None})
            chunk = decode_frame(cmd)
            if chunk is None:
                buf["bad"] = f"frame {cmd.get('seq')} corrupt"
                return
            buf["frames"][int(cmd["seq"])] = chunk
            # bound the staging dict: frames whose submit_pages never
            # arrives (router died mid-send) must not grow forever
            while len(page_buf) > 32:
                page_buf.pop(next(iter(page_buf)))
        elif op == "submit_pages":
            gid = cmd["gid"]
            buf = page_buf.pop(gid, None) or {"frames": {}, "bad": None}
            why = buf["bad"]
            pages = None
            if why is None:
                blob, why = join_frames(buf["frames"],
                                        cmd.get("frames", 0),
                                        cmd.get("crc"))
            if why is None:
                try:
                    pages = unpack_kv_pages(blob)
                except ValueError as ex:
                    why = str(ex)
            if why is not None:
                # typed rejection: the router re-drives the prefill under
                # its transfer retry budget — NEVER decode on garbage
                _emit({"e": "err", "gid": gid, "kind": "KVTransferError",
                       "msg": f"rejecting handed-off pages: {why}"})
                return
            try:
                rid = eng.add_request_with_pages(
                    np.asarray(cmd["prompt"], np.int32), pages,
                    SamplingParams(max_new_tokens=int(cmd["max_new"]),
                                   eos_token_id=cmd.get("eos")),
                    deadline=cmd.get("deadline"),
                    tenant=cmd.get("tenant"), tier=cmd.get("tier"))
            except RequestTimeoutError:
                # expired between prefill completion and decode
                # admission: imported pages dropped, typed end
                _emit({"e": "tok", "gid": gid, "gen": cmd.get("gen", 0),
                       "toks": [], "fin": True, "reason": "timeout"})
                return
            except Exception as ex:
                _emit({"e": "err", "gid": gid,
                       "kind": type(ex).__name__, "msg": str(ex)})
                return
            rid_of[gid] = rid
            meta[gid] = {"gen": cmd.get("gen", 0)}
        elif op == "cancel":
            gid = cmd["gid"]
            page_buf.pop(gid, None)
            handoff.pop(gid, None)
            rid = rid_of.get(gid)
            if rid is not None:
                eng.cancel(rid, reason=cmd.get("reason", "cancelled"))
                # cancelled requests emit no fin event — drop the
                # bookkeeping here or it grows for the server's life
                rid_of.pop(gid, None)
                meta.pop(gid, None)
                eng.release(rid)
        elif op == "reload":
            from ....distributed.checkpoint.manager import CheckpointManager

            step = eng.reload_weights(CheckpointManager(cmd["root"]))
            _emit({"e": "reloaded", "replica": replica_id, "step": step})
        elif op == "stats":
            s = eng.stats()
            m = eng.metrics()
            _emit({"e": "stats", "replica": replica_id, "role": role,
                   "blocks_free": s["blocks_free"],
                   "blocks_high_water": s["blocks_high_water"],
                   "waiting": s["waiting"], "running": s["running"],
                   "steps": s["steps"], "tokens_out": s["tokens_out"],
                   # engine-owned latency percentiles (ISSUE 15): the
                   # disagg bench reads DECODE-worker ITL from here, so
                   # the comparison is engine-measured, not bench-timed
                   "itl_p50_ms": m["itl_ms"]["p50"],
                   "itl_p99_ms": m["itl_ms"]["p99"],
                   "ttft_p99_ms": m["ttft_ms"]["p99"],
                   # per-replica QoS counters (ISSUE 17): the qos drill
                   # and bench sum these fleet-wide to prove batch-tier
                   # work YIELDED slots rather than being dropped
                   "quota_throttled": s["quota_throttled"],
                   "batch_yields": s["batch_yields"],
                   # integrity counters (ISSUE 20). For tp groups, rank
                   # 0 is the group's one mouth and runs in SPMD
                   # lockstep with every member, so its engine-owned
                   # counters ARE the group's aggregate.
                   "kv_pages_verified": m["kv_pages_verified"],
                   "kv_pages_rejected": m["kv_pages_rejected"],
                   "weight_audits": m["weight_audits"],
                   "weight_audit_failures": m["weight_audit_failures"]})
        elif op == "configure_tenant":
            # QoS envelope push (ISSUE 17): idempotent — the router
            # re-sends the full set to every new incarnation. Cache
            # shares only apply where the subsystem exists; a fleet
            # without tiering/prefix-sharing serves the tenant without
            # those caps rather than erroring the whole config.
            eng.configure_tenant(
                cmd["tenant"], weight=cmd.get("weight", 1.0),
                rate_tokens_per_s=cmd.get("rate"),
                window_s=cmd.get("window", 1.0),
                host_blocks=(cmd.get("host_blocks")
                             if eng.kv_tier is not None else None),
                prefix_blocks=(cmd.get("prefix_blocks")
                               if eng.prefix_cache is not None else None))
        elif op == "reset_metrics":
            # window discipline (bench): warm-phase latency observations
            # must not pollute the timed window's percentiles
            eng.reset_metrics()
            eng.reset_block_high_water()
        elif op == "shutdown":
            shutting = True

    gid_by_rid = {}
    # heartbeat/load-report throttles: an atomic file replace and a JSON
    # line per ~1ms engine step is pure overhead — the watchdog judges
    # in seconds and the router's load signal tolerates 100ms staleness
    last_hb = [0.0]
    last_load = [0.0]

    def _beat():
        now = time.monotonic()
        if now - last_hb[0] >= 0.25:
            last_hb[0] = now
            hb.write(step=steps, dir=hb_dir, rank=hb_rank)

    while True:
        # chaos probes count BUSY ticks only: a crash/hang while idle
        # exercises nothing — the interesting failure is mid-serve, with
        # in-flight requests for the router to recover. The group sites
        # are armed on ONE member (the spec's "rank"): member_crash is
        # the partial-group OOM-kill shape, member_hang wedges this rank
        # so the next collective stalls the WHOLE group — every member's
        # heartbeat goes stale and only the watchdog can end it.
        if eng.has_work():
            if fi.should_fire("serve.replica_crash"):
                os.kill(os.getpid(), signal.SIGKILL)
            if fi.should_fire("serve.group_member_crash"):
                os.kill(os.getpid(), signal.SIGKILL)
            if fi.should_fire("serve.replica_hang") or \
                    fi.should_fire("serve.group_member_hang"):
                while True:  # wedged: no heartbeat, no service, no exit
                    time.sleep(3600)
            if fi.should_fire("serve.bit_flip"):
                # SILENT corruption: nothing raises, nothing exits — the
                # flip lands and this replica keeps serving wrong bytes
                # until the integrity sentinel catches it
                _integrity.flip_bit(
                    eng, os.environ.get("CHAOS_SERVE_BIT_FLIP_TARGET",
                                        "weights"))
        if chan is not None and group_rank > 0:
            # member rank: commands arrive ONLY on the broadcast channel,
            # in rank 0's exact application order (SPMD lockstep); a
            # fetch timeout is an idle tick — heartbeat and re-poll
            cmds = chan.fetch()
            if cmds is None:
                steps += 1
                _beat()
                continue
            for cmd in cmds:
                _handle(cmd)
        else:
            try:
                cmd = (cmd_q.get_nowait() if eng.has_work() or shutting
                       else cmd_q.get(timeout=0.05))
            except queue.Empty:
                cmd = None
            cmds = []
            while cmd is not None:
                cmds.append(cmd)
                try:
                    cmd = cmd_q.get_nowait()
                except queue.Empty:
                    cmd = None
            if chan is not None:
                # group lockstep cannot follow wall clocks: a deadline
                # expiring between two ranks' admission checks would
                # desynchronize the collectives, so group replicas strip
                # it — deadline enforcement stays at the router, whose
                # cancel commands ride this same ordered channel
                for c in cmds:
                    c.pop("deadline", None)
                if cmds or eng.has_work():
                    chan.publish(cmds)
            for cmd in cmds:
                _handle(cmd)
        if eng.has_work():
            gid_by_rid = {rid: gid for gid, rid in rid_of.items()}
            per_gid = {}
            for out in eng.step():
                gid = gid_by_rid.get(out.rid)
                if gid is None:
                    continue
                if gid in handoff:
                    # prefill handoff: the first token triggers the page
                    # transfer instead of a tok event
                    _stream_pages(gid, out)
                    continue
                rec = per_gid.setdefault(
                    gid, {"toks": [], "fin": False, "reason": None})
                if out.token >= 0:
                    rec["toks"].append(int(out.token))
                if out.finished:
                    rec["fin"] = True
                    rec["reason"] = out.finish_reason
            for gid, rec in per_gid.items():
                _emit({"e": "tok", "gid": gid, "gen": meta[gid]["gen"],
                       "toks": rec["toks"], "fin": rec["fin"],
                       "reason": rec["reason"]})
                if rec["fin"]:
                    rid = rid_of.pop(gid)
                    meta.pop(gid, None)
                    eng.release(rid)
            now = time.monotonic()
            if now - last_load[0] >= 0.1:
                last_load[0] = now
                m = eng.metrics()
                _emit({"e": "load", "replica": replica_id,
                       "kv": m["kv_block_utilization"] or 0.0,
                       "occ": m["decode_batch_occupancy"] or 0.0,
                       "waiting": len(eng.scheduler.waiting)})
        steps += 1
        if audit_every and steps % audit_every == 0 and not shutting:
            if not eng.audit_weights():
                # in-place weight corruption: tell the router (suspicion
                # charge) and hot-swap the artifact's clean weights so
                # this replica stops serving wrong bytes NOW — the
                # router may still quarantine-restart it
                _emit({"e": "integrity", "kind": "weight_audit",
                       "replica": replica_id})
                try:
                    eng.reload_weights(cfg["artifact"])
                except Exception as ex:  # pragma: no cover - defensive
                    _emit({"e": "err", "gid": None,
                           "kind": type(ex).__name__,
                           "msg": f"reload after failed weight audit: "
                                  f"{ex}"})
        _beat()
        if shutting and not eng.has_work():
            eng.close()
            _emit({"e": "bye", "replica": replica_id})
            return 0


if __name__ == "__main__":
    sys.exit(replica_worker_main())
