"""Elastic supervision suite (ISSUE 4): hang watchdogs, graceful
preemption, resumable data streams, and the crash-loop breaker.

Fast-tier tests drive each mechanism in-process (seeded fault injection,
fake clocks, self-delivered signals); the slow tier launches REAL worker
processes under ``python -m paddle_tpu.distributed.launch`` and exercises
the supervisor end to end — hang kill, budget-free preemption relaunch,
crash-loop exhaustion, fresh rendezvous ports.
"""

import errno
import gc
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.io as io
import paddle_tpu.nn as nn
from paddle_tpu import TrainStallError
from paddle_tpu.core.exceptions import stall_guard
from paddle_tpu.distributed.launch import heartbeat as hb
from paddle_tpu.distributed.launch.controllers.collective import (
    HANG_EXIT_CODE, CollectiveController, CrashLoopError, RestartBudget)
from paddle_tpu.incubate.fused_train_step import FusedTrainStep
from paddle_tpu.utils import fault_injection as fi
from paddle_tpu.utils.retry import replace_across_fs, retry_os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    paddle.set_flags({"FLAGS_step_timeout_s": 0.0,
                      "FLAGS_check_nan_inf_action": "none"})


# ---------------------------------------------------------------------------
# heartbeats
# ---------------------------------------------------------------------------

class TestHeartbeat:
    def test_write_read_roundtrip(self, tmp_path, monkeypatch):
        monkeypatch.setenv(hb.HEARTBEAT_DIR_ENV, str(tmp_path))
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        assert hb.write(step=17)
        beats = hb.read_all(str(tmp_path))
        assert beats["3"]["step"] == 17
        assert beats["3"]["pid"] == os.getpid()
        assert abs(beats["3"]["time"] - time.time()) < 5

    def test_unsupervised_write_is_noop(self, monkeypatch):
        monkeypatch.delenv(hb.HEARTBEAT_DIR_ENV, raising=False)
        assert hb.write(step=1) is False

    def test_staleness_is_judged_on_stalest_rank(self, tmp_path):
        # rank 0 beats freshly, rank 1 went silent: the GROUP is stale —
        # training is lockstep, one wedged rank wedges everyone
        d = str(tmp_path)
        now = time.time()
        hb.write(step=5, dir=d, rank="0")
        assert not hb.stale(d, 10.0, now=now, expected=1)
        assert hb.stale(d, 10.0, since=now - 100, now=now, expected=2)

    def test_spawn_baseline_grace(self, tmp_path):
        # no heartbeats yet: not stale until since + timeout elapses
        d = str(tmp_path)
        now = time.time()
        assert not hb.stale(d, 10.0, since=now - 5, now=now, expected=2)
        assert hb.stale(d, 10.0, since=now - 11, now=now, expected=2)
        # nothing to judge at all -> never stale
        assert not hb.stale(d, 10.0, now=now)

    def test_injected_write_failure_is_contained(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv(hb.HEARTBEAT_DIR_ENV, str(tmp_path))
        with fi.inject("hb.write") as inj:
            assert hb.write(step=1) is False  # swallowed, not raised
        assert inj.fires == 1
        assert hb.read_all(str(tmp_path)) == {}
        assert hb.write(step=2)  # healthy again once disarmed

    def test_disabled_timeout_never_stale(self, tmp_path):
        assert not hb.stale(str(tmp_path), 0, since=0, now=1e9)

    def test_exited_ranks_heartbeats_are_ignored(self, tmp_path):
        # rank 0 finished (its file ages), rank 1 still beats: judging
        # only the live ranks, the group is NOT hung
        import json

        d = str(tmp_path)
        now = time.time()
        with open(os.path.join(d, "hb.0"), "w") as f:
            json.dump({"step": 9, "time": now - 300, "pid": 1}, f)
        hb.write(step=5, dir=d, rank="1")
        assert hb.stale(d, 30.0, since=now - 400, now=now, expected=2)
        assert not hb.stale(d, 30.0, since=now - 400, now=now,
                            ranks=["1"])
        # and a live rank that went silent is still caught
        assert hb.stale(d, 30.0, since=now - 400, now=now, ranks=["0"])


# ---------------------------------------------------------------------------
# restart budget (leaky bucket + backoff)
# ---------------------------------------------------------------------------

class TestRestartBudget:
    def _budget(self, k, window=100.0, base=1.0):
        clk = {"t": 0.0}
        delays = []
        b = RestartBudget(k, window_s=window, backoff_base_s=base,
                          clock=lambda: clk["t"], sleep=delays.append)
        return b, clk, delays

    def test_k_restarts_then_refusal(self):
        b, _, _ = self._budget(2)
        assert b.try_acquire() and b.try_acquire()
        assert not b.try_acquire()
        assert b.used == 2 and b.total_restarts == 2

    def test_zero_budget_refuses_immediately(self):
        b, _, _ = self._budget(0)
        assert not b.try_acquire()

    def test_rolling_window_leaks_old_crashes(self):
        b, clk, _ = self._budget(1, window=100.0)
        assert b.try_acquire()
        assert not b.try_acquire()
        clk["t"] = 150.0  # the old crash aged out of the window
        assert b.used == 0
        assert b.try_acquire()
        assert b.total_restarts == 2  # lifetime counter keeps the truth

    def test_backoff_exponential_and_capped(self):
        b, _, delays = self._budget(10, base=1.0)
        for _ in range(7):
            b.try_acquire()
            b.backoff()
        assert delays[:6] == [1.0, 2.0, 4.0, 8.0, 16.0, 30.0]
        assert delays[6] == 30.0  # capped

    def test_preemption_cap_stops_a_123_loop(self):
        # clean preemptions are budget-free AND backoff-free, but capped:
        # past the per-window cap they are charged like crashes
        b, clk, delays = self._budget(0)
        for _ in range(RestartBudget.PREEMPT_CAP_PER_WINDOW):
            assert b.note_preemption()
        assert not b.note_preemption()
        assert delays == []  # immediate relaunch, as the flag docs promise
        assert b.used == 0  # the crash bucket was never touched
        clk["t"] = 1000.0  # preemptions age out of the window too
        assert b.note_preemption()

    def test_crash_loop_error_carries_exit_code(self):
        e = CrashLoopError("boom", exit_code=7, restarts=3)
        assert e.exit_code == 7 and e.restarts == 3
        assert isinstance(e, RuntimeError)


# ---------------------------------------------------------------------------
# in-process stall guard
# ---------------------------------------------------------------------------

class TestStallGuard:
    def test_raises_typed_error_on_stall(self):
        t0 = time.time()
        with pytest.raises(TrainStallError, match="no progress"):
            with stall_guard(0.2, "unit test"):
                time.sleep(10)
        assert time.time() - t0 < 5  # interrupted, not slept out

    def test_zero_timeout_disables(self):
        with stall_guard(0, "x"):
            time.sleep(0.01)

    def test_fast_block_passes_and_restores_handler(self):
        prev = signal.getsignal(signal.SIGALRM)
        with stall_guard(5.0, "x"):
            pass
        assert signal.getsignal(signal.SIGALRM) is prev
        # and the itimer is disarmed
        assert signal.getitimer(signal.ITIMER_REAL)[0] == 0.0

    def test_noop_off_main_thread(self):
        out = {}

        def run():
            try:
                with stall_guard(0.05, "thread"):
                    time.sleep(0.2)
                out["ok"] = True
            except BaseException as e:  # pragma: no cover
                out["err"] = e

        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert out.get("ok") is True


# ---------------------------------------------------------------------------
# resumable data stream
# ---------------------------------------------------------------------------

class _VarLen(io.Dataset):
    def __init__(self, n=24, seed=0):
        rng = np.random.RandomState(seed)
        self.lens = rng.randint(3, 25, size=n)
        self.data = [rng.randn(int(l), 2).astype("float32")
                     for l in self.lens]

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return self.data[i]


def _sampler(**kw):
    ds = _VarLen()
    kw.setdefault("batch_size", 2)
    kw.setdefault("boundaries", [8, 16, 32])
    kw.setdefault("lengths", ds.lens.tolist())
    return io.BucketedBatchSampler(ds, **kw)


class TestSamplerState:
    def test_state_dict_roundtrip_mid_epoch(self):
        s = _sampler(shuffle=True, seed=5)
        s.set_epoch(1)
        full = list(s)
        s.advance(4)
        sd = s.state_dict()
        s2 = _sampler(shuffle=True)  # different (auto) seed on purpose
        s2.set_state_dict(sd)
        assert list(s2) == full[4:]  # exact remaining sequence

    def test_unseeded_sampler_is_still_replayable(self):
        s = _sampler(shuffle=True, seed=None)
        full = list(s)
        s.advance(3)
        s2 = _sampler(shuffle=True, seed=None)
        s2.set_state_dict(s.state_dict())
        assert list(s2) == full[3:]

    def test_set_epoch_resets_cursor_only_on_change(self):
        s = _sampler(shuffle=True, seed=1)
        s.advance(5)
        s.set_epoch(0)  # same epoch (resume re-entry): keep the cursor
        assert s.state_dict()["cursor"] == 5
        s.set_epoch(1)  # new epoch: start clean
        assert s.state_dict()["cursor"] == 0

    def test_consumers_without_advance_see_full_epochs(self):
        s = _sampler(shuffle=True, seed=2)
        assert list(s) == list(s)  # unchanged legacy behavior

    def test_unseeded_epochs_still_differ(self):
        # resumability must not forfeit unseeded reshuffling: successive
        # full passes draw fresh epoch seeds (each recorded for replay)
        s = _sampler(shuffle=True, seed=None)
        orders = [tuple(map(tuple, s)) for _ in range(4)]
        assert len(set(orders)) > 1

    def test_fully_consumed_epoch_rolls_over(self):
        # a resume-armed loop that never calls set_epoch must keep making
        # progress: exhausting the epoch rolls to the next one. Since the
        # divergence-rollback work, advance() itself carries the cursor
        # across the epoch edge (re-seeding exactly as a real epoch
        # transition would), so the roll happens eagerly at consumption
        # time rather than lazily at the next __iter__
        s = _sampler(shuffle=True, seed=4)
        n = len(list(s))
        epoch0 = s.state_dict()["epoch"]
        s.advance(n)
        assert s.state_dict()["epoch"] == epoch0 + 1
        assert s.state_dict()["cursor"] == 0
        nxt = list(s)  # a full fresh pass, not an empty one
        assert len(nxt) == n
        assert s.state_dict()["epoch"] == epoch0 + 1

    def test_fingerprint_mismatch_raises(self):
        s = _sampler()
        sd = s.state_dict()
        other = _sampler(batch_size=3)
        with pytest.raises(ValueError, match="batch_size"):
            other.set_state_dict(sd)

    def test_shuffle_mismatch_raises(self):
        sd = _sampler(shuffle=True, seed=1).state_dict()
        with pytest.raises(ValueError, match="shuffle"):
            _sampler(shuffle=False).set_state_dict(sd)

    def test_dataloader_delegates_stream_state(self):
        s = _sampler(shuffle=True, seed=3)
        loader = io.DataLoader(_VarLen(), batch_sampler=s,
                               collate_fn=io.PadToBucket([8, 16, 32]))
        loader.advance(2)
        assert loader.state_dict()["cursor"] == 2
        loader.set_epoch(4)
        assert loader.state_dict()["epoch"] == 4
        assert io.resolve_resumable(loader) is s

    def test_plain_dataloader_is_not_resumable(self):
        loader = io.DataLoader(_VarLen(), batch_size=2)
        with pytest.raises(TypeError, match="not resumable"):
            loader.state_dict()
        assert io.resolve_resumable(loader) is None

    def test_checkpoint_manager_persists_and_restores_sampler(self,
                                                              tmp_path):
        s = _sampler(shuffle=True, seed=7)
        loader = io.DataLoader(_VarLen(), batch_sampler=s,
                               collate_fn=io.PadToBucket([8, 16, 32]))
        full = list(s)
        loader.advance(3)
        mgr = paddle.CheckpointManager(str(tmp_path))
        mgr.save(3, sampler=loader)
        assert mgr.latest_valid_step() == 3
        s2 = _sampler(shuffle=True)
        loader2 = io.DataLoader(_VarLen(), batch_sampler=s2,
                                collate_fn=io.PadToBucket([8, 16, 32]))
        mgr2 = paddle.CheckpointManager(str(tmp_path))
        assert mgr2.auto_resume(sampler=loader2) == 3
        assert list(s2) == full[3:]

    def test_prefetcher_resume_never_double_consumes(self):
        # a prefetcher stages ahead of consumption; a resume must replay
        # from the CONSUMED cursor, so staged-but-unconsumed batches are
        # re-staged, never skipped and never trained twice
        s = _sampler(shuffle=True, seed=9)
        loader = io.DataLoader(_VarLen(), batch_sampler=s,
                               collate_fn=io.PadToBucket([8, 16, 32]))
        expected = list(s)
        pf = io.DevicePrefetcher(loader, depth=2)
        assert io.resolve_resumable(pf) is s
        consumed = 0
        for batch in pf:
            consumed += 1
            s.advance(1)
            if consumed == 2:
                break
        pf.close()
        sd = s.state_dict()
        assert sd["cursor"] == 2
        s2 = _sampler(shuffle=True)
        s2.set_state_dict(sd)
        assert list(s2) == expected[2:]


# ---------------------------------------------------------------------------
# prefetcher lifecycle (thread-leak satellite)
# ---------------------------------------------------------------------------

def _live_transfer_threads(tag):
    return [t for t in threading.enumerate()
            if t.is_alive() and tag in t.name]


class TestPrefetcherClose:
    def _pf(self, name, n=16):
        batches = [np.full((2, 3), i, dtype="float32") for i in range(n)]
        return io.DevicePrefetcher(batches, depth=2, name=name)

    def test_close_after_early_break_leaves_no_threads(self):
        pf = self._pf("leaktest1")
        for i, _ in enumerate(pf):
            if i == 1:
                break
        pf.close()
        assert _live_transfer_threads("leaktest1") == []

    def test_context_manager_closes(self):
        with self._pf("leaktest2") as pf:
            next(iter(pf))
        assert _live_transfer_threads("leaktest2") == []

    def test_generator_close_joins_thread(self):
        pf = self._pf("leaktest3")
        it = iter(pf)
        next(it)
        it.close()  # GeneratorExit path (del/garbage collection)
        gc.collect()
        assert _live_transfer_threads("leaktest3") == []

    def test_close_is_idempotent_and_reiterable(self):
        pf = self._pf("leaktest4", n=4)
        it = iter(pf)
        next(it)
        pf.close()
        pf.close()
        assert len(list(pf)) == 4  # fresh full pass after close
        assert _live_transfer_threads("leaktest4") == []

    def test_abandoned_generator_terminates_after_close(self):
        pf = self._pf("leaktest5", n=8)
        it = iter(pf)
        next(it)
        pf.close()
        assert len(list(it)) <= 7  # drains/ends; must not block forever

    def test_hapi_fit_closes_prefetcher_on_error(self):
        class Boom(io.Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i >= 4:
                    raise RuntimeError("poisoned sample")
                return (np.ones(3, dtype="float32"),
                        np.zeros(1, dtype="float32"))

        model = paddle.Model(nn.Linear(3, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        model.prepare(opt, nn.MSELoss())
        before = {t.name for t in threading.enumerate()}
        with pytest.raises(RuntimeError, match="poisoned"):
            model.fit(Boom(), batch_size=2, epochs=1, verbose=0)
        time.sleep(0.05)
        leaked = [t for t in threading.enumerate()
                  if t.name not in before and "-transfer" in t.name
                  and t.is_alive()]
        assert leaked == []


# ---------------------------------------------------------------------------
# drive() supervision: stall, preemption, chaos sites, heartbeats
# ---------------------------------------------------------------------------

def _tiny_step():
    paddle.seed(0)
    model = nn.Linear(4, 1)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = FusedTrainStep(model, opt, loss_fn=lambda o: (o * o).mean())
    batches = [[paddle.to_tensor(
        np.random.RandomState(i).randn(2, 4).astype("float32"))]
        for i in range(12)]
    return step, batches


class TestDriveSupervision:
    def test_wedged_step_raises_train_stall_error(self):
        step, batches = _tiny_step()
        paddle.set_flags({"FLAGS_step_timeout_s": 0.3})
        t0 = time.time()
        with fi.inject("train.stall", every_n=2):
            with pytest.raises(TrainStallError):
                step.drive(batches, steps=6, log_every=3)
        assert time.time() - t0 < 30

    def test_stall_site_inert_when_unarmed(self):
        step, batches = _tiny_step()
        paddle.set_flags({"FLAGS_step_timeout_s": 5.0})
        h = step.drive(batches, steps=4, log_every=2)
        assert h["steps"] == 4

    def test_proc_kill_site_fires_sigkill(self, monkeypatch):
        step, batches = _tiny_step()
        calls = []
        monkeypatch.setattr(os, "kill",
                            lambda pid, sig: calls.append((pid, sig)))
        with fi.inject("proc.kill", every_n=3):
            step.drive(batches, steps=5, log_every=2)
        assert (os.getpid(), signal.SIGKILL) in calls

    def test_sigterm_checkpoints_and_exits_123(self, tmp_path):
        step, batches = _tiny_step()
        mgr = paddle.CheckpointManager(str(tmp_path))

        def preempt_now(win):
            signal.raise_signal(signal.SIGTERM)

        with pytest.raises(SystemExit) as exc:
            step.drive(batches, steps=9, log_every=3,
                       on_window=preempt_now, checkpoint=mgr)
        assert exc.value.code == hb.PREEMPT_EXIT_CODE
        # the preemption checkpoint committed at the window-boundary step
        assert mgr.latest_valid_step() == \
            step.device_metrics()["step_count"] == 3
        # handler restored: a later SIGTERM is no longer swallowed
        assert signal.getsignal(signal.SIGTERM) in (
            signal.SIG_DFL, signal.default_int_handler)

    def test_preemption_stops_at_window_boundary(self):
        # SIGTERM mid-window: the in-flight window finishes (all ranks
        # align on one global step) before the preemption exit
        step, batches = _tiny_step()
        fired = {"n": 0}
        orig_dispatch = step._dispatch

        def dispatch_and_preempt(*a, **kw):
            fired["n"] += 1
            if fired["n"] == 4:  # mid-window (log_every=3)
                signal.raise_signal(signal.SIGTERM)
            return orig_dispatch(*a, **kw)

        step._dispatch = dispatch_and_preempt
        with pytest.raises(SystemExit):
            step.drive(batches, steps=12, log_every=3)
        # windows are 3 steps: preempted during step 4 -> stopped at 6
        assert step.device_metrics()["step_count"] == 6

    def test_preemption_persists_sampler_cursor(self, tmp_path):
        paddle.seed(0)
        ds = _VarLen()
        model = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = FusedTrainStep(model, opt,
                              loss_fn=lambda o: (o * o).mean())
        s = _sampler(shuffle=True, seed=13)
        loader = io.DataLoader(ds, batch_sampler=s,
                               collate_fn=io.PadToBucket(
                                   [8, 16, 32], with_mask=False))
        mgr = paddle.CheckpointManager(str(tmp_path))
        with pytest.raises(SystemExit):
            step.drive(loader, log_every=2, checkpoint=mgr,
                       sampler=loader,
                       on_window=lambda w: signal.raise_signal(
                           signal.SIGTERM))
        assert mgr.latest_valid_step() == 2
        s2 = _sampler(shuffle=True)
        loader2 = io.DataLoader(ds, batch_sampler=s2,
                                collate_fn=io.PadToBucket(
                                    [8, 16, 32], with_mask=False))
        assert paddle.CheckpointManager(str(tmp_path)).auto_resume(
            sampler=loader2) == 2
        assert s2.state_dict()["cursor"] == 2  # exactly the trained batches

    def test_drive_heartbeats_at_window_boundaries(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv(hb.HEARTBEAT_DIR_ENV, str(tmp_path))
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        step, batches = _tiny_step()
        step.drive(batches, steps=6, log_every=3)
        beats = hb.read_all(str(tmp_path))
        assert beats["0"]["step"] == 6  # final boundary heartbeat

    def test_fit_heartbeats_when_supervised(self, tmp_path, monkeypatch):
        monkeypatch.setenv(hb.HEARTBEAT_DIR_ENV, str(tmp_path))
        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")

        class Eight(io.Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return (np.ones(3, dtype="float32"),
                        np.zeros(1, dtype="float32"))

        model = paddle.Model(nn.Linear(3, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        model.prepare(opt, nn.MSELoss())
        model.fit(Eight(), batch_size=2, epochs=1, verbose=0)
        beats = hb.read_all(str(tmp_path))
        assert beats["2"]["step"] == 4  # one per trained batch

    def test_non_resumable_sampler_kwarg_raises(self):
        step, batches = _tiny_step()
        with pytest.raises(TypeError, match="not a resumable"):
            step.drive(batches, steps=2, sampler=object())


# ---------------------------------------------------------------------------
# cross-filesystem rename satellite
# ---------------------------------------------------------------------------

def _exdev(*a, **kw):
    raise OSError(errno.EXDEV, "Invalid cross-device link")


class TestCrossFilesystem:
    def test_exdev_is_never_retried(self):
        calls = []

        def fn():
            calls.append(1)
            _exdev()

        with pytest.raises(OSError) as exc:
            retry_os(fn, retries=5)
        assert exc.value.errno == errno.EXDEV
        assert len(calls) == 1  # deterministic: no backoff spinning

    def test_replace_across_fs_file_fallback(self, tmp_path, monkeypatch):
        src = tmp_path / "src.bin"
        dst = tmp_path / "dst.bin"
        src.write_bytes(b"payload")
        dst.write_bytes(b"old")
        real_replace = os.replace
        state = {"first": True}

        def flaky_replace(a, b):
            if state["first"]:
                state["first"] = False
                _exdev()
            return real_replace(a, b)

        monkeypatch.setattr(os, "replace", flaky_replace)
        replace_across_fs(str(src), str(dst))
        assert dst.read_bytes() == b"payload"
        assert not src.exists()  # rename semantics
        assert list(tmp_path.iterdir()) == [dst]  # no tmp litter

    def test_replace_across_fs_directory_fallback(self, tmp_path,
                                                  monkeypatch):
        src = tmp_path / "srcdir"
        src.mkdir()
        (src / "a.txt").write_text("hello")
        dst = tmp_path / "dstdir"
        real_replace = os.replace
        state = {"first": True}

        def flaky_replace(a, b):
            if state["first"]:
                state["first"] = False
                _exdev()
            return real_replace(a, b)

        monkeypatch.setattr(os, "replace", flaky_replace)
        replace_across_fs(str(src), str(dst))
        assert (dst / "a.txt").read_text() == "hello"
        assert not src.exists()

    def test_localfs_rename_survives_exdev(self, tmp_path, monkeypatch):
        from paddle_tpu.distributed.fleet.utils.fs import LocalFS

        src = tmp_path / "ckpt.tmp"
        src.write_bytes(b"shard bytes")
        dst = tmp_path / "ckpt"
        real_replace = os.replace
        state = {"first": True}

        def flaky_replace(a, b):
            if state["first"]:
                state["first"] = False
                _exdev()
            return real_replace(a, b)

        monkeypatch.setattr(os, "replace", flaky_replace)
        LocalFS().rename(str(src), str(dst))
        assert dst.read_bytes() == b"shard bytes"

    def test_atomic_write_publishes_through_fallback(self, tmp_path,
                                                     monkeypatch):
        from paddle_tpu.utils.retry import atomic_write

        dst = tmp_path / "blob"
        real_replace = os.replace
        state = {"first": True}

        def flaky_replace(a, b):
            if state["first"]:
                state["first"] = False
                _exdev()
            return real_replace(a, b)

        monkeypatch.setattr(os, "replace", flaky_replace)
        atomic_write(str(dst), lambda f: f.write(b"abc"))
        assert dst.read_bytes() == b"abc"


# ---------------------------------------------------------------------------
# fault-site lint (tier-1 wiring of scripts/check_fault_sites.py)
# ---------------------------------------------------------------------------

class TestFaultSiteLint:
    def _mod(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_fault_sites",
            os.path.join(REPO, "scripts", "check_fault_sites.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_every_registered_site_is_exercised(self):
        mod = self._mod()
        sites = mod.registered_sites()
        assert set(sites) == set(fi.SITES)  # source parse == live registry
        assert mod.find_missing() == []

    def test_lint_catches_an_untested_site(self):
        mod = self._mod()
        # built by concatenation so the literal can't appear in this file
        # (the lint greps tests/, including this very test)
        fake = "totally." + "new_site"
        missing = mod.find_missing(sites=[fake])
        assert missing == [fake]


# ---------------------------------------------------------------------------
# controller units (no subprocesses)
# ---------------------------------------------------------------------------

def _args(tmp_path, **kw):
    from paddle_tpu.distributed.launch.main import parse_args

    a = parse_args(["--nproc_per_node=1", "x.py"])
    a.master = "127.0.0.1:45000"
    a.master_auto = kw.pop("master_auto", True)
    a.log_dir = str(tmp_path / "logs")
    for k, v in kw.items():
        setattr(a, k, v)
    return a


class TestControllerUnits:
    def test_refresh_master_picks_fresh_port(self, tmp_path):
        ctrl = CollectiveController(_args(tmp_path))
        before = ctrl.args.master
        ctrl._refresh_master()
        assert ctrl.args.master != before
        assert ctrl.args.master.startswith("127.0.0.1:")

    def test_explicit_master_is_never_rewritten(self, tmp_path):
        ctrl = CollectiveController(_args(tmp_path, master_auto=False))
        before = ctrl.args.master
        ctrl._refresh_master()
        assert ctrl.args.master == before

    def test_worker_env_exports_heartbeat_dir(self, tmp_path):
        ctrl = CollectiveController(_args(tmp_path))
        env = ctrl._worker_env(0)
        assert env["PADDLE_HEARTBEAT_DIR"] == ctrl._hb_dir
        assert os.path.isdir(ctrl._hb_dir)

    def test_spawn_clears_previous_rounds_heartbeats(self, tmp_path):
        ctrl = CollectiveController(_args(tmp_path))
        hb.write(step=1, dir=ctrl._hb_dir, rank="0")
        ctrl.args.training_script = sys.executable  # non-.py: exec direct
        ctrl.args.training_script_args = ["-c", "pass"]
        ctrl._spawn_all()
        try:
            assert hb.read_all(ctrl._hb_dir) == {}
            assert ctrl._spawn_time is not None
        finally:
            ctrl._kill_all()
            ctrl._close_logs()


# ---------------------------------------------------------------------------
# launcher end-to-end (real subprocesses) — slow tier
# ---------------------------------------------------------------------------

def _launch_env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["FLAGS_restart_backoff_s"] = "0.05"
    env.update(extra or {})
    return env


def _run_launch(args, script, extra_env=None, timeout=240):
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           *args, script]
    return subprocess.run(cmd, env=_launch_env(extra_env), cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


# exits 123 (clean preemption) on the first incarnation, 0 on the second
PREEMPT_SCRIPT = """
import os, sys
flag = os.path.join({out!r}, "attempted")
if not os.path.exists(flag):
    open(flag, "w").write("x")
    sys.exit(123)
open(os.path.join({out!r}, "succeeded"), "w").write("x")
"""

# hangs (beats once via bootstrap, then sleeps silently) on the first
# incarnation, exits 0 on the second
HANG_SCRIPT = """
import os, sys, time
flag = os.path.join({out!r}, "attempted")
if not os.path.exists(flag):
    open(flag, "w").write("x")
    time.sleep(120)   # no further heartbeats -> watchdog must kill us
open(os.path.join({out!r}, "succeeded"), "w").write("x")
"""

CRASH_SCRIPT = """
import os, sys
log = os.path.join({out!r}, "attempts")
open(log, "a").write("x")
sys.exit(5)
"""

PORT_SCRIPT = """
import os, sys
open(os.path.join({out!r}, "ports"), "a").write(
    os.environ["MASTER_PORT"] + "\\n")
flag = os.path.join({out!r}, "attempted")
if not os.path.exists(flag):
    open(flag, "w").write("x")
    sys.exit(3)
"""


@pytest.mark.slow
class TestLauncherSupervision:
    def test_clean_preemption_consumes_no_budget(self, tmp_path):
        script = tmp_path / "preempt.py"
        script.write_text(PREEMPT_SCRIPT.format(out=str(tmp_path)))
        # max_restart=0: the relaunch MUST ride the preemption path
        r = _run_launch(["--nproc_per_node=1", "--max_restart=0"],
                        str(script))
        assert r.returncode == 0, r.stderr[-2000:]
        assert (tmp_path / "succeeded").exists()
        assert "restart budget untouched" in r.stderr
        assert "worker failed" not in r.stderr

    def test_hang_watchdog_kills_and_restarts(self, tmp_path):
        script = tmp_path / "hang.py"
        script.write_text(HANG_SCRIPT.format(out=str(tmp_path)))
        # timeout > worst-case framework import on a loaded CI box (the
        # bootstrap heartbeat lands only after the heavy import), and one
        # spare restart so a spurious load-induced kill can't fail the test
        r = _run_launch(
            ["--nproc_per_node=1", "--max_restart=2"], str(script),
            extra_env={"FLAGS_worker_hang_timeout_s": "10",
                       "FLAGS_worker_term_grace_s": "2"})
        assert r.returncode == 0, r.stderr[-2000:]
        assert (tmp_path / "succeeded").exists()
        assert "heartbeats stale" in r.stderr
        assert "restart 1/2" in r.stderr  # a hang consumes budget

    def test_crash_loop_breaker_stops_relaunching(self, tmp_path):
        script = tmp_path / "crash.py"
        script.write_text(CRASH_SCRIPT.format(out=str(tmp_path)))
        r = _run_launch(["--nproc_per_node=1", "--max_restart=2"],
                        str(script))
        assert r.returncode == 5  # the real failure code propagates
        assert "crash loop" in r.stderr
        # initial attempt + exactly 2 budgeted restarts, then STOP
        assert (tmp_path / "attempts").read_text() == "xxx"

    def test_restart_gets_fresh_master_port(self, tmp_path):
        script = tmp_path / "port.py"
        script.write_text(PORT_SCRIPT.format(out=str(tmp_path)))
        r = _run_launch(["--nproc_per_node=1", "--max_restart=1"],
                        str(script))
        assert r.returncode == 0, r.stderr[-2000:]
        ports = (tmp_path / "ports").read_text().split()
        assert len(ports) == 2 and ports[0] != ports[1]


@pytest.mark.slow
class TestChaosDrill:
    def test_kill_preempt_hang_recover_bit_exact(self, tmp_path):
        """The ISSUE-4 acceptance drill: SIGKILL, graceful preemption and
        a hang in a real 2-worker job all recover to a loss sequence
        bit-identical to an uninterrupted baseline, within budget."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "chaos_train.py"),
             "--out", str(tmp_path)],
            env=_launch_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "ALL SCENARIOS PASSED" in r.stdout

    def test_plan_drill_sharded_restarts_bit_exact(self, tmp_path):
        """The ISSUE-8 acceptance drill: kill -9 / preempt / hang under a
        dp x tp SHARDED PLAN (zero1 moments, plan-fingerprinted
        checkpoints) restart to a loss sequence bit-identical to the
        uninterrupted sharded baseline."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "chaos_train.py"),
             "--drill", "plan", "--out", str(tmp_path)],
            env=_launch_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "PLAN DRILL PASSED" in r.stdout
