"""Crash-consistency suite for the fault-tolerance layer (ISSUE 2).

Every failure mode is driven through paddle_tpu.utils.fault_injection's
named sites, so the exact production code paths fail deterministically:
a save killed mid-shard-write, a corrupt shard byte, a NaN grad, a flaky
rename. Assertions follow the issue's acceptance criteria: torn saves are
invisible to latest_valid_step(), corruption raises a typed error instead
of garbage, and the step guard skips exactly the poisoned step while the
GradScaler backs off.
"""

import json
import os
import pickle

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu import CheckpointCorruptionError, CheckpointManager
from paddle_tpu.distributed.checkpoint import (COMMIT_FILE, is_committed,
                                               verify_checkpoint)
from paddle_tpu.utils import fault_injection as fi


def _flip_shard_byte(npz_path):
    """Flip the last payload byte of the first npz member — guaranteed to be
    array data (npy layout is header-then-raw-bytes, stored uncompressed),
    not zip/npy header padding a blind mid-file flip can land in."""
    import struct
    import zipfile

    with zipfile.ZipFile(npz_path) as z:
        info = z.infolist()[0]
    blob = bytearray(open(npz_path, "rb").read())
    hdr = info.header_offset
    nlen, elen = struct.unpack("<HH", blob[hdr + 26:hdr + 30])
    data_end = hdr + 30 + nlen + elen + info.compress_size
    blob[data_end - 1] ^= 0xFF
    open(npz_path, "wb").write(bytes(blob))


@pytest.fixture(autouse=True)
def _fast_retries():
    """Keep backoff sleeps negligible and reset guard flags per test."""
    paddle.set_flags({"FLAGS_ckpt_save_retries": 2})
    yield
    paddle.set_flags({"FLAGS_ckpt_save_retries": 3,
                      "FLAGS_check_nan_inf_action": "none"})


# ---------------------------------------------------------------------------
# paddle.save / paddle.load durability
# ---------------------------------------------------------------------------

class TestAtomicSave:
    def test_killed_save_preserves_previous_file(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        paddle.save({"w": 1}, p)
        with fi.inject("io.save"):
            with pytest.raises(OSError):
                paddle.save({"w": 2}, p)
        assert paddle.load(p)["w"] == 1  # old bytes untouched
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]

    def test_killed_first_save_leaves_nothing(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        with fi.inject("io.save"):
            with pytest.raises(OSError):
                paddle.save({"w": 2}, p)
        assert not os.path.exists(p)

    def test_transient_oserror_is_retried(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        with fi.inject("io.save", max_fires=1, exc=OSError) as inj:
            paddle.save({"w": 7}, p)
        assert inj.fires == 1 and inj.calls == 2  # failed once, then landed
        assert paddle.load(p)["w"] == 7

    def test_retry_budget_flag(self, tmp_path):
        paddle.set_flags({"FLAGS_ckpt_save_retries": 0})
        p = str(tmp_path / "m.pdparams")
        with fi.inject("io.save", exc=OSError) as inj:
            with pytest.raises(OSError):
                paddle.save({"w": 7}, p)
        assert inj.calls == 1  # no retries at budget 0

    def test_missing_file_names_path(self, tmp_path):
        p = str(tmp_path / "nope.pdparams")
        with pytest.raises(FileNotFoundError, match="nope.pdparams"):
            paddle.load(p)

    def test_truncated_file_raises_typed_error(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        paddle.save({"w": np.arange(1000)}, p)
        blob = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(blob[: len(blob) // 2])
        with pytest.raises(CheckpointCorruptionError, match="m.pdparams"):
            paddle.load(p)

    def test_garbage_pickle_raises_typed_error(self, tmp_path):
        p = str(tmp_path / "m.pdparams")
        with open(p, "wb") as f:
            f.write(b"not a pickle at all")
        with pytest.raises(CheckpointCorruptionError):
            paddle.load(p)

    def test_roundtrip_still_plain_pickle(self, tmp_path):
        # durability must not change the on-disk format
        p = str(tmp_path / "m.pdparams")
        paddle.save({"a": [1, 2], "b": "x"}, p)
        with open(p, "rb") as f:
            raw = pickle.load(f)
        assert raw["a"] == [1, 2] and raw["b"] == "x"


# ---------------------------------------------------------------------------
# sharded checkpoint commit protocol
# ---------------------------------------------------------------------------

def _linear_state(seed=0, din=6, dout=3):
    paddle.seed(seed)
    return nn.Linear(din, dout)


class TestCommitProtocol:
    def test_commit_sentinel_written_last(self, tmp_path):
        lin = _linear_state()
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        assert is_committed(str(tmp_path))
        commit = json.load(open(tmp_path / COMMIT_FILE))
        assert commit["version"] == 3 and commit["world_size"] == 1

    def test_fragments_carry_crc(self, tmp_path):
        lin = _linear_state()
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        frag = json.load(open(tmp_path / "rank0.meta.json"))
        for info in frag["state"].values():
            assert all("crc32" in sh for sh in info["shards"])

    def test_torn_save_has_no_commit_and_load_raises(self, tmp_path):
        lin = _linear_state()
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                dist.save_state_dict(lin.state_dict(), str(tmp_path))
        assert not is_committed(str(tmp_path))

    def test_resave_retracts_commit_first(self, tmp_path):
        lin = _linear_state()
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                dist.save_state_dict(lin.state_dict(), str(tmp_path))
        # the overwriting save died mid-write: the directory must not still
        # claim the previous COMMIT
        assert not is_committed(str(tmp_path))
        with pytest.raises(CheckpointCorruptionError, match="COMMIT"):
            dist.load_state_dict(lin.state_dict(), str(tmp_path))

    def test_corrupt_shard_byte_raises(self, tmp_path):
        lin = _linear_state()
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        _flip_shard_byte(str(tmp_path / "rank0.npz"))
        with pytest.raises(CheckpointCorruptionError):
            dist.load_state_dict(lin.state_dict(), str(tmp_path))
        with pytest.raises(CheckpointCorruptionError):
            verify_checkpoint(str(tmp_path))

    def test_verify_passes_on_healthy_checkpoint(self, tmp_path):
        lin = _linear_state()
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        meta = verify_checkpoint(str(tmp_path))
        assert set(lin.state_dict()) <= set(meta["state"])

    def test_missing_dir_raises_file_not_found(self, tmp_path):
        lin = _linear_state()
        with pytest.raises(FileNotFoundError, match="latest_valid_step"):
            dist.load_state_dict(lin.state_dict(), str(tmp_path / "absent"))

    def test_committed_roundtrip_bit_exact(self, tmp_path):
        lin = _linear_state(seed=3)
        want = {k: np.asarray(v._data).copy()
                for k, v in lin.state_dict().items()}
        dist.save_state_dict(lin.state_dict(), str(tmp_path))
        fresh = _linear_state(seed=9)
        dist.load_state_dict(fresh.state_dict(), str(tmp_path))
        for k, v in fresh.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._data), want[k])


# ---------------------------------------------------------------------------
# CheckpointManager lifecycle
# ---------------------------------------------------------------------------

def _training_stack(seed=0):
    paddle.seed(seed)
    model = nn.Linear(5, 2)
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-2)
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0)
    return model, opt, scaler


def _train_steps(model, opt, n, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        x = paddle.to_tensor(rng.randn(4, 5).astype("float32"))
        loss = (model(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()


class TestCheckpointManager:
    def test_latest_valid_skips_torn_save_and_resumes_bit_exact(
            self, tmp_path):
        model, opt, scaler = _training_stack()
        _train_steps(model, opt, 2)
        scaler._scale = 512.0
        scaler._good_steps = 7
        mgr = CheckpointManager(str(tmp_path), keep_last_n=3)
        mgr.save(10, model=model, optimizer=opt, scaler=scaler)

        snap_params = {k: np.asarray(v._data).copy()
                       for k, v in model.state_dict().items()}
        snap_opt = {k: (np.asarray(v._data).copy()
                        if hasattr(v, "_data") else v)
                    for k, v in opt.state_dict().items()}

        # train on, then a save killed mid-shard-write at step 20
        _train_steps(model, opt, 2, seed=1)
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                mgr.save(20, model=model, optimizer=opt, scaler=scaler)

        assert mgr.latest_valid_step() == 10  # torn step_20 is invisible
        assert 20 in mgr.steps() and not is_committed(mgr.step_dir(20))

        # perturb live state, then auto-resume must restore all three
        _train_steps(model, opt, 1, seed=2)
        scaler._scale = 2.0
        scaler._good_steps = 0
        step = mgr.auto_resume(model=model, optimizer=opt, scaler=scaler)
        assert step == 10
        for k, v in model.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._data),
                                          snap_params[k])
        got_opt = opt.state_dict()
        for k, v in snap_opt.items():
            got = got_opt[k]
            got = np.asarray(got._data) if hasattr(got, "_data") else got
            np.testing.assert_array_equal(got, v)
        assert scaler._scale == 512.0 and scaler._good_steps == 7

    def test_auto_resume_cold_start_returns_none(self, tmp_path):
        model, opt, scaler = _training_stack()
        mgr = CheckpointManager(str(tmp_path))
        before = {k: np.asarray(v._data).copy()
                  for k, v in model.state_dict().items()}
        assert mgr.auto_resume(model=model, optimizer=opt,
                               scaler=scaler) is None
        for k, v in model.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._data), before[k])

    def test_retention_keeps_last_n_and_sweeps_torn(self, tmp_path):
        model, opt, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path), keep_last_n=2)
        for s in (1, 2, 3):
            mgr.save(s, model=model)
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                mgr.save(4, model=model)
        mgr.save(5, model=model)  # drains + retention sweeps torn step_4
        assert mgr.steps() == [3, 5]
        assert mgr.latest_valid_step() == 5

    def test_retention_never_deletes_newest_committed(self, tmp_path):
        with pytest.raises(ValueError):
            CheckpointManager(str(tmp_path), keep_last_n=0)
        model, _, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(1, model=model)
        mgr.save(2, model=model)
        assert mgr.committed_steps() == [2]

    def test_resave_of_committed_step_quarantines_not_deletes(self,
                                                              tmp_path):
        model, _, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path), keep_last_n=2)
        mgr.save(1, model=model)
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                mgr.save(1, model=model)  # overwrite dies mid-write
        # the previously committed bytes were moved aside, not destroyed
        quarantined = [e for e in os.listdir(tmp_path) if ".replaced." in e]
        assert len(quarantined) == 1
        assert is_committed(str(tmp_path / quarantined[0]))
        # a later successful save sweeps the quarantine
        mgr.save(2, model=model)
        assert not [e for e in os.listdir(tmp_path) if ".replaced." in e]
        assert mgr.latest_valid_step() == 2

    def test_crash_mid_resave_recovers_quarantined_checkpoint(
            self, tmp_path):
        model, _, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(1, model=model)
        with fi.inject("ckpt.shard_write"):
            with pytest.raises(OSError):
                mgr.save(1, model=model)  # re-save dies mid-write
        # "restart": a fresh manager must find the quarantined committed
        # copy, restore it over the torn re-save, and resume from it
        mgr2 = CheckpointManager(str(tmp_path), keep_last_n=1)
        assert mgr2.latest_valid_step() == 1
        assert is_committed(mgr2.step_dir(1))
        assert not [e for e in os.listdir(tmp_path) if ".replaced." in e]

    def test_async_save_defers_retention_until_landed(self, tmp_path):
        model, _, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1,
                                async_save=True)
        mgr.save(1, model=model)
        mgr.wait()
        handle = mgr.save(2, model=model)
        assert handle is not None
        mgr.wait()  # lands the write, then retention prunes step_1
        assert mgr.committed_steps() == [2]
        assert mgr.latest_valid_step() == 2

    def test_fused_step_composes_with_auto_resume(self, tmp_path):
        def stack():
            paddle.seed(7)
            model = nn.Linear(4, 1)
            opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                        learning_rate=1e-2)
            step = paddle.incubate.fused_train_step(
                model, opt, loss_fn=lambda o: (o ** 2).mean())
            return model, step

        x = np.random.RandomState(0).randn(8, 4).astype("float32")
        model, step = stack()
        for _ in range(3):
            step(x)
        mgr = CheckpointManager(str(tmp_path))
        # the fused step owns the moments/step-count while it trains:
        # checkpoint it as the optimizer-state object
        mgr.save(3, model=model, optimizer=step)
        step(x)
        w_after_4 = np.asarray(model.weight._data).copy()

        # resume in the SAME stack: restored weights must not be clobbered
        # by the step's stale internal copies on the next dispatch
        assert mgr.auto_resume(model=model, optimizer=step) == 3
        step(x)
        np.testing.assert_array_equal(np.asarray(model.weight._data),
                                      w_after_4)

        # resume in a FRESH stack (restart): bit-exact continuation
        model2, step2 = stack()
        assert mgr.auto_resume(model=model2, optimizer=step2) == 3
        step2(x)
        np.testing.assert_array_equal(np.asarray(model2.weight._data),
                                      w_after_4)

    def test_latest_valid_verify_walks_past_corruption(self, tmp_path):
        model, _, _ = _training_stack()
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, model=model)
        mgr.save(2, model=model)
        _flip_shard_byte(os.path.join(mgr.step_dir(2), "rank0.npz"))
        assert mgr.latest_valid_step() == 2        # shallow: committed
        assert mgr.latest_valid_step(verify=True) == 1  # deep: CRC fails


# ---------------------------------------------------------------------------
# step anomaly guard (FusedTrainStep + GradScaler)
# ---------------------------------------------------------------------------

def _fused_stack(scaler=None):
    paddle.seed(7)
    model = nn.Linear(4, 1)
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-2)
    step = paddle.incubate.fused_train_step(
        model, opt, loss_fn=lambda o: (o ** 2).mean(), grad_scaler=scaler)
    x = np.random.RandomState(0).randn(8, 4).astype("float32")
    return model, step, x


class TestStepGuard:
    def test_skip_discards_exactly_the_poisoned_step(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "skip"})
        scaler = paddle.amp.GradScaler(init_loss_scaling=4096.0)
        model, step, x = _fused_stack(scaler)
        step(x)
        w = np.asarray(model.weight._data).copy()
        scale_before = scaler._scale
        with fi.inject("train.grad_nan"):
            loss = step(x)
        assert not np.isfinite(float(loss))
        np.testing.assert_array_equal(np.asarray(model.weight._data), w)
        stats = step.guard_stats()
        assert stats["skipped"] == 1 and stats["consecutive_skips"] == 1
        assert scaler._scale == scale_before * 0.5  # backoff fired
        # next clean step trains normally and resets the streak
        step(x)
        assert step.guard_stats()["consecutive_skips"] == 0
        assert step.guard_stats()["skipped"] == 1
        assert not np.array_equal(np.asarray(model.weight._data), w)

    def test_raise_raises_on_the_same_step_with_params_intact(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "raise"})
        model, step, x = _fused_stack()
        step(x)
        w = np.asarray(model.weight._data).copy()
        with fi.inject("train.grad_nan"):
            with pytest.raises(FloatingPointError):
                step(x)
        np.testing.assert_array_equal(np.asarray(model.weight._data), w)
        assert step.guard_stats()["skipped"] == 1

    def test_warn_warns_but_does_not_skip(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "warn"})
        model, step, x = _fused_stack()
        step(x)
        with fi.inject("train.grad_nan"):
            with pytest.warns(UserWarning, match="non-finite"):
                step(x)
        stats = step.guard_stats()
        assert stats["warned"] == 1 and stats["skipped"] == 0

    def test_guard_off_means_no_counters(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "none"})
        model, step, x = _fused_stack()
        with fi.inject("train.grad_nan"):
            step(x)
        assert step.guard_stats()["skipped"] == 0

    def test_disabled_scaler_behaves_like_no_scaler(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "none"})
        scaler = paddle.amp.GradScaler(enable=False)
        model, step, x = _fused_stack(scaler)
        with fi.inject("train.grad_nan"):
            step(x)
        # no silent skip semantics: the guard stayed off, nothing counted
        assert step.guard_stats()["skipped"] == 0
        assert scaler._scale == 2.0 ** 15  # untouched

    def test_every_n_poisons_only_matching_steps(self):
        paddle.set_flags({"FLAGS_check_nan_inf_action": "skip"})
        model, step, x = _fused_stack()
        with fi.inject("train.grad_nan", every_n=3):
            for _ in range(6):
                step(x)
        assert step.guard_stats()["skipped"] == 2  # steps 3 and 6

    def test_action_flag_validates(self):
        with pytest.raises(ValueError):
            paddle.set_flags({"FLAGS_check_nan_inf_action": "explode"})
        paddle.set_flags({"FLAGS_check_nan_inf_action": "none"})


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

class TestAmpScalerRoundTrip:
    def test_full_schedule_survives(self):
        src = paddle.amp.AmpScaler(
            init_loss_scaling=128.0, incr_ratio=3.0, decr_ratio=0.25,
            incr_every_n_steps=50, decr_every_n_nan_or_inf=4,
            use_dynamic_loss_scaling=False)
        src._good_steps, src._bad_steps = 11, 2
        dst = paddle.amp.AmpScaler()
        dst.load_state_dict(src.state_dict())
        assert dst._scale == 128.0
        assert dst._incr_ratio == 3.0 and dst._decr_ratio == 0.25
        assert dst._incr_every_n_steps == 50
        assert dst._decr_every_n_nan_or_inf == 4
        assert dst._use_dynamic is False
        assert dst._good_steps == 11 and dst._bad_steps == 2


class TestElasticTTL:
    def test_memory_store_expires_dead_host(self, monkeypatch):
        from paddle_tpu.distributed.fleet.elastic import MemoryStore

        store = MemoryStore()
        now = [1000.0]
        monkeypatch.setattr("time.time", lambda: now[0])
        store.register("a", ttl=10)
        store.register("b")  # no ttl: never expires
        assert store.hosts() == ["a", "b"]
        now[0] += 11
        assert store.hosts() == ["b"]
        store.register("a", ttl=10)  # re-register revives the lease
        assert store.hosts() == ["a", "b"]

    def test_file_store_prunes_on_read(self, tmp_path, monkeypatch):
        from paddle_tpu.distributed.fleet.elastic import FileStore

        now = [1000.0]
        monkeypatch.setattr("time.time", lambda: now[0])
        store = FileStore(str(tmp_path / "hosts.json"))
        store.register("a", ttl=5)
        store.register("b", ttl=50)
        now[0] += 10
        assert store.hosts() == ["b"]
        # pruned on disk too, not just in the returned view
        raw = json.load(open(tmp_path / "hosts.json"))
        assert set(raw) == {"b"}

    def test_manager_surfaces_expiry_as_membership_change(self, monkeypatch):
        from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                          ElasticStatus,
                                                          MemoryStore)

        now = [1000.0]
        monkeypatch.setattr("time.time", lambda: now[0])
        store = MemoryStore()
        mgr = ElasticManager("2", host="h1", store=store, host_ttl=10)
        mgr.register()
        store.register("h2", ttl=10)
        assert mgr.ready()
        assert mgr.watch() == ElasticStatus.HOLD
        now[0] += 5
        mgr.heartbeat()  # h1 renews its lease; h2 goes silent
        now[0] += 6
        # h2's lease expired -> membership shrank below np -> HOLD (FT mode
        # waits for the host to come back or be replaced)
        assert mgr.hosts() == ["h1"]
        assert mgr.watch() == ElasticStatus.HOLD
        store.register("h3", ttl=10)  # replacement arrives
        assert mgr.watch() == ElasticStatus.RESTART


class TestLocalFSRetry:
    def test_rename_retries_transient_failure(self, tmp_path):
        from paddle_tpu.distributed.fleet.utils.fs import LocalFS

        fs = LocalFS()
        src, dst = str(tmp_path / "a"), str(tmp_path / "b")
        open(src, "w").write("x")
        with fi.inject("fs.rename", max_fires=1, exc=OSError) as inj:
            fs.rename(src, dst)
        assert inj.calls == 2 and os.path.exists(dst)

    def test_rename_exhausts_budget_and_raises(self, tmp_path):
        from paddle_tpu.distributed.fleet.utils.fs import LocalFS

        fs = LocalFS()
        src, dst = str(tmp_path / "a"), str(tmp_path / "b")
        open(src, "w").write("x")
        with fi.inject("fs.rename", exc=OSError) as inj:
            with pytest.raises(OSError):
                fs.rename(src, dst)
        assert inj.calls == 3  # 1 try + FLAGS_ckpt_save_retries(=2) retries
        assert os.path.exists(src) and not os.path.exists(dst)


class _SaveCounter:
    """Minimal hapi-model stand-in: save(prefix) writes prefix.pdparams."""

    def save(self, path, training=True):
        paddle.save({"w": 1}, path + ".pdparams")


class TestModelCheckpointKeepLastN:
    def test_epoch_saves_are_committed_and_pruned(self, tmp_path):
        from paddle_tpu.hapi.callbacks import ModelCheckpoint

        cb = ModelCheckpoint(save_freq=1, save_dir=str(tmp_path),
                             keep_last_n=2)
        cb.set_model(_SaveCounter())
        for epoch in range(4):
            cb.on_epoch_end(epoch)
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.committed_steps() == [2, 3]
        assert os.path.exists(
            os.path.join(mgr.step_dir(3), "model.pdparams"))

    def test_writer_only_step_survives_deep_verify(self, tmp_path):
        from paddle_tpu.hapi.callbacks import ModelCheckpoint

        cb = ModelCheckpoint(save_freq=1, save_dir=str(tmp_path),
                             keep_last_n=2)
        cb.set_model(_SaveCounter())
        cb.on_epoch_end(0)
        mgr = CheckpointManager(str(tmp_path))
        assert mgr.latest_valid_step(verify=True) == 0
        verify_checkpoint(mgr.step_dir(0))

    def test_default_path_unchanged_but_atomic(self, tmp_path):
        from paddle_tpu.hapi.callbacks import ModelCheckpoint

        cb = ModelCheckpoint(save_freq=1, save_dir=str(tmp_path))
        cb.set_model(_SaveCounter())
        cb.on_epoch_end(0)
        assert os.path.exists(tmp_path / "0.pdparams")


class TestInjectorSemantics:
    def test_unarmed_sites_are_free(self):
        assert fi.should_fire("train.grad_nan") is False
        fi.fire("io.save")  # no-op, no raise

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError):
            with fi.inject("no.such.site"):
                pass

    def test_seeded_prob_is_deterministic(self):
        def run():
            hits = []
            with fi.inject("train.grad_nan", prob=0.5, seed=42):
                hits = [fi.should_fire("train.grad_nan")
                        for _ in range(20)]
            return hits

        assert run() == run()

    def test_nested_injection_restores_outer(self):
        with fi.inject("io.save", exc=ValueError):
            with fi.inject("io.save", max_fires=0):
                fi.fire("io.save")  # inner injector: never fires
            with pytest.raises(ValueError):
                fi.fire("io.save")  # outer restored


# ---------------------------------------------------------------------------
# kill -9 durability: a REAL SIGKILL mid-CheckpointManager.save
# ---------------------------------------------------------------------------

import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one script, three phases: seed a committed step-1 checkpoint (+ a side
# dump of its exact bytes), SIGKILL ourselves mid-save of step 2 at an
# injected fault site, then verify the lifecycle recovered.
KILL9_SCRIPT = '''
import os, signal, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.utils import fault_injection as fi

root, mode, site = {root!r}, sys.argv[1], sys.argv[2]
paddle.seed(7)
model = nn.Linear(4, 3)
opt = paddle.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
mgr = paddle.CheckpointManager(root, keep_last_n=None)


def perturb():
    # deterministic change so step-2 state differs from step-1
    for t in model.parameters():
        t.set_value(t.numpy() + 1.0)


def side_dump(name):
    np.savez(os.path.join(root, name),
             **{{n: np.asarray(t.numpy())
                for n, t in model.state_dict().items()}})


if mode == "seed":
    mgr.save(1, model=model, optimizer=opt)
    side_dump("side1.npz")
elif mode == "kill":
    assert mgr.auto_resume(model, opt) == 1
    perturb()

    class Killer(BaseException):
        def __init__(self, *a):
            os.kill(os.getpid(), signal.SIGKILL)

    with fi.inject(site, exc=Killer):
        mgr.save(2, model=model, optimizer=opt)
    raise SystemExit(99)  # unreachable: the save must have died
elif mode == "resave":
    assert mgr.auto_resume(model, opt) == 1
    perturb()
    mgr.save(2, model=model, optimizer=opt)
    side_dump("side2.npz")
elif mode == "verify":
    expect_step, side = int(sys.argv[3]), sys.argv[4]
    step = mgr.auto_resume(model, opt)
    assert step == expect_step, (step, expect_step)
    ref = np.load(os.path.join(root, side))
    for n, t in model.state_dict().items():
        got = np.asarray(t.numpy())
        assert np.array_equal(got, ref[n]), n
    print("VERIFIED", step)
'''


@pytest.mark.slow
class TestKillNineDurability:
    def _run(self, root, *argv):
        script = os.path.join(root, "kill9.py")
        if not os.path.exists(script):
            with open(script, "w") as f:
                f.write(KILL9_SCRIPT.format(repo=REPO, root=root))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run([sys.executable, script, *argv], env=env,
                              capture_output=True, text=True, timeout=180)

    def test_sigkill_mid_save_never_regresses_latest_valid_step(
            self, tmp_path):
        root = str(tmp_path)
        r = self._run(root, "seed", "-")
        assert r.returncode == 0, r.stderr[-2000:]

        for site in ("io.save", "ckpt.shard_write"):
            r = self._run(root, "kill", site)
            # the writer died to a REAL SIGKILL mid-save...
            assert r.returncode == -signal.SIGKILL, (site, r.returncode,
                                                     r.stderr[-1500:])
            # ...and a fresh process still resumes step 1 bit-exactly
            r = self._run(root, "verify", site, "1", "side1.npz")
            assert r.returncode == 0, (site, r.stderr[-2000:])
            assert "VERIFIED 1" in r.stdout

    def test_post_kill_resave_moves_forward_bit_exactly(self, tmp_path):
        root = str(tmp_path)
        assert self._run(root, "seed", "-").returncode == 0
        assert self._run(root, "kill", "io.save").returncode == \
            -signal.SIGKILL
        # recovery is not just "don't regress": the next healthy save
        # advances the lifecycle and restores bit-exactly
        r = self._run(root, "resave", "-")
        assert r.returncode == 0, r.stderr[-2000:]
        r = self._run(root, "verify", "-", "2", "side2.npz")
        assert r.returncode == 0, r.stderr[-2000:]
        assert "VERIFIED 2" in r.stdout
