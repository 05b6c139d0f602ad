"""Fault-tolerant streaming data plane suite (ISSUE 13).

Fast tier: record/shard format, manifest fingerprints, sharded-by-rank
iteration, retry/typed-error behavior over a flaky FS (seeded fault
injection), corruption quarantine under the per-epoch skip budget,
bit-exact mid-epoch resume through the sampler-state protocol +
CheckpointManager, elastic world-size rebalance, DevicePrefetcher
lifecycle under reader exceptions, and the LocalFS/HDFSClient parity +
atomic upload/download satellites. Slow tier: the chaos stream drill
(kill/preempt over a slow+flaky stream, corrupt-shard quarantine arm)
and the device-utilization acceptance A/B.
"""

import os
import shutil
import struct
import subprocess
import sys
import threading
import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.io as io
import paddle_tpu.nn as nn
from paddle_tpu.distributed.fleet.utils.fs import (
    ExecuteError, HDFSClient, LocalFS)
from paddle_tpu.incubate.fused_train_step import FusedTrainStep
from paddle_tpu.io.streaming import (
    _C_BYTES, _C_QUARANTINED, _C_RECORDS, _C_RETRIES, MAGIC, ShardManifest,
    StreamCorruptionError, StreamingDataset, StreamReadError,
    rebalance_states)
from paddle_tpu.utils import fault_injection as fi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_shards(root, n_shards=4, per_shard=5, feats=4, seed=0,
                lengths=None):
    """Deterministic shard set; returns the flat expected sample list in
    stream order (shard-major)."""
    os.makedirs(str(root), exist_ok=True)
    rng = np.random.RandomState(seed)
    flat = []
    for s in range(n_shards):
        recs = []
        for r in range(per_shard):
            n = feats if lengths is None else int(lengths[s * per_shard + r])
            x = rng.randn(n).astype("float32") if lengths is None \
                else rng.randn(n, feats).astype("float32")
            y = np.float32(rng.randn())
            recs.append((x, y))
            flat.append((x, y))
        io.write_stream_shard(
            os.path.join(str(root), f"shard-{s:02d}.pdstream"), recs)
    return flat


def batch_rows(batches):
    return [tuple(np.asarray(row)) for b in batches
            for row in np.asarray(b[0])]


# ---------------------------------------------------------------------------
# record / shard format
# ---------------------------------------------------------------------------

class TestRecordFormat:
    def test_pack_unpack_roundtrip(self):
        x = np.arange(12, dtype="float32").reshape(3, 4)
        y = np.float32(7.5)
        out = io.unpack_arrays(io.pack_arrays(x, y))
        assert len(out) == 2
        np.testing.assert_array_equal(out[0], x)
        np.testing.assert_array_equal(out[1], y)

    def test_write_read_shard(self, tmp_path):
        recs = [(np.full(3, i, "float32"), np.float32(i)) for i in range(9)]
        p = str(tmp_path / "a.pdstream")
        assert io.write_stream_shard(p, recs) == 9
        back = io.read_stream_shard(p)
        assert len(back) == 9
        for i, (x, y) in enumerate(back):
            np.testing.assert_array_equal(x, recs[i][0])
        with open(p, "rb") as f:
            assert f.read(len(MAGIC)) == MAGIC

    def test_shard_write_is_atomic(self, tmp_path):
        """A writer that dies mid-stream leaves NO shard visible (tmp is
        cleaned), and never clobbers a previous complete shard."""
        p = str(tmp_path / "a.pdstream")
        io.write_stream_shard(p, [(np.zeros(2, "float32"), np.float32(0))])
        old = open(p, "rb").read()

        def dying():
            yield (np.ones(2, "float32"), np.float32(1))
            raise RuntimeError("killed mid-write")

        with pytest.raises(RuntimeError):
            io.write_stream_shard(p, dying())
        assert open(p, "rb").read() == old
        assert [f for f in os.listdir(tmp_path) if "tmp" in f] == []

    def test_read_stream_shard_raises_on_corruption(self, tmp_path):
        p = str(tmp_path / "a.pdstream")
        io.write_stream_shard(p, [(np.zeros(4, "float32"), np.float32(0))])
        raw = bytearray(open(p, "rb").read())
        raw[len(MAGIC) + 8 + 2] ^= 0xFF
        open(p, "wb").write(bytes(raw))
        with pytest.raises(StreamCorruptionError):
            io.read_stream_shard(p)


class TestManifest:
    def test_build_is_sorted_and_filtered(self, tmp_path):
        for name in ("b.pdstream", "a.pdstream", "c.pdstream", "x.txt"):
            (tmp_path / name).write_bytes(MAGIC)
        m = ShardManifest.build(str(tmp_path))
        assert [os.path.basename(p) for p in m.paths] == \
            ["a.pdstream", "b.pdstream", "c.pdstream"]

    def test_fingerprint_tracks_membership(self, tmp_path):
        make_shards(tmp_path, n_shards=3)
        m1 = ShardManifest.build(str(tmp_path))
        (tmp_path / "shard-99.pdstream").write_bytes(MAGIC)
        m2 = ShardManifest.build(str(tmp_path))
        assert m1.fingerprint() != m2.fingerprint()
        assert m1.fingerprint().startswith("3:")

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            ShardManifest.build(str(tmp_path))


# ---------------------------------------------------------------------------
# FS satellites: deterministic listings, atomic copies, parity
# ---------------------------------------------------------------------------

class _FakeHadoopFS(HDFSClient):
    """HDFSClient test double: the exact CLI surface, backed by the local
    filesystem instead of a hadoop install — so LocalFS and the
    HDFSClient *shape* can be parity-tested without a cluster."""

    def __init__(self):
        self._base_cmd = ["hadoop", "fs"]
        self._time_out = 1000

    def _run(self, *args):
        op, rest = args[0], list(args[1:])
        if op == "-ls":
            p = rest[0]
            if not os.path.exists(p):
                raise ExecuteError(f"ls: {p}: No such file or directory")
            lines = []
            for e in os.listdir(p):
                full = os.path.join(p, e)
                kind = "d" if os.path.isdir(full) else "-"
                lines.append(f"{kind}rwxr-xr-x - u g 0 2024-01-01 "
                             f"00:00 {full}")
            return "\n".join(lines)
        if op == "-test":
            flag, p = rest
            ok = {"-e": os.path.exists, "-d": os.path.isdir}[flag](p)
            if not ok:
                raise ExecuteError(f"test {flag} {p} failed")
            return ""
        if op == "-mkdir":
            os.makedirs(rest[-1], exist_ok=True)
            return ""
        if op == "-put":
            force = rest[0] == "-f"
            src, dst = rest[-2], rest[-1]
            if os.path.exists(dst) and not force:
                raise ExecuteError(f"put: {dst}: File exists")
            shutil.copy(src, dst)
            return ""
        if op == "-get":
            shutil.copy(rest[-2], rest[-1])
            return ""
        if op == "-rm":
            p = rest[-1]
            if os.path.isdir(p):
                shutil.rmtree(p)
            else:
                os.remove(p)
            return ""
        if op == "-mv":
            os.rename(rest[0], rest[1])
            return ""
        if op == "-touchz":
            open(rest[0], "a").close()
            return ""
        raise ExecuteError(f"unknown op {op}")


class TestFSSatellites:
    def _populate(self, root):
        os.makedirs(root)
        # scrambled creation order: the listing must sort, not inherit
        for name in ("c.txt", "a.txt", "b.txt"):
            open(os.path.join(root, name), "w").write(name)
        for name in ("zdir", "xdir", "ydir"):
            os.makedirs(os.path.join(root, name))

    def test_localfs_listings_sorted(self, tmp_path):
        root = str(tmp_path / "r")
        self._populate(root)
        fs = LocalFS()
        dirs, files = fs.ls_dir(root)
        assert files == ["a.txt", "b.txt", "c.txt"]
        assert dirs == ["xdir", "ydir", "zdir"]
        assert fs.list_dirs(root) == ["xdir", "ydir", "zdir"]

    def test_fs_parity_local_vs_hdfs_shape(self, tmp_path):
        """The FS-parity satellite: LocalFS and the HDFSClient double
        must agree on listings (sorted), existence probes, mkdir/touch/
        upload/download/mv/delete semantics."""
        roots = {}
        for key, fs in (("local", LocalFS()), ("hdfs", _FakeHadoopFS())):
            root = str(tmp_path / key / "r")
            self._populate(root)
            roots[key] = (fs, root)
        results = {}
        for key, (fs, root) in roots.items():
            fs.mkdirs(os.path.join(root, "made", "deep"))
            fs.touch(os.path.join(root, "t.txt"))
            src = os.path.join(str(tmp_path), f"{key}.up")
            open(src, "w").write("payload")
            fs.upload(src, os.path.join(root, "up.bin"))
            down = os.path.join(str(tmp_path), f"{key}.down")
            fs.download(os.path.join(root, "up.bin"), down)
            fs.mv(os.path.join(root, "a.txt"), os.path.join(root, "d.txt"))
            fs.delete(os.path.join(root, "b.txt"))
            results[key] = {
                "ls": fs.ls_dir(root),
                "list_dirs": fs.list_dirs(root),
                "is_file": fs.is_file(os.path.join(root, "c.txt")),
                "is_dir": fs.is_dir(os.path.join(root, "made")),
                "exists_gone": fs.is_exist(os.path.join(root, "b.txt")),
                "downloaded": open(down).read(),
            }
        assert results["local"] == results["hdfs"]
        assert results["local"]["ls"][1] == ["c.txt", "d.txt", "t.txt",
                                             "up.bin"]

    def test_upload_is_atomic_on_death(self, tmp_path, monkeypatch):
        """A copy killed mid-stream must never leave a torn destination:
        the old content survives and no tmp litter remains."""
        from paddle_tpu.utils import retry as retry_mod

        fs = LocalFS()
        src = str(tmp_path / "src")
        dst = str(tmp_path / "dst")
        open(src, "w").write("NEW" * 1000)
        open(dst, "w").write("OLD")

        real = shutil.copyfileobj

        def dying_copy(fsrc, fdst, *a):
            fdst.write(b"torn")
            raise RuntimeError("killed mid-copy")

        monkeypatch.setattr(retry_mod.shutil, "copyfileobj", dying_copy)
        with pytest.raises(RuntimeError):
            fs.upload(src, dst)
        monkeypatch.setattr(retry_mod.shutil, "copyfileobj", real)
        assert open(dst).read() == "OLD"
        assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
        fs.upload(src, dst)
        assert open(dst).read() == "NEW" * 1000

    def test_dir_upload_failed_publish_keeps_old_destination(
            self, tmp_path, monkeypatch):
        """Review fix: a directory copy whose PUBLISH step fails must
        put the quarantined previous tree back — the old destination
        survives any failure, it is deleted only after the new tree
        landed."""
        from paddle_tpu.utils import retry as retry_mod

        fs = LocalFS()
        src = str(tmp_path / "src")
        os.makedirs(src)
        open(os.path.join(src, "f"), "w").write("NEW")
        dst = str(tmp_path / "dst")
        os.makedirs(dst)
        open(os.path.join(dst, "f"), "w").write("OLD")

        real = retry_mod.replace_across_fs

        def dying_publish(a, b):
            raise RuntimeError("publish died")

        monkeypatch.setattr(retry_mod, "replace_across_fs", dying_publish)
        with pytest.raises(RuntimeError):
            fs.upload(src, dst)
        assert open(os.path.join(dst, "f")).read() == "OLD"
        monkeypatch.setattr(retry_mod, "replace_across_fs", real)
        fs.upload(src, dst)
        assert open(os.path.join(dst, "f")).read() == "NEW"
        assert not os.path.exists(dst + ".__atomic_copy_old__")

    def test_dir_copy_crash_window_is_recoverable(self, tmp_path):
        """A copy SIGKILLed between quarantine and publish leaves dst
        absent with the old tree under dst+'.old' — the next atomic_copy
        to the same destination restores it before proceeding."""
        from paddle_tpu.utils.retry import atomic_copy

        src = str(tmp_path / "src")
        os.makedirs(src)
        open(os.path.join(src, "f"), "w").write("NEW")
        dst = str(tmp_path / "dst")
        # simulate the post-crash state: dst gone, old tree quarantined
        os.makedirs(dst + ".__atomic_copy_old__")
        open(os.path.join(dst + ".__atomic_copy_old__", "f"), "w").write("OLD")
        atomic_copy(src, dst)
        assert open(os.path.join(dst, "f")).read() == "NEW"
        assert not os.path.exists(dst + ".__atomic_copy_old__")

    def test_upload_download_directory(self, tmp_path):
        fs = LocalFS()
        src = str(tmp_path / "srcdir")
        os.makedirs(os.path.join(src, "sub"))
        open(os.path.join(src, "a"), "w").write("A")
        open(os.path.join(src, "sub", "b"), "w").write("B")
        dst = str(tmp_path / "dstdir")
        fs.upload(src, dst)
        assert open(os.path.join(dst, "sub", "b")).read() == "B"
        back = str(tmp_path / "backdir")
        fs.download(dst, back)
        assert open(os.path.join(back, "a")).read() == "A"
        # overwrite an existing destination tree atomically
        open(os.path.join(src, "a"), "w").write("A2")
        fs.upload(src, dst)
        assert open(os.path.join(dst, "a")).read() == "A2"

    def test_upload_missing_source_raises(self, tmp_path):
        from paddle_tpu.distributed.fleet.utils.fs import \
            FSFileNotExistsError

        with pytest.raises(FSFileNotExistsError):
            LocalFS().upload(str(tmp_path / "nope"), str(tmp_path / "d"))
        with pytest.raises(FSFileNotExistsError):
            LocalFS().download(str(tmp_path / "nope"), str(tmp_path / "d"))

    def test_touch_atomic_and_guards(self, tmp_path):
        from paddle_tpu.distributed.fleet.utils.fs import FSFileExistsError

        fs = LocalFS()
        p = str(tmp_path / "t")
        fs.touch(p)
        assert fs.is_file(p) and os.path.getsize(p) == 0
        fs.touch(p)  # exist_ok default
        with pytest.raises(FSFileExistsError):
            fs.touch(p, exist_ok=False)


# ---------------------------------------------------------------------------
# iteration & sharding
# ---------------------------------------------------------------------------

class TestStreamingIteration:
    def test_stream_order_and_default_collate(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=3, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                              world_size=1, num_workers=2)
        batches = list(iter(ds))
        assert len(batches) == 3
        assert batch_rows(batches) == [tuple(x) for (x, _y) in flat]
        assert isinstance(batches[0], list)
        assert batches[0][0].shape == (4, 4)
        assert batches[0][1].shape == (4,)

    def test_rank_sharding_partitions_exactly(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=5, per_shard=3)
        seen = []
        for r in range(2):
            ds = StreamingDataset(str(tmp_path), batch_size=3, rank=r,
                                  world_size=2, num_workers=0)
            seen += batch_rows(list(iter(ds)))
        assert sorted(seen) == sorted(tuple(x) for (x, _y) in flat)
        # round-robin over the SORTED manifest: rank 0 owns shards 0,2,4
        ds0 = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                               world_size=2, num_workers=0)
        assert [it[0] for it in ds0.state_dict()["work"]] == [0, 2, 4]

    def test_env_rank_defaults(self, tmp_path, monkeypatch):
        make_shards(tmp_path, n_shards=4, per_shard=1)
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
        ds = StreamingDataset(str(tmp_path), batch_size=1)
        assert [it[0] for it in ds.state_dict()["work"]] == [1, 3]

    def test_drop_last(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=5)  # 10 records
        ds = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                              world_size=1, drop_last=True, num_workers=0)
        assert len(list(iter(ds))) == 2
        ds2 = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                               world_size=1, num_workers=0)
        assert len(list(iter(ds2))) == 3

    def test_bucket_collate(self, tmp_path):
        lengths = np.random.RandomState(3).randint(3, 25, size=8)
        make_shards(tmp_path, n_shards=2, per_shard=4, lengths=lengths)
        ds = StreamingDataset(
            str(tmp_path), batch_size=4, rank=0, world_size=1,
            collate_fn=io.PadToBucket([8, 16, 32], as_tensor=False))
        batches = list(iter(ds))
        assert len(batches) == 2
        for b in batches:
            x, y, mask = b
            assert x.shape[1] in (8, 16, 32)
            assert mask.shape == x.shape[:2]

    def test_remote_fs_cache_keyed_by_full_path(self, tmp_path):
        """Review fix: two remote datasets whose shards share a BASENAME
        must not read each other's download cache."""
        a_flat = make_shards(tmp_path / "jobA", n_shards=2, per_shard=2,
                             seed=1)
        b_flat = make_shards(tmp_path / "jobB", n_shards=2, per_shard=2,
                             seed=2)
        fs = _FakeHadoopFS()
        assert fs.need_upload_download()
        cache = str(tmp_path / "cache")
        rows = {}
        for key, root, flat in (("A", "jobA", a_flat),
                                ("B", "jobB", b_flat)):
            ds = StreamingDataset(str(tmp_path / root), batch_size=2,
                                  rank=0, world_size=1, num_workers=0,
                                  fs=fs, cache_dir=cache)
            rows[key] = batch_rows(list(iter(ds)))
        assert rows["A"] == [tuple(x) for (x, _y) in a_flat]
        assert rows["B"] == [tuple(x) for (x, _y) in b_flat]

    def test_remote_cache_fill_is_atomic(self, tmp_path):
        """Review fix: a download killed midway must not poison the
        cache — the torn bytes never land under the final cache name,
        and the next read re-downloads cleanly."""
        flat = make_shards(tmp_path / "remote", n_shards=1, per_shard=3)

        class TornOnceFS(_FakeHadoopFS):
            def __init__(self):
                super().__init__()
                self.fail_next = True

            def download(self, fs_path, local_path, *a, **k):
                if self.fail_next:
                    self.fail_next = False
                    open(local_path, "wb").write(b"torn")
                    raise ExecuteError("network died mid -get")
                return super().download(fs_path, local_path, *a, **k)

        fs = TornOnceFS()
        cache = str(tmp_path / "cache")
        ds = StreamingDataset(str(tmp_path / "remote"), batch_size=3,
                              rank=0, world_size=1, num_workers=0,
                              fs=fs, cache_dir=cache)
        with pytest.raises(ExecuteError):
            list(iter(ds))
        # no torn file under a final cache name; the retry reads clean
        assert all(".dl." in f or open(os.path.join(cache, f),
                                       "rb").read() != b"torn"
                   for f in os.listdir(cache))
        assert batch_rows(list(iter(ds))) == \
            [tuple(x) for (x, _y) in flat]

    def test_records_and_bytes_metrics(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=3)
        assert _C_RECORDS.name == "io_stream_records_total"
        assert _C_BYTES.name == "io_stream_bytes_total"
        assert _C_RETRIES.name == "io_stream_retries_total"
        assert _C_QUARANTINED.name == "io_records_quarantined_total"
        with StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0) as ds:
            list(iter(ds))
            label = ds._metrics_label
            assert _C_RECORDS.value(instance=label) == 6
            assert _C_BYTES.value(instance=label) > 0
            assert ds.stats()["records"] == 6
        # close() (via the context manager) removed the instance series
        assert _C_RECORDS.value(instance=label) == 0

    def test_validation_errors(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=2)
        with pytest.raises(ValueError):
            StreamingDataset(str(tmp_path), batch_size=0)
        with pytest.raises(ValueError):
            StreamingDataset(str(tmp_path), batch_size=1, rank=2,
                             world_size=2)
        with pytest.raises(ValueError):
            StreamingDataset(str(tmp_path), batch_size=1,
                             max_skips_per_epoch=-1)
        # a world larger than the shard set would leave silent
        # zero-data ranks — typed at construction
        with pytest.raises(ValueError, match="train NOTHING"):
            StreamingDataset(str(tmp_path), batch_size=1, rank=0,
                             world_size=3)


# ---------------------------------------------------------------------------
# flaky filesystem: retries + typed errors
# ---------------------------------------------------------------------------

class TestFlakyFS:
    def test_transient_open_recovers_and_counts(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=3)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0,
                              retry_base_delay_s=0.001)
        with fi.inject("io.stream.open", max_fires=1):
            batches = list(iter(ds))
        assert len(batches) == 2
        assert ds.stats()["retries"] == 1
        assert _C_RETRIES.value(instance=ds._metrics_label) == 1
        ds.close()

    def test_transient_read_recovers(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=2, per_shard=3)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=2,
                              retry_base_delay_s=0.001)
        with fi.inject("io.stream.read", every_n=5):
            batches = list(iter(ds))
        # flakiness is invisible to the data: same records, same order
        assert batch_rows(batches) == [tuple(x) for (x, _y) in flat]
        assert ds.stats()["retries"] >= 1
        ds.close()

    def test_open_budget_exhaustion_is_typed(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0,
                              retry_base_delay_s=0.001)
        with fi.inject("io.stream.open"):
            with pytest.raises(StreamReadError) as ei:
                list(iter(ds))
        assert ei.value.path and "shard-00" in ei.value.path
        assert isinstance(ei.value, paddle.StreamReadError)

    def test_read_budget_exhaustion_is_typed_with_offset(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0,
                              retry_base_delay_s=0.001)
        with fi.inject("io.stream.read"):
            with pytest.raises(StreamReadError) as ei:
                list(iter(ds))
        assert ei.value.offset is not None


# ---------------------------------------------------------------------------
# corruption quarantine
# ---------------------------------------------------------------------------

def _flip_payload_byte(shards_dir, shard="shard-00.pdstream", off=None):
    p = os.path.join(str(shards_dir), shard)
    raw = bytearray(open(p, "rb").read())
    raw[len(MAGIC) + 8 + 2 if off is None else off] ^= 0xFF
    open(p, "wb").write(bytes(raw))


class TestQuarantine:
    def test_default_budget_zero_raises_typed(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=3)
        _flip_payload_byte(tmp_path)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0)
        with pytest.raises(StreamCorruptionError) as ei:
            list(iter(ds))
        assert isinstance(ei.value, paddle.StreamCorruptionError)
        assert ei.value.quarantined
        path, off, reason = ei.value.quarantined[0]
        assert "shard-00" in path and reason == "crc mismatch"

    def test_budget_skips_and_counts(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=2, per_shard=3)
        _flip_payload_byte(tmp_path)
        ds = StreamingDataset(str(tmp_path), batch_size=5, rank=0,
                              world_size=1, num_workers=2,
                              max_skips_per_epoch=1)
        batches = list(iter(ds))
        # 6 records, 1 quarantined -> 5 delivered, record 0 skipped
        assert batch_rows(batches) == [tuple(x) for (x, _y) in flat[1:]]
        assert ds.stats()["quarantined"] == 1
        assert _C_QUARANTINED.value(instance=ds._metrics_label) == 1
        ds.close()

    def test_quarantine_telemetry_idempotent_on_reiteration(self,
                                                           tmp_path):
        """Review fix: read-ahead past a corrupt record, then a reset /
        re-iteration from the committed cursor re-encounters the SAME
        on-disk corruption — counted once, not once per pass."""
        make_shards(tmp_path, n_shards=1, per_shard=4)
        _flip_payload_byte(tmp_path)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=1)
        list(iter(ds))   # read-ahead pass, nothing advanced
        list(iter(ds))   # discarded; replays from the committed cursor
        assert ds.stats()["quarantined"] == 1
        assert len(ds.stats()["quarantine_log"]) == 1
        assert _C_QUARANTINED.value(instance=ds._metrics_label) == 1
        ds.close()

    def test_budget_is_per_epoch(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=4)
        _flip_payload_byte(tmp_path)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=1)
        for epoch in range(2):  # the budget re-arms; epoch 2 passes too
            for _b in iter(ds):
                ds.advance(1)
        assert ds.stats()["quarantined"] == 2

    def test_torn_tail_quarantines_shard_end(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=2, per_shard=3)
        p = os.path.join(str(tmp_path), "shard-00.pdstream")
        raw = open(p, "rb").read()
        open(p, "wb").write(raw[:-5])  # truncate the final record
        ds = StreamingDataset(str(tmp_path), batch_size=6, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=1)
        batches = list(iter(ds))
        rows = batch_rows(batches)
        assert len(rows) == 5
        assert ds.stats()["quarantine_log"][0][2] == "torn record tail"
        ds.close()

    def test_unparseable_length_ends_shard(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=3)
        p = os.path.join(str(tmp_path), "shard-00.pdstream")
        raw = bytearray(open(p, "rb").read())
        # lie in the first frame's length field: no resync is possible
        raw[len(MAGIC):len(MAGIC) + 4] = struct.pack("<I", 0x7FFFFFFF)
        open(p, "wb").write(bytes(raw))
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=1)
        batches = list(iter(ds))
        assert len(batch_rows(batches)) == 3  # shard-01 only
        assert ds.stats()["quarantine_log"][0][2] == "unparseable frame " \
                                                     "length"

    def test_bad_magic_quarantines_whole_shard(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=2)
        p = os.path.join(str(tmp_path), "shard-01.pdstream")
        raw = bytearray(open(p, "rb").read())
        raw[0] ^= 0xFF
        open(p, "wb").write(bytes(raw))
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=1)
        assert len(batch_rows(list(iter(ds)))) == 2
        assert ds.stats()["quarantine_log"][0][2] == "bad shard magic"

    def test_decode_failure_quarantines(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=1, per_shard=4)

        def flaky_decode(payload):
            # deterministic poison: the SECOND record fails to decode
            # (decode runs on the thread pool, so a call counter would
            # race — key off the payload instead)
            out = io.unpack_arrays(payload)
            if np.array_equal(out[0], flat[1][0]):
                raise ValueError("poisoned sample")
            return out

        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=2,
                              decode_fn=flaky_decode, max_skips_per_epoch=1)
        assert len(batch_rows(list(iter(ds)))) == 3
        path, off, reason = ds.stats()["quarantine_log"][0]
        assert "decode failed" in reason
        # the log names the FAILING record's own offset: record 0's
        # frame sits right after the magic, record 1 after it
        first_len = len(io.pack_arrays(*flat[0]))
        assert off == len(MAGIC) + 8 + first_len

    def test_decode_stream_read_error_not_quarantined(self, tmp_path):
        """A decode_fn surfacing StreamReadError (an IO-performing
        tokenizer whose side reads exhausted the retry budget) fails
        typed on BOTH decode paths — an unreadable filesystem must never
        be misclassified as on-disk corruption and skipped past."""
        make_shards(tmp_path, n_shards=1, per_shard=3)

        def io_decode(payload):
            raise StreamReadError("side file unreadable", path="side")

        for workers in (0, 2):
            ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                                  world_size=1, num_workers=workers,
                                  decode_fn=io_decode,
                                  max_skips_per_epoch=100)
            with pytest.raises(StreamReadError):
                list(iter(ds))
            assert ds.stats()["quarantined"] == 0

    def test_corrupt_site_injection(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                              world_size=1, num_workers=0,
                              max_skips_per_epoch=2)
        with fi.inject("io.stream.corrupt", every_n=3):
            rows = batch_rows(list(iter(ds)))
        assert len(rows) == 3
        assert ds.stats()["quarantined"] == 1
        # budget exhaustion through the same site is the typed error
        ds2 = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                               world_size=1, num_workers=0)
        with fi.inject("io.stream.corrupt"):
            with pytest.raises(StreamCorruptionError):
                list(iter(ds2))


# ---------------------------------------------------------------------------
# resumable stream protocol
# ---------------------------------------------------------------------------

class TestResume:
    def test_mid_epoch_resume_bit_exact(self, tmp_path):
        flat = make_shards(tmp_path, n_shards=3, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=2)
        it = iter(ds)
        for _ in range(3):
            next(it)
        ds.advance(3)
        sd = ds.state_dict()
        ds2 = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                               world_size=1, num_workers=0)
        ds2.set_state_dict(sd)
        rest = batch_rows(list(iter(ds2)))
        assert rest == [tuple(x) for (x, _y) in flat[6:]]

    def test_read_ahead_never_moves_cursor(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0)
        it = iter(ds)
        for _ in range(3):       # produced 3, consumed (advanced) only 1
            next(it)
        ds.advance(1)
        sd = ds.state_dict()
        assert sd["batches_consumed"] == 1
        ds2 = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                               world_size=1, num_workers=0)
        ds2.set_state_dict(sd)
        assert len(list(iter(ds2))) == 3  # 8 records: 4 batches, 1 done

    def test_superseded_iterator_cannot_corrupt_cursor(self, tmp_path):
        """Review fix: a stale generator (a prefetcher transfer thread
        outliving a timed-out join) finishing batches AFTER the stream
        was re-opened must not append handoff entries, roll the epoch,
        or mark end-of-epoch — a phantom entry would make advance()
        commit a stale cursor and break bit-exact resume."""
        flat = make_shards(tmp_path, n_shards=2, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0)
        stale = iter(ds)
        next(stale)
        ds.advance(1)
        fresh = iter(ds)             # supersedes `stale`
        records_before = ds.stats()["records"]
        # the stale generator keeps producing (its thread didn't know)
        stale_rows = batch_rows(list(stale))
        assert stale_rows            # it still yields data...
        assert len(ds._produced) == 0  # ...but no phantom handoff entry
        # ...and no phantom DELIVERY telemetry (bytes-read still counts)
        assert ds.stats()["records"] == records_before
        sd = ds.state_dict()
        assert sd["batches_consumed"] == 1 and sd["epoch"] == 0
        # ...but the committed stream is untouched: the fresh pass
        # replays exactly the remaining records
        rest = []
        for b in fresh:
            rest += batch_rows([b])
            ds.advance(1)
        assert rest == [tuple(x) for (x, _y) in flat[2:]]
        assert ds.state_dict()["epoch"] == 1  # only the FRESH pass rolls

    def test_epoch_boundary_advance_rolls(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0)
        for _b in iter(ds):
            ds.advance(1)
        sd = ds.state_dict()
        assert sd["epoch"] == 1 and sd["cursor_k"] == 0
        assert not sd["exhausted"]

    def test_set_epoch_contract(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=4)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1, num_workers=0)
        it = iter(ds)
        next(it)
        ds.advance(1)
        ds.set_epoch(0)  # same epoch: resume keeps its place
        assert ds.state_dict()["batches_consumed"] == 1
        ds.set_epoch(1)  # new epoch: fresh cursor
        sd = ds.state_dict()
        assert sd["epoch"] == 1 and sd["batches_consumed"] == 0

    def test_manifest_fingerprint_gate(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1)
        sd = ds.state_dict()
        ds3 = StreamingDataset(str(tmp_path), batch_size=4, rank=0,
                               world_size=1)
        with pytest.raises(ValueError, match="batch_size"):
            ds3.set_state_dict(sd)
        (tmp_path / "shard-09.pdstream").write_bytes(MAGIC)
        ds2 = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                               world_size=1)
        with pytest.raises(ValueError, match="manifest"):
            ds2.set_state_dict(sd)

    def test_world_size_mismatch_is_typed(self, tmp_path):
        make_shards(tmp_path, n_shards=4, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=2)
        sd = ds.state_dict()
        ds2 = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                               world_size=1)
        with pytest.raises(ValueError, match="set_group_state"):
            ds2.set_state_dict(sd)

    def test_foreign_state_rejected(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2)
        with pytest.raises(ValueError, match="not a StreamingDataset"):
            ds.set_state_dict({"epoch": 0, "cursor": 3})

    def test_resume_replays_quarantine_deterministically(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=3)
        _flip_payload_byte(tmp_path, shard="shard-01.pdstream")

        def run(resume_from=None, stop_after=None):
            ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                                  world_size=1, num_workers=0,
                                  max_skips_per_epoch=1)
            if resume_from is not None:
                ds.set_state_dict(resume_from)
            rows = []
            for i, b in enumerate(iter(ds)):
                rows += batch_rows([b])
                ds.advance(1)
                if stop_after is not None and i + 1 == stop_after:
                    return rows, ds.state_dict(), ds
            return rows, ds.state_dict(), ds

        full, _, _ = run()
        first, sd, _ = run(stop_after=1)
        rest, sd2, ds2 = run(resume_from=sd)
        assert first + rest == full
        # the resumed pass re-quarantined the same on-disk record (and a
        # completed pass rolls into the next epoch's clean budget)
        assert ds2.stats()["quarantined"] == 1
        assert sd2["epoch"] == 1 and sd2["skips"] == 0


class TestRebalance:
    def _consume(self, tmp_path, rank, world, n_batches):
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=rank,
                              world_size=world, num_workers=0)
        it = iter(ds)
        rows = []
        for _ in range(n_batches):
            rows += batch_rows([next(it)])
            ds.advance(1)
        return rows, ds.state_dict()

    @pytest.mark.parametrize("old_world,new_world", [(2, 3), (3, 2),
                                                     (2, 1), (1, 2)])
    def test_rebalance_preserves_remaining_exactly(self, tmp_path,
                                                   old_world, new_world):
        flat = make_shards(tmp_path, n_shards=6, per_shard=3)
        all_rows = [tuple(x) for (x, _y) in flat]
        consumed, states = [], []
        for r in range(old_world):
            rows, sd = self._consume(tmp_path, r, old_world, 2)
            consumed += rows
            states.append(sd)
        remaining = []
        for r in range(new_world):
            ds = StreamingDataset(str(tmp_path), batch_size=2, rank=r,
                                  world_size=new_world, num_workers=0)
            ds.set_group_state(states)
            remaining += batch_rows(list(iter(ds)))
        # every record exactly once across the old consumption + the new
        # world's remainder: nothing lost, nothing replayed
        assert sorted(consumed + remaining) == sorted(all_rows)

    def test_same_world_group_restore_is_bit_exact(self, tmp_path):
        make_shards(tmp_path, n_shards=4, per_shard=3)
        states = []
        for r in range(2):
            _rows, sd = self._consume(tmp_path, r, 2, 1)
            states.append(sd)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=1,
                              world_size=2, num_workers=0)
        ds.set_group_state(states)
        direct = StreamingDataset(str(tmp_path), batch_size=2, rank=1,
                                  world_size=2, num_workers=0)
        direct.set_state_dict(states[1])
        assert batch_rows(list(iter(ds))) == batch_rows(list(iter(direct)))

    def test_rebalance_from_fresh_epoch_cursor(self, tmp_path):
        """A state whose cursor sits at a work-item boundary (fresh
        epoch after a completed pass: cursor_offset=None) re-balances
        to the full shard set, not a crash."""
        flat = make_shards(tmp_path, n_shards=4, per_shard=3)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0)
        for _b in iter(ds):
            ds.advance(1)          # full pass -> rolled, fresh epoch 1
        sd = ds.state_dict()
        assert sd["cursor_offset"] is None
        rows = []
        for r in range(2):
            scaled = StreamingDataset(str(tmp_path), batch_size=3,
                                      rank=r, world_size=2,
                                      num_workers=0)
            scaled.set_group_state([sd])
            rows += batch_rows(list(iter(scaled)))
        assert sorted(rows) == sorted(tuple(x) for (x, _y) in flat)

    def test_group_restore_prefers_own_rank_over_rebalance(self,
                                                           tmp_path):
        """A single rank file recorded under world W restoring into the
        SAME (rank, W) is a private-checkpoint-dir restore, never a
        rebalance; a partial set across a world change is typed."""
        make_shards(tmp_path, n_shards=4, per_shard=3)
        _rows, sd1 = self._consume(tmp_path, 1, 2, 1)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=1,
                              world_size=2, num_workers=0)
        ds.set_group_state([sd1])   # own (rank=1, world=2) state
        assert ds.state_dict() == sd1
        solo = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                                world_size=1, num_workers=0)
        with pytest.raises(ValueError, match="partial set"):
            solo.set_group_state([sd1])

    def test_rebalance_rejects_torn_state_sets(self, tmp_path):
        make_shards(tmp_path, n_shards=4, per_shard=2)
        _r0, sd0 = self._consume(tmp_path, 0, 2, 1)
        sd1 = dict(sd0, rank=1, epoch=sd0["epoch"] + 1)
        with pytest.raises(ValueError, match="epoch"):
            rebalance_states([sd0, sd1], 2)


# ---------------------------------------------------------------------------
# CheckpointManager integration
# ---------------------------------------------------------------------------

class TestManagerIntegration:
    def _train_setup(self, tmp_path, ck):
        paddle.seed(0)
        np.random.seed(0)
        make_shards(tmp_path / "shards", n_shards=3, per_shard=4)
        ds = StreamingDataset(str(tmp_path / "shards"), batch_size=2,
                              rank=0, world_size=1, num_workers=0)
        mgr = paddle.CheckpointManager(str(ck), keep_last_n=2)
        return ds, mgr

    def test_save_auto_resume_roundtrip(self, tmp_path):
        ds, mgr = self._train_setup(tmp_path, tmp_path / "ck")
        it = iter(ds)
        for _ in range(3):
            next(it)
        ds.advance(3)
        mgr.save(3, state_dict={}, sampler=ds)
        ds2 = StreamingDataset(str(tmp_path / "shards"), batch_size=2,
                               rank=0, world_size=1, num_workers=0)
        step = mgr.auto_resume(sampler=ds2)
        assert step == 3
        assert ds2.state_dict() == ds.state_dict()

    def test_rank_files_beat_legacy_and_rebalance(self, tmp_path):
        """Per-rank cursor files (the multi-process save layout) restore
        through set_group_state — including across a WORLD-SIZE CHANGE:
        a 2-rank checkpoint resumed by a 1-rank job re-partitions the
        unconsumed shards instead of replaying rank 0's slice only."""
        from paddle_tpu.framework import io as fio

        make_shards(tmp_path / "shards", n_shards=4, per_shard=3)
        states, consumed = [], []
        for r in range(2):
            ds = StreamingDataset(str(tmp_path / "shards"), batch_size=3,
                                  rank=r, world_size=2, num_workers=0)
            it = iter(ds)
            consumed += batch_rows([next(it)])
            ds.advance(1)
            states.append(ds.state_dict())
        mgr = paddle.CheckpointManager(str(tmp_path / "ck"))
        mgr.save(1, state_dict={})
        d = mgr.step_dir(1)
        for r, sd in enumerate(states):
            fio.save(sd, os.path.join(d, f"sampler.rank{r}.pdsampler"))
        solo = StreamingDataset(str(tmp_path / "shards"), batch_size=3,
                                rank=0, world_size=1, num_workers=0)
        assert mgr.auto_resume(sampler=solo) == 1
        remaining = batch_rows(list(iter(solo)))
        flat = make_shards(tmp_path / "shards2", n_shards=4, per_shard=3)
        assert sorted(consumed + remaining) == \
            sorted(tuple(x) for (x, _y) in flat)

    def test_single_process_checkpoint_scales_up(self, tmp_path):
        """Review fix: single-process saves also write the per-rank
        cursor file, so a world-1 checkpoint restores into a LARGER
        world through set_group_state's re-partition."""
        flat = make_shards(tmp_path / "shards", n_shards=4, per_shard=3)
        ds = StreamingDataset(str(tmp_path / "shards"), batch_size=3,
                              rank=0, world_size=1, num_workers=0)
        it = iter(ds)
        consumed = batch_rows([next(it)])
        ds.advance(1)
        mgr = paddle.CheckpointManager(str(tmp_path / "ck"))
        mgr.save(1, state_dict={}, sampler=ds)
        assert os.path.exists(os.path.join(
            mgr.step_dir(1), "sampler.rank0.pdsampler"))
        remaining = []
        for r in range(2):
            scaled = StreamingDataset(str(tmp_path / "shards"),
                                      batch_size=3, rank=r, world_size=2,
                                      num_workers=0)
            assert mgr.auto_resume(sampler=scaled) == 1
            remaining += batch_rows(list(iter(scaled)))
        assert sorted(consumed + remaining) == \
            sorted(tuple(x) for (x, _y) in flat)

    def test_drive_interrupt_resume_bit_exact(self, tmp_path):
        """The in-process half of the chaos drill: drive N steps, 'crash',
        rebuild everything, auto_resume, finish — per-step losses equal
        an undisturbed run bit-for-bit."""
        def run(ck_dir, cap_first):
            paddle.seed(0)
            np.random.seed(0)
            model = nn.Linear(4, 1)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())

            class WithLoss(nn.Layer):
                def __init__(self):
                    super().__init__()
                    self.inner = model

                def forward(self, x, y):
                    d = self.inner(x)[:, 0] - y
                    return (d * d).mean()

            fstep = FusedTrainStep(WithLoss(), opt)
            ds = StreamingDataset(str(tmp_path / "shards"), batch_size=2,
                                  rank=0, world_size=1, num_workers=2)
            mgr = paddle.CheckpointManager(str(ck_dir), keep_last_n=2)
            mgr.auto_resume(model, fstep, sampler=ds)
            losses = []

            def on_window(win):
                losses.extend(float(x) for x in win["losses"])
                mgr.save(int(fstep.device_metrics()["step_count"]),
                         model=model, optimizer=fstep, sampler=ds)

            for epoch in range(ds.state_dict()["epoch"], 2):
                ds.set_epoch(epoch)
                fstep.drive(ds, steps=cap_first, log_every=2,
                            on_window=on_window, checkpoint=mgr,
                            sampler=ds)
                if cap_first is not None:
                    return losses
            return losses

        make_shards(tmp_path / "shards", n_shards=3, per_shard=4)
        base = run(tmp_path / "ck_base", None)
        first = run(tmp_path / "ck", 4)
        rest = run(tmp_path / "ck", None)
        assert [repr(x) for x in (first + rest)] == \
            [repr(x) for x in base]
        assert len(base) == 12  # 6 batches/epoch x 2 epochs

    def test_hapi_fit_streams(self, tmp_path):
        """hapi wiring: Model.fit consumes a StreamingDataset directly
        (it already yields collated batches) through the prefetcher."""
        paddle.seed(0)
        np.random.seed(0)
        rng = np.random.RandomState(0)
        recs = [(rng.randn(4).astype("float32"),
                 rng.randn(1).astype("float32")) for _ in range(12)]
        io.write_stream_shard(str(tmp_path / "a.pdstream"), recs)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0)
        model = paddle.Model(nn.Linear(4, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=model.parameters())
        model.prepare(opt, nn.MSELoss())
        model.fit(ds, epochs=1, verbose=0)
        # the stream was fully consumed once
        assert ds.stats()["batches"] == 4


# ---------------------------------------------------------------------------
# DevicePrefetcher lifecycle under reader exceptions (satellite)
# ---------------------------------------------------------------------------

class _ReplayableSource:
    """Re-iterable batch source that raises mid-epoch on the FIRST pass
    only (a reader exception: flaky loader, poisoned record)."""

    def __init__(self, batches, fail_at):
        self.batches = batches
        self.fail_at = fail_at
        self.passes = 0

    def __iter__(self):
        self.passes += 1
        this_pass = self.passes
        for i, b in enumerate(self.batches):
            if this_pass == 1 and i == self.fail_at:
                raise RuntimeError("reader died mid-epoch")
            yield b

    def __len__(self):
        return len(self.batches)


class TestPrefetcherLifecycle:
    def _batches(self, n=6):
        rng = np.random.RandomState(0)
        return [[rng.randn(2, 3).astype("float32")] for _ in range(n)]

    def test_reader_exception_propagates_and_close_joins(self):
        from paddle_tpu.io.prefetch import _G_QUEUE_DEPTH, _M_HOST_BLOCKED

        src = _ReplayableSource(self._batches(), fail_at=3)
        pf = io.DevicePrefetcher(src, depth=2, name="lifecycle_test")
        got = []
        with pytest.raises(RuntimeError, match="reader died"):
            for b in pf:
                got.append(b)
        assert len(got) == 3
        before = threading.active_count()
        pf.close()
        # no transfer thread survives close()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("lifecycle_test")]
        assert threading.active_count() <= before
        # close() removed the per-instance registry series
        assert _M_HOST_BLOCKED.count(instance=pf._metrics_label) == 0
        assert pf._metrics_label not in [
            dict(k).get("instance") for k in _G_QUEUE_DEPTH.labels()]

    def test_reiterate_after_failure_no_loss_no_double(self):
        """After a mid-epoch reader exception + close(), a fresh pass
        yields EVERY batch exactly once — nothing staged by the dead
        pass leaks into the new one, nothing is dropped."""
        src = _ReplayableSource(self._batches(), fail_at=2)
        pf = io.DevicePrefetcher(src, depth=2, name="reiter_test")
        with pytest.raises(RuntimeError):
            list(iter(pf))
        pf.close()
        second = list(iter(pf))
        assert len(second) == 6
        for got, want in zip(second, self._batches()):
            np.testing.assert_array_equal(np.asarray(got[0]._data), want[0])
        pf.close()

    def test_streaming_source_resolves_resumable(self, tmp_path):
        make_shards(tmp_path, n_shards=2, per_shard=2)
        ds = StreamingDataset(str(tmp_path), batch_size=2, rank=0,
                              world_size=1)
        pf = io.DevicePrefetcher(ds, name="resolve_test")
        assert io.resolve_resumable(pf) is ds
        pf.close()

    def test_streaming_error_crosses_prefetcher_typed(self, tmp_path):
        make_shards(tmp_path, n_shards=1, per_shard=3)
        _flip_payload_byte(tmp_path)
        ds = StreamingDataset(str(tmp_path), batch_size=3, rank=0,
                              world_size=1, num_workers=0)
        pf = io.DevicePrefetcher(ds, name="typed_err_test")
        with pytest.raises(StreamCorruptionError):
            list(iter(pf))
        pf.close()


# ---------------------------------------------------------------------------
# lint + bench wiring
# ---------------------------------------------------------------------------

class TestToolingWiring:
    def test_stream_sites_registered_and_linted(self):
        for site in ("io.stream.open", "io.stream.read",
                     "io.stream.corrupt"):
            assert site in fi.SITES
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import check_fault_sites as cfs

        assert cfs.find_missing() == []
        assert os.path.join(REPO, "scripts", "bench_streaming.py") in \
            cfs.EXTRA_EXERCISERS

    def test_bench_streaming_record_roundtrip(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import bench_streaming as bst

        recs = bst.make_records(4, 8)
        x, y = bst.decode_record(bst.encode_record(recs[2]), 8, 0.0)
        np.testing.assert_array_equal(x, recs[2][0])
        assert y == recs[2][1]

    def test_bench_has_streaming_workload(self):
        src = open(os.path.join(REPO, "bench.py")).read()
        assert "ingest_stream_device_util_ratio" in src
        assert "ingest_cpu_stream_device_util_ratio" in src
        assert '"streaming": lambda: bench_streaming' in src


# ---------------------------------------------------------------------------
# slow tier: acceptance drills
# ---------------------------------------------------------------------------

def _clean_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
class TestStreamChaosDrill:
    def test_kill_preempt_corrupt_over_flaky_stream(self, tmp_path):
        """The ISSUE-13 acceptance drill: SIGKILL + preemption mid-epoch
        over a slow+flaky sharded stream resume bit-exact on both ranks,
        and the corrupt-shard arm finishes via quarantine."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "chaos_train.py"),
             "--drill", "stream", "--out", str(tmp_path)],
            env=_clean_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "STREAM DRILL PASSED" in r.stdout


@pytest.mark.slow
class TestStreamingUtilAcceptance:
    def test_slow_host_stream_sustains_090x_device_util(self):
        """ROADMAP item 3 acceptance: the slow-host streaming arm holds
        >= 0.9x of the in-memory arm's device utilization at CPU smoke
        scale, losses bit-equal, read off the io_host_blocked_ms
        telemetry."""
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import bench_streaming as bst

        res = bst.run_ab(tiny=True)
        assert res["bit_exact"]
        assert res["util_ratio"] >= 0.9, res
