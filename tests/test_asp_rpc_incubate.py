"""Tests: incubate.asp (2:4 sparsity), distributed.rpc, incubate.autotune,
DistributedFusedLamb.

Reference parity: python/paddle/incubate/asp/ (asp.py:216,302;
utils.py:78,184,326,569), python/paddle/distributed/rpc/rpc.py:73-339,
python/paddle/incubate/autotune.py:24,
python/paddle/incubate/optimizer/distributed_fused_lamb.py:115.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate import asp


class TestAspMasks:
    def test_mask_1d_pattern(self):
        mat = np.arange(16, dtype="float32").reshape(2, 8)
        mask = asp.get_mask_1d(mat, 2, 4)
        assert mask.shape == (2, 8)
        flat = mask.reshape(-1, 4)
        assert (flat.sum(1) == 2).all()
        # keeps the largest two of each group
        assert mask[0, 2] and mask[0, 3] and not mask[0, 0]

    def test_mask_2d_greedy_rows_and_cols(self):
        """Greedy never exceeds n per row/column of a block (the reference
        greedy makes the same <=n guarantee and may underfill — exact n:m
        in both dims needs its enumerated 'best' patterns)."""
        rng = np.random.RandomState(0)
        mat = rng.randn(8, 8).astype("float32")
        mask = asp.get_mask_2d_greedy(mat, 2, 4)
        for bi in range(0, 8, 4):
            for bj in range(0, 8, 4):
                b = mask[bi:bi + 4, bj:bj + 4]
                assert (b.sum(0) <= 2).all() and (b.sum(1) <= 2).all()
                assert b.sum() >= 6  # near-full fill on random data

    def test_calculate_density_and_check(self):
        t = paddle.to_tensor(np.asarray([[1., 0, 2, 0], [0, 3, 0, 4]],
                                        "float32"))
        assert asp.calculate_density(t) == pytest.approx(0.5)
        assert asp.check_sparsity(t, n=2, m=4)


class TestAspWorkflow:
    def test_prune_train_keeps_sparsity(self):
        paddle.seed(7)
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
        asp.prune_model(model, n=2, m=4)
        for name, p in model.named_parameters():
            if "weight" in name:
                assert asp.check_sparsity(p, 2, 4), name
        opt = asp.decorate(paddle.optimizer.SGD(
            learning_rate=0.1, parameters=model.parameters()))
        x = paddle.to_tensor(np.random.randn(8, 16).astype("float32"))
        y = paddle.to_tensor(np.random.randn(8, 8).astype("float32"))
        for _ in range(3):
            loss = nn.MSELoss()(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        # dense SGD would densify; the decorated optimizer must not
        for name, p in model.named_parameters():
            if "weight" in name:
                assert asp.check_sparsity(p, 2, 4), name
        assert asp.calculate_density(model[0].weight) == pytest.approx(0.5)

    def test_excluded_layers(self):
        paddle.seed(8)
        model = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 8))
        asp.set_excluded_layers(["0.weight"])
        try:
            asp.prune_model(model, 2, 4)
            assert not asp.check_sparsity(model[0].weight, 2, 4)
            assert asp.check_sparsity(model[1].weight, 2, 4)
        finally:
            asp.reset_excluded_layers()


def _double(x):
    return x * 2


def _boom():
    raise RuntimeError("remote kaboom")


def _set_quit():
    from paddle_tpu.distributed import rpc

    rpc._QUIT = True
    return "bye"


@pytest.mark.slow
class TestRpc:
    @pytest.fixture()
    def rpc(self):
        from paddle_tpu.distributed import rpc as rpc_mod
        import uuid

        rpc_mod.init_rpc("worker0", rank=0, world_size=1,
                         master_endpoint=f"test:{uuid.uuid4().hex[:8]}")
        yield rpc_mod
        rpc_mod.shutdown()

    def test_sync_roundtrip(self, rpc):
        assert rpc.rpc_sync("worker0", _double, args=(21,)) == 42

    def test_async_future(self, rpc):
        fut = rpc.rpc_async("worker0", _double, args=(5,))
        assert fut.wait() == 10

    def test_remote_exception_reraises(self, rpc):
        with pytest.raises(RuntimeError, match="remote kaboom"):
            rpc.rpc_sync("worker0", _boom)

    def test_worker_infos(self, rpc):
        info = rpc.get_worker_info("worker0")
        assert info.rank == 0 and info.port > 0
        assert [w.name for w in rpc.get_all_worker_infos()] == ["worker0"]
        with pytest.raises(ValueError):
            rpc.get_worker_info("nope")

    def test_two_process_gang(self, tmp_path):
        """A real second process joins the gang and serves calls."""
        import multiprocessing as mp
        import textwrap
        import subprocess
        import sys
        import uuid

        ep = f"gang:{uuid.uuid4().hex[:8]}"
        child_code = textwrap.dedent(f"""
            import sys
            sys.path.insert(0, {repr('/root/repo')})
            sys.path.insert(0, {repr('/root/repo/tests')})
            import os
            os.environ['JAX_PLATFORMS'] = 'cpu'
            from paddle_tpu.distributed import rpc
            rpc.init_rpc('w1', rank=1, world_size=2,
                         master_endpoint={repr(ep)})
            # serve until the parent tells us to quit
            import time
            deadline = time.time() + 20
            while time.time() < deadline and not getattr(
                    rpc, '_QUIT', False):
                time.sleep(0.05)
            rpc.shutdown()
        """)
        proc = subprocess.Popen([sys.executable, "-c", child_code])
        from paddle_tpu.distributed import rpc as rpc_mod

        try:
            rpc_mod.init_rpc("w0", rank=0, world_size=2,
                             master_endpoint=ep)
            assert rpc_mod.rpc_sync("w1", _double, args=(8,),
                                    timeout=15) == 16
            assert rpc_mod.rpc_sync("w1", _set_quit, timeout=15) == "bye"
        finally:
            rpc_mod.shutdown()
            proc.wait(timeout=20)


class TestAutotune:
    def test_set_get_config(self):
        from paddle_tpu.incubate import autotune

        autotune.set_config({"dataloader": {"enable": True,
                                            "num_workers": 2}})
        assert autotune.get_config()["dataloader"]["enable"]
        assert autotune.tuned_num_workers() == 2
        autotune.set_config({"dataloader": {"enable": False}})
        assert autotune.tuned_num_workers() is None
        with pytest.raises(ValueError):
            autotune.set_config({"bogus": {}})

    def test_kernel_section_places_the_compile_cache(self, monkeypatch):
        """autotune's kernel section goes through the one placement
        helper: the checkout's .jax_cache unless the place was chosen from
        outside (tests/test_bucketing.py::TestCompileCachePlacement)."""
        import os

        import jax

        from paddle_tpu.incubate import autotune

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            autotune.set_config({"kernel": {"enable": True}})
            assert jax.config.jax_compilation_cache_dir == os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache")
        finally:
            autotune.set_config({"kernel": {"enable": False}})
            jax.config.update("jax_compilation_cache_dir", before)


class TestDistributedFusedLamb:
    def test_trains_like_lamb(self):
        from paddle_tpu.incubate.optimizer import DistributedFusedLamb

        paddle.seed(10)
        model = nn.Linear(8, 4)
        opt = DistributedFusedLamb(learning_rate=0.05,
                                   parameters=model.parameters())
        x = paddle.to_tensor(np.random.randn(16, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randn(16, 4).astype("float32"))
        losses = []
        for _ in range(10):
            loss = nn.MSELoss()(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(loss.item())
        assert losses[-1] < losses[0]

    def test_gradient_accumulation(self):
        from paddle_tpu.incubate.optimizer import DistributedFusedLamb

        paddle.seed(11)
        model = nn.Linear(4, 2)
        opt = DistributedFusedLamb(learning_rate=0.1,
                                   parameters=model.parameters(),
                                   gradient_accumulation_steps=2)
        w0 = model.weight.numpy().copy()
        x = paddle.to_tensor(np.ones((2, 4), "float32"))
        loss = model(x).sum()
        loss.backward()
        opt.step()  # accumulation step: no update yet
        np.testing.assert_array_equal(model.weight.numpy(), w0)
        loss = model(x).sum()
        loss.backward()
        opt.step()  # second step applies
        assert not np.allclose(model.weight.numpy(), w0)
