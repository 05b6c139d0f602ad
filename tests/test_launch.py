"""Launcher CLI tests (reference launch/main.py + controllers).

Each test launches REAL worker processes over the jax.distributed
coordination service with CPU Gloo collectives."""

import os
import subprocess
import sys

import pytest

# heavyweight module (model zoo / e2e / subprocess): slow tier
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLLECTIVE_SCRIPT = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import paddle_tpu.distributed as dist

dist.init_parallel_env()
assert dist.get_world_size() == 2, dist.get_world_size()
rank = dist.get_rank()

import numpy as np
from jax.experimental import multihost_utils
# real cross-process collective: allgather each rank's contribution
gathered = multihost_utils.process_allgather(np.array(rank + 1))
assert sorted(gathered.tolist()) == [1, 2], gathered
open(os.path.join({out!r}, f"rank{{rank}}.ok"), "w").write(str(gathered))
"""

FLAKY_SCRIPT = """
import os, sys

flag = os.path.join({out!r}, "attempted")
if not os.path.exists(flag):
    open(flag, "w").write("x")
    sys.exit(3)
open(os.path.join({out!r}, "succeeded"), "w").write("x")
"""


def launch_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_launch(extra_args, script_path, timeout=180):
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           *extra_args, script_path]
    return subprocess.run(cmd, env=launch_env(), cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


class TestLaunchCLI:
    def test_two_process_collective(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(COLLECTIVE_SCRIPT.format(repo=REPO,
                                                   out=str(tmp_path)))
        r = run_launch(["--nproc_per_node=2"], str(script))
        assert r.returncode == 0, r.stderr[-3000:]
        assert (tmp_path / "rank0.ok").exists()
        assert (tmp_path / "rank1.ok").exists()

    def test_restart_on_failure(self, tmp_path):
        script = tmp_path / "flaky.py"
        script.write_text(FLAKY_SCRIPT.format(out=str(tmp_path)))
        r = run_launch(["--nproc_per_node=1", "--max_restart=1"],
                       str(script))
        assert r.returncode == 0, r.stderr[-2000:]
        assert (tmp_path / "succeeded").exists()
        assert "restart 1/1" in r.stderr

    def test_failure_propagates_exit_code(self, tmp_path):
        script = tmp_path / "bad.py"
        script.write_text("import sys; sys.exit(7)")
        r = run_launch(["--nproc_per_node=1"], str(script))
        assert r.returncode == 7

    def test_multinode_requires_master(self, tmp_path):
        script = tmp_path / "x.py"
        script.write_text("pass")
        r = run_launch(["--nnodes=2"], str(script))
        assert r.returncode != 0
        assert "--master" in r.stderr


class TestParseArgs:
    def test_defaults(self):
        from paddle_tpu.distributed.launch.main import parse_args

        a = parse_args(["train.py", "--lr", "0.1"])
        assert a.nnodes == 1 and a.rank == 0
        assert a.training_script == "train.py"
        assert a.training_script_args == ["--lr", "0.1"]


def _spawn_target(out_dir):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    assert dist.get_world_size() == 2
    with open(os.path.join(out_dir, f"spawn{dist.get_rank()}.ok"),
              "w") as f:
        f.write("x")


def _spawn_crasher(out_dir):
    raise RuntimeError("boom")


class TestSpawn:
    def test_spawn_inline_single(self):
        import paddle_tpu.distributed as dist

        called = []
        dist.spawn(called.append, args=(1,), nprocs=1)
        assert called == [1]

    def test_spawn_invalid_nprocs(self):
        import paddle_tpu.distributed as dist

        with pytest.raises(ValueError):
            dist.spawn(lambda: None, nprocs=-2)

    def test_spawn_two_process(self, tmp_path, monkeypatch):
        import paddle_tpu.distributed as dist

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        dist.spawn(_spawn_target, args=(str(tmp_path),), nprocs=2)
        assert (tmp_path / "spawn0.ok").exists()
        assert (tmp_path / "spawn1.ok").exists()

    def test_spawn_failure_raises_not_hangs(self, tmp_path, monkeypatch):
        import paddle_tpu.distributed as dist

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        with pytest.raises(RuntimeError, match="exit codes"):
            dist.spawn(_spawn_crasher, args=(str(tmp_path),), nprocs=2)
