"""Every Pallas kernel on the default path, taken as far toward a TPU as a
machine without one allows (ISSUE 21).

Two stages, at the shapes ``chip_smoke.py`` runs:

1. **Lowering** (always): ``jit(...).trace(...).lower(lowering_platforms=
   ("tpu",))`` turns the kernel's jaxpr into Mosaic MLIR. This is the stage
   that rejected the old decode kernel (a batched mat-vec with no lhs free
   dimension). It proves nothing about what Mosaic or XLA then accept.
2. **Deviceless compile** (when the installed libtpu can describe a v5e
   topology without a chip): the full XLA:TPU + Mosaic compile against
   ``jax.experimental.topologies``. This is the stage that refused the
   whole-bucket multi-query kernel (VMEM), refuses Mosaic calls under GSPMD
   outside a manual region, and where the rope op on its own aborted the
   compiler. It runs in a subprocess: a compiler abort is a SIGABRT.

Neither stage executes a kernel; numbers and numerics come from the chip
(``python chip_smoke.py``).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# llama_1b serving geometry: 16 heads x 128, 512 pages of 16 tokens, 128
# pages per request; llama_125m training: 16 x 12 heads, seq 1024, d 64
H, D, BLK, N, P = 16, 128, 16, 512, 128


def _paged_cases():
    """(name, fn, abstract args) for decode and every multi-query rung the
    smoke touches, fp and int8 pools."""
    sds = jax.ShapeDtypeStruct
    cases = []
    for kv in (jnp.bfloat16, jnp.int8):
        pool = sds((N, BLK, H, D), kv)
        scales = ((sds((N, BLK, H), jnp.float32),) * 2
                  if kv == jnp.int8 else ())

        def decode(q, k, v, t, l, *s):
            return pa.paged_decode_attention_pallas(
                q, k, v, t, l, 0.088, **dict(zip(("k_scale", "v_scale"), s)))

        def mq(q, k, v, t, l, st, *s):
            return pa.paged_multiquery_attention_pallas(
                q, k, v, t, l, st, 0.088,
                **dict(zip(("k_scale", "v_scale"), s)))

        name = jnp.dtype(kv).name
        cases.append((f"decode-{name}", decode,
                      (sds((8, H, D), jnp.bfloat16), pool, pool,
                       sds((8, P), jnp.int32), sds((8,), jnp.int32))
                      + scales))
        for t in (64, 128, 2048):
            cases.append((f"mq{t}-{name}", mq,
                          (sds((1, t, H, D), jnp.bfloat16), pool, pool,
                           sds((1, P), jnp.int32), sds((1,), jnp.int32),
                           sds((1,), jnp.int32)) + scales))
    return cases


def _flash_cases():
    sds = jax.ShapeDtypeStruct
    cases = []
    for bh, sq, d in ((192, 1024, 64), (16, 2048, 128)):
        q = sds((bh, sq, d), jnp.bfloat16)
        bq, bk = fa._block_sizes(sq, sq)

        def fwd(q, k, v, bq=bq, bk=bk):
            return fa._flash_mha(q, k, v, 0.125, True, bq, bk)

        def bwd(q, k, v, fwd=fwd):
            return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        cases.append((f"flash-fwd-{sq}x{d}", fwd, (q, q, q)))
        cases.append((f"flash-bwd-{sq}x{d}", bwd, (q, q, q)))
    return cases


CASES = _flash_cases() + _paged_cases()
#: stage 2 keeps tier-1 short: the backward cases (a grad compiles the
#: forward kernel too), decode, and the top rung that VMEM decides
COMPILED_CASES = [c for c in CASES if c[0].startswith(
    ("flash-bwd", "decode", "mq2048"))]


@pytest.mark.parametrize("name,fn,args", CASES, ids=[c[0] for c in CASES])
def test_lowers_to_mosaic(name, fn, args, monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


_COMPILE = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {tests!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        print("NO_TOPOLOGY", repr(e)[:300])
        sys.exit(0)
    import test_pallas_tpu_lowering as T
    from paddle_tpu.models import llama

    one = SingleDeviceSharding(topo.devices[0])

    def on(sharding, args):
        return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]

    for name, fn, args in T.COMPILED_CASES:
        text = jax.jit(fn).trace(*on(one, args)).lower().compile().as_text()
        print("COMPILED", name, flush=True)
        # the HLO instructions that are kernels, by their own names
        print("KERNELS", name, *sorted(set(
            ln.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
            for ln in text.splitlines() if " custom-call(" in ln)), flush=True)

    # the rope op on its own, at head-dim 128 (the split-and-concatenate
    # form aborted the compiler here)
    x = jax.ShapeDtypeStruct((7, 200, 16, 128), jnp.bfloat16, sharding=one)
    t = jax.ShapeDtypeStruct((200, 64), jnp.float32, sharding=one)
    jax.jit(llama._rope_apply.raw_fn).trace(x, t, t).lower().compile()
    print("COMPILED rope", flush=True)

    # a Mosaic call under a 4-device GSPMD jit: refused outside a manual
    # region, accepted per shard under an active plan
    from paddle_tpu.distributed.plan import Plan, compile_step_with_plan
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    plan = Plan.build(mesh, ["dp", "tp"])
    q = jax.ShapeDtypeStruct((16, 1024, 12, 64), jnp.bfloat16,
                             sharding=NamedSharding(
                                 mesh, P("dp", None, "tp", None)))
    attn = lambda q, k, v: T.fa._flash_attention_arrays.raw_fn(q, k, v)
    try:
        jax.jit(attn).trace(q, q, q).lower().compile()
        print("UNEXPECTED: GSPMD partitioned a Mosaic call")
    except NotImplementedError:
        print("REFUSED bare mosaic under gspmd", flush=True)
    compile_step_with_plan(attn, plan).trace(q, q, q).lower().compile()
    print("COMPILED per-shard under plan", flush=True)
""")


def test_compiles_for_v5e_without_a_chip():
    """Stage 2 (see module docstring). Skips when libtpu cannot describe a
    topology on this machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PT_PALLAS_INTERPRET="0")
    code = _COMPILE.format(repo=REPO, tests=os.path.dirname(__file__))
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=240)
    except subprocess.TimeoutExpired:
        # ~12 s here; a libtpu that stalls looking for a chip is the
        # machine's business, not a kernel regression
        pytest.skip("deviceless compile did not finish in 240 s")
    if "NO_TOPOLOGY" in r.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here: "
                    + r.stdout.strip()[-300:])
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    done = {ln.split(" ", 1)[1] for ln in r.stdout.splitlines()
            if ln.startswith("COMPILED ")}
    assert done == {c[0] for c in COMPILED_CASES} | {
        "rope", "per-shard under plan"}
    assert "REFUSED bare mosaic under gspmd" in r.stdout
    # a pallas_call's ``name`` becomes its HLO instruction's own name (under
    # jax.grad wrapped: ``transpose_jvp_flash_attention_bwd_dq__``), which is
    # what a profile's device line and the benchmark's reduction show
    kernels = {ln.split(" ")[1]: ln.split(" ")[2:] for ln in
               r.stdout.splitlines() if ln.startswith("KERNELS ")}
    for case, want in (("decode", ["paged_decode_attention"]),
                       ("mq2048", ["paged_prefill_attention"]),
                       ("flash-bwd", ["flash_attention_fwd",
                                      "flash_attention_bwd_dq",
                                      "flash_attention_bwd_dkv"])):
        for name, got in kernels.items():
            if name.startswith(case):
                assert len(got) == len(want) and all(
                    any(w in g for g in got) for w in want), (name, got)
    assert set(kernels) == {c[0] for c in COMPILED_CASES}
