"""One layer's paged decode call on the chip, timed over the live context.

    python3 scripts/paged_decode_microbench.py [--parent DIR]
        [--heads 32 --kv-heads 8] [--dtype bfloat16|int8]

The benchmark's serving geometry (B 32, D 128, block 16; heads as given),
ragged contexts of ``live`` pages a row for live in {8, 32, 61, 128, 256}
under table caps P in {128, 256}. Per row of the output: microseconds a
call, microseconds a live page (call / (B x live)), and the call's share of
its byte floor (every live token's K and V once at the chip's HBM peak,
``benchmarks/harness/peaks.py``). ``--parent DIR`` also times the kernel of
a checkout of another commit (``git archive <commit> | tar -x -C DIR``)
under ``impl: parent``, in the same process on the same pools.

A call is timed inside one jit that loops over ``CHAIN`` calls (each call's
output is the next one's query, so none is dropped or merged), which keeps
the host's dispatch out of it. Needs the chip: exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B, D, BLOCK = 32, 128, 16
LIVE = (8, 32, 61, 128, 256)
CAPS = (128, 256)
CHAIN, REPS = 64, 12


def load_kernel(checkout):
    """``paged_decode_attention_pallas`` of the checkout at ``checkout``,
    beside this checkout's own (its relative imports resolve here)."""
    import paddle_tpu.ops.pallas  # noqa: F401  (the package of the name)

    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas._parent_paged_attention",
        os.path.join(checkout, "paddle_tpu", "ops", "pallas",
                     "paged_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_decode_attention_pallas


def measure(kernels, h, hkv, dtype, hbm):
    """Time every kernel of ``kernels`` (name -> function) over CAPS x LIVE
    and print one JSON line each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = B * max(LIVE) + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, h, D), jnp.bfloat16)
    shape = (n, BLOCK, hkv, D)
    if dtype == "int8":
        k_pool = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        v_pool = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        scales = (jnp.full(shape[:3], 0.01, jnp.float32),) * 2
    else:
        k_pool = jax.random.normal(kk, shape, jnp.bfloat16)
        v_pool = jax.random.normal(kv, shape, jnp.bfloat16)
        scales = ()
    rng = np.random.default_rng(0)

    def chained(kernel):
        def run(q, k_pool, v_pool, tables, lens, *scales):
            return jax.lax.fori_loop(0, CHAIN, lambda _, q: kernel(
                q, k_pool, v_pool, tables, lens, D ** -0.5,
                **dict(zip(("k_scale", "v_scale"), scales))), q)
        return jax.jit(run)

    fns = {name: chained(k) for name, k in kernels.items()}
    for cap in CAPS:
        for live in (l for l in LIVE if l <= cap):
            # every row's pages are its own, dealt at random over the pool;
            # unused slots point at page 0, as the engine's tables do
            tables = np.zeros((B, cap), np.int32)
            tables[:, :live] = (rng.permutation(n - 1)[:B * live] + 1
                                ).reshape(B, live)
            lens = np.full((B,), live * BLOCK, np.int32)
            lens -= rng.integers(0, BLOCK, B).astype(np.int32)  # ragged tails
            floor_s = (int(lens.sum()) * 2 * hkv * D
                       * k_pool.dtype.itemsize) / hbm
            ops = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens),
                   *scales)
            for name, fn in fns.items():
                fn(*ops).block_until_ready()
                times = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    fn(*ops).block_until_ready()
                    times.append((time.perf_counter() - t0) / CHAIN)
                t = statistics.median(times)
                print(json.dumps({
                    "impl": name, "cap": cap, "live_pages": live,
                    "call_us": t * 1e6,
                    "us_per_live_page": t * 1e6 / (B * live),
                    "byte_floor_share_pct": 100 * floor_s / t}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="checkout of the commit to compare with")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "int8"))
    args = ap.parse_args()

    import jax

    from benchmarks.harness import peaks
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"paged_decode_microbench: needs a TPU, JAX reports "
              f"{dev.platform}", file=sys.stderr)
        return 2
    kernels = {"this": paged_decode_attention_pallas}
    if args.parent:
        kernels["parent"] = load_kernel(args.parent)
    print(json.dumps({"device": dev.device_kind, "batch": B,
                      "heads": args.heads, "kv_heads": args.kv_heads,
                      "head_dim": D, "block": BLOCK, "dtype": args.dtype,
                      "chain": CHAIN, "reps": REPS}), flush=True)
    measure(kernels, args.heads, args.kv_heads, args.dtype,
            peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
