"""One expert layer of MiMo-V2-Flash alone on the chip: the tile loop against
the grouped kernel, over the experts a step hits.

    python3 scripts/moe_ffn_microbench.py [--parent DIR] [--tokens 64 2048]

The published widths (hidden 4096, expert width 2048, router 256 wide, 8 a
token) and the benchmark's share (16 held experts), bf16, random weights.
For T tokens a call, the router's bias takes 16 - n of the held experts out
of every token's choice, n in ``ENABLED``, so the call hits about n experts
at the load a held expert really sees (T x 8 / 256 rows). Per row of the
output: the implementation, T, the experts hit (the block's own count) and
the microseconds a call. Then per implementation and T a least-squares line
``a us + b us x experts hit`` with ``b``'s share of the byte floor (an
expert's three matrices once at the chip's HBM peak,
``benchmarks/harness/peaks.py``): 61.5 us an expert.

``impl``: ``kernel`` is this checkout's ``moe_dropless`` as the chip runs
it; ``loop`` is the same function with the kernel's gate held shut (the
CPU's path, which was the chip's until ISSUE 30); ``kernel_alone`` is
``grouped_swiglu`` alone (no routing, sort or sum), the hit experts' pairs at
the same load; ``parent`` (with ``--parent
DIR``, a checkout of another commit) is that commit's ``moe_dropless``.

A call is timed inside one jit that loops over ``CHAIN`` calls (each call's
output enters the next one's input times a scalar the compiler cannot see
is tiny), which keeps the host's dispatch out of it. Needs the chip: exits
2 without a TPU.
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

D, F, E, HELD, TOP_K = 4096, 2048, 256, 16, 8
ENABLED = (2, 4, 8, 12, 16)
CHAIN, REPS = 16, 8


def load_parent(checkout):
    """``moe_dropless`` of the checkout at ``checkout``, beside this
    checkout's own (its relative imports resolve here)."""
    import paddle_tpu.models  # noqa: F401  (the package of the name)

    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.models._parent_mimo_v2",
        os.path.join(checkout, "paddle_tpu", "models", "mimo_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod             # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod.moe_dropless


def timed(fn, *ops):
    out = fn(*ops)
    out[0].block_until_ready()
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn(*ops)[0].block_until_ready()
        times.append((time.perf_counter() - t0) / CHAIN)
    return statistics.median(times) * 1e6, out


def fit(points):
    """Least squares ``a + b x`` through (x, y) points."""
    n = len(points)
    mx = sum(p[0] for p in points) / n
    my = sum(p[1] for p in points) / n
    sxx = sum((p[0] - mx) ** 2 for p in points)
    b = sum((p[0] - mx) * (p[1] - my) for p in points) / sxx if sxx else 0.0
    return my - b * mx, b


def measure(tokens, parent, hbm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import mimo_v2
    from paddle_tpu.ops.pallas import grouped_ffn

    keys = jax.random.split(jax.random.PRNGKey(0), 3 * HELD + 2)
    experts = [tuple(
        (jax.random.normal(keys[3 * e + m], shape, jnp.float32) * 0.02
         ).astype(jnp.bfloat16)
        for m, shape in enumerate(((D, F), (D, F), (F, D))))
        for e in range(HELD)]
    router = jax.random.normal(keys[-1], (D, E), jnp.float32) * 0.02
    slot = np.full(E, HELD, np.int32)
    slot[:HELD] = np.arange(HELD)
    floor_us = 3 * D * F * 2 / hbm * 1e6

    def gated(shut, fn):
        """``fn`` traced with the kernel's gate held shut, or as it is."""
        def run(*args):
            gate = grouped_ffn.use_pallas_grouped_ffn
            if shut:
                grouped_ffn.use_pallas_grouped_ffn = lambda d, f: False
            try:
                return fn(*args)
            finally:
                grouped_ffn.use_pallas_grouped_ffn = gate
        return jax.jit(run)

    def once(moe, shut):
        return gated(shut, lambda x, bias, router, experts: moe(
            x, router, bias, experts, slot, top_k=TOP_K)[0])

    # the weights go in as arguments: closed over they would be constants
    # of the program, 0.8 GB to fold and to keep with every executable
    def block(moe, shut):
        def chain(x, bias, tiny, router, experts):
            def step(_, carry):
                x, _ = carry
                y, _, hit = moe(x, router, bias, experts, slot, top_k=TOP_K)
                return x + y * tiny, hit
            return jax.lax.fori_loop(0, CHAIN, step, (x, jnp.int32(0)))
        return gated(shut, chain)

    impls = {"loop": block(mimo_v2.moe_dropless, True),
             "kernel": block(mimo_v2.moe_dropless, False)}
    if parent:
        impls["parent"] = block(load_parent(parent), False)
    tiny = jnp.asarray(1e-30, jnp.bfloat16)
    rng = np.random.default_rng(0)
    for t in tokens:
        x = jax.random.normal(keys[-2], (t, D), jnp.float32
                              ).astype(jnp.bfloat16)
        rows = grouped_ffn.rows_for(t)
        load = -(-t * TOP_K // E)            # pairs a held expert sees

        def alone(x, order, ie, start, live, n, tiny, experts):
            def step(_, x):
                y = grouped_ffn.grouped_swiglu(
                    x, order, ie, start, live, n, experts, rows=rows,
                    top_k=TOP_K)
                return x + (y[:t].reshape(t, D) * tiny).astype(x.dtype)
            return (jax.lax.fori_loop(0, CHAIN, step, x),)

        alone = jax.jit(alone)
        points = {name: [] for name in (*impls, "kernel_alone")}
        for n in ENABLED:
            bias = rng.normal(0.0, 0.01, E).astype(np.float32)
            out_of = rng.permutation(HELD)[:HELD - n]
            bias[out_of] = -10.0
            for name, fn in impls.items():
                us, (_, hit) = timed(fn, x, jnp.asarray(bias), tiny, router,
                                     experts)
                points[name].append((int(hit), us))
                print(json.dumps({"impl": name, "tokens": t, "enabled": n,
                                  "experts_hit": int(hit), "call_us": us}),
                      flush=True)
            on = np.sort(np.setdiff1d(np.arange(HELD), out_of))
            ie = np.zeros(HELD + t * TOP_K // rows, np.int32)
            start, live = np.zeros_like(ie), np.zeros_like(ie)
            ie[:n], start[:n], live[:n] = on, np.arange(n) * load, load
            us, _ = timed(alone, x, jnp.asarray(rng.permutation(t * TOP_K),
                                                jnp.int32),
                          *(jnp.asarray(a) for a in (ie, start, live)),
                          jnp.int32(n), tiny, experts)
            points["kernel_alone"].append((n, us))
            print(json.dumps({"impl": "kernel_alone", "tokens": t,
                              "enabled": n, "experts_hit": n, "call_us": us}),
                  flush=True)
        # the same call once through both, on the last bias: what the chip's
        # kernel gives against the chip's loop
        y = {name: np.asarray(once(mimo_v2.moe_dropless, name == "loop")(
            x, jnp.asarray(bias), router, experts), np.float32)
            for name in ("loop", "kernel")}
        print(json.dumps({
            "tokens": t, "kernel_vs_loop_max_abs": float(
                np.abs(y["kernel"] - y["loop"]).max()),
            "loop_max_abs": float(np.abs(y["loop"]).max())}), flush=True)
        for name, pts in points.items():
            a, b = fit(pts)
            print(json.dumps({
                "fit": name, "tokens": t, "a_us": a, "b_us_per_expert": b,
                "floor_us_per_expert": floor_us,
                "byte_floor_share_pct": 100 * floor_us / b if b else None}),
                flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="checkout of the commit to compare with")
    ap.add_argument("--tokens", type=int, nargs="+", default=[64, 2048])
    args = ap.parse_args()

    import jax

    from benchmarks.harness import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"moe_ffn_microbench: needs a TPU, JAX reports {dev.platform}",
              file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind, "hidden": D, "expert": F,
                      "router": E, "held": HELD, "top_k": TOP_K,
                      "chain": CHAIN, "reps": REPS}), flush=True)
    measure(args.tokens, args.parent,
            peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
