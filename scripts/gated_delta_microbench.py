"""The gated delta rule's two forms (ISSUE 37), alone on the chip, at the new
cell's shapes.

    python3 scripts/gated_delta_microbench.py [--batch 96] [--probe DIR]

1. ``gated_delta_decode_update`` (``ops/pallas/gated_delta.py``): one delta
   layer's decode step over ``--batch`` rows at the published widths (32
   value heads of 128 over 16 key heads of 128; float32 states of 2,097,152 B
   in ``batch + 1`` slots), every row live and with a quarter of the rows
   dead (the null slot). Per line: microseconds a call, and the share of the
   byte floor, each LIVE row's state read once and written once at the chip's
   HBM peak (``benchmarks/harness/peaks.py``). Once through the ``lax`` twin
   too: the largest difference of ``o`` and of the live states.
2. ``gated_delta_chunk``: one layer's chunk of 256 and of 2,048 tokens from a
   carried state, microseconds a call; against the recurrence token by token
   on the first 256 tokens, the largest difference (the chip's products are
   one bfloat16 pass outside the inverse).
3. With ``--probe DIR``: a profile of a jit that holds ``gated_delta_chunk``,
   written under DIR, and what the device line's events say of it: which
   statistics of an "XLA Ops" event name the inner jit. (PR 37 found: none.
   An event carries its HLO instruction and its device time, not the path of
   jits it came from, so no per-layer metric can read the chunked form's
   share while it is XLA; 2 above times it alone.)

A call is timed inside one jit that loops over ``CHAIN`` calls, each call's
output entering the next one's input, which keeps the host's dispatch out of
it. Needs the chip: exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KEY_HEADS, HEADS, N, P = 16, 32, 128, 128
CHAIN, REPS = 16, 8


def timed(fn, *ops):
    """Microseconds a call of the chain ``fn`` runs (the median of ``REPS``
    runs), and its last result. ``fn`` donates its first operand and hands it
    back first, so each run takes the last one's."""
    import jax

    out = jax.block_until_ready(fn(*ops))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(out[0], *ops[1:]))
        times.append((time.perf_counter() - t0) / CHAIN)
    return statistics.median(times) * 1e6, out


def operands(rng, t):
    import jax.numpy as jnp

    bf = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)  # noqa: E731
    return (bf(t, KEY_HEADS, N), bf(t, KEY_HEADS, N), bf(t, HEADS, P),
            -jnp.asarray(rng.uniform(0.0, 1.6, size=(t, HEADS)), jnp.float32),
            jnp.asarray(rng.uniform(0.05, 0.95, size=(t, HEADS)), jnp.float32))


def decode_update(batch, hbm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import gated_delta as gd

    rng = np.random.default_rng(0)
    q, k, v, g, beta = operands(rng, batch)
    state = jnp.asarray(rng.normal(size=(batch + 1, HEADS, N, P)), jnp.float32)

    def chain(update):
        def run(state, slots):
            def body(_, carry):
                state, o = carry
                o, state = update(state, slots, q, k, v + o.astype(v.dtype),
                                  g, beta)
                return state, o
            return jax.lax.fori_loop(
                0, CHAIN, body, (state, jnp.zeros((batch, HEADS, P), jnp.float32)))
        return jax.jit(run, donate_argnums=0)

    for dead in (0, batch // 4):
        slots = np.arange(batch, dtype=np.int32)
        slots[:dead] = batch
        live = batch - dead
        us, _ = timed(chain(gd.gated_delta_decode_update), state + 0,
                      jnp.asarray(slots))
        floor_us = 2 * live * HEADS * N * P * 4 / hbm * 1e6
        print(json.dumps({"kernel": "gated_delta_decode_update", "batch": batch,
                          "dead_rows": dead, "us_a_call": us,
                          "byte_floor_us": floor_us,
                          "share_of_floor": 100 * floor_us / us}), flush=True)
    slots = jnp.arange(batch, dtype=jnp.int32)
    o_k, s_k = jax.jit(gd.gated_delta_decode_update)(state, slots, q, k, v, g, beta)
    o_l, s_l = jax.jit(gd.gated_delta_decode_update_lax)(state, slots, q, k, v,
                                                         g, beta)
    print(json.dumps({"kernel_against_lax": {
        "o": float(jnp.max(jnp.abs(o_k - o_l))),
        "state": float(jnp.max(jnp.abs(s_k - s_l))),
        "o_scale": float(jnp.max(jnp.abs(o_l)))}}), flush=True)


def chunk():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import gated_delta as gd

    rng = np.random.default_rng(1)
    s0 = jnp.asarray(rng.normal(size=(HEADS, N, P)) * 0.1, jnp.float32)
    for t in (256, 2048):
        q, k, v, g, beta = operands(rng, t)

        def run(s, q, k, v, g, beta):
            def body(_, carry):
                s, o = carry
                o, s = gd.gated_delta_chunk(q, k, v + o.astype(v.dtype), g,
                                            beta, s)
                return s * 0.5, o
            return jax.lax.fori_loop(
                0, CHAIN, body, (s, jnp.zeros((t, HEADS, P), jnp.float32)))

        us, _ = timed(jax.jit(run, donate_argnums=0), s0 + 0, q, k, v, g, beta)
        print(json.dumps({"program": "gated_delta_chunk", "tokens": t,
                          "us_a_call": us}), flush=True)
    q, k, v, g, beta = operands(rng, 256)
    o_c, s_c = gd.gated_delta_chunk(q, k, v, g, beta, s0)
    with jax.default_matmul_precision("highest"):
        o_r, s_r = jax.jit(gd.gated_delta_recurrence)(q, k, v, g, beta, s0)
    print(json.dumps({"chunk_against_recurrence": {
        "o": float(jnp.max(jnp.abs(o_c - o_r))),
        "state": float(jnp.max(jnp.abs(s_c - s_r))),
        "o_scale": float(jnp.max(jnp.abs(o_r))),
        "state_scale": float(jnp.max(jnp.abs(s_r)))}}), flush=True)


def probe(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import gated_delta as gd

    rng = np.random.default_rng(2)
    q, k, v, g, beta = operands(rng, 512)
    s0 = jnp.zeros((HEADS, N, P), jnp.float32)

    @jax.jit
    def chunk_pure(q, k, v, g, beta, s0):
        o, s = gd.gated_delta_chunk(q * 2, k, v, g, beta, s0)
        return jnp.tanh(o) @ jnp.ones((P, P)), s

    jax.block_until_ready(chunk_pure(q, k, v, g, beta, s0))
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        jax.block_until_ready(chunk_pure(q, k, v, g, beta, s0))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    seen, named, total = {}, 0, 0
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                total += 1
                stats = dict(e.stats)
                hit = [key for key, val in stats.items()
                       if isinstance(val, str) and "gated_delta_chunk" in val]
                named += bool(hit)
                for key in hit:
                    seen.setdefault(key, str(stats[key])[:200])
                if total == 1:
                    print(json.dumps({"first_event": e.name[:120],
                                      "stats": {key: str(val)[:120] for key, val
                                                in stats.items()}}), flush=True)
    print(json.dumps({"probe": {"events": total, "naming_the_jit": named,
                                "by_statistic": seen}}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=96)
    ap.add_argument("--probe")
    args = ap.parse_args()
    from benchmarks.harness import peaks
    from benchmarks.runners import common

    devs = common.require_tpu(1)
    hbm = peaks.peaks_for(devs[0].device_kind)["hbm_bytes_per_s"]
    decode_update(args.batch, hbm)
    chunk()
    if args.probe:
        probe(args.probe)


if __name__ == "__main__":
    main()
