"""The chunk attention kernel alone on the chip, at every serving
configuration's chunk shape.

    python3 scripts/chunk_attention_microbench.py [--parent DIR ...]
        [--config NAME ...]

One layer's ``chunk_attention_pallas`` call for a 2,048-token chunk (the
rung the serving cells' long prompts run at) at offsets 0, 2,048, 6,144 and
10,240 where the configuration's keys reach that far, at the benchmark's
geometry (``CONFIGS``: query heads over kv heads, K / V widths as stored and
as published, keys in the row). Per row: milliseconds a call; the call's
share of its FLOP roofline, counting the query-key pairs the chunk's real
queries see at the PUBLISHED widths as ``benchmarks/harness/prefill_spans.py``
``attention_flops`` counts them for ``decode.device.prefill_attention_roofline``;
microseconds a live tile; and the grid's live and dead steps
(``chunk_tile_counts``). Implementations, in one process on the same
operands: ``change``, this checkout's kernel, and with ``--parent DIR``
(repeatable; a checkout of another commit, ``git archive <commit> | tar -x
-C DIR``) that commit's kernel under ``parent``.

``equals_change`` says whether a call's output is ``change``'s bit for bit.
A call is timed inside one jit that chains ``CHAIN`` calls (each call's
output written into the next one's query), so the host's dispatch is not in
it. Needs the chip: exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

RUNG = 2048
OFFSETS = (0, 2048, 6144, 10240)
CHAIN, REPS = 8, 7

#: one attending layer of each serving configuration: query heads, kv heads
#: (JoyAI's chunk attends over expanded heads: as many as the queries), K / V
#: widths as stored and as published, keys in the row (a Llama table's
#: pages, or the cap's), and a window layer's window (its row is the 128
#: keys of the ring's tail before the chunk and the chunk's own)
CONFIGS = {
    "mistral7b": dict(h=32, hkv=8, dk=128, dv=128, pub=(128, 128), ln=4096),
    "mimo-global": dict(h=64, hkv=4, dk=256, dv=128, pub=(192, 128),
                        ln=12288),
    "mimo-window": dict(h=64, hkv=8, dk=256, dv=128, pub=(192, 128),
                        ln=128 + RUNG, window=128),
    "joyai-expanded": dict(h=32, hkv=32, dk=256, dv=128, pub=(192, 128),
                           ln=12288),
    "nemotron3": dict(h=32, hkv=2, dk=128, dv=128, pub=(128, 128), ln=4096),
    "qwen3-next": dict(h=16, hkv=2, dk=256, dv=256, pub=(256, 256),
                       ln=10240),
}


def measure(name, g, mods, peak_flops):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import prefill_spans
    from paddle_tpu.ops.pallas.paged_attention import chunk_tile_counts

    h, hkv, dk, dv, ln = (g[k] for k in ("h", "hkv", "dk", "dv", "ln"))
    window = g.get("window")
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (RUNG, h, dk), jnp.bfloat16)
    k = jax.random.normal(keys[1], (ln, hkv, dk), jnp.bfloat16)
    v = jax.random.normal(keys[2], (ln, hkv, dv), jnp.bfloat16)
    sink = jax.random.normal(keys[3], (h,), jnp.float32) if window else None
    scale = g["pub"][0] ** -0.5
    kernel = "chunk_attention_" + ("window" if window else "global")

    def chained(m):
        def run(q, k, v, start):
            k_start = start - 128 if window else 0

            def one(_, q):
                out = m.chunk_attention_pallas(
                    q, k, v, start, k_start, start + RUNG, scale,
                    window=window, sink=sink, name=kernel)
                return q.at[..., :dv].set(out)
            return jax.lax.fori_loop(0, CHAIN, one, q)
        return jax.jit(run)

    fns = {tag: chained(m) for tag, m in mods.items()}
    for start in OFFSETS:
        if not window and start + RUNG > ln:
            continue
        live, dead = chunk_tile_counts(
            start, RUNG, RUNG, h, hkv, ln, window,
            start - 128 if window else 0)
        flops = prefill_spans.attention_flops(
            [(1, h, *g["pub"], window)], [{"start": start, "tokens": RUNG}])
        first = None
        for tag, fn in fns.items():
            out = np.asarray(fn(q, k, v, start).astype(jnp.float32))
            first = out if first is None else first
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                fn(q, k, v, start).block_until_ready()
                times.append((time.perf_counter() - t0) / CHAIN)
            t = statistics.median(times)
            print(json.dumps({
                "config": name, "offset": start, "impl": tag,
                "call_ms": t * 1e3,
                "flop_roofline_pct": 100 * flops / peak_flops / t,
                "us_per_live_tile": t * 1e6 / live,
                "live": live, "dead": dead,
                "equals_change": bool(np.array_equal(out, first))}),
                flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", action="append", default=[],
                    help="checkout of a commit to compare with (repeatable)")
    ap.add_argument("--config", action="append", choices=sorted(CONFIGS),
                    help="configurations to run (default: all)")
    args = ap.parse_args()

    import jax

    from benchmarks.harness import peaks
    from paddle_tpu.ops.pallas import paged_attention
    from paged_decode_microbench import load_kernels

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chunk_attention_microbench: needs a TPU, JAX reports "
              f"{dev.platform}", file=sys.stderr)
        return 2
    mods = {"change": paged_attention}
    for i, checkout in enumerate(args.parent):
        tag = "parent" if i == 0 else os.path.basename(
            os.path.normpath(checkout))
        mods[tag] = load_kernels(checkout, tag)
    peak = peaks.peaks_for(dev.device_kind)["bf16_flops"]
    print(json.dumps({"device": dev.device_kind, "rung": RUNG,
                      "chain": CHAIN, "reps": REPS}), flush=True)
    for name in args.config or CONFIGS:
        measure(name, CONFIGS[name], mods, peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
