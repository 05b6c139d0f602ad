"""The head-64 attention kernels alone at lfm2-8b-a1b-serve's shapes, on the
chip: the paged decode kernel over pools of two 64-wide kv heads a lane row
(the program's form) against the same kernel over pools padded to the lanes
(each kv head a row of 128, K and q padded with zeros: what
``KVLayerSpec.k_store``'s padding would hold), and the prefill chunk's
attention over a request's pages in a row in the paired form against the
lane-sliced one (the pages laid out as ``[L, 8, 64]`` and the chunk kernel at
64 lanes).

    python3 scripts/h64_attention_microbench.py [--reps N]

Each form is one ``jax.jit`` of one layer's call, timed over ``--reps`` calls
after two warm ones (``block_until_ready`` around the lot). A decode reading
is also given as a share of the bytes' floor at the published widths: live
tokens x 2,048 B over the chip's HBM bandwidth, over the time, which is what
``lfm2.kernels.h64_decode_roofline`` computes from a trace. Prints one JSON
line. The outputs of each pair of forms are compared (max |a - b|).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import peaks, schedule  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

H, HKV, D, BLK = 32, 8, 64, 16
SCALE = D ** -0.5


def timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=50)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("the chip only: a CPU timing says nothing of the kernels")
    config = bench_run.load_json("benchmarks", "configs",
                                 "lfm2-8b-a1b-serve.json")
    traffic = bench_run.load_json("benchmarks", "traffic", "doc-qa-decode.json")
    eng = config["engine"]
    b, p = eng["max_batch_size"], eng["max_model_len"] // BLK
    bw = peaks.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    rng = np.random.default_rng(0)
    # the batch half way through its requests: prompt + half the answer
    items = schedule.build(traffic)[:b]
    lens = np.asarray([it.prompt_len + it.output_len // 2 for it in items],
                      np.int32)
    pages = -(-lens // BLK)
    n = int(pages.sum()) + 1
    perm = rng.permutation(np.arange(1, n)).astype(np.int32)
    tables = np.zeros((b, p), np.int32)
    at = 0
    for r, k in enumerate(pages):
        tables[r, :k] = perm[at:at + k]
        at += k
    tables, lens_d = jnp.asarray(tables), jnp.asarray(lens)
    q = jnp.asarray(rng.normal(size=(b, H, D)), jnp.bfloat16)
    kv = jnp.asarray(rng.normal(size=(2, n, BLK, HKV, D)), jnp.bfloat16)
    floor_s = float(lens.sum()) * 2 * HKV * D * 2 / bw

    paired = [x.reshape(n, BLK * HKV // 2, 2 * D) for x in kv]

    @jax.jit
    def decode_paired(q, k, v, t, ln):
        return pa.paged_decode_attention_pairs_pallas(
            q, k, v, t, ln, SCALE, num_kv_heads=HKV)

    t_pair, o_pair = timed(decode_paired, (q, *paired, tables, lens_d),
                           a.reps)
    del paired
    padded = [jnp.pad(x, [(0, 0)] * 3 + [(0, D)]).reshape(n, BLK * HKV, 2 * D)
              for x in kv]

    @jax.jit
    def decode_padded(q, k, v, t, ln):
        out = pa.paged_decode_attention_pallas(
            jnp.pad(q, [(0, 0), (0, 0), (0, D)]), k, v, t, ln, SCALE,
            num_kv_heads=HKV, name="paged_decode_attention_padded")
        return out[..., :D]

    t_pad, o_pad = timed(decode_padded, (q, *padded, tables, lens_d), a.reps)
    del padded

    # a chunk of 2,048 queries at the end of the longest prompt, over the
    # request's pages gathered in a row as the program gathers them
    upto = int(traffic["prompt_tokens"]["max"])
    row = jnp.asarray(np.arange(1, p + 1) % n, jnp.int32)
    qc = jnp.asarray(rng.normal(size=(2048, H, D)), jnp.bfloat16)
    pool = [x.reshape(n, BLK * HKV // 2, 2 * D) for x in kv]
    start = jnp.int32(upto - 2048)

    @jax.jit
    def chunk_paired(q, k, v, t, s):
        return pa.chunk_attention_pairs_pallas(
            q, k[t].reshape(-1, HKV // 2, 2 * D),
            v[t].reshape(-1, HKV // 2, 2 * D), s, 0, upto, SCALE,
            num_kv_heads=HKV)

    @jax.jit
    def chunk_lanes(q, k, v, t, s):
        return pa.chunk_attention_pallas(
            q, k[t].reshape(-1, HKV, D), v[t].reshape(-1, HKV, D), s, 0, upto,
            SCALE, name="chunk_attention_lanes64")

    t_cp, o_cp = timed(chunk_paired, (qc, *pool, row, start), a.reps)
    t_cl, o_cl = timed(chunk_lanes, (qc, *pool, row, start), a.reps)
    flops = 4 * 2048 * H * D * (upto - 1024)        # causal, published widths

    def diff(x, y):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))

    print(json.dumps({
        "device": jax.devices()[0].device_kind, "batch": b,
        "live_tokens": int(lens.sum()),
        "decode_paired_ms": t_pair * 1e3, "decode_padded_ms": t_pad * 1e3,
        "decode_paired_floor_share": 100 * floor_s / t_pair,
        "decode_padded_floor_share": 100 * floor_s / t_pad,
        "decode_max_diff": diff(o_pair, o_pad),
        "chunk_paired_ms": t_cp * 1e3, "chunk_lanes_ms": t_cl * 1e3,
        "chunk_paired_flop_share": 100 * flops / 197e12 / t_cp,
        "chunk_lanes_flop_share": 100 * flops / 197e12 / t_cl,
        "chunk_max_diff": diff(o_cp[:2048], o_cl[:2048]),
    }), flush=True)


if __name__ == "__main__":
    main()
