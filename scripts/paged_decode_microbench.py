"""One layer's paged decode call on the chip, at the serving cells' shapes.

    python3 scripts/paged_decode_microbench.py [--kind gqa|latent|global|window]
        [--parent DIR ...] [--heads 32 --kv-heads 8] [--dtype bfloat16|int8]

``--kind`` picks the page kind and its cell's geometry (block 16 in all):

* ``gqa`` (the default; ``mistral7b-serve.decode-sat``): B 32, D 128, heads
  as given (``--heads 8 --kv-heads 2`` is a tp4 shard), every row ``live``
  pages for live in {8, 32, 61, 128, 256} under table caps P in {128, 256};
* ``latent`` (``joyai-llm-flash-serve.long-ctx-decode``): B 64, 32 heads over
  one 640-wide row a token (576 published, values its first 512), 768 pages
  a request, contexts 1k-11k, ~6.1k a request;
* ``global`` (``mimo-v2-flash-serve.mixed-len-decode``, a full layer): B 64,
  64 heads over 4 kv heads, K 256 wide stored (192 published), V 128, 768
  pages a request, contexts 0.2k-6.8k, ~3.5k a request;
* ``window`` (the same cell, a window layer): 64 heads over 8 kv heads, the
  same widths, a ring of 9 pages a request, window 128, a sink, the same
  contexts.

Per row of the output: microseconds a call, microseconds a live page, the
call's share of its byte floor (every live token's PUBLISHED bytes once at
the chip's HBM peak, ``benchmarks/harness/peaks.py``), and
``full_chunk_share``: of the chunks the call walks, the share whose every
page is live, which are the ones whose copies are started written out and
waited for with one descriptor a pool (``full_chunk_share`` below; a
request's last chunk waits by the binary digits of its live pages), and
whether the chained result equals ``change``'s bit for bit.
``--parent DIR`` also times the kernel of a checkout of another commit
(``git archive <commit> | tar -x -C DIR``) under ``impl: parent``, in the
same process on the same pools; given again, further checkouts go by their
directory's name.

A call is timed inside one jit that loops over ``CHAIN`` calls (each call's
output is written into the next one's query, so none is dropped or merged),
which keeps the host's dispatch out of it. Needs the chip: exits 2 without a
TPU.
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

BLOCK = 16
LIVE = (8, 32, 61, 128, 256)
CAPS = (128, 256)
CHAIN, REPS = 64, 12

#: the three kinds beside ``gqa``: batch, heads, kv heads, K / V widths as
#: stored and as published, pages a table row, context range, and the
#: kernel's keywords
KINDS = {
    "latent": dict(b=64, h=32, hkv=1, dk=640, dv=512, pub=576, cap=768,
                   ctx=(1024, 11264)),
    "global": dict(b=64, h=64, hkv=4, dk=256, dv=128, pub=192 + 128, cap=768,
                   ctx=(256, 6800)),
    "window": dict(b=64, h=64, hkv=8, dk=256, dv=128, pub=192 + 128, cap=9,
                   ctx=(256, 6800), window=128),
}


def live_pages(ctx, block, window=None):
    """Pages of a context of ``ctx`` tokens the decode kernel walks: all of
    them, or with ``window`` those from the page of token ``ctx - window``."""
    first = max(ctx - window, 0) // block if window else 0
    return -(-ctx // block) - first


def full_chunk_share(lens, block, chunk, window=None):
    """Of the chunks of ``chunk`` pages a decode call walks over contexts
    ``lens`` (an empty request takes one too), the share whose every page
    holds a live token: what ``_kernel`` starts written out and waits for
    with one descriptor a pool. ``window`` as the kernel has it."""
    pages = [live_pages(int(ctx), block, window) for ctx in lens]
    total = sum(max(-(-p // chunk), 1) for p in pages)
    return sum(p // chunk for p in pages) / total if total else None


def load_kernels(checkout, tag):
    """The paged-attention module of the checkout at ``checkout``, beside
    this checkout's own (its relative imports resolve here)."""
    import paddle_tpu.ops.pallas  # noqa: F401  (the package of the name)

    spec = importlib.util.spec_from_file_location(
        f"paddle_tpu.ops.pallas._{tag}_paged_attention",
        os.path.join(checkout, "paddle_tpu", "ops", "pallas",
                     "paged_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def timed(fns, ops, line):
    """Time every jitted function of ``fns`` on ``ops``; print a line each,
    with whether the chain's result is ``change``'s bit for bit."""
    import numpy as np

    first = None
    for name, fn in fns.items():
        out = np.asarray(fn(*ops).astype("float32"))
        first = out if first is None else first
        times = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            fn(*ops).block_until_ready()
            times.append((time.perf_counter() - t0) / CHAIN)
        t = statistics.median(times)
        print(json.dumps({"impl": name, **line(t),
                          "equals_change": bool(np.array_equal(out, first))}),
              flush=True)


def measure_gqa(mods, h, hkv, dtype, hbm):
    """``paged_decode_attention_pallas`` of every module over CAPS x LIVE."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, d = 32, 128
    n = b * max(LIVE) + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    shape = (n, BLOCK, hkv, d)
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
                q, k_pool, v_pool, tables, lens, d ** -0.5,
                **dict(zip(("k_scale", "v_scale"), scales))), q)
        return jax.jit(run)

    fns = {name: chained(m.paged_decode_attention_pallas)
           for name, m in mods.items()}
    pa = mods["change"]
    for cap in CAPS:
        chunk = pa._decode_chunk(BLOCK, hkv, h, d, k_pool.dtype.itemsize,
                                 cap)[0]
        for live in (l for l in LIVE if l <= cap):
            # every row's pages are its own, dealt at random over the pool;
            # unused slots point at page 0, as the engine's tables do
            tables = np.zeros((b, cap), np.int32)
            tables[:, :live] = (rng.permutation(n - 1)[:b * live] + 1
                                ).reshape(b, live)
            lens = np.full((b,), live * BLOCK, np.int32)
            lens -= rng.integers(0, BLOCK, b).astype(np.int32)  # ragged tails
            floor_s = (int(lens.sum()) * 2 * hkv * d
                       * k_pool.dtype.itemsize) / hbm
            share = None if scales else full_chunk_share(lens, BLOCK, chunk)
            timed(fns, (q, k_pool, v_pool, jnp.asarray(tables),
                        jnp.asarray(lens), *scales),
                  lambda t: {"cap": cap, "live_pages": live,
                             "call_us": t * 1e6,
                             "us_per_live_page": t * 1e6 / (b * live),
                             "byte_floor_share_pct": 100 * floor_s / t,
                             "chunk": chunk, "full_chunk_share": share})


def measure_kind(kind, mods, hbm):
    """One call at the geometry of ``KINDS[kind]`` over ragged contexts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    g = KINDS[kind]
    b, h, hkv, dk, dv, cap = (g[k] for k in ("b", "h", "hkv", "dk", "dv",
                                             "cap"))
    window = g.get("window")
    rng = np.random.default_rng(0)
    lens = rng.integers(*g["ctx"], b).astype(np.int32)
    pages = -(-lens // BLOCK)
    held = np.minimum(pages, cap)           # a ring holds its last `cap`
    n = int(held.sum()) + 1
    order = rng.permutation(n - 1) + 1
    tables, at = np.zeros((b, cap), np.int32), 0
    for i in range(b):
        # logical page p of a ring sits in slot p % cap
        slots = np.arange(pages[i] - held[i], pages[i]) % cap
        tables[i, slots] = order[at:at + held[i]]
        at += held[i]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, h, dk), jnp.bfloat16)
    if kind == "latent":
        pools = (jax.random.normal(keys[1], (n, BLOCK, dk), jnp.bfloat16),)
        pa = mods["change"]
        chunk = pa._decode_chunk(BLOCK, 1, h, dk, 2, cap, 0)[0]

        def call(m, q, pool, tables, lens):
            return m.paged_decode_attention_latent_pallas(
                q, pool, tables, lens, dk ** -0.5, dv)
    else:
        pools = (jax.random.normal(keys[1], (n, BLOCK, hkv, dk), jnp.bfloat16),
                 jax.random.normal(keys[2], (n, BLOCK, hkv, dv), jnp.bfloat16))
        chunk = mods["change"]._decode_chunk(BLOCK, hkv, h, dk, 2, cap, dv)[0]
        sink = jax.random.normal(keys[3], (h,), jnp.float32) \
            if window else None

        def call(m, q, k_pool, v_pool, tables, lens):
            return m.paged_decode_attention_pallas(
                q, k_pool, v_pool, tables, lens, dk ** -0.5, window=window,
                ring=bool(window), sink=sink)

    def chained(m):
        def run(q, *ops):
            return jax.lax.fori_loop(0, CHAIN, lambda _, q: q.at[
                ..., :dv].set(call(m, q, *ops)), q)
        return jax.jit(run)

    seen = np.minimum(lens, window) if window else lens
    floor_s = int(seen.sum()) * hkv * g["pub"] * 2 / hbm
    live = sum(live_pages(int(ctx), BLOCK, window) for ctx in lens)
    share = full_chunk_share(lens, BLOCK, chunk, window)
    timed({name: chained(m) for name, m in mods.items()},
          (q, *pools, jnp.asarray(tables), jnp.asarray(lens)),
          lambda t: {"kind": kind, "rows_a_request": float(lens.mean()),
                     "live_pages": live, "call_us": t * 1e6,
                     "us_per_live_page": t * 1e6 / live,
                     "byte_floor_share_pct": 100 * floor_s / t,
                     "chunk": chunk, "full_chunk_share": share})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kind", default="gqa", choices=("gqa", *KINDS))
    ap.add_argument("--parent", action="append", default=[],
                    help="checkout of a commit to compare with (repeatable)")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "int8"))
    args = ap.parse_args()

    import jax

    from benchmarks.harness import peaks
    from paddle_tpu.ops.pallas import paged_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"paged_decode_microbench: needs a TPU, JAX reports "
              f"{dev.platform}", file=sys.stderr)
        return 2
    mods = {"change": paged_attention}
    for i, checkout in enumerate(args.parent):
        tag = "parent" if i == 0 else os.path.basename(
            os.path.normpath(checkout))
        mods[tag] = load_kernels(checkout, tag)
    hbm = peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    head = {"device": dev.device_kind, "kind": args.kind, "block": BLOCK,
            "chain": CHAIN, "reps": REPS}
    if args.kind == "gqa":
        print(json.dumps({**head, "batch": 32, "heads": args.heads,
                          "kv_heads": args.kv_heads, "head_dim": 128,
                          "dtype": args.dtype}), flush=True)
        measure_gqa(mods, args.heads, args.kv_heads, args.dtype, hbm)
    else:
        print(json.dumps({**head, **{k: v for k, v in KINDS[args.kind].items()
                                     if k != "ctx"}}), flush=True)
        measure_kind(args.kind, mods, hbm)
    return 0


if __name__ == "__main__":
    sys.exit(main())
