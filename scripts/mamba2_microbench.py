"""The two kernels ISSUE 33 brings, alone on the chip, at the new cell's shapes.

    python3 scripts/mamba2_microbench.py [--parent DIR] [--batch 192]

1. ``mamba2_decode_update`` (``ops/pallas/mamba2.py``): one state-space
   block's decode step over ``--batch`` rows at the published widths (64
   heads of 64 over a state of 128, 8 groups; float32 states of 2,097,152 B
   in ``batch + 1`` slots), every row live and with a quarter of the rows
   dead (the null slot). Per line: microseconds a call, and the share of the
   byte floor, each LIVE row's state read once and written once at the
   chip's HBM peak (``benchmarks/harness/peaks.py``). Once through the
   ``lax`` form too: the largest difference of ``y`` and of the live states.
2. The grouped expert kernel alone (``grouped_swiglu``: no routing, sort or
   sum), 16 held experts all hit at the load a held expert sees: the ungated
   form at hidden 2,688 / width 1,856 stored 1,920 (``moe_grouped_relu2``,
   192 tokens, 6 a token), and the gated form at MiMo's (4,096 / 2,048, 64
   tokens, 8 a token) and JoyAI's (2,048 / 768) shapes; with ``--parent DIR``
   (a checkout of another commit) the gated shapes through that commit's
   kernel too. Share of the byte floor: the PUBLISHED matrices once.

A call is timed inside one jit that loops over ``CHAIN`` calls, each call's
output entering the next one's input, which keeps the host's dispatch out of
it. Needs the chip: exits 2 without a TPU.
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

HEADS, HEAD_DIM, STATE, GROUPS = 64, 64, 128, 8
CHAIN, REPS = 16, 8


def timed(fn, first, *ops, donated=False):
    """Microseconds a call of the chain ``fn`` runs (the median of ``REPS``
    runs), and its last result. ``donated``: ``fn`` donates its first operand
    and hands it back first, so each run takes the last one's."""
    import jax

    out = jax.block_until_ready(fn(first, *ops))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(out[0] if donated else first, *ops))
        times.append((time.perf_counter() - t0) / CHAIN)
    return statistics.median(times) * 1e6, out


def decode_update(batch, hbm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import mamba2

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(keys[0], (batch + 1, HEADS // 2, STATE,
                                        2 * HEAD_DIM), jnp.float32)
    x = jax.random.normal(keys[1], (batch, HEADS, HEAD_DIM)).astype(jnp.bfloat16)
    dt = jax.random.uniform(keys[2], (batch, HEADS), jnp.float32, 0.001, 0.1)
    a = -jnp.arange(1, HEADS + 1, dtype=jnp.float32)
    b = jax.random.normal(keys[3], (batch, GROUPS, STATE)).astype(jnp.bfloat16)
    c = jax.random.normal(keys[4], (batch, GROUPS, STATE)).astype(jnp.bfloat16)
    row_bytes = 2 * HEADS * HEAD_DIM * STATE * 4

    def chain(update):
        def run(state, slots, x, dt, b, c):
            def step(_, carry):
                state, x = carry
                y, state = update(state, slots, x, dt, a, b, c)
                return state, (x + y.astype(x.dtype) * 1e-30).astype(x.dtype)
            return jax.lax.fori_loop(0, CHAIN, step, (state, x))
        return jax.jit(run, donate_argnums=(0,))

    kernel = chain(mamba2.mamba2_decode_update)
    for dead in (0, batch // 4):
        slots = np.arange(batch, dtype=np.int32)
        slots[np.random.default_rng(0).permutation(batch)[:dead]] = batch
        us, (state, _) = timed(kernel, state, jnp.asarray(slots), x, dt, b, c,
                               donated=True)
        floor = (batch - dead) * row_bytes / hbm * 1e6
        print(json.dumps({
            "kernel": "mamba2_decode_update", "batch": batch, "dead": dead,
            "call_us": us, "floor_us": floor,
            "byte_floor_share_pct": 100 * floor / us}), flush=True)
    # once through both forms, from the same states
    slots = jnp.asarray(slots)
    y_k, s_k = jax.jit(mamba2.mamba2_decode_update)(state, slots, x, dt, a, b, c)
    y_l, s_l = jax.jit(mamba2.mamba2_decode_update_lax)(state, slots, x, dt, a,
                                                        b, c)
    live = np.asarray(slots) != batch
    rows = np.asarray(slots)[live]
    print(json.dumps({
        "kernel_vs_lax_y_max_abs": float(jnp.abs(y_k - y_l)[live].max()),
        "kernel_vs_lax_state_max_abs": float(
            jnp.abs(s_k[rows] - s_l[rows]).max()),
        "y_max_abs": float(jnp.abs(y_l)[live].max())}), flush=True)


def load_parent(checkout):
    """``ops/pallas/grouped_ffn.py`` of the checkout at ``checkout``, beside
    this checkout's own (its relative imports resolve here)."""
    import paddle_tpu.ops.pallas  # noqa: F401  (the package of the name)

    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas._parent_grouped_ffn",
        os.path.join(checkout, "paddle_tpu", "ops", "pallas", "grouped_ffn.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def experts_alone(parent, hbm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import grouped_ffn

    held = 16
    shapes = {   # name: (hidden, published width, stored, matrices, T,
        #                  top_k, the router's width)
        "nemotron relu2": (2688, 1856, 1920, 2, 192, 6, 128),
        "mimo swiglu": (4096, 2048, 2048, 3, 64, 8, 256),
        "joyai swiglu": (2048, 768, 768, 3, 64, 8, 256)}
    sides = {"change": grouped_ffn}
    if parent:
        sides["parent"] = load_parent(parent)
    rng = np.random.default_rng(0)
    for name, (d, f, store, mats, t, top_k, routed) in shapes.items():
        keys = jax.random.split(jax.random.PRNGKey(1), mats * held + 1)
        dims = [(d, store)] * (mats - 1) + [(store, d)]
        experts = []
        for e in range(held):
            ws = [(jax.random.normal(keys[mats * e + m], s, jnp.float32) * 0.02
                   ).astype(jnp.bfloat16) for m, s in enumerate(dims)]
            if store != f:      # zeros past the published width
                ws = [w.at[:, f:].set(0) for w in ws[:-1]] \
                    + [ws[-1].at[f:].set(0)]
            experts.append(tuple(ws))
        x = jax.random.normal(keys[-1], (t, d), jnp.float32).astype(jnp.bfloat16)
        rows = grouped_ffn.rows_for(t)
        load = -(-t * top_k // routed)        # pairs a held expert sees
        n_items = held + t * top_k // rows
        ie = np.zeros(n_items, np.int32)
        start, live = np.zeros_like(ie), np.zeros_like(ie)
        ie[:held] = np.arange(held)
        start[:held], live[:held] = np.arange(held) * load, load
        order = jnp.asarray(rng.permutation(t * top_k), jnp.int32)
        floor = held * mats * d * f * 2 / hbm * 1e6
        outs = {}
        for side, mod in sides.items():
            if side == "parent" and mats == 2:
                continue         # the parent has no ungated form

            def alone(x, order, ie, start, live, n, tiny, experts, mod=mod):
                def step(_, x):
                    y = mod.grouped_swiglu(x, order, ie, start, live, n,
                                           experts, rows=rows, top_k=top_k)
                    return x + (y[:t].reshape(t, d) * tiny).astype(x.dtype)
                return jax.lax.fori_loop(0, CHAIN, step, x)

            ops = [jnp.asarray(a) for a in (ie, start, live)]
            us, _ = timed(jax.jit(alone), x, order, *ops, jnp.int32(held),
                          jnp.asarray(1e-30, jnp.bfloat16), experts)
            # one call's rows, those of the pairs an item holds (the others
            # are left as they were: whatever the buffer held)
            y = jax.jit(lambda x, experts, mod=mod: mod.grouped_swiglu(
                x, order, *ops, jnp.int32(held), experts, rows=rows,
                top_k=top_k))(x, experts)
            outs[side] = np.asarray(y, np.float32)[
                np.asarray(order)[:held * load]]
            print(json.dumps({
                "kernel": name, "side": side, "tokens": t, "rows_an_expert":
                int(load), "call_us": us, "floor_us": floor,
                "byte_floor_share_pct": 100 * floor / us}), flush=True)
        if len(outs) == 2:
            print(json.dumps({"kernel": name, "parent_equals_change": bool(
                np.array_equal(outs["parent"], outs["change"]))}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="checkout of the commit to compare with")
    ap.add_argument("--batch", type=int, default=192)
    args = ap.parse_args()

    import jax

    from benchmarks.harness import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"mamba2_microbench: needs a TPU, JAX reports {dev.platform}",
              file=sys.stderr)
        return 2
    hbm = peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    print(json.dumps({"device": dev.device_kind, "chain": CHAIN,
                      "reps": REPS}), flush=True)
    decode_update(args.batch, hbm)
    experts_alone(args.parent, hbm)
    return 0


if __name__ == "__main__":
    sys.exit(main())
