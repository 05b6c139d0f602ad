"""Does the chip's DMA semaphore count bytes? (ISSUE 32, step 0)

    timeout 60 python3 scripts/dma_wait_probe.py

The paged decode kernels start one async copy a page onto one DMA semaphore
and used to wait once a page. Whether ONE wait whose descriptor is as large
as all the copies together waits for them all is not documented; jax's two
interpreters count bytes (``dma_wait`` subtracts the size of ITS
destination). This asks the chip, smallest shape first, one line a case:

* ``page``: one wait a page (the control);
* ``once``: every page started, one wait over the whole buffer;
* ``bits``: ``live < pages`` started, one wait for each binary digit of
  ``live`` (1, 2, 4, ... pages).

The buffer is filled with NaN before the copies start and read right after
the last wait, so a wait that returns before its bytes have landed shows as
NaN (the last cases copy 0.3 MB a page for that). Each case runs twice: a
semaphore left off zero would show in the second run. ``us_a_round`` times
``ROUNDS`` rounds of start-all, wait-all inside one kernel; a round waits
for its own copies' latency, so the three modes read alike there (PR 32):
what a wait costs beside a fold is ``paged_decode_microbench.py``'s to say.

A wrong guess about the semaphore HANGS: run it under ``timeout``. Exit 0:
every case right; 1: wrong data somewhere; 2: no TPU. On the CPU,
``PT_PALLAS_INTERPRET=1`` rehearses it in the TPU interpreter, whose copies
move only when their bytes are waited for.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: (pages, rows a page, width): 4 KB pages first, then the latent kind's
#: 20 KB, the K / V kinds' 32 KB, and 0.3 MB a page
CASES = ((4, 16, 128), (16, 16, 640), (64, 16, 640), (64, 128, 128),
         (8, 256, 640))
MODES = ("page", "once", "bits")
ROUNDS = 2000


def _kernel(tab_ref, live_ref, pool_hbm, out_ref, buf, sem, *, pages, rows,
            mode, rounds):
    live = live_ref[0]

    def copy(j):
        return pltpu.make_async_copy(
            pool_hbm.at[tab_ref[j]], buf.at[pl.ds(j * rows, rows)], sem.at[0])

    def whole(n):
        part = buf.at[pl.ds(0, n * rows)]
        return pltpu.make_async_copy(part, part, sem.at[0])

    def start_page(j, carry):
        copy(j).start()
        return carry

    def wait_page(j, carry):
        copy(j).wait()
        return carry

    def round_(_, carry):
        jax.lax.fori_loop(0, live, start_page, 0)
        if mode == "page":
            jax.lax.fori_loop(0, live, wait_page, 0)
        elif mode == "once":
            whole(pages).wait()
        else:
            for k in range(pages.bit_length()):
                pl.when(live & (1 << k) != 0)(whole(1 << k).wait)
        return carry

    buf[...] = jnp.full(buf.shape, jnp.nan, buf.dtype)
    jax.lax.fori_loop(0, rounds, round_, 0)
    out_ref[...] = buf[...]


def probe(pool, table, live, *, pages, rows, mode, rounds, interpret):
    width = pool.shape[-1]
    return pl.pallas_call(
        functools.partial(_kernel, pages=pages, rows=rows, mode=mode,
                          rounds=rounds),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((pages * rows, width),
                                   lambda i, T, L: (0, 0)),
            scratch_shapes=[pltpu.VMEM((pages * rows, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((pages * rows, width), pool.dtype),
        interpret=interpret, name=f"dma_wait_probe_{mode}",
    )(table, live, pool)


def main():
    interpret = False
    if os.environ.get("PT_PALLAS_INTERPRET") == "1":
        interpret = pltpu.InterpretParams(dma_execution_mode="on_wait")
    elif jax.devices()[0].platform != "tpu":
        print("dma_wait_probe: needs a TPU, JAX reports "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 2
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "interpret": bool(interpret)}), flush=True)
    rng = np.random.default_rng(0)
    wrong = 0
    for pages, rows, width in CASES:
        n = 4 * pages
        pool = jnp.asarray(rng.standard_normal((n, rows, width)),
                           jnp.bfloat16)
        table = rng.permutation(n)[:pages].astype(np.int32)
        for mode in MODES:
            # `once` waits for the whole buffer, so it starts every page;
            # `bits` takes a count with several digits set
            live = pages if mode == "once" else pages - max(pages // 4, 1)
            want = np.full((pages * rows, width), np.nan, np.float32)
            want[:live * rows] = np.asarray(
                pool[table[:live]], np.float32).reshape(-1, width)
            line = {"pages": pages, "page_bytes": rows * width * 2,
                    "mode": mode, "live": live}
            print(json.dumps({**line, "state": "started"}), flush=True)
            run = jax.jit(functools.partial(
                probe, pages=pages, rows=rows, mode=mode, rounds=1,
                interpret=interpret))
            ops = (pool, jnp.asarray(table), jnp.asarray([live], jnp.int32))
            ok = []
            for _ in range(2):
                got = np.asarray(run(*ops), np.float32)
                ok.append(bool(np.array_equal(got, want, equal_nan=True)))
            line["right"] = ok
            wrong += not all(ok)
            if not interpret:
                timed = jax.jit(functools.partial(
                    probe, pages=pages, rows=rows, mode=mode, rounds=ROUNDS,
                    interpret=False))
                timed(*ops).block_until_ready()
                best = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    timed(*ops).block_until_ready()
                    best.append(time.perf_counter() - t0)
                line["us_a_round"] = min(best) * 1e6 / ROUNDS
                line["ns_a_page"] = min(best) * 1e9 / ROUNDS / live
            print(json.dumps(line), flush=True)
    print(json.dumps({"wrong_cases": wrong}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
