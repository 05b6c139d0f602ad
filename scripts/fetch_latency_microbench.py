"""What a serving step pays between the device finishing and the host
holding a small result: a jitted call of ~15 ms of device work that gives
``[64]`` int32 (and a ``[64, 4096]`` float32 beside it), fetched in several
ways. Prints, for each way, the median and the quartiles of (dispatch to
result in hand) less the device time of the call, in microseconds.
Run on the chip: ``python3 scripts/fetch_latency_microbench.py``."""
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

N, ITERS = 200, 20


@jax.jit
def work(x, i):
    y = jax.lax.fori_loop(0, ITERS, lambda _, c: jnp.tanh(c @ x), x)
    rows = y[:64].astype(jnp.float32)
    return jnp.argmax(rows, -1).astype(jnp.int32) + i, rows


def plain(tok, rows):
    return np.asarray(tok)


def copy_async(tok, rows):
    tok.copy_to_host_async()
    return np.asarray(tok)


def spin(tok, rows):
    while not tok.is_ready():
        pass
    return np.asarray(tok)


def copy_async_spin(tok, rows):
    tok.copy_to_host_async()
    while not tok.is_ready():
        pass
    return np.asarray(tok)


def block_first(tok, rows):
    tok.block_until_ready()
    return np.asarray(tok)


def rows_plain(tok, rows):
    return np.asarray(rows)


def rows_copy_async(tok, rows):
    rows.copy_to_host_async()
    return np.asarray(rows)


def main():
    x = jnp.full((4096, 4096), 0.01, jnp.bfloat16)
    i = jnp.int32(1)
    work(x, i)[0].block_until_ready()
    t = time.perf_counter()
    outs = [work(x, i) for _ in range(40)]
    outs[-1][0].block_until_ready()
    device_s = (time.perf_counter() - t) / 40
    out = {"device_ms": device_s * 1e3, "backend": jax.default_backend()}
    for way in (plain, copy_async, spin, copy_async_spin, block_first,
                rows_plain, rows_copy_async, plain):
        over = []
        for _ in range(N):
            t = time.perf_counter()
            tok, rows = work(x, i)
            way(tok, rows)
            over.append((time.perf_counter() - t - device_s) * 1e6)
        q = statistics.quantiles(over, n=4)
        out.setdefault(way.__name__, []).append(
            [round(q[0]), round(q[1]), round(q[2])])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
