"""The decode kernel's DMA semaphores keep their books (ISSUE 32).

``_kernel`` starts one copy a live page onto a slot's semaphore and waits
with descriptors as large as SEVERAL pages. Under
``pltpu.InterpretParams(dma_execution_mode="on_wait")`` a semaphore counts
bytes and a copy moves only when its bytes are waited for, so a wait for
more than was started never returns and a wait for less leaves pages
unmoved (NaN here). Each case therefore runs in a process of its own under a
time limit: equal to the lax path AND finished.

Run as a script, this file is that process: ``python
tests/test_paged_decode_dma_books.py <kind> <chunk>``.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK, WINDOW = 4, 32

#: kind -> heads, kv heads, K width, V width (the latent kind: one row a
#: token, the values its first ``dv`` lanes)
KINDS = {"gqa": (4, 2, 16, 16), "global": (4, 2, 32, 16),
         "window": (4, 2, 32, 16), "latent": (4, 1, 48, 32)}


def _case(kind, chunk):
    """Contexts at every edge of a chunk, an empty request between two live
    ones, pages dealt at random, and two pools a kind: clean, and with NaN
    in the null page and in every slot that holds no live token."""
    rng = np.random.default_rng(chunk)
    h, hkv, dk, dv = KINDS[kind]
    c = chunk * BLOCK
    if kind == "window":
        p_max = WINDOW // BLOCK + 1
        # live pages 1, chunk (a token short, exactly), chunk + 1, a whole
        # window, then contexts whose oldest live page is a ring slot
        lens = [1, c - 1, c, 0, c + 1, WINDOW, 100, 101, 1000]
    else:
        p_max = 2 * chunk + chunk // 2
        lens = [1, c - 1, c, 0, c + 1, 2 * c, p_max * BLOCK, 2 * c + 3]
    lens = np.asarray(lens, np.int32)
    held = [min(-(-int(n) // BLOCK), p_max) for n in lens]
    n = sum(held) + 1
    free = list(rng.permutation(np.arange(1, n)))
    tables = np.zeros((len(lens), p_max), np.int32)
    live = np.zeros((n, BLOCK), bool)
    for b, ctx in enumerate(int(x) for x in lens):
        last = -(-ctx // BLOCK)
        for page in range(last - held[b], last):
            blk = free.pop()
            tables[b, page % p_max] = blk
            live[blk, :min(BLOCK, ctx - page * BLOCK)] = True
    shapes = [(n, BLOCK, dk)] if kind == "latent" else \
        [(n, BLOCK, hkv, dk), (n, BLOCK, hkv, dv)]
    clean = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    dirty = [np.where(live.reshape(n, BLOCK, *[1] * (x.ndim - 2)), x, np.nan)
             for x in clean]
    q = rng.standard_normal((len(lens), h, dk)).astype(np.float32)
    sink = rng.standard_normal(h).astype(np.float32) \
        if kind in ("global", "window") else None
    return lens, tables, q, sink, clean, dirty


def main(kind, chunk):
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    sys.path.insert(0, ROOT)
    from paddle_tpu.inference.serving import paged_attention as spa
    from paddle_tpu.ops.pallas import paged_attention as pa

    h, hkv, dk, dv = KINDS[kind]
    lens, tables, q, sink, clean, dirty = _case(kind, chunk)
    tables, lens_j, q = jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(q)
    window = WINDOW if kind == "window" else None
    extra = dict(window=window, ring=window is not None,
                 sink=None if sink is None else jnp.asarray(sink))
    # the budget that holds `chunk` pages and not twice as many
    plan = lambda p: pa._decode_chunk(                          # noqa: E731
        BLOCK, hkv, h, dk, 4, p, 0 if kind == "latent" else dv)
    pa._DECODE_VMEM_BUDGET = plan(chunk)[1]
    assert plan(tables.shape[1])[0] == chunk, plan(tables.shape[1])
    if kind == "latent":
        want = spa.paged_decode_attention_latent(      # the lax path: no TPU
            q, jnp.asarray(clean[0]), tables, lens_j, 0.3, dv)
    else:
        want = spa._lax_fallback(q[:, None], *map(jnp.asarray, clean), tables,
                                 lens_j, 0.3, **extra)[:, 0]
    pa._interpret = lambda: pltpu.InterpretParams(
        dma_execution_mode="on_wait")
    if kind == "latent":
        got = pa.paged_decode_attention_latent_pallas(
            q, jnp.asarray(dirty[0]), tables, lens_j, 0.3, dv)
    else:
        got = pa.paged_decode_attention_pallas(
            q, *map(jnp.asarray, dirty), tables, lens_j, 0.3, **extra)
    rows = lens > 0
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               atol=2e-5)
    print("books kept:", kind, chunk)


def _run(args, seconds):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PT_PALLAS_INTERPRET": "0"}
    return subprocess.run([sys.executable, *args], env=env, timeout=seconds,
                          capture_output=True, text=True)


@pytest.mark.parametrize("kind,chunk", [
    ("gqa", 4), ("gqa", 32), ("global", 4), ("global", 32),
    ("window", 4), ("window", 8), ("latent", 4), ("latent", 32)])
def test_every_wait_is_for_the_bytes_that_were_started(kind, chunk):
    done = _run([__file__, kind, str(chunk)], 300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    assert f"books kept: {kind} {chunk}" in done.stdout


def test_that_interpreter_does_not_return_from_a_wait_for_too_much():
    """What makes the test above a test: one page fewer started than the
    single wait is for, and the process never ends."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]);"
        "import jax.numpy as jnp, dma_wait_probe as p;"
        "from jax.experimental.pallas import tpu as pltpu;"
        "print(p.probe(jnp.ones((8, 8, 128), jnp.float32),"
        " jnp.arange(4, dtype=jnp.int32), jnp.asarray([int(sys.argv[2])]),"
        " pages=4, rows=8, mode='once', rounds=1, interpret=pltpu."
        "InterpretParams(dma_execution_mode='on_wait')).sum())")
    args = ["-c", code, os.path.join(ROOT, "scripts")]
    assert _run(args + ["4"], 120).returncode == 0
    try:
        done = _run(args + ["3"], 30)
    except subprocess.TimeoutExpired:
        return
    assert done.returncode != 0, done.stdout[-2000:]


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
