"""A Llama-form layer's prefill chunk reads the request's pages in a row
through the chunk kernel (ISSUE 36): ``ChunkAttnState.attend`` on 4-D pools
in interpret mode against the gather fallback, the pools it writes, which
reader int8 pools and the CPU keep, and the call per shard under a plan."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import paged_attention as spa
from paddle_tpu.inference.serving.kv_cache import (KVLayerSpec,
                                                   quantize_kv_rows)

H, HKV, D, BS, N_PAGES, P, C = 8, 2, 128, 16, 12, 8, 32
SPEC = KVLayerSpec("global", HKV, D, D)
SCALE = 1.0 / np.sqrt(D)
#: (start, real tokens of the chunk): the request's first chunk, one chunk
#: in, and a last chunk whose tail is padding
CHUNKS = {"offset-0": (0, C), "one-chunk-in": (C, C), "padded-last": (2 * C, 21)}


def _kernels(fn, *args):
    """The Pallas calls of ``fn``'s lowered text, by their names (a name
    enters the name stack of what the call lowers to:
    ``tests/test_engine_spans.py``)."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return {name for name in ("chunk_attention", "paged_prefill_attention")
            if re.search(r'loc\("(?:[^"]*[/(])?' + name + r'[/)]', text)}


def _case(dtype, start, seed=0):
    """Pools holding ``start`` tokens of one request in scattered pages (and
    noise everywhere else), and the next chunk's q, k and v."""
    rng = np.random.RandomState(seed)
    tables_row = np.zeros(P, np.int32)
    tables_row[:6] = [7, 3, 9, 1, 10, 5]          # 0: the null block
    pools = [jnp.asarray(rng.randn(N_PAGES, BS, HKV, D), dtype)
             for _ in range(2)]
    q, k, v = (jnp.asarray(rng.randn(1, C, h, D), dtype)
               for h in (H, HKV, HKV))
    return pools, jnp.asarray(tables_row), q, k, v


def _attend(pools, tables_row, q, k, v, start, upto, scales=(None, None)):
    st = spa.ChunkAttnState(
        SPEC, BS, jnp.int32(start), jnp.int32(upto), tables_row, *pools,
        *scales, counters=None)
    out = st.attend(q, k, v, SCALE)
    return out, st.k_pool, st.v_pool


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", list(CHUNKS))
def test_a_paged_layers_chunk_reads_its_pages_in_a_row(chunk, dtype, atol,
                                                       monkeypatch):
    start, real = CHUNKS[chunk]
    upto = start + real
    pools, tables_row, q, k, v = _case(dtype, start)
    # what the old branch did: the same page write, then the gather fallback
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    ref, ref_k, ref_v = _attend(pools, tables_row, q, k, v, start, upto)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    assert _kernels(lambda *a: _attend(*a, start, upto)[0], pools,
                    tables_row, q, k, v) == {"chunk_attention"}
    out, new_k, new_v = _attend(pools, tables_row, q, k, v, start, upto)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out[0, :real], np.float32),
        np.asarray(ref[0, :real], np.float32), atol=atol)
    np.testing.assert_array_equal(np.asarray(new_k, np.float32),
                                  np.asarray(ref_k, np.float32))
    np.testing.assert_array_equal(np.asarray(new_v, np.float32),
                                  np.asarray(ref_v, np.float32))
    # the pages the chunk wrote hold its rows, the others what they held
    blks = np.asarray(tables_row)[start // BS:start // BS + C // BS]
    np.testing.assert_array_equal(
        np.asarray(new_k[blks], np.float32).reshape(C, HKV, D),
        np.asarray(k[0], np.float32))
    others = np.setdiff1d(np.arange(N_PAGES), blks)
    np.testing.assert_array_equal(np.asarray(new_v[others], np.float32),
                                  np.asarray(pools[1][others], np.float32))


def test_int8_pools_keep_the_page_by_page_kernel(monkeypatch):
    """The chunk kernel takes no scales: a quantized chunk lowers to
    ``paged_prefill_attention`` as before, and gives what the gather
    fallback gives over the dequantized codes."""
    start, upto = C, C + 21
    pools, tables_row, q, k, v = _case(jnp.float32, start)
    codes, scales = zip(*(quantize_kv_rows(p) for p in pools))

    def run(*a):
        return _attend(list(a[:2]), *a[2:6], start, upto, tuple(a[6:]))[0]

    args = (*codes, tables_row, q, k, v, *scales)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    ref = run(*args)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    assert _kernels(run, *args) == {"paged_prefill_attention"}
    np.testing.assert_allclose(np.asarray(run(*args))[0, :21],
                               np.asarray(ref)[0, :21], atol=1e-4)


def test_off_the_tpu_a_paged_chunk_is_the_gather_fallback():
    """Bit for bit: the suites that compare chunked prefill with one-shot,
    and verify's arithmetic with prefill's, rest on it."""
    start, upto = C, 2 * C
    pools, tables_row, q, k, v = _case(jnp.float32, start)
    out, new_k, new_v = _attend(pools, tables_row, q, k, v, start, upto)
    ref = spa._lax_multiquery_fallback(
        q, new_k, new_v, tables_row[None], jnp.int32(upto)[None],
        jnp.int32(start)[None], float(SCALE))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_under_a_plan_each_shard_gathers_its_own_heads(monkeypatch):
    """Under an active plan the call runs per shard, q heads and the pools'
    kv heads split over the head axis, the table and positions replicated:
    the same rows as one device gives."""
    from jax.sharding import Mesh

    from paddle_tpu.distributed.plan import Plan, compile_step_with_plan

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    start, upto = C, C + 21
    pools, tables_row, q, k, v = _case(jnp.float32, start)
    ref = _attend(pools, tables_row, q, k, v, start, upto)[0]
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    plan = Plan.build(mesh, ["tp"])
    step = compile_step_with_plan(
        lambda *a: _attend(list(a[:2]), *a[2:], start, upto)[0], plan)
    text = step.trace(*pools, tables_row, q, k, v).lower().as_text()
    assert "shard_map" in text or "manual" in text
    out = step(*pools, tables_row, q, k, v)
    np.testing.assert_allclose(np.asarray(out)[0, :21],
                               np.asarray(ref)[0, :21], atol=1e-5)
