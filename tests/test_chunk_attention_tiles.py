"""The chunk kernel's fold and its grid.

* In interpret mode at float32 the kernel is held to a dense reference
  across chunk offsets, ragged and wholly padded query tiles, negative key
  positions, a window with a sink, group sizes and widths; and its running
  max and sum, held in 128 lanes, to the same fold holding them in one
  lane, bit for bit.
* ``chunk_tile_counts`` (live and dead grid steps, from the kernel's own
  tile arithmetic) is held to a brute-force check of the mask over every
  tile, at the serving configurations' chunk shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa

#: (id, rows a chunk, real rows, query heads a kv head, kv heads, K / V
#: width, chunk offset, position of key row 0, keys in the row, window,
#: sink)
CASES = [
    ("offset0-g1-k256v128", 512, 512, 1, 2, 256, 128, 0, 0, 512, None, False),
    ("offset256-g4-k128v128", 512, 512, 4, 1, 128, 128, 256, 0, 1024, None,
     False),
    ("offset2048-g1-k256v128-upto-in-last-tile", 512, 400, 1, 1, 256, 128,
     2048, 0, 2560, None, False),
    ("offset8192-g1-k256v128", 512, 512, 1, 1, 256, 128, 8192, 0, 8704,
     None, False),
    ("offset2048-g8-k256v256", 512, 512, 8, 1, 256, 256, 2048, 0, 2560,
     None, False),
    ("offset256-g16-k128v128-padded-query-tiles", 256, 100, 16, 1, 128, 128,
     256, 0, 512, None, False),
    ("offset2048-g16-k256v256-upto-in-last-tile", 256, 230, 16, 2, 256, 256,
     2048, 0, 2304, None, False),
    ("window128-sink-g8-negative-k-start", 256, 256, 8, 2, 256, 128, 0, -128,
     384, 128, True),
    ("window128-sink-g8-offset2048", 256, 200, 8, 1, 256, 128, 2048, 1920,
     384, 128, True),
    ("offset2048-g4-k128v128-sink", 256, 256, 4, 2, 128, 128, 2048, 0, 2304,
     None, True),
]


def _operands(case):
    _, t, tokens, groups, hkv, dk, dv, start, k_start, ln, window, sink = case
    rng = np.random.default_rng(t + groups + start + ln)
    h = groups * hkv
    q = rng.standard_normal((t, h, dk)).astype(np.float32)
    k = rng.standard_normal((ln, hkv, dk)).astype(np.float32)
    v = rng.standard_normal((ln, hkv, dv)).astype(np.float32)
    s = rng.standard_normal(h).astype(np.float32) if sink else None
    return q, k, v, s


def _dense(q, k, v, sink, start, k_start, upto, scale, window):
    """Every real row's attention in float64, its mask written out."""
    t, h, _ = q.shape
    groups = h // k.shape[1]
    qp = start + np.arange(t)[:, None]
    kp = k_start + np.arange(k.shape[0])[None, :]
    ok = (kp <= qp) & (kp >= 0) & (kp < upto)
    if window is not None:
        ok &= kp > qp - window
    out = np.zeros((t, h, v.shape[-1]))
    for head in range(h):
        kh, vh = (x[:, head // groups].astype(np.float64) for x in (k, v))
        s = np.where(ok, q[:, head].astype(np.float64) @ kh.T * scale,
                     -np.inf)
        m = s.max(1, keepdims=True)
        if sink is not None:
            m = np.maximum(m, sink[head])
        p = np.exp(s - m)
        den = p.sum(1, keepdims=True)
        if sink is not None:
            den += np.exp(sink[head] - m)
        out[:, head] = p @ vh / den
    return out


def _call(case, q, k, v, sink):
    _, _, tokens, _, _, dk, _, start, k_start, _, window, _ = case
    return np.asarray(pa.chunk_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), start, k_start,
        start + tokens, dk ** -0.5, window=window,
        sink=None if sink is None else jnp.asarray(sink),
        name="chunk_attention_global"))


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_fold_matches_dense_reference(case, monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    _, _, tokens, _, _, dk, _, start, k_start, _, window, _ = case
    q, k, v, sink = _operands(case)
    with jax.default_matmul_precision("highest"):
        got = _call(case, q, k, v, sink)
    want = _dense(q, k, v, sink, start, k_start, start + tokens, dk ** -0.5,
                  window)[:tokens]
    # |kernel - reference| / |reference| over the real rows (Euclidean):
    # float32's own rounding reads 4e-7 to 7e-7 here
    err = np.linalg.norm(got[:tokens] - want) / np.linalg.norm(want)
    assert err < 1e-6, err


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_running_max_and_sum_in_lanes_are_the_one_lane_fold(case,
                                                            monkeypatch):
    """The running max and sum held in 128 lanes give, bit for bit, what
    the same fold gives with them held ``[rows, 1]`` (``_LANES`` 1)."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    tokens = case[2]
    q, k, v, sink = _operands(case)
    lanes = _call(case, q, k, v, sink)[:tokens]
    monkeypatch.setattr(pa, "_LANES", 1)
    one_lane = _call(case, q, k, v, sink)[:tokens]
    assert np.array_equal(lanes, one_lane)


#: (id, chunk offset, real rows, rung, query heads, kv heads, keys in the
#: row, window, position of key row 0) at the serving configurations'
#: chunk shapes, and the small cases above
COUNTS = [
    (f"{name}-at{start}", start, tokens, 2048, h, hkv, ln, None, 0)
    for name, h, hkv, ln in (("joyai-expanded", 32, 32, 12288),
                             ("mimo-global", 64, 4, 12288),
                             ("mistral", 32, 8, 4096),
                             ("nemotron", 32, 2, 4096),
                             ("qwen3next", 16, 2, 10240))
    for start, tokens in ((0, 2048), (2048, 2048), (6144, 1500),
                          (10240, 2048))
    if start + 2048 <= ln
] + [
    (f"mimo-window-at{start}", start, tokens, 2048, 64, 8, 128 + 2048, 128,
     start - 128)
    for start, tokens in ((0, 2048), (2048, 777), (6144, 2048))
] + [
    (c[0], c[7], c[2], c[1], c[3] * c[4], c[4], c[9], c[10], c[8])
    for c in CASES
]


@pytest.mark.parametrize("case", COUNTS, ids=[c[0] for c in COUNTS])
def test_tile_counts_match_the_mask_tile_by_tile(case):
    _, start, tokens, rung, h, hkv, ln, window, k_start = case
    tq, tk, n_k = pa._chunk_tiles(rung, h // hkv, ln, window)
    upto = start + tokens
    qp = start + np.arange(rung)[:, None]
    kp = k_start + np.arange(ln)[None, :]
    ok = (kp <= qp) & (kp >= 0) & (kp < upto)
    if window is not None:
        ok &= kp > qp - window
    tiles = ok.reshape(rung // tq, tq, ln // tk, tk)
    seen = tiles.any(axis=(1, 3))
    live, dead = pa.chunk_tile_counts(start, tokens, rung, h, hkv, ln, window,
                                      k_start)
    # a tile that some row sees is folded, and the grid's other steps fold
    # nothing
    assert live == hkv * int(seen.sum()), (live, seen.sum())
    assert live + dead == hkv * (rung // tq) * n_k
    # every tile a row sees lies inside its query tile's walk of n_k steps
    assert int(seen.sum(1).max()) <= n_k
