"""The gated delta rule's two forms (ISSUE 37, ``ops/pallas/gated_delta.py``)
against the recurrence token by token, in float32: the chunked form of a
prefill chunk (a state carried in, lengths that are no multiple of the block,
padding behind ``upto``) and the decode kernel (interpret mode against its
``lax`` twin and against the recurrence; dead rows at the null slot; a slot no
row names left bit for bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import kv_cache as kvc
from paddle_tpu.ops.pallas import gated_delta as gd


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(rng, t, key_heads, heads, n, p):
    """What a layer hands the recurrence for ``t`` tokens: q and k as a
    convolution leaves them (not normed), decays of e^-1.6 .. 1."""
    f32 = jnp.float32
    return (jnp.asarray(rng.normal(size=(t, key_heads, n)), f32),
            jnp.asarray(rng.normal(size=(t, key_heads, n)), f32),
            jnp.asarray(rng.normal(size=(t, heads, p)), f32),
            -jnp.asarray(rng.uniform(0.0, 1.6, size=(t, heads)), f32),
            jnp.asarray(rng.uniform(0.05, 0.95, size=(t, heads)), f32))


@pytest.mark.parametrize("t,block", [(1, 8), (7, 8), (8, 8), (37, 8),
                                     (64, 64), (130, 64), (300, 64)])
def test_the_chunked_form_is_the_recurrence_token_by_token(t, block):
    """From a carried state that is not zero; a position with ``g = 0`` and
    ``beta = 0`` changes nothing."""
    rng = np.random.default_rng(t)
    key_heads, heads, n, p = 2, 4, 16, 8
    q, k, v, g, beta = operands(rng, t, key_heads, heads, n, p)
    s0 = jnp.asarray(rng.normal(size=(heads, n, p)), jnp.float32)
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta, s0)
    got_o, got_s = gd.gated_delta_chunk(q, k, v, g, beta, s0, block=block)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=1e-5)
    # padding: the same tokens with dead positions behind them
    pad = lambda m, x: jnp.concatenate(  # noqa: E731
        [m, jnp.full((5,) + m.shape[1:], x, m.dtype)])
    padded_o, padded_s = gd.gated_delta_chunk(
        pad(q, 1.0), pad(k, 1.0), pad(v, 1.0), pad(g, 0.0), pad(beta, 0.0),
        s0, block=block)
    np.testing.assert_allclose(padded_s, want_s, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(padded_o[:t], want_o, atol=1e-5, rtol=1e-5)


def test_a_chunk_cut_in_two_carries_its_state():
    rng = np.random.default_rng(5)
    q, k, v, g, beta = operands(rng, 100, 2, 4, 16, 8)
    s0 = jnp.zeros((4, 16, 8), jnp.float32)
    whole_o, whole_s = gd.gated_delta_chunk(q, k, v, g, beta, s0)
    cut = 41                                   # inside a block
    o1, s1 = gd.gated_delta_chunk(q[:cut], k[:cut], v[:cut], g[:cut],
                                  beta[:cut], s0)
    o2, s2 = gd.gated_delta_chunk(q[cut:], k[cut:], v[cut:], g[cut:],
                                  beta[cut:], s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), whole_o, atol=1e-5)
    np.testing.assert_allclose(s2, whole_s, atol=1e-5)


def test_the_inverse_of_a_block_is_the_triangular_solve():
    rng = np.random.default_rng(2)
    m = np.tril(rng.normal(size=(3, 64, 64)), -1).astype(np.float32) * 0.3
    inv = np.asarray(gd._inverse_unit_lower(jnp.asarray(m)))
    np.testing.assert_allclose(inv @ (np.eye(64) + m),
                               np.broadcast_to(np.eye(64), m.shape), atol=1e-4)


def test_q_and_k_are_normed_a_head_and_q_scaled():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(5, 2, 16)) * 7, jnp.float32)
    k = jnp.asarray(rng.normal(size=(5, 2, 16)) * 0.1, jnp.float32)
    qn, kn = gd.normed_qk(q, k)
    np.testing.assert_allclose(jnp.sum(kn * kn, -1), 1.0, atol=1e-3)
    np.testing.assert_allclose(jnp.sum(qn * qn, -1), 1.0 / 16, atol=1e-5)


@pytest.mark.parametrize("key_heads,heads,n,p", [(2, 4, 128, 128), (2, 4, 16, 16),
                                                 (1, 3, 16, 8)],
                         ids=["a-head-a-lane-row", "toy", "three-heads-a-key"])
def test_the_decode_kernel_is_its_lax_form_and_touches_live_slots_only(
        key_heads, heads, n, p, monkeypatch):
    """Interpret mode against the ``lax`` form and against the recurrence
    written out; rows 1 and 3 are dead (the null slot, twice); a slot no row
    names is left bit for bit."""
    rng = np.random.default_rng(0)
    bsz, slots_n = 5, 7
    spec = kvc.KVLayerSpec("state", heads, 2 * key_heads * n + heads * p, p,
                           conv_rows=3, state_dim=n)
    natural = rng.normal(size=(slots_n, heads, n, p)).astype(np.float32)
    state = jnp.asarray(natural)
    if spec.heads_a_lane_row == 1:
        assert state.shape == spec.state_shapes(slots_n)[1]
    slots = jnp.asarray([3, 6, 0, 6, 1], jnp.int32)
    q, k, v, g, beta = operands(rng, bsz, key_heads, heads, n, p)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    o_lax, s_lax = gd.gated_delta_decode_update(state, slots, q, k, v, g, beta)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    o_k, s_k = gd.gated_delta_decode_update(state, slots, q, k, v, g, beta)
    live = [0, 2, 4]
    np.testing.assert_allclose(np.asarray(o_k)[live], np.asarray(o_lax)[live],
                               atol=1e-5)
    for i in live:
        o1, s1 = gd.gated_delta_recurrence(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1], beta[i:i + 1],
            natural[int(slots[i])])
        np.testing.assert_allclose(o_k[i], o1[0], atol=1e-4)
        for got in (s_k, s_lax):
            np.testing.assert_allclose(got[int(slots[i])], s1, atol=1e-5)
    for got in (s_k, s_lax):
        for untouched in (2, 4, 5):
            np.testing.assert_array_equal(got[untouched], natural[untouched])


def test_the_kernel_refuses_a_state_that_is_not_a_head_a_lane_row(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(1)
    q, k, v, g, beta = operands(rng, 2, 2, 4, 16, 8)
    packed = jnp.zeros((3, 2, 16, 16), jnp.float32)   # two heads a lane row
    with pytest.raises(ValueError, match="a value head a lane row"):
        gd.gated_delta_decode_update(packed, jnp.asarray([0, 1]), q, k, v, g,
                                     beta)
    with pytest.raises(ValueError, match="float32 states"):
        gd.gated_delta_decode_update(
            jnp.zeros((3, 4, 16, 8), jnp.bfloat16), jnp.asarray([0, 1]), q, k,
            v, g, beta)


def test_the_chunked_form_is_a_jit_of_its_name():
    """The kernel's own name is held by ``tests/test_engine_spans.py``; the
    chunked form is XLA under a ``jax.jit`` of its name."""
    rng = np.random.default_rng(1)
    q, k, v, g, beta = operands(rng, 2, 2, 4, 16, 8)
    chunk = jax.jit(lambda *a: gd.gated_delta_chunk(*a)).lower(
        q, k, v, g, beta, jnp.zeros((4, 16, 8), jnp.float32)).as_text()
    assert "gated_delta_chunk" in chunk
