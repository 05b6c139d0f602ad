"""MiMo-V2's held experts through the grouped feed-forward kernel (ISSUE 30),
in interpret mode at widths the kernel takes (multiples of 128; the toy
widths of ``mimo_v2_tiny`` are the tile loop's), against the tile loop and
against the float32 reference (``benchmarks/harness/reference_mimo_v2.py``,
which shares no code with ``paddle_tpu``)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM, mimo_v2_tiny,
                                       moe_dropless)
from paddle_tpu.ops.pallas import grouped_ffn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_mimo_v2 as ref  # noqa: E402

D, F, E, TOP_K = 256, 128, 12, 4
HELD = {"all": tuple(range(E)), "a-share": (3, 9, 10, 11), "one": (5,)}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def layer():
    """An expert layer's weights under the names ``named_parameters`` gives
    them, every expert of the router held."""
    rng = np.random.default_rng(0)
    w = {"post_attention_layernorm.weight": jnp.ones(D),
         "mlp.router.weight": jnp.asarray(rng.normal(size=(D, E)) * 0.1,
                                          jnp.float32),
         "mlp.router.e_score_correction_bias": jnp.asarray(
             rng.normal(size=E) * 0.01, jnp.float32)}
    for e in range(E):
        for name, shape in (("gate", (D, F)), ("up", (D, F)), ("down", (F, D))):
            w[f"mlp.experts.{e}.{name}_proj.weight"] = jnp.asarray(
                rng.normal(size=shape) * 0.05, jnp.float32)
    return w


def _block(w, x, held, *, kernel, monkeypatch, bias=None, **kw):
    """``moe_dropless`` for the share ``held``, through the kernel or the
    tile loop."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1" if kernel else "0")
    slot = np.full(E, len(held), np.int32)
    slot[list(held)] = np.arange(len(held))
    experts = [tuple(w[f"mlp.experts.{e}.{m}_proj.weight"]
                     for m in ("gate", "up", "down")) for e in held]
    return moe_dropless(
        x, w["mlp.router.weight"],
        w["mlp.router.e_score_correction_bias"] if bias is None else bias,
        experts, slot, top_k=TOP_K, **kw)


def _normed(t, seed):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(t, D)),
                    jnp.float32)
    return jnp.asarray(ref._rms(x, jnp.ones(D), 0.0))


def _reference_part(w, x, held, bias=None):
    """What the float32 reference gives for the share ``held`` on rows that
    are already normed (its own norm at eps 0 leaves them as they are up to
    rounding)."""
    w = dict(w)
    if bias is not None:
        w["mlp.router.e_score_correction_bias"] = bias
    named = dict(w)
    for row, e in enumerate(held):        # it names an expert by its row
        for m in ("gate", "up", "down"):
            named[f"mlp.experts.{row}.{m}_proj.weight"] = \
                w[f"mlp.experts.{e}.{m}_proj.weight"]
    out, _ = ref._expert_ffn(x[None], named, eps=0.0, top_k=TOP_K,
                             norm_topk=True, scaling=None, held=tuple(held))
    return np.asarray(out)[0] - np.asarray(x)


@pytest.mark.parametrize("held", list(HELD))
@pytest.mark.parametrize("tokens", [1, 5, 64, 300])
def test_the_kernel_gives_what_the_loop_and_the_reference_give(
        layer, tokens, held, monkeypatch):
    ids = HELD[held]
    x = _normed(tokens, seed=tokens)
    want = _reference_part(layer, x, ids)
    loop = _block(layer, x, ids, kernel=False, monkeypatch=monkeypatch,
                  with_passes=True)
    got = _block(layer, x, ids, kernel=True, monkeypatch=monkeypatch,
                 with_passes=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(loop[0]),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    # the three values the block returns are what they were
    assert [int(v) for v in got[1:3]] == [int(v) for v in loop[1:3]]
    if held == "one":
        three = _block(layer, x, ids, kernel=True, monkeypatch=monkeypatch)
        assert len(three) == 3
        assert [int(v) for v in three[1:]] == [int(v) for v in got[1:3]]
        np.testing.assert_array_equal(np.asarray(three[0]), np.asarray(got[0]))
    pairs, hit, passes = (int(v) for v in got[1:])
    assert hit <= min(len(ids), pairs) and passes >= hit
    if tokens <= 64:
        # all of an expert's rows in one pass: each hit expert's weights
        # are streamed exactly once
        assert passes == hit


@pytest.mark.parametrize("case", ["an-expert-without-a-token",
                                  "one-expert-with-every-token",
                                  "rows-over-several-passes",
                                  "no-pair-routed-here"])
def test_the_kernel_at_the_edges_of_the_routing(layer, case, monkeypatch):
    ids = HELD["a-share"]
    bias = np.zeros(E, np.float32)
    tokens, tm, want_passes = 40, None, None
    if case == "an-expert-without-a-token":
        bias[9] = -10.0                   # held, never chosen
    elif case == "one-expert-with-every-token":
        bias[[10, 0, 1, 2]] = 10.0        # 10 is held; 0, 1, 2 are not
        want_passes = 1
    elif case == "rows-over-several-passes":
        bias[[10, 11, 1, 2]] = 10.0
        tokens, tm, want_passes = 300, 32, 2 * -(-300 // 32)
    else:
        bias[list(ids)] = -10.0
        want_passes = 0
    bias = jnp.asarray(bias)
    x = _normed(tokens, seed=11)
    want = _reference_part(layer, x, ids, bias)
    y, pairs, hit, passes = _block(layer, x, ids, kernel=True, bias=bias,
                                   monkeypatch=monkeypatch, tm=tm,
                                   with_passes=True)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    if case == "an-expert-without-a-token":
        assert int(hit) == len(ids) - 1
    elif case == "one-expert-with-every-token":
        assert (int(pairs), int(hit)) == (tokens, 1)    # dropless
    elif case == "rows-over-several-passes":
        assert (int(pairs), int(hit)) == (2 * tokens, 2)
    else:
        assert (int(pairs), int(hit)) == (0, 0) and not np.asarray(y).any()
    if want_passes is not None:
        assert int(passes) == want_passes


def test_the_gate_takes_the_chip_and_the_widths_it_can(monkeypatch):
    """The CPU's path is the loop; interpret mode takes the kernel at widths
    it can; on the chip another width is an error, not a fallback."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    assert not grouped_ffn.use_pallas_grouped_ffn(4096, 2048)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    assert grouped_ffn.use_pallas_grouped_ffn(4096, 2048)
    assert not grouped_ffn.use_pallas_grouped_ffn(64, 32)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert grouped_ffn.use_pallas_grouped_ffn(4096, 2048)
    with pytest.raises(ValueError, match="multiples of 128"):
        grouped_ffn.use_pallas_grouped_ffn(4096, 2000)


def test_the_weight_blocks_follow_from_the_widths():
    """2 MiB a block at the published widths: 512 rows of gate and of up,
    256 of down; a width under a block is one block."""
    assert grouped_ffn._block_rows(4096, 2048, 2, 2 << 20) == 512
    assert grouped_ffn._block_rows(2048, 4096, 2, 2 << 20) == 256
    assert grouped_ffn._block_rows(256, 128, 4, 2 << 20) == 256
    assert grouped_ffn._block_rows(384, 128, 4, 128 * 128 * 4 * 2) == 128


@functools.cache
def _served(path):
    """Three requests through an engine whose expert widths the kernel
    takes and which holds every second expert: (``metrics()``, the tokens a
    request)."""
    before = os.environ.get("PT_PALLAS_INTERPRET")
    os.environ["PT_PALLAS_INTERPRET"] = "1" if path == "kernel" else "0"
    try:
        paddle_tpu.seed(3)
        net = MiMoV2ForCausalLM(mimo_v2_tiny(
            hidden_size=128, moe_intermediate_size=128, num_hidden_layers=3,
            hybrid_layer_pattern=(0, 1, 1), moe_layer_freq=(0, 1, 1),
            n_routed_experts=16, experts_held=tuple(range(0, 16, 2))))
        net.eval()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 160, size=n).astype(np.int32)
                   for n in (11, 19, 7)]
        with LLMEngine(net, num_blocks=64, block_size=4, max_batch_size=4,
                       max_model_len=64, prefill_buckets=[8, 16, 32],
                       max_prefill_tokens_per_step=16) as eng:
            rids = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                    for p in prompts]
            tokens = {r: [] for r in rids}
            while eng.has_work():
                for out in eng.step():
                    tokens[out.rid].append(int(out.token))
            return eng.metrics(), list(tokens.values())
    finally:
        if before is None:
            del os.environ["PT_PALLAS_INTERPRET"]
        else:
            os.environ["PT_PALLAS_INTERPRET"] = before


@pytest.mark.parametrize("path", ["loop", "kernel"])
def test_the_engine_counts_a_pass_a_hit_expert_in_decode(path):
    """``moe_weight_passes`` beside ``moe_experts_hit``: equal over decode
    steps (a step's rows fit one pass), and the tokens are the same through
    the kernel as through the loop."""
    m, tokens = _served(path)
    assert m["moe_experts_hit_decode"] > 0
    assert m["moe_weight_passes_decode"] == m["moe_experts_hit_decode"]
    assert m["moe_weight_passes"] >= m["moe_experts_hit"]
    assert m["moe_weight_passes_prefill"] >= m["moe_experts_hit_prefill"]
    assert tokens == _served("loop")[1]
