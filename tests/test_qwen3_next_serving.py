"""Qwen3-Next on the serving path (ISSUE 37), at toy widths that keep the
structure: a period ``L L L F`` (three gated delta layers before the
attention layer; two periods in the plain forward's test), 4 value heads over
2 key heads, 4 query heads over 2 kv heads with a gate a head and rotary on a
quarter, 16 softmax-routed experts top 4 beside a gated shared one.

The float32 reference is ``benchmarks/harness/reference_qwen3_next.py``: it
shares no code with ``paddle_tpu`` and runs the recurrence token by token.
What a wrong ``(1 + w)``, ``A_log``, ``dt_bias`` or shared gate would hide at
its default is DRAWN here (``build``).

The second half is the STATE'S LIFE under the second recurrence of the state
kind (``state.delta``): what ``tests/test_nemotron_h_serving.py`` holds for
``state.scan`` (a recycled slot, a decode step between two chunks, a drained
step, a dead row), held again because the delta rule READS the state before
it writes it."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.inference.serving import kv_cache as kvc
from paddle_tpu.models import Qwen3NextForCausalLM, qwen3_next_tiny
from paddle_tpu.models.mimo_v2 import (moe_dropless, sigmoid_scores,
                                       softmax_scores)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_qwen3_next as ref  # noqa: E402

ENGINE = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
              prefill_buckets=[8, 16, 32, 64, 96],
              max_prefill_tokens_per_step=16)
#: the parameters whose defaults would hide a fault
DRAWN = ("layernorm.weight", "norm.weight", "norm_weight", ".A_log",
         ".dt_bias", "shared_expert_gate.weight")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(seed=3, **kw):
    """The tiny model with its norms' weights, the decays' ``A_log`` and
    ``dt_bias`` and the shared expert's gate drawn away from their
    defaults."""
    paddle_tpu.seed(seed)
    net = Qwen3NextForCausalLM(qwen3_next_tiny(**kw))
    rng = np.random.default_rng(seed)
    for name, p in net.named_parameters():
        if name.endswith(DRAWN):
            wide = 1.0 if name.endswith("shared_expert_gate.weight") else 0.3
            p._rebind(p._data + jnp.asarray(
                rng.normal(0.0, wide, p._data.shape), p._data.dtype))
    net.eval()
    return net


def weights_of(net):
    return {n: p._data for n, p in net.named_parameters()}


def model_of(net):
    return dataclasses.asdict(net.config)


def prompts_of(lengths, seed=0, vocab=160):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


def rows_of(eng, prompts, n_new):
    """{(request, j): the logits row token j was sampled from} and the
    outputs (row 0 comes from a second pass of one-token requests)."""
    rows = {}

    def burst(lengths):
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, lengths)]
        seen = dict.fromkeys(rids, 0)
        while eng.has_work():
            for out in eng.step():
                j = seen[out.rid]
                seen[out.rid] += 1
                if j > 0 or out.finished:
                    rows[(rids.index(out.rid), j)] = \
                        eng.request(out.rid).last_logits.copy()
        toks = [list(eng.request(r).output_tokens) for r in rids]
        for r in rids:
            eng.release(r)
        return toks

    toks = burst([n_new] * len(prompts))
    burst([1] * len(prompts))
    return rows, toks


def generate(eng, prompt, n_new, **sampling):
    """One request alone to its end: ``(tokens, the logits row of each but
    the first)``."""
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=n_new,
                                                 **sampling))
    rows = []
    while not eng.request(rid).finished:
        for out in eng.step():
            if out.rid == rid:
                rows.append(eng.request(rid).last_logits.copy())
    toks = list(eng.request(rid).output_tokens)
    eng.release(rid)
    return toks, rows[1:]


def reference_rows(net, prompt, toks):
    want = np.asarray(ref.logits(
        weights_of(net), np.concatenate([prompt, toks])[None].astype(np.int32),
        model_of(net), experts_held=net.config.experts_held))[0]
    return [want[len(prompt) - 1 + j] for j in range(len(toks))]


# -- (a) against the reference --------------------------------------------------

@pytest.mark.parametrize("held", [None, tuple(range(4, 12))],
                         ids=["all-experts", "a-share"])
def test_the_models_plain_forward_matches_the_reference(held):
    net = build(experts_held=held, num_hidden_layers=8)
    ids = prompts_of((71,), seed=4)[0][None]
    got = np.asarray(net(jnp.asarray(ids))._data)[0]
    want = np.asarray(ref.logits(weights_of(net), ids, model_of(net),
                                 experts_held=net.config.experts_held))[0]
    for t in (0, 7, 8, 23, 63, 64, 70):
        assert ref.row_error(got[t], want[t]) < 2e-5, t
    assert [sp.kind for sp in net.kv_layout()] == (["state"] * 3
                                                   + ["global"]) * 2


@pytest.mark.parametrize("name", DRAWN)
def test_a_parameter_at_its_default_would_have_hidden_nothing(name):
    """Each drawn parameter MOVES the result: the reference with that
    parameter put back at its default differs from the model's forward, so
    the agreement above holds the ``(1 + w)``, the decays and the gate."""
    net = build()
    ids = prompts_of((33,), seed=6)[0][None]
    got = np.asarray(net(jnp.asarray(ids))._data)[0, -1]
    w = weights_of(net)
    fresh = weights_of(Qwen3NextForCausalLM(qwen3_next_tiny()))
    touched = [k for k in w if k.endswith(name)]
    assert touched
    reset = dict(w, **{k: (jnp.zeros_like(w[k]) if "gate" in k else fresh[k])
                       for k in touched})
    off = np.asarray(ref.logits(reset, ids, model_of(net)))[0, -1]
    assert ref.row_error(got, off) > 1e-3


@pytest.mark.parametrize("budget", [16, 8], ids=["chunks-of-16", "chunks-of-8"])
@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_chunks_then_decode_match_the_references_full_forward(
        interpret, budget, monkeypatch):
    """Prompts that fit one chunk, cross a chunk boundary and cross several
    (a state carried twice and more), each ending in a padded bucket: the
    rows compared come from chunks that start from a carried state and from
    decode steps that start from what the last chunk left."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    net = build(experts_held=tuple(range(8)))
    prompts = prompts_of((5, 21, 38))
    engine = dict(ENGINE, max_prefill_tokens_per_step=budget)
    with LLMEngine(net, capture_logits=True, **engine) as eng:
        rows, toks = rows_of(eng, prompts, 4)
        m = eng.metrics()
        assert m["global_blocks_in_use"] == 0 == m["state_slots_in_use"]
        # three delta layers: every real token scanned once, a live row a step
        assert m["delta_tokens_scanned"] == 2 * 3 * (5 + 21 + 38)
        assert m["delta_state_rows_updated_decode"] == 3 * 3 * 3
        assert m["delta_state_rows_updated_prefill"] == 0 \
            == m["delta_tokens_scanned_decode"]
        assert m["moe_layer_steps"] > 0
    assert len(rows) == 12
    for i, (p, t) in enumerate(zip(prompts, toks)):
        want = reference_rows(net, p, t)
        for j in range(4):
            assert ref.row_error(rows[(i, j)], want[j]) < 2e-5, (i, j)


def test_an_engine_that_captures_keeps_how_every_position_was_routed():
    net = build(experts_held=tuple(range(8)))
    prompts = prompts_of((5, 38))
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=4))
                for p in prompts]
        while eng.has_work():
            eng.step()
        for p, r in zip(prompts, map(eng.request, rids)):
            choice = np.concatenate(r.kept["moe_choice"], 1)
            # every layer has experts: four layers, every position a step
            # computed, top 4
            assert choice.shape == (4, len(p) + 3, 4)
            ids = np.concatenate([p, r.output_tokens])[None, :len(p) + 3]
            own, scores = ref.logits(weights_of(net), ids.astype(np.int32),
                                     model_of(net), net.config.experts_held,
                                     with_scores=True)
            for layer in range(4):
                turned, gap = ref.choice_gaps(scores[layer][0], choice[layer])
                assert gap < 1e-4 and turned <= 2, (layer, turned, gap)
            handed = np.asarray(ref.logits(
                weights_of(net), ids.astype(np.int32), model_of(net),
                net.config.experts_held,
                choice={layer: choice[layer][None] for layer in range(4)}))
            assert ref.row_error(handed[0, -1], np.asarray(own)[0, -1]) < 2e-5


# -- (b) the experts ------------------------------------------------------------

def test_softmax_top_k_against_a_hand_made_case_with_a_tie():
    """Four experts, top 2, logits a hand can follow; token 1 ties its second
    and third score: ``top_k`` keeps the lower index, and the weights are the
    chosen probabilities over their sum."""
    d = 128
    x = np.zeros((3, d), np.float32)
    x[0, 0], x[1, 1], x[2, 2] = 1.0, 1.0, 1.0
    router = np.zeros((d, 4), np.float32)
    router[0] = [2.0, 1.0, 0.0, -1.0]
    router[1] = [0.0, 1.0, 1.0, 3.0]          # a tie between experts 1 and 2
    router[2] = [0.5, 0.5, 0.5, 0.5]          # all tie: experts 0 and 1
    # an expert e multiplies by (e + 1): gate = up = const so that the
    # output is a known multiple of a fixed vector
    rng = np.random.default_rng(0)
    experts = [tuple(jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
                     for s in ((d, 128), (d, 128), (128, d)))
               for _ in range(4)]
    slot = np.arange(4, dtype=np.int32)
    y, pairs, hit, choice = moe_dropless(
        jnp.asarray(x), jnp.asarray(router), None, experts, slot, top_k=2,
        with_choice=True, score=softmax_scores)
    assert np.asarray(choice).tolist() == [[0, 1], [3, 1], [0, 1]]
    assert int(pairs) == 6 and int(hit) == 3

    def swiglu(v, e):
        g, u, dn = (np.asarray(m, np.float64) for m in experts[e])
        h = v @ g
        return (h / (1 + np.exp(-h)) * (v @ u)) @ dn

    for tok, (a, b) in enumerate([(0, 1), (3, 1), (0, 1)]):
        logits = x[tok] @ router
        p = np.exp(logits) / np.exp(logits).sum()
        wa, wb = p[a] / (p[a] + p[b]), p[b] / (p[a] + p[b])
        want = wa * swiglu(x[tok].astype(np.float64), a) \
            + wb * swiglu(x[tok].astype(np.float64), b)
        np.testing.assert_allclose(y[tok], want, atol=1e-6)
    # softmax scores sum to one over ALL experts, so the unnormalised
    # weights are smaller than the normalised
    y_raw = moe_dropless(jnp.asarray(x), jnp.asarray(router), None, experts,
                         slot, top_k=2, norm_topk=False,
                         score=softmax_scores)[0]
    assert np.abs(y_raw).sum() < np.abs(y).sum()


def _sigmoid_moe_before_pr37(x, router_w, bias, experts, held_slot, top_k,
                             scaling):
    """``moe_dropless``' routing as it stood before it took a score function
    (PR 36's lines), around the tile loop it still has."""
    from paddle_tpu.models.mimo_v2 import _tile_loop

    n_held = len(experts)
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    if scaling:
        w = w * scaling
    slot = jnp.asarray(held_slot)[sel].reshape(-1)
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    return _tile_loop(x, experts, slot, w, order, sizes, starts, None)[0] \
        .astype(x.dtype)


@pytest.mark.parametrize("model", ["mimo-v2", "joyai-flash", "nemotron-h"])
def test_the_three_sigmoid_models_route_bit_equal_to_before(model):
    """MiMo's and JoyAI's gated experts (JoyAI with a scaling factor) and
    Nemotron's ungated ones, by the default and by ``sigmoid_scores`` handed
    in: bit for bit what the lines before PR 37 give."""
    rng = np.random.default_rng(7)
    t, d, f, n_exp, held, top_k = 24, 64, 32, 16, 6, 4
    mats = 2 if model == "nemotron-h" else 3
    scaling = {"mimo-v2": None, "joyai-flash": 2.5, "nemotron-h": 2.5}[model]
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, n_exp)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=n_exp) * 0.01, jnp.float32)
    experts = [tuple(jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
                     for s in [(d, f)] * (mats - 1) + [(f, d)])
               for _ in range(held)]
    slot = np.full(n_exp, held, np.int32)
    slot[[1, 2, 5, 9, 11, 14]] = np.arange(held)
    want = _sigmoid_moe_before_pr37(x, router, bias, experts, slot, top_k,
                                    scaling)
    for kw in ({}, {"score": sigmoid_scores}):
        got = moe_dropless(x, router, bias, experts, slot, top_k=top_k,
                           scaling=scaling, **kw)[0]
        np.testing.assert_array_equal(got, want)
    # and the softmax is another function: it chooses otherwise somewhere
    other = moe_dropless(x, router, None, experts, slot, top_k=top_k,
                         scaling=scaling, score=softmax_scores)[0]
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs section 4: the routed parts that sixteen shares of one
    expert give, plus the gated shared expert counted ONCE, are what the
    uncut reference gives for the whole layer."""
    net = build()
    layer = net.model.layers[1]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, 64)), jnp.float32)
    u = layer.post_attention_layernorm(paddle_tpu.Tensor._wrap(x))
    w = {k[len("model.layers.1."):]: v for k, v in weights_of(net).items()
         if k.startswith("model.layers.1.")}
    whole = np.asarray(ref._experts(x[None], w, model_of(net),
                                    tuple(range(16)))[0])[0] - np.asarray(x)
    routed = np.zeros((37, 64), np.float32)
    for share in range(16):
        held = (share,)
        part = build(experts_held=held, num_hidden_layers=2,
                     full_attention_interval=2)
        moe = part.model.layers[1].mlp
        # the same 16 experts' weights, this share's one of them
        for mine, theirs in zip(moe.experts, held):
            for name in ("gate_proj", "up_proj", "down_proj"):
                getattr(mine, name).weight._rebind(
                    getattr(layer.mlp.experts[theirs], name).weight._data)
        moe.router.weight._rebind(layer.mlp.router.weight._data)
        routed += np.asarray(moe.forward_arrays(u._data)[0])
    gate = jax.nn.sigmoid(layer.mlp.shared_expert_gate(u)._data)
    shared = np.asarray(layer.mlp.shared_expert(u)._data * gate)
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    assert np.abs(shared).max() > 1e-3 and np.abs(routed).max() > 1e-3
    # the gate is drawn: it is not a half everywhere
    assert np.abs(np.asarray(gate) - 0.5).max() > 0.1


# -- (c) what the cache is built from -----------------------------------------------

def test_a_cache_with_the_new_layers_is_built_from_the_layout_alone():
    net = build()
    layout = net.kv_layout()
    delta, attn = layout[0], layout[3]
    assert (delta.kind, delta.num_kv_heads, delta.k_dim, delta.v_dim,
            delta.conv_rows, delta.state_dim) == ("state", 4, 128, 16, 3, 16)
    assert (attn.kind, attn.num_kv_heads, attn.k_dim, attn.v_dim,
            attn.prefill) == ("global", 2, 32, 32, "linear")
    cache = kvc.PagedKVCache(
        8, 24, block_size=4, layout=layout, max_batch_size=4)
    assert cache.state_slots == 5
    assert cache.k[0].shape == (5, 3 * 128) and cache.v[0].shape == (5, 4, 16, 16)
    assert cache.v[0].dtype == jnp.float32
    assert cache.k[3].shape == (24, 4 * 2, 32) == cache.v[3].shape
    # at the published widths a delta layer's slot is Nemotron's to the byte
    full = kvc.KVLayerSpec("state", 32, 8192, 128, conv_rows=3, state_dim=128)
    assert full.state_bytes() == 2_097_152 + 49_152
    assert full.state_shapes(97) == ((97, 3 * 8192), (97, 32, 128, 128))


def test_an_attention_layer_has_no_recurrence_and_a_delta_layer_no_pages():
    from paddle_tpu.inference.serving import paged_attention as spa

    net = build()
    layout = net.kv_layout()
    state = spa.DecodeAttnState(layout[3], 4, None, None, None, None)
    with pytest.raises(ValueError, match="this entry is for state layers"):
        state.delta(None, None, None, None)
    state = spa.DecodeAttnState(layout[0], 4, None, None, None, None)
    with pytest.raises(ValueError, match="global / window"):
        state.attend(None, None, None, 1.0)
    two_a_row = kvc.KVLayerSpec("state", 4, 2 * 2 * 16 + 4 * 64, 64,
                                conv_rows=3, state_dim=16)
    assert two_a_row.heads_a_lane_row == 2
    with pytest.raises(ValueError, match="a value head a lane row"):
        spa._conv_and_split_qkv(two_a_row, [jnp.zeros((1, 320))],
                                jnp.zeros((320, 1)))


# -- (d) the state's life ----------------------------------------------------------

def test_a_second_request_in_a_recycled_slot_is_as_in_a_fresh_engine():
    """The slot held another request's state, and a row dispatched ahead for
    it wrote there after it had left: the next request's first chunk starts
    from zeros whatever the slot holds."""
    net = build()
    first, second = prompts_of((30, 23), seed=7)
    with LLMEngine(net, capture_logits=True, **ENGINE) as fresh:
        want = generate(fresh, second, 5)
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        generate(eng, first, 6)
        held = [np.asarray(v[0]) for sp, v in zip(eng.cache.layout, eng.cache.v)
                if sp.kind == "state"]
        assert len(held) == 3
        assert all(np.abs(h).max() > 1e-3 for h in held)   # slot 0 is not zeros
        got = generate(eng, second, 5)
    assert got[0] == want[0]
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(a, b)
    for row, wanted in zip(got[1], reference_rows(net, second, got[0])[1:]):
        assert ref.row_error(row, wanted) < 2e-5


def test_a_decode_step_between_two_chunks_leaves_the_prefilling_request_alone():
    """One request decodes while another's prompt goes through in four
    chunks: each decode step in between runs the whole batch, the slot
    mid-prefill among its rows AT THE NULL SLOT (a dead row). The second
    request reads as it does alone, and so does the first."""
    net = build()
    short, long_ = prompts_of((6, 60), seed=9)
    with LLMEngine(net, capture_logits=True, **ENGINE) as fresh:
        want = generate(fresh, long_, 4)
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        a = eng.add_request(short, SamplingParams(max_new_tokens=40))
        while not eng.request(a).output_tokens:
            eng.step()
        b = eng.add_request(long_, SamplingParams(max_new_tokens=4))
        between, rows = 0, []
        while not eng.request(b).finished:
            outs = eng.step()
            if eng.request(b).prefilling:
                between += sum(1 for o in outs if o.rid == a)
            rows += [eng.request(b).last_logits.copy() for o in outs
                     if o.rid == b]
        assert between >= 2        # decode steps did run between its chunks
        got = list(eng.request(b).output_tokens), rows[1:]
        alone = list(eng.request(a).output_tokens)
        eng.cancel(a)
    assert got[0] == want[0]
    for x, y in zip(got[1], want[1]):
        assert ref.row_error(x, y) < 2e-5
    want_a = reference_rows(net, short, alone)
    assert [int(np.argmax(r)) for r in want_a] == alone


@pytest.mark.parametrize("how", ["drain", "reload"])
def test_a_drained_step_is_never_applied_twice(how, tmp_path):
    """``_drain`` with a step in flight, then on: the tokens of an undrained
    run. A delta step applied twice would decay AND correct the state
    twice."""
    net = build()
    prompt = prompts_of((19,), seed=2)[0]

    def plain(eng):
        rid = eng.add_request(prompt, SamplingParams(max_new_tokens=12))
        while eng.has_work():
            eng.step()
        return list(eng.request(rid).output_tokens)

    with LLMEngine(net, **ENGINE) as eng:
        want = plain(eng)
    path = str(tmp_path / "w.pdparams")
    paddle_tpu.save(net.state_dict(), path)
    with LLMEngine(net, **ENGINE) as eng:
        rid = eng.add_request(prompt, SamplingParams(max_new_tokens=12))
        seen = []
        while len(seen) < 4:
            seen += [o.token for o in eng.step() if o.rid == rid]
        assert eng._ahead is not None and len(eng._ahead.rows) == 1
        cached = eng.request(rid).num_cached
        if how == "drain":
            eng._drain()
        else:
            eng.reload_weights(path)
        assert eng._ahead is None and eng.has_work()
        assert eng.request(rid).num_cached == cached + 1
        while eng.has_work():
            seen += [o.token for o in eng.step() if o.rid == rid]
        assert eng.metrics()["decode_steps_sync_by_reason"]["commit"] == 1
    assert seen == want


def test_a_row_dispatched_ahead_for_a_request_that_stopped_reaches_nobody():
    """EOS cannot be seen ahead: the step after the last token is run and
    discarded. It advanced the state in the slot it was made for; the next
    request there starts from zeros, and a neighbour's state is its own."""
    net = build()
    prompt, other, nxt = prompts_of((11, 14, 17), seed=5)
    with LLMEngine(net, capture_logits=True, **ENGINE) as fresh:
        want_next = generate(fresh, nxt, 4)
        toks, _ = generate(fresh, prompt, 6)
    eos = toks[3]
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        b = eng.add_request(other, SamplingParams(max_new_tokens=20))
        a = eng.add_request(prompt, SamplingParams(max_new_tokens=6,
                                                   eos_token_id=eos))
        while not eng.request(a).finished:
            eng.step()
        assert list(eng.request(a).output_tokens) == toks[:toks.index(eos) + 1]
        eng.release(a)
        got_next = generate(eng, nxt, 4)
        while not eng.request(b).finished:
            eng.step()
        neighbour = list(eng.request(b).output_tokens)
    assert got_next[0] == want_next[0]
    for x, y in zip(got_next[1], want_next[1]):
        assert ref.row_error(x, y) < 2e-5
    want_b = reference_rows(net, other, neighbour)
    assert [int(np.argmax(r)) for r in want_b] == neighbour


def test_a_token_sixty_four_positions_back_still_moves_a_row():
    """A state that forgot within a token would hide a wrong slot or a stale
    state from every check: at the decays the family initialises, changing
    ONE token 64 positions back moves the last row."""
    net = build()
    ids = prompts_of((80,), seed=8)[0]
    other = ids.copy()
    other[15] = (other[15] + 1) % 160
    w, m = weights_of(net), model_of(net)
    # the delta layers alone: attention would carry the token anyway
    x0, x1 = (w["model.embed_tokens.weight"][jnp.asarray(t)][None]
              for t in (ids, other))
    layer = {k[len("model.layers.0."):]: v for k, v in w.items()
             if k.startswith("model.layers.0.")}
    y0, y1 = (np.asarray(ref.mixer(x, layer, m, 0))[0, 79] for x in (x0, x1))
    assert ref.row_error(y1, y0) > 1e-4
