"""Nemotron-H on the serving path (ISSUE 33), at toy widths that keep the
structure: blocks ``M E M * E M`` (two state-space blocks before the first
attention), 4 Mamba-2 heads of 8 over 2 groups and a state of 16, 4 query
heads over 2 kv heads, 32 experts of two matrices stored wider than
published, one shared expert.

The float32 reference is ``benchmarks/harness/reference_nemotron_h.py``: it
shares no code with ``paddle_tpu`` and runs the recurrence token by token.

The second half is the STATE'S LIFE: what is harmless for pages and wrong
for ``h <- a h + b`` (a dead row of a decode step, a drained step, a recycled
slot), each of which passed before this PR only because no state existed."""

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
from paddle_tpu.inference.serving import paged_attention as spa
from paddle_tpu.models import (LlamaForCausalLM, NemotronHForCausalLM,
                               llama_tiny, nemotron_h_tiny)
from paddle_tpu.models.mimo_v2 import moe_dropless
from paddle_tpu.ops.pallas import mamba2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_nemotron_h as ref  # noqa: E402

ENGINE = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
              prefill_buckets=[8, 16, 32, 64, 96],
              max_prefill_tokens_per_step=16)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(seed=3, **kw):
    paddle_tpu.seed(seed)
    net = NemotronHForCausalLM(nemotron_h_tiny(**kw))
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
    outputs (``tests/test_mimo_v2_serving.py``'s method: row 0 comes from a
    second pass of one-token requests)."""
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

@pytest.mark.parametrize("held", [None, tuple(range(8, 16))],
                         ids=["all-experts", "a-share"])
def test_the_models_plain_forward_matches_the_reference(held):
    net = build(experts_held=held)
    ids = prompts_of((41,), seed=4)[0][None]
    got = np.asarray(net(jnp.asarray(ids))._data)[0]
    want = np.asarray(ref.logits(weights_of(net), ids, model_of(net),
                                 experts_held=net.config.experts_held))[0]
    for t in (0, 7, 8, 23, 40):
        assert ref.row_error(got[t], want[t]) < 2e-5, t
    # every kind of block is in it, and each moves the result
    assert set(net.config.hybrid_override_pattern) == set("ME*")
    assert [sp.kind for sp in net.kv_layout()] == [
        "state", "none", "state", "global", "none", "state"]


@pytest.mark.parametrize("scan_block,budget", [(8, 16), (16, 8)],
                         ids=["chunks-of-two-blocks", "a-boundary-inside-a-block"])
@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_chunks_then_decode_match_the_references_full_forward(
        interpret, scan_block, budget, monkeypatch):
    """Prompts that fit one chunk, cross a chunk boundary and cross several,
    each ending in a padded bucket: the rows compared come from chunks that
    start from a carried state (with scan blocks of 16 and chunks of 8 the
    boundary lies inside a block) and from decode steps that start from what
    the last chunk left."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    net = build(experts_held=tuple(range(8)), chunk_size=scan_block)
    prompts = prompts_of((5, 21, 38))
    engine = dict(ENGINE, max_prefill_tokens_per_step=budget)
    with LLMEngine(net, capture_logits=True, **engine) as eng:
        rows, toks = rows_of(eng, prompts, 4)
        m = eng.metrics()
        assert m["global_blocks_in_use"] == 0 == m["state_slots_in_use"]
        # three state blocks: every real token scanned once, a live row a step
        assert m["ssm_tokens_scanned"] == 2 * 3 * (5 + 21 + 38)
        assert m["ssm_state_rows_updated_decode"] == 3 * 3 * 3
        assert m["ssm_state_rows_updated_prefill"] == 0 \
            == m["ssm_tokens_scanned_decode"]
    assert len(rows) == 12
    for i, (p, t) in enumerate(zip(prompts, toks)):
        want = reference_rows(net, p, t)
        for j in range(4):
            assert ref.row_error(rows[(i, j)], want[j]) < 2e-5, (i, j)


@pytest.mark.parametrize("capture", [True, False], ids=["captures", "does-not"])
def test_an_engine_that_captures_keeps_how_every_position_was_routed(capture):
    """``state.keep``: the experts each token chose in each expert block come
    out of both graphs and, under ``capture_logits``, lie on the request in
    the order its positions were computed (chunks, then a token a decode
    step): the reference routed by them is the reference routed by itself,
    in float32."""
    net = build(experts_held=tuple(range(8)))
    prompts = prompts_of((5, 21, 38))
    with LLMEngine(net, capture_logits=capture, **ENGINE) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=4))
                for p in prompts]
        while eng.has_work():
            eng.step()
        reqs = [eng.request(r) for r in rids]
        if not capture:
            assert all(r.kept == {} for r in reqs)
            return
        for p, r in zip(prompts, reqs):
            choice = np.concatenate(r.kept["moe_choice"], 1)
            # two expert blocks, every position a step computed, top 4
            assert choice.shape == (2, len(p) + 3, 4) and r.num_cached == len(p) + 3
            assert len(r.kept["moe_choice"]) == -(-len(p) // 16) + 3
            ids = np.concatenate([p, r.output_tokens])[None, :len(p) + 3]
            own, scores = ref.logits(weights_of(net), ids.astype(np.int32),
                                     model_of(net), net.config.experts_held,
                                     with_scores=True)
            for k, b in enumerate(sorted(scores)):
                turned, gap = ref.choice_gaps(scores[b][0], choice[k])
                assert gap < 1e-5 and turned <= 2, (b, turned, gap)
            handed = np.asarray(ref.logits(
                weights_of(net), ids.astype(np.int32), model_of(net),
                net.config.experts_held,
                choice={b: choice[k][None]
                        for k, b in enumerate(sorted(scores))}))
            assert ref.row_error(handed[0, -1], np.asarray(own)[0, -1]) < 2e-5


@pytest.mark.parametrize("t,block", [(1, 8), (7, 8), (8, 8), (37, 8),
                                     (130, 128), (300, 128)])
def test_the_chunked_scan_is_the_recurrence_token_by_token(t, block):
    """At lengths that are no multiple of the block, from a carried state
    that is not zero; a position with a step of 0 changes nothing."""
    rng = np.random.default_rng(t)
    heads, p, n, groups = 4, 8, 16, 2
    x = jnp.asarray(rng.normal(size=(t, heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(t, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 8, size=heads), jnp.float32)
    b = jnp.asarray(rng.normal(size=(t, groups, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(t, groups, n)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(heads, p, n)), jnp.float32)
    want_y, want_h = mamba2.ssd_recurrence(x, dt, a, b, c, h0)
    got_y, got_h = mamba2.ssd_chunk_scan(x, dt, a, b, c, h0, block)
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(got_h, want_h, atol=2e-5, rtol=2e-5)
    # padding: the same tokens with dead positions behind them
    pad = lambda m: jnp.concatenate([m, jnp.ones((5,) + m.shape[1:])])  # noqa: E731
    _, padded_h = mamba2.ssd_chunk_scan(
        pad(x), jnp.concatenate([dt, jnp.zeros((5, heads))]), a, pad(b),
        pad(c), h0, block)
    np.testing.assert_allclose(padded_h, want_h, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,p,n,groups", [(4, 64, 128, 2), (4, 8, 16, 2)],
                         ids=["two-heads-a-lane-row", "toy"])
def test_the_decode_kernel_is_its_lax_form_and_touches_live_slots_only(
        heads, p, n, groups, monkeypatch):
    """Interpret mode against the ``lax`` form and against the recurrence
    written out; rows 1 and 3 are dead (the null slot, twice); a slot no row
    names is left bit for bit."""
    rng = np.random.default_rng(0)
    bsz, slots_n = 5, 7
    spec = kvc.KVLayerSpec("state", heads, heads * p + 2 * groups * n, p,
                           conv_rows=3, state_dim=n)
    pack = spec.heads_a_lane_row
    assert pack == (2 if p == 64 else 1)
    natural = rng.normal(size=(slots_n, heads, p, n)).astype(np.float32)
    state = mamba2.to_stored(jnp.asarray(natural), pack)
    assert state.shape == spec.state_shapes(slots_n)[1]
    np.testing.assert_array_equal(mamba2.from_stored(state, pack), natural)
    slots = jnp.asarray([3, 6, 0, 6, 1], jnp.int32)
    x = jnp.asarray(rng.normal(size=(bsz, heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(bsz, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2, size=heads), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bsz, groups, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bsz, groups, n)), jnp.float32)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    y_lax, s_lax = mamba2.mamba2_decode_update(state, slots, x, dt, a, b, c)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    y_k, s_k = mamba2.mamba2_decode_update(state, slots, x, dt, a, b, c)
    live = [0, 2, 4]
    np.testing.assert_allclose(np.asarray(y_k)[live], np.asarray(y_lax)[live],
                               atol=1e-5)
    for i in live:
        y1, h1 = mamba2.ssd_recurrence(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1],
                                       c[i:i + 1], natural[int(slots[i])])
        np.testing.assert_allclose(y_k[i], y1[0], atol=1e-4)
        for got in (s_k, s_lax):
            np.testing.assert_allclose(
                mamba2.from_stored(got[int(slots[i])], pack), h1, atol=1e-5)
    for untouched in (2, 4, 5):
        for got in (s_k, s_lax):
            np.testing.assert_array_equal(got[untouched], state[untouched])


@pytest.mark.parametrize("interpret", ["0", "1"], ids=["loop", "kernel"])
def test_ungated_experts_stored_wider_than_published(interpret, monkeypatch):
    """Experts of two matrices and ``relu(.)^2``, 200 wide stored 256 with
    zeros: the tile loop and the grouped kernel (interpret mode) against a
    loop over the PUBLISHED width."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    rng = np.random.default_rng(1)
    t, d, f, store, n_exp, held, top_k = 24, 128, 200, 256, 16, 4, 3
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, n_exp)) * 0.3, jnp.float32)
    bias = jnp.zeros(n_exp, jnp.float32)
    ups = rng.normal(size=(held, d, store)).astype(np.float32) * 0.1
    downs = rng.normal(size=(held, store, d)).astype(np.float32) * 0.1
    ups[:, :, f:] = 0.0
    downs[:, f:] = 0.0
    slot = np.full(n_exp, held, np.int32)
    slot[[2, 5, 9, 11]] = np.arange(held)
    got, pairs, hit, passes = moe_dropless(
        x, router, bias, [(jnp.asarray(u), jnp.asarray(w))
                          for u, w in zip(ups, downs)],
        slot, top_k=top_k, scaling=2.5, with_passes=True)
    scores = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(router)))
    sel = np.argsort(-scores, axis=1, kind="stable")[:, :top_k]
    want = np.zeros((t, d), np.float32)
    routed = 0
    for tok in range(t):
        w = scores[tok, sel[tok]]
        w = 2.5 * w / w.sum()
        for e, wt in zip(sel[tok], w):
            if slot[e] < held:
                routed += 1
                h = np.maximum(np.asarray(x)[tok] @ ups[slot[e], :, :f], 0.0)
                want[tok] += wt * ((h * h) @ downs[slot[e], :f])
    assert int(pairs) == routed and 0 < int(hit) <= held
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_the_kernels_trace_names_and_the_refusal_name_the_ungated_form(
        monkeypatch):
    from paddle_tpu.ops.pallas import grouped_ffn as gf

    sds = jax.ShapeDtypeStruct
    ints = [sds((48,), jnp.int32)] + [sds((4,), jnp.int32)] * 3 \
        + [sds((), jnp.int32)]
    for mats, name in ((2, "moe_grouped_relu2"), (3, "moe_grouped_swiglu")):
        experts = [tuple(sds(s, jnp.bfloat16) for s in
                         [(128, 256)] * (mats - 1) + [(256, 128)])] * 2
        text = jax.jit(lambda x, e, *a: gf.grouped_swiglu(
            x, *a, e, rows=16, top_k=3)).trace(
                sds((16, 128), jnp.bfloat16), experts, *ints).lower(
                    lowering_platforms=("tpu",)).as_text()
        assert f'kernel_name = "{name}"' in text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="stored wider with zeros"):
        gf.use_pallas_grouped_ffn(2688, 1856)
    assert gf.use_pallas_grouped_ffn(2688, 1920)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs section 4: the routed parts that eight shares of four
    experts give, plus the shared expert counted ONCE, are what the uncut
    reference gives for the whole block."""
    net = build()
    block = net.model.layers[1]
    assert block.letter == "E"
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, 64)), jnp.float32)
    u = block.norm(paddle_tpu.Tensor._wrap(x))
    w = {k[len("model.layers.1."):]: v for k, v in weights_of(net).items()
         if k.startswith("model.layers.1.")}
    whole = np.asarray(ref._experts(x[None], w, model_of(net),
                                    tuple(range(32)))[0])[0] - np.asarray(x)
    routed = np.zeros((37, 64), np.float32)
    for share in range(8):
        held = tuple(range(4 * share, 4 * share + 4))
        part = build(experts_held=held)
        moe = part.model.layers[1].mixer
        # the same 32 experts' weights, this share's four of them
        for mine, theirs in zip(moe.experts, held):
            for name in ("up_proj", "down_proj"):
                getattr(mine, name).weight._rebind(
                    getattr(block.mixer.experts[theirs], name).weight._data)
        moe.router.weight._rebind(block.mixer.router.weight._data)
        moe.router.e_score_correction_bias._rebind(
            block.mixer.router.e_score_correction_bias._data)
        routed += np.asarray(moe.forward_arrays(u._data)[0])
    shared = np.asarray(block.mixer.shared_experts(u)._data)
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    assert np.abs(shared).max() > 1e-3 and np.abs(routed).max() > 1e-3


def test_a_dense_block_and_an_unknown_letter_are_refused_by_name():
    with pytest.raises(ValueError, match="dense feed-forward"):
        nemotron_h_tiny(hybrid_override_pattern="ME-*EM")
    with pytest.raises(ValueError, match="unknown block letter"):
        nemotron_h_tiny(hybrid_override_pattern="MEX*EM")
    with pytest.raises(ValueError, match="a letter a block"):
        nemotron_h_tiny(hybrid_override_pattern="ME")
    # the pattern is cut to the depth, as the benchmark cuts it
    assert nemotron_h_tiny(num_hidden_layers=3).hybrid_override_pattern == "MEM"


# -- (b) the cache manager's fourth and fifth kinds -------------------------------

def test_a_state_is_slots_and_a_none_layer_holds_nothing():
    net = build()
    with LLMEngine(net, **ENGINE) as eng:
        cache = eng.cache
        assert cache.state_slots == ENGINE["max_batch_size"] + 1
        for sp, k, v in zip(cache.layout, cache.k, cache.v):
            if sp.kind == "state":
                assert k.shape == (5, 3 * 96) and k.dtype == jnp.float32
                assert v.shape == (5, 4, 16, 8) and v.dtype == jnp.float32
            elif sp.kind == "none":
                assert k.shape == v.shape == (0,)
            else:
                assert k.shape == (96, 4 * 2, 16) == v.shape
        # a state is no bytes a token: only the attention block pages
        assert cache.published_bytes_per_token("global") == 2 * (16 + 16) * 2
        assert cache.published_bytes_per_token("window") == 0
        per_request = 3 * (3 * 96 * 2 + 4 * 8 * 16 * 4)
        assert cache.state_bytes_per_request() == per_request
        rid = eng.add_request(prompts_of((9,))[0],
                              SamplingParams(max_new_tokens=3))
        eng.step()
        m = eng.metrics()
        assert m["state_slots_in_use"] == 1 and m["state_bytes"] == per_request
        assert m["kv_live_byte_steps"] == 4 * 3 * 128      # 3 pages of 4
        assert m["state_byte_steps"] == per_request
        while eng.has_work():
            eng.step()
        eng.release(rid)
        assert eng.metrics()["state_slots_in_use"] == 0
    spec = net.kv_layout()[0]
    assert (spec.paged, spec.bytes_per_token(), kvc.KVLayerSpec("none").paged) \
        == (False, 0, False)
    with pytest.raises(ValueError, match="state kind, and only it"):
        kvc.KVLayerSpec("global", 2, 16, 16, state_dim=16)
    with pytest.raises(ValueError, match="state kind, and only it"):
        kvc.KVLayerSpec("state", 4, 64, 8)
    with pytest.raises(ValueError, match="has no such state"):
        spa.DecodeAttnState(kvc.KVLayerSpec("none"), 4, None, None,
                            jnp.zeros((0,)), jnp.zeros((0,))).attend(
                                None, None, None, 1.0)


REFUSALS = {
    "prefix sharing": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    "spill": (dict(kv_host_blocks=8), "kv_host_blocks"),
    "prefix store": (dict(enable_prefix_cache=True, kv_host_blocks=8,
                          prefix_store_path="/nonexistent/store"),
                     "enable_prefix_cache"),
    "handoff": (dict(prefill_only=True), "prefill_only"),
    "checksums": (dict(kv_page_checksums=True), "kv_page_checksums"),
    "int8": (dict(kv_dtype="int8"), "int8 KV pools"),
    "draft": (dict(draft_model="llama"), "draft_model"),
    "plan": (dict(plan="plan"), "plan"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_assumes_pages_is_refused_at_construction_by_name(what):
    kw, name = REFUSALS[what]
    kw = dict(kw)
    if "draft_model" in kw:
        paddle_tpu.seed(0)
        kw["draft_model"] = LlamaForCausalLM(llama_tiny())
    if "plan" in kw:
        kw["plan"] = object()
    with pytest.raises(ValueError, match=name) as err:
        LLMEngine(build(), **ENGINE, **kw)
    text = str(err.value)
    assert "state" in text or "NemotronHForCausalLM" in text


def test_export_import_and_copy_on_write_are_refused_by_name():
    net = build()
    prompt = prompts_of((9,))[0]
    with LLMEngine(net, **ENGINE) as eng:
        rid = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        eng.step()
        with pytest.raises(ValueError, match="page export"):
            eng.export_kv_pages(rid)
        with pytest.raises(ValueError, match="copy-on-write"):
            eng.cache.copy_block(1, 2)
        with pytest.raises(ValueError, match="page import"):
            eng.cache.import_request_pages([1], {})
        with pytest.raises(ValueError, match="page import|one pool geometry"):
            eng.add_request_with_pages(prompt, {"covered": 8})
    # a window kind and a state kind each have an operand of their own: a
    # cache may hold both
    layout = [kvc.KVLayerSpec("window", 2, 16, 16, 16, 8, prefill="linear"),
              net.kv_layout()[0]]
    both = kvc.PagedKVCache(net.config, 16, 4, layout=layout, max_batch_size=2)
    assert both.window is not None and both.state_slots == 3
    with pytest.raises(ValueError, match="needs max_batch_size"):
        kvc.PagedKVCache(net.config, 16, 4, layout=[net.kv_layout()[0]])


# -- (c) the state's life ----------------------------------------------------------

def test_dead_rows_point_at_the_null_slot():
    """Empty slots, a slot mid-prefill and a row a step leaves out all read
    and write slot ``max_batch_size``; the operand is put once while the
    live rows stay the same."""
    with LLMEngine(build(), **ENGINE) as eng:
        rows = [(2, None, 7, 0), (0, None, 3, 1)]
        first = eng._state_slots(rows)
        assert np.asarray(first).tolist() == [0, 4, 2, 4]
        assert eng._state_slots(rows) is first
        assert np.asarray(eng._state_slots(rows[:1])).tolist() == [4, 4, 2, 4]
        assert np.asarray(eng.decode_abstract_args()[-2].shape) == 4
    with LLMEngine(LlamaForCausalLM(llama_tiny()), **ENGINE) as eng:
        assert eng._state_slots([(0, None, 3, 1)]) is None


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
    mid-prefill among its rows. The second request reads as it does alone."""
    net = build()
    short, long_ = prompts_of((6, 60), seed=9)
    engine = dict(ENGINE, max_prefill_tokens_per_step=16)
    with LLMEngine(net, capture_logits=True, **engine) as fresh:
        want = generate(fresh, long_, 4)
    with LLMEngine(net, capture_logits=True, **engine) as eng:
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
    # and the decoding request is the reference's too: the chunks beside it
    # did not touch ITS state either
    want_a = reference_rows(net, short, alone)
    assert [int(np.argmax(r)) for r in want_a] == alone


def _state_and_tail(eng, slot):
    """What the state blocks hold in a slot: ``[(tail, state)]`` a block,
    read behind whatever is in flight."""
    return [(np.asarray(k[slot], np.float32), np.asarray(v[slot]))
            for sp, k, v in zip(eng.cache.layout, eng.cache.k, eng.cache.v)
            if sp.kind == "state"]


def test_a_step_dispatched_before_the_first_token_leaves_the_new_state_alone():
    """ISSUE 34: a request arrives beside one that decodes. Its chunk is
    enqueued behind the step in flight, and the NEXT step is dispatched, for
    the row that decodes, before the chunk's logits are fetched: the new
    request is still mid-prefill to that step, its row at the null slot and
    the null block. Its state and its convolution's tail are then as the
    chunk wrote them, and it reads on as it does alone."""
    net = build()
    short, new = prompts_of((6, 13), seed=11)
    engine = dict(ENGINE, ingest_async=False)
    with LLMEngine(net, capture_logits=True, **engine) as fresh:
        want = generate(fresh, new, 5)
        # what the chunk alone leaves: a request that wants one token ends
        # there, and no decode step follows in its slot
        generate(fresh, new, 1)
        chunk_wrote = _state_and_tail(fresh, 0)
    with LLMEngine(net, capture_logits=True, **engine) as eng:
        a = eng.add_request(short, SamplingParams(max_new_tokens=40))
        while len(eng.request(a).output_tokens) < 3:
            eng.step()
        assert eng._ahead is not None
        b = eng.add_request(new, SamplingParams(max_new_tokens=5))
        outs = eng.step()
        assert [o.rid for o in outs] == [b, a]
        m = eng.metrics()
        assert (m["prefills"], m["prefill_ends_behind_decode"]) == (2, 1)
        # the step in flight now was dispatched before b's first token
        ahead = eng._ahead
        assert [row[1].rid for row in ahead.rows] == [a]
        assert np.asarray(eng._slots_dev).tolist().count(4) == 3
        slot = eng.scheduler.slots.index(eng.request(b))
        for (tail, state), (t0, s0) in zip(_state_and_tail(eng, slot),
                                           chunk_wrote):
            assert np.abs(s0).max() > 1e-3
            np.testing.assert_allclose(state, s0, rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(tail, t0, rtol=2e-5, atol=2e-6)
        rows = [eng.request(b).last_logits.copy()]
        while not eng.request(b).finished:
            rows += [eng.request(b).last_logits.copy() for o in eng.step()
                     if o.rid == b]
        got = list(eng.request(b).output_tokens)
        alone = list(eng.request(a).output_tokens)
        eng.cancel(a)
    assert got == want[0]
    for x, y in zip(rows[1:], want[1]):
        assert ref.row_error(x, y) < 2e-5
    for row, wanted in zip(rows, reference_rows(net, new, got)):
        assert ref.row_error(row, wanted) < 2e-5
    # the request that decoded beside the chunk is the reference's too
    assert [int(np.argmax(r)) for r in reference_rows(net, short, alone)] == alone


@pytest.mark.parametrize("how", ["drain", "reload"])
def test_a_drained_step_is_never_applied_twice(how, tmp_path):
    """``_drain`` with a step in flight, then on: the tokens of an undrained
    run. The step is committed (fetched and emitted where it would have been
    forgotten), its outputs come out of the next call, and the policy is
    counted."""
    net = build()
    prompt = prompts_of((19,), seed=2)[0]
    with LLMEngine(net, **ENGINE) as plain:
        want, _ = generate_plain(plain, prompt, 12)
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
        # committed: the request moved on by the step that was in flight
        assert eng.request(rid).num_cached == cached + 1
        assert len(eng._committed) == 1
        while eng.has_work():
            seen += [o.token for o in eng.step() if o.rid == rid]
        m = eng.metrics()
        assert m["decode_steps_sync_by_reason"]["commit"] == 1
        assert m["decode_rows_discarded"] == 0
    assert seen == want


def generate_plain(eng, prompt, n_new):
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=n_new))
    while eng.has_work():
        eng.step()
    return list(eng.request(rid).output_tokens), None


def test_a_llama_engine_still_forgets_a_drained_step():
    """Without a state kind the step is forgotten and made again."""
    paddle_tpu.seed(0)
    with LLMEngine(LlamaForCausalLM(llama_tiny()), **ENGINE) as eng:
        rid = eng.add_request(prompts_of((9,), vocab=100)[0],
                              SamplingParams(max_new_tokens=8))
        while len(eng.request(rid).output_tokens) < 3:
            eng.step()
        cached = eng.request(rid).num_cached
        eng._drain()
        assert eng.request(rid).num_cached == cached and not eng._committed
        assert eng.metrics()["decode_rows_discarded"] == 1


def test_a_row_dispatched_ahead_for_a_request_that_stopped_reaches_nobody():
    """EOS cannot be seen ahead: the step after the last token is run and
    discarded. It advanced the state in the slot it was made for; the next
    request there starts from zeros, and a neighbour's state is its own."""
    net = build()
    prompt, other, nxt = prompts_of((11, 14, 17), seed=5)
    with LLMEngine(net, **ENGINE) as plain:
        toks, _ = generate_plain(plain, prompt, 8)
        want_other, _ = generate_plain(plain, other, 12)
        want_next, _ = generate_plain(plain, nxt, 6)
    with LLMEngine(net, **ENGINE) as eng:
        a = eng.add_request(prompt, SamplingParams(max_new_tokens=8,
                                                   eos_token_id=toks[2]))
        b = eng.add_request(other, SamplingParams(max_new_tokens=12))
        while not eng.request(a).finished:
            eng.step()
        assert list(eng.request(a).output_tokens) == toks[:3]
        c = eng.add_request(nxt, SamplingParams(max_new_tokens=6))
        while eng.has_work():
            eng.step()
        assert eng.metrics()["decode_rows_discarded"] >= 1
        assert list(eng.request(b).output_tokens) == want_other
        assert list(eng.request(c).output_tokens) == want_next


def test_a_preempted_request_is_recomputed_into_the_same_state():
    """Room for K/V pages can still run out (the state kind itself never
    preempts): the victim comes back from its tokens, its first chunk from
    zeros, and ends as if it had never left."""
    net = build()
    prompts = prompts_of((20, 22, 18), seed=11)
    with LLMEngine(net, **ENGINE) as roomy:
        want = [generate_plain(roomy, p, 20)[0] for p in prompts]
    tight = dict(ENGINE, num_blocks=22)     # 21 pages of 4 for 3 x ~41 tokens
    with LLMEngine(net, capture_logits=True, **tight) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=20))
                for p in prompts]
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        assert m["evictions"] >= 1
        assert [list(eng.request(r).output_tokens) for r in rids] == want
        # what the layers kept starts again with the recomputation: a row a
        # position the request's cache holds, none twice
        for r in map(eng.request, rids):
            assert sum(a.shape[1] for a in r.kept["moe_choice"]) == r.num_cached
