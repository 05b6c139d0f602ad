"""LFM2-MoE on the serving path, at toy widths that keep the
structure: a dense conv layer, then ``conv full_attention conv conv`` with
sigmoid-routed experts; 4 query heads over 2 kv heads of 64, the published
head width, so that the pages take the two-a-lane-row form.

The float32 reference is ``benchmarks/harness/reference_lfm2.py``: it shares
no code with ``paddle_tpu``. Beside it: the state kind that holds a tail
alone (its shapes, its life in a recycled slot, a tail lost at a chunk
boundary or never renewed), the head-64 kernels in interpret mode against
plain ``jax.numpy``, and the head-128 kernels bit for bit as they were."""

import dataclasses
import hashlib
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
from paddle_tpu.models import Lfm2MoeForCausalLM, lfm2_moe_tiny
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_lfm2 as ref  # noqa: E402

ENGINE = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
              prefill_buckets=[8, 16, 32, 64, 96],
              max_prefill_tokens_per_step=16)
#: the norms' weights, whose default (1) would hide a norm left out
DRAWN = ("norm.weight", "layernorm.weight")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(seed=3, **kw):
    paddle_tpu.seed(seed)
    net = Lfm2MoeForCausalLM(lfm2_moe_tiny(**kw))
    rng = np.random.default_rng(seed)
    for name, p in net.named_parameters():
        if name.endswith(DRAWN):
            p._rebind(p._data + jnp.asarray(
                rng.normal(0.0, 0.3, p._data.shape), p._data.dtype))
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


def generate(eng, prompt, n_new):
    """One request alone to its end: ``(tokens, the logits row of each but
    the first)``."""
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=n_new))
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
        model_of(net)))[0]
    return [want[len(prompt) - 1 + j] for j in range(len(toks))]


# -- (a) against the reference --------------------------------------------------

def test_the_models_plain_forward_matches_the_reference():
    net = build()
    ids = prompts_of((41,), seed=4)[0][None]
    got = np.asarray(net(jnp.asarray(ids))._data)[0]
    want = np.asarray(ref.logits(weights_of(net), ids, model_of(net)))[0]
    for t in (0, 1, 2, 3, 17, 40):
        assert ref.row_error(got[t], want[t]) < 2e-5, t
    assert [(sp.kind, sp.heads_a_lane_row) for sp in net.kv_layout()] == [
        ("state", 1), ("state", 1), ("global", 2), ("state", 1), ("state", 1)]


@pytest.mark.parametrize("part", ["conv.conv_weight", "conv.in_proj.weight",
                                  "self_attn.q_layernorm.weight",
                                  "router.expert_bias", "embedding_norm.weight"])
def test_every_part_moves_the_reference(part):
    """What the agreement above holds: each of these parts, scaled, moves
    the reference's last row (the taps, the gate's columns, the 64-wide
    norm, the bias that chooses, the norm before the tied head)."""
    net = build()
    ids = prompts_of((33,), seed=6)[0][None]
    w = weights_of(net)
    base = np.asarray(ref.logits(w, ids, model_of(net)))[0, -1]
    moved = {k: (v * 1.5 + (0.05 if "bias" in k else 0.0)
                 if k.endswith(part) else v) for k, v in w.items()}
    got = np.asarray(ref.logits(moved, ids, model_of(net)))[0, -1]
    assert ref.row_error(got, base) > 1e-4


@pytest.mark.parametrize("budget", [16, 8], ids=["chunks-of-16", "chunks-of-8"])
@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_chunks_then_decode_match_the_references_full_forward(
        interpret, budget, monkeypatch):
    """Prompts that fit one chunk, cross a chunk boundary and cross several
    (a tail carried twice and more), decoding beside each other: every row
    against the reference's full forward, and the counters."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    net = build()
    prompts = prompts_of((5, 21, 38))
    engine = dict(ENGINE, max_prefill_tokens_per_step=budget)
    with LLMEngine(net, capture_logits=True, **engine) as eng:
        rows, toks = rows_of(eng, prompts, 4)
        m = eng.metrics()
        assert m["global_blocks_in_use"] == 0 == m["state_slots_in_use"]
        # four conv layers: every real token convolved once a pass
        assert m["conv_tokens_convolved_prefill"] == 2 * 4 * (5 + 21 + 38)
        # three rows decode three steps after their first token, in 4 layers
        assert m["conv_tail_rows_updated_decode"] == 3 * 3 * 4 \
            == m["conv_tokens_convolved_decode"]
        assert m["attn_tokens_read_prefill"] == 0 < m["attn_tokens_read_decode"]
    assert len(rows) == 12
    for i, (p, t) in enumerate(zip(prompts, toks)):
        want = reference_rows(net, p, t)
        for j in range(4):
            assert ref.row_error(rows[(i, j)], want[j]) < 2e-5, (i, j)


def test_the_attention_counter_is_the_live_tokens_the_kernel_walks():
    """``attn_tokens_read``: a decode step's rows' contexts, each counted in
    each attention layer; empty slots read the null page and count
    nothing."""
    net = build()
    prompt = prompts_of((9,), seed=3)[0]
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        generate(eng, prompt, 5)
        m = eng.metrics()
    # one attention layer; the four decode steps attend over 10..13 tokens
    assert m["attn_tokens_read_decode"] == 10 + 11 + 12 + 13


# -- (b) what the cache holds ---------------------------------------------------

def test_a_tail_only_state_kind_holds_no_recurrent_state():
    spec = kvc.KVLayerSpec("state", k_dim=2048, conv_rows=2, state_dim=0)
    assert spec.state_shapes(129) == ((129, 2 * 2048), None)
    assert spec.state_bytes() == 2 * 2048 * 2 == 8192
    assert (spec.paged, spec.bytes_per_token()) == (False, 0)
    net = build()
    cache = kvc.PagedKVCache(8, 24, block_size=4, layout=net.kv_layout(),
                             max_batch_size=4)
    assert cache.state_slots == 5
    assert cache.k[0].shape == (5, 2 * 256) and cache.k[0].dtype == jnp.float32
    # nothing in the recurrent state's place: the empty array a none kind has
    assert cache.v[0].shape == (0,)
    assert cache.state_bytes_per_request() == 4 * 2 * 256 * 2
    # a tail is still needed; a recurrent state still belongs to the kind
    with pytest.raises(ValueError, match="state kind, and only it"):
        kvc.KVLayerSpec("state", k_dim=2048, conv_rows=0, state_dim=0)
    with pytest.raises(ValueError, match="state kind, and only it"):
        kvc.KVLayerSpec("global", 8, 64, 64, state_dim=16)


def test_the_state_kinds_before_keep_their_shapes():
    """Nemotron's and Qwen3-Next's state specs at their published widths:
    the shapes and bytes they had."""
    mamba = kvc.KVLayerSpec("state", 64, 4096 + 2 * 8 * 128, 64, conv_rows=3,
                            state_dim=128)
    assert mamba.heads_a_lane_row == 2
    assert mamba.state_shapes(193) == ((193, 3 * 6144), (193, 32, 128, 128))
    assert mamba.state_bytes() == 3 * 6144 * 2 + 64 * 64 * 128 * 4
    delta = kvc.KVLayerSpec("state", 32, 8192, 128, conv_rows=3, state_dim=128)
    assert delta.heads_a_lane_row == 1
    assert delta.state_shapes(97) == ((97, 3 * 8192), (97, 32, 128, 128))
    assert delta.state_bytes() == 2_097_152 + 49_152


def test_head_64_pages_hold_two_kv_heads_a_lane_row_and_nothing_else_does():
    """A global layer in rows with K and V 64 wide: ``[N, block * Hkv / 2,
    128]``, 2,048 B a token at 8 kv heads, the bytes of ``[N, block, Hkv,
    64]``. Every other form keeps its own."""
    h64 = kvc.KVLayerSpec("global", 8, 64, 64, prefill="linear")
    assert h64.heads_a_lane_row == 2
    assert h64.pool_shape(81921, 16, 64) == (81921, 64, 128)
    assert h64.bytes_per_token() == 2048
    for spec, shape in (
            (kvc.KVLayerSpec("global", 8, 64, 64), (10, 16, 8, 64)),
            (kvc.KVLayerSpec("global", 8, 128, 128, prefill="linear"),
             (10, 128, 128)),
            (kvc.KVLayerSpec("global", 2, 192, 128, 256, prefill="linear"),
             (10, 32, 128)),
            (kvc.KVLayerSpec("window", 8, 64, 64, window=32, prefill="linear"),
             (10, 128, 64)),
            (kvc.KVLayerSpec("global", 3, 64, 64, prefill="linear"),
             (10, 48, 64))):
        assert spec.heads_a_lane_row == 1, spec
        assert spec.pool_shape(10, 16, spec.v_dim) == shape, spec


# -- (c) the tail's life ----------------------------------------------------------

def test_a_second_request_in_a_recycled_slot_is_as_in_a_fresh_engine():
    """The slot held another request's tail, and a row dispatched ahead for
    it wrote there after it had left: the next request's first chunk starts
    from zeros whatever the slot holds."""
    net = build()
    first, second = prompts_of((30, 23), seed=7)
    with LLMEngine(net, capture_logits=True, **ENGINE) as fresh:
        want = generate(fresh, second, 5)
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        generate(eng, first, 6)
        held = [np.asarray(k[0]) for sp, k in zip(eng.cache.layout, eng.cache.k)
                if sp.kind == "state"]
        assert len(held) == 4
        assert all(np.abs(h).max() > 1e-3 for h in held)   # slot 0 is not zeros
        got = generate(eng, second, 5)
    assert got[0] == want[0]
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(a, b)
    for row, wanted in zip(got[1], reference_rows(net, second, got[0])[1:]):
        assert ref.row_error(row, wanted) < 2e-5


def _lose_the_tail(monkeypatch, how):
    """A fault the check must see: a chunk that is not a request's first
    starting from zeros (``boundary``), or a decode step that reads its tail
    and never writes the new one back (``stale``)."""
    if how == "boundary":
        shifted = spa.ChunkAttnState._shifted

        def zeroed(self, rows, slot):
            out, fresh, real = shifted(self, rows, slot)
            keep = self.spec.conv_rows
            return ([a.at[:keep - j].set(0) if j < keep else a
                     for j, a in enumerate(out)], fresh, real)

        monkeypatch.setattr(spa.ChunkAttnState, "_shifted", zeroed)
    else:
        shifted = spa.DecodeAttnState._shifted

        def stale(self, rows):
            pool = self.k_pool
            out = shifted(self, rows)
            self.k_pool = pool
            return out

        monkeypatch.setattr(spa.DecodeAttnState, "_shifted", stale)


@pytest.mark.parametrize("how", ["boundary", "stale"])
def test_a_tail_lost_fails_by_far(how, monkeypatch):
    """The same rows as the passing test, with the tail lost: every row
    after the fault is off the reference by orders of magnitude more than
    the float32 tolerance. (A stale tail is first read by the second decode
    step: the first reads what the last chunk left.)"""
    net = build()
    prompt = prompts_of((38,), seed=1)[0]
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        good = generate(eng, prompt, 5)
    _lose_the_tail(monkeypatch, how)
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        toks, rows = generate(eng, prompt, 5)
    want = reference_rows(net, prompt, toks)[1:]
    assert all(ref.row_error(a, b) < 2e-5
               for a, b in zip(good[1], reference_rows(net, prompt, good[0])[1:]))
    assert min(ref.row_error(a, b) for a, b in zip(rows[1:], want[1:])) > 1e-2


# -- (d) the head-64 kernels -------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_head_64_decode_kernel_matches_plain_jnp(dtype, monkeypatch):
    """Pools of two 64-wide kv heads a lane row through the decode kernel in
    interpret mode, against the gather over the same bytes read a head at a
    time: contexts at every edge of a page and of a chunk, dead slots on the
    null page, NaN wherever no live token is."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    hkv, h, d, blk, p = 4, 16, 64, 4, 10
    dt = jnp.dtype(dtype)
    # a chunk of 2 pages: full and partial chunks, and requests across them
    for budget in range(256, 1 << 20, 64):
        monkeypatch.setattr(pa, "_DECODE_VMEM_BUDGET", budget)
        if pa._decode_chunk(blk, hkv // 2, h, 128, dt.itemsize, p)[0] == 2:
            break
    lens = np.array([0, 1, blk - 1, blk, 2 * blk - 1, 2 * blk, 2 * blk + 1,
                     p * blk], np.int32)
    b, n = len(lens), len(lens) * p + 1
    rng = np.random.default_rng(0)
    order = rng.permutation(np.arange(1, n))
    tables = np.zeros((b, p), np.int32)
    live = np.zeros((n, blk), bool)
    at = 0
    for i, ctx in enumerate(lens):
        pages = -(-ctx // blk)
        tables[i, :pages] = order[at:at + pages]
        at += pages
        live[tables[i, :pages]] = True
        if pages:
            live[tables[i, pages - 1], ctx - (pages - 1) * blk:] = False
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), dt)
    kv = [jnp.asarray(rng.standard_normal((n, blk, hkv, d)), dt)
          for _ in range(2)]
    dirty = [jnp.where(jnp.asarray(live)[..., None, None], x, jnp.nan)
             .reshape(n, blk * hkv // 2, 2 * d) for x in kv]
    got = spa.paged_decode_attention_pairs(
        q, *dirty, jnp.asarray(tables), jnp.asarray(lens), 0.125, hkv)
    want = spa._lax_fallback(q, *kv, jnp.asarray(tables), jnp.asarray(lens),
                             0.125)
    rows = lens > 0                   # an empty row's output is undefined
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[rows], np.asarray(want, np.float32)[rows],
        atol=2e-2 if dtype == "bfloat16" else 1e-5)


@pytest.mark.parametrize("start,upto", [(0, 13), (16, 40), (48, 64)],
                         ids=["first", "across-tiles", "last"])
def test_the_head_64_chunk_kernel_matches_plain_jnp(start, upto, monkeypatch):
    """A chunk of 16 queries over keys in a row in the paired form, in
    interpret mode with key tiles of 8 (several a chunk, a chunk across
    two), against the masked attention over the same keys a head at a
    time."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    real = pa._chunk_tiles
    monkeypatch.setattr(pa, "_chunk_tiles", lambda t, g, ln, w: (
        lambda tq, tk, nk: (tq, 8, ln // 8))(*real(t, g, ln, w)))
    hkv, h, d, ln, t = 4, 8, 64, 64, 16
    rng = np.random.default_rng(start)
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((ln, hkv, d)), jnp.float32)
            for _ in range(2))
    got = spa.chunk_attention_pairs(
        q, k.reshape(ln, hkv // 2, 2 * d), v.reshape(ln, hkv // 2, 2 * d),
        jnp.int32(start), 0, jnp.int32(upto), 0.125, hkv, block_size=4)
    qp = start + np.arange(t)[:, None]
    kp = np.arange(ln)[None, :]
    ok = (kp <= qp) & (kp < upto)
    want = spa._masked_attention(q[None], k[None], v[None],
                                 jnp.asarray(ok)[None, None], 0.125)[0]
    real_rows = np.arange(t) < upto - start
    np.testing.assert_allclose(np.asarray(got)[real_rows],
                               np.asarray(want)[real_rows], atol=1e-5)


#: sha256 of the head-128 kernels' outputs (and the pools they wrote) on the
#: inputs of ``_head_128_outputs``, through the serving layer in interpret
#: mode, as the tree before head-64 pages computed them
HEAD_128_BEFORE = {
    "chunk-linear":
        "5b65a877bae818cc57241f732a7fe53300a1f51aee58c06908e48c8512837c02",
    "chunk-paged":
        "9c1afab927114a8159d1293125ebee6b15024c49c38edfde933534f5666178fc",
    "decode-linear":
        "9858bf695350cf54d758f96e0a9745f33d5f9131ea01a3b8265c9e2b13dc6ed7",
    "decode-paged":
        "715f150619c2d80ad7988be0478c33ccdef1f06c893d351b3e5bbb825c7ceb7d",
}


def _head_128_outputs():
    rng = np.random.default_rng(42)
    hkv, h, d, bs, p, b = 2, 8, 128, 4, 6, 3
    out = {}
    for prefill in ("linear", "paged"):
        spec = kvc.KVLayerSpec("global", hkv, d, d, prefill=prefill)
        n = b * p + 1
        kp, vp = (jnp.asarray(rng.standard_normal(spec.pool_shape(n, bs, d)),
                              jnp.bfloat16) for _ in range(2))
        tables = jnp.asarray(np.arange(1, n).reshape(b, p), jnp.int32)
        pos = jnp.asarray([0, 9, 22], jnp.int32)
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, 1, hkv, d)), jnp.bfloat16)
        st = spa.DecodeAttnState(spec, bs, pos, tables, kp, vp)
        o = st.attend(q, k, v, 0.088)
        out[f"decode-{prefill}"] = (o, st.k_pool, st.v_pool)
        qc = jnp.asarray(rng.standard_normal((1, 8, h, d)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((1, 8, hkv, d)), jnp.bfloat16)
        vc = jnp.asarray(rng.standard_normal((1, 8, hkv, d)), jnp.bfloat16)
        st = spa.ChunkAttnState(spec, bs, jnp.int32(8), jnp.int32(14),
                                tables[1], kp, vp)
        o = st.attend(qc, kc, vc, 0.088)
        out[f"chunk-{prefill}"] = (o, st.k_pool, st.v_pool)
    return out


def test_the_head_128_kernels_are_bit_for_bit_what_they_were(monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    got = {}
    for name, arrays in _head_128_outputs().items():
        digest = hashlib.sha256()
        for a in arrays:
            digest.update(np.asarray(a.astype(jnp.float32)).tobytes())
        got[name] = digest.hexdigest()
    assert got == HEAD_128_BEFORE
