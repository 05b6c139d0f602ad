"""MiMo-V2-Flash on the serving path (ISSUE 27), at toy widths that keep
the structure: K rows wider than V rows, 4 against 8 kv heads, a window
shorter than the prompts, the seven-layer pattern, 32 experts, 4 a token.

The float32 reference is ``benchmarks/harness/reference_mimo_v2.py``: it
shares no code with ``paddle_tpu``."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.inference.serving import kv_cache as kvc
from paddle_tpu.inference.serving import paged_attention as spa
from paddle_tpu.models import llama_tiny, LlamaForCausalLM
from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM, mimo_v2_tiny,
                                       moe_dropless)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_mimo_v2 as ref  # noqa: E402

ENGINE = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
              prefill_buckets=[8, 16, 32, 64, 96],
              max_prefill_tokens_per_step=16)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(seed=3, **kw):
    paddle_tpu.seed(seed)
    net = MiMoV2ForCausalLM(mimo_v2_tiny(**kw))
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
    outputs. A step that ends a prefill decodes once too and
    ``last_logits`` keeps the newer row, so row 0 comes from a second pass
    of one-token requests."""
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


# -- (a) the engine against the reference's full forward ---------------------

@pytest.mark.parametrize("held", [None, tuple(range(8, 16))],
                         ids=["all-experts", "a-share"])
@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_prefill_then_decode_matches_the_reference(interpret, held,
                                                   monkeypatch):
    """Prompts that fit one chunk, cross a chunk boundary (16) and cross it
    twice; all but the first are longer than the window (8) plus two pages,
    so the rows compared include queries whose window has slid, whose pages
    were released, and decodes after a release."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    net = build(experts_held=held)
    prompts = prompts_of((5, 21, 38))
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        rows, toks = rows_of(eng, prompts, 4)
        m = eng.metrics()
        assert m["window_blocks_released"] > 0
        assert m["global_blocks_in_use"] == m["window_blocks_in_use"] == 0
    assert len(rows) == 12
    w, model = weights_of(net), model_of(net)
    for i, (p, t) in enumerate(zip(prompts, toks)):
        want = np.asarray(ref.logits(
            w, np.concatenate([p, t])[None].astype(np.int32), model,
            experts_held=net.config.experts_held))[0]
        for j in range(4):
            assert ref.row_error(rows[(i, j)], want[len(p) - 1 + j]) < 2e-5, (i, j)


# -- (b) each kernel against plain attention ---------------------------------

def _plain_attention(q, k, v, scale, window, sink):
    """q [T, H, Dk] at the LAST T of S positions, k/v [S, Hkv, D]."""
    t, h, _ = q.shape
    s_len, hkv, _ = k.shape
    k = np.repeat(k, h // hkv, 1)
    v = np.repeat(v, h // hkv, 1)
    z = np.einsum("thd,shd->hts", q, k) * scale
    qp = np.arange(s_len - t, s_len)[:, None]
    kp = np.arange(s_len)[None, :]
    see = kp <= qp
    if window:
        see &= kp > qp - window
    z = np.where(see[None], z, -np.inf)
    if sink is not None:
        z = np.concatenate([z, np.broadcast_to(sink[:, None, None], (h, t, 1))], -1)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    if sink is not None:
        p = p[..., :-1]
    return np.einsum("hts,shd->thd", p, v)


@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
@pytest.mark.parametrize("sink_on", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("kind", ["global", "window"])
def test_decode_kernel_over_ragged_lengths(kind, sink_on, interpret,
                                           monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    rng = np.random.default_rng(5)
    bs, h, hkv, dk, dv, window = 4, 8, 4, 32, 16, 8
    lens = [1, 7, 9, 23, 40]
    ring = kvc.ring_pages(window, bs)
    p_max = ring if kind == "window" else 12
    n = 64
    kp = rng.normal(size=(n, bs * hkv, dk)).astype(np.float32)
    vp = rng.normal(size=(n, bs * hkv, dv)).astype(np.float32)
    tables = np.zeros((len(lens), p_max), np.int32)
    ks, vs = [], []
    free = list(range(1, n))
    for b, ln in enumerate(lens):
        k = rng.normal(size=(ln, hkv, dk)).astype(np.float32)
        v = rng.normal(size=(ln, hkv, dv)).astype(np.float32)
        ks.append(k)
        vs.append(v)
        last = (ln - 1) // bs
        first = max(last - ring + 1, 0) if kind == "window" else 0
        for page in range(first, last + 1):
            blk = free.pop()
            tables[b, page % p_max if kind == "window" else page] = blk
            rows = slice(page * bs, min((page + 1) * bs, ln))
            cnt = rows.stop - rows.start
            kp[blk, :cnt * hkv] = k[rows].reshape(cnt * hkv, dk)
            vp[blk, :cnt * hkv] = v[rows].reshape(cnt * hkv, dv)
    q = rng.normal(size=(len(lens), 1, h, dk)).astype(np.float32)
    sink = rng.normal(size=h).astype(np.float32) if sink_on else None
    got = spa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), scale=0.2,
        window=window if kind == "window" else None, ring=kind == "window",
        sink=None if sink is None else jnp.asarray(sink), num_kv_heads=hkv,
        name="paged_decode_attention_" + kind)
    for b, ln in enumerate(lens):
        want = _plain_attention(q[b], ks[b], vs[b], 0.2,
                                window if kind == "window" else None, sink)
        np.testing.assert_allclose(np.asarray(got[b]), want, atol=2e-5)


@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
@pytest.mark.parametrize("sink_on", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("kind", ["global", "window"])
def test_chunk_kernel_at_every_offset(kind, sink_on, interpret, monkeypatch):
    """A 16-row chunk at offsets 0, 16 and 32 of a 41-token request, the
    last chunk ragged (9 real rows): keys before the chunk in a row with a
    start of its own, as ``ChunkAttnState`` hands them over."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    rng = np.random.default_rng(9)
    h, hkv, dk, dv, window, t = 8, 4, 32, 16, 8, 16
    total = 41
    k = rng.normal(size=(48, hkv, dk)).astype(np.float32)
    v = rng.normal(size=(48, hkv, dv)).astype(np.float32)
    q = rng.normal(size=(48, h, dk)).astype(np.float32)
    sink = rng.normal(size=h).astype(np.float32) if sink_on else None
    for start in (0, 16, 32):
        upto = min(start + t, total)
        if kind == "window":        # the tail of 8 before the chunk
            k_start = start - 8
            rows = np.arange(k_start, start + t)
            kk = np.where((rows >= 0)[:, None, None], k[np.clip(rows, 0, 47)], 7.0)
            vv = np.where((rows >= 0)[:, None, None], v[np.clip(rows, 0, 47)], 7.0)
        else:
            k_start, kk, vv = 0, k, v
        got = spa.chunk_attention(
            jnp.asarray(q[start:start + t]), jnp.asarray(kk), jnp.asarray(vv),
            start, k_start, upto, 0.2,
            window=window if kind == "window" else None,
            sink=None if sink is None else jnp.asarray(sink), block_size=4,
            name="chunk_attention_" + kind)
        want = _plain_attention(q[:upto], k[:upto], v[:upto], 0.2,
                                window if kind == "window" else None, sink)
        np.testing.assert_allclose(np.asarray(got)[:upto - start],
                                   want[start:upto], atol=2e-5)


# -- (c), (d) the expert block ------------------------------------------------

def _moe_parts(net, layer=1):
    mlp = net.model.layers[layer].mlp
    experts = [(e.gate_proj.weight._data, e.up_proj.weight._data,
                e.down_proj.weight._data) for e in mlp.experts]
    return mlp, experts


def _share(net, x, held, layer=1, bias=None, **kw):
    mlp, experts = _moe_parts(net, layer)
    slot = np.full(net.config.n_routed_experts, len(held), np.int32)
    slot[list(held)] = np.arange(len(held))
    c = net.config
    return moe_dropless(
        x, mlp.router.weight._data,
        mlp.router.e_score_correction_bias._data if bias is None else bias,
        [experts[e] for e in held], slot, top_k=c.num_experts_per_tok, **kw)


@pytest.mark.parametrize("path,width", [("loop", 64), ("kernel", 128)])
def test_the_shares_add_up_to_the_uncut_layer(path, width, monkeypatch):
    """model-configs section 4: the parts that the four shares of eight
    experts give add up to what the uncut reference gives for the layer,
    through the tile loop and through the grouped kernel (interpret mode, at
    a width it takes)."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1" if path == "kernel" else "0")
    net = build(hidden_size=width, moe_intermediate_size=2 * width)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, width)),
                    jnp.float32)
    w = {k[len("model.layers.1."):]: v for k, v in weights_of(net).items()
         if k.startswith("model.layers.1.")}
    w = dict(w, **{"post_attention_layernorm.weight": jnp.ones(width)})
    full, _ = ref._expert_ffn(
        x[None], w, eps=0.0, top_k=4, norm_topk=True, scaling=None,
        held=tuple(range(32)))
    # the reference norms its input: hand the shares the same normed rows
    normed = np.asarray(ref._rms(x, jnp.ones(width), 0.0))
    parts, pairs = 0.0, 0
    for s in range(4):
        y, n_pairs, _ = _share(net, jnp.asarray(normed), range(8 * s, 8 * s + 8))
        parts = parts + np.asarray(y)
        pairs += int(n_pairs)
    assert pairs == 37 * 4                 # every pair computed exactly once
    np.testing.assert_allclose(parts, np.asarray(full)[0] - np.asarray(x),
                               atol=2e-5)


def test_router_chooses_by_corrected_and_weighs_by_uncorrected_scores():
    net = build()
    mlp, experts = _moe_parts(net)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    bias = np.zeros(32, np.float32)
    bias[[3, 17]] = 10.0                   # always chosen, whatever their score
    y, pairs, hit = _share(net, x, range(32), bias=jnp.asarray(bias))
    scores = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                               @ np.asarray(mlp.router.weight._data, np.float64))))
    want = np.zeros((9, 64))
    for t in range(9):
        sel = np.argsort(-(scores[t] + bias))[:4]
        assert {3, 17} <= set(sel)
        comb = scores[t, sel] / scores[t, sel].sum()    # uncorrected
        for e, c in zip(sel, comb):
            g, u, d = (np.asarray(a, np.float64) for a in experts[e])
            hx = np.asarray(x[t], np.float64)
            a = hx @ g
            want[t] += c * (((a / (1 + np.exp(-a))) * (hx @ u)) @ d)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert int(pairs) == 36 and int(hit) >= 2


@pytest.mark.parametrize("tokens", [1, 8, 67])
def test_no_token_is_dropped_whatever_the_batch(tokens):
    """Every token routed to the same four experts: a capacity would drop
    most of them; here all ``tokens x 4`` pairs are computed."""
    net = build()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(tokens, 64)), jnp.float32)
    bias = np.zeros(32, np.float32)
    bias[[1, 2, 5, 30]] = 10.0
    y, pairs, hit = _share(net, x, range(32), bias=jnp.asarray(bias), tm=8)
    assert int(pairs) == tokens * 4 and int(hit) == 4
    alone = np.stack([np.asarray(_share(net, x[i:i + 1], range(32),
                                        bias=jnp.asarray(bias), tm=8)[0])[0]
                      for i in range(min(tokens, 5))])
    np.testing.assert_allclose(np.asarray(y)[:len(alone)], alone, atol=1e-6)


def test_a_requests_logits_are_the_same_alone_and_in_a_full_batch():
    net = build()
    prompts = prompts_of((21, 9, 30, 14), seed=8)

    def rows_for(batch):
        with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
            rids = [eng.add_request(p, SamplingParams(max_new_tokens=5))
                    for p in batch]
            got = []
            while eng.has_work():
                for out in eng.step():
                    if out.rid == rids[0]:
                        got.append(eng.request(out.rid).last_logits.copy())
            return np.stack(got)

    alone, full = rows_for(prompts[:1]), rows_for(prompts)
    np.testing.assert_allclose(full, alone, atol=1e-5)
    assert np.array_equal(full.argmax(-1), alone.argmax(-1))


def test_a_step_dispatched_before_the_first_token_leaves_the_new_ring_alone():
    """ISSUE 34 on the window kind: a request arrives beside one that
    decodes, its chunk (prompt past the window: the ring has turned in
    prefill) is enqueued behind the step in flight and the NEXT step is
    dispatched before the chunk's logits are fetched. To that step the new
    request is mid-prefill: its row of the table, ring and all, is the null
    block. It then reads as it does alone, and as the reference does."""
    net = build()
    short, new = prompts_of((6, 14), seed=12)
    engine = dict(ENGINE, ingest_async=False)

    def rows_of_last(eng, rid):
        rows = [eng.request(rid).last_logits.copy()]
        while not eng.request(rid).finished:
            rows += [eng.request(rid).last_logits.copy() for o in eng.step()
                     if o.rid == rid]
        return rows

    with LLMEngine(net, capture_logits=True, **engine) as fresh:
        rid = fresh.add_request(new, SamplingParams(max_new_tokens=5))
        fresh.step()
        want = rows_of_last(fresh, rid)     # alone, the first call decodes too
        want_toks = list(fresh.request(rid).output_tokens)
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
        # the step in flight now was dispatched before b's first token: one
        # live row, every other row of its table at the null block
        assert [row[1].rid for row in eng._ahead.rows] == [a]
        slot = eng.scheduler.slots.index(eng.request(b))
        table = np.asarray(eng._tables_dev)
        assert not table[slot].any() and table.any()
        held = eng.cache.window.held(b)
        rows = rows_of_last(eng, b)
        assert eng.cache.window.held(b) == 0 < held
        got = list(eng.request(b).output_tokens)
        eng.cancel(a)
    assert got == want_toks
    assert len(rows) == 5 == len(want) + 1
    np.testing.assert_allclose(np.stack(rows[1:]), np.stack(want), atol=1e-5)
    full = np.asarray(ref.logits(
        weights_of(net), np.concatenate([new, got])[None].astype(np.int32),
        model_of(net), experts_held=net.config.experts_held))[0]
    for j, row in enumerate(rows):
        assert ref.row_error(row, full[len(new) - 1 + j]) < 2e-5, j


def test_a_greedy_step_fetches_its_tokens_and_they_are_the_rows_argmax():
    """The default engine fetches ``[B]`` tokens a decode step, the decode
    graph's own argmax; with ``capture_logits`` it fetches the rows beside
    them. Both give the same tokens, through releases and chunks."""
    net = build()
    prompts = prompts_of((21, 9, 38, 14), seed=11)
    got = {}
    for rows in (False, True):
        with LLMEngine(net, capture_logits=rows, **ENGINE) as eng:
            got[rows] = eng.generate(prompts, SamplingParams(max_new_tokens=7))
            m = eng.metrics()
        width = 160 + 1 if rows else 1
        assert m["decode_fetch_bytes"] == \
            m["host_syncs"] * ENGINE["max_batch_size"] * width * 4
    for a, b in zip(got[False], got[True]):
        assert np.array_equal(a, b)


# -- (e) two kinds of pages ----------------------------------------------------

def test_a_window_request_never_holds_more_than_a_ring_and_nothing_leaks():
    """1,000 steps of churn over the window pages alone: requests arrive
    with prompts of any length, are prefilled in chunks, decode, and leave."""
    bs, window, batch = 4, 8, 6
    ring = kvc.ring_pages(window, bs)
    assert ring == 3
    alloc = kvc.BlockAllocator(batch * (ring + 1) + 1)
    pages = kvc.WindowPages(alloc, window, bs)
    rng = np.random.default_rng(0)
    live, next_rid, released_before = {}, 0, 0
    for _ in range(1000):
        if len(live) < batch and rng.random() < 0.3:
            live[next_rid] = [0, int(rng.integers(1, 90)), int(rng.integers(1, 40))]
            next_rid += 1
        for rid, st in list(live.items()):
            pos, prompt, out = st
            if pos < prompt:                       # a prefill chunk
                take = min(16, prompt - pos)
                row = pages.chunk_row(rid, pos, pos + take, 4)
                assert len(row) == pages.n_tail + min(ring, 4) + 1
                st[0] = pos + take
            elif pos < prompt + out:               # a decode step
                pages.ensure(rid, pos // bs, pos // bs)
                row = pages.table_row(rid)
                assert row[(pos // bs) % ring] != 0
                st[0] = pos + 1
            else:
                pages.release(rid)
                del live[rid]
                continue
            assert pages.held(rid) <= ring
        assert pages.blocks_in_use == sum(pages.held(r) for r in live)
    assert pages.released > released_before + 100
    for rid in list(live):
        pages.release(rid)
    assert pages.blocks_in_use == 0 and alloc.num_free == alloc.num_blocks - 1


def test_engine_returns_every_page_of_both_kinds():
    net = build()
    with LLMEngine(net, **ENGINE) as eng:
        free = (eng.cache.allocator.num_free,
                eng.cache.window.allocator.num_free)
        for wave in range(3):
            rids = [eng.add_request(p, SamplingParams(max_new_tokens=12))
                    for p in prompts_of((30, 7, 19, 40, 11), seed=wave)]
            while eng.has_work():
                eng.step()
                for r in eng.scheduler.running:
                    assert eng.cache.window.held(r.rid) <= eng.cache.window.ring
            for r in rids:
                eng.release(r)
        assert (eng.cache.allocator.num_free,
                eng.cache.window.allocator.num_free) == free
        assert eng.metrics()["window_blocks_released"] > 20


@pytest.mark.parametrize("kwargs,names", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(kv_host_blocks=8), "kv_host_blocks"),
    (dict(prefill_only=True), "prefill_only"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_page_checksums=True), "kv_page_checksums"),
])
def test_what_assumes_one_layout_refuses_a_window_kind(kwargs, names):
    net = build()
    with pytest.raises(ValueError, match=names):
        LLMEngine(net, **dict(ENGINE, **kwargs))


def test_the_window_pool_is_sized_from_the_batch_and_is_no_option():
    # every slot a full ring and a page of slack, and the null page: the
    # cache derives it, and neither it nor the engine takes a number for it
    net = build()
    eng = LLMEngine(net, **ENGINE)
    try:
        ring = eng.cache.window.ring
        assert eng.cache.window_num_blocks \
            == ENGINE["max_batch_size"] * (ring + 1) + 1
        assert eng.cache.window.allocator.num_free \
            == eng.cache.window_num_blocks - 1
    finally:
        eng.close()
    with pytest.raises(TypeError):
        LLMEngine(net, **dict(ENGINE, window_num_blocks=5))
    with pytest.raises(ValueError, match="max_batch_size"):
        kvc.PagedKVCache(net.config, 8, 4, layout=net.kv_layout())


def test_export_refuses_a_cache_that_is_not_uniform():
    net = build()
    with LLMEngine(net, **ENGINE) as eng:
        with pytest.raises(ValueError, match="one pool geometry"):
            eng.cache.export_request_pages([1], 4)
        with pytest.raises(ValueError, match="one pool geometry"):
            eng.cache.copy_block(1, 2)


@pytest.mark.parametrize("kwargs", [
    dict(draft_model="a-llama"), dict(plan="a-plan")],
    ids=["draft-verify", "plan"])
def test_the_llama_only_paths_refuse_another_model_by_name(kwargs):
    net = build()
    if "draft_model" in kwargs:
        kwargs = dict(draft_model=LlamaForCausalLM(llama_tiny()))
    with pytest.raises(ValueError, match="LlamaForCausalLM only.*MiMoV2ForCausalLM"):
        LLMEngine(net, **dict(ENGINE, **kwargs))


def test_a_model_without_the_serving_calls_is_refused():
    from paddle_tpu.models import bert_tiny, BertModel

    with pytest.raises(TypeError, match="serving calls"):
        LLMEngine(BertModel(bert_tiny()))


def test_the_router_and_the_sinks_stay_float32_under_bfloat16():
    net = build()
    net.bfloat16()
    kinds = {n: str(p.dtype) for n, p in net.named_parameters()}
    for n, dt in kinds.items():
        want = "float32" if (".router." in n or n.endswith("sink_bias")) \
            else "bfloat16"
        assert want in dt, (n, dt)
    assert sum(1 for n in kinds if n.endswith("sink_bias")) == 5


def test_kv_layout_pads_k_rows_and_counts_published_bytes():
    net = build()
    layout = net.kv_layout()
    assert [sp.kind for sp in layout] == ["global", "window", "window",
                                          "window", "window", "global", "window"]
    assert {(sp.k_dim, sp.k_store, sp.v_dim) for sp in layout} == {(24, 32, 16)}
    assert {sp.num_kv_heads for sp in layout if sp.kind == "window"} == {8}
    cache = kvc.PagedKVCache(net.config, 8, 4, layout=layout, max_batch_size=2)
    assert not cache.uniform and cache.window.ring == 3
    assert cache.published_bytes_per_token("global") == 2 * 4 * 40 * 2
    assert cache.published_bytes_per_token("window") == 5 * 8 * 40 * 2
    assert cache.k[0].shape == (8, 16, 32) and cache.v[1].shape == (9, 32, 16)


# -- the kernels at the published geometry, as far as a machine without a chip
#    allows --------------------------------------------------------------------

_COMPILE = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        print("NO_TOPOLOGY", repr(e)[:300])
        sys.exit(0)
    from paddle_tpu.ops.pallas import paged_attention as pa
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
    bf, i32 = jnp.bfloat16, jnp.int32
    cases = {{}}
    for kind, hkv, n, p, window in (("global", 4, 49153, 768, None),
                                    ("window", 8, 641, 9, 128)):
        def decode(q, k, v, t, l, s, window=window, hkv=hkv, kind=kind):
            return pa.paged_decode_attention_pallas(
                q, k, v, t, l, 0.072, window=window, ring=window is not None,
                sink=s if window else None, num_kv_heads=hkv,
                name="paged_decode_attention_" + kind)
        cases["decode-" + kind] = (decode, (
            sds((64, 64, 256), bf), sds((n, 16 * hkv, 256), bf),
            sds((n, 16 * hkv, 128), bf), sds((64, p), i32), sds((64,), i32),
            sds((64,), jnp.float32)))
        ln = 128 + 2048 if window else 12288
        def chunk(q, k, v, a, b, c, s, window=window, kind=kind):
            return pa.chunk_attention_pallas(
                q, k, v, a, b, c, 0.072, window=window,
                sink=s if window else None, name="chunk_attention_" + kind)
        cases["chunk-" + kind] = (chunk, (
            sds((2048, 64, 256), bf), sds((ln, hkv, 256), bf),
            sds((ln, hkv, 128), bf), sds((), i32), sds((), i32), sds((), i32),
            sds((64,), jnp.float32)))
    for name, (fn, args) in cases.items():
        text = jax.jit(fn).trace(*args).lower().compile().as_text()
        print("COMPILED", name, *sorted(set(
            ln.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
            for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln)), flush=True)
""")


def test_the_new_kernels_compile_for_a_v5e_at_the_published_geometry():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PT_PALLAS_INTERPRET="0")
    try:
        r = subprocess.run([sys.executable, "-c", _COMPILE.format(repo=REPO)],
                           env=env, capture_output=True, text=True, timeout=240)
    except subprocess.TimeoutExpired:
        pytest.skip("deviceless compile did not finish in 240 s")
    if "NO_TOPOLOGY" in r.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    done = dict(ln.split(" ")[1:3] for ln in r.stdout.splitlines()
                if ln.startswith("COMPILED "))
    assert done == {
        "decode-global": "paged_decode_attention_global",
        "decode-window": "paged_decode_attention_window",
        "chunk-global": "chunk_attention_global",
        "chunk-window": "chunk_attention_window"}
