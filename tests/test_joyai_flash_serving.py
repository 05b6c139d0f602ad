"""JoyAI-LLM-Flash on the serving path (ISSUE 31), at toy widths that keep
the structure: one latent row a token (32 + 8 wide, stored 48) under 4 heads
of 16 + 8 / 16, a dense layer then two expert layers, 32 experts, 4 a token,
one shared expert, the prediction module.

The float32 reference is ``benchmarks/harness/reference_joyai_flash.py``: it
shares no code with ``paddle_tpu`` and rotates the rope pairs as named."""

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
from paddle_tpu.models import (JoyAIFlashForCausalLM, LlamaForCausalLM,
                               joyai_flash_tiny, llama_tiny)
from paddle_tpu.models.mimo_v2 import moe_dropless

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_joyai_flash as ref  # noqa: E402

ENGINE = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
              prefill_buckets=[8, 16, 32, 64, 96],
              max_prefill_tokens_per_step=16)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(seed=3, **kw):
    paddle_tpu.seed(seed)
    net = JoyAIFlashForCausalLM(joyai_flash_tiny(**kw))
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


class SyncEngine(LLMEngine):
    """No step in flight behind the one fetched: the loop's books and the
    device's counters then cover the same steps."""

    def _dispatch_ahead(self, cur):
        self._sync_reason = "path"
        return None


# -- (a) the engine against the reference's full forward ---------------------

@pytest.mark.parametrize("held", [None, tuple(range(8, 16))],
                         ids=["all-experts", "a-share"])
@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_prefill_then_decode_matches_the_reference(interpret, held,
                                                   monkeypatch):
    """Prompts that fit one chunk, cross a chunk boundary (16) and cross it
    twice: the rows compared come from expanded chunks over cached rows of
    earlier chunks and from absorbed decode steps over both."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    net = build(experts_held=held)
    prompts = prompts_of((5, 21, 38))
    with LLMEngine(net, capture_logits=True, **ENGINE) as eng:
        rows, toks = rows_of(eng, prompts, 4)
        assert eng.metrics()["global_blocks_in_use"] == 0
    assert len(rows) == 12
    w, model = weights_of(net), model_of(net)
    for i, (p, t) in enumerate(zip(prompts, toks)):
        want = np.asarray(ref.logits(
            w, np.concatenate([p, t])[None].astype(np.int32), model,
            experts_held=net.config.experts_held))[0]
        for j in range(4):
            assert ref.row_error(rows[(i, j)], want[len(p) - 1 + j]) < 2e-5, (i, j)


def test_the_models_own_forward_and_prediction_module_match_the_reference():
    net = build()
    w, model = weights_of(net), model_of(net)
    ids = prompts_of((29,), seed=4)[0][None]
    logits, hidden = net(ids)
    want, want_hidden = ref.logits(w, ids, model, with_hidden=True)
    np.testing.assert_allclose(np.asarray(hidden._data),
                               np.asarray(want_hidden), atol=2e-6)
    for t in (0, 13, 28):
        assert ref.row_error(np.asarray(logits._data)[0, t],
                             np.asarray(want)[0, t]) < 2e-5
    # position t: the trunk's state after tokens 0..t, and token t + 1
    got = np.asarray(net.mtp_logits(hidden[:, :-1], ids[:, 1:])._data)
    module = np.asarray(ref.mtp_logits(w, want_hidden[:, :-1], ids[:, 1:], model))
    assert got.shape == (1, 28, 160)
    for t in (0, 13, 27):
        assert ref.row_error(got[0, t], module[0, t]) < 2e-5
    # it is another function than the trunk's head, and it reads the token
    assert ref.row_error(got[0, 13], np.asarray(want)[0, 13]) > 0.1
    other = ids.copy()
    other[0, 14] = (other[0, 14] + 1) % 160
    moved = np.asarray(net.mtp_logits(hidden[:, :-1], other[:, 1:])._data)
    assert np.array_equal(moved[0, :13], got[0, :13])          # causal
    assert ref.row_error(moved[0, 13], got[0, 13]) > 1e-3
    with pytest.raises(ValueError, match="no prediction module"):
        build(num_nextn_predict_layers=0).mtp_logits(hidden, ids)


# -- (b) absorbed against expanded, kernel against fallback ---------------------

def _latent_case(seed=5, b=5, h=4, rank=32, rope=8, bs=4, p_max=12, n=64):
    rng = np.random.default_rng(seed)
    lens = [1, 7, 9, 23, 40][:b]
    store = 48
    pool = rng.normal(size=(n, bs, store)).astype(np.float32)
    pool[..., rank + rope:] = 0.0
    tables = np.zeros((b, p_max), np.int32)
    rows, free = [], list(range(1, n))
    for i, ln in enumerate(lens):
        r = rng.normal(size=(ln, rank + rope)).astype(np.float32)
        rows.append(r)
        for page in range((ln - 1) // bs + 1):
            blk = free.pop()
            tables[i, page] = blk
            part = r[page * bs:(page + 1) * bs]
            pool[blk, :len(part), :rank + rope] = part
            # what a page's unwritten slots hold must not matter
            pool[blk, len(part):] = np.nan
    return rng, lens, pool, tables, rows, store


@pytest.mark.parametrize("interpret,chunk_pages",
                         [("0", None), ("1", None), ("1", 4)],
                         ids=["lax", "pallas", "pallas-chunks-of-4"])
def test_latent_decode_kernel_over_ragged_lengths(interpret, chunk_pages,
                                                  monkeypatch):
    """Every head of a request against the SAME cached rows: scores over the
    whole row, values its first ``v_dim``; lengths of one token, under a
    page, across pages and across chunks of the copy pipeline. One chunk
    holds any of these requests; with the kernel's plan cut to 4 pages a
    chunk they take 1, 2 and 3 pages of one, a full chunk and 2, two full
    chunks and 2: copies started written out and in the last chunk's loop,
    waits of 4 pages and of the binary digits of fewer."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    rng, lens, pool, tables, rows, store = _latent_case()
    h, rank = 4, 32
    if chunk_pages:
        from paddle_tpu.ops.pallas import paged_attention as pa

        plan = (4, 1, h, store, 4)
        monkeypatch.setattr(pa, "_DECODE_VMEM_BUDGET",
                            pa._decode_chunk(*plan, chunk_pages, 0)[1])
        assert pa._decode_chunk(*plan, tables.shape[1], 0)[0] == chunk_pages
    q = rng.normal(size=(len(lens), h, store)).astype(np.float32)
    q[..., 40:] = 0.0
    got = np.asarray(spa.paged_decode_attention_latent(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), 0.2, rank))
    assert got.shape == (len(lens), h, rank)
    for b, r in enumerate(rows):
        z = q[b, :, :40] @ r.T * 0.2
        p = np.exp(z - z.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[b], p @ r[:, :rank], atol=2e-5)


@pytest.mark.parametrize("chunk_pages", [256, 32], ids=["one-chunk", "chunks-of-32"])
def test_the_pallas_kernel_and_the_lax_fallback_agree_on_a_long_table(
        chunk_pages, monkeypatch):
    """256 pages a request at the widths of the CPU tests. With the kernel's
    VMEM plan cut to 32 pages a chunk: full chunks (whose copies are started
    written out and waited for with one descriptor) and a ragged last one, a
    request that ends on a chunk's edge, one of a single page, one of none."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(11)
    n, bs, store, rank, h = 300, 4, 48, 32, 4
    monkeypatch.setattr(pa, "_DECODE_VMEM_BUDGET",
                        pa._decode_chunk(bs, 1, h, store, 4, chunk_pages, 0)[1])
    assert pa._decode_chunk(bs, 1, h, store, 4, 256, 0)[0] == chunk_pages
    pool = rng.normal(size=(n, bs, store)).astype(np.float32)
    lens = np.array([1000, 0, 517, 64, 3, 512], np.int32)
    tables = np.zeros((len(lens), 256), np.int32)
    for b, ln in enumerate(lens):
        pages = -(-int(ln) // bs)
        tables[b, :pages] = rng.permutation(np.arange(1, n))[:pages]
    q = rng.normal(size=(len(lens), h, store)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(lens), 0.1, rank)
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    want = np.asarray(spa.paged_decode_attention_latent(*args))
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    got = np.asarray(spa.paged_decode_attention_latent(*args))
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)


@pytest.mark.parametrize("interpret", ["0", "1"], ids=["lax", "pallas"])
def test_absorbed_and_expanded_give_the_same_numbers_on_the_same_rows(
        interpret, monkeypatch):
    """A decode step (absorbed) and a one-page chunk that ends at the same
    token (expanded), over the same cached rows, through the two state
    handles: the last row's attention output is the same."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", interpret)
    rng = np.random.default_rng(2)
    h, dn, dr, dv, rank, bs = 4, 16, 8, 16, 32, 4
    spec = kvc.KVLayerSpec("latent", 1, rank + dr, rank, 48, prefill="linear")
    ctx = 22                                     # the new token sits at 21
    rows = rng.normal(size=(ctx, rank + dr)).astype(np.float32)
    w_kvb = (rng.normal(size=(rank, h * (dn + dv))) * 0.2).astype(np.float32)
    q_nope = rng.normal(size=(ctx, h, dn)).astype(np.float32)
    q_rope = rng.normal(size=(ctx, h, dr)).astype(np.float32)
    pool = np.zeros((16, bs, 48), np.float32)
    table = np.zeros(8, np.int32)
    table[:6] = [5, 3, 9, 1, 7, 2]
    for t in range(20):                          # pages 0..4 are cached
        pool[table[t // bs], t % bs, :rank + dr] = rows[t]
    scale = 1.0 / np.sqrt(dn + dr)

    dec = spa.DecodeAttnState(
        spec, bs, jnp.asarray([21, 0], jnp.int32),
        jnp.asarray(np.stack([table, np.zeros(8, np.int32)])),
        jnp.asarray(pool).at[table[5], 0].set(
            jnp.asarray(np.pad(rows[20], (0, 8)))), jnp.zeros((0,)),
        counters=(counts := {}))
    a = np.asarray(dec.attend_latent(
        jnp.asarray(q_nope[None, 21:22].repeat(2, 0)),
        jnp.asarray(q_rope[None, 21:22].repeat(2, 0)),
        jnp.asarray(rows[None, 21:22].repeat(2, 0)), jnp.asarray(w_kvb),
        scale))[0, 0]
    # the live row's context, and not the empty slot's
    assert int(counts["mla_latent_tokens_read"]) == 22

    chunk = spa.ChunkAttnState(
        spec, bs, jnp.int32(20), jnp.int32(22), jnp.asarray(table),
        jnp.asarray(pool), jnp.zeros((0,)), counters=(counts := {}))
    pad = lambda x: np.concatenate([x[20:22], np.zeros_like(x[:2])])[None]  # noqa: E731
    e = np.asarray(chunk.attend_latent(
        jnp.asarray(pad(q_nope)), jnp.asarray(pad(q_rope)),
        jnp.asarray(pad(rows)), jnp.asarray(w_kvb), scale))[0, 1]
    assert int(counts["mla_context_tokens_expanded"]) == 24    # 6 blocks of 4
    np.testing.assert_allclose(a, e, atol=2e-5)
    # both wrote the token's row where the table says, and only there
    np.testing.assert_allclose(
        np.asarray(dec.k_pool)[table[5], 1, :40], rows[21], atol=0)
    np.testing.assert_allclose(
        np.asarray(chunk.k_pool)[table[5], :2, :40], rows[20:22], atol=0)
    # and the published form, written out
    kv = (rows[:, :rank] @ w_kvb).reshape(ctx, h, dn + dv)
    z = (np.einsum("hd,khd->hk", q_nope[21], kv[..., :dn])
         + q_rope[21] @ rows[:, rank:].T) * scale
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(a, np.einsum("hk,khd->hd", p, kv[..., dn:]),
                               atol=2e-5)


def test_the_rope_pairs_are_the_named_ones_in_another_order():
    """Even-first then half-split is the rotation of pairs (2i, 2i + 1), the
    result left in even-first order: the same scores."""
    from paddle_tpu.models.joyai_flash import _even_first
    from paddle_tpu.models.llama import _rope_cache, rope_rotate

    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 9, 3, 8)).astype(np.float32)
    y = rng.normal(size=(1, 9, 1, 8)).astype(np.float32)
    cos, sin = (jnp.asarray(t)[None, :, None, :]
                for t in _rope_cache(9, 8, 32e6))
    mine = lambda a: np.asarray(rope_rotate(_even_first(jnp.asarray(a)), cos, sin))  # noqa: E731
    named = lambda a: np.asarray(ref._rope_pairs(jnp.asarray(a), 32e6))  # noqa: E731
    np.testing.assert_allclose(mine(x), np.asarray(_even_first(named(x))),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.einsum("bqhd,bkd->bhqk", mine(x), mine(y)[:, :, 0]),
        np.einsum("bqhd,bkd->bhqk", named(x), named(y)[:, :, 0]), atol=1e-5)


# -- (c) the shares add up ---------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs section 4: the routed parts that the four shares of
    eight experts give, plus the shared expert counted ONCE, are what the
    uncut reference gives for the whole layer."""
    net = build()
    layer = net.model.layers[1]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, 64)), jnp.float32)
    w = {k[len("model.layers.1."):]: v for k, v in weights_of(net).items()
         if k.startswith("model.layers.1.")}
    model = model_of(net)
    full, _ = ref._feed_forward(x[None], w, model, tuple(range(32)), True)
    normed = layer.post_attention_layernorm(paddle_tpu.to_tensor(np.asarray(x)))
    experts = [(e.gate_proj.weight._data, e.up_proj.weight._data,
                e.down_proj.weight._data) for e in layer.mlp.experts]
    parts, pairs = 0.0, 0
    for s in range(4):
        held = range(8 * s, 8 * s + 8)
        slot = np.full(32, 8, np.int32)
        slot[list(held)] = np.arange(8)
        y, n_pairs, _ = moe_dropless(
            normed._data, layer.mlp.router.weight._data,
            layer.mlp.router.e_score_correction_bias._data,
            [experts[e] for e in held], slot, top_k=4, scaling=2.5)
        parts = parts + np.asarray(y)
        pairs += int(n_pairs)
    assert pairs == 37 * 4                 # every pair computed exactly once
    shared = np.asarray(layer.shared_experts(normed)._data)
    np.testing.assert_allclose(parts + shared,
                               np.asarray(full)[0] - np.asarray(x), atol=2e-5)
    # the scaling is the routed sum's alone: twice the shared expert is wrong
    assert np.abs(parts + 2 * shared
                  - (np.asarray(full)[0] - np.asarray(x))).max() > 1e-3
    # and a share's layer is its routed part plus the shared expert
    share = build(experts_held=tuple(range(8)))
    out = share.model.layers[1].feed_forward(paddle_tpu.to_tensor(np.asarray(x)[None]))
    want, _ = ref._feed_forward(
        x[None], {k[len("model.layers.1."):]: v
                  for k, v in weights_of(share).items()
                  if k.startswith("model.layers.1.")},
        model_of(share), tuple(range(8)), True)
    np.testing.assert_allclose(np.asarray(out._data), np.asarray(want), atol=2e-5)


# -- (d) a request alone and in a full batch; counters ----------------------------

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


def test_the_counters_count_live_rows_and_expanded_rows_a_layer():
    """``mla_latent_tokens_read_decode`` is the sum of the decoded rows'
    live lengths times the layers; ``mla_context_tokens_expanded_prefill``
    the rows the chunks expanded (whole blocks of a chunk's length up to its
    end) times the layers; MiMo's expert counters under their names."""
    net = build()
    prompts = prompts_of((21, 9, 38), seed=3)
    n_new, layers = 6, net.config.num_hidden_layers
    with SyncEngine(net, **ENGINE) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=n_new))
                for p in prompts]
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        assert [len(eng.request(r).output_tokens) for r in rids] == [n_new] * 3
    # token j (j >= 1) of a request is decoded against prompt + j rows
    live = sum(len(p) + j for p in prompts for j in range(1, n_new))
    assert m["mla_latent_tokens_read_decode"] == live * layers
    assert m["mla_latent_tokens_read_prefill"] == 0
    # chunks of 16: (0,16) (16,8) | (0,16 as 9 -> rung 16) | (0,16) (16,16) (32,8)
    expanded = (16 + 24) + 16 + (16 + 32 + 40)
    assert m["mla_context_tokens_expanded_prefill"] == expanded * layers
    assert m["mla_context_tokens_expanded_decode"] == 0
    assert m["moe_layer_steps"] == 2 * (m["host_syncs"] + m["prefill_chunks"])
    assert m["moe_weight_passes_decode"] >= m["moe_experts_hit_decode"] > 0
    assert set(net.serve_counters) <= set(m)


# -- (e) the latent page kind -------------------------------------------------------

def test_a_latent_layer_caches_one_row_a_token_in_one_pool():
    spec = kvc.KVLayerSpec("latent", 1, 576, 512, 640, prefill="linear")
    assert spec.bytes_per_token() == 1152
    assert spec.pool_shape(49153, 16, spec.k_store) == (49153, 16, 640)
    # expanded heads would be 32 x (192 + 128) x 2 B
    assert kvc.KVLayerSpec("global", 32, 192, 128).bytes_per_token() == 20480
    for bad in (dict(num_kv_heads=2), dict(prefill="paged"), dict(v_dim=600),
                dict(window=8)):
        with pytest.raises(ValueError):
            kvc.KVLayerSpec(**dict(dict(
                kind="latent", num_kv_heads=1, k_dim=576, v_dim=512,
                prefill="linear"), **bad))
    net = build()
    layout = net.kv_layout()
    assert {(sp.kind, sp.num_kv_heads, sp.k_dim, sp.v_dim, sp.k_store)
            for sp in layout} == {("latent", 1, 40, 32, 48)}
    cache = kvc.PagedKVCache(net.config, 8, 4, layout=layout)
    assert not cache.uniform and cache.window is None
    assert [a.shape for a in cache.k] == [(8, 4, 48)] * 3
    assert [a.shape for a in cache.v] == [(0,)] * 3         # no V pool
    # latent pages are the global table's
    assert cache.published_bytes_per_token("global") == 3 * 40 * 2
    assert cache.published_bytes_per_token("window") == 0


def test_engine_returns_every_latent_page():
    net = build()
    with LLMEngine(net, **ENGINE) as eng:
        free = eng.cache.allocator.num_free
        for wave in range(3):
            rids = [eng.add_request(p, SamplingParams(max_new_tokens=12))
                    for p in prompts_of((30, 7, 19, 40, 11), seed=wave)]
            while eng.has_work():
                eng.step()
            for r in rids:
                eng.release(r)
        assert eng.cache.allocator.num_free == free
        m = eng.metrics()
        assert m["global_blocks_in_use"] == 0 and m["window_blocks_in_use"] == 0
        assert m["kv_live_byte_steps"] == m["kv_one_table_byte_steps"] > 0


@pytest.mark.parametrize("kwargs,names", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(kv_host_blocks=8), "kv_host_blocks"),
    (dict(prefill_only=True), "prefill_only"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_page_checksums=True), "kv_page_checksums"),
])
def test_what_reads_a_page_as_heads_refuses_a_latent_kind(kwargs, names):
    net = build()
    with pytest.raises(ValueError, match=names + ".*latent: 1 kv heads"):
        LLMEngine(net, **dict(ENGINE, **kwargs))


def test_export_import_and_copy_refuse_latent_pages():
    net = build()
    with LLMEngine(net, **ENGINE) as eng:
        for call in (lambda: eng.cache.export_request_pages([1], 4),
                     lambda: eng.cache.copy_block(1, 2),
                     lambda: eng.cache.validate_request_pages({})):
            with pytest.raises(ValueError, match="one pool geometry.*latent"):
                call()


@pytest.mark.parametrize("kwargs", [
    dict(draft_model="a-llama"), dict(plan="a-plan")],
    ids=["draft-verify", "plan"])
def test_the_llama_only_paths_refuse_this_model_by_name(kwargs):
    net = build()
    if "draft_model" in kwargs:
        kwargs = dict(draft_model=LlamaForCausalLM(llama_tiny()))
    with pytest.raises(ValueError,
                       match="LlamaForCausalLM only.*JoyAIFlashForCausalLM"):
        LLMEngine(net, **dict(ENGINE, **kwargs))


def test_the_routers_stay_float32_under_bfloat16_and_the_config_says_no():
    net = build()
    net.bfloat16()
    kinds = {n: str(p.dtype) for n, p in net.named_parameters()}
    for n, dt in kinds.items():
        assert ("float32" if ".router." in n else "bfloat16") in dt, (n, dt)
    assert sum(1 for n in kinds if ".router." in n) == 2 * 3   # two layers + mtp
    assert any(n.startswith("model.mtp.block.shared_experts.") for n in kinds)
    for bad in (dict(rope_interleave=False), dict(rope_scaling={"factor": 2}),
                dict(tie_word_embeddings=True), dict(num_nextn_predict_layers=2)):
        with pytest.raises(ValueError):
            joyai_flash_tiny(**bad)


# -- the new kernel at the published geometry, as far as a machine without a chip
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

    def decode(q, pool, t, l):
        return pa.paged_decode_attention_latent_pallas(q, pool, t, l, 0.072, 512)
    def chunk(q, k, v, a, b, c):
        return pa.chunk_attention_pallas(q, k, v, a, b, c, 0.072,
                                         name="chunk_attention_global")
    cases = {{
        "decode-latent": (decode, (sds((64, 32, 640), bf),
                                   sds((49153, 16, 640), bf),
                                   sds((64, 768), i32), sds((64,), i32))),
        "chunk-expanded": (chunk, (sds((2048, 32, 256), bf),
                                   sds((12288, 32, 256), bf),
                                   sds((12288, 32, 128), bf),
                                   sds((), i32), sds((), i32), sds((), i32)))}}
    for name, (fn, args) in cases.items():
        text = jax.jit(fn).trace(*args).lower().compile().as_text()
        print("COMPILED", name, *sorted(set(
            ln.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
            for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln)), flush=True)
""")


def test_the_latent_kernels_compile_for_a_v5e_at_the_published_geometry():
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
    assert done == {"decode-latent": "paged_decode_attention_latent",
                    "chunk-expanded": "chunk_attention_global"}


def test_a_device_counter_carries_past_two_to_the_thirty_first():
    """Cached rows a decode step walks, over nine layers, pass 2^31 within
    a thousand steps of the benchmark's cell: a counter is two limbs, and
    ``metrics()`` puts them together."""
    from paddle_tpu.inference.serving import engine as eng_mod

    names = ("a", "b")
    c = jnp.zeros((2, 4), jnp.int32)
    step = jax.jit(lambda c, n: eng_mod._add_counts(
        c, {"a": n, "b": 1}, names, decode=True))
    for _ in range(1000):
        c = step(c, 3_300_000)
    c = eng_mod._add_counts(c, {"a": 7}, names, decode=False)
    low, carried = np.asarray(c)
    total = [int(lo) + eng_mod._LIMB * int(hi) for lo, hi in zip(low, carried)]
    assert total == [3_300_000_000, 1000, 7, 0]
    assert (low < eng_mod._LIMB).all() and (low >= 0).all()
