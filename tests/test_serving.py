"""LLM serving engine tests (ISSUE 7 + ISSUE 11): block allocator,
paged-vs-dense attention parity, continuous-batching bit-exactness,
scheduler admission/eviction, O(1)-compile decode, create_predictor
wiring; prefix-cache block sharing (refcounts, hash chains, COW),
chunked prefill, speculative decoding."""

import dataclasses
import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (
    BlockAllocator, LLMEngine, PagedKVCache, PrefixCache, Request,
    SamplingParams, Scheduler, load_llama_artifact, paged_decode_attention,
    paged_multiquery_attention, save_llama_artifact,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg():
    from paddle_tpu.models import llama_tiny

    return llama_tiny()


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(7)
    m = LlamaForCausalLM(tiny_cfg())
    m.eval()
    return m


def prompts_fixed(cfg, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_block_zero_reserved(self):
        a = BlockAllocator(4)
        got = a.allocate(3)
        assert sorted(got) == [1, 2, 3]  # block 0 never handed out
        assert a.num_free == 0

    def test_exhaustion_all_or_nothing(self):
        a = BlockAllocator(4)
        assert a.allocate(2) is not None
        free_before = a.num_free
        assert a.allocate(2) is None  # only 1 free
        assert a.num_free == free_before  # no partial grab

    def test_free_and_lifo_reuse(self):
        a = BlockAllocator(8)
        first = a.allocate(3)
        a.free(first)
        again = a.allocate(3)
        assert again == list(reversed(first))  # LIFO: warm blocks first
        assert a.num_free == 8 - 1 - 3

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        ids = a.allocate(1)
        a.free(ids)
        with pytest.raises(ValueError):
            a.free(ids)

    def test_high_water(self):
        a = BlockAllocator(8)
        x = a.allocate(4)
        a.free(x)
        a.allocate(2)
        assert a.high_water == 4

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError):
            BlockAllocator(1)


# ---------------------------------------------------------------------------
# scheduler (host-only: no jax)
# ---------------------------------------------------------------------------

def _mk_req(n_prompt, **samp):
    return Request(np.arange(1, n_prompt + 1, dtype=np.int32),
                   SamplingParams(**samp) if samp else None)


class TestScheduler:
    def _sched(self, num_blocks=16, block_size=4, slots=2, prefills=1):
        return Scheduler(BlockAllocator(num_blocks), block_size, slots,
                         prefills)

    def test_fifo_admission_respects_slots_and_quota(self):
        s = self._sched(slots=2, prefills=4)
        reqs = [_mk_req(3) for _ in range(3)]
        s.waiting.extend(reqs)
        picked = s.pick_prefills()
        # 3 waiting, 4 allowed per step, but only 2 slots
        assert [r for _, r in picked] == reqs[:2]
        assert list(s.waiting) == reqs[2:]

    def test_max_prefills_per_step(self):
        s = self._sched(slots=4, prefills=1)
        s.waiting.extend(_mk_req(3) for _ in range(3))
        assert len(s.pick_prefills()) == 1
        assert len(s.pick_prefills()) == 1

    def test_queue_on_exhaustion_no_overtake(self):
        # pool: 3 usable blocks of 4 => a 12-token prompt needs 4 (12+1
        # tokens) and cannot be admitted; a later short request must NOT
        # overtake it (FIFO)
        s = self._sched(num_blocks=4, block_size=4, slots=2)
        big, small = _mk_req(12), _mk_req(3)
        s.waiting.extend([big, small])
        assert s.pick_prefills() == []
        assert s.stats["queued_on_exhaustion"] == 1
        assert list(s.waiting) == [big, small]

    def test_finish_frees_blocks(self):
        s = self._sched()
        s.waiting.append(_mk_req(6))
        ((slot, req),) = s.pick_prefills()
        held = list(req.blocks)
        assert held
        s.finish(req)
        assert req.blocks == [] and s.slots[slot] is None
        assert s.allocator.num_free == s.allocator.num_blocks - 1
        assert s.stats["finished"] == 1
        assert held[0] not in s.allocator._allocated

    def test_eviction_picks_most_recent_and_requeues_front(self):
        # 7 usable blocks of 2: two 5-token requests (3 blocks each for
        # tokens+1) admit; growth then exhausts the pool
        s = self._sched(num_blocks=8, block_size=2, slots=2, prefills=2)
        a, b = _mk_req(5), _mk_req(5)
        s.waiting.extend([a, b])
        assert len(s.pick_prefills()) == 2
        a.num_cached = b.num_cached = 6
        a.output_tokens.extend([1, 1])  # tokens=7 > capacity 6: each needs
        b.output_tokens.extend([1, 1])  # a 4th block, but only 1 is free
        s.ensure_decode_room()          # second grower must evict
        assert s.stats["evictions"] == 1
        evicted = s.waiting[0]
        assert evicted in (a, b)
        assert evicted.blocks == [] and evicted.num_cached == 0
        assert evicted.state == "waiting" and evicted.evictions == 1

    def test_lone_request_out_of_memory_preempts_self(self):
        s = self._sched(num_blocks=3, block_size=2, slots=1)
        r = _mk_req(3)
        s.waiting.append(r)
        assert len(s.pick_prefills()) == 1
        r.num_cached = 4
        r.output_tokens.extend([1, 1])  # tokens=5 > capacity 4: needs a
        evicted = s.ensure_decode_room()  # 3rd block and none exist
        assert evicted == [r] and s.waiting[0] is r

    def test_no_eviction_when_exactly_at_block_boundary(self):
        # decode writes at position len(tokens)-1, so a request whose
        # tokens EXACTLY fill its blocks needs no growth — demanding a
        # lookahead block here used to evict when the pool was full
        s = self._sched(num_blocks=3, block_size=2, slots=1)
        r = _mk_req(3)
        s.waiting.append(r)
        assert len(s.pick_prefills()) == 1  # 2 blocks = capacity 4, 0 free
        r.num_cached = 3
        r.output_tokens.append(1)  # tokens=4 == capacity: write pos 3 fits
        assert s.ensure_decode_room() == []
        assert s.stats["evictions"] == 0 and r.state == "running"

    def test_seeded_stream_never_leaks_blocks(self):
        rng = np.random.RandomState(0)
        s = self._sched(num_blocks=12, block_size=2, slots=3, prefills=2)
        backlog = [_mk_req(int(rng.randint(1, 8))) for _ in range(20)]
        done = 0
        for _ in range(300):
            while backlog and len(s.waiting) < 4:
                s.waiting.append(backlog.pop())
            for _, r in s.pick_prefills():
                r.num_cached = len(r.prompt)
            s.ensure_decode_room()
            for r in list(s.running):
                r.output_tokens.append(1)
                r.num_cached += 1
                if len(r.output_tokens) >= 3 and rng.rand() < 0.5:
                    s.finish(r)
                    done += 1
            # invariant: allocated blocks == exactly the running requests'
            held = sorted(b for r in s.running for b in r.blocks)
            assert sorted(s.allocator._allocated) == held
            if done == 20 and not s.has_work():
                break
        assert done == 20
        assert s.allocator.num_free == s.allocator.num_blocks - 1


# ---------------------------------------------------------------------------
# paged attention parity
# ---------------------------------------------------------------------------

def _paged_case(seed=0, B=3, H=4, Hkv=2, D=16, block=4, P=5, N=32):
    rng = np.random.RandomState(seed)
    q = rng.randn(B, 1, H, D).astype(np.float32)
    k_pool = rng.randn(N, block, Hkv, D).astype(np.float32)
    v_pool = rng.randn(N, block, Hkv, D).astype(np.float32)
    # distinct non-null blocks per request
    perm = rng.permutation(np.arange(1, N))[:B * P].reshape(B, P)
    lens = rng.randint(1, P * block + 1, size=B).astype(np.int32)
    return q, k_pool, v_pool, perm.astype(np.int32), lens


def _dense_reference(q, k_pool, v_pool, tables, lens):
    """Independent numpy reference: gather + masked softmax, GQA repeat."""
    B, _, H, D = q.shape
    _, block, Hkv, _ = k_pool.shape
    P = tables.shape[1]
    out = np.zeros_like(q)
    for i in range(B):
        k = k_pool[tables[i]].reshape(P * block, Hkv, D)[:lens[i]]
        v = v_pool[tables[i]].reshape(P * block, Hkv, D)[:lens[i]]
        k = np.repeat(k, H // Hkv, axis=1)  # [S, H, D]
        v = np.repeat(v, H // Hkv, axis=1)
        for h in range(H):
            s = (q[i, 0, h] @ k[:, h].T) / np.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, 0, h] = p @ v[:, h]
    return out


class TestPagedAttentionParity:
    def test_lax_fallback_matches_dense(self):
        import jax.numpy as jnp

        q, kp, vp, tables, lens = _paged_case()
        got = np.asarray(paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens)))
        np.testing.assert_allclose(got, _dense_reference(q, kp, vp, tables,
                                                         lens), atol=1e-5)

    def test_single_token_context(self):
        import jax.numpy as jnp

        q, kp, vp, tables, lens = _paged_case(seed=3)
        lens[:] = 1  # only the just-written token is visible
        got = np.asarray(paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens)))
        np.testing.assert_allclose(got, _dense_reference(q, kp, vp, tables,
                                                         lens), atol=1e-5)

    def test_pallas_interpret_matches_dense(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_pallas, use_pallas_paged)

        assert use_pallas_paged(16, 4)
        q, kp, vp, tables, lens = _paged_case(seed=5)
        got = np.asarray(paged_decode_attention_pallas(
            jnp.asarray(q[:, 0]), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens),
            1.0 / np.sqrt(q.shape[-1])))[:, None]
        np.testing.assert_allclose(got, _dense_reference(q, kp, vp, tables,
                                                         lens), atol=1e-5)

    def test_pallas_routing_gate(self):
        from paddle_tpu.ops.pallas.paged_attention import use_pallas_paged

        # CPU backend, no interpret: must route to the lax fallback
        assert not use_pallas_paged(128, 16)


# ---------------------------------------------------------------------------
# static-cache eager generate (satellite: O(1) compiles per bucket)
# ---------------------------------------------------------------------------

class TestStaticCacheGenerate:
    def test_greedy_matches_full_forward(self, model):
        cfg = model.config
        ids = paddle.to_tensor(prompts_fixed(cfg, [6, 6], seed=1)[0][None])
        out = model.generate(ids, max_new_tokens=2).numpy()
        logits = model(ids).numpy()
        assert out[0, 6] == logits[0, -1].argmax()
        ext = paddle.to_tensor(out[:, :7].astype(np.int32))
        assert out[0, 7] == model(ext).numpy()[0, -1].argmax()

    def test_decode_compiles_o1_across_32_tokens(self):
        from paddle_tpu.models import LlamaForCausalLM

        paddle.seed(3)
        m = LlamaForCausalLM(tiny_cfg())
        m.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 512, (1, 8)).astype("int32"))
        m.generate(ids, max_new_tokens=32)
        row = paddle.jit.cache_stats()[m.__dict__["_gen_jit"].name]
        # prefill shape + decode shape = 2 compiles; every other decode
        # step hits (the pre-ISSUE-7 concat path compiled O(tokens))
        assert row["compiles"] == 2
        assert row["hits"] == 30
        # same capacity bucket again: zero new compiles
        m.generate(ids, max_new_tokens=32)
        row = paddle.jit.cache_stats()[m.__dict__["_gen_jit"].name]
        assert row["compiles"] == 2

    def test_capacity_bucketing_bounds_compiles(self):
        from paddle_tpu.models import LlamaForCausalLM

        paddle.seed(3)
        m = LlamaForCausalLM(tiny_cfg())
        m.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 512, (1, 8)).astype("int32"))
        # 8+24 and 8+40 both round up to the same 64-capacity bucket:
        # the decode executable is shared, only hit counts grow
        m.generate(ids, max_new_tokens=24)
        c1 = paddle.jit.cache_stats()[m.__dict__["_gen_jit"].name]["compiles"]
        m.generate(ids, max_new_tokens=40)
        c2 = paddle.jit.cache_stats()[m.__dict__["_gen_jit"].name]["compiles"]
        assert c1 == c2 == 2

    def test_sampling_seeded_reproducible(self, model):
        ids = paddle.to_tensor(np.zeros((1, 4), "int32"))
        a = model.generate(ids, max_new_tokens=4, do_sample=True,
                           temperature=1.3, top_k=16, top_p=0.9, seed=11)
        b = model.generate(ids, max_new_tokens=4, do_sample=True,
                           temperature=1.3, top_k=16, top_p=0.9, seed=11)
        np.testing.assert_array_equal(a.numpy(), b.numpy())


# ---------------------------------------------------------------------------
# engine: continuous batching bit-exactness + lifecycle
# ---------------------------------------------------------------------------

class TestEngine:
    def test_continuous_batching_bit_exact_vs_batch_of_one(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 9, 3, 12], seed=2)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=8).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=64, block_size=8,
                       max_batch_size=4) as eng:
            outs = eng.generate(prompts,
                                SamplingParams(max_new_tokens=8))
            stats = eng.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert stats["finished"] == 4
        assert stats["blocks_free"] == 63  # everything freed on finish

    def test_bit_exact_under_eviction(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [10, 11, 9], seed=4)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=10).numpy()[0]
                for p in prompts]
        # pool deliberately too small for three full requests: forces
        # token-granularity eviction + re-prefill mid-stream
        with LLMEngine(model, num_blocks=9, block_size=4,
                       max_batch_size=3) as eng:
            outs = eng.generate(prompts,
                                SamplingParams(max_new_tokens=10))
            stats = eng.stats()
        assert stats["evictions"] >= 1  # the stress actually happened
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_pool_exhaustion_queues_not_crashes(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [8, 8, 8], seed=5)
        # 4 usable blocks of 4 = room for ~one request at a time
        with LLMEngine(model, num_blocks=5, block_size=4,
                       max_batch_size=2) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
            stats = eng.stats()
        assert len(outs) == 3 and all(len(o) == 14 for o in outs)
        assert stats["queued_on_exhaustion"] >= 1
        assert stats["finished"] == 3

    def test_eos_finishes_and_frees_blocks(self, model):
        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=6)[0]
        first = int(model.generate(paddle.to_tensor(p[None]),
                                   max_new_tokens=1).numpy()[0, -1])
        with LLMEngine(model, num_blocks=16, block_size=8,
                       max_batch_size=2) as eng:
            rid = eng.add_request(p, SamplingParams(max_new_tokens=32,
                                                    eos_token_id=first))
            finals = [o for o in eng.stream() if o.finished]
            assert eng.request(rid).finish_reason() == "eos"
            assert len(eng.output_tokens(rid)) == 7  # stopped at eos
            assert eng.stats()["blocks_free"] == 15
        assert finals[0].rid == rid

    def test_one_decode_compile_across_request_mix(self, model):
        cfg = model.config
        with LLMEngine(model, num_blocks=64, block_size=8,
                       max_batch_size=4) as eng:
            eng.generate(prompts_fixed(cfg, [4, 7], seed=7),
                         SamplingParams(max_new_tokens=5))
            eng.generate(prompts_fixed(cfg, [3, 9, 5, 6], seed=8),
                         SamplingParams(max_new_tokens=7))
            row = paddle.jit.cache_stats()[eng._decode_name]
        # every decode step of every mix hits ONE executable
        assert row["compiles"] == 1
        assert row["hits"] >= 10

    def test_request_longer_than_capacity_rejected(self, model):
        with LLMEngine(model, num_blocks=4, block_size=4,
                       max_batch_size=2) as eng:
            with pytest.raises(ValueError):
                eng.add_request(np.arange(1, 30, dtype=np.int32),
                                SamplingParams(max_new_tokens=8))

    def test_request_exceeding_largest_prefill_bucket_rejected(self, model):
        # custom rungs smaller than max_model_len: a request whose
        # re-prefill prefix could outgrow the top rung must fail at
        # add_request, not on the ingest thread mid-stream
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, prefill_buckets=[32]) as eng:
            with pytest.raises(ValueError, match="prefill bucket"):
                eng.add_request(np.arange(1, 21, dtype=np.int32),
                                SamplingParams(max_new_tokens=20))

    def test_unaligned_max_model_len_rounds_down(self, model):
        # an unaligned cap used to leave the top prefill bucket unaligned:
        # prefill writes whole pages only, so a 34-token prompt's tail
        # never reached the pool and decode was silently wrong. The cap
        # now rounds DOWN to whole pages (with a warning) and a prompt
        # that needed the truncated tail is rejected up front.
        cfg = model.config
        with pytest.warns(RuntimeWarning, match="not a multiple"):
            eng = LLMEngine(model, num_blocks=8, block_size=16,
                            max_batch_size=2, max_model_len=40)
        with eng:
            assert eng.max_model_len == 32
            assert eng.prefill_buckets[-1] == 32
            assert all(b % 16 == 0 for b in eng.prefill_buckets)
            with pytest.raises(ValueError, match="caps at"):
                eng.add_request(prompts_fixed(cfg, [34], seed=20)[0],
                                SamplingParams(max_new_tokens=1))
            p = prompts_fixed(cfg, [20], seed=21)[0]
            (out,) = eng.generate([p], SamplingParams(max_new_tokens=4))
            ref = model.generate(paddle.to_tensor(p[None]),
                                 max_new_tokens=4).numpy()[0]
            np.testing.assert_array_equal(out, ref)

    def test_max_model_len_below_block_size_rejected(self, model):
        with pytest.raises(ValueError, match="block_size"):
            LLMEngine(model, num_blocks=8, block_size=16, max_model_len=8)

    def test_submit_after_ingest_death_not_stranded(self, model):
        # a request submitted AFTER the worker died and flushed its queue
        # must land in _ready (drained by step), never sit in _q forever
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 6], seed=22)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=3).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2) as eng:
            def boom(req):
                raise RuntimeError("boom")

            eng._ingest._stage = boom
            with pytest.warns(RuntimeWarning, match="ingest thread died"):
                r1 = eng.add_request(prompts[0],
                                     SamplingParams(max_new_tokens=3))
                eng._ingest._thread.join(timeout=5.0)
                assert not eng._ingest._thread.is_alive()
                r2 = eng.add_request(prompts[1],
                                     SamplingParams(max_new_tokens=3))
                assert eng._ingest._q.empty()  # nothing stranded in _q
                for _ in eng.stream():
                    pass
            np.testing.assert_array_equal(eng.output_tokens(r1), refs[0])
            np.testing.assert_array_equal(eng.output_tokens(r2), refs[1])

    def test_ingest_death_flushes_queued_requests(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 7], seed=14)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=4).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2) as eng:
            real_stage = eng._ingest._stage
            calls = {"n": 0}

            def dying_stage(req):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("boom")
                real_stage(req)

            eng._ingest._stage = dying_stage
            with pytest.warns(RuntimeWarning, match="ingest thread died"):
                r1 = eng.add_request(prompts[0],
                                     SamplingParams(max_new_tokens=4))
                r2 = eng.add_request(prompts[1],
                                     SamplingParams(max_new_tokens=4))
                # both requests (the failing one AND the one queued
                # behind it) must still complete via sync re-staging
                for _ in eng.stream():
                    pass
            np.testing.assert_array_equal(eng.output_tokens(r1), refs[0])
            np.testing.assert_array_equal(eng.output_tokens(r2), refs[1])

    def test_release_bounds_request_bookkeeping(self, model):
        cfg = model.config
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2) as eng:
            # generate() auto-releases: nothing retained afterwards
            eng.generate(prompts_fixed(cfg, [4, 6], seed=15),
                         SamplingParams(max_new_tokens=3))
            assert eng._requests == {}
            # a running request cannot be released
            rid = eng.add_request(prompts_fixed(cfg, [4], seed=16)[0],
                                  SamplingParams(max_new_tokens=3))
            eng.step()
            with pytest.raises(ValueError, match="finished"):
                eng.release(rid)
            for _ in eng.stream():
                pass
            eng.release(rid)
            assert rid not in eng._requests
            eng.release(rid)  # idempotent

    def test_sync_ingest_path(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 7], seed=9)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=4).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=4))
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_reload_weights_from_checkpoint_manager(self, model, tmp_path):
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager)

        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=10)[0]
        mgr = CheckpointManager(str(tmp_path / "ckpts"))
        mgr.save(3, model=model)
        mgr.note_window(True)  # promote to healthy
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2) as eng:
            ref = eng.generate([p], SamplingParams(max_new_tokens=5))[0]
            # poison the weights in place — decode now diverges
            w = model.llama.embed_tokens.weight
            orig = np.asarray(w.numpy()).copy()
            w.set_value(paddle.to_tensor(orig + 1.0))
            bad = eng.generate([p], SamplingParams(max_new_tokens=5))[0]
            assert not np.array_equal(ref, bad)
            step = eng.reload_weights(mgr)
            assert step == 3
            # NO recompile: same executable, restored outputs
            compiles = paddle.jit.cache_stats()[eng._decode_name]["compiles"]
            good = eng.generate([p], SamplingParams(max_new_tokens=5))[0]
            np.testing.assert_array_equal(ref, good)
            assert (paddle.jit.cache_stats()[eng._decode_name]["compiles"]
                    == compiles)


def test_the_readme_tables_every_engine_option():
    """README "`LLMEngine` options" against the constructor's signature:
    the same keywords in the same order, each with its default."""
    import inspect
    import re

    with open(os.path.join(REPO, "README.md")) as f:
        section = f.read().split("### `LLMEngine` options", 1)[1]
    section = section.split("\n### ", 1)[0]
    table = [(m.group(1), m.group(2)) for m in re.finditer(
        r"^\| `(\w+)` \| `([^`]*)` \| \S.*\|$", section, re.M)]
    options = [(name, repr(p.default)) for name, p in inspect.signature(
        LLMEngine.__init__).parameters.items()
        if p.kind is inspect.Parameter.KEYWORD_ONLY]
    assert table == options
    assert len(options) == 20


# ---------------------------------------------------------------------------
# create_predictor wiring
# ---------------------------------------------------------------------------

class TestPredictorWiring:
    @pytest.fixture(scope="class")
    def artifact(self, model):
        d = tempfile.mkdtemp()
        path = os.path.join(d, "model")
        save_llama_artifact(model, path)
        return path

    def test_engine_predictor_bit_exact(self, model, artifact):
        from paddle_tpu import inference

        cfg = model.config
        c = inference.Config(artifact)
        c.enable_llm_engine(num_blocks=32, block_size=8, max_batch_size=2,
                            max_new_tokens=5)
        pred = inference.create_predictor(c)
        assert isinstance(pred, inference.LLMEnginePredictor)
        try:
            ids = np.stack(prompts_fixed(cfg, [6, 6], seed=11))
            outs = pred.run([ids])
            ref = model.generate(paddle.to_tensor(ids.astype(np.int32)),
                                 max_new_tokens=5).numpy()
            for i in range(2):
                np.testing.assert_array_equal(outs[i], ref[i])
            assert pred.get_output_names() == ["out0", "out1"]
        finally:
            pred.close()

    def test_output_names_fetchable_before_run(self, model, artifact):
        # every advertised output name must resolve to a handle even
        # before the first run() (it used to KeyError on "out0")
        from paddle_tpu import inference

        c = inference.Config(artifact)
        c.enable_llm_engine(num_blocks=16, block_size=8, max_batch_size=2)
        pred = inference.create_predictor(c)
        try:
            assert pred.get_output_names() == ["out0"]
            h = pred.get_output_handle("out0")
            assert h.name() == "out0"
        finally:
            pred.close()

    def test_seq_lens_handle_trims_padding(self, model, artifact):
        from paddle_tpu import inference

        cfg = model.config
        c = inference.Config(artifact)
        c.enable_llm_engine(num_blocks=32, block_size=8, max_batch_size=2,
                            max_new_tokens=4)
        pred = inference.create_predictor(c)
        try:
            row = prompts_fixed(cfg, [5], seed=12)[0]
            padded = np.zeros((1, 9), np.int32)
            padded[0, :5] = row
            (out,) = pred.run([padded, np.array([5])])
            ref = model.generate(paddle.to_tensor(row[None]),
                                 max_new_tokens=4).numpy()[0]
            np.testing.assert_array_equal(out, ref)
            # seq_lens is per-batch: the next run's unpadded 2-row batch
            # must NOT be truncated by the stale [5]
            rows2 = np.stack(prompts_fixed(cfg, [7, 7], seed=14))
            outs2 = pred.run([rows2])
            ref2 = model.generate(paddle.to_tensor(rows2.astype(np.int32)),
                                  max_new_tokens=4).numpy()
            for i in range(2):
                np.testing.assert_array_equal(outs2[i], ref2[i])
            # mismatched seq_lens count is a typed error, not silent
            with pytest.raises(ValueError, match="seq_lens"):
                pred.run([rows2, np.array([7])])
        finally:
            pred.close()

    def test_artifact_roundtrip(self, model, artifact):
        m2 = load_llama_artifact(artifact)
        ids = paddle.to_tensor(
            prompts_fixed(model.config, [6], seed=13)[0][None])
        np.testing.assert_array_equal(
            model.generate(ids, max_new_tokens=3).numpy(),
            m2.generate(ids, max_new_tokens=3).numpy())

    def test_knob_recorded_for_non_llama_artifacts(self, tmp_path):
        from paddle_tpu import inference, nn
        from paddle_tpu.static import InputSpec

        paddle.seed(1)
        m = nn.Linear(4, 2)
        m.eval()
        path = str(tmp_path / "dense")
        paddle.jit.save(m, path,
                        input_spec=[InputSpec([-1, 4], "float32", "x")])
        c = inference.Config(path)
        c.enable_llm_engine()  # knob on, but not a llama artifact
        assert c.llm_engine_enabled()
        pred = inference.create_predictor(c)
        assert isinstance(pred, inference.Predictor)  # record-only
        # advertised output names are fetchable before the first run
        for n in pred.get_output_names():
            assert pred.get_output_handle(n).name() == n
        x = np.random.randn(3, 4).astype(np.float32)
        (out,) = pred.run([x])
        np.testing.assert_allclose(out, m(paddle.to_tensor(x)).numpy(),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# bench harness acceptance
# ---------------------------------------------------------------------------

def _bench_mod():
    import importlib
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    return importlib.import_module("bench_serving")


class TestBenchServing:
    def test_ab_smoke_bit_exact_zero_recompiles(self):
        bsv = _bench_mod()
        cfg, _, _ = bsv.default_sizing(tiny=True)
        res = bsv.run_ab(cfg,
                         dict(n=5, rate=200.0, min_prompt=4, max_prompt=10,
                              min_new=4, max_new=8),
                         dict(num_blocks=32, block_size=8, max_batch_size=4),
                         seed=0)
        assert res["bit_exact"]
        assert res["engine"]["decode_compiles_in_window"] == 0

    @pytest.mark.slow
    def test_acceptance_2x_tokens_per_sec(self):
        # ISSUE 7 acceptance: >=2x tokens/s vs the naive batch-of-one
        # loop on the llama CPU smoke, bit-exact, zero decode recompiles
        bsv = _bench_mod()
        res = bsv.run_ab(tiny=True)
        assert res["bit_exact"]
        assert res["engine"]["decode_compiles_in_window"] == 0
        assert res["speedup"] >= 2.0, res


# ---------------------------------------------------------------------------
# ISSUE 11: ref-counted allocator + prefix cache (host-only: no jax model)
# ---------------------------------------------------------------------------

class TestRefcountedAllocator:
    def test_acquire_shares_and_free_decrefs(self):
        a = BlockAllocator(8)
        ids = a.allocate(2)
        a.acquire(ids)                       # second holder
        assert all(a.ref(b) == 2 for b in ids)
        assert a.is_shared(ids[0])
        a.free(ids)                          # first holder releases
        assert all(a.ref(b) == 1 for b in ids)
        assert sorted(a._allocated) == sorted(ids)  # still live
        a.free(ids)                          # last holder: back to pool
        assert a.num_free == 7
        with pytest.raises(ValueError):
            a.free(ids)                      # now a double-free

    def test_free_all_or_nothing_on_duplicate(self):
        # ISSUE 11 satellite: a duplicate id in ONE call must raise with
        # the allocator untouched (it used to free the first then raise
        # midway, leaving half-mutated state)
        a = BlockAllocator(8)
        ids = a.allocate(3)
        before_free = a.num_free
        before_refs = {b: a.ref(b) for b in ids}
        with pytest.raises(ValueError, match="duplicate"):
            a.free([ids[0], ids[1], ids[0]])
        assert a.num_free == before_free
        assert {b: a.ref(b) for b in ids} == before_refs
        with pytest.raises(ValueError, match="double-free|foreign"):
            a.free([ids[0], 7])              # foreign id: same guarantee
        assert a.num_free == before_free
        a.free(ids)                          # the valid free still works
        assert a.num_free == 7

    def test_acquire_free_or_foreign_rejected(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError):
            a.acquire([2])                   # never allocated

    def test_shared_block_eviction_waits_for_refcount_zero(self):
        # eviction ordering: a cached (reusable) block is reclaimable, a
        # block ANY holder references is not — exhaustion prefers the
        # free list, then LRU reusable, and never touches ref >= 1
        a = BlockAllocator(4)
        pc = PrefixCache(a, block_size=2)
        toks = np.arange(1, 7, dtype=np.int32)
        held = a.allocate(3)                 # the whole pool
        pc.register(toks, held, upto=6)      # all three identities known
        a.acquire([held[0]])                 # a second holder of block 0
        a.free(held)                         # first holder releases all
        # held[0] still ref 1; held[1], held[2] parked reusable
        assert a.ref(held[0]) == 1
        assert a.num_free == 2
        got = a.allocate(2)                  # must reclaim the reusable 2
        assert sorted(got) == sorted(held[1:])
        assert a.allocate(1) is None         # held[0] is NOT reclaimable
        a.free([held[0]])                    # refcount 0: now it parks
        assert a.allocate(1) == [held[0]]

    def test_lru_reclaim_order_and_forget(self):
        a = BlockAllocator(5)                # pool exactly fits the chain
        pc = PrefixCache(a, block_size=2)
        toks = np.arange(1, 9, dtype=np.int32)
        held = a.allocate(4)
        pc.register(toks, held, upto=8)
        a.free([held[2]])                    # released first -> oldest
        a.free([held[0], held[1], held[3]])
        assert len(pc) == 4
        got = a.allocate(1)
        assert got == [held[2]]              # LRU reclaim
        assert not pc.registered(held[2])    # reclaimed identity forgotten
        assert len(pc) == 3


class TestPrefixCacheIndex:
    def test_match_walks_full_block_chain(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, block_size=4)
        toks = np.arange(100, 114, dtype=np.int32)  # 14 tokens
        blocks = a.allocate(4)
        pc.register(toks, blocks, upto=14)   # 3 full blocks register
        got, ntok = pc.match(toks)
        assert got == blocks[:3] and ntok == 12
        # a different continuation after 8 shared tokens matches 2 blocks
        other = np.concatenate([toks[:8], toks[8:] + 1])
        got, ntok = pc.match(other)
        assert got == blocks[:2] and ntok == 8

    def test_match_capped_at_proper_prefix(self):
        # a full-chain hit must leave >= 1 token to prefill: admission
        # needs the last position's logits to sample the first token
        a = BlockAllocator(16)
        pc = PrefixCache(a, block_size=4)
        toks = np.arange(1, 9, dtype=np.int32)  # exactly 2 blocks
        blocks = a.allocate(2)
        pc.register(toks, blocks, upto=8)
        got, ntok = pc.match(toks)
        assert got == blocks[:1] and ntok == 4

    def test_chain_identity_is_positional(self):
        # the same 4 tokens after a DIFFERENT prefix hash differently —
        # block identity is causal content, not raw bytes
        a = BlockAllocator(16)
        pc = PrefixCache(a, block_size=4)
        t1 = np.array([1, 2, 3, 4, 9, 9, 9, 9, 5], np.int32)
        t2 = np.array([8, 8, 8, 8, 9, 9, 9, 9, 5], np.int32)
        blocks = a.allocate(2)
        pc.register(t1, blocks, upto=8)
        got, ntok = pc.match(t2)
        assert got == [] and ntok == 0

    def test_register_first_writer_wins(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, block_size=4)
        toks = np.arange(1, 9, dtype=np.int32)
        b1 = a.allocate(2)
        b2 = a.allocate(2)
        pc.register(toks, b1, upto=8)
        pc.register(toks, b2, upto=8)        # duplicate content: ignored
        got, _ = pc.match(np.concatenate([toks, [3]]))
        assert got == b1

    def test_partial_tail_never_registered(self):
        a = BlockAllocator(16)
        pc = PrefixCache(a, block_size=4)
        toks = np.arange(1, 8, dtype=np.int32)  # 7 tokens: 1 full + tail
        blocks = a.allocate(2)
        pc.register(toks, blocks, upto=7)
        assert pc.registered(blocks[0])
        assert not pc.registered(blocks[1])


class TestSchedulerPrefixAndCOW:
    def _sched(self, num_blocks=16, block_size=4, slots=2, prefills=1):
        alloc = BlockAllocator(num_blocks)
        pc = PrefixCache(alloc, block_size)
        return Scheduler(alloc, block_size, slots, prefills,
                         prefix_cache=pc), alloc, pc

    def test_admission_charges_only_unshared_blocks(self):
        # hash-chain admission charging: follower pays for its suffix only
        s, alloc, pc = self._sched()
        a = _mk_req(12)
        s.waiting.append(a)
        ((_, ra),) = s.pick_prefills()       # charges 4 blocks (12+1 tok)
        pc.register(ra.tokens, ra.blocks, upto=12)
        free_before = alloc.num_free
        b = Request(np.arange(1, 13, dtype=np.int32))  # same 12 tokens
        s.waiting.append(b)
        ((_, rb),) = s.pick_prefills()
        # matched 2 full blocks (the proper-prefix cap: 12 tokens never
        # match all 3 full blocks — at least one token must prefill so
        # admission has last-position logits) + 2 fresh
        assert rb.blocks[:2] == ra.blocks[:2]
        assert rb.num_cached == 8            # prefix already in-pool
        assert free_before - alloc.num_free == 2
        assert all(alloc.ref(blk) == 2 for blk in rb.blocks[:2])
        assert s.stats["prefix_blocks_reused"] == 2
        # registry name (metrics lint): serving_prefix_blocks_reused_total
        from paddle_tpu.observability import metrics as om

        assert om.REGISTRY.get(
            "serving_prefix_blocks_reused_total").value(
            instance=s.instance) == 2

    def test_finish_decrefs_shared_blocks(self):
        s, alloc, pc = self._sched()
        a = _mk_req(12)
        s.waiting.append(a)
        s.pick_prefills()
        pc.register(a.tokens, a.blocks, upto=12)
        b = Request(np.arange(1, 13, dtype=np.int32))
        s.waiting.append(b)
        s.pick_prefills()
        shared = list(b.blocks[:2])
        assert shared == a.blocks[:2]
        s.finish(a)                          # decref only: b still holds
        assert all(alloc.ref(blk) == 1 for blk in shared)
        s.finish(b)                          # last holder: parks reusable
        assert all(alloc.ref(blk) == 0 for blk in shared)
        assert alloc.num_free == 15          # all reclaimable

    def test_cow_divergent_write_gets_private_copy(self):
        # forge a shared write-target (the engine never produces one —
        # only FULL blocks are shared — so the guard is exercised
        # directly): the divergent writer must get a COPY, the shared
        # block must keep its refcount and identity
        s, alloc, pc = self._sched(num_blocks=16)
        a = _mk_req(6)
        s.waiting.append(a)
        s.pick_prefills()
        a.num_cached = 6
        a.prefilling = False
        tail = a.blocks[1]                   # write target (pos 6 -> blk 1)
        alloc.acquire([tail])                # forged second holder
        evicted = s.ensure_decode_room()
        assert evicted == []
        assert s.pending_cow and s.pending_cow[0][0] == tail
        new = s.pending_cow[0][1]
        assert a.blocks[1] == new and new != tail
        assert alloc.ref(tail) == 1          # the other holder keeps it
        assert alloc.ref(new) == 1
        assert s.stats["cow_copies"] == 1
        # registry name (metrics lint): serving_cow_copies_total
        from paddle_tpu.observability import metrics as om

        assert om.REGISTRY.get("serving_cow_copies_total").value(
            instance=s.instance) == 1

    def test_cow_sole_holder_registered_block_forgets_identity(self):
        # ref==1 but published: the write diverges content from its hash,
        # so the identity retracts — no copy needed
        s, alloc, pc = self._sched()
        a = _mk_req(8)
        s.waiting.append(a)
        s.pick_prefills()
        a.num_cached = 8
        a.prefilling = False
        a.output_tokens.append(1)            # write pos 8 -> block 2
        target = a.blocks[2]
        pc._by_hash[b"forged"] = target      # forge a published identity
        pc._block_hash[target] = b"forged"
        s.ensure_decode_room()
        assert not pc.registered(target)
        assert not s.pending_cow

    def test_copy_block_never_mutates_source_pool_page(self):
        import jax.numpy as jnp

        cfg = tiny_cfg()
        cache = PagedKVCache(cfg, num_blocks=8, block_size=4)
        marked = jnp.full_like(cache.k[0][1], 7.0)
        cache.k = [kp.at[1].set(marked) for kp in cache.k]
        before = np.asarray(cache.k[0][1]).copy()
        cache.copy_block(1, 3)
        np.testing.assert_array_equal(np.asarray(cache.k[0][1]), before)
        np.testing.assert_array_equal(np.asarray(cache.k[0][3]), before)

    def test_trim_frees_overallocated_tail(self):
        s, alloc, _ = self._sched()
        a = _mk_req(6)
        s.waiting.append(a)
        s.pick_prefills()                    # 2 blocks for 7 tokens
        extra = alloc.allocate(2)
        a.blocks.extend(extra)               # speculative lookahead blocks
        v0 = s.version
        s.trim_to_capacity(a)                # 6 tokens need 2 blocks
        assert len(a.blocks) == 2
        assert alloc.num_free == 13
        assert s.version > v0


# ---------------------------------------------------------------------------
# ISSUE 11: multi-query paged attention parity
# ---------------------------------------------------------------------------

def _mq_case(seed=0, B=2, T=3, H=4, Hkv=2, D=16, block=4, P=5, N=32):
    rng = np.random.RandomState(seed)
    q = rng.randn(B, T, H, D).astype(np.float32)
    k_pool = rng.randn(N, block, Hkv, D).astype(np.float32)
    v_pool = rng.randn(N, block, Hkv, D).astype(np.float32)
    perm = rng.permutation(np.arange(1, N))[:B * P].reshape(B, P)
    # q_start positions leaving room for T rows inside P*block
    starts = rng.randint(0, P * block - T + 1, size=B).astype(np.int32)
    lens = (starts + T).astype(np.int32)
    return q, k_pool, v_pool, perm.astype(np.int32), lens, starts


def _mq_reference(q, k_pool, v_pool, tables, lens, starts):
    """Independent numpy reference: per-row causal mask at q_start+t."""
    B, T, H, D = q.shape
    _, block, Hkv, _ = k_pool.shape
    P = tables.shape[1]
    out = np.zeros_like(q)
    for i in range(B):
        k = k_pool[tables[i]].reshape(P * block, Hkv, D)
        v = v_pool[tables[i]].reshape(P * block, Hkv, D)
        k = np.repeat(k, H // Hkv, axis=1)
        v = np.repeat(v, H // Hkv, axis=1)
        for t in range(T):
            n_vis = min(starts[i] + t + 1, lens[i])
            for h in range(H):
                s = (q[i, t, h] @ k[:n_vis, h].T) / np.sqrt(D)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, t, h] = p @ v[:n_vis, h]
    return out


class TestMultiqueryPagedAttention:
    def test_lax_fallback_matches_reference(self):
        import jax.numpy as jnp

        q, kp, vp, tables, lens, starts = _mq_case()
        got = np.asarray(paged_multiquery_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(starts)))
        np.testing.assert_allclose(
            got, _mq_reference(q, kp, vp, tables, lens, starts), atol=1e-5)

    def test_single_row_equals_decode_attention(self):
        import jax.numpy as jnp

        q, kp, vp, tables, lens = _paged_case(seed=11)
        starts = (lens - 1).astype(np.int32)
        got = np.asarray(paged_multiquery_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(starts)))
        ref = np.asarray(paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens)))
        np.testing.assert_allclose(got, ref, atol=1e-5)

    def test_pallas_interpret_matches_reference(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_multiquery_attention_pallas, use_pallas_paged)

        assert use_pallas_paged(16, 4)
        q, kp, vp, tables, lens, starts = _mq_case(seed=5, B=3, T=4)
        got = np.asarray(paged_multiquery_attention_pallas(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(starts),
            1.0 / np.sqrt(q.shape[-1])))
        np.testing.assert_allclose(
            got, _mq_reference(q, kp, vp, tables, lens, starts), atol=1e-4)


# ---------------------------------------------------------------------------
# ISSUE 11: prefix sharing through the engine
# ---------------------------------------------------------------------------

def shared_prompts(cfg, shared_len, suffix_lens, seed=0):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab_size, shared_len).astype(np.int32)
    return [np.concatenate([shared,
                            rng.randint(0, cfg.vocab_size, n).astype(
                                np.int32)])
            for n in suffix_lens]


class TestPrefixSharingEngine:
    def test_bit_exact_and_blocks_reused(self, model):
        cfg = model.config
        prompts = shared_prompts(cfg, 24, [5, 7, 3, 6], seed=30)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=6).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=96, block_size=8, max_batch_size=4,
                       enable_prefix_cache=True) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
            em = eng.metrics()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        # 3 followers x 3 full shared blocks (24 tokens / 8)
        assert em["prefix_blocks_reused"] >= 9
        assert em["prefill_chunks"] == 4  # suffix-only prefill per req

    def test_reusable_blocks_revive_across_waves(self, model):
        # wave 2 arrives AFTER wave 1 fully finished: the shared blocks
        # sit at refcount 0 (reusable) and must revive, not re-prefill
        cfg = model.config
        with LLMEngine(model, num_blocks=96, block_size=8, max_batch_size=2,
                       enable_prefix_cache=True) as eng:
            w1 = shared_prompts(cfg, 16, [4], seed=31)
            eng.generate(w1, SamplingParams(max_new_tokens=4))
            reused0 = eng.metrics()["prefix_blocks_reused"]
            w2 = shared_prompts(cfg, 16, [6], seed=31)  # same shared 16
            out2 = eng.generate(w2, SamplingParams(max_new_tokens=4))[0]
            em = eng.metrics()
        ref = model.generate(paddle.to_tensor(w2[0][None]),
                             max_new_tokens=4).numpy()[0]
        np.testing.assert_array_equal(out2, ref)
        assert em["prefix_blocks_reused"] - reused0 >= 2

    def test_bit_exact_under_eviction_with_sharing(self, model):
        # ISSUE 11 test item: mid-stream eviction under sharing — evicted
        # requests decref shared blocks, re-admission re-matches the chain
        cfg = model.config
        prompts = shared_prompts(cfg, 12, [4, 6, 5], seed=32)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=10).numpy()[0]
                for p in prompts]
        # a pool one block tighter since ISSUE 34: a request whose prefill
        # ends beside a step in flight decodes a call later, and with 14
        # blocks the first one had left before the third grew
        with LLMEngine(model, num_blocks=13, block_size=4, max_batch_size=3,
                       enable_prefix_cache=True) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=10))
            em = eng.metrics()
        assert em["evictions"] >= 1
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_suffix_chunks_always_bucket_shaped(self, model):
        # a prefix match can leave a remainder whose covering ladder rung
        # does not fit the staged room (e.g. 136 matched of 250: take=114
        # wants rung 128 but only 120 tokens remain staged) — the chunk
        # must SPLIT across rungs, never compile an off-ladder shape
        # (review finding: one off-ladder compile per distinct match
        # offset is the recompile-per-shape cliff)
        cfg = model.config
        rng = np.random.RandomState(60)
        leader = rng.randint(0, cfg.vocab_size, 137).astype(np.int32)
        follower = np.concatenate(
            [leader[:136], rng.randint(0, cfg.vocab_size, 114).astype(
                np.int32)])
        ref = model.generate(paddle.to_tensor(follower[None]),
                             max_new_tokens=3).numpy()[0]
        with LLMEngine(model, num_blocks=128, block_size=8,
                       max_batch_size=2, enable_prefix_cache=True) as eng:
            eng.generate([leader], SamplingParams(max_new_tokens=1))
            orig = eng._prefill_jit
            chunk_lens = []

            def spy(params, ids, *a):
                chunk_lens.append(ids.shape[1])
                return orig(params, ids, *a)

            eng._prefill_jit = spy
            (out,) = eng.generate([follower],
                                  SamplingParams(max_new_tokens=3))
            assert eng.metrics()["prefix_blocks_reused"] >= 17
        np.testing.assert_array_equal(out, ref)
        assert chunk_lens and all(c in eng.prefill_buckets
                                  for c in chunk_lens), chunk_lens

    def test_pool_drains_clean_under_sharing(self, model):
        cfg = model.config
        prompts = shared_prompts(cfg, 16, [4, 5], seed=33)
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=2,
                       enable_prefix_cache=True) as eng:
            eng.generate(prompts, SamplingParams(max_new_tokens=4))
            stats = eng.stats()
        # every block either free or parked reusable — nothing leaked
        assert stats["blocks_free"] == 63


# ---------------------------------------------------------------------------
# ISSUE 11: chunked prefill
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_bit_exact_across_budgets(self, model):
        cfg = model.config
        p = prompts_fixed(cfg, [30], seed=40)[0]
        ref = model.generate(paddle.to_tensor(p[None]),
                             max_new_tokens=5).numpy()[0]
        for budget in (8, 16, None):
            with LLMEngine(model, num_blocks=64, block_size=8,
                           max_batch_size=2,
                           max_prefill_tokens_per_step=budget) as eng:
                (out,) = eng.generate([p], SamplingParams(max_new_tokens=5))
            np.testing.assert_array_equal(out, ref)

    def test_budget_bounds_tokens_per_step_and_interleaves_decode(
            self, model):
        # the structural ITL bound: while a long prompt prefills in
        # chunks, an in-flight request keeps emitting tokens EVERY step —
        # unchunked, it would stall for the whole prefill
        cfg = model.config
        short = prompts_fixed(cfg, [4], seed=41)[0]
        long_p = prompts_fixed(cfg, [64], seed=42)[0]
        with LLMEngine(model, num_blocks=96, block_size=8, max_batch_size=2,
                       max_prefill_tokens_per_step=8) as eng:
            rid_s = eng.add_request(short, SamplingParams(max_new_tokens=20))
            eng.step()  # admit + prefill short (1 chunk), first token
            assert not eng.request(rid_s).prefilling
            rid_l = eng.add_request(long_p,
                                    SamplingParams(max_new_tokens=2))
            per_step = []
            while eng.request(rid_l).state != "finished" or \
                    eng.request(rid_s).state != "finished":
                before_s = len(eng.request(rid_s).output_tokens)
                before_l = eng.request(rid_l).num_cached
                was_prefilling = (eng.request(rid_l).state == "waiting"
                                  or eng.request(rid_l).prefilling)
                eng.step()
                after_l = eng.request(rid_l).num_cached
                per_step.append(
                    (len(eng.request(rid_s).output_tokens) - before_s,
                     after_l - before_l, was_prefilling))
            em = eng.metrics()
        # chunk budget respected: never more than 8 new PREFILL tokens per
        # step (+1 when the final chunk's same-step decode also lands);
        # registry name (metrics lint): serving_prefill_chunks_total
        assert all(d_l <= 8 + 1 for _, d_l, _w in per_step)
        assert em["prefill_chunks"] >= 64 // 8 + 1
        # decode interleaved: the short request emitted tokens during the
        # long prompt's prefill-chunk steps — unchunked it would stall
        prefill_steps = [d_s for d_s, _d_l, w in per_step if w]
        assert len(prefill_steps) >= 64 // 8
        assert sum(1 for d_s in prefill_steps if d_s >= 1) >= 6, per_step

    def test_bit_exact_with_prefix_and_chunks(self, model):
        cfg = model.config
        prompts = shared_prompts(cfg, 32, [4, 7], seed=43)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=6).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=96, block_size=8, max_batch_size=2,
                       enable_prefix_cache=True,
                       max_prefill_tokens_per_step=8) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_invalid_budget_rejected(self, model):
        with pytest.raises(ValueError, match="max_prefill_tokens_per_step"):
            LLMEngine(model, num_blocks=16, block_size=8,
                      max_prefill_tokens_per_step=0)

    def test_steady_state_decode_zero_table_uploads(self, model):
        # ISSUE 11 satellite: the device block-table array re-uploaded
        # only on admission/growth/eviction — steady-state decode hits
        # the cached array
        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=44)[0]
        with LLMEngine(model, num_blocks=64, block_size=16,
                       max_batch_size=2) as eng:
            calls = {"n": 0}
            orig = eng.cache.table_array

            def counting(*a, **kw):
                calls["n"] += 1
                return orig(*a, **kw)

            eng.cache.table_array = counting
            rid = eng.add_request(p, SamplingParams(max_new_tokens=8))
            steps = 0
            while eng.has_work():
                eng.step()
                steps += 1
            # prefill+first decode share step 1, then one step per token
            assert steps >= 7
        # one upload when the request becomes decode-ready; every later
        # decode step reuses it (6+8 tokens fit one 16-token block: no
        # growth, no re-upload)
        assert calls["n"] == 1, calls


# ---------------------------------------------------------------------------
# ISSUE 11: speculative decoding
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft_model(model):
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(99)
    m = LlamaForCausalLM(dataclasses.replace(tiny_cfg(),
                                             num_hidden_layers=1))
    m.eval()
    return m


class TestSpeculativeDecoding:
    def test_self_draft_bit_exact_full_accept(self, model):
        # target as its own draft: every proposal matches, the verify
        # window commits k+1 tokens per step, outputs stay bit-exact
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 9, 3], seed=50)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=9).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=3,
                       draft_model=model, spec_tokens=3) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=9))
            em = eng.metrics()
            # registry names (metrics lint): serving_spec_proposed_total,
            # serving_spec_accepted_total, serving_spec_accept_ratio —
            # read INSIDE the context: close() removes the instance's
            # registry series (ISSUE 12)
            from paddle_tpu.observability import metrics as om

            inst = em["instance"]
            assert om.REGISTRY.get("serving_spec_proposed_total").value(
                instance=inst) == em["spec_proposed"]
            assert om.REGISTRY.get("serving_spec_accepted_total").value(
                instance=inst) == em["spec_accepted"]
            assert om.REGISTRY.get("serving_spec_accept_ratio").value(
                instance=inst) == em["spec_accept_ratio"]
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert em["spec_proposed"] > 0
        assert em["spec_accepted"] > 0
        assert em["spec_accept_ratio"] is not None
        assert em["spec_accept_ratio"] > 0.5

    def test_independent_draft_bit_exact(self, model, draft_model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [6, 11, 4, 8], seed=51)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=8).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=4,
                       draft_model=draft_model, spec_tokens=2) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=8))
            em = eng.metrics()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert em["spec_proposed"] > 0

    def test_forced_full_rejection_bit_exact(self, model, draft_model):
        # ISSUE 11 test item: every proposal wrong -> every window
        # rejects in full, emits exactly the target's greedy token, and
        # the rollback path (rewind + tail-block trim) runs every step
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 7], seed=52)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=6).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=2,
                       draft_model=draft_model, spec_tokens=3) as eng:
            orig = eng._draft_propose

            def all_wrong(ready, tables):
                d = orig(ready, tables)
                return (d + 1) % cfg.vocab_size

            eng._draft_propose = all_wrong
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
            em = eng.metrics()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert em["spec_accepted"] == 0
        assert em["spec_accept_ratio"] == 0.0

    def test_spec_with_eviction_under_sharing(self, model, draft_model):
        # the full stack: prefix sharing + speculative decode + a pool
        # small enough to force mid-stream eviction
        cfg = model.config
        prompts = shared_prompts(cfg, 12, [4, 6, 5], seed=53)
        refs = [model.generate(paddle.to_tensor(p[None]),
                               max_new_tokens=8).numpy()[0]
                for p in prompts]
        with LLMEngine(model, num_blocks=12, block_size=4, max_batch_size=3,
                       enable_prefix_cache=True, draft_model=draft_model,
                       spec_tokens=2) as eng:
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=8))
            em = eng.metrics()
        assert em["evictions"] >= 1
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_eos_inside_accept_window_truncates(self, model):
        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=54)[0]
        ref = model.generate(paddle.to_tensor(p[None]),
                             max_new_tokens=32).numpy()[0]
        eos = int(ref[len(p) + 2])  # the 3rd generated token ends it
        ref_eos = model.generate(paddle.to_tensor(p[None]),
                                 max_new_tokens=32,
                                 eos_token_id=eos).numpy()[0]
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=2,
                       draft_model=model, spec_tokens=4) as eng:
            rid = eng.add_request(p, SamplingParams(max_new_tokens=32,
                                                    eos_token_id=eos))
            for _ in eng.stream():
                pass
            out = eng.output_tokens(rid)
            assert eng.request(rid).finish_reason() == "eos"
        np.testing.assert_array_equal(out, ref_eos)

    def test_sampling_request_rejected_on_spec_engine(self, model):
        with LLMEngine(model, num_blocks=32, block_size=8, max_batch_size=2,
                       draft_model=model, spec_tokens=2) as eng:
            with pytest.raises(ValueError, match="greedy-only"):
                eng.add_request(np.arange(1, 6, dtype=np.int32),
                                SamplingParams(max_new_tokens=4,
                                               do_sample=True))

    def test_vocab_mismatch_rejected(self, model):
        from paddle_tpu.models import LlamaForCausalLM

        bad = LlamaForCausalLM(dataclasses.replace(tiny_cfg(),
                                                   vocab_size=256))
        with pytest.raises(ValueError, match="vocab_size"):
            LLMEngine(model, num_blocks=16, block_size=8, draft_model=bad)


# ---------------------------------------------------------------------------
# ISSUE 11: bench harness acceptance (shared-prefix / chunked / spec)
# ---------------------------------------------------------------------------

class TestBenchServingRawSpeed:
    def test_shared_prefix_smoke_bit_exact(self):
        bsv = _bench_mod()
        res = bsv.run_shared_prefix_ab(tiny=True, seed=0)
        assert res["bit_exact"]
        assert res["prefix_hit_ratio"] > 0.5
        assert res["sharing"]["prefix_blocks_reused"] > 0

    @pytest.mark.slow
    def test_acceptance_shared_prefix_2x_effective_tokens(self):
        # ISSUE 11 acceptance: >=2x effective tokens/s vs the no-sharing
        # arm on the CPU smoke, greedy outputs bit-exact
        bsv = _bench_mod()
        res = bsv.run_shared_prefix_ab(tiny=True, seed=0, repeat=3)
        assert res["bit_exact"]
        assert res["speedup"] >= 2.0, res

    @pytest.mark.slow
    def test_acceptance_chunked_bounds_itl_p99(self):
        # ISSUE 11 acceptance: chunked prefill bounds decode ITL p99
        # (engine-owned serving_itl_ms histogram) below the unchunked arm
        # at equal total tokens/s +-10%
        bsv = _bench_mod()
        res = bsv.run_chunked_ab(tiny=True, seed=0, repeat=5)
        assert res["bit_exact"]
        assert res["itl_p99_ms"]["chunked"] < \
            res["itl_p99_ms"]["unchunked"], res
        # the +-10% equal-throughput criterion guards against LOSS; being
        # faster than the unchunked arm (which standalone runs are) is
        # strictly better, so only the lower bound is asserted
        assert res["tokens_per_sec_ratio"] >= 0.9, res

    @pytest.mark.slow
    def test_acceptance_spec_reports_ratio_bit_exact(self):
        # ISSUE 11 acceptance: the speculative arm reports accept-ratio
        # in LLMEngine.metrics() and is bit-exact vs non-speculative
        bsv = _bench_mod()
        res = bsv.run_spec_ab(tiny=True, seed=0)
        assert res["bit_exact"]
        assert res["spec_accept_ratio"] is not None
        assert res["spec_accept_ratio"] > 0.5  # self-draft upper bound


# ---------------------------------------------------------------------------
# per-request deadlines (ISSUE 12 satellite: the edge matrix)
# ---------------------------------------------------------------------------

class TestEngineDeadlines:
    def test_expired_at_add_request_allocator_untouched(self, model):
        """An already-expired deadline is rejected BEFORE any block
        allocation or staging — typed RequestTimeoutError, allocator and
        request table bit-identical to before."""
        import time

        from paddle_tpu.inference.serving import RequestTimeoutError

        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            free0 = eng.cache.allocator.num_free
            n_reqs = len(eng._requests)
            with pytest.raises(RequestTimeoutError):
                eng.add_request(np.arange(1, 6, dtype=np.int32),
                                SamplingParams(max_new_tokens=4),
                                deadline=time.time() - 1.0)
            assert eng.cache.allocator.num_free == free0
            assert len(eng._requests) == n_reqs
            assert not eng.has_work()
            assert eng.metrics()["deadline_expired"] == 0  # never admitted

    def test_mid_decode_expiry_frees_blocks_and_recycles_slot(self, model):
        """A deadline expiring mid-decode ends the partial stream with
        the typed reason, frees every block (high-water returns to the
        burst baseline) and recycles the slot for the next admission."""
        import time

        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=1, ingest_async=False) as eng:
            free0 = eng.cache.allocator.num_free
            eng.reset_block_high_water()
            rid = eng.add_request(np.arange(1, 7, dtype=np.int32),
                                  SamplingParams(max_new_tokens=200),
                                  deadline=time.time() + 0.4)
            outs = []
            while eng.has_work():
                outs.extend(eng.step())
            # partial stream: some tokens, then the typed end
            assert outs[-1].finished and outs[-1].finish_reason == "timeout"
            assert len(eng.request(rid).output_tokens) > 0
            assert eng.request(rid).finish_reason() == "timeout"
            # registry name (metrics lint): serving_deadline_expired_total
            from paddle_tpu.observability import metrics as om

            assert om.REGISTRY.get(
                "serving_deadline_expired_total").value(
                instance=eng._name) == 1
            assert eng.metrics()["deadline_expired"] == 1
            # allocator clean: all blocks back, slot reusable immediately
            assert eng.cache.allocator.num_free == free0
            out2 = eng.generate([np.arange(1, 5, dtype=np.int32)],
                                SamplingParams(max_new_tokens=3))
            assert len(out2[0]) == 4 + 3
            assert eng.cache.allocator.num_free == free0
            eng.reset_block_high_water()
            assert eng.cache.allocator.high_water == 0

    def test_generate_raises_after_drain(self, model):
        import time

        from paddle_tpu.inference.serving import RequestTimeoutError

        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=1, ingest_async=False) as eng:
            with pytest.raises(RequestTimeoutError):
                eng.generate([np.arange(1, 7, dtype=np.int32)],
                             SamplingParams(max_new_tokens=200),
                             deadline=time.time() + 0.3)
            # failed batch released its bookkeeping
            assert not eng._requests

    def test_generate_mid_admission_expiry_leaves_no_orphans(
            self, model, monkeypatch):
        """A deadline expiring BETWEEN a batch's admissions must not
        orphan the already-admitted requests — they would decode to
        completion on the next stream() and leak bookkeeping."""
        import time

        from paddle_tpu.inference.serving import RequestTimeoutError

        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            free0 = eng.cache.allocator.num_free
            real = time.time
            deadline = real() + 30.0
            calls = {"n": 0}

            def fake_time():
                # the SECOND add_request's admission check (and later
                # reads) sees a clock past the deadline
                calls["n"] += 1
                return real() + (60.0 if calls["n"] >= 2 else 0.0)

            monkeypatch.setattr(time, "time", fake_time)
            with pytest.raises(RequestTimeoutError):
                eng.generate([np.arange(1, 5, dtype=np.int32),
                              np.arange(1, 7, dtype=np.int32)],
                             SamplingParams(max_new_tokens=4),
                             deadline=deadline)
            monkeypatch.undo()
            assert not eng._requests
            assert not eng.has_work()
            assert eng.cache.allocator.num_free == free0

    def test_cancel_frees_and_types_reason(self, model):
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            free0 = eng.cache.allocator.num_free
            rid = eng.add_request(np.arange(1, 9, dtype=np.int32),
                                  SamplingParams(max_new_tokens=20))
            eng.step()
            assert eng.cancel(rid)
            assert eng.request(rid).finish_reason() == "cancelled"
            assert eng.cache.allocator.num_free == free0
            assert not eng.cancel(rid)  # idempotent on finished


# ---------------------------------------------------------------------------
# LLMEngine.close() lifecycle (ISSUE 12 satellite)
# ---------------------------------------------------------------------------

class TestEngineClose:
    def test_close_frees_blocks_joins_ingest_and_guards(self, model):
        from paddle_tpu.inference.serving import EngineClosedError

        eng = LLMEngine(model, num_blocks=32, block_size=8,
                        max_batch_size=2)  # async ingest on
        free0 = eng.cache.allocator.num_free
        eng.add_request(np.arange(1, 9, dtype=np.int32),
                        SamplingParams(max_new_tokens=20))
        eng.step()  # admitted: blocks held
        assert eng.cache.allocator.num_free < free0
        eng.close()
        assert eng.cache.allocator.num_free == free0
        assert eng._ingest._thread.is_alive() is False
        for call in (eng.step, lambda: next(iter(eng.stream())),
                     lambda: eng.add_request(np.arange(3, dtype=np.int32)),
                     lambda: eng.generate([np.arange(3, dtype=np.int32)])):
            with pytest.raises(EngineClosedError):
                call()
        eng.close()  # idempotent

    def test_repeated_engines_do_not_grow_registry(self, model):
        """Mirrors DevicePrefetcher.close(): per-instance registry series
        are removed, so constructing engines in a loop keeps the metrics
        registry bounded."""
        from paddle_tpu.observability import metrics as om

        names = []
        for _ in range(3):
            with LLMEngine(model, num_blocks=16, block_size=8,
                           max_batch_size=1, ingest_async=False) as eng:
                names.append(eng._name)
                eng.generate([np.arange(1, 5, dtype=np.int32)],
                             SamplingParams(max_new_tokens=2))
        snap = om.REGISTRY.snapshot()
        for metric in ("serving_requests_admitted_total",
                       "serving_tokens_out_total", "serving_ttft_ms",
                       "serving_deadline_expired_total"):
            series = snap.get(metric, {"series": {}})["series"]
            for name in names:
                assert not any(name in k for k in series), (metric, name)


# ---------------------------------------------------------------------------
# fleet chaos drill + scaling (ISSUE 12 acceptance, slow tier — the
# chaos_train.py discipline applied to serving)
# ---------------------------------------------------------------------------

def _chaos_env():
    import os as _os

    env = dict(_os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + _os.pathsep
                + env.get("PYTHONPATH", "")})
    return env


@pytest.mark.slow
class TestChaosServeDrill:
    @pytest.mark.parametrize("drill", ["kill", "hang", "drain", "qos",
                                       "sdc"])
    def test_drill(self, drill, tmp_path):
        """ISSUE 12 acceptance: scripts/chaos_serve.py --drill kill runs
        the storm (one replica SIGKILLed AND one hung mid-burst with
        fleet >= 3); hang and drain exercise their paths in isolation.
        qos (ISSUE 17) floods the fleet with batch + over-quota traffic
        and asserts the latency tier holds p99 TTFT, the abuser is
        rate-limited typed, batch work yields-not-drops, and a
        mid-flood scale-down (draining replica SIGKILLed) drops zero.
        sdc (ISSUE 20) proves the silent-data-corruption defense via
        ``serve.bit_flip``: a host-tier flip is rejected by the page
        CRC at revive (re-prefill, bit-exact), a weight flip on a
        replica is caught by the sampled output audit + referee vote
        and quarantined through one restart-budget slot, and a
        single-engine weight flip is healed by the fingerprint
        re-audit + reload_weights.
        Every drill asserts bit-exact outputs vs an undisturbed baseline,
        typed-error accounting, liveness dip+recovery and clean
        allocators — see the script for the full checklist."""
        import subprocess
        import sys as _sys

        r = subprocess.run(
            [_sys.executable, os.path.join(REPO, "scripts",
                                           "chaos_serve.py"),
             "--drill", drill, "--fleet", "3", "--out", str(tmp_path)],
            env=_chaos_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "SERVE DRILL PASSED" in r.stdout

    def test_drill_shed(self, tmp_path):
        import subprocess
        import sys as _sys

        r = subprocess.run(
            [_sys.executable, os.path.join(REPO, "scripts",
                                           "chaos_serve.py"),
             "--drill", "shed", "--fleet", "2", "--out", str(tmp_path)],
            env=_chaos_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "SERVE DRILL PASSED" in r.stdout


@pytest.mark.slow
class TestFleetScaling:
    def test_fleet_ab_bit_exact_and_scales(self):
        """ROADMAP item 1 / ISSUE 12: bench_serving --workload fleet —
        1-replica vs 3-replica subprocess fleets over one seeded Poisson
        burst, bit-exact vs the in-process engine, with real tokens/s
        scaling from replica parallelism (threshold is deliberately
        conservative vs near-linear: CI boxes share cores)."""
        bsv = _bench_mod()
        res = bsv.run_fleet_ab(tiny=True, seed=0, fleet=3)
        assert res["bit_exact"], res
        assert res["fleet"]["requests_shed"] == 0
        assert res["scaling"] >= 1.3, res
