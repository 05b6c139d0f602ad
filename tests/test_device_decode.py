"""Device-resident decode tests (ISSUE 18): in-graph greedy sampling
(serving_host_syncs_total / serving_decode_fetch_bytes_total shrink the
per-token fetch from B*V*4 logits bytes to B*4 token bytes) and fused
multi-step decode windows (decode_steps_per_sync=k) — bit-exact against
the per-step host-sampling path across eviction pressure, prefix
sharing, int8 KV, chunked prefill, mid-window EOS, and deadline aborts
at window boundaries; zero extra decode compiles; typed rejections for
the combinations the window cannot serve (speculative decoding,
host-side do_sample, capture_logits)."""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import LLMEngine, SamplingParams


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


def _generate(model, prompts, sampling, **kw):
    kw.setdefault("num_blocks", 96)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("ingest_async", False)
    with LLMEngine(model, **kw) as eng:
        outs = eng.generate(prompts, sampling)
        metrics = eng.metrics()
    return [np.asarray(o) for o in outs], metrics


class TestInGraphSampling:
    def test_greedy_head_matches_host_sampler(self):
        # the bit-exactness contract at its root: sample_next_tokens
        # argmaxes a float64 view (exact, monotone cast of f32), so the
        # in-graph f32 argmax must pick the identical index — including
        # the first-occurrence tie-break rule
        import jax.numpy as jnp

        from paddle_tpu.models.llama import (greedy_tokens_in_graph,
                                             sample_next_tokens)

        rng = np.random.RandomState(0)
        logits = rng.randn(5, 64).astype(np.float32)
        logits[1, 7] = logits[1, 3] = logits[1].max() + 1.0  # forced tie
        host = sample_next_tokens(logits)
        dev = np.asarray(greedy_tokens_in_graph(jnp.asarray(logits)))
        np.testing.assert_array_equal(host, dev)
        assert dev[1] == 3  # first occurrence wins on both paths

    def test_bit_exact_and_fetch_bytes_drop(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 12, 9, 17], seed=3)
        sp = SamplingParams(max_new_tokens=9)
        # host-sampled from the fetched rows (capture_logits keeps them),
        # the default (ISSUE 27: the decode graph's own argmax, fetched
        # when the whole batch is greedy), and the window path's switch
        ref, mref = _generate(model, prompts, sp, capture_logits=True)
        dflt, mdflt = _generate(model, prompts, sp)
        ing, ming = _generate(model, prompts, sp, in_graph_sampling=True)
        for a, b, c in zip(ref, dflt, ing):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        # ISSUE 18 satellite: per-sync decode fetch drops from B*V*4
        # logits bytes to B*4 token bytes; the kept rows come beside the
        # tokens, which are the graph's argmax either way (ISSUE 28)
        B, V = 4, cfg.vocab_size
        assert mref["host_syncs"] > 0
        assert mref["decode_fetch_bytes"] == \
            mref["host_syncs"] * B * (V + 1) * 4
        assert mdflt["host_syncs"] == mref["host_syncs"]
        # the per-step path runs a step ahead, so a request whose prefill
        # ends beside a step in flight joins the step after it (ISSUE 28):
        # the same tokens, and here one more step than the window path's
        assert mdflt["host_syncs"] == ming["host_syncs"] + 1
        for m in (mdflt, ming):
            assert m["decode_fetch_bytes"] == m["host_syncs"] * B * 4

    def test_rows_are_fetched_only_while_something_reads_them(self, model):
        # one engine, three stretches: greedy (tokens), a sampled request
        # in the batch (rows for all), capture_logits switched on and off
        # between steps (rows, then tokens again); the greedy request's
        # tokens are what it gets alone whatever was fetched beside it
        cfg = model.config
        B, V = 2, cfg.vocab_size
        p, q = prompts_fixed(cfg, [7, 11], seed=9)
        alone, _ = _generate(model, [p], SamplingParams(max_new_tokens=12))
        with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=B,
                       ingest_async=False) as eng:
            rid = eng.add_request(p, SamplingParams(max_new_tokens=12))

            def stretch(steps):
                m0 = eng.metrics()
                for _ in range(steps):
                    eng.step()
                m1 = eng.metrics()
                return ((m1["decode_fetch_bytes"] - m0["decode_fetch_bytes"])
                        // (m1["host_syncs"] - m0["host_syncs"]))

            eng.step()                                   # prefill + 1 decode
            assert stretch(2) == B * 4
            other = eng.add_request(q, SamplingParams(
                max_new_tokens=3, do_sample=True, temperature=1.1, seed=5))
            # the step in flight was made before the sampled request came
            # (ISSUE 28): this call prefills it beside that greedy step
            assert stretch(1) == B * 4
            assert len(eng.request(other).output_tokens) == 1
            while not eng.request(other).finished:
                assert stretch(1) == B * V * 4
            eng.release(other)
            eng.capture_logits = True
            assert stretch(1) == B * (V + 1) * 4
            assert eng.request(rid).last_logits.shape == (V,)
            eng.capture_logits = False
            assert stretch(1) == B * 4
            while not eng.request(rid).finished:
                eng.step()
            np.testing.assert_array_equal(
                alone[0][len(p):], eng.request(rid).output_tokens)

    def test_do_sample_keeps_host_path_with_one_shot_warning(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [6, 10], seed=4)
        sp = SamplingParams(max_new_tokens=6, do_sample=True,
                            temperature=1.3, top_k=16, seed=11)
        ref, _ = _generate(model, prompts, sp)
        with pytest.warns(RuntimeWarning, match="host sampling path"):
            got, m = _generate(model, prompts, sp,
                               decode_steps_per_sync=4)
        # the per-request numpy RNG path is untouched: seeded sampling
        # reproduces exactly, and every decode fetch is a logits row
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert m["decode_fetch_bytes"] % (cfg.vocab_size * 4) == 0


class TestDecodeWindows:
    @pytest.mark.parametrize("k", [1, 2, 8])
    @pytest.mark.parametrize("variant", [
        "plain", "eviction", "prefix", "int8", "chunked"])
    def test_bit_exact_vs_per_step(self, model, k, variant):
        cfg = model.config
        prompts = prompts_fixed(cfg, [5, 12, 9, 17], seed=5)
        sp = SamplingParams(max_new_tokens=11)
        kw = {}
        if variant == "eviction":
            # lockstep identical-length requests over a pool (5 usable
            # blocks) that cannot hold two full 20-token tails (3 blocks
            # each): both slots demand their 3rd block on the same step,
            # forcing an eviction + re-prefill in EVERY arm — which must
            # not change the greedy trajectory
            prompts = prompts_fixed(cfg, [9, 9, 9], seed=5)
            kw = dict(num_blocks=6, max_batch_size=2)
        elif variant == "prefix":
            # the shared prefix must span full blocks to register
            shared = prompts_fixed(cfg, [16], seed=15)[0]
            prompts = [shared] + [
                np.concatenate([shared, p]) for p in prompts[1:]]
            kw = dict(enable_prefix_cache=True)
        elif variant == "int8":
            kw = dict(kv_dtype="int8")
        elif variant == "chunked":
            prompts = prompts_fixed(cfg, [5, 29, 9, 23], seed=5)
            kw = dict(max_prefill_tokens_per_step=8,
                      max_prefills_per_step=4)
        ref, mref = _generate(model, prompts, sp, **kw)
        win, mwin = _generate(model, prompts, sp,
                              decode_steps_per_sync=k, **kw)
        for a, b in zip(ref, win):
            np.testing.assert_array_equal(a, b)
        if variant == "eviction":
            assert mref["evictions"] >= 1 and mwin["evictions"] >= 1
        if variant == "prefix":
            assert mwin["prefix_blocks_reused"] >= 1
        if k > 1:
            # host syncs per token shrink ~k x (window boundaries only)
            assert mwin["host_syncs"] < mref["host_syncs"]

    def test_host_syncs_reduced_k_fold(self, model):
        # decode-bound, co-admitted pair: the first token comes from
        # prefill, the remaining 24 from decode rounds
        cfg = model.config
        prompts = prompts_fixed(cfg, [4, 4], seed=6)
        sp = SamplingParams(max_new_tokens=25)
        kw = dict(max_batch_size=2, max_prefills_per_step=2)
        _, m1 = _generate(model, prompts, sp, in_graph_sampling=True,
                          **kw)
        _, m8 = _generate(model, prompts, sp, decode_steps_per_sync=8,
                          **kw)
        assert m1["host_syncs"] == 24  # one sync per decode step
        assert m8["host_syncs"] == 3   # ceil(24 / 8) window boundaries
        assert m8["decode_fetch_bytes"] == 3 * 2 * 8 * 4  # [B=2, k=8] i32

    def test_mid_window_eos_freezes_row(self, model):
        # pick an eos id the greedy stream actually emits mid-window, so
        # the in-graph freeze (not the length cap) ends the request
        cfg = model.config
        prompts = prompts_fixed(cfg, [7, 13], seed=7)
        base = SamplingParams(max_new_tokens=12)
        ref, _ = _generate(model, prompts, base)
        eos = int(ref[0][len(prompts[0]) + 4])  # 5th generated token
        sp = SamplingParams(max_new_tokens=12, eos_token_id=eos)
        stop, _ = _generate(model, prompts, sp)
        win, mwin = _generate(model, prompts, sp, decode_steps_per_sync=8)
        for a, b in zip(stop, win):
            np.testing.assert_array_equal(a, b)
        assert len(win[0]) < len(ref[0])  # eos actually cut the stream

    def test_deadline_abort_at_window_boundary(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [6], seed=8)
        with LLMEngine(model, num_blocks=64, block_size=8,
                       max_batch_size=2, ingest_async=False,
                       decode_steps_per_sync=4) as eng:
            rid = eng.add_request(
                prompts[0], SamplingParams(max_new_tokens=64),
                deadline=time.time() + 3600)
            outs = eng.step()  # prefill + first window
            assert outs and not any(o.finished for o in outs)
            # expire between windows: the NEXT boundary must abort it
            eng.request(rid).deadline = time.time() - 1.0
            outs = eng.step()
            assert [(o.token, o.finish_reason) for o in outs
                    if o.finished] == [(-1, "timeout")]
            assert eng.metrics()["deadline_expired"] == 1
            # allocator clean: the aborted request freed every block
            alloc = eng.cache.allocator
            assert alloc.num_free == eng.cache.num_blocks - 1

    def test_window_compiles_once(self, model):
        cfg = model.config
        sp = SamplingParams(max_new_tokens=7)
        with LLMEngine(model, num_blocks=96, block_size=8,
                       max_batch_size=4, ingest_async=False,
                       decode_steps_per_sync=4) as eng:
            eng.generate(prompts_fixed(cfg, [4, 7], seed=9), sp)
            eng.generate(prompts_fixed(cfg, [3, 9, 5, 6], seed=10), sp)
            row = paddle.jit.cache_stats()[eng._window_name]
            # one executable serves every mix; the per-step decode graph
            # never runs (and never compiles) on a pure-greedy window
            # engine
            assert row["compiles"] == 1
            assert row["hits"] >= 3
            assert eng._decode_name not in paddle.jit.cache_stats()
            alloc = eng.cache.allocator
            assert alloc.num_free == eng.cache.num_blocks - 1

    def test_window_one_defaults_keep_the_per_step_path(self, model):
        # decode_steps_per_sync=1 (the default) decodes a step at a time
        # and never builds the window graph; a greedy batch fetches its
        # tokens, as the decode graph's own argmax gives them (ISSUE 27)
        cfg = model.config
        with LLMEngine(model, num_blocks=64, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            assert eng._decode_window == 1
            assert not eng._in_graph
            eng.generate(prompts_fixed(cfg, [5], seed=11),
                         SamplingParams(max_new_tokens=3))
            assert eng._window_jit is None
            assert eng._window_name not in paddle.jit.cache_stats()
            assert eng.metrics()["decode_fetch_bytes"] == (
                eng.metrics()["host_syncs"] * 2 * 4)


class TestTypedRejections:
    def test_spec_decode_and_windows_mutually_exclusive(self, model):
        with pytest.raises(ValueError, match="mutually exclusive"):
            LLMEngine(model, num_blocks=32, block_size=8,
                      max_batch_size=2, ingest_async=False,
                      draft_model=model, decode_steps_per_sync=2)

    def test_in_graph_sampling_with_draft_rejected(self, model):
        with pytest.raises(ValueError, match="verify step"):
            LLMEngine(model, num_blocks=32, block_size=8,
                      max_batch_size=2, ingest_async=False,
                      draft_model=model, in_graph_sampling=True)

    def test_window_requires_in_graph_sampling(self, model):
        with pytest.raises(ValueError, match="in_graph_sampling"):
            LLMEngine(model, num_blocks=32, block_size=8,
                      max_batch_size=2, ingest_async=False,
                      in_graph_sampling=False, decode_steps_per_sync=4)

    def test_capture_logits_needs_host_sampling(self, model):
        with pytest.raises(ValueError, match="capture_logits"):
            LLMEngine(model, num_blocks=32, block_size=8,
                      max_batch_size=2, ingest_async=False,
                      capture_logits=True, decode_steps_per_sync=2)

    def test_window_must_be_positive(self, model):
        with pytest.raises(ValueError, match="decode_steps_per_sync"):
            LLMEngine(model, num_blocks=32, block_size=8,
                      max_batch_size=2, ingest_async=False,
                      decode_steps_per_sync=0)


class TestCaptureLogits:
    def test_last_logits_gated_off_by_default(self, model):
        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=12)[0]
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False) as eng:
            rid = eng.add_request(p, SamplingParams(max_new_tokens=2))
            for _ in eng.stream():
                pass
            assert eng.request(rid).last_logits is None

    def test_capture_logits_opt_in(self, model):
        cfg = model.config
        p = prompts_fixed(cfg, [6], seed=12)[0]
        with LLMEngine(model, num_blocks=32, block_size=8,
                       max_batch_size=2, ingest_async=False,
                       capture_logits=True) as eng:
            rid = eng.add_request(p, SamplingParams(max_new_tokens=2))
            for _ in eng.stream():
                pass
            row = eng.request(rid).last_logits
            assert row is not None and row.shape == (cfg.vocab_size,)
