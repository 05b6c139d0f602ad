"""What a decode step fetches (ISSUE 18, 27, 28): the decode graph's own
argmax is the host sampler's choice bit for bit, a greedy step fetches
``[B]`` int32 tokens (serving_host_syncs_total /
serving_decode_fetch_bytes_total), the ``[B, V]`` float32 rows come only
while a request samples or ``capture_logits`` is on, and a seeded sampled
batch reproduces token for token."""

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
        # host-sampled from the fetched rows (capture_logits keeps them)
        # and the default (ISSUE 27: the decode graph's own argmax, fetched
        # when the whole batch is greedy)
        ref, mref = _generate(model, prompts, sp, capture_logits=True)
        dflt, mdflt = _generate(model, prompts, sp)
        for a, b in zip(ref, dflt):
            np.testing.assert_array_equal(a, b)
        # ISSUE 18 satellite: per-sync decode fetch drops from B*V*4
        # logits bytes to B*4 token bytes; the kept rows come beside the
        # tokens, which are the graph's argmax either way (ISSUE 28)
        B, V = 4, cfg.vocab_size
        assert mref["host_syncs"] > 0
        assert mref["decode_fetch_bytes"] == \
            mref["host_syncs"] * B * (V + 1) * 4
        assert mdflt["host_syncs"] == mref["host_syncs"]
        assert mdflt["decode_fetch_bytes"] == mdflt["host_syncs"] * B * 4

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
            # (ISSUE 28): this call prefills it beside that greedy step, and
            # dispatches the next greedy step before it fetches the sampled
            # request's first token (ISSUE 34)
            assert stretch(1) == B * 4
            assert len(eng.request(other).output_tokens) == 1
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

    def test_a_seeded_sampled_batch_reproduces_and_fetches_rows(self, model):
        cfg = model.config
        prompts = prompts_fixed(cfg, [6, 10], seed=4)
        sp = SamplingParams(max_new_tokens=6, do_sample=True,
                            temperature=1.3, top_k=16, seed=11)
        ref, _ = _generate(model, prompts, sp)
        got, m = _generate(model, prompts, sp)
        # the per-request numpy RNG: seeded sampling reproduces token for
        # token, and every decode fetch is the batch's logits rows, made
        # with nothing in flight (a sampled row is the host's to choose)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert m["decode_fetch_bytes"] == \
            m["host_syncs"] * 4 * cfg.vocab_size * 4
        assert m["decode_steps_ahead"] == 0
        assert set(m["decode_steps_sync_by_reason"]) <= {"idle", "sampled"}


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
