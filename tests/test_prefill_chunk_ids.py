"""A prefill chunk's ids are cut out of the staged prompt with the chunk's
offset as an operand: one executable a (staging bucket, rung), whatever the
offsets, so a request whose chunks start where no earlier request's did
compiles nothing new."""

import numpy as np

import paddle_tpu
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.models import LlamaForCausalLM, llama_tiny


def test_chunks_at_every_offset_share_one_slice_executable():
    paddle_tpu.seed(5)
    net = LlamaForCausalLM(llama_tiny())
    net.eval()
    rng = np.random.default_rng(5)
    vocab = net.config.vocab_size
    with LLMEngine(net, num_blocks=64, block_size=8, max_batch_size=4,
                   max_model_len=64, prefill_buckets=[8, 16, 32],
                   max_prefill_tokens_per_step=8,
                   ingest_async=False) as eng:
        # staged at 32 both, chunks of 8 at offsets 0, 8, 16 and then 24
        for n in (20, 30):
            eng.add_request(rng.integers(1, vocab, n).astype(np.int32),
                            SamplingParams(max_new_tokens=2))
            while eng.has_work():
                eng.step()
        assert eng.metrics()["prefill_chunks"] == 3 + 4
        assert eng._chunk_ids._cache_size() == 1
