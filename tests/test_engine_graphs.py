"""The serving engine's graph builders (ISSUE 29): what their shared
preamble promises when a trace raises, and the fused draft catch-up against
a token-at-a-time replay written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny

ENGINES = {
    "llama": dict(num_blocks=64, block_size=8, max_batch_size=4,
                  max_prefills_per_step=4, ingest_async=False),
    "mimo": dict(num_blocks=96, block_size=4, max_batch_size=4,
                 max_model_len=96, prefill_buckets=[8, 16, 32, 64, 96],
                 max_prefills_per_step=4, ingest_async=False)}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def build(name, seed=7):
    paddle_tpu.seed(seed)
    net = (LlamaForCausalLM(llama_tiny()) if name == "llama"
           else MiMoV2ForCausalLM(mimo_v2_tiny()))
    net.eval()
    return net


def target_and_first_layer_draft():
    """A target and a one-layer draft that agrees with it often and not
    always: the target's embedding, first layer, norm and head, against a
    target whose second layer is turned down to a tenth."""
    net = build("llama")
    second = net.llama.layers[1]
    for lin in (second.self_attn.o_proj, second.mlp.down_proj):
        lin.weight._data = lin.weight._data * 0.1
    draft = LlamaForCausalLM(
        dataclasses.replace(net.config, num_hidden_layers=1))
    own = draft.state_dict()
    draft.set_state_dict({k: v for k, v in net.state_dict().items()
                          if k in own})
    draft.eval()
    return net, draft


def prompts_of(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


# --------------------------------------------------------------------------
# a trace that raises leaves the model as it was
# --------------------------------------------------------------------------

class Boom(Exception):
    pass


#: graph -> (what holds its executable, whose layers it traces)
GRAPHS = {"chunk": ("_prefill_jit", "model"),
          "decode": ("_decode_jit", "model"),
          "catchup": ("_catchup_jit", "draft_model"),
          "verify": ("_verify_jit", "model")}


@pytest.mark.parametrize("name,graph", [
    ("llama", "chunk"), ("llama", "decode"), ("mimo", "chunk"),
    ("mimo", "decode"), ("llama", "catchup"), ("llama", "verify")])
def test_a_trace_that_raises_leaves_no_tracer_in_the_model(name, graph):
    """The engine is run until it first calls ``graph``'s executable; from
    that call on the traced model's last layer raises, so the trace dies
    with every earlier layer's parameters read. Every parameter of both
    models then holds the array it held before, and none a tracer."""
    net = build(name)
    kw = dict(ENGINES[name])
    if graph in ("catchup", "verify"):
        # itself as its draft: everything is accepted, so the second
        # speculative step catches two tokens up through the fused loop
        kw.update(draft_model=net, spec_tokens=2)
    holder, whose = GRAPHS[graph]
    armed = []

    def arm_at(jit):
        def call(*args, **kwargs):
            armed.append(True)
            return jit(*args, **kwargs)
        return call

    with LLMEngine(net, **kw) as eng:
        eng._build_jits()
        if graph == "catchup":
            make = eng._catchup_jit
            eng._catchup_jit = lambda F: arm_at(make(F))
        else:
            setattr(eng, holder, arm_at(getattr(eng, holder)))
        traced = getattr(eng, whose)
        last = len(traced.kv_layout()) - 1
        if graph == "verify":
            # the verify step writes Llama's layer out: its last MLP raises
            mlp = traced.llama.layers[last].mlp
            real, target, attr = mlp.forward, mlp, "forward"
        else:
            real, target, attr = traced.serve_layer, traced, "serve_layer"

        def raising(*args, **kwargs):
            if armed and (graph == "verify" or args[0] == last):
                raise Boom(graph)
            return real(*args, **kwargs)

        setattr(target, attr, raising)
        params = net._unique_params()
        before = [p._data for p in params]
        try:
            for p in prompts_of((5, 11), net.config.vocab_size):
                eng.add_request(p, SamplingParams(max_new_tokens=9))
            with pytest.raises(Boom, match=graph):
                for _ in range(4):
                    eng.step()
        finally:
            delattr(target, attr)
        assert armed
        for p, was in zip(params, before):
            assert p._data is was
            assert not isinstance(p._data, jax.core.Tracer)


# --------------------------------------------------------------------------
# the fused catch-up against a replay a token at a time
# --------------------------------------------------------------------------

class Replayed(LLMEngine):
    """Before each speculative step's draft phase, plays that phase on
    copies of the draft pools a token at a time through the draft's decode
    executable (what the engine did before its catch-up was fused), then
    lets the engine run it and compares proposals and pools."""

    def _draft_propose(self, ready, tables):
        want, pools = self._replay(ready, tables)
        drafts = super()._draft_propose(ready, tables)
        rows = [i for i, _ in ready]
        np.testing.assert_array_equal(drafts[rows], want[rows])
        dc = self.draft_cache
        for got, ref in zip(dc.k + dc.v, pools[0] + pools[1]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        return drafts

    def _replay(self, ready, tables):
        B, K = self.max_batch_size, self._spec_k
        dc = self.draft_cache
        # copies: the executable is given its pools to keep
        k, v = ([jnp.array(x, copy=True) for x in pool]
                for pool in (dc.k, dc.v))
        params = [p._data for p in self._draft_params]

        def decode(ids, pos):
            nonlocal k, v
            logits, _, k, v, _, _ = self._draft_decode_jit(
                params, jnp.asarray(ids), jnp.asarray(pos), tables,
                k, v, [], [])
            return np.asarray(logits)

        feeds = {r.rid: list(range(min(r.draft_cached, r.num_tokens - 1),
                                   r.num_tokens)) for _, r in ready}
        F = max(len(fs) for fs in feeds.values())
        self.feed_lengths.append(sorted(len(fs) for fs in feeds.values()))
        for t in range(F):
            ids = np.zeros((B, 1), np.int32)
            pos = np.zeros(B, np.int32)
            for i, r in ready:
                fs = feeds[r.rid]
                j = ([fs[0]] * (F - len(fs)) + fs)[t]
                ids[i, 0], pos[i] = r.tokens[j], j
            logits = decode(ids, pos)
        drafts = np.zeros((B, K), np.int32)
        for step in range(K):
            for i, _ in ready:
                drafts[i, step] = int(logits[i].argmax())
            if step + 1 < K:
                ids = np.zeros((B, 1), np.int32)
                pos = np.zeros(B, np.int32)
                for i, r in ready:
                    ids[i, 0], pos[i] = drafts[i, step], r.num_tokens + step
                logits = decode(ids, pos)
        return drafts, (k, v)


@pytest.mark.parametrize("spec_tokens", [1, 2, 3, 4])
def test_the_fused_catchup_leaves_what_a_replay_leaves(spec_tokens):
    net, draft = target_and_first_layer_draft()
    ps = prompts_of((5, 11, 7, 14), net.config.vocab_size, seed=spec_tokens)
    sp = SamplingParams(max_new_tokens=14)
    with LLMEngine(net, **ENGINES["llama"]) as eng:
        plain = eng.generate(ps, sp)
    with Replayed(net, **ENGINES["llama"], draft_model=draft,
                  spec_tokens=spec_tokens) as eng:
        eng.feed_lengths = []
        got = eng.generate(ps, sp)
        m = eng.metrics()
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)
    # the draft diverges: some proposals are taken, some are not, so that
    # rows one token behind and rows two behind meet in one catch-up
    assert 0 < m["spec_accepted"] < m["spec_proposed"]
    assert any(ls[0] == 1 and ls[-1] == 2 for ls in eng.feed_lengths)
