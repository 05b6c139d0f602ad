"""LFM2-MoE — gated short convolutions beside attention, sigmoid-routed experts.

The decoder of ``LiquidAI/LFM2-8B-A1B`` as its ``config.json`` states it
(``model_type`` ``lfm2_moe``), for the serving path (``LLMEngine`` calls
``serve_layer`` once a layer; ``inference/serving/paged_attention.py``
documents the state handle):

* layer ``i``: ``h = x + mixer_i(N(x))``, ``x' = h + ffn_i(N(h))``; after the
  last layer ``N`` again (the published ``embedding_norm``) and the head
  TIED to the embedding, ``logits = x E^T``. ``N(x) = x / rms(x) * w`` in
  float32, eps ``norm_eps``, ``w`` from 1. Mixer ``i`` is attention where
  ``layer_types[i] == "full_attention"``, the short convolution otherwise;
  ``ffn_i`` is the dense SwiGLU at ``intermediate_size`` for ``i <
  num_dense_layers``, the experts otherwise.
* **gated short convolution** (``conv_L_cache`` taps, no bias anywhere):
  ``[B | C | x~] = u W_in`` (hidden -> 3 x hidden, split in that order),
  ``v_t = B_t x~_t``, ``z_t = sum_j w[:, j] v_{t - K + 1 + j}`` (causal,
  depthwise, zeros before the sequence, NO activation), ``y_t = C_t z_t``,
  ``out = y W_out``. A request carries the last ``K - 1`` rows of ``v``: a
  state kind with a tail and no recurrent state (``state_dim == 0``), kept
  by ``state.shortconv``.
* **attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` kv heads, head ``hidden / heads`` (64); ``q`` and
  ``k`` RMS-normed a head (``q_layernorm``, ``k_layernorm``), then rotary over
  the whole head (rotate-half, ``rope_theta``); causal softmax at
  ``head^-1/2``; query heads ``g j .. g j + g - 1`` read kv head ``j``. Global
  pages, K and V 64 wide, held two kv heads a lane row
  (``KVLayerSpec.heads_a_lane_row``): no lane padding.
* **experts**: ``moe_dropless`` (``models/mimo_v2.py``) with ``sigmoid``
  scores in float32, the ``num_experts_per_tok`` largest of ``score +
  expert_bias`` chosen and weighed by the uncorrected score, over their sum
  (``norm_topk_prob``), times ``routed_scaling_factor``; SwiGLU experts at
  ``moe_intermediate_size``, no shared expert. DEPARTURE: the published code
  divides by ``sum + 1e-6``; ``moe_dropless`` by the sum (four sigmoid
  scores sum to at least a few tenths, so the two differ by a few parts in a
  million, under bfloat16's rounding). ``experts_held`` lists the experts
  held here; every one of them by default.
* ``expert_bias`` is learned and published; drawn here from the seed, small
  (``Normal(0, 0.01)``, as MiMo's correction bias), so that the load stays
  near even.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from ..core.tensor import Tensor
from ..nn.initializer import Normal, Uniform
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from .llama import _rope_cache
from .mimo_v2 import SERVE_COUNTERS as _MOE_COUNTERS
from .mimo_v2 import MiMoV2ForCausalLM as _MiMoV2
from .mimo_v2 import MiMoV2MLP as _MLP
from .mimo_v2 import moe_dropless, sigmoid_scores
from .qwen3_next import _WholeSequence as _QwenWholeSequence

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny"]

#: device-side counters ``serve_layer`` adds to, per call (the last three
#: are the state handle's: the tails a step renews and the tokens it
#: convolves, and the live tokens the head-64 decode kernel walks)
SERVE_COUNTERS = _MOE_COUNTERS + ("conv_tail_rows_updated",
                                  "conv_tokens_convolved", "attn_tokens_read")
#: rows ``serve_layer`` hands the host beside the logits (``state.keep``):
#: the experts each token chose, an expert layer after another
SERVE_KEEPS = ("moe_choice",)


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    #: global ids of the experts held here; None holds them all
    experts_held: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)[:n]
        if len(self.layer_types) != n or not set(self.layer_types) <= {
                "conv", "full_attention"}:
            raise ValueError(
                f"layer_types needs an entry a layer ({n}), each 'conv' or "
                "'full_attention'")
        if self.experts_held is None:
            self.experts_held = tuple(range(self.num_experts))
        self.experts_held = tuple(int(e) for e in self.experts_held)
        if self.conv_bias or not self.tie_word_embeddings \
                or not self.use_expert_bias:
            raise ValueError(
                "LFM2-MoE as published: no convolution bias, the head tied to "
                "the embedding, an expert bias in the choice")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, i):
        return self.layer_types[i] == "full_attention"


class Lfm2ShortConv(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        d, k = c.hidden_size, c.conv_L_cache
        self.in_proj = Linear(d, 3 * d, weight_attr=init, bias_attr=False)
        # a depthwise convolution's default: uniform in +-1 / sqrt(kernel)
        bound = 1.0 / math.sqrt(k)
        self.conv_weight = self.create_parameter(
            [d, k], default_initializer=Uniform(-bound, bound))
        self.out_proj = Linear(d, d, weight_attr=init, bias_attr=False)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("state", k_dim=c.hidden_size,
                           conv_rows=c.conv_L_cache - 1, state_dim=0)

    def serve(self, u, state):
        """``u`` [B, S, hidden] normed input -> the mixer's output; ``state``
        convolves and holds the tail."""
        d = self.config.hidden_size
        proj = self.in_proj(u)._data
        b, c, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
        z = state.shortconv(b * x, self.conv_weight._data)
        return self.out_proj(Tensor._wrap(c * z))


class Lfm2Attention(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        h, d = c.hidden_size, c.head_dim
        self.q_proj = Linear(h, c.num_attention_heads * d, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(h, c.num_key_value_heads * d, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(h, c.num_key_value_heads * d, weight_attr=init,
                             bias_attr=False)
        self.out_proj = Linear(c.num_attention_heads * d, h, weight_attr=init,
                               bias_attr=False)
        self.q_layernorm = RMSNorm(d, c.norm_eps)
        self.k_layernorm = RMSNorm(d, c.norm_eps)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("global", c.num_key_value_heads, c.head_dim,
                           c.head_dim, prefill="linear")

    def serve(self, u, state, cos_t, sin_t):
        c = self.config
        b, s, d = u.shape[0], u.shape[1], c.head_dim
        q = self.q_layernorm(self.q_proj(u).reshape(
            [b, s, c.num_attention_heads, d]))._data
        k = self.k_layernorm(self.k_proj(u).reshape(
            [b, s, c.num_key_value_heads, d]))._data
        v = self.v_proj(u)._data.reshape(b, s, c.num_key_value_heads, d)
        out = state.attend(state.rope(q, cos_t, sin_t),
                           state.rope(k, cos_t, sin_t), v,
                           scale=1.0 / math.sqrt(d))
        return self.out_proj(Tensor._wrap(out.reshape(b, s, -1)))


class Lfm2Router(Layer):
    """The router's matrix and the expert bias, float32 whatever the model
    is cast to."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.num_experts], dtype="float32",
            default_initializer=Normal(0.0, 0.02))
        self.expert_bias = self.create_parameter(
            [config.num_experts], dtype="float32",
            default_initializer=Normal(0.0, 0.01))


class Lfm2MoE(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.router = Lfm2Router(config)
        # the held experts, in the order of ``experts_held``
        self.experts = LayerList([_MLP(config, config.moe_intermediate_size)
                                  for _ in config.experts_held])
        slot = np.full(config.num_experts, len(config.experts_held), np.int32)
        slot[list(config.experts_held)] = np.arange(len(config.experts_held))
        self._held_slot = slot

    def forward_arrays(self, x, tm=None):
        """``x`` [T, D] array -> (the held experts' output, routed pairs,
        experts hit, weight passes, the choice)."""
        c = self.config
        return moe_dropless(
            x, self.router.weight._data, self.router.expert_bias._data,
            [(e.gate_proj.weight._data, e.up_proj.weight._data,
              e.down_proj.weight._data) for e in self.experts],
            self._held_slot, top_k=c.num_experts_per_tok,
            norm_topk=c.norm_topk_prob, scaling=c.routed_scaling_factor,
            tm=tm, with_passes=True, with_choice=True, score=sigmoid_scores)

    def serve(self, h, state):
        shape = h.shape
        y, pairs, hit, passes, choice = self.forward_arrays(
            h._data.reshape(-1, shape[-1]))
        state.keep("moe_choice", choice)
        state.count("moe_pairs_routed_here", pairs)
        state.count("moe_experts_hit", hit)
        state.count("moe_layer_steps", 1)
        state.count("moe_weight_passes", passes)
        return Tensor._wrap(y.reshape(shape))


class Lfm2DecoderLayer(Layer):
    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        eps = config.norm_eps
        self.attention = config.is_attention(layer_idx)
        self.operator_norm = RMSNorm(config.hidden_size, eps)
        if self.attention:
            self.self_attn = Lfm2Attention(config)
        else:
            self.conv = Lfm2ShortConv(config)
        self.ffn_norm = RMSNorm(config.hidden_size, eps)
        self.dense = layer_idx < config.num_dense_layers
        self.feed_forward = (_MLP(config) if self.dense
                             else Lfm2MoE(config))

    def kv_spec(self):
        return (self.self_attn if self.attention else self.conv).kv_spec()


class Lfm2MoeModel(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([Lfm2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.embedding_norm = RMSNorm(config.hidden_size, config.norm_eps)
        # [max_pos, head_dim / 2]
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.head_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)


class _WholeSequence(_QwenWholeSequence):
    """The state handle of the plain forward: whole sequences, no cache
    (``qwen3_next._WholeSequence``'s rotary, attention and counters). A
    short convolution starts from zeros before the sequence."""

    def shortconv(self, bx, conv_w):
        import jax.numpy as jnp

        from ..inference.serving.paged_attention import _conv

        s, k = bx.shape[1], conv_w.shape[1]
        rows = jnp.pad(bx, [(0, 0), (k - 1, 0), (0, 0)])
        return _conv([rows[:, j:j + s] for j in range(k)],
                     conv_w).astype(bx.dtype)


class Lfm2MoeForCausalLM(Layer):
    #: device-side counters ``serve_layer`` adds to (``state.count``)
    serve_counters = SERVE_COUNTERS
    #: rows ``serve_layer`` keeps for the host (``state.keep``)
    serve_keeps = SERVE_KEEPS

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)

    def _cast_params(self, dtype, only_float=True):
        """The router and the expert bias stay float32, as published."""
        keep = [(p, p._data) for n, p in self.named_parameters()
                if ".router." in n]
        super()._cast_params(dtype, only_float)
        for p, data in keep:
            p._rebind(data)

    _unique_params = _MiMoV2._unique_params

    # -- the whole sequence at once (no cache, no engine) ------------------
    def forward(self, ids):
        """``ids`` [B, S] -> logits [B, S, V]: every layer over the whole
        sequence, a convolution from zeros."""
        state = _WholeSequence()
        x = self.serve_embed(getattr(ids, "_data", ids))
        for i, layer in enumerate(self.model.layers):
            state.spec = layer.kv_spec()
            x = self.serve_layer(i, x, state)
        return self.serve_head(self.serve_norm(x))

    # -- the serving path (LLMEngine) ------------------------------------
    def kv_layout(self):
        return [layer.kv_spec() for layer in self.model.layers]

    def serve_dtype(self):
        return self.model.embed_tokens.weight.dtype

    def serve_embed(self, ids):
        return self.model.embed_tokens(Tensor._wrap(ids))

    def serve_layer(self, i, x, state):
        """One layer: its mixer and its feed-forward block, a residual
        each."""
        layer, m = self.model.layers[i], self.model
        u = layer.operator_norm(x)
        if layer.attention:
            x = x + layer.self_attn.serve(u, state, m.rope_cos._data,
                                          m.rope_sin._data)
        else:
            x = x + layer.conv.serve(u, state)
        f = layer.ffn_norm(x)
        return x + (layer.feed_forward(f) if layer.dense
                    else layer.feed_forward.serve(f, state))

    def serve_norm(self, x):
        return self.model.embedding_norm(x)

    def serve_head(self, h):
        """The head tied to the embedding: ``h E^T``."""
        import jax.numpy as jnp

        e = self.model.embed_tokens.weight._data
        return Tensor._wrap(jnp.einsum("...d,vd->...v",
                                       getattr(h, "_data", h), e))


def lfm2_moe_tiny(**kw):
    """The structure at toy widths, for the CPU tests: a dense conv layer,
    then ``conv full_attention conv conv`` with experts; hidden 256, 4 query
    heads over 2 kv heads of 64 (the published head width, kept so that the
    pages take the two-a-lane-row form), 8 experts 32 wide, 2 a token."""
    base = dict(
        vocab_size=160, hidden_size=256, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        layer_types=("conv", "conv", "full_attention", "conv", "conv"),
        num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2, rope_theta=1e4,
        max_position_embeddings=256)
    base.update(kw)
    return Lfm2MoeConfig(**base)
