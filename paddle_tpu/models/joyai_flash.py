"""JoyAI-LLM-Flash — latent attention, a shared expert beside routed ones.

The decoder of ``jdopensource/JoyAI-LLM-Flash`` as its ``config.json``
states it (``model_type`` ``joyai_llm_flash``, of DeepSeek-V3's family), for
the serving path (``LLMEngine`` calls ``serve_layer`` once a layer;
``inference/serving/paged_attention.py`` documents the state handle):

* pre-norm residual blocks, RMSNorm, SwiGLU, untied head, no bias;
* latent attention (MLA). Queries: ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``
  wide), ``q = c_q W_qb``: ``num_attention_heads`` heads, each ``[q_nope |
  q_rope]``. What a token caches: ``x W_kva -> [c | k_r]`` (``kv_lora_rank``
  and ``qk_rope_head_dim`` wide), ``c <- RMSNorm(c)``, ``k_r <- RoPE(k_r)``:
  ONE row, shared by all heads, and nothing else. ``W_kvb`` expands ``c`` to
  every head's ``[k_nope | v]``; a head's key is ``[k_nope | k_r]``, scores
  ``q.k / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, the
  heads' outputs (``v_head_dim`` each) through ``W_o``. The state handle's
  latent entry (``attend_latent``) attends absorbed in a decode step and
  expanded in a prefill chunk: the same numbers up to rounding;
* rotary embedding on the rope dims, pairs ``(2i, 2i + 1)``
  (``rope_interleave``), base ``rope_theta``, no scaling. Here the rope dims
  of ``q_rope`` and ``k_r`` are put even-first (``[0, 2, .. | 1, 3, ..]``)
  and rotated as half-split pairs: the same rotation of the same pairs, and
  a fixed permutation applied alike to both leaves every score as it is. The
  cached ``k_r`` lies in that order;
* feed-forward: the first ``first_k_dense_replace`` layers dense at
  ``intermediate_size``; the others ``moe_dropless`` (``models/mimo_v2.py``:
  sigmoid scores in float32, chosen by ``score + e_score_correction_bias``,
  weighed by the uncorrected scores over their sum, times
  ``routed_scaling_factor``; no token dropped; ``experts_held`` as there)
  plus ``n_shared_experts`` shared SwiGLU experts every token takes, weight
  1, which every chip computes alike;
* one multi-token-prediction module (``num_nextn_predict_layers``,
  DeepSeek-V3's): ``mtp_logits``, a plain full-sequence function outside the
  engine.
"""

from __future__ import annotations

import dataclasses
import math

from ..core.tensor import Tensor
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from .llama import _rope_cache, rope_rotate
from .mimo_v2 import SERVE_COUNTERS as _MOE_COUNTERS
from .mimo_v2 import MiMoV2MLP as _MLP
from .mimo_v2 import MiMoV2ForCausalLM as _MiMoV2
from .mimo_v2 import MiMoV2MoE as _MoE
from .mimo_v2 import _store_width

__all__ = ["JoyAIFlashConfig", "JoyAIFlashForCausalLM", "joyai_flash_tiny"]

#: device-side counters ``serve_layer`` adds to, per call
SERVE_COUNTERS = ("mla_latent_tokens_read", "mla_context_tokens_expanded") \
    + _MOE_COUNTERS


@dataclasses.dataclass
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256           # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = 2.5
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    #: global ids of the experts held here; None holds them all
    experts_held: tuple | None = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = tuple(range(self.n_routed_experts))
        self.experts_held = tuple(int(e) for e in self.experts_held)
        if self.tie_word_embeddings:
            raise ValueError("JoyAI-LLM-Flash's head is untied")
        if self.rope_scaling is not None or not self.rope_interleave:
            raise ValueError("models/joyai_flash.py rotates pairs (2i, 2i+1) "
                             "with no scaling, as the config states")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one prediction module, or none")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _even_first(x):
    """The last axis' even places, then its odd ones: pairs ``(2i, 2i + 1)``
    become the half-split pairs ``(i, i + D/2)`` that ``rope_rotate`` turns."""
    import jax.numpy as jnp

    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)


class JoyAIFlashAttention(Layer):
    def __init__(self, config: JoyAIFlashConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        h, heads = c.hidden_size, c.num_attention_heads
        self.q_a_proj = Linear(h, c.q_lora_rank, weight_attr=init,
                               bias_attr=False)
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = Linear(c.q_lora_rank, heads * c.qk_head_dim,
                               weight_attr=init, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(
            h, c.kv_lora_rank + c.qk_rope_head_dim, weight_attr=init,
            bias_attr=False)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = Linear(
            c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim),
            weight_attr=init, bias_attr=False)
        self.o_proj = Linear(heads * c.v_head_dim, h, weight_attr=init,
                             bias_attr=False)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        k_dim = self.config.kv_lora_rank + self.config.qk_rope_head_dim
        return KVLayerSpec("latent", 1, k_dim, self.config.kv_lora_rank,
                           _store_width(k_dim), prefill="linear")

    def _project(self, h, rope):
        """``h`` [B, S, hidden] normed -> ``(q_nope [B, S, H, dn], q_rope
        [B, S, H, dr], row [B, S, kv_lora_rank + dr])``, rotated by
        ``rope(x [B, S, heads, dr])``: all of the block before attention."""
        import jax
        import jax.numpy as jnp

        c = self.config
        b, s = h.shape[0], h.shape[1]
        # the barrier keeps XLA from folding the split into heads into the
        # projection (models/mimo_v2.py: 192 is no multiple of the lanes)
        q = jax.lax.optimization_barrier(
            self.q_b_proj(self.q_a_layernorm(self.q_a_proj(h)))._data)
        q = q.reshape(b, s, c.num_attention_heads, c.qk_head_dim)
        q_nope, q_rope = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
        kv = self.kv_a_proj_with_mqa(h)._data
        lat = self.kv_a_layernorm(
            Tensor._wrap(kv[..., :c.kv_lora_rank]))._data
        k_r = kv[..., c.kv_lora_rank:]
        q_rope = rope(_even_first(q_rope))
        k_r = rope(_even_first(k_r)[:, :, None, :])[:, :, 0]
        return q_nope, q_rope, jnp.concatenate([lat, k_r.astype(lat.dtype)], -1)

    def serve(self, h, state, cos_t, sin_t):
        """``h`` [B, S, hidden] normed input -> the block's output before
        the residual; ``state`` writes the token's row and attends."""
        q_nope, q_rope, row = self._project(
            h, lambda x: state.rope(x, cos_t, sin_t))
        out = state.attend_latent(
            q_nope, q_rope, row, self.kv_b_proj.weight._data,
            1.0 / math.sqrt(self.config.qk_head_dim))
        return self.o_proj(Tensor._wrap(
            out.reshape(h.shape[0], h.shape[1], -1)))

    def forward(self, h, cos_t, sin_t):
        """The whole sequence at once, expanded, causal, no cache: the
        prediction module's path (and a plain forward's)."""
        import jax
        import jax.numpy as jnp

        c = self.config
        b, s = h.shape[0], h.shape[1]
        cos, sin = (t[:s][None, :, None, :] for t in (cos_t, sin_t))
        q_nope, q_rope, row = self._project(
            h, lambda x: rope_rotate(x, cos, sin))
        kv = jnp.dot(row[..., :c.kv_lora_rank], self.kv_b_proj.weight._data)
        kv = kv.reshape(b, s, c.num_attention_heads, -1)
        k_r = row[:, :, None, c.kv_lora_rank:]
        z = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :c.qk_nope_head_dim])
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r[:, :, 0])
             ).astype(jnp.float32) / math.sqrt(c.qk_head_dim)
        t = jnp.arange(s)
        z = jnp.where(t[None, :] <= t[:, None], z, -jnp.inf)
        p = jax.nn.softmax(z, -1).astype(kv.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., c.qk_nope_head_dim:])
        return self.o_proj(Tensor._wrap(out.reshape(b, s, -1)))


class JoyAIFlashDecoderLayer(Layer):
    """One block; ``is_moe`` chooses the dense feed-forward or the routed
    experts (held here) beside the shared ones."""

    def __init__(self, config: JoyAIFlashConfig, is_moe: bool):
        super().__init__()
        eps = config.rms_norm_eps
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = JoyAIFlashAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.is_moe = is_moe
        if is_moe:
            self.mlp = _MoE(config)
            self.shared_experts = _MLP(
                config, config.moe_intermediate_size * config.n_shared_experts)
        else:
            self.mlp = _MLP(config)

    def feed_forward(self, x, count=None):
        """``x + ffn(norm(x))``; ``count(name, value)`` takes the expert
        block's counters."""
        h = self.post_attention_layernorm(x)
        if not self.is_moe:
            return x + self.mlp(h)
        shape = h.shape
        y, pairs, hit, passes = self.mlp.forward_arrays(
            h._data.reshape(-1, shape[-1]))
        if count is not None:
            count("moe_pairs_routed_here", pairs)
            count("moe_experts_hit", hit)
            count("moe_layer_steps", 1)
            count("moe_weight_passes", passes)
        return x + Tensor._wrap(y.reshape(shape)) + self.shared_experts(h)

    def forward(self, x, cos_t, sin_t):
        x = x + self.self_attn(self.input_layernorm(x), cos_t, sin_t)
        return self.feed_forward(x)


class JoyAIFlashMTP(Layer):
    """The prediction module: the next token's embedding and the trunk's
    last hidden state, each normed, joined and projected back to the hidden
    width; one more block of the expert kind; a norm of its own. The
    embedding table and the head are the model's."""

    def __init__(self, config: JoyAIFlashConfig):
        super().__init__()
        d, eps = config.hidden_size, config.rms_norm_eps
        self.enorm = RMSNorm(d, eps)
        self.hnorm = RMSNorm(d, eps)
        self.eh_proj = Linear(2 * d, d, weight_attr=Normal(0.0, 0.02),
                              bias_attr=False)
        self.block = JoyAIFlashDecoderLayer(config, is_moe=True)
        self.norm = RMSNorm(d, eps)


class JoyAIFlashModel(Layer):
    def __init__(self, config: JoyAIFlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([
            JoyAIFlashDecoderLayer(config, i >= config.first_k_dense_replace)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mtp = (JoyAIFlashMTP(config)
                    if config.num_nextn_predict_layers else None)
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.qk_rope_head_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)


class JoyAIFlashForCausalLM(Layer):
    #: device-side counters ``serve_layer`` adds to (``state.count``)
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: JoyAIFlashConfig):
        super().__init__()
        self.config = config
        self.model = JoyAIFlashModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0, 0.02), bias_attr=False)

    def _cast_params(self, dtype, only_float=True):
        """The routers stay float32, as published."""
        keep = [(p, p._data) for n, p in self.named_parameters()
                if ".router." in n]
        super()._cast_params(dtype, only_float)
        for p, data in keep:
            p._rebind(data)

    _unique_params = _MiMoV2._unique_params

    def _rope(self):
        return self.model.rope_cos._data, self.model.rope_sin._data

    # -- the whole sequence at once (no cache, no engine) ------------------
    def forward(self, ids):
        """``ids`` [B, S] -> ``(logits [B, S, V], hidden [B, S, D])``;
        ``hidden`` is the last layer's output before the final norm, which
        is what ``mtp_logits`` continues from."""
        x = self.serve_embed(getattr(ids, "_data", ids))
        for layer in self.model.layers:
            x = layer(x, *self._rope())
        return self.lm_head(self.model.norm(x)), x

    def mtp_logits(self, hidden, next_ids):
        """The prediction module over a whole sequence: ``hidden`` [B, S, D]
        (position t: the trunk's state after reading tokens ``0..t``),
        ``next_ids`` [B, S] (position t: token ``t + 1``). Returns logits
        [B, S, V] for token ``t + 2``."""
        import jax.numpy as jnp

        m = self.model.mtp
        if m is None:
            raise ValueError("this configuration has no prediction module")
        u = m.enorm(self.serve_embed(getattr(next_ids, "_data", next_ids)))
        hidden = Tensor._wrap(getattr(hidden, "_data", hidden))
        x = m.eh_proj(Tensor._wrap(jnp.concatenate(
            [u._data, m.hnorm(hidden)._data], -1)))
        return self.lm_head(m.norm(m.block(x, *self._rope())))

    # -- the serving path (LLMEngine) ------------------------------------
    def kv_layout(self):
        return [layer.self_attn.kv_spec() for layer in self.model.layers]

    def serve_dtype(self):
        return self.model.layers[0].self_attn.o_proj.weight.dtype

    def serve_embed(self, ids):
        return self.model.embed_tokens(Tensor._wrap(ids))

    def serve_layer(self, i, x, state):
        layer = self.model.layers[i]
        x = x + layer.self_attn.serve(layer.input_layernorm(x), state,
                                      *self._rope())
        return layer.feed_forward(x, state.count)

    def serve_norm(self, x):
        return self.model.norm(x)

    def serve_head(self, h):
        return self.lm_head(h)


def joyai_flash_tiny(**kw):
    """The structure at toy widths, for the CPU tests: a latent row of 32 +
    8 (stored 48 wide) under 4 heads of 16 + 8 / 16, a dense layer then two
    expert layers, 32 experts, 4 a token, one shared, the prediction
    module."""
    base = dict(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, n_routed_experts=32,
        num_experts_per_tok=4, max_position_embeddings=256)
    base.update(kw)
    return JoyAIFlashConfig(**base)
