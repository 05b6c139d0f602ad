"""Qwen3-Next — gated delta layers, gated attention and experts by a period.

The decoder of ``Qwen/Qwen3-Next-80B-A3B-Instruct`` as its ``config.json``
states it (``model_type`` ``qwen3_next``), for the serving path
(``LLMEngine`` calls ``serve_layer`` once a layer;
``inference/serving/paged_attention.py`` documents the state handle):

* layer ``i``: ``x <- x + mixer_i(N(x))``, ``x <- x + moe(N(x))``; then ``N``
  and an untied head. ``N(x) = x / rms(x) * (1 + w)`` in float32
  (ZERO-CENTRED: ``w = 0`` is the identity scale), eps ``rms_norm_eps``. Mixer
  ``i`` is full attention where ``(i + 1) % full_attention_interval == 0``,
  else the gated delta mixer: ``L L L F`` a period.
* **gated delta mixer**. With ``u`` the normed input: ``[q | k | v | z] = u
  W_qkvz``, ``[b | a] = u W_ba``. THE ORDER OF THE COLUMNS IS THIS FILE'S:
  ``q`` (key heads x key dim, head-major), ``k`` likewise, ``v`` (value
  heads x value dim), ``z`` likewise, then ``b`` and ``a`` a value head (the
  published checkpoint interleaves them a key head; weights here are drawn
  from a seed, and the benchmark's reference reads the same order). ``[q | k
  | v]`` pass a causal depthwise convolution of ``linear_conv_kernel_dim``
  taps, NO bias, then ``silu``; zeros before the sequence. ``q`` and ``k``
  are L2-normed a head and ``q`` scaled by ``key_dim^-1/2``; key head ``j``
  serves value heads ``j * r .. j * r + r - 1`` (``r`` = value heads / key
  heads). ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` a
  value head, float32. ``S' = exp(g_t) S_{t-1}``; ``d_t = beta_t (v_t - S'^T
  k_t)``; ``S_t = S' + k_t (x) d_t``; ``o_t = S_t^T q_t``, ``S [key_dim,
  value_dim]`` float32 from zero. Then ``y = rms(o) w_n silu(z)`` a head
  (``w_n`` PLAIN, not ``1 + w``), ``out = y W_o``. The convolution's tail and
  ``S`` are what a request carries: the state handle's ``delta`` keeps them
  (kind ``"state"``) and norms q and k (``ops/pallas/gated_delta.py``).
* **gated attention**. ``q_proj`` gives a head ``2 x head_dim``: its first
  half the query, its last the gate. ``q, k <- N(q), N(k)`` over ``head_dim``
  (zero-centred, a weight each); rotary, half-rotation, on the first
  ``partial_rotary_factor x head_dim`` of a head; causal softmax at
  ``head_dim^-1/2``; ``attn <- attn * sigmoid(gate)``; ``o_proj``. Global
  pages, K and V ``head_dim`` wide.
* **experts**, every layer: ``moe_dropless`` (``models/mimo_v2.py``) with THIS
  model's score function, ``softmax`` over all ``num_experts`` in float32,
  the ``num_experts_per_tok`` largest, their ``p`` over their sum
  (``norm_topk_prob``), no bias, no scaling; SwiGLU experts; plus
  ``sigmoid(x w_g) * SwiGLU_shared(x)``. ``experts_held`` as there.
* the multi-token-prediction module the model card names has no key in the
  config and is left out.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal, Uniform
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .llama import _rope_cache
from .mimo_v2 import SERVE_COUNTERS as _MOE_COUNTERS
from .mimo_v2 import MiMoV2ForCausalLM as _MiMoV2
from .mimo_v2 import MiMoV2MLP as _MLP
from .mimo_v2 import _store_width, moe_dropless, softmax_scores
from .nemotron_h import _WholeSequence as _NemotronWholeSequence
from .nemotron_h import inverse_softplus_steps

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM", "qwen3_next_tiny"]

#: device-side counters ``serve_layer`` adds to, per call
SERVE_COUNTERS = ("delta_state_rows_updated", "delta_tokens_scanned") \
    + _MOE_COUNTERS
#: rows ``serve_layer`` hands the host beside the logits (``state.keep``):
#: the experts each token chose, a layer after another (``nemotron_h.py``
#: says why a model that reads its context through a state hands them out)
SERVE_KEEPS = ("moe_choice",)


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    rms_norm_eps: float = 1e-6
    time_step_min: float = 0.001          # the family's initialisation of
    time_step_max: float = 0.1            # dt_bias; no key of the config
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512                # the router's width, as published
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: global ids of the experts held here; None holds them all
    experts_held: tuple | None = None

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = tuple(range(self.num_experts))
        self.experts_held = tuple(int(e) for e in self.experts_held)
        if self.tie_word_embeddings:
            raise ValueError("Qwen3-Next's head is untied")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("the value heads divide among the key heads")

    def is_full_attention(self, i):
        return (i + 1) % self.full_attention_interval == 0

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim


class Qwen3NextRMSNorm(Layer):
    """``x / rms(x) * (1 + w)`` in float32; ``w`` starts at 0."""

    def __init__(self, width, epsilon):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            [width], default_initializer=Constant(0.0))

    def forward(self, x):
        return Tensor._wrap(self.arrays(getattr(x, "_data", x)))

    def arrays(self, x):
        import jax
        import jax.numpy as jnp

        f = x.astype(jnp.float32)
        f = f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True)
                              + self._epsilon)
        return (f * (1.0 + self.weight._data.astype(jnp.float32))
                ).astype(x.dtype)


class Qwen3NextGatedDeltaNet(Layer):
    def __init__(self, config: Qwen3NextConfig):
        import jax.numpy as jnp

        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        heads, k = c.linear_num_value_heads, c.linear_conv_kernel_dim
        self.in_proj_qkvz = Linear(c.hidden_size, c.conv_dim + c.value_dim,
                                   weight_attr=init, bias_attr=False)
        self.in_proj_ba = Linear(c.hidden_size, 2 * heads, weight_attr=init,
                                 bias_attr=False)
        # a depthwise convolution's default: uniform in +-1 / sqrt(kernel)
        bound = 1.0 / math.sqrt(k)
        self.conv_weight = self.create_parameter(
            [c.conv_dim, k], default_initializer=Uniform(-bound, bound))
        # as the family's training code initialises them: A uniform in
        # (0, 16); the step sizes log-uniform in [time_step_min,
        # time_step_max], through the inverse of the softplus
        self.A_log = self.create_parameter(
            [heads], default_initializer=Uniform(0.0, 16.0))
        self.A_log._rebind(jnp.log(self.A_log._data))
        self.dt_bias = self.create_parameter(
            [heads], default_initializer=Uniform(0.0, 1.0))
        self.dt_bias._rebind(inverse_softplus_steps(
            self.dt_bias._data, c.time_step_min, c.time_step_max, 1e-4))
        self.norm_weight = self.create_parameter(
            [c.linear_value_head_dim], default_initializer=Constant(1.0))
        self.out_proj = Linear(c.value_dim, c.hidden_size, weight_attr=init,
                               bias_attr=False)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("state", c.linear_num_value_heads, c.conv_dim,
                           c.linear_value_head_dim,
                           conv_rows=c.linear_conv_kernel_dim - 1,
                           state_dim=c.linear_key_head_dim)

    def serve(self, u, state):
        """``u`` [B, S, hidden] normed input -> the mixer's output; ``state``
        convolves, holds the tail and runs the recurrence."""
        import jax
        import jax.numpy as jnp

        c = self.config
        f32 = jnp.float32
        heads, p = c.linear_num_value_heads, c.linear_value_head_dim
        proj = self.in_proj_qkvz(u)._data
        qkv, z = proj[..., :c.conv_dim], proj[..., c.conv_dim:]
        ba = self.in_proj_ba(u)._data.astype(f32)
        beta = jax.nn.sigmoid(ba[..., :heads])
        g = -jnp.exp(self.A_log._data.astype(f32)) * jax.nn.softplus(
            ba[..., heads:] + self.dt_bias._data.astype(f32))
        o = state.delta(qkv, g, beta, self.conv_weight._data)    # float32
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + c.rms_norm_eps)
        y = o * self.norm_weight._data.astype(f32) * jax.nn.silu(
            z.astype(f32).reshape(*z.shape[:2], heads, p))
        return self.out_proj(Tensor._wrap(
            y.reshape(*y.shape[:2], heads * p).astype(proj.dtype)))


class Qwen3NextAttention(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        h, d = c.hidden_size, c.head_dim
        self.q_proj = Linear(h, c.num_attention_heads * 2 * d,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, c.num_key_value_heads * d, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(h, c.num_key_value_heads * d, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(c.num_attention_heads * d, h, weight_attr=init,
                             bias_attr=False)
        self.q_norm = Qwen3NextRMSNorm(d, c.rms_norm_eps)
        self.k_norm = Qwen3NextRMSNorm(d, c.rms_norm_eps)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("global", c.num_key_value_heads, c.head_dim,
                           c.head_dim, _store_width(c.head_dim),
                           prefill="linear")

    def serve(self, u, state, cos_t, sin_t):
        import jax
        import jax.numpy as jnp

        c = self.config
        b, s, d = u.shape[0], u.shape[1], c.head_dim
        qg = self.q_proj(u)._data.reshape(b, s, c.num_attention_heads, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = self.k_proj(u)._data.reshape(b, s, c.num_key_value_heads, d)
        v = self.v_proj(u)._data.reshape(b, s, c.num_key_value_heads, d)
        r = c.rotary_dim

        def rot(x):
            return jnp.concatenate(
                [state.rope(x[..., :r], cos_t, sin_t), x[..., r:]], -1)

        out = state.attend(rot(self.q_norm.arrays(q)),
                           rot(self.k_norm.arrays(k)), v,
                           scale=1.0 / math.sqrt(d))
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return self.o_proj(Tensor._wrap(out.reshape(b, s, -1)))


class Qwen3NextRouter(Layer):
    """The router's float32 matrix; it stays float32 whatever the model is
    cast to."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.num_experts], dtype="float32",
            default_initializer=Normal(0.0, 0.02))


class Qwen3NextMoE(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.router = Qwen3NextRouter(config)
        # the held experts, in the order of ``experts_held``
        self.experts = LayerList([_MLP(config, config.moe_intermediate_size)
                                  for _ in config.experts_held])
        self.shared_expert = _MLP(config,
                                  config.shared_expert_intermediate_size)
        self.shared_expert_gate = Linear(
            config.hidden_size, 1, weight_attr=Normal(0.0, 0.02),
            bias_attr=False)
        slot = np.full(config.num_experts, len(config.experts_held), np.int32)
        slot[list(config.experts_held)] = np.arange(len(config.experts_held))
        self._held_slot = slot

    def forward_arrays(self, x, tm=None):
        """``x`` [T, D] array -> (this chip's part of the routed experts'
        output, routed pairs, experts hit, weight passes, the choice)."""
        c = self.config
        return moe_dropless(
            x, self.router.weight._data, None,
            [(e.gate_proj.weight._data, e.up_proj.weight._data,
              e.down_proj.weight._data) for e in self.experts],
            self._held_slot, top_k=c.num_experts_per_tok,
            norm_topk=c.norm_topk_prob, tm=tm, with_passes=True,
            with_choice=True, score=softmax_scores)

    def serve(self, h, state):
        import jax
        import jax.numpy as jnp

        shape = h.shape
        y, pairs, hit, passes, choice = self.forward_arrays(
            h._data.reshape(-1, shape[-1]))
        state.keep("moe_choice", choice)
        state.count("moe_pairs_routed_here", pairs)
        state.count("moe_experts_hit", hit)
        state.count("moe_layer_steps", 1)
        state.count("moe_weight_passes", passes)
        shared = self.shared_expert(h)._data
        gate = jax.nn.sigmoid(
            self.shared_expert_gate(h)._data.astype(jnp.float32))
        return Tensor._wrap(y.reshape(shape)
                            + (shared * gate.astype(shared.dtype)))


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, config: Qwen3NextConfig, layer_idx: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.full_attention = config.is_full_attention(layer_idx)
        self.input_layernorm = Qwen3NextRMSNorm(config.hidden_size, eps)
        if self.full_attention:
            self.self_attn = Qwen3NextAttention(config)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(config)
        self.post_attention_layernorm = Qwen3NextRMSNorm(config.hidden_size,
                                                         eps)
        self.mlp = Qwen3NextMoE(config)

    def kv_spec(self):
        return (self.self_attn if self.full_attention
                else self.linear_attn).kv_spec()


class Qwen3NextModel(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([Qwen3NextDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = Qwen3NextRMSNorm(config.hidden_size, config.rms_norm_eps)
        # [max_pos, rotary_dim / 2]
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.rotary_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)


class _WholeSequence(_NemotronWholeSequence):
    """The state handle of the plain forward: whole sequences, no cache
    (``nemotron_h._WholeSequence``'s attention, counters and kept rows). A
    delta layer starts from a zero state and runs the sequence in the
    chunked form."""

    def rope(self, x, cos_t, sin_t):
        from .llama import _rope_apply_at

        return _rope_apply_at.raw_fn(x, cos_t, sin_t, 0)

    def delta(self, qkv, g, beta, conv_w):
        import jax
        import jax.numpy as jnp

        from ..inference.serving.paged_attention import _conv_and_split_qkv
        from ..ops.pallas.gated_delta import gated_delta_chunk

        spec, s = self.spec, qkv.shape[1]
        rows = jnp.pad(qkv, [(0, 0), (spec.conv_rows, 0), (0, 0)])
        q, k, v = _conv_and_split_qkv(
            spec, [rows[:, j:j + s] for j in range(spec.conv_rows + 1)],
            conv_w)
        s0 = jnp.zeros((spec.num_kv_heads, spec.state_dim, spec.v_dim))
        o, _ = jax.vmap(lambda q, k, v, g, beta: gated_delta_chunk(
            q, k, v, g, beta, s0))(q, k, v, g, beta)
        return o


class Qwen3NextForCausalLM(Layer):
    #: device-side counters ``serve_layer`` adds to (``state.count``)
    serve_counters = SERVE_COUNTERS
    #: rows ``serve_layer`` keeps for the host (``state.keep``)
    serve_keeps = SERVE_KEEPS

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0, 0.02), bias_attr=False)

    def _cast_params(self, dtype, only_float=True):
        """The routers and the decays' ``A_log`` and ``dt_bias`` stay
        float32, as published."""
        keep = [(p, p._data) for n, p in self.named_parameters()
                if ".router." in n or n.endswith((".A_log", ".dt_bias"))]
        super()._cast_params(dtype, only_float)
        for p, data in keep:
            p._rebind(data)

    _unique_params = _MiMoV2._unique_params

    # -- the whole sequence at once (no cache, no engine) ------------------
    def forward(self, ids):
        """``ids`` [B, S] -> logits [B, S, V]: every layer over the whole
        sequence, a delta layer from a zero state."""
        state = _WholeSequence()
        x = self.serve_embed(getattr(ids, "_data", ids))
        for i, layer in enumerate(self.model.layers):
            state.spec = layer.kv_spec()
            x = self.serve_layer(i, x, state)
        return self.lm_head(self.model.norm(x))

    # -- the serving path (LLMEngine) ------------------------------------
    def kv_layout(self):
        return [layer.kv_spec() for layer in self.model.layers]

    def serve_dtype(self):
        return self.lm_head.weight.dtype

    def serve_embed(self, ids):
        return self.model.embed_tokens(Tensor._wrap(ids))

    def serve_layer(self, i, x, state):
        """One layer: its mixer and its experts, a residual each."""
        layer, m = self.model.layers[i], self.model
        u = layer.input_layernorm(x)
        if layer.full_attention:
            x = x + layer.self_attn.serve(u, state, m.rope_cos._data,
                                          m.rope_sin._data)
        else:
            x = x + layer.linear_attn.serve(u, state)
        return x + layer.mlp.serve(layer.post_attention_layernorm(x), state)

    def serve_norm(self, x):
        return self.model.norm(x)

    def serve_head(self, h):
        return self.lm_head(h)


def qwen3_next_tiny(**kw):
    """The structure at toy widths, for the CPU tests: one period ``L L L
    F``, 4 value heads of 16 over 2 key heads of 16, 4 query heads over 2 kv
    heads of 32 (rotary on the first 8), 16 experts 32 wide, 4 a token, a
    shared expert 48 wide. (Every held expert is a branch of the CPU's tile
    loop in every layer: few layers and few experts keep the tests' compiles
    short.)"""
    base = dict(
        vocab_size=160, hidden_size=64, num_hidden_layers=4,
        full_attention_interval=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
        rope_theta=1e4, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, num_experts=16,
        num_experts_per_tok=4, max_position_embeddings=256)
    base.update(kw)
    return Qwen3NextConfig(**base)
