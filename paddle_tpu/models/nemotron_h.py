"""Nemotron-H — state-space, attention and expert blocks by a pattern.

The decoder of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` as its
``config.json`` states it (``model_type`` ``nemotron_h``), for the serving
path (``LLMEngine`` calls ``serve_layer`` once a block;
``inference/serving/paged_attention.py`` documents the state handle):

* ``hybrid_override_pattern`` names a block a letter, and **a block is ONE
  mixer**: ``x <- x + mixer(RMSNorm(x))``; then a final RMSNorm and an untied
  head. No bias but the convolution's.
* ``M``, Mamba-2. With ``u`` the normed input: ``[z | xBC | dt] = u W_in``;
  ``xBC_t <- silu(sum_j w_conv[:, j] xBC_{t-K+1+j} + b_conv)`` (depthwise,
  causal, zeros before the sequence), cut into ``x`` (``mamba_num_heads`` x
  ``mamba_head_dim``), ``B`` and ``C`` (``n_groups`` x ``ssm_state_size``;
  head ``h`` reads group ``h // (heads / groups)``); ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  (x) B_t`` from ``h = 0``; ``y_t = h_t C_t + D x_t``; ``y <- RMSNorm(y *
  silu(z))`` over groups of ``inner / n_groups`` channels (the gate BEFORE
  the norm), times a weight; ``out = y W_out``. The convolution's tail and
  ``h`` are what a request carries: the state handle's ``scan`` keeps them
  (kind ``"state"``), the model hands it ``xBC`` and ``dt``.
* ``*``, attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` kv heads of ``head_dim``, causal softmax at ``1 /
  sqrt(head_dim)``, **no rotary embedding** (the family's modeling code
  applies none: positions come from the state-space blocks).
* ``E``, experts: ``moe_dropless`` (``models/mimo_v2.py``: sigmoid scores in
  float32, chosen by ``score + e_score_correction_bias``, weighed by the
  uncorrected scores over their sum, times ``routed_scaling_factor``; no
  token dropped; ``experts_held`` as there) over experts of TWO matrices,
  ``relu(h W_up)^2 W_down``, beside one shared expert of the same form every
  token takes at weight 1. Such a block caches nothing (kind ``"none"``).
  An expert's width is stored up to a multiple of 128 with zeros
  (``relu(0)^2 = 0``: exact), which is what the grouped kernel's blocks take.
* ``-``, a dense feed-forward, is refused by name: this model has none.
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
from ..nn.layer.norm import RMSNorm
from .mimo_v2 import SERVE_COUNTERS as _MOE_COUNTERS
from .mimo_v2 import MiMoV2ForCausalLM as _MiMoV2
from .mimo_v2 import MiMoV2Router as _Router
from .mimo_v2 import _store_width, moe_dropless, sigmoid_scores

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "nemotron_h_tiny"]

#: device-side counters ``serve_layer`` adds to, per call
SERVE_COUNTERS = ("ssm_state_rows_updated", "ssm_tokens_scanned") \
    + _MOE_COUNTERS
#: rows ``serve_layer`` hands the host beside the logits (``state.keep``):
#: the experts each token chose, an expert block after another. A row of this
#: model reads the tokens before it through a state, so whoever compares it
#: with another computation of the same weights has to know how THOSE tokens
#: were routed, where two scores all but tie
SERVE_KEEPS = ("moe_choice",)


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128           # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = 2.5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: global ids of the experts held here; None holds them all
    experts_held: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.hybrid_override_pattern = str(self.hybrid_override_pattern)[:n]
        if len(self.hybrid_override_pattern) != n:
            raise ValueError(
                f"hybrid_override_pattern needs a letter a block ({n})")
        for letter in self.hybrid_override_pattern:
            if letter == "-":
                raise ValueError(
                    "a '-' block (a dense feed-forward) is not computed by "
                    "models/nemotron_h.py: this model has none")
            if letter not in "ME*":
                raise ValueError(f"unknown block letter {letter!r}: "
                                 "M (Mamba-2), E (experts), * (attention)")
        if self.experts_held is None:
            self.experts_held = tuple(range(self.n_routed_experts))
        self.experts_held = tuple(int(e) for e in self.experts_held)
        if self.tie_word_embeddings:
            raise ValueError("Nemotron-H's head is untied")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert beside the routed ones")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("the Mamba-2 heads divide among n_groups")

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def _expert_store_width(f):
    """The width an expert's matrices are stored at: up to the 128 lanes the
    grouped kernel cuts its blocks by (16 at the toy widths of the CPU
    tests, so that they pad too)."""
    lane = 128 if f >= 128 else 16
    return -(-f // lane) * lane


class NemotronHMLP(Layer):
    """``relu(x W_up)^2 W_down``: an expert at ``moe_intermediate_size``
    (stored ``store`` wide, zeros past the published width), the shared one
    at its own width."""

    def __init__(self, config: NemotronHConfig, width, store=None):
        super().__init__()
        init = Normal(0.0, 0.02)
        d, store = config.hidden_size, store or width
        self.up_proj = Linear(d, store, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(store, d, weight_attr=init, bias_attr=False)
        if store != width:
            up, down = self.up_proj.weight, self.down_proj.weight
            up._rebind(up._data.at[:, width:].set(0))
            down._rebind(down._data.at[width:].set(0))

    def forward(self, x):
        import jax
        import jax.numpy as jnp

        return self.down_proj(Tensor._wrap(
            jnp.square(jax.nn.relu(self.up_proj(x)._data))))


class NemotronHMoE(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.router = _Router(config)
        f = config.moe_intermediate_size
        # the held experts, in the order of ``experts_held``
        self.experts = LayerList([NemotronHMLP(config, f, _expert_store_width(f))
                                  for _ in config.experts_held])
        self.shared_experts = NemotronHMLP(
            config, config.moe_shared_expert_intermediate_size)
        slot = np.full(config.n_routed_experts, len(config.experts_held),
                       np.int32)
        slot[list(config.experts_held)] = np.arange(len(config.experts_held))
        self._held_slot = slot

    def forward_arrays(self, x, tm=None):
        """``x`` [T, D] array -> (this chip's part of the routed experts'
        output, routed pairs, experts hit, weight passes)."""
        c = self.config
        return moe_dropless(
            x, self.router.weight._data,
            self.router.e_score_correction_bias._data,
            [(e.up_proj.weight._data, e.down_proj.weight._data)
             for e in self.experts],
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
        return Tensor._wrap(y.reshape(shape)) + self.shared_experts(h)


def inverse_softplus_steps(u, step_min, step_max, floor):
    """``u`` uniform in (0, 1) -> the bias whose softplus is a step size
    log-uniform in ``[step_min, step_max]``, floored: how the family (and the
    gated delta layers of ``models/qwen3_next.py``) initialises ``dt_bias``."""
    import jax.numpy as jnp

    lo, hi = math.log(step_min), math.log(step_max)
    dt = jnp.maximum(jnp.exp(u.astype(jnp.float32) * (hi - lo) + lo), floor)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(u.dtype)


class NemotronHMamba2(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = self.config = config
        init = Normal(0.0, 0.02)
        heads, k = c.mamba_num_heads, c.conv_kernel
        self.in_proj = Linear(c.hidden_size,
                              c.mamba_inner + c.conv_dim + heads,
                              weight_attr=init, bias_attr=False)
        # a depthwise convolution's default: uniform in +-1 / sqrt(kernel)
        bound = 1.0 / math.sqrt(k)
        self.conv_weight = self.create_parameter(
            [c.conv_dim, k], default_initializer=Uniform(-bound, bound))
        self.conv_bias = self.create_parameter(
            [c.conv_dim], default_initializer=Uniform(-bound, bound))
        # as the family initialises them: A = 1 .. heads; the step sizes
        # log-uniform in [time_step_min, time_step_max], through the inverse
        # of the softplus; D = 1
        self.A_log = self.create_parameter(
            [heads], default_initializer=Constant(0.0))
        self.A_log._rebind(self.A_log._data + np.log(
            np.arange(1, heads + 1)).astype(np.float32))
        self.dt_bias = self.create_parameter(
            [heads], default_initializer=Uniform(0.0, 1.0))
        self.dt_bias._rebind(self._inverse_softplus_dt(self.dt_bias._data))
        self.D = self.create_parameter(
            [heads], default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [c.mamba_inner], default_initializer=Constant(1.0))
        self.out_proj = Linear(c.mamba_inner, c.hidden_size,
                               weight_attr=init, bias_attr=False)

    def _inverse_softplus_dt(self, u):
        c = self.config
        return inverse_softplus_steps(u, c.time_step_min, c.time_step_max,
                                      c.time_step_floor)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("state", c.mamba_num_heads, c.conv_dim,
                           c.mamba_head_dim, conv_rows=c.conv_kernel - 1,
                           state_dim=c.ssm_state_size,
                           scan_block=c.chunk_size)

    def serve(self, u, state):
        """``u`` [B, S, hidden] normed input -> the mixer's output; ``state``
        convolves, holds the tail and runs the recurrence."""
        import jax
        import jax.numpy as jnp

        c = self.config
        f32 = jnp.float32
        inner, heads = c.mamba_inner, c.mamba_num_heads
        proj = self.in_proj(u)._data
        z, xbc = proj[..., :inner], proj[..., inner:inner + c.conv_dim]
        dt = jax.nn.softplus(proj[..., inner + c.conv_dim:].astype(f32)
                             + self.dt_bias._data.astype(f32))
        x, y = state.scan(xbc, dt, -jnp.exp(self.A_log._data.astype(f32)),
                          self.conv_weight._data, self.conv_bias._data)
        y = y.astype(f32) + self.D._data.astype(f32)[:, None] * x.astype(f32)
        y = y.reshape(*y.shape[:2], inner) * jax.nn.silu(z.astype(f32))
        # the gate before the norm; a norm a group of channels
        g = y.reshape(*y.shape[:2], c.n_groups, inner // c.n_groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + c.layer_norm_epsilon)
        y = g.reshape(y.shape) * self.norm_weight._data.astype(f32)
        return self.out_proj(Tensor._wrap(y.astype(proj.dtype)))


class NemotronHAttention(Layer):
    def __init__(self, config: NemotronHConfig):
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
        self.o_proj = Linear(c.num_attention_heads * d, h, weight_attr=init,
                             bias_attr=False)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        c = self.config
        return KVLayerSpec("global", c.num_key_value_heads, c.head_dim,
                           c.head_dim, _store_width(c.head_dim),
                           prefill="linear")

    def serve(self, u, state):
        c = self.config
        b, s = u.shape[0], u.shape[1]
        q = self.q_proj(u)._data.reshape(b, s, c.num_attention_heads,
                                         c.head_dim)
        k = self.k_proj(u)._data.reshape(b, s, c.num_key_value_heads,
                                         c.head_dim)
        v = self.v_proj(u)._data.reshape(b, s, c.num_key_value_heads,
                                         c.head_dim)
        out = state.attend(q, k, v, scale=1.0 / math.sqrt(c.head_dim))
        return self.o_proj(Tensor._wrap(out.reshape(b, s, -1)))


_MIXERS = {"M": NemotronHMamba2, "*": NemotronHAttention, "E": NemotronHMoE}


class NemotronHBlock(Layer):
    """One block: a norm and ONE mixer, chosen by the pattern's letter."""

    def __init__(self, config: NemotronHConfig, letter: str):
        super().__init__()
        self.letter = letter
        self.norm = RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        self.mixer = _MIXERS[letter](config)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        return KVLayerSpec("none") if self.letter == "E" \
            else self.mixer.kv_spec()


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([NemotronHBlock(config, letter) for letter
                                 in config.hybrid_override_pattern])
        self.norm = RMSNorm(config.hidden_size, config.layer_norm_epsilon)


class _WholeSequence:
    """The state handle of the plain forward: whole sequences, no cache. A
    state-space block starts from zeros and scans the sequence; attention is
    causal over the sequence's own keys; counters go nowhere."""

    spec = None

    def count(self, name, value):
        pass

    def keep(self, name, rows):
        pass

    def scan(self, xbc, dt, a, conv_w, conv_b):
        import jax
        import jax.numpy as jnp

        from ..inference.serving.paged_attention import _conv_and_split
        from ..ops.pallas.mamba2 import ssd_chunk_scan

        spec, s = self.spec, xbc.shape[1]
        rows = jnp.pad(xbc, [(0, 0), (spec.conv_rows, 0), (0, 0)])
        x, b, c = _conv_and_split(
            spec, [rows[:, j:j + s] for j in range(spec.conv_rows + 1)],
            conv_w, conv_b)
        h0 = jnp.zeros((spec.num_kv_heads, spec.v_dim, spec.state_dim))
        y, _ = jax.vmap(lambda x, dt, b, c: ssd_chunk_scan(
            x, dt, a, b, c, h0, spec.scan_block))(x, dt, b, c)
        return x, y

    def attend(self, q, k, v, scale):
        import jax.numpy as jnp

        from ..inference.serving.paged_attention import _masked_attention

        t = jnp.arange(q.shape[1])
        return _masked_attention(q, k, v, (t[None, :] <= t[:, None])[None, None],
                                 scale)


class NemotronHForCausalLM(Layer):
    #: device-side counters ``serve_layer`` adds to (``state.count``)
    serve_counters = SERVE_COUNTERS
    #: rows ``serve_layer`` keeps for the host (``state.keep``)
    serve_keeps = SERVE_KEEPS

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.model = NemotronHModel(config)
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

    # -- the whole sequence at once (no cache, no engine) ------------------
    def forward(self, ids):
        """``ids`` [B, S] -> logits [B, S, V]: every block over the whole
        sequence, a state-space block from a zero state."""
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
        """One block: one mixer and its residual."""
        layer = self.model.layers[i]
        return x + layer.mixer.serve(layer.norm(x), state)

    def serve_norm(self, x):
        return self.model.norm(x)

    def serve_head(self, h):
        return self.lm_head(h)


def nemotron_h_tiny(**kw):
    """The structure at toy widths, for the CPU tests: all three kinds of
    block and two state-space blocks before the first attention, 4 Mamba-2
    heads of 8 over 2 groups and a state of 16, scan blocks of 8, 4 query
    heads over 2 kv heads of 16, 32 experts 24 wide, 4 a token, one shared
    expert 40 wide."""
    base = dict(
        vocab_size=160, hidden_size=64, num_hidden_layers=6,
        hybrid_override_pattern="MEM*EM", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
        chunk_size=8, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, n_routed_experts=32,
        num_experts_per_tok=4, max_position_embeddings=256)
    base.update(kw)
    return NemotronHConfig(**base)
