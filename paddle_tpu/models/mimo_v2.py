"""MiMo-V2-Flash — window and full attention mixed, sigmoid-routed experts.

The decoder of ``XiaomiMiMo/MiMo-V2-Flash`` as its ``config.json`` states
it, for the serving path (``LLMEngine`` calls ``serve_layer`` once a layer;
``inference/serving/paged_attention.py`` documents the state handle):

* pre-norm residual blocks, RMSNorm, SwiGLU, untied head, no bias;
* two kinds of attention by ``hybrid_layer_pattern`` (0 full, 1 window).
  Both: query and key heads ``head_dim`` (192) wide, value heads
  ``v_head_dim`` (128), rotary embedding on the first
  ``int(head_dim * partial_rotary_factor)`` dims of a head (half-split
  pairs), scores ``q.k / sqrt(head_dim)``, output times
  ``attention_value_scale``. Full layers: ``num_key_value_heads`` kv heads,
  base ``rope_theta``, plain causal softmax. Window layers:
  ``swa_num_key_value_heads`` kv heads, base ``swa_rope_theta``, query t
  sees keys ``t - sliding_window + 1 .. t``, and a learned per-head sink
  logit joins the softmax's denominator and carries no value;
* feed-forward by ``moe_layer_freq`` (0 dense at ``intermediate_size``, 1
  experts). Expert layers: a float32 router over ALL ``n_routed_experts``,
  scores ``sigmoid(x W_r)``, the ``num_experts_per_tok`` experts with the
  largest ``score + e_score_correction_bias`` chosen, combine weights the
  uncorrected scores of the chosen over their sum. No token is ever dropped.

**One chip's share of the experts.** ``experts_held`` names the experts
whose weights live here (all of them if None). The block routes over the
full router, computes the part of the result its own experts give, and
returns that: what expert parallelism asks of a layer. On one chip it runs
without the exchange, and nothing stands in for the absent experts.

**Dropless with static shapes.** The (token, held expert) pairs are sorted
by expert and cut into tiles of ``tm`` rows that each belong to one expert;
a ``while_loop`` runs over the tiles that exist, so the matmul work follows
the routed pairs (rounded up to a tile an expert), not tokens x experts
held. A row's result depends on that row and its expert's weights alone,
and a token's experts are summed in expert order: its output is the same
alone and in a full batch.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops import manipulation as M
from .llama import _rope_cache

__all__ = ["MiMoV2Config", "MiMoV2ForCausalLM", "moe_dropless",
           "sigmoid_scores", "softmax_scores",
           "mimo_v2_tiny"]

#: device-side counters an expert layer adds to, per call
SERVE_COUNTERS = ("moe_pairs_routed_here", "moe_experts_hit",
                  "moe_layer_steps", "moe_weight_passes")


@dataclasses.dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    hybrid_layer_pattern: tuple = ()      # 0 full, 1 window; one a layer
    moe_layer_freq: tuple = ()            # 0 dense, 1 experts; one a layer
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256           # the router's width, as published
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: global ids of the experts held here; None holds them all
    experts_held: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)[:n]
        self.moe_layer_freq = tuple(self.moe_layer_freq)[:n]
        if len(self.hybrid_layer_pattern) != n or len(self.moe_layer_freq) != n:
            raise ValueError(
                "hybrid_layer_pattern and moe_layer_freq need an entry a "
                f"layer ({n})")
        if self.experts_held is None:
            self.experts_held = tuple(range(self.n_routed_experts))
        self.experts_held = tuple(int(e) for e in self.experts_held)
        if self.tie_word_embeddings:
            raise ValueError("MiMo-V2's head is untied")

    def is_window(self, i):
        return bool(self.hybrid_layer_pattern[i])

    def rotary_dim(self, i):
        d = self.swa_head_dim if self.is_window(i) else self.head_dim
        return int(d * self.partial_rotary_factor)


def _store_width(k_dim):
    """The width a K row is stored at: up to the 128 lanes (16 at the toy
    widths of the CPU tests, so that they pad too)."""
    lane = 128 if k_dim >= 128 else 16
    return -(-k_dim // lane) * lane


def sigmoid_scores(logits, bias):
    """The score function of MiMo-V2, JoyAI-LLM-Flash and Nemotron-H:
    ``sigmoid`` of the router's logits weighs, and the same plus a learned
    correction (``bias`` [E] float32) chooses."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(logits)
    return scores, scores + bias.astype(jnp.float32)


def softmax_scores(logits, bias=None):
    """``softmax`` over ALL the router's experts weighs and chooses
    (Qwen3-Next); there is no correction."""
    import jax

    scores = jax.nn.softmax(logits, axis=-1)
    return scores, scores


def moe_dropless(x, router_w, bias, experts, held_slot, *, top_k,
                 norm_topk=True, scaling=None, tm=None, with_passes=False,
                 with_choice=False, score=sigmoid_scores):
    """The expert block on arrays. ``x`` [T, D]; ``router_w`` [D, E] and
    ``bias`` [E] float32 (None where the score function takes none);
    ``experts``: one ``(gate [D, F], up [D, F], down
    [F, D])`` a held expert, or one ``(up, down)`` for experts with no gate,
    whose activation is ``relu(.)^2``; ``held_slot`` int32 [E]: an expert's place in
    ``experts``, ``len(experts)`` if it is not held here. ``score`` is the
    MODEL's: ``score(logits [T, E] float32, bias) -> (what weighs, what
    chooses)``, the ``top_k`` largest of the second are taken and weighed by
    the first (over their sum with ``norm_topk``, times ``scaling``).
    Returns ``(y
    [T, D], routed (token, held expert) pairs, held experts with a
    token)``, and with ``with_passes`` a fourth: how many times an expert's
    three matrices were streamed; with ``with_choice`` last of all the
    experts each token chose, global ids int32 ``[T, top_k]``.

    Between "rows sorted by expert" and "weighted rows added into the
    output" runs the grouped kernel (``ops/pallas/grouped_ffn.py``) on the
    chip, and under ``PT_PALLAS_INTERPRET=1`` at widths it takes; the tile
    loop is the CPU's path. ``tm`` is the tests' hook: the rows a tile or a
    pass holds."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.grouped_ffn import use_pallas_grouped_ffn

    t, d = x.shape
    n_held = len(experts)
    scores, by = score(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), bias)           # [T, E]
    _, sel = jax.lax.top_k(by, top_k)
    w = jnp.take_along_axis(scores, sel, axis=1)              # uncorrected
    if norm_topk:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    if scaling:
        w = w * scaling
    slot = jnp.asarray(held_slot)[sel].reshape(-1)            # [T * k]
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    rows = (_grouped_rows if use_pallas_grouped_ffn(d, experts[0][0].shape[1])
            else _tile_loop)
    out, passes = rows(x, experts, slot, w, order, sizes, starts, tm)
    res = (out.astype(x.dtype), jnp.sum(sizes),
           jnp.sum((sizes > 0).astype(jnp.int32)))
    if with_passes:
        res += (passes,)
    return res + (sel.astype(jnp.int32),) if with_choice else res


def _tile_loop(x, experts, slot, w, order, sizes, starts, tm):
    """The sorted rows cut into tiles of ``tm`` that each belong to one
    expert, a ``while_loop`` over the tiles that exist: the CPU's path.
    Returns (the weighted sum a token [T, D] float32, tiles run)."""
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    n_pairs = order.shape[0]
    s_tok = (order // (n_pairs // t)).astype(jnp.int32)
    s_w = w.reshape(-1)[order]
    if tm is None:
        tm = 128 if n_pairs >= 128 else 8
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)

    def ffn(weights):
        # a branch of its own an expert: its weights are read where they
        # lie. (One stacked array indexed by the tile's expert made XLA
        # copy the 16 MiB slice before each of the three dots.)
        if len(weights) == 2:                 # no gate: relu(.)^2
            up_w, down_w = weights
            return lambda xs: jnp.dot(
                jnp.square(jax.nn.relu(jnp.dot(xs, up_w))), down_w)
        gate_w, up_w, down_w = weights
        return lambda xs: jnp.dot(
            jax.nn.silu(jnp.dot(xs, gate_w)) * jnp.dot(xs, up_w), down_w)

    branches = [ffn(e) for e in experts]

    def body(carry):
        i, out = carry
        e = jnp.searchsorted(tile_end, i, side="right").astype(jnp.int32)
        r = (i - (tile_end[e] - tiles[e])) * tm + jnp.arange(tm, dtype=jnp.int32)
        valid = r < sizes[e]
        rows = jnp.clip(starts[e] + r, 0, n_pairs - 1)
        tok = jnp.where(valid, s_tok[rows], 0)
        wt = jnp.where(valid, s_w[rows], 0.0)
        y = jax.lax.switch(e, branches, x[tok])               # [tm, D]
        return i + 1, out.at[tok].add(y.astype(jnp.float32) * wt[:, None])

    _, out = jax.lax.while_loop(
        lambda c: c[0] < tile_end[-1], body,
        (jnp.int32(0), jnp.zeros((t, d), jnp.float32)))
    return out, tile_end[-1]


def _grouped_rows(x, experts, slot, w, order, sizes, starts, tm):
    """The sorted pairs through ``grouped_swiglu``: one call, which fetches
    the rows of the pairs routed here, streams each hit expert's weights
    once for every ``rows`` of its pairs, and puts each pair's result in the
    pair's own row; then one weighted sum of a token's rows, in the order of
    its choice. The rows a pass holds follow from ``T`` (``rows_for``).
    Returns (the sum [T, D] float32, passes made)."""
    import jax.numpy as jnp

    from ..ops.pallas.grouped_ffn import grouped_swiglu, rows_for

    t, d = x.shape
    n_held = len(experts)
    n_pairs = slot.shape[0]
    top_k = n_pairs // t
    rows = tm or rows_for(t)
    # the items: an expert's passes follow one another
    passes = (sizes + rows - 1) // rows
    item_end = jnp.cumsum(passes)
    i = jnp.arange(n_held + n_pairs // rows, dtype=jnp.int32)
    e = jnp.minimum(jnp.searchsorted(item_end, i, side="right"), n_held - 1)
    done = (i - (item_end[e] - passes[e])) * rows    # of e's pairs, before i
    live = jnp.where(i < item_end[-1], jnp.clip(sizes[e] - done, 0, rows), 0)
    y = grouped_swiglu(x, order, e, starts[e] + done, live, item_end[-1],
                       experts, rows=rows, top_k=top_k)
    # a pair of no held expert has no row of y: whatever lies there is
    # selected away, not multiplied. y comes in lane blocks, [T x k, D /
    # 128, 128]: summed as it lies (one pass over it), only the [T, D] sum
    # is laid out anew
    y = y.reshape(t, top_k, d // 128, 128)
    held = (slot < n_held).reshape(t, top_k, 1, 1)
    out = jnp.sum(jnp.where(held, y * w[:, :, None, None], 0.0), axis=1)
    return out.reshape(t, d), item_end[-1]


class MiMoV2Router(Layer):
    """The router's two float32 parameters; they stay float32 whatever the
    model is cast to."""

    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.weight = self.create_parameter(
            [config.hidden_size, config.n_routed_experts], dtype="float32",
            default_initializer=Normal(0.0, 0.02))
        # published: learned, takes part in the choice only, and is what
        # keeps a trained model's load even. Drawn small: at 0.01 it changes
        # one choice in a few and the load stays near even; at 0.1 it left
        # 106 of 256 experts without one token of 512 (PERF.md, PR 27)
        self.e_score_correction_bias = self.create_parameter(
            [config.n_routed_experts], dtype="float32",
            default_initializer=Normal(0.0, 0.01))


class MiMoV2MoE(Layer):
    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.router = MiMoV2Router(config)
        # the held experts, in the order of ``experts_held``
        self.experts = LayerList([
            MiMoV2MLP(config, config.moe_intermediate_size)
            for _ in config.experts_held])
        slot = np.full(config.n_routed_experts, len(config.experts_held),
                       np.int32)
        slot[list(config.experts_held)] = np.arange(len(config.experts_held))
        self._held_slot = slot

    def forward_arrays(self, x, tm=None):
        """``x`` [T, D] array -> (this chip's part of the block's output,
        routed pairs, experts hit, weight passes)."""
        c = self.config
        return moe_dropless(
            x, self.router.weight._data,
            self.router.e_score_correction_bias._data,
            [(e.gate_proj.weight._data, e.up_proj.weight._data,
              e.down_proj.weight._data) for e in self.experts],
            self._held_slot,
            top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
            scaling=c.routed_scaling_factor, tm=tm, with_passes=True,
            score=sigmoid_scores)

    def forward(self, x):
        shape = x.shape
        y = self.forward_arrays(x._data.reshape(-1, shape[-1]))[0]
        return Tensor._wrap(y.reshape(shape))


class MiMoV2MLP(Layer):
    """SwiGLU: the dense block at ``intermediate_size``, one expert at
    ``moe_intermediate_size``."""

    def __init__(self, config: MiMoV2Config, width=None):
        super().__init__()
        init = Normal(0.0, 0.02)
        d, f = config.hidden_size, width or config.intermediate_size
        self.gate_proj = Linear(d, f, weight_attr=init, bias_attr=False)
        self.up_proj = Linear(d, f, weight_attr=init, bias_attr=False)
        self.down_proj = Linear(f, d, weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class MiMoV2Attention(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        c = config
        self.window = c.sliding_window if c.is_window(layer_idx) else None
        if self.window:
            self.num_heads, self.num_kv_heads = (c.swa_num_attention_heads,
                                                 c.swa_num_key_value_heads)
            self.head_dim, self.v_head_dim = c.swa_head_dim, c.swa_v_head_dim
            has_sink = c.add_swa_attention_sink_bias
        else:
            self.num_heads, self.num_kv_heads = (c.num_attention_heads,
                                                 c.num_key_value_heads)
            self.head_dim, self.v_head_dim = c.head_dim, c.v_head_dim
            has_sink = c.add_full_attention_sink_bias
        self.rotary_dim = c.rotary_dim(layer_idx)
        self.value_scale = c.attention_value_scale
        init = Normal(0.0, 0.02)
        h = c.hidden_size
        self.q_proj = Linear(h, self.num_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(h, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.v_proj = Linear(h, self.num_kv_heads * self.v_head_dim,
                             weight_attr=init, bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.v_head_dim, h,
                             weight_attr=init, bias_attr=False)
        # float32 whatever the model is cast to; random so that it matters
        self.attention_sink_bias = (self.create_parameter(
            [self.num_heads], dtype="float32",
            default_initializer=Normal(0.0, 1.0)) if has_sink else None)

    def kv_spec(self):
        from ..inference.serving.kv_cache import KVLayerSpec

        return KVLayerSpec(
            "window" if self.window else "global", self.num_kv_heads,
            self.head_dim, self.v_head_dim, _store_width(self.head_dim),
            self.window, prefill="linear")

    def serve(self, h, state, cos_t, sin_t):
        """``h`` [B, S, hidden] normed input -> the block's output before
        the residual; ``state`` writes k and v and attends."""
        import jax.numpy as jnp

        import jax

        b, s = h.shape[0], h.shape[1]
        # the barrier keeps XLA from folding the split into heads into the
        # projection: 192 is no multiple of the lanes, and it then laid
        # the 100 MB q weight out anew in every step
        q, k = jax.lax.optimization_barrier(
            (self.q_proj(h)._data, self.k_proj(h)._data))
        q = q.reshape(b, s, self.num_heads, self.head_dim)
        k = k.reshape(b, s, self.num_kv_heads, self.head_dim)
        v = M.reshape(self.v_proj(h), [b, s, self.num_kv_heads, self.v_head_dim])._data
        r = self.rotary_dim

        def rot(x):
            return jnp.concatenate(
                [state.rope(x[..., :r], cos_t, sin_t), x[..., r:]], -1)

        sink = (self.attention_sink_bias._data
                if self.attention_sink_bias is not None else None)
        out = state.attend(rot(q), rot(k), v,
                           scale=1.0 / math.sqrt(self.head_dim), sink=sink)
        out = out * jnp.asarray(self.value_scale, out.dtype)
        return self.o_proj(M.reshape(Tensor._wrap(out), [b, s, -1]))


class MiMoV2DecoderLayer(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        eps = config.layernorm_epsilon
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = MiMoV2Attention(config, layer_idx)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.is_moe = bool(config.moe_layer_freq[layer_idx])
        self.mlp = MiMoV2MoE(config) if self.is_moe else MiMoV2MLP(config)


class MiMoV2Model(Layer):
    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([MiMoV2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.layernorm_epsilon)
        # one table pair a kind: [max_pos, rotary_dim / 2]
        for name, idx, theta in (("rope", 0, config.rope_theta),
                                 ("swa_rope", 1, config.swa_rope_theta)):
            layers = [i for i in range(config.num_hidden_layers)
                      if config.is_window(i) == bool(idx)]
            rd = config.rotary_dim(layers[0]) if layers else 2
            cos, sin = _rope_cache(config.max_position_embeddings, rd, theta)
            self.register_buffer(name + "_cos", Tensor(cos), persistable=False)
            self.register_buffer(name + "_sin", Tensor(sin), persistable=False)


class MiMoV2ForCausalLM(Layer):
    #: device-side counters ``serve_layer`` adds to (``state.count``)
    serve_counters = SERVE_COUNTERS

    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.model = MiMoV2Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0, 0.02), bias_attr=False)

    def _cast_params(self, dtype, only_float=True):
        """The router and the sinks stay float32, as published."""
        keep = [(p, p._data) for n, p in self.named_parameters()
                if ".router." in n or n.endswith("attention_sink_bias")]
        super()._cast_params(dtype, only_float)
        for p, data in keep:
            p._rebind(data)

    def _unique_params(self):
        seen, params = set(), []
        for _, p in self.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                params.append(p)
        return params

    # -- the serving path (LLMEngine) ------------------------------------
    def kv_layout(self):
        return [layer.self_attn.kv_spec() for layer in self.model.layers]

    def serve_dtype(self):
        return self.model.layers[0].self_attn.k_proj.weight.dtype

    def serve_embed(self, ids):
        return self.model.embed_tokens(Tensor._wrap(ids))

    def serve_layer(self, i, x, state):
        layer = self.model.layers[i]
        m = self.model
        cos_t, sin_t = ((m.swa_rope_cos, m.swa_rope_sin)
                        if layer.self_attn.window else (m.rope_cos, m.rope_sin))
        x = x + layer.self_attn.serve(layer.input_layernorm(x), state,
                                      cos_t._data, sin_t._data)
        h = layer.post_attention_layernorm(x)
        if not layer.is_moe:
            return x + layer.mlp(h)
        shape = h.shape
        y, pairs, hit, passes = layer.mlp.forward_arrays(
            h._data.reshape(-1, shape[-1]))
        state.count("moe_pairs_routed_here", pairs)
        state.count("moe_experts_hit", hit)
        state.count("moe_layer_steps", 1)
        state.count("moe_weight_passes", passes)
        return x + Tensor._wrap(y.reshape(shape))

    def serve_norm(self, x):
        return self.model.norm(x)

    def serve_head(self, h):
        return self.lm_head(h)


def mimo_v2_tiny(**kw):
    """The structure at toy widths, for the CPU tests: K rows wider than V
    rows, 4 against 8 kv heads, a window shorter than the prompts, the
    seven-layer pattern (dense full layer, then a period), 32 experts, 4 a
    token."""
    base = dict(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=4,
        head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
        swa_num_key_value_heads=8, swa_head_dim=24, swa_v_head_dim=16,
        sliding_window=8, hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), moe_intermediate_size=32,
        n_routed_experts=32, num_experts_per_tok=4,
        max_position_embeddings=256)
    base.update(kw)
    return MiMoV2Config(**base)
