"""Llama-family decoder — the flagship transformer.

Capability target: PaddleNLP's Llama implementation driven by the reference's
Fleet hybrid-parallel stack (BASELINE.md config 5: Llama-2-13B TP+PP+DP).
TPU-first design choices:

* bf16-native; norms/softmax accumulate in fp32 (see nn/functional/norm.py)
* attention dispatches to the Pallas flash-attention kernel on TPU
  (ops/pallas/flash_attention.py) with an XLA fallback
* GQA (num_kv_heads <= num_heads), RoPE, SwiGLU — matmul shapes kept
  multiple-of-128 so XLA tiles cleanly onto the MXU
* ``tp_partition_spec`` publishes the Megatron-style sharding plan consumed by
  GSPMD (auto_parallel) and by the meta_parallel TP layers — column-parallel
  qkv/gate/up, row-parallel o/down, vocab-parallel embedding.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops import creation, manipulation as M, math as ops_math

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "StaticKVCache",
           "sample_next_tokens", "greedy_tokens_in_graph",
           "llama_tiny", "llama_small", "llama_125m",
           "llama_1b", "llama_7b", "llama_13b"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dropout: float = 0.0
    # context parallelism: attention over a seq shard per device, K/V
    # rotated around the 'sep' mesh axis (nn/functional/ring_attention.py)
    use_ring_attention: bool = False
    # alternative sequence parallelism: Ulysses all_to_all head/seq
    # re-shard (nn/functional/ulysses_attention.py) — num_heads and
    # seq_len must each be divisible BY the 'sep' axis size
    use_sep_attention: bool = False
    # MoE (expert-parallel axis); 0 = dense
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_every: int = 2  # every Nth layer is MoE when num_experts > 0
    moe_capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.01

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def _rope_cache(seq_len, head_dim, theta, dtype=np.float32):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    t = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(t, inv)
    return (np.cos(freqs).astype(dtype), np.sin(freqs).astype(dtype))


from ..core.dispatch import op as _op


def rope_rotate(x, c, s):
    """Rotary embedding of ``x [..., D]`` by half-width ``c``/``s``
    (broadcastable against ``x[..., :D/2]``): ``[x1*c - x2*s, x2*c +
    x1*s]``, written as two full-width multiplies around one lane roll.
    Bit-identical to the split-and-concatenate form (negation and
    ``a + (-b)`` are exact) — which, compiled on its own at head-dim 128,
    aborts libtpu 0.0.34's fusion emitter (``IsFusibleUnalignedDUS``: the
    concatenate lands at lane offset 64), so an eager forward killed the
    process. Every rope in the repo goes through here."""
    import jax.numpy as jnp

    c = jnp.concatenate([c, c], axis=-1).astype(x.dtype)
    s = jnp.concatenate([-s, s], axis=-1).astype(x.dtype)
    return x * c + jnp.roll(x, x.shape[-1] // 2, axis=-1) * s


@_op("rope_apply")
def _rope_apply(x, cos, sin):
    return rope_rotate(x, cos[None, :, None, :], sin[None, :, None, :])


def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; cos/sin: [S, D/2] tensors."""
    return _rope_apply(x, cos, sin)


@_op("rope_apply_at")
def _rope_apply_at(x, cos_t, sin_t, pos):
    """Rope at a traced offset: x [B, s, H, D] holds absolute positions
    ``pos..pos+s-1``; cos_t/sin_t are the FULL [max_pos, D/2] tables and the
    slice happens in-graph (lax.dynamic_slice), so one compiled decode step
    serves every position — the static-cache decode contract."""
    import jax
    import jax.numpy as jnp

    s, d2 = x.shape[1], x.shape[-1] // 2
    pos = jnp.asarray(pos, jnp.int32)
    cos = jax.lax.dynamic_slice(cos_t, (pos, jnp.int32(0)), (s, d2))
    sin = jax.lax.dynamic_slice(sin_t, (pos, jnp.int32(0)), (s, d2))
    return rope_rotate(x, cos[None, :, None, :], sin[None, :, None, :])


@_op("llama_cached_attn_step")
def _cached_attn_step(q, k, v, k_buf, v_buf, pos):
    """Static-capacity KV cache step: write this call's K/V (already
    rope'd) at ``pos`` via ``lax.dynamic_update_slice`` — the cache shape
    NEVER changes, so decode never recompiles — then attend over the cache
    prefix. q/k/v: [B, s, H(kv), D]; k_buf/v_buf: [B, C, Hkv, D];
    pos: scalar tokens-already-written. Masked columns contribute exactly
    zero (fp32 softmax underflow of the -1e30 logits against zero-filled
    buffers), so prefill through this path matches the dense causal
    forward. Returns (out [B, s, H, D], k_buf, v_buf)."""
    import jax
    import jax.numpy as jnp

    from ..nn.functional.flash_attention import _sdpa_ref

    s, cap = q.shape[1], k_buf.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    zero = jnp.int32(0)
    k_buf = jax.lax.dynamic_update_slice(
        k_buf, k.astype(k_buf.dtype), (zero, pos, zero, zero))
    v_buf = jax.lax.dynamic_update_slice(
        v_buf, v.astype(v_buf.dtype), (zero, pos, zero, zero))
    col = jnp.arange(cap, dtype=jnp.int32)[None, None, None, :]
    row = jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
    mask = col <= (pos + row)  # causal over the written prefix
    out = _sdpa_ref.raw_fn(q, k_buf, v_buf, attn_mask=mask)
    return out, k_buf, v_buf


class StaticKVCache:
    """Preallocated static-capacity KV cache for autoregressive decode.

    Per-layer K/V buffers of shape ``[batch, capacity, num_kv_heads,
    head_dim]`` plus a host-side write offset ``pos``. Every decode step
    writes one token in-graph (``lax.dynamic_update_slice``) and attends
    over the first ``pos+1`` entries — shapes never change, so the whole
    32-token decode reuses ONE compiled executable instead of the
    concat-per-step path's compile-per-token cliff (ISSUE 7 satellite;
    ``paddle.jit.cache_stats()`` shows the counts)."""

    __slots__ = ("k", "v", "pos")

    def __init__(self, config: LlamaConfig, batch_size, capacity,
                 dtype=None):
        import jax.numpy as jnp

        if dtype is None:
            dtype = jnp.float32
        shape = (batch_size, capacity, config.num_key_value_heads,
                 config.head_dim)
        self.k = [jnp.zeros(shape, dtype)
                  for _ in range(config.num_hidden_layers)]
        self.v = [jnp.zeros(shape, dtype)
                  for _ in range(config.num_hidden_layers)]
        self.pos = 0

    @property
    def capacity(self):
        return self.k[0].shape[1]

    @property
    def batch_size(self):
        return self.k[0].shape[0]


def sample_next_tokens(last, *, do_sample=False, temperature=1.0, top_k=None,
                       top_p=None, rng=None):
    """Host-side next-token selection over logits ``last`` (np [B, V]):
    greedy argmax, or seeded temperature/top-k/top-p sampling via ``rng``
    (a ``np.random.RandomState``). Shared by ``LlamaForCausalLM.generate``
    and the serving engine so both paths sample identically."""
    last = np.asarray(last)
    if not do_sample and last.dtype in (np.float32, np.float64):
        # float32 -> float64 is exact and keeps order and ties: the argmax
        # of the row as it is fetched is the same index, without the copy
        return last.argmax(-1)
    last = last.astype(np.float64)
    if not do_sample:
        return last.argmax(-1)
    if rng is None:
        rng = np.random.RandomState()
    last = last / max(temperature, 1e-6)
    if top_k is not None:
        k_eff = min(int(top_k), last.shape[1])
        kth = np.sort(last, -1)[:, -k_eff][:, None]
        last = np.where(last < kth, -np.inf, last)
    probs = np.exp(last - last.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    if top_p is not None:
        srt = np.argsort(-probs, -1)
        cum = np.cumsum(np.take_along_axis(probs, srt, -1), -1)
        cut = cum - np.take_along_axis(probs, srt, -1) > top_p
        kill = np.zeros_like(probs, bool)
        np.put_along_axis(kill, srt, cut, -1)
        probs = np.where(kill, 0, probs)
        probs /= probs.sum(-1, keepdims=True)
    return np.array([rng.choice(probs.shape[1], p=probs[i])
                     for i in range(last.shape[0])])


def greedy_tokens_in_graph(last):
    """In-graph greedy companion to :func:`sample_next_tokens`: argmax over
    the last axis of logits ``last`` (jnp [B, V] f32), returned as int32.

    Bit-identical to the host path: ``sample_next_tokens`` casts f32 logits
    to float64 before ``np.argmax`` — the cast is exact and monotone, so the
    winning index (first occurrence on ties, same rule as ``jnp.argmax``)
    cannot change. Used by the serving engine's device-resident decode so
    the per-step fetch is ``[B]`` int32 instead of ``[B, V]`` f32."""
    import jax.numpy as jnp

    return jnp.argmax(last, axis=-1).astype(jnp.int32)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        self.use_ring_attention = c.use_ring_attention
        self.use_sep_attention = c.use_sep_attention
        self._ring_mesh = None  # optional explicit mesh (else fleet hcg)
        std = 0.02
        init = Normal(0.0, std)
        self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.k_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.v_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                             weight_attr=init, bias_attr=False)

    def forward(self, x, cos, sin, attn_mask=None, cache=None):
        b, s = x.shape[0], x.shape[1]
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if cache is not None:
            k = M.concat([cache[0], k], axis=1)
            v = M.concat([cache[1], v], axis=1)
            new_cache = (k, v)
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=False)
            return self.o_proj(M.reshape(out, [b, s, -1])), new_cache
        if self.use_ring_attention and attn_mask is None:
            from ..nn.functional.ring_attention import ring_flash_attention

            out = ring_flash_attention(q, k, v, mesh=self._ring_mesh,
                                       axis="sep", causal=True)
        elif self.use_sep_attention and attn_mask is None:
            from ..nn.functional.ulysses_attention import (
                sep_all_to_all_attention)

            out = sep_all_to_all_attention(q, k, v, mesh=self._ring_mesh,
                                           axis="sep", causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=attn_mask is None)
        return self.o_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))

    def forward_cached(self, x, k_buf, v_buf, pos, cos_t, sin_t):
        """Static-cache step (prefill when ``pos==0`` with s>1, decode when
        s==1): project, rope at offset ``pos``, write into the preallocated
        buffers, attend over the prefix. Returns (out, k_buf, v_buf)."""
        b, s = x.shape[0], x.shape[1]
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        q = _rope_apply_at(q, cos_t, sin_t, pos)
        k = _rope_apply_at(k, cos_t, sin_t, pos)
        out, k_buf, v_buf = _cached_attn_step(q, k, v, k_buf, v_buf, pos)
        return (self.o_proj(M.reshape(out, [b, s, -1])), k_buf, v_buf)

    def forward_einsum_block(self, x, cos, sin, attn_mask=None):
        """Head-major single-op attention block (PT_ATTN_EINSUM=1): the
        h<->s transposes fold into the projection einsums. Returns None
        when unavailable."""
        import os

        if (attn_mask is not None or self.use_ring_attention
                or self.use_sep_attention
                or os.environ.get("PT_ATTN_EINSUM", "0") != "1"):
            return None
        b, s = x.shape[0], x.shape[1]
        from ..ops.pallas.flash_attention import _attention_block_bhsd
        from ..nn.functional.flash_attention import _use_pallas

        class _S:
            shape = (b, s, self.num_heads, self.head_dim)

        if not _use_pallas(_S(), _S()):
            return None
        out = _attention_block_bhsd(
            x, self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
            self.o_proj.weight, cos, sin, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, causal=True)
        import importlib

        # path observability (LAST_PATH), same contract as the other routes
        importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention").LAST_PATH = \
            "einsum_block"
        return out

    def forward_pre_rope(self, x, cos, sin, attn_mask=None):
        """Projection + rope-fused flash attention (rope applied inside the
        Pallas kernel); returns None when the fused path is unavailable."""
        if attn_mask is not None or self.use_ring_attention \
                or self.use_sep_attention:
            return None
        b, s = x.shape[0], x.shape[1]
        # gate BEFORE the projections: otherwise the eager fallback pays the
        # qkv matmuls twice (advisor r4)
        if not F.fused_rope_attention_enabled(b, s, self.num_heads,
                                              self.head_dim):
            return None
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        out = F.fused_rope_attention(q, k, v, cos, sin, is_causal=True)
        if out is None:
            return None
        return self.o_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = Normal(0.0, 0.02)
        self.gate_proj = Linear(config.hidden_size, config.intermediate_size,
                                weight_attr=init, bias_attr=False)
        self.up_proj = Linear(config.hidden_size, config.intermediate_size,
                              weight_attr=init, bias_attr=False)
        self.down_proj = Linear(config.intermediate_size, config.hidden_size,
                                weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


@_op("moe_topk_capacity")
def _moe_topk_capacity(x, logits, gate_w, up_w, down_w, top_k=2,
                       capacity_factor=1.25):
    """Token-choice top-k MoE, GShard capacity-based dispatch: each expert
    computes at most C = ceil(k*T/E * factor) tokens, so per-token FLOPs
    are k * expert_FLOPs, independent of num_experts (the reference's
    global_scatter/global_gather semantics under static shapes). Dispatch/
    combine are scatter-add/gather on flat slot indices (O(T) memory).
    Under GSPMD the expert dim shards over the 'ep' mesh axis and XLA
    inserts the all_to_all the reference's collective ops implement by
    hand. Returns (out, aux) — aux is the load-balance loss."""
    import jax
    import jax.numpy as jnp

    from ..incubate.distributed.models.moe.moe_layer import (
        combine_from_experts, dispatch_to_experts, moe_capacity,
        top_k_capacity_gating)

    b, s, h = x.shape
    e = gate_w.shape[0]
    xf = x.reshape(b * s, h)
    probs = jax.nn.softmax(
        logits.reshape(b * s, e).astype(jnp.float32), axis=-1)
    cap = moe_capacity(b * s, e, top_k, capacity_factor)
    ei, si, keep, w, aux = top_k_capacity_gating(probs, top_k, cap)
    expert_in = dispatch_to_experts(xf, ei, si, keep, e, cap)
    from ..ops.pallas.moe_ffn import (
        moe_expert_ffn, moe_ffn_shapes_ok, use_fused_moe_ffn)

    if use_fused_moe_ffn() and moe_ffn_shapes_ok(h, gate_w.shape[-1]):
        expert_out = moe_expert_ffn(expert_in, gate_w, up_w, down_w)
    else:
        hidden = jnp.einsum("ech,ehi->eci", expert_in, gate_w)
        hidden = jax.nn.silu(hidden) * jnp.einsum("ech,ehi->eci", expert_in,
                                                  up_w)
        expert_out = jnp.einsum("eci,eih->ech", hidden, down_w)
    out = combine_from_experts(expert_out, ei, si, keep, w)
    return out.reshape(b, s, h), aux


class LlamaMoE(Layer):
    """Mixtral-style token-choice MoE (reference analog:
    incubate/distributed/models/moe/moe_layer.py via global_scatter/gather;
    TPU-native: GShard capacity-based dispatch — under GSPMD the expert
    dimension shards over the 'ep' mesh axis and XLA inserts the
    all_to_all; see incubate.distributed.models.moe for the explicit
    shard_map form)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_experts = c.num_experts
        self.top_k = c.num_experts_per_tok
        self.capacity_factor = c.moe_capacity_factor
        self.l_aux = None
        init = Normal(0.0, 0.02)
        self.router = Linear(c.hidden_size, c.num_experts, weight_attr=init,
                             bias_attr=False)
        e, h, i = c.num_experts, c.hidden_size, c.intermediate_size
        self.gate_w = self.create_parameter([e, h, i], default_initializer=init)
        self.up_w = self.create_parameter([e, h, i], default_initializer=init)
        self.down_w = self.create_parameter([e, i, h], default_initializer=init)

    def forward(self, x):
        logits = self.router(x)
        out, self.l_aux = _moe_topk_capacity(
            x, logits, self.gate_w, self.up_w, self.down_w,
            top_k=self.top_k, capacity_factor=self.capacity_factor)
        return out


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        use_moe = (config.num_experts > 0
                   and layer_idx % config.moe_every == config.moe_every - 1)
        self.mlp = LlamaMoE(config) if use_moe else LlamaMLP(config)
        self._fusable_norm = config.hidden_size % 128 == 0

    def forward_cached(self, x, k_buf, v_buf, pos, cos_t, sin_t):
        attn_out, k_buf, v_buf = self.self_attn.forward_cached(
            self.input_layernorm(x), k_buf, v_buf, pos, cos_t, sin_t)
        x = x + attn_out
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, k_buf, v_buf

    def forward(self, x, cos, sin, attn_mask=None, cache=None):
        if cache is not None:
            attn_out, new_cache = self.self_attn(
                self.input_layernorm(x), cos, sin, attn_mask, cache)
            x = x + attn_out
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        h = self.input_layernorm(x)
        attn_out = self.self_attn.forward_einsum_block(h, cos, sin,
                                                       attn_mask)
        if attn_out is None:
            attn_out = self.self_attn.forward_pre_rope(h, cos, sin,
                                                       attn_mask)
        if attn_out is None:
            attn_out = self.self_attn(h, cos, sin, attn_mask)
        from ..ops.pallas.rms_norm import (
            fused_add_rms_norm,
            use_fused_rms_norm,
        )

        if use_fused_rms_norm() and self._fusable_norm:
            ln = self.post_attention_layernorm
            n2, resid = fused_add_rms_norm(x, attn_out, ln.weight,
                                           epsilon=ln._epsilon)
            return resid + self.mlp(n2)
        x = x + attn_out
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))
        self.layers = LayerList([
            LlamaDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)
        ])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config.max_position_embeddings, config.head_dim,
                               config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward_cached(self, input_ids, k_bufs, v_bufs, pos):
        """Static-cache forward: ``k_bufs``/``v_bufs`` are per-layer
        [B, C, Hkv, D] buffers (arrays or Tensors), ``pos`` the write
        offset. Returns (normed hidden, new k_bufs, new v_bufs)."""
        x = self.embed_tokens(input_ids)
        new_k, new_v = [], []
        for layer, kb, vb in zip(self.layers, k_bufs, v_bufs):
            x, kb, vb = layer.forward_cached(x, kb, vb, pos,
                                             self.rope_cos, self.rope_sin)
            new_k.append(kb)
            new_v.append(vb)
        return self.norm(x), new_k, new_v

    def forward(self, input_ids, attn_mask=None, caches=None):
        x = self.embed_tokens(input_ids)
        s = input_ids.shape[1]
        if caches is not None:
            past = caches[0][0].shape[1] if caches[0] is not None else 0
            cos = self.rope_cos[past : past + s]
            sin = self.rope_sin[past : past + s]
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, c = layer(x, cos, sin, attn_mask, cache)
                new_caches.append(c)
            return self.norm(x), new_caches
        cos = self.rope_cos[:s]
        sin = self.rope_sin[:s]
        for layer in self.layers:
            x = layer(x, cos, sin, attn_mask)
        return self.norm(x)


import itertools as _itertools


class LlamaForCausalLM(Layer):
    _decode_instance_ids = _itertools.count(1)

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(0.0, 0.02),
                                  bias_attr=False)

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.llama(input_ids, attn_mask)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = F.linear(h, self.llama.embed_tokens.weight.t())
        if labels is not None:
            loss = F.cross_entropy(
                M.reshape(logits, [-1, self.config.vocab_size]),
                M.reshape(labels, [-1]))
            if self.config.num_experts > 0:
                # router load-balancing term (Switch/GShard); without it
                # capacity dispatch lets the router collapse and drop tokens
                coef = self.config.router_aux_loss_coef
                for layer in self.llama.layers:
                    aux = getattr(layer.mlp, "l_aux", None)
                    if aux is not None and coef > 0:
                        loss = loss + coef * aux
            return loss, logits
        return logits

    # ---- the serving path (LLMEngine; ISSUE 27) -----------------------
    # ``LLMEngine`` builds its prefill-chunk and decode graphs from these
    # calls and nothing else of the model: embed, one ``serve_layer`` a
    # layer with an attention-state handle (``serving.paged_attention``:
    # ``state.rope`` rotates at the state's positions, ``state.attend``
    # writes k and v and attends over the request's state), norm, head.
    #: device-side counters ``serve_layer`` adds to (none here)
    serve_counters = ()

    def kv_layout(self):
        from ..inference.serving.kv_cache import uniform_layout

        return uniform_layout(self.config)

    def serve_dtype(self):
        return self.llama.layers[0].self_attn.k_proj.weight.dtype

    def serve_embed(self, ids):
        return self.llama.embed_tokens(Tensor._wrap(ids))

    def serve_layer(self, i, x, state):
        layer = self.llama.layers[i]
        attn = layer.self_attn
        h = layer.input_layernorm(x)
        b, s = x.shape[0], x.shape[1]
        q = M.reshape(attn.q_proj(h), [b, s, attn.num_heads, attn.head_dim])
        k = M.reshape(attn.k_proj(h),
                      [b, s, attn.num_kv_heads, attn.head_dim])
        v = M.reshape(attn.v_proj(h),
                      [b, s, attn.num_kv_heads, attn.head_dim])
        cos_t, sin_t = self.llama.rope_cos._data, self.llama.rope_sin._data
        qa = state.rope(q._data, cos_t, sin_t)
        ka = state.rope(k._data, cos_t, sin_t)
        out = state.attend(qa, ka, v._data,
                           scale=1.0 / math.sqrt(attn.head_dim))
        x = x + attn.o_proj(M.reshape(Tensor._wrap(out), [b, s, -1]))
        return x + layer.mlp(layer.post_attention_layernorm(x))

    def serve_norm(self, x):
        return self.llama.norm(x)

    def serve_head(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        return F.linear(h, self.llama.embed_tokens.weight.t())

    # ---- generation (static-capacity KV-cache decode) ----------------
    #: decode caches round their capacity up to this multiple so compile
    #: count is O(capacity buckets), not O(distinct prompt+max_new sums)
    DECODE_CAPACITY_BUCKET = 64

    def _unique_params(self):
        seen, params = set(), []
        for _, p in self.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                params.append(p)
        return params

    def _cached_step_jit(self):
        """Lazily-built compiled (prefill+decode) step over the static KV
        cache: ``(param_arrays, ids, pos, k_bufs, v_bufs) -> (last-position
        logits [B, V], k_bufs, v_bufs)``. One executable per (batch,
        seq-len, capacity) shape — decode steps all share one — counted in
        ``paddle.jit.cache_stats()`` under this model's ``llama_decode#n``
        row. Cache buffers are donated on TPU backends."""
        jit = self.__dict__.get("_gen_jit")
        if jit is not None:
            return jit
        from ..core import state as _state
        from ..jit.cache import CountingJit

        params = self._unique_params()
        model = self

        def pure(param_arrays, ids, pos, k_bufs, v_bufs):
            old = [p._data for p in params]
            try:
                for p, a in zip(params, param_arrays):
                    p._data = a
                with _state.trace_guard():
                    h, k_bufs, v_bufs = model.llama.forward_cached(
                        Tensor._wrap(ids), k_bufs, v_bufs, pos)
                    h = h[:, -1:]
                    logits = (model.lm_head(h) if model.lm_head is not None
                              else F.linear(
                                  h, model.llama.embed_tokens.weight.t()))
            finally:
                for p, a in zip(params, old):
                    p._data = a

            def arr(x):
                return x._data if isinstance(x, Tensor) else x

            return (arr(logits)[:, 0], [arr(b) for b in k_bufs],
                    [arr(b) for b in v_bufs])

        name = f"llama_decode#{next(LlamaForCausalLM._decode_instance_ids)}"
        jit = CountingJit(pure, name, donate_argnums=(3, 4))
        self.__dict__["_gen_jit"] = jit
        self.__dict__["_gen_params"] = params
        return jit

    def cached_step(self, ids, cache: StaticKVCache):
        """Run one compiled static-cache step over ``ids`` (np/jnp
        [B, s] int32) at the cache's current offset; advances the cache
        and returns last-position logits as a jax array [B, V]."""
        import jax.numpy as jnp

        jit = self._cached_step_jit()
        params = self.__dict__["_gen_params"]
        logits, cache.k, cache.v = jit(
            [p._data for p in params], jnp.asarray(ids, jnp.int32),
            np.int32(cache.pos), cache.k, cache.v)
        cache.pos += int(ids.shape[1])
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, top_p=None, eos_token_id=None, seed=None,
                 do_sample=False):
        """Autoregressive decode against a preallocated static-capacity KV
        cache (capability analog of PaddleNLP's model.generate
        greedy/sampling path): one compiled prefill over the prompt writes
        K/V at offset 0, then each new token runs the SAME compiled decode
        step at an advancing offset — O(1) XLA compiles per capacity
        bucket across the whole decode instead of the old concat-grown
        cache's compile-and-copy per token. Returns [B, prompt + new]."""
        rng = np.random.RandomState(seed)
        b, s = input_ids.shape[0], input_ids.shape[1]
        limit = self.config.max_position_embeddings
        if s + max_new_tokens > limit:
            raise ValueError(
                f"generate: prompt ({s}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_position_embeddings "
                f"({limit})")
        bucket = self.DECODE_CAPACITY_BUCKET
        capacity = min(-(-(s + max_new_tokens) // bucket) * bucket, limit)
        dtype = self.llama.layers[0].self_attn.k_proj.weight.dtype
        cache = StaticKVCache(self.config, b, capacity, dtype=dtype)

        logits = self.cached_step(input_ids._data
                                  if isinstance(input_ids, Tensor)
                                  else input_ids, cache)
        out_ids = [input_ids]
        finished = np.zeros(b, bool)
        for step in range(max_new_tokens):
            nxt = sample_next_tokens(logits, do_sample=do_sample,
                                     temperature=temperature, top_k=top_k,
                                     top_p=top_p, rng=rng)
            if eos_token_id is not None:
                nxt = np.where(finished, eos_token_id, nxt)
                finished |= nxt == eos_token_id
            cur = nxt.astype(np.int32)[:, None]
            out_ids.append(Tensor(cur))
            if eos_token_id is not None and finished.all():
                break
            if step + 1 < max_new_tokens:  # no wasted trailing forward
                logits = self.cached_step(cur, cache)
        return M.concat(out_ids, axis=1)

    # ---- sharding plan (consumed by auto_parallel / graft dryrun) ----
    @staticmethod
    def tp_partition_spec(param_name: str):
        """Megatron TP plan as (dim -> mesh axis) specs keyed on param name.
        Column-parallel: shard output dim on 'tp'; row-parallel: input dim.
        Weights are stored [in, out] (Linear convention)."""
        n = param_name
        if "embed_tokens" in n or "lm_head" in n:
            return {1: "tp"} if "lm_head" in n else {0: "tp"}
        if any(k in n for k in ("q_proj", "k_proj", "v_proj", "gate_proj",
                                "up_proj")):
            return {1: "tp"}  # column parallel: [in, out/tp]
        if any(k in n for k in ("o_proj", "down_proj")):
            return {0: "tp"}  # row parallel: [in/tp, out]
        if any(k in n for k in ("gate_w", "up_w", "down_w")):
            return {0: "ep"}  # expert parallel: [E/ep, ...]
        return {}


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=384,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=256,
                       **kw)


def llama_small(**kw):
    return LlamaConfig(vocab_size=8192, hidden_size=512,
                       intermediate_size=1408, num_hidden_layers=8,
                       num_attention_heads=8, num_key_value_heads=8,
                       max_position_embeddings=2048, **kw)


def llama_125m(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=768,
                       intermediate_size=2048, num_hidden_layers=12,
                       num_attention_heads=12, num_key_value_heads=12,
                       max_position_embeddings=2048, **kw)


def llama_1b(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5504, num_hidden_layers=22,
                       num_attention_heads=16, num_key_value_heads=16,
                       max_position_embeddings=2048, **kw)


def llama_7b(**kw):
    return LlamaConfig(**kw)


def llama_13b(**kw):
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40, **kw)


# ---- pipeline-parallel variant --------------------------------------------
# Capability analog of PaddleNLP's LlamaForCausalLMPipe: the model expressed
# as a PipelineLayer (LayerDesc list) so the compiled stage-scan engine
# (distributed/meta_parallel/pp_scan.py) can pipeline it. Each block carries
# its own rope buffers so the per-stage forward is a pure x -> x map (the
# activation shape the ppermute rotation requires).


class LlamaEmbeddingPipe(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(0.0, 0.02))

    def forward(self, input_ids):
        return self.embed_tokens(input_ids)


class LlamaDecoderLayerPipe(LlamaDecoderLayer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__(config, layer_idx)
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.head_dim, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, x):
        s = x.shape[1]
        return super().forward(x, self.rope_cos[:s], self.rope_sin[:s])


class LlamaHeadPipe(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(0.0, 0.02), bias_attr=False)

    def forward(self, h):
        return self.lm_head(self.norm(h))


class LlamaCausalLoss(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.vocab_size = config.vocab_size

    def forward(self, logits, labels):
        return F.cross_entropy(M.reshape(logits, [-1, self.vocab_size]),
                               M.reshape(labels, [-1]))


def LlamaForCausalLMPipe(config: LlamaConfig, num_stages: int, **pp_kwargs):
    """Build the flagship model as a PipelineLayer for the stage-scan engine.
    MoE layers are structurally distinct from dense blocks (breaks the
    uniform-stack contract), so the pipe variant requires num_experts=0."""
    from ..distributed.meta_parallel import LayerDesc, PipelineLayer

    if config.num_experts > 0:
        raise ValueError("LlamaForCausalLMPipe requires a dense config "
                         "(num_experts=0); MoE layers break the uniform "
                         "block stack the stage scan pipelines")
    descs = ([LayerDesc(LlamaEmbeddingPipe, config)]
             + [LayerDesc(LlamaDecoderLayerPipe, config, i)
                for i in range(config.num_hidden_layers)]
             + [LayerDesc(LlamaHeadPipe, config)])
    return PipelineLayer(layers=descs, num_stages=num_stages,
                         loss_fn=LlamaCausalLoss(config), **pp_kwargs)
