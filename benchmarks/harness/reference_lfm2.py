"""The plain reference of LFM2-MoE's decoder (LiquidAI/LFM2-8B-A1B), written
from its ``config.json`` straight in ``jax.numpy``: float32, matmul precision
"highest", the short convolution as ``conv_L_cache`` shifted sums, attention
over the whole sequence, the experts by a loop. No kernel, no cache, no
batching of requests. It shares no code with ``paddle_tpu``: only the names
of the parameters, which is how it is handed the same weights. Attention
takes its queries a block at a time so that a sequence of 6,000 tokens fits
at the published widths.

What the config states and this computes:

* layer ``i``: ``h = x + mixer_i(N(x))``, ``x' = h + ffn_i(N(h))``; then
  ``N`` (``embedding_norm``) and the head TIED to the embedding, ``x E^T``.
  ``N(x) = x / rms(x) * w``, eps ``norm_eps``;
* ``conv`` layers (``layer_types``): ``[B | C | x~] = u W_in``, ``v = B *
  x~``, ``z_t = sum_j w[:, j] v_{t - K + 1 + j}`` with zeros before the
  sequence and no bias, no activation; ``out = (C * z) W_out``;
* ``full_attention`` layers: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` of ``hidden / heads``; ``q`` and ``k`` RMS-normed
  a head, rotary over the whole head (rotate-half, ``rope_theta``), causal
  softmax at ``head^-1/2``;
* ``ffn``: SwiGLU at ``intermediate_size`` in the first ``num_dense_layers``
  layers; after them the experts: ``sigmoid(u W_r)`` in float32, the
  ``num_experts_per_tok`` largest of ``score + expert_bias`` chosen, weighed
  by their uncorrected scores over the scores' sum (``norm_topk_prob``),
  times ``routed_scaling_factor``; SwiGLU experts at
  ``moe_intermediate_size``, no shared expert.

DEPARTURE, the engine's as well: the published code divides the chosen
scores by ``sum + 1e-6``; this by the sum. Four sigmoid scores sum to a few
tenths at least, so the two differ by a few parts in a million.

``experts_held`` and ``choice`` (the experts handed in, ``{layer: [B, S,
top_k]}``) and ``with_scores`` are ``reference_nemotron_h``'s: a row of this
model reads the tokens before it through every layer, and a token routed the
other way where two scores all but tie moves it by more than the rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference_nemotron_h import choice_gaps, row_error  # noqa: F401

F32 = jnp.float32
#: query rows a block of attention takes
Q_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


@functools.partial(jax.jit, static_argnames=("kernel", "eps"))
def _conv_mixer(x, w, *, kernel, eps):
    """x + shortconv(norm(x))."""
    with jax.default_matmul_precision("highest"):
        s, d = x.shape[1], x.shape[2]
        u = _rms(x, w["operator_norm.weight"], eps)
        proj = u @ w["conv.in_proj.weight"].astype(F32)
        b, c, xt = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
        v = jnp.pad(b * xt, [(0, 0), (kernel - 1, 0), (0, 0)])
        cw = w["conv.conv_weight"].astype(F32)                   # [D, K]
        z = sum(v[:, j:j + s] * cw[:, j] for j in range(kernel))
        return x + (c * z) @ w["conv.out_proj.weight"].astype(F32)


def _rotate(x, theta):
    """Rotary over the whole head of ``x [B, S, H, D]`` at positions 0..S-1,
    rotate-half."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)[None, :, None]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)[None, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def _attention(x, w, *, heads, kv_heads, eps, theta):
    """x + attention(norm(x))."""
    with jax.default_matmul_precision("highest"):
        bsz, s, hidden = x.shape
        d = hidden // heads
        u = _rms(x, w["operator_norm.weight"], eps)
        q = (u @ w["self_attn.q_proj.weight"].astype(F32)).reshape(
            bsz, s, heads, d)
        k = (u @ w["self_attn.k_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, d)
        v = (u @ w["self_attn.v_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, d)
        q = _rotate(_rms(q, w["self_attn.q_layernorm.weight"], eps), theta)
        k = _rotate(_rms(k, w["self_attn.k_layernorm.weight"], eps), theta)
        k, v = (jnp.repeat(m, heads // kv_heads, axis=2) for m in (k, v))
        t = jnp.arange(s)
        outs = []
        for q0 in range(0, s, Q_BLOCK):
            z = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + Q_BLOCK], k) \
                / jnp.sqrt(F32(d))
            see = t[None, :] <= t[q0:q0 + Q_BLOCK, None]
            p = jax.nn.softmax(jnp.where(see, z, -jnp.inf), -1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
        attn = jnp.concatenate(outs, 1).reshape(bsz, s, heads * d)
        return x + attn @ w["self_attn.out_proj.weight"].astype(F32)


@jax.jit
def _swiglu(u, gate_w, up_w, down_w):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ gate_w.astype(F32)) * (u @ up_w.astype(F32))) \
            @ down_w.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn_norm(x, w, *, eps):
    return _rms(x, w, eps)


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk"))
def _route(u, router_w, bias, choice, *, top_k, norm_topk):
    """The chosen experts (``choice`` if it is handed in, else the
    ``top_k`` of the corrected scores), their combine weights and the
    corrected scores."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(u @ router_w.astype(F32))
        corrected = scores + bias.astype(F32)
        sel = jax.lax.top_k(corrected, top_k)[1] if choice is None else choice
        comb = jnp.take_along_axis(scores, sel, -1)
        if norm_topk:
            comb = comb / jnp.sum(comb, -1, keepdims=True)
        return sel, comb, corrected


def _ffn(x, w, model, held, choice=None):
    """x + ffn(norm(x)) of an expert layer, and the corrected scores."""
    u = _ffn_norm(x, w["ffn_norm.weight"], eps=model["norm_eps"])
    sel, comb, corrected = _route(
        u, w["feed_forward.router.weight"],
        w["feed_forward.router.expert_bias"],
        None if choice is None else jnp.asarray(choice, jnp.int32),
        top_k=model["num_experts_per_tok"], norm_topk=model["norm_topk_prob"])
    comb = comb * float(model.get("routed_scaling_factor", 1.0))
    out = x
    for row, e in enumerate(held):
        weight = jnp.sum(jnp.where(sel == e, comb, 0.0), -1)
        p = f"feed_forward.experts.{row}."
        out = out + _swiglu(u, w[p + "gate_proj.weight"],
                            w[p + "up_proj.weight"],
                            w[p + "down_proj.weight"]) * weight[..., None]
    return out, corrected


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w, eps) @ embed.astype(F32).T


def _under(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def expert_layers(model):
    """The layers that have experts (after the dense ones)."""
    return list(range(int(model["num_dense_layers"]),
                      int(model["num_hidden_layers"])))


def logits(weights, ids, model, experts_held=None, with_scores=False,
           choice=None):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config's keys (a dict);
    ``experts_held``: global ids of the experts ``weights`` holds, all of
    them if None; ``choice``: ``{layer: [B, S, top_k]}``. Returns float32
    logits [B, S, V]; with ``with_scores`` also ``{layer: corrected scores
    [B, S, E]}`` of the expert layers."""
    held = tuple(range(model["num_experts"])) if experts_held is None \
        else tuple(int(e) for e in experts_held)
    eps = model["norm_eps"]
    embed = weights["model.embed_tokens.weight"]
    x = embed[jnp.asarray(ids)].astype(F32)
    scores = {}
    types = model["layer_types"][:model["num_hidden_layers"]]
    for i, kind in enumerate(types):
        w = _under(weights, f"model.layers.{i}.")
        if kind == "conv":
            x = _conv_mixer(x, w, kernel=int(model["conv_L_cache"]), eps=eps)
        elif kind == "full_attention":
            x = _attention(x, w, heads=model["num_attention_heads"],
                           kv_heads=model["num_key_value_heads"], eps=eps,
                           theta=float(model["rope_theta"]))
        else:
            raise ValueError(f"no layer type {kind!r} in this model")
        if i < model["num_dense_layers"]:
            x = x + _swiglu(_ffn_norm(x, w["ffn_norm.weight"], eps=eps),
                            w["feed_forward.gate_proj.weight"],
                            w["feed_forward.up_proj.weight"],
                            w["feed_forward.down_proj.weight"])
        else:
            x, scores[i] = _ffn(x, w, model, held, (choice or {}).get(i))
    out = _head(x, weights["model.embedding_norm.weight"], embed, eps=eps)
    return (out, scores) if with_scores else out
