"""The plain reference of MiMo-V2-Flash's decoder, written from its
``config.json`` straight in ``jax.numpy``: float32, matmul precision
"highest", no kernel, no cache, no batching tricks, one jitted function a
layer kind. It shares no code with ``paddle_tpu``: only the names of the
parameters, which is how it is handed the same weights.

What the config states and this computes (departures and assumptions are
the configuration file's ``assumed``):

* pre-norm residual block, RMS norm (``layernorm_epsilon``), SwiGLU, untied
  head, no bias;
* attention by ``hybrid_layer_pattern`` (0 full, 1 window): q and k heads
  ``head_dim`` wide, v heads ``v_head_dim``; rotary embedding on the first
  ``int(head_dim * partial_rotary_factor)`` dims of a head, half-split pairs,
  base ``rope_theta`` (full) or ``swa_rope_theta`` (window); scores
  ``q.k / sqrt(head_dim)``; a window layer's query t sees keys
  ``t - sliding_window + 1 .. t`` and a learned per-head sink logit joins
  the softmax's denominator and carries no value; the output is scaled by
  ``attention_value_scale``;
* feed-forward by ``moe_layer_freq``: dense SwiGLU, or experts: scores
  ``sigmoid(x W_r)`` in float32, the ``num_experts_per_tok`` experts with the
  largest ``score + e_score_correction_bias``, combine weights the
  uncorrected scores of the chosen over their sum.

**One chip's share.** ``experts_held`` lists the global ids of the experts
whose weights ``weights`` holds (``mlp.experts.<n>`` is the n-th of them). The router is
whole; the loop below runs over the held experts with a mask and adds what
they give; what the absent experts would have added is left out, and that
partial result goes on to the next layer. The three multi-token-prediction
layers of the model card have no key in ``config.json`` and are left out.

**Where its own scores tie.** The reference routes by itself, from its own
float32 scores. ``logits(..., with_scores=True)`` also gives every expert
layer's corrected scores at every position: how near the reference itself
was to routing otherwise is for the caller to read from them. An engine in
bf16 sees two scores a rounding apart and may take the other expert; that
is another valid result, not an error. ``nudge`` (``{layer: float32 [B, S,
E]}``) is added to the corrected scores of those layers FOR THE CHOICE
ONLY, as the correction bias is, so that a caller can make the reference
take or leave one expert at one (layer, position) and nothing else
differently: a row routed otherwise can then still be COMPARED, against the
reference's other routing, rather than left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope_part(x, rot, theta):
    """x [B, S, H, D]: the first ``rot`` dims rotated as pairs (i, i + rot/2)
    by position * theta^(-2i/rot); the rest unrotated."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "v_dim", "rot", "theta", "window",
    "value_scale", "eps"))
def _attention(x, w, *, heads, kv_heads, head_dim, v_dim, rot, theta, window,
               value_scale, eps):
    """x + attention(norm(x)); ``window`` None for a full layer."""
    with jax.default_matmul_precision("highest"):
        b, s, _ = x.shape
        h = _rms(x, w["input_layernorm.weight"], eps)
        q = (h @ w["self_attn.q_proj.weight"].astype(F32)).reshape(b, s, heads, head_dim)
        k = (h @ w["self_attn.k_proj.weight"].astype(F32)).reshape(b, s, kv_heads, head_dim)
        v = (h @ w["self_attn.v_proj.weight"].astype(F32)).reshape(b, s, kv_heads, v_dim)
        q, k = _rope_part(q, rot, theta), _rope_part(k, rot, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        z = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(head_dim))
        t = jnp.arange(s)
        see = t[None, :] <= t[:, None]
        if window is not None:
            see = see & (t[None, :] > t[:, None] - window)
        z = jnp.where(see, z, -jnp.inf)
        m = jnp.max(z, -1, keepdims=True)
        e = jnp.exp(z - m)
        den = jnp.sum(e, -1, keepdims=True)
        if "self_attn.attention_sink_bias" in w:
            sink = w["self_attn.attention_sink_bias"].astype(F32)
            m2 = jnp.maximum(m, sink[None, :, None, None])
            e = e * jnp.exp(m - m2)
            den = jnp.sum(e, -1, keepdims=True) + jnp.exp(sink[None, :, None, None] - m2)
        p = e / den
        attn = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, heads * v_dim)
        return x + (attn * value_scale) @ w["self_attn.o_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, w, *, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["post_attention_layernorm.weight"], eps)
        gate = jax.nn.silu(h @ w["mlp.gate_proj.weight"].astype(F32))
        up = h @ w["mlp.up_proj.weight"].astype(F32)
        return x + (gate * up) @ w["mlp.down_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk"))
def _route(x, w, nudge, *, eps, top_k, norm_topk):
    """The normed input, the chosen experts, their combine weights, and the
    corrected scores the choice was made from (``nudge`` [B, S, E] is added
    to them for the choice, and is not in what is returned)."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["post_attention_layernorm.weight"], eps)
        scores = jax.nn.sigmoid(h @ w["mlp.router.weight"].astype(F32))
        corrected = scores + w["mlp.router.e_score_correction_bias"].astype(F32)
        _, sel = jax.lax.top_k(corrected + nudge, top_k)
        comb = jnp.take_along_axis(scores, sel, -1)
        if norm_topk:
            comb = comb / jnp.sum(comb, -1, keepdims=True)
        return h, sel, comb, corrected


@jax.jit
def _expert(h, gate_w, up_w, down_w, weight):
    """One expert on every position, times its combine weight there (0
    where it was not chosen)."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(h @ gate_w.astype(F32)) * (h @ up_w.astype(F32))) \
            @ down_w.astype(F32)
        return y * weight[..., None]


def _expert_ffn(x, w, *, eps, top_k, norm_topk, scaling, held, nudge=None):
    if nudge is None:
        nudge = jnp.zeros((), F32)
    h, sel, comb, corrected = _route(x, w, jnp.asarray(nudge, F32), eps=eps,
                                     top_k=top_k, norm_topk=norm_topk)
    if scaling:
        comb = comb * scaling
    out = x
    for row, e in enumerate(held):
        weight = jnp.sum(jnp.where(sel == e, comb, 0.0), -1)
        out = out + _expert(h, w[f"mlp.experts.{row}.gate_proj.weight"],
                            w[f"mlp.experts.{row}.up_proj.weight"],
                            w[f"mlp.experts.{row}.down_proj.weight"], weight)
    return out, corrected


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w, eps) @ head_w.astype(F32)


def logits(weights, ids, model, experts_held=None, with_scores=False,
           nudge=None):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config's keys (a dict);
    ``experts_held``: global ids of the experts ``weights`` holds, all of
    them if None; ``nudge``: ``{layer: [B, S, E]}``, see the module
    docstring. Returns float32 logits [B, S, V] (and ``{layer: corrected
    scores [B, S, E]}`` of the expert layers, with ``with_scores``)."""
    held = tuple(range(model["n_routed_experts"])) if experts_held is None \
        else tuple(int(e) for e in experts_held)
    eps = model["layernorm_epsilon"]
    x = weights["model.embed_tokens.weight"][ids].astype(F32)
    scores = {}
    for i in range(model["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}
        window = bool(model["hybrid_layer_pattern"][i])
        p = "swa_" if window else ""
        head_dim = model[p + "head_dim"]
        x = _attention(
            x, w, heads=model[p + "num_attention_heads"],
            kv_heads=model[p + "num_key_value_heads"], head_dim=head_dim,
            v_dim=model[p + "v_head_dim"],
            rot=int(head_dim * model["partial_rotary_factor"]),
            theta=float(model["swa_rope_theta" if window else "rope_theta"]),
            window=model["sliding_window"] if window else None,
            value_scale=model["attention_value_scale"], eps=eps)
        if model["moe_layer_freq"][i]:
            x, scores[i] = _expert_ffn(
                x, w, eps=eps, top_k=model["num_experts_per_tok"],
                norm_topk=model["norm_topk_prob"],
                scaling=model.get("routed_scaling_factor"), held=held,
                nudge=(nudge or {}).get(i))
        else:
            x = _dense_ffn(x, w, eps=eps)
    out = _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                eps=eps)
    return (out, scores) if with_scores else out


def row_error(got, want):
    """|got - want| / |want| in the Euclidean norm over one logits row
    (``reference.row_error``'s definition, repeated so that this file
    stands alone)."""
    import numpy as np

    want = np.asarray(want, np.float64)
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt((d * d).sum()) / (np.sqrt((want * want).sum()) + 1e-9))
