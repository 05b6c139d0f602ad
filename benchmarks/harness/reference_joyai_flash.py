"""The plain reference of JoyAI-LLM-Flash's decoder, written from its
``config.json`` straight in ``jax.numpy``: float32, matmul precision
"highest", the EXPANDED form of latent attention, no kernel, no cache, no
absorbed projections, no batching tricks. It shares no code with
``paddle_tpu``: only the names of the parameters, which is how it is handed
the same weights. Queries are taken a block at a time so that a sequence of
4,500 tokens fits (the scores of one block are ``[heads, 512, S]``).

What the config states and this computes (departures and assumptions are
the configuration file's ``assumed``):

* pre-norm residual block, RMS norm (``rms_norm_eps``), SwiGLU, untied head,
  no bias;
* latent attention: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` in heads of
  ``[q_nope | q_rope]``; ``x W_kva -> [c | k_r]``, ``c <- norm(c)``, one
  rotary key ``k_r`` for all heads; ``c W_kvb`` in heads of ``[k_nope | v]``;
  ``k = [k_nope | k_r]``; scores ``q.k / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, causal softmax, heads joined through ``W_o``;
* rotary embedding on the rope dims, pairs ``(2i, 2i + 1)`` as the config
  names them (``rope_interleave``), angle ``position x rope_theta^(-2i /
  qk_rope_head_dim)``, no scaling;
* feed-forward: layers before ``first_k_dense_replace`` dense; the others
  scores ``sigmoid(x W_r)`` in float32, the ``num_experts_per_tok`` experts
  with the largest ``score + e_score_correction_bias``, combine weights the
  uncorrected scores of the chosen over their sum, times
  ``routed_scaling_factor``, plus the shared expert with weight 1;
* the prediction module (``mtp_logits``), DeepSeek-V3's: ``[norm_e(emb(t +
  1)) ; norm_h(h_t)] W_eh``, one block of the expert kind, a norm, the
  shared head.

**One chip's share.** ``experts_held`` lists the global ids of the experts
whose weights ``weights`` holds (``mlp.experts.<n>`` is the n-th of them).
The router is whole; the loop runs over the held experts and adds what they
give, and the shared expert, which every chip computes alike, is added once;
what the absent experts would have added is left out, and that partial
result goes on to the next layer.

**Where its own scores tie**: ``with_scores`` and ``nudge`` as
``reference_mimo_v2`` has them: the corrected scores of every expert layer
at every position come back, and ``nudge`` (``{layer: [B, S, E]}``; the
prediction module's block is layer ``num_hidden_layers``) is added to them
for the choice only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows a block of attention takes
Q_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope_pairs(x, theta):
    """x [B, S, H, D]: pairs (2i, 2i + 1) rotated by position *
    theta^(-2i / D), as named."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rope", "v_dim", "theta", "eps"))
def _attention(x, w, *, heads, rank, nope, rope, v_dim, theta, eps):
    """x + attention(norm(x)), expanded."""
    with jax.default_matmul_precision("highest"):
        b, s, _ = x.shape
        h = _rms(x, w["input_layernorm.weight"], eps)
        cq = _rms(h @ w["self_attn.q_a_proj.weight"].astype(F32),
                  w["self_attn.q_a_layernorm.weight"], eps)
        q = (cq @ w["self_attn.q_b_proj.weight"].astype(F32)).reshape(
            b, s, heads, nope + rope)
        kv = h @ w["self_attn.kv_a_proj_with_mqa.weight"].astype(F32)
        c = _rms(kv[..., :rank], w["self_attn.kv_a_layernorm.weight"], eps)
        k_r = _rope_pairs(kv[..., None, rank:], theta)           # one head
        q = jnp.concatenate(
            [q[..., :nope], _rope_pairs(q[..., nope:], theta)], -1)
        e = (c @ w["self_attn.kv_b_proj.weight"].astype(F32)).reshape(
            b, s, heads, nope + v_dim)
        k = jnp.concatenate(
            [e[..., :nope], jnp.broadcast_to(k_r, (b, s, heads, rope))], -1)
        v = e[..., nope:]
        t = jnp.arange(s)
        outs = []
        for q0 in range(0, s, Q_BLOCK):
            qb = q[:, q0:q0 + Q_BLOCK]
            z = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(nope + rope))
            see = t[None, :] <= t[q0:q0 + Q_BLOCK, None]
            p = jax.nn.softmax(jnp.where(see, z, -jnp.inf), -1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
        attn = jnp.concatenate(outs, 1).reshape(b, s, heads * v_dim)
        return x + attn @ w["self_attn.o_proj.weight"].astype(F32)


@jax.jit
def _swiglu(h, gate_w, up_w, down_w):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ gate_w.astype(F32)) * (h @ up_w.astype(F32))) \
            @ down_w.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk"))
def _route(x, w, nudge, *, eps, top_k, norm_topk):
    """The normed input, the chosen experts, their combine weights (before
    the scaling factor), and the corrected scores the choice was made from
    (``nudge`` is added to them for the choice, and is not in what is
    returned)."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["post_attention_layernorm.weight"], eps)
        scores = jax.nn.sigmoid(h @ w["mlp.router.weight"].astype(F32))
        corrected = scores + w["mlp.router.e_score_correction_bias"].astype(F32)
        _, sel = jax.lax.top_k(corrected + nudge, top_k)
        comb = jnp.take_along_axis(scores, sel, -1)
        if norm_topk:
            comb = comb / jnp.sum(comb, -1, keepdims=True)
        return h, sel, comb, corrected


def _feed_forward(x, w, model, held, is_moe, nudge=None):
    """x + ffn(norm(x)) and, for an expert layer, the corrected scores."""
    eps = model["rms_norm_eps"]
    if not is_moe:
        h = _rms(x, w["post_attention_layernorm.weight"], eps)
        return x + _swiglu(h, w["mlp.gate_proj.weight"],
                           w["mlp.up_proj.weight"],
                           w["mlp.down_proj.weight"]), None
    h, sel, comb, corrected = _route(
        x, w, jnp.asarray(0.0 if nudge is None else nudge, F32), eps=eps,
        top_k=model["num_experts_per_tok"], norm_topk=model["norm_topk_prob"])
    if model.get("routed_scaling_factor"):
        comb = comb * model["routed_scaling_factor"]
    out = x
    for row, e in enumerate(held):
        weight = jnp.sum(jnp.where(sel == e, comb, 0.0), -1)
        out = out + _swiglu(
            h, w[f"mlp.experts.{row}.gate_proj.weight"],
            w[f"mlp.experts.{row}.up_proj.weight"],
            w[f"mlp.experts.{row}.down_proj.weight"]) * weight[..., None]
    if model.get("n_shared_experts"):
        out = out + _swiglu(h, w["shared_experts.gate_proj.weight"],
                            w["shared_experts.up_proj.weight"],
                            w["shared_experts.down_proj.weight"])
    return out, corrected


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w, eps) @ head_w.astype(F32)


def _under(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def _block(x, w, model, held, is_moe, nudge=None):
    x = _attention(
        x, w, heads=model["num_attention_heads"], rank=model["kv_lora_rank"],
        nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
        v_dim=model["v_head_dim"], theta=float(model["rope_theta"]),
        eps=model["rms_norm_eps"])
    return _feed_forward(x, w, model, held, is_moe, nudge)


def _held(model, experts_held):
    return tuple(range(model["n_routed_experts"])) if experts_held is None \
        else tuple(int(e) for e in experts_held)


def logits(weights, ids, model, experts_held=None, with_scores=False,
           nudge=None, with_hidden=False):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config's keys (a dict);
    ``experts_held``: global ids of the experts ``weights`` holds, all of
    them if None; ``nudge``: ``{layer: [B, S, E]}``. Returns float32 logits
    [B, S, V]; with ``with_scores`` also ``{layer: corrected scores [B, S,
    E]}`` of the expert layers; with ``with_hidden`` also the last layer's
    output before the final norm [B, S, D]."""
    held = _held(model, experts_held)
    x = weights["model.embed_tokens.weight"][ids].astype(F32)
    scores = {}
    for i in range(model["num_hidden_layers"]):
        is_moe = i >= model["first_k_dense_replace"]
        x, sc = _block(x, _under(weights, f"model.layers.{i}."), model, held,
                       is_moe, (nudge or {}).get(i))
        if is_moe:
            scores[i] = sc
    out = (_head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                 eps=model["rms_norm_eps"]),)
    if with_scores:
        out += (scores,)
    if with_hidden:
        out += (x,)
    return out if len(out) > 1 else out[0]


def mtp_logits(weights, hidden, next_ids, model, experts_held=None,
               with_scores=False, nudge=None):
    """The prediction module: ``hidden`` [B, S, D] float32 (position t: the
    trunk's last layer's output before the final norm), ``next_ids`` [B, S]
    (position t: token t + 1). Returns float32 logits [B, S, V] for token
    t + 2 (and the block's corrected scores with ``with_scores``)."""
    eps = model["rms_norm_eps"]
    w = _under(weights, "model.mtp.")
    with jax.default_matmul_precision("highest"):
        u = _rms(weights["model.embed_tokens.weight"][next_ids].astype(F32),
                 w["enorm.weight"], eps)
        x = jnp.concatenate(
            [u, _rms(jnp.asarray(hidden, F32), w["hnorm.weight"], eps)], -1) \
            @ w["eh_proj.weight"].astype(F32)
    x, sc = _block(x, _under(w, "block."), model, _held(model, experts_held),
                   True, nudge)
    out = _head(x, w["norm.weight"], weights["lm_head.weight"], eps=eps)
    return (out, sc) if with_scores else out


def row_error(got, want):
    """|got - want| / |want| in the Euclidean norm over one logits row
    (``reference.row_error``'s definition, repeated so that this file
    stands alone)."""
    import numpy as np

    want = np.asarray(want, np.float64)
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt((d * d).sum()) / (np.sqrt((want * want).sum()) + 1e-9))
