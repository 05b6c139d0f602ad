"""The plain reference of Qwen3-Next's decoder (Qwen3-Next-80B-A3B-Instruct),
written from its ``config.json`` straight in ``jax.numpy``: float32, matmul
precision "highest", the gated delta rule TOKEN BY TOKEN (``lax.scan`` over
``t``: no chunked form, no cache, no kernel), the convolution as
``linear_conv_kernel_dim`` shifted sums, the experts by a loop. It shares no
code with ``paddle_tpu``: only the names of the parameters, which is how it
is handed the same weights. Attention takes its queries a block at a time so
that a sequence of 4,500 tokens fits at the published widths.

What the config states and this computes (departures and assumptions are the
configuration file's ``assumed``; none beyond the cut):

* layer ``i``: ``x <- x + mixer_i(N(x))``, ``x <- x + moe(N(x))``; a final
  ``N`` and an untied head. ``N(x) = x / rms(x) * (1 + w)`` (zero-centred),
  eps ``rms_norm_eps``. Mixer ``i`` is full attention where ``(i + 1) %
  full_attention_interval == 0``, else the gated delta mixer;
* gated delta mixer: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``, the
  columns in THAT order (q: key heads x key dim; k likewise; v: value heads x
  value dim; z likewise; b, a a value head), which is the order
  ``paddle_tpu/models/qwen3_next.py`` states for its seeded weights (the
  published checkpoint interleaves them a key head: with weights drawn from
  a seed a fixed permutation of columns is the same model); ``[q | k | v]``
  through a causal depthwise convolution, no bias, then ``silu``, zeros
  before the sequence; ``q, k`` L2-normed a head (eps 1e-6 under the root),
  ``q`` times ``key_dim^-1/2``; key head ``j`` serves value heads ``j r .. j r
  + r - 1``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
  ``S' = exp(g_t) S_{t-1}``, ``d_t = beta_t (v_t - S'^T k_t)``, ``S_t = S' +
  k_t (x) d_t``, ``o_t = S_t^T q_t`` from ``S = 0``; ``y = rms(o) w_n
  silu(z)`` a head (``w_n`` plain), ``out = y W_o``;
* gated attention: a head of ``q_proj`` is ``[query | gate]``; ``q, k <-
  N(q), N(k)`` over ``head_dim``; rotary, half-rotation, on the first
  ``partial_rotary_factor x head_dim``, ``rope_theta``, no scaling; causal
  softmax at ``head_dim^-1/2``; ``attn * sigmoid(gate)``; ``o_proj``;
* experts: ``p = softmax(u W_r)`` over all ``num_experts`` in float32, the
  ``num_experts_per_tok`` largest, their ``p`` over their sum; SwiGLU
  experts; plus ``sigmoid(u w_g) * SwiGLU_shared(u)``.

**One chip's share.** ``experts_held`` lists the global ids of the experts
whose weights ``weights`` holds (``mlp.experts.<n>`` is the n-th of them). The
router is whole; the loop runs over the held experts and adds what they give,
and the shared expert, which every chip computes alike, is added once
(``with_shared=False`` leaves it out: a further chip's part of a layer).

**Where its own scores all but tie.** As ``reference_nemotron_h``: a row
reads its context through a state, so ``choice`` (``{layer: [B, S, top_k]}``
global expert ids) HANDS the routing in, and ``with_scores`` gives back this
reference's own scores (the softmax's ``p``) of every layer at every
position, which ``choice_gaps`` holds a choice against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows a block of attention takes
Q_BLOCK = 512


def _norm(x, w, eps):
    """Zero-centred: ``x / rms(x) * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(F32))


@functools.partial(jax.jit, static_argnames=(
    "key_heads", "heads", "key_dim", "value_dim", "kernel", "eps"))
def _delta(x, w, *, key_heads, heads, key_dim, value_dim, kernel, eps):
    """x + delta(N(x)), the recurrence token by token."""
    with jax.default_matmul_precision("highest"):
        bsz, s, _ = x.shape
        kd, vd = key_heads * key_dim, heads * value_dim
        u = _norm(x, w["input_layernorm.weight"], eps)
        proj = u @ w["linear_attn.in_proj_qkvz.weight"].astype(F32)
        qkv, z = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:]
        ba = u @ w["linear_attn.in_proj_ba.weight"].astype(F32)
        beta = jax.nn.sigmoid(ba[..., :heads])                    # [B,S,H]
        g = -jnp.exp(w["linear_attn.A_log"].astype(F32)) * jax.nn.softplus(
            ba[..., heads:] + w["linear_attn.dt_bias"].astype(F32))
        # the causal convolution: kernel shifted sums, zeros before t = 0
        cw = w["linear_attn.conv_weight"].astype(F32)             # [D, K]
        padded = jnp.pad(qkv, [(0, 0), (kernel - 1, 0), (0, 0)])
        conv = 0.0
        for j in range(kernel):
            conv = conv + padded[:, j:j + s] * cw[:, j]
        qkv = jax.nn.silu(conv)
        per = heads // key_heads

        def unit(m):
            return m * jax.lax.rsqrt(jnp.sum(m * m, -1, keepdims=True) + 1e-6)

        q = unit(qkv[..., :kd].reshape(bsz, s, key_heads, key_dim)) \
            / jnp.sqrt(F32(key_dim))
        k = unit(qkv[..., kd:2 * kd].reshape(bsz, s, key_heads, key_dim))
        q, k = (jnp.repeat(m, per, axis=2) for m in (q, k))       # [B,S,H,N]
        v = qkv[..., 2 * kd:].reshape(bsz, s, heads, value_dim)

        def step(st, inp):
            q_t, k_t, v_t, g_t, b_t = inp       # [B,H,N] [B,H,N] [B,H,P] [B,H]
            st = st * jnp.exp(g_t)[..., None, None]
            d = b_t[..., None] * (v_t - jnp.sum(st * k_t[..., None], -2))
            st = st + k_t[..., None] * d[..., None, :]
            return st, jnp.sum(st * q_t[..., None], -2)

        _, o = jax.lax.scan(
            step, jnp.zeros((bsz, heads, key_dim, value_dim), F32),
            tuple(jnp.swapaxes(m, 0, 1) for m in (q, k, v, g, beta)))
        o = jnp.swapaxes(o, 0, 1)                                 # [B,S,H,P]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        y = o * w["linear_attn.norm_weight"].astype(F32) * jax.nn.silu(
            z.reshape(bsz, s, heads, value_dim))
        return x + y.reshape(bsz, s, vd) \
            @ w["linear_attn.out_proj.weight"].astype(F32)


def _rotate(x, rotary, theta):
    """Half-rotation on the first ``rotary`` of a head, positions 0..S-1."""
    s = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]        # [S, r/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "rotary", "theta", "eps"))
def _attention(x, w, *, heads, kv_heads, head_dim, rotary, theta, eps):
    """x + gated attention(N(x))."""
    with jax.default_matmul_precision("highest"):
        bsz, s, _ = x.shape
        u = _norm(x, w["input_layernorm.weight"], eps)
        qg = (u @ w["self_attn.q_proj.weight"].astype(F32)).reshape(
            bsz, s, heads, 2 * head_dim)
        q, gate = qg[..., :head_dim], qg[..., head_dim:]
        k = (u @ w["self_attn.k_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, head_dim)
        v = (u @ w["self_attn.v_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, head_dim)
        q = _rotate(_norm(q, w["self_attn.q_norm.weight"], eps), rotary, theta)
        k = _rotate(_norm(k, w["self_attn.k_norm.weight"], eps), rotary, theta)
        k, v = (jnp.repeat(m, heads // kv_heads, axis=2) for m in (k, v))
        t = jnp.arange(s)
        outs = []
        for q0 in range(0, s, Q_BLOCK):
            zz = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + Q_BLOCK], k) \
                / jnp.sqrt(F32(head_dim))
            see = t[None, :] <= t[q0:q0 + Q_BLOCK, None]
            p = jax.nn.softmax(jnp.where(see, zz, -jnp.inf), -1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
        attn = jnp.concatenate(outs, 1) * jax.nn.sigmoid(gate)
        return x + attn.reshape(bsz, s, heads * head_dim) \
            @ w["self_attn.o_proj.weight"].astype(F32)


@jax.jit
def _swiglu(u, gate_w, up_w, down_w):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ gate_w.astype(F32)) * (u @ up_w.astype(F32))) \
            @ down_w.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk"))
def _route(x, w, choice, *, eps, top_k, norm_topk):
    """The normed input, the chosen experts (``choice`` if it is handed in,
    else the ``top_k`` of the scores), their combine weights, the scores."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, w["post_attention_layernorm.weight"], eps)
        scores = jax.nn.softmax(u @ w["mlp.router.weight"].astype(F32), -1)
        sel = jax.lax.top_k(scores, top_k)[1] if choice is None else choice
        comb = jnp.take_along_axis(scores, sel, -1)
        if norm_topk:
            comb = comb / jnp.sum(comb, -1, keepdims=True)
        return u, sel, comb, scores


@jax.jit
def _shared_gate(u, gate_w):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(u @ gate_w.astype(F32))


def _experts(x, w, model, held, choice=None, with_shared=True):
    """x + experts(N(x)), and the scores."""
    u, sel, comb, scores = _route(
        x, w, None if choice is None else jnp.asarray(choice, jnp.int32),
        eps=model["rms_norm_eps"], top_k=model["num_experts_per_tok"],
        norm_topk=model["norm_topk_prob"])
    out = x
    for row, e in enumerate(held):
        weight = jnp.sum(jnp.where(sel == e, comb, 0.0), -1)
        out = out + _swiglu(
            u, w[f"mlp.experts.{row}.gate_proj.weight"],
            w[f"mlp.experts.{row}.up_proj.weight"],
            w[f"mlp.experts.{row}.down_proj.weight"]) * weight[..., None]
    if with_shared:
        out = out + _shared_gate(u, w["mlp.shared_expert_gate.weight"]) \
            * _swiglu(u, w["mlp.shared_expert.gate_proj.weight"],
                      w["mlp.shared_expert.up_proj.weight"],
                      w["mlp.shared_expert.down_proj.weight"])
    return out, scores


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _norm(x, norm_w, eps) @ head_w.astype(F32)


def _under(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def _held(model, experts_held):
    return tuple(range(model["num_experts"])) if experts_held is None \
        else tuple(int(e) for e in experts_held)


def is_full_attention(model, i):
    return (i + 1) % model["full_attention_interval"] == 0


def mixer(x, w, model, i):
    """``x + mixer_i(N(x))`` of layer ``i`` (``w``: the layer's weights)."""
    eps = model["rms_norm_eps"]
    if is_full_attention(model, i):
        return _attention(
            x, w, heads=model["num_attention_heads"],
            kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
            rotary=int(model["head_dim"] * model["partial_rotary_factor"]),
            theta=float(model["rope_theta"]), eps=eps)
    return _delta(x, w, key_heads=model["linear_num_key_heads"],
                  heads=model["linear_num_value_heads"],
                  key_dim=model["linear_key_head_dim"],
                  value_dim=model["linear_value_head_dim"],
                  kernel=model["linear_conv_kernel_dim"], eps=eps)


def logits(weights, ids, model, experts_held=None, with_scores=False,
           choice=None):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config's keys (a dict);
    ``experts_held``: global ids of the experts ``weights`` holds, all of
    them if None; ``choice``: ``{layer: [B, S, top_k]}``, the experts handed
    in. Returns float32 logits [B, S, V]; with ``with_scores`` also ``{layer:
    scores [B, S, E]}``."""
    held = _held(model, experts_held)
    x = weights["model.embed_tokens.weight"][ids].astype(F32)
    scores = {}
    for i in range(model["num_hidden_layers"]):
        w = _under(weights, f"model.layers.{i}.")
        x = mixer(x, w, model, i)
        x, scores[i] = _experts(x, w, model, held, (choice or {}).get(i))
    out = _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                eps=model["rms_norm_eps"])
    return (out, scores) if with_scores else out


def choice_gaps(scores, choice):
    """How far a ``choice`` ([S, top_k] expert ids) lies from the one the
    ``scores`` ([S, E]) would make themselves: ``(pairs turned, the largest
    gap)`` (``reference_nemotron_h.choice_gaps``' definition, repeated so
    that this file stands alone). The gap is RELATIVE here, the change in
    that one score over the score at the edge: a softmax's scores over 512
    experts lie near 1/512, and what turns a choice is a ratio."""
    import numpy as np

    scores, choice = np.asarray(scores, np.float64), np.asarray(choice)
    k = choice.shape[-1]
    ranked = -np.sort(-scores, -1)
    worst_in, best_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
    would = scores >= worst_in
    took = np.zeros(scores.shape, bool)
    np.put_along_axis(took, choice, True, -1)
    gaps = np.where(took & ~would, (worst_in - scores) / worst_in,
                    np.where(would & ~took, (scores - best_out) / best_out,
                             0.0))
    return int((took != would).sum()), float(gaps.max(initial=0.0))


def row_error(got, want):
    """|got - want| / |want| in the Euclidean norm over one logits row
    (``reference.row_error``'s definition, repeated so that this file
    stands alone)."""
    import numpy as np

    want = np.asarray(want, np.float64)
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt((d * d).sum()) / (np.sqrt((want * want).sum()) + 1e-9))
