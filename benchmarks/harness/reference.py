"""The plain reference: Mistral-7B's decoder (the Llama block: pre-norm,
grouped-query attention with rotary embedding on half-split pairs, SwiGLU,
untied head) written straight in ``jax.numpy``, float32, matmul precision
"highest", no kernel, no cache, no batching tricks. It shares no code with
``paddle_tpu``: only the names of the parameters, which is how it is handed
the same weights. v0.3 has no sliding window, so full causal attention is
the published arithmetic.

One jitted function per layer, called layer after layer, so that the
float32 copy of a layer's weights is all that is alive at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D]: pairs (i, i + D/2) rotated by position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _layer(x, w, *, heads, kv_heads, eps, theta):
    with jax.default_matmul_precision("highest"):
        b, s, _ = x.shape
        h = _rms(x, w["input_layernorm.weight"], eps)
        q = (h @ w["self_attn.q_proj.weight"].astype(F32)).reshape(b, s, heads, -1)
        k = (h @ w["self_attn.k_proj.weight"].astype(F32)).reshape(b, s, kv_heads, -1)
        v = (h @ w["self_attn.v_proj.weight"].astype(F32)).reshape(b, s, kv_heads, -1)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        x = x + attn @ w["self_attn.o_proj.weight"].astype(F32)
        h = _rms(x, w["post_attention_layernorm.weight"], eps)
        gate = jax.nn.silu(h @ w["mlp.gate_proj.weight"].astype(F32))
        up = h @ w["mlp.up_proj.weight"].astype(F32)
        return x + (gate * up) @ w["mlp.down_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w, eps) @ head_w.astype(F32)


def logits(weights, ids, model):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config file's model group.
    Returns float32 logits [B, S, V]."""
    x = weights["llama.embed_tokens.weight"][ids].astype(F32)
    for i in range(model["num_hidden_layers"]):
        pre = f"llama.layers.{i}."
        w = {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}
        x = _layer(x, w, heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   eps=model["rms_norm_eps"], theta=model["rope_theta"])
    return _head(x, weights["llama.norm.weight"], weights["lm_head.weight"],
                 eps=model["rms_norm_eps"])


def loss(weights, ids, labels, model):
    """Mean next-token cross entropy over a batch, one sequence at a time
    (a sequence's float32 logits are all that is alive)."""
    total = 0.0
    for row_ids, row_labels in zip(ids, labels):
        lg = logits(weights, row_ids[None], model)[0]
        logp = jax.nn.log_softmax(lg, -1)
        total += float(-jnp.take_along_axis(
            logp, jnp.asarray(row_labels)[:, None], -1).mean())
    return total / len(ids)


def row_error(got, want):
    """|got - want| / |want| in the Euclidean norm over one logits row.
    Every entry counts, so the reading is steady from row to row;
    chip_smoke.py's max|got - want| / max|want| moves with the one entry of
    32,768 that happens to be worst (PERF.md, PR 24: worst row of a check
    0.044-0.051 by the max, 0.044-0.047 by this, same rows)."""
    import numpy as np

    want = np.asarray(want, np.float64)
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt((d * d).sum()) / (np.sqrt((want * want).sum()) + 1e-9))
