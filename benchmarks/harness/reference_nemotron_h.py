"""The plain reference of Nemotron-H's decoder (NVIDIA-Nemotron-3-Nano-30B-A3B),
written from its ``config.json`` straight in ``jax.numpy``: float32, matmul
precision "highest", the state-space recurrence TOKEN BY TOKEN (``lax.scan``
over ``t``: no chunked form, no cache, no kernel), the convolution as
``conv_kernel`` shifted sums, the experts by a loop. It shares no code with
``paddle_tpu``: only the names of the parameters, which is how it is handed
the same weights. Attention takes its queries a block at a time so that a
sequence of 2,200 tokens fits at the published widths.

What the config states and this computes (departures and assumptions are the
configuration file's ``assumed``; none beyond the cut):

* ``hybrid_override_pattern`` names a block a letter; a block is ONE mixer,
  ``x <- x + mixer(norm(x))``, RMS norm (``layer_norm_epsilon``); a final norm
  and an untied head; no bias but the convolution's;
* ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC_t <- silu(sum_j
  w[:, j] xBC_{t - K + 1 + j} + b)``, zeros before the sequence; ``xBC -> x
  [heads, head_dim], B [groups, N], C [groups, N]``, head ``h`` reads group
  ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` from 0;
  ``y_t = h_t C_t + D x_t``; ``y <- norm_groups(y * silu(z)) * w`` over groups
  of ``inner / n_groups`` (the gate before the norm); ``out = y W_out``;
* ``*`` (attention): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` kv heads of ``head_dim``, causal softmax at ``1 /
  sqrt(head_dim)``, no rotary embedding;
* ``E`` (experts): scores ``sigmoid(u W_r)`` in float32, the
  ``num_experts_per_tok`` experts with the largest ``score +
  e_score_correction_bias``, combine weights the uncorrected scores of the
  chosen over their sum, times ``routed_scaling_factor``; an expert is
  ``relu(u W_up)^2 W_down`` at ``moe_intermediate_size`` (the weights may be
  stored wider with zeros: the published width is what is read); the shared
  expert, of the same form at its own width, with weight 1.

**One chip's share.** ``experts_held`` lists the global ids of the experts
whose weights ``weights`` holds (``mixer.experts.<n>`` is the n-th of them).
The router is whole; the loop runs over the held experts and adds what they
give, and the shared expert, which every chip computes alike, is added once;
what the absent experts would have added is left out, and that partial
result goes on to the next block.

**Where its own scores all but tie.** A row of this model reads the tokens
before it through the recurrent state, so one token routed the other way a
few positions back moves a row by a tenth, and a computation in bfloat16
routes several tokens in a hundred otherwise than this one does, each by a
hair. ``choice`` (``{block: [B, S, top_k]}`` global expert ids) therefore
HANDS the routing in: where it is given, a token takes those experts and not
the ``top_k`` of its own corrected scores; everything else, the combine
weights from the uncorrected scores among them, is computed here as ever.
``with_scores`` gives back this reference's own corrected scores of every
expert block at every position, which is what whoever handed a choice in
holds it against (``choice_gaps``): a choice may differ from the scores' own
only where they all but tie.
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


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "state", "groups", "kernel", "eps"))
def _mamba2(x, w, *, heads, head_dim, state, groups, kernel, eps):
    """x + mamba2(norm(x)), the recurrence token by token."""
    with jax.default_matmul_precision("highest"):
        bsz, s, _ = x.shape
        inner = heads * head_dim
        conv_dim = inner + 2 * groups * state
        u = _rms(x, w["norm.weight"], eps)
        proj = u @ w["mixer.in_proj.weight"].astype(F32)
        z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + conv_dim],
                      proj[..., inner + conv_dim:])
        # the causal convolution: kernel shifted sums, zeros before t = 0
        cw = w["mixer.conv_weight"].astype(F32)                  # [D, K]
        padded = jnp.pad(xbc, [(0, 0), (kernel - 1, 0), (0, 0)])
        conv = w["mixer.conv_bias"].astype(F32)
        for j in range(kernel):
            conv = conv + padded[:, j:j + s] * cw[:, j]
        xbc = jax.nn.silu(conv)
        xs = xbc[..., :inner].reshape(bsz, s, heads, head_dim)
        per = heads // groups
        b = jnp.repeat(xbc[..., inner:inner + groups * state].reshape(
            bsz, s, groups, state), per, axis=2)                 # [B,S,H,N]
        c = jnp.repeat(xbc[..., inner + groups * state:].reshape(
            bsz, s, groups, state), per, axis=2)
        dt = jax.nn.softplus(dt + w["mixer.dt_bias"].astype(F32))  # [B,S,H]
        a = -jnp.exp(w["mixer.A_log"].astype(F32))                # [H]

        def step(h, inp):
            x_t, b_t, c_t, dt_t = inp           # [B,H,P] [B,H,N] [B,H,N] [B,H]
            h = (jnp.exp(dt_t * a)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return h, jnp.sum(h * c_t[:, :, None, :], -1)

        _, y = jax.lax.scan(
            step, jnp.zeros((bsz, heads, head_dim, state), F32),
            tuple(jnp.swapaxes(m, 0, 1) for m in (xs, b, c, dt)))
        y = jnp.swapaxes(y, 0, 1) + w["mixer.D"].astype(F32)[:, None] * xs
        y = y.reshape(bsz, s, inner) * jax.nn.silu(z)
        g = y.reshape(bsz, s, groups, inner // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        y = g.reshape(bsz, s, inner) * w["mixer.norm_weight"].astype(F32)
        return x + y @ w["mixer.out_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps"))
def _attention(x, w, *, heads, kv_heads, head_dim, eps):
    """x + attention(norm(x)): causal, no rotary embedding."""
    with jax.default_matmul_precision("highest"):
        bsz, s, _ = x.shape
        u = _rms(x, w["norm.weight"], eps)
        q = (u @ w["mixer.q_proj.weight"].astype(F32)).reshape(
            bsz, s, heads, head_dim)
        k = (u @ w["mixer.k_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, head_dim)
        v = (u @ w["mixer.v_proj.weight"].astype(F32)).reshape(
            bsz, s, kv_heads, head_dim)
        k, v = (jnp.repeat(m, heads // kv_heads, axis=2) for m in (k, v))
        t = jnp.arange(s)
        outs = []
        for q0 in range(0, s, Q_BLOCK):
            z = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + Q_BLOCK], k) \
                / jnp.sqrt(F32(head_dim))
            see = t[None, :] <= t[q0:q0 + Q_BLOCK, None]
            p = jax.nn.softmax(jnp.where(see, z, -jnp.inf), -1)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
        attn = jnp.concatenate(outs, 1).reshape(bsz, s, heads * head_dim)
        return x + attn @ w["mixer.o_proj.weight"].astype(F32)


@functools.partial(jax.jit, static_argnames=("width",))
def _relu2_mlp(u, up_w, down_w, *, width):
    with jax.default_matmul_precision("highest"):
        h = jax.nn.relu(u @ up_w[:, :width].astype(F32))
        return (h * h) @ down_w[:width].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "norm_topk"))
def _route(x, w, choice, *, eps, top_k, norm_topk):
    """The normed input, the chosen experts (``choice`` if it is handed in,
    else the ``top_k`` of the corrected scores), their combine weights
    (before the scaling factor), and the corrected scores."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, w["norm.weight"], eps)
        scores = jax.nn.sigmoid(u @ w["mixer.router.weight"].astype(F32))
        corrected = scores + w["mixer.router.e_score_correction_bias"].astype(F32)
        sel = jax.lax.top_k(corrected, top_k)[1] if choice is None else choice
        comb = jnp.take_along_axis(scores, sel, -1)
        if norm_topk:
            comb = comb / jnp.sum(comb, -1, keepdims=True)
        return u, sel, comb, corrected


def _experts(x, w, model, held, choice=None):
    """x + experts(norm(x)), and the corrected scores."""
    u, sel, comb, corrected = _route(
        x, w, None if choice is None else jnp.asarray(choice, jnp.int32),
        eps=model["layer_norm_epsilon"], top_k=model["num_experts_per_tok"],
        norm_topk=model["norm_topk_prob"])
    if model.get("routed_scaling_factor"):
        comb = comb * model["routed_scaling_factor"]
    out = x
    for row, e in enumerate(held):
        weight = jnp.sum(jnp.where(sel == e, comb, 0.0), -1)
        out = out + _relu2_mlp(
            u, w[f"mixer.experts.{row}.up_proj.weight"],
            w[f"mixer.experts.{row}.down_proj.weight"],
            width=model["moe_intermediate_size"]) * weight[..., None]
    if model.get("n_shared_experts", 1):
        out = out + _relu2_mlp(
            u, w["mixer.shared_experts.up_proj.weight"],
            w["mixer.shared_experts.down_proj.weight"],
            width=model["moe_shared_expert_intermediate_size"])
    return out, corrected


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, head_w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm_w, eps) @ head_w.astype(F32)


def _under(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def _held(model, experts_held):
    return tuple(range(model["n_routed_experts"])) if experts_held is None \
        else tuple(int(e) for e in experts_held)


def logits(weights, ids, model, experts_held=None, with_scores=False,
           choice=None):
    """``weights``: {parameter name: array} as ``named_parameters`` names
    them; ``ids`` [B, S] int32; ``model``: the config's keys (a dict);
    ``experts_held``: global ids of the experts ``weights`` holds, all of
    them if None; ``choice``: ``{block: [B, S, top_k]}``, the experts handed
    in. Returns float32 logits
    [B, S, V]; with ``with_scores`` also ``{block: corrected scores [B, S,
    E]}`` of the expert blocks."""
    held = _held(model, experts_held)
    eps = model["layer_norm_epsilon"]
    x = weights["model.embed_tokens.weight"][ids].astype(F32)
    scores = {}
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    for i, letter in enumerate(pattern):
        w = _under(weights, f"model.layers.{i}.")
        if letter == "M":
            x = _mamba2(x, w, heads=model["mamba_num_heads"],
                        head_dim=model["mamba_head_dim"],
                        state=model["ssm_state_size"],
                        groups=model["n_groups"],
                        kernel=model["conv_kernel"], eps=eps)
        elif letter == "*":
            x = _attention(x, w, heads=model["num_attention_heads"],
                           kv_heads=model["num_key_value_heads"],
                           head_dim=model["head_dim"], eps=eps)
        elif letter == "E":
            x, scores[i] = _experts(x, w, model, held, (choice or {}).get(i))
        else:
            raise ValueError(f"no block {letter!r} in this model")
    out = _head(x, weights["model.norm.weight"], weights["lm_head.weight"],
                eps=eps)
    return (out, scores) if with_scores else out


def choice_gaps(scores, choice):
    """How far a ``choice`` ([S, top_k] expert ids) lies from the one the
    corrected ``scores`` ([S, E]) would make themselves: ``(pairs turned,
    the largest gap)``. A pair is turned where a token takes an expert its
    scores' own ``top_k`` leaves out, or leaves one out that they take; its
    gap is the change in that one score that would turn it, measured from the
    scores' own edge (the worst score chosen for an expert taken, the best
    one left out for an expert left)."""
    import numpy as np

    scores, choice = np.asarray(scores, np.float64), np.asarray(choice)
    k = choice.shape[-1]
    ranked = -np.sort(-scores, -1)
    worst_in, best_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
    would = scores >= worst_in
    took = np.zeros(scores.shape, bool)
    np.put_along_axis(took, choice, True, -1)
    gaps = np.where(took & ~would, worst_in - scores,
                    np.where(would & ~took, scores - best_out, 0.0))
    return int((took != would).sum()), float(gaps.max(initial=0.0))


def row_error(got, want):
    """|got - want| / |want| in the Euclidean norm over one logits row
    (``reference.row_error``'s definition, repeated so that this file
    stands alone)."""
    import numpy as np

    want = np.asarray(want, np.float64)
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt((d * d).sum()) / (np.sqrt((want * want).sum()) + 1e-9))
