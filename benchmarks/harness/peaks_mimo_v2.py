"""What MiMo-V2-Flash's kernels and decode step have to do, from the
configuration's published sizes. The rooflines of ``mimo.*`` divide these
by ``peaks.peaks_for`` and by nothing else.

``m`` is the configuration file (its top level holds the source's keys).
Every count is of the PUBLISHED elements: a K row is 192 wide here though
the pools store it padded to 256, so padding shows as a lower share.
"""

from __future__ import annotations


def layers_of(m, window):
    """Indices of the full (``window`` False) or window layers run."""
    return [i for i in range(m["num_hidden_layers"])
            if bool(m["hybrid_layer_pattern"][i]) == bool(window)]


def kv_bytes_per_token(m, window, itemsize=2):
    """K and V of one token in ONE layer of the kind."""
    p = "swa_" if window else ""
    return (m[p + "num_key_value_heads"]
            * (m[p + "head_dim"] + m[p + "v_head_dim"]) * itemsize)


def global_decode_bytes(m, context_tokens, itemsize=2):
    """Bytes the full layers' decode kernel must read in the steps counted:
    every live token's K and V once a layer. ``context_tokens`` is the sum
    of the decoded rows' context lengths."""
    return (context_tokens * kv_bytes_per_token(m, False, itemsize)
            * len(layers_of(m, False)))


def window_decode_bytes(m, decoded_rows, context_tokens, itemsize=2):
    """Bytes the window layers' decode kernel must read: min(context,
    window) tokens a decoded row a layer. The runner's steps carry the
    rows and the sum of their contexts, not each context, so this takes
    min(sum, rows x window): exact when every context is at least a window
    (the cell's prompts are at least 128), too high otherwise."""
    tokens = min(context_tokens, decoded_rows * m["sliding_window"])
    return (tokens * kv_bytes_per_token(m, True, itemsize)
            * len(layers_of(m, True)))


def attention_params(m, window):
    p = "swa_" if window else ""
    h, heads, kv = m["hidden_size"], m[p + "num_attention_heads"], \
        m[p + "num_key_value_heads"]
    d, dv = m[p + "head_dim"], m[p + "v_head_dim"]
    return h * heads * d + h * kv * (d + dv) + heads * dv * h


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_stream_bytes(m, routed_experts, itemsize=2):
    """Bytes of weights EVERY decode step reads once, whatever it routes:
    attention of each layer, the dense blocks, the routers (float32, over
    all ``routed_experts`` published), the head's slice. The embedding
    table is looked up, not streamed; norms and sinks are left out (KBs)."""
    total = 0
    for i in range(m["num_hidden_layers"]):
        total += attention_params(m, m["hybrid_layer_pattern"][i]) * itemsize
        if m["moe_layer_freq"][i]:
            total += m["hidden_size"] * routed_experts * 4
        else:
            total += 3 * m["hidden_size"] * m["intermediate_size"] * itemsize
    return total + m["hidden_size"] * m["vocab_size"] * itemsize


def weight_stream_bytes(m, decode_steps, experts_hit, routed_experts,
                        itemsize=2):
    """Bytes ``decode_steps`` decode steps need once each: the fixed part
    a step, and each held expert once in every expert layer-step whose
    routing hit it (``experts_hit``: the engine's ``moe_experts_hit_decode``
    over the same steps)."""
    return (decode_steps * fixed_stream_bytes(m, routed_experts, itemsize)
            + experts_hit * expert_params(m) * itemsize)
