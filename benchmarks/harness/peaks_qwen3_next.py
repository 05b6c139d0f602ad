"""What Qwen3-Next's kernels and decode step have to do, from the
configuration's published sizes. The rooflines of ``qwen3next.*`` divide these
by ``peaks.peaks_for`` and by nothing else.

``m`` is the configuration file (its top level holds the source's keys).
Every count is of the PUBLISHED elements; the recurrent state is counted in
float32, which is what the configuration's ``assumed`` states and the cache
holds.
"""

from __future__ import annotations


def is_full_attention(m, i):
    return (i + 1) % m["full_attention_interval"] == 0


def attention_layers(m):
    """The layers that keep K/V pages (every ``full_attention_interval``-th)."""
    return sum(1 for i in range(m["num_hidden_layers"])
               if is_full_attention(m, i))


def state_layers(m):
    """The layers that hold a state a request (the gated delta mixers)."""
    return m["num_hidden_layers"] - attention_layers(m)


def delta_state_bytes(m):
    """One request's state in ONE delta layer: value heads x key dim x value
    dim, float32 (2,097,152 B). The convolution's tail is not the decode
    kernel's: XLA shifts it."""
    return (m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"] * 4)


def delta_decode_bytes(m, rows_updated):
    """Bytes the decode update has to move for ``rows_updated`` states (live
    rows x delta layers, summed over steps: the engine's
    ``delta_state_rows_updated_decode``): each read once and written once."""
    return 2 * rows_updated * delta_state_bytes(m)


def gated_attn_decode_bytes(m, context_tokens, itemsize=2):
    """Bytes the attention layers' decode kernel has to read in the steps
    counted: every live token's K and V (``num_key_value_heads`` x
    ``head_dim`` each: 2,048 B a token a layer) once a layer.
    ``context_tokens`` is the sum of the decoded rows' context lengths."""
    return (context_tokens * attention_layers(m)
            * 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize)


def expert_bytes(m, itemsize=2):
    """One routed expert's three matrices (3 x 2,048 x 512 x 2 B =
    6,291,456 B)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def delta_params(m):
    """``in_proj_qkvz``, ``in_proj_ba``, the convolution and ``out_proj`` of
    one delta mixer (``A_log``, ``dt_bias`` and the gated norm are 192
    numbers)."""
    kd = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    vd = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    h = m["hidden_size"]
    return (h * (2 * kd + 2 * vd) + h * 2 * m["linear_num_value_heads"]
            + (2 * kd + vd) * m["linear_conv_kernel_dim"] + vd * h)


def attention_params(m):
    """q (query and gate), k, v and o of one attention mixer (the two norms
    of 256 are left out)."""
    h, d = m["hidden_size"], m["head_dim"]
    return (3 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)


def fixed_stream_bytes(m, routed_experts, itemsize=2):
    """Bytes of weights EVERY decode step reads once, whatever it routes and
    outside the three kernels: the mixers, and of each layer the router
    (float32, over all ``routed_experts`` published), the shared expert and
    its gate; the head (this chip's slice of the vocabulary). The routed
    experts are the expert kernel's (``expert_bytes``); the embedding table
    is looked up, not streamed; norms are left out (KBs)."""
    h = m["hidden_size"]
    total = 0
    for i in range(m["num_hidden_layers"]):
        mixer = attention_params(m) if is_full_attention(m, i) \
            else delta_params(m)
        total += mixer * itemsize + h * routed_experts * 4
        total += (3 * h * m["shared_expert_intermediate_size"] + h) * itemsize
    return total + h * m["vocab_size"] * itemsize

