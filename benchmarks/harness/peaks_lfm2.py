"""What LFM2-MoE's kernels and decode step have to do, from the
configuration's published sizes. The rooflines of ``lfm2.*`` divide these by
``peaks.peaks_for`` and by nothing else.

``m`` is the configuration file (its top level holds the source's keys).
Every count is of the PUBLISHED elements at 2 B (bfloat16): a head is
``hidden_size / num_attention_heads`` = 64 wide, and the pages hold it so,
two kv heads a lane row; a form that padded it to the lanes would show here
as a share half as large.
"""

from __future__ import annotations


def _types(m):
    return m["layer_types"][:m["num_hidden_layers"]]


def attention_layers(m):
    return _types(m).count("full_attention")


def conv_layers(m):
    return _types(m).count("conv")


def head_dim(m):
    return m["hidden_size"] // m["num_attention_heads"]


def kv_bytes_per_token(m, itemsize=2):
    """K and V of one token in ONE attention layer: 8 kv heads x (64 + 64) x
    2 B = 2,048 B."""
    return 2 * m["num_key_value_heads"] * head_dim(m) * itemsize


def attention_decode_bytes(m, tokens_read, itemsize=2):
    """Bytes the head-64 decode kernel has to read for ``tokens_read`` live
    tokens summed over its layers (the engine's ``attn_tokens_read_decode``):
    each token's K and V once."""
    return tokens_read * kv_bytes_per_token(m, itemsize)


def expert_bytes(m, itemsize=2):
    """One expert's three matrices at the published width (3 x 2,048 x
    1,792 x 2 B = 22,020,096 B)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def conv_params(m):
    """in_proj (hidden -> 3 x hidden), the taps and out_proj of one short
    convolution (16.78 M at the published widths)."""
    h = m["hidden_size"]
    return 3 * h * h + h * m["conv_L_cache"] + h * h


def attention_params(m):
    """q, k, v and out_proj of one attention layer (10.49 M); the two
    64-wide norms are bytes."""
    h, d = m["hidden_size"], head_dim(m)
    return (2 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)


def fixed_stream_bytes(m, itemsize=2):
    """Bytes of weights EVERY decode step reads once, whatever it routes and
    outside the two kernels: the mixers, the dense layers' SwiGLU, each
    expert layer's router (float32) and the tied head (the embedding read
    whole as ``x E^T``; a lookup of the embedding streams nothing). The
    experts are the expert kernel's (``expert_bytes``); norms and the expert
    bias are left out (KBs). About 0.76 GB at the cut."""
    h = m["hidden_size"]
    total = 0
    for i, kind in enumerate(_types(m)):
        total += (attention_params(m) if kind == "full_attention"
                  else conv_params(m)) * itemsize
        if i < m["num_dense_layers"]:
            total += 3 * h * m["intermediate_size"] * itemsize
        else:
            total += h * m["num_experts"] * 4
    return total + h * m["vocab_size"] * itemsize
