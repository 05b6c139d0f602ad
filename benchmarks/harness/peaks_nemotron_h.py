"""What Nemotron-H's kernels and decode step have to do, from the
configuration's published sizes. The rooflines of ``nemotron.*`` divide these
by ``peaks.peaks_for`` and by nothing else.

``m`` is the configuration file (its top level holds the source's keys).
Every count is of the PUBLISHED elements: an expert is ``moe_intermediate_size``
= 1,856 wide here though its matrices are stored 1,920 wide, so padding shows
as a lower share; the recurrent state is counted in float32, which is what
the configuration's ``assumed`` states and the cache holds.
"""

from __future__ import annotations


def _pattern(m):
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def state_layers(m):
    """The blocks that hold a state a request (``M``)."""
    return _pattern(m).count("M")


def ssm_state_bytes(m):
    """One request's recurrent state in ONE state-space block: heads x
    head_dim x state, float32 (2,097,152 B). The convolution's tail is not
    the decode kernel's: XLA shifts it."""
    return m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"] * 4


def ssm_decode_bytes(m, rows_updated):
    """Bytes the decode update has to move for ``rows_updated`` states (live
    rows x state blocks, summed over steps: the engine's
    ``ssm_state_rows_updated_decode``): each read once and written once."""
    return 2 * rows_updated * ssm_state_bytes(m)


def global_decode_bytes(m, context_tokens, itemsize=2):
    """Bytes the attention blocks' decode kernel has to read in the steps
    counted: every live token's K and V (``num_key_value_heads`` x
    ``head_dim`` each: 1,024 B a token a block) once a block.
    ``context_tokens`` is the sum of the decoded rows' context lengths."""
    return (context_tokens * _pattern(m).count("*")
            * 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize)


def expert_bytes(m, itemsize=2):
    """One routed expert's two matrices at the published width (2 x 2,688 x
    1,856 x 2 B = 19.96 MB)."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def mamba_params(m):
    """in_proj, the convolution (weights and bias) and out_proj of one
    state-space block (A_log, D, dt_bias and the gated norm are KBs)."""
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv = inner + 2 * m["n_groups"] * m["ssm_state_size"]
    return (m["hidden_size"] * (inner + conv + m["mamba_num_heads"])
            + conv * (m["conv_kernel"] + 1) + inner * m["hidden_size"])


def attention_params(m):
    """q, k, v and o of one attention block."""
    h, d = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)


def fixed_stream_bytes(m, routed_experts, itemsize=2):
    """Bytes of weights EVERY decode step reads once, whatever it routes and
    outside the three kernels: the mixers of the state-space and attention
    blocks, and of each expert block the router (float32, over all
    ``routed_experts`` published) and the shared expert; the head (this
    chip's slice of the vocabulary). The routed experts are the expert
    kernel's (``expert_bytes``); the embedding table is looked up, not
    streamed; norms are left out (KBs)."""
    total = 0
    for letter in _pattern(m):
        if letter == "M":
            total += mamba_params(m) * itemsize
        elif letter == "*":
            total += attention_params(m) * itemsize
        else:
            total += m["hidden_size"] * routed_experts * 4
            total += (2 * m["hidden_size"]
                      * m["moe_shared_expert_intermediate_size"] * itemsize)
    return total + m["hidden_size"] * m["vocab_size"] * itemsize
