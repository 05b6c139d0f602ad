"""What JoyAI-LLM-Flash's kernels and decode step have to do, from the
configuration's published sizes. The rooflines of ``joyai.*`` divide these
by ``peaks.peaks_for`` and by nothing else.

``m`` is the configuration file (its top level holds the source's keys).
Every count is of the PUBLISHED elements: a latent row is ``kv_lora_rank +
qk_rope_head_dim`` = 576 wide here though the pools store it padded to 640,
so padding shows as a lower share.
"""

from __future__ import annotations


def latent_row_bytes(m, itemsize=2):
    """One token's cached row in ONE layer: ``[c | k_r]``, and nothing else
    (1,152 B in bf16)."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def latent_row_flops(m):
    """Multiply-adds x 2 the absorbed decode kernel needs for one cached row
    in one layer: every head's score over the whole row and its values from
    the row's first ``kv_lora_rank`` (69.6 kFLOP)."""
    return 2 * m["num_attention_heads"] * (
        m["kv_lora_rank"] + m["qk_rope_head_dim"] + m["kv_lora_rank"])


def latent_decode_seconds(m, rows_read, peaks, itemsize=2):
    """The least time the chip could take to walk ``rows_read`` cached rows
    (summed over layers: the engine's ``mla_latent_tokens_read_decode``):
    the larger of their bytes over the HBM peak and their FLOPs over the
    bf16 peak. ``(seconds, which bound)``."""
    by_bytes = rows_read * latent_row_bytes(m, itemsize) / peaks["hbm_bytes_per_s"]
    by_flops = rows_read * latent_row_flops(m) / peaks["bf16_flops"]
    return max(by_bytes, by_flops), "bytes" if by_bytes >= by_flops else "flops"


def attention_params(m):
    """q_a, q_b, kv_a, kv_b and o of one layer (the two inner norms are
    KBs)."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
            + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * heads * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * h)


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_stream_bytes(m, routed_experts, itemsize=2):
    """Bytes of weights EVERY decode step reads once, whatever it routes:
    attention of each layer, the dense blocks, the routers (float32, over
    all ``routed_experts`` published), the shared experts, the whole head.
    The embedding table is looked up, not streamed; norms are left out
    (KBs); the prediction module runs in no decode step."""
    total = 0
    for i in range(m["num_hidden_layers"]):
        total += attention_params(m) * itemsize
        if i < m["first_k_dense_replace"]:
            total += 3 * m["hidden_size"] * m["intermediate_size"] * itemsize
        else:
            total += m["hidden_size"] * routed_experts * 4
            total += (m.get("n_shared_experts") or 0) * expert_params(m) * itemsize
    return total + m["hidden_size"] * m["vocab_size"] * itemsize


def weight_stream_bytes(m, decode_steps, experts_hit, routed_experts,
                        itemsize=2):
    """Bytes ``decode_steps`` decode steps need once each: the fixed part
    a step, and each held expert once in every expert layer-step whose
    routing hit it (``experts_hit``: the engine's ``moe_experts_hit_decode``
    over the same steps)."""
    return (decode_steps * fixed_stream_bytes(m, routed_experts, itemsize)
            + experts_hit * expert_params(m) * itemsize)
