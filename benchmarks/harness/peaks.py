"""The chip's published peaks, and the functions that compute what a
kernel or a step has to do from its shapes. Rooflines and MFU divide by
these and by nothing else.

Source of the peaks: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s per chip. The bf16 row agrees with ``bench._PEAK_BF16["v5 lite"]``.
"""

from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30}

#: keyed by ``jax.devices()[0].device_kind``
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    """A device that is not in the table is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add a row to "
            "benchmarks/harness/peaks.py with its source") from None


# --- sizes from a model's config (the ``model`` group of a config file) ----

def layer_params(m):
    """Matmul parameters of one decoder layer: q, o, k, v and the three
    feed-forward matrices."""
    h, d = m["hidden_size"], m["head_dim"]
    q = h * m["num_attention_heads"] * d
    kv = h * m["num_key_value_heads"] * d
    return 2 * q + 2 * kv + 3 * h * m["intermediate_size"]


def matmul_params(m):
    """Parameters every token is multiplied with: the layers and the
    (untied) head. The embedding table is looked up, not multiplied."""
    return (m["num_hidden_layers"] * layer_params(m)
            + m["hidden_size"] * m["vocab_size"])


def kv_bytes_per_token(m, itemsize=2):
    """K and V of one token across all layers."""
    return (2 * m["num_key_value_heads"] * m["head_dim"] * itemsize
            * m["num_hidden_layers"])


# --- what a kernel or a step has to do ---------------------------------------

def paged_decode_bytes(m, context_tokens, itemsize=2):
    """Bytes the paged decode kernels of all layers must read in one
    step: every live token's K and V once. ``context_tokens`` is the sum
    of the batch's context lengths. Queries and outputs (one row a
    request) are left out: under 0.1% at these contexts."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def weight_stream_bytes(m, itemsize=2):
    """Bytes of weights a decode step streams from HBM once."""
    return matmul_params(m) * itemsize


def causal_attention_flops(m, q_tokens, kv_start=0):
    """Multiply-adds x 2 of QK^T and PV for ``q_tokens`` queries that
    follow ``kv_start`` cached tokens, causal, all heads, one layer: query
    i sees kv_start + i + 1 keys."""
    keys = q_tokens * kv_start + q_tokens * (q_tokens + 1) // 2
    return 4 * m["num_attention_heads"] * m["head_dim"] * keys


def flash_train_flops(m, batch, seq):
    """Forward and backward causal attention of one training step, all
    layers: the forward's two matmuls and the backward's four (dV, dP, dQ,
    dK). The backward's recomputation of QK^T is not counted: it is the
    kernel's choice, not the algorithm's need."""
    fwd = causal_attention_flops(m, seq) * batch * m["num_hidden_layers"]
    return 3 * fwd


def train_flops_per_token(m, seq):
    """6 N for the matmuls (``bench._train_flops_per_token``'s accounting,
    N without the embedding table) plus causal attention forward and
    backward, per token. bench.py counts attention at 12 L h s, the
    non-causal figure; half of those products are masked and the kernel
    skips them, so they are not required work."""
    return (6.0 * matmul_params(m)
            + flash_train_flops(m, 1, seq) / seq)
