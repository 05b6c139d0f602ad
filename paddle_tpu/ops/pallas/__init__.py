"""Hand-written Pallas TPU kernels for the ops where XLA fusion isn't enough
— the TPU-native replacement for the reference's fused CUDA ops
(paddle/fluid/operators/fused/, paddle/phi/kernels/fusion/,
third_party/flashattn).

Kernels: flash_attention (plain + rope-fused), paged_attention (decode,
multi-query and chunk attention over paged KV), grouped_ffn (the dropless
grouped SwiGLU of MiMo-V2's held experts), rms_norm (fused residual-add +
RMSNorm), moe_ffn (blockwise SwiGLU expert FFN over capacity-routed
blocks). Each is parity-tested in interpret mode (tests/test_pallas_*.py,
tests/test_mimo_v2_grouped_ffn.py); rms_norm and moe_ffn stay behind an
opt-in env flag until an end-to-end win is measured on real hardware
(PERF.md records every verdict)."""
