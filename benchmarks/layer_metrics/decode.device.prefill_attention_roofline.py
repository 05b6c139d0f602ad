"""Least time the chip could take for the traced chunks' attention (the
query-key pairs their real tokens see, at the published widths, over the
bf16 peak) over the time the chunk program's attention kernels took."""
from benchmarks.harness import prefill_spans


def read(run):
    return prefill_spans.prefill_attention_roofline(run)
