"""Device milliseconds of the ``chunk_pure`` executions of the traced
window over thousands of REAL prompt tokens of the chunk spans they are
paired with: what a thousand prompt tokens cost the device, padding and
context included."""
from benchmarks.harness import prefill_spans


def read(run):
    return prefill_spans.prefill_ms_per_ktoken(run)
