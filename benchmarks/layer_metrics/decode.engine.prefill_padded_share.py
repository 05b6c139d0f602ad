"""Of the tokens the prefill chunk graphs computed in the traced window, the
share that was padding: a chunk of ``tokens`` real tokens runs at a rung of
``prefill_buckets``, ``padded`` tokens long (``engine.prefill.chunk``'s
statistics in the profile)."""
from benchmarks.harness import prefill_spans


def read(run):
    return prefill_spans.prefill_padded_share(run)
