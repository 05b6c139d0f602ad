"""Host time of a decode-only step outside dispatch, fetch and emit:
``engine.admit`` + ``engine.decode.prepare`` + ``engine.bookkeeping``,
median over the traced decode-only steps."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, program_spans.PREPARE)
