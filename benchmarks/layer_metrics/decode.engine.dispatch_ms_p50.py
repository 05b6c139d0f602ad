"""``engine.decode.dispatch``: the call of the decode executable until it
returns, median over the traced decode-only steps."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, ("engine.decode.dispatch",))
