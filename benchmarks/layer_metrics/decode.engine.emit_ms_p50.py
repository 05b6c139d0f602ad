"""``engine.decode.emit``: sampling and commit of every ready row, median
over the traced decode-only steps."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.phase_ms_p50(run, ("engine.decode.emit",))
