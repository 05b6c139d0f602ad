"""Device-idle time inside ``engine.decode.fetch``: the transfer after the
device has finished the step, median over the traced decode-only steps."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.fetch_tail_ms_p50(run)
