"""Device idle time of the traced window under none of the engine's phase
spans, over all idle time there: what the program's tracing cannot see."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.idle_unattributed_share(run)
