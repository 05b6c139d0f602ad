"""Device-idle time inside the ``engine.prefill.first_token`` spans (the
fetch of a last chunk's logits and the first token), over the traced
window: what the wait for a first token costs the device."""
from benchmarks.harness import prefill_spans


def read(run):
    return prefill_spans.first_token_idle_share(run)
