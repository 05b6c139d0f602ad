"""Traffic generation: one general generator that reads a traffic file.

The schedule belongs to the cell and the content to the seed. A traffic
file's parameters and its ``schedule_seed`` fix one list of (due offset,
prompt length, output length): lengths are the mid-quantiles of the stated
log-normal, dealt in a fixed shuffled order. ``--seed`` draws token ids (and
the weights) and nothing else, so every run of a cell offers the same work
in the same order and what spreads between runs is the system.

Only the closed loop is here: every due offset is 0 and the order alone
matters. The open loop (exponential gaps at the mid-quantiles, a rate in
the traffic file) was built, run on the chip and taken out again with its
cell in PR 24 (PERF.md section 7); it comes back with the cell that needs
it, as a second branch of ``build`` and a second driver beside the serve
runner's ``_closed``.
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    index: int
    due_s: float       # offset from the start of the schedule; 0 in a closed loop
    prompt_len: int
    output_len: int


def _mid_quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(n, spec, rng):
    """``n`` whole lengths at the mid-quantiles of a clipped log-normal
    ``{"median", "sigma", "min", "max"}``, in an order shuffled by ``rng``."""
    inv = NormalDist().inv_cdf
    out = [int(round(min(max(spec["median"] * math.exp(spec["sigma"] * inv(p)),
                             spec["min"]), spec["max"])))
           for p in _mid_quantiles(n)]
    rng.shuffle(out)
    return out


def build(traffic):
    """The cell's fixed list of requests."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    rng = random.Random(traffic["schedule_seed"])
    n = int(traffic["requests"])
    prompts = lognormal_lengths(n, traffic["prompt_tokens"], rng)
    outputs = lognormal_lengths(n, traffic["output_tokens"], rng)
    return [Item(i, 0.0, p, o)
            for i, (p, o) in enumerate(zip(prompts, outputs))]


def cycled(items):
    """The list over and over; the index keeps counting, so a request's
    token ids differ from lap to lap."""
    lap = 0
    while True:
        for it in items:
            yield dataclasses.replace(it, index=it.index + lap * len(items))
        lap += 1


def token_ids(seed, index, length, vocab_size):
    """Token ids of request (or batch) ``index`` under ``--seed``: a stream
    of its own, so content does not depend on the order of submission."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab_size, size=length, dtype=np.int32)
