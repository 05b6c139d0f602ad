"""Arithmetic on timings and counts; no JAX, no I/O."""

from __future__ import annotations

import math


def percentile(values, q):
    """``q``-th percentile (0-100), linear interpolation between closest
    ranks (numpy's default). ``math.inf`` entries (failed requests) sort
    last. None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def slice_rates(events, t_open, seconds, slice_s):
    """Rate in each whole ``slice_s`` slice of the window ``[t_open,
    t_open + seconds)``. ``events`` is a list of (time, count), one per
    step, in order of time; a step belongs to the slice its time falls in,
    and a slice runs from the last step before it to its own last step, so
    that its length is measured between the same events as its count: a
    slice cut at fixed instants would read one step more or less (2% at 40
    steps a slice) by where the boundary happens to fall. A trailing
    part-slice is left out, and so is a slice with no step."""
    n = int(seconds // slice_s)
    counts, last = [0] * n, [None] * n
    for t, c in events:
        i = int((t - t_open) // slice_s)
        if 0 <= i < n and t >= t_open:
            counts[i] += c
            last[i] = t
    rates, start = [], t_open
    for c, end in zip(counts, last):
        if end is not None:
            rates.append(c / (end - start))
            start = end
    return rates


def window_rate(events, t_open, t_close):
    """Plain count over the whole window."""
    total = sum(c for t, c in events if t_open <= t < t_close)
    return total / (t_close - t_open)
