"""The generic readers of per-layer metrics. A file
``benchmarks/layer_metrics/<name>.json`` names one of these under
``reader`` and gives its arguments under ``args``; a metric that needs
more brings ``<name>.py`` with a ``read(run)`` of its own. ``run`` is the
record a runner returns. A reader that finds nothing to read returns None
and the metric is left out of the line."""

from __future__ import annotations

from . import peaks, stats, trace_reduce


def _get(run, path):
    """``"counters.host_syncs"`` -> run["counters"]["host_syncs"], or None."""
    node = run
    for key in path.split("."):
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return node


def percentile(run, series, q):
    """``q``-th percentile of a series of the record."""
    xs = _get(run, f"series.{series}")
    return stats.percentile(xs, q) if xs else None


def mean(run, series):
    xs = _get(run, f"series.{series}")
    return sum(xs) / len(xs) if xs else None


def value(run, key):
    """A number the runner worked out itself, by its path in the record."""
    return _get(run, key)


def ratio(run, num, den, scale=1.0):
    """Ratio of two numbers of the record, e.g. two counters."""
    a, b = _get(run, num), _get(run, den)
    return scale * a / b if a is not None and b else None


def _events(run):
    return (run.get("trace") or {}).get("events")


def device_share(run, op=None, module=None):
    """Device time of the operations matching ``op`` in programs matching
    ``module``, as a share of the device's busy time, percent."""
    ev = _events(run)
    if not ev:
        return None
    return 100.0 * trace_reduce.op_seconds(ev, op, module) / run["trace"]["busy_s"]


def idle_share(run):
    tr = run.get("trace")
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None


def peak_hbm_share(run):
    peak = _get(run, "device.memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / peaks.peaks_for(run["device"]["kind"])["hbm_bytes"]


def roofline(run, work, op=None, module=None, not_op=None):
    """Least time the chip could take for the traced steps' work
    (``work.<name>``, seconds, from the shape functions in ``peaks``) over
    the time the matching operations took, percent. ``not_op`` takes the
    busy time of the programs matching ``module`` less the operations
    matching it: "everything but the attention kernels"."""
    ev, least = _events(run), _get(run, f"work.{work}")
    if not ev or not least:
        return None
    if not_op is None:
        took = trace_reduce.op_seconds(ev, op, module)
    else:
        inside = trace_reduce.select(ev, None, module)
        took = (trace_reduce.busy_seconds(inside)
                - trace_reduce.op_seconds(inside, not_op))
    return 100.0 * least / took if took > 0 else None


READERS = {f.__name__: f for f in (percentile, mean, value, ratio,
                                   device_share, idle_share, peak_hbm_share,
                                   roofline)}
