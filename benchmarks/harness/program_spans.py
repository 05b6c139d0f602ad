"""The program's own spans in a profile, and the per-layer metrics read
from them.

``paddle_tpu.observability.trace.span`` enters a
``jax.profiler.TraceAnnotation`` of the span's name while a profiler session
runs, so the engine's step phases sit on the profile's host line, inside the
benchmark's ``bench.step``, on the clock the device line is on:

| span | covers |
|---|---|
| `engine.step` | one `LLMEngine.step()` call; parent of the rest |
| `engine.admit` | ingest drain, deadline scan, admission, tier revivals |
| `engine.prefill` | one prefill chunk: dispatch, and on the last chunk the fetch and the first token |
| `engine.decode.prepare` | decode room, copy-on-write, the ready list, the step's inputs and their puts |
| `engine.decode.dispatch` | the call of the decode / window / verify executable until it returns |
| `engine.decode.fetch` | the wait for the device, then the transfer of the step's result |
| `engine.decode.emit` | sampling and commit of every ready row, latency observations, finishes |
| `engine.bookkeeping` | prefix store autosave, gauges |
| `engine.decode.draft` | speculative path only: the draft model's catch-up and proposals |

Everything below ``host_line`` works on plain lists: host spans ``(name,
start_s, dur_s)`` and device events ``(name, start_s, dur_s, module)``, as
``trace_reduce`` has them, so that it can be checked on hand-made input.
A profile of a program without these spans reads as nothing: every reader
returns None and the metric is left out of the line.
"""

from __future__ import annotations

import glob
import os

from . import stats, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEP = "engine.step"
PREFILL = "engine.prefill"
FETCH = "engine.decode.fetch"
#: what the host does for a decode step besides dispatch, fetch and emit
PREPARE = ("engine.admit", "engine.decode.prepare", "engine.bookkeeping")
_PREFIX = "engine."


def steps_of(host):
    """One ``{name: [(start, end), ...]}`` per ``engine.step`` span, in
    order, holding the ``engine.*`` spans that lie inside it (the step's
    own span under ``STEP``)."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name.startswith(_PREFIX))
    out = []
    for a, b, name in spans:
        if name == STEP:
            out.append({STEP: [(a, b)]})
        elif out and out[-1][STEP][0][0] <= a and b <= out[-1][STEP][0][1]:
            out[-1].setdefault(name, []).append((a, b))
    return out


def decode_only(steps):
    """The steps that decoded and prefilled nothing."""
    return [st for st in steps if PREFILL not in st and FETCH in st]


def span_ms(step, names):
    """Milliseconds the step spent under the named spans."""
    return 1e3 * sum(b - a for name in names for a, b in step.get(name, ()))


def idle_inside(gaps, a, b):
    """Seconds of the idle gaps that lie inside ``[a, b]``; seams between
    two operations are not the host's."""
    return sum(min(e, b) - max(s, a) for s, e in gaps
               if e - s >= trace_reduce.SEAM_S and s < b and e > a)


def fetch_tail_ms(step, gaps):
    """Device-idle milliseconds inside the step's fetch: from the end of
    the last operation the fetch waited for to the fetch's own end, which
    is the transfer."""
    return 1e3 * sum(idle_inside(gaps, a, b) for a, b in step.get(FETCH, ()))


def unattributed_share(host, gaps):
    """Idle time under none of the engine's phase spans over all idle
    time, percent: what the program's tracing still cannot see. A step's
    own time between two phases counts as unseen, as does all time outside
    ``engine.step``. None without such spans or without idle time."""
    covered = trace_reduce.busy_intervals(
        [(name, s, d) for name, s, d in host
         if name.startswith(_PREFIX) and name != STEP])
    gaps = [(s, e) for s, e in gaps if e - s >= trace_reduce.SEAM_S]
    idle = sum(e - s for s, e in gaps)
    if not covered or idle <= 0:
        return None
    seen = sum(idle_inside(gaps, a, b) for a, b in covered)
    return 100.0 * (1.0 - seen / idle)


# --- from a run's record --------------------------------------------------------

_PARSED = {}   # path of the one profile parsed in this process -> its host line


def host_line(run):
    """The host line that belongs to the record's trace, or None. The
    reduced record keeps the device events but not the host line, so this
    loads the newest profile under ``benchmarks_out/*/trace/`` (the run
    has just written it) and takes it only if its window of ``bench.step``
    spans is the record's own; a record that brings ``trace.host`` itself
    is believed."""
    tr = run.get("trace")
    if not tr:
        return None
    if tr.get("host") is not None:
        return tr["host"]
    files = glob.glob(os.path.join(ROOT, "benchmarks_out", "*", "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _PARSED:
        _PARSED.clear()
        _PARSED[path] = trace_reduce.load_xplane(path)["host"]
    host = _PARSED[path]
    if trace_reduce.window_of(host) != (tr.get("t0"), tr.get("t1")):
        return None
    return host


def _decode_steps(run):
    """(decode-only steps inside the traced window, the window's idle
    gaps) of a record, or None."""
    host = host_line(run)
    if not host:
        return None
    tr = run["trace"]
    t0, t1 = tr["t0"], tr["t1"]
    steps = [st for st in decode_only(steps_of(host))
             if t0 <= st[STEP][0][0] and st[STEP][0][1] <= t1]
    if not steps:
        return None
    return steps, trace_reduce.idle_gaps(tr["events"], t0, t1)


def phase_ms_p50(run, names):
    """Median over the traced decode-only steps of the milliseconds a step
    spent under the named spans."""
    found = _decode_steps(run)
    if found is None:
        return None
    return stats.percentile([span_ms(st, names) for st in found[0]], 50)


def fetch_tail_ms_p50(run):
    found = _decode_steps(run)
    if found is None:
        return None
    steps, gaps = found
    return stats.percentile([fetch_tail_ms(st, gaps) for st in steps], 50)


def idle_unattributed_share(run):
    host = host_line(run)
    if not host:
        return None
    tr = run["trace"]
    return unattributed_share(
        host, trace_reduce.idle_gaps(tr["events"], tr["t0"], tr["t1"]))
