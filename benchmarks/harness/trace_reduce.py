"""From a profiler trace to numbers: device busy and idle time, time by
operation, and what the host was doing in each idle gap.

Everything below ``load_xplane`` works on plain lists of events
``(name, start_s, duration_s)`` so that it can be checked on hand-made
input; ``load_xplane`` is the only part that knows the file format.
"""

from __future__ import annotations

import re

#: the benchmark's own host span around one engine or trainer step
STEP_SPAN = "bench.step"
#: gaps shorter than this between two operations of one program are seams,
#: not the host holding the device back
SEAM_S = 20e-6


def load_xplane(path):
    """``{"device": {plane: [(name, start_s, dur_s, module)]}, "host":
    [(name, start_s, dur_s)]}`` from an ``.xplane.pb``. Device events are
    the "XLA Ops" line of each ``/device:TPU:n`` plane; ``module`` is the
    jitted program the operation ran in, from the event's own statistics
    or else from the "XLA Modules" line by time. Host events are those of
    the thread that carries the benchmark's step spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device, host_lines = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        ops.append((op_name(e.name), e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9,
                                    dict(e.stats).get("hlo_module")))
                elif line.name == "XLA Modules":
                    # "jit_decode_pure(1683...)": drop the fingerprint
                    modules = [(e.name.split("(")[0], e.start_ns * 1e-9,
                                e.duration_ns * 1e-9) for e in line.events]
            device[plane.name] = _with_modules(ops, modules)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host_lines.append([(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                                   for e in line.events])
    host = next((ln for ln in host_lines
                 if any(name == STEP_SPAN for name, _, _ in ln)), [])
    return {"device": device, "host": host}


_LAYOUT = re.compile(r"\{[^}]*\}")
_HLO = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\]).*?\s([a-z][a-z0-9\-]*)\(")


def op_name(text):
    """The device line names an operation by its whole HLO instruction,
    ``%decode_pure.16 = bf16[32,4,8,128]{...} custom-call(...)``. Keep
    ``<name> <opcode> <result>``: ``decode_pure.16 custom-call
    bf16[32,4,8,128]``. A pattern such as ``" custom-call "`` then matches
    the opcode alone, never an operand or a name."""
    m = _HLO.match(_LAYOUT.sub("", text))
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else text


def _with_modules(ops, modules):
    """Give each operation without a module the one whose interval holds
    its start."""
    if not modules or all(m for *_, m in ops):
        return ops
    modules = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for name, start, dur, mod in sorted(ops, key=lambda o: o[1]):
        while i + 1 < len(modules) and modules[i + 1][1] <= start:
            i += 1
        mname, mstart, mdur = modules[i]
        if mod is None and mstart <= start <= mstart + mdur:
            mod = mname
        out.append((name, start, dur, mod))
    return out


def window_of(host, span=STEP_SPAN):
    """(start, end) from the first step span's start to the last one's
    end: the traced window. None without step spans."""
    steps = [(s, s + d) for name, s, d in host if name == span]
    if not steps:
        return None
    return min(s for s, _ in steps), max(e for _, e in steps)


def clip(events, t0, t1):
    """Events cut to the window; those outside it dropped."""
    out = []
    for name, start, dur, *rest in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a, *rest))
    return out


def busy_intervals(events):
    """Union of the events' intervals, merged and sorted."""
    spans = sorted((s, s + d) for _, s, d, *_ in events)
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events):
    return sum(b - a for a, b in busy_intervals(events))


def idle_share(events, t0, t1):
    """1 - busy over the window, in percent."""
    return 100.0 * (1.0 - busy_seconds(clip(events, t0, t1)) / (t1 - t0))


def select(events, op=None, module=None):
    """Events whose name matches ``op`` and whose module matches
    ``module`` (regular expressions, searched; None matches all)."""
    return [e for e in events
            if (op is None or re.search(op, e[0]))
            and (module is None or (e[3] and re.search(module, e[3])))]


def op_seconds(events, op=None, module=None):
    return sum(e[2] for e in select(events, op, module))


def tidy(name):
    """A name fit for the result line: no spaces, commas or brackets."""
    return re.sub(r"[^A-Za-z0-9_.\-/]+", "_", name)[:120]


def top_ops(events, n=10):
    """The ``n`` operations that took most device time, as [name,
    seconds], names prefixed with their program."""
    total = {}
    for name, _, dur, mod in events:
        key = tidy(f"{mod}/{name}" if mod else name)
        total[key] = total.get(key, 0.0) + dur
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, t0, t1):
    """(start, end) of every stretch of the window with no operation on
    the device."""
    gaps, at = [], t0
    for a, b in busy_intervals(clip(events, t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def attribute_gaps(gaps, host, n=10):
    """Idle time by what the host was doing: every instant of a gap goes
    to the innermost (shortest) host event that holds it, so a gap that
    begins inside a fetch and ends in the next step's dispatch is split
    between them; gaps under ``SEAM_S`` are seams between operations;
    time under no event lies outside the benchmark's spans. Returns the
    ``n`` largest as [name, seconds]. (A whole gap to the event at its
    midpoint, as this first did, gave one process's 5 ms a step to
    ``np.asarray`` and the next process's to ``bench.step``: the fetch
    ends near the middle of the gap.)"""
    total = {}

    def add(key, seconds):
        total[key] = total.get(key, 0.0) + seconds

    for a, b in gaps:
        if b - a < SEAM_S:
            add("seams_between_ops", b - a)
            continue
        over = [(d, name, s, s + d) for name, s, d in host
                if s < b and s + d > a]
        cuts = sorted({a, b, *(t for _, _, s, e in over for t in (s, e)
                               if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            holders = [(d, name) for d, name, s, e in over
                       if s <= lo and hi <= e]
            add(tidy(min(holders)[1]) if holders else "outside_bench_spans",
                hi - lo)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace):
    """What the runners and readers need from one loaded trace: the window,
    the fullest-average busy time over the chips used, the first device's
    events cut to the window, and the breakdown."""
    win = window_of(trace["host"])
    if win is None or not trace["device"]:
        return None
    t0, t1 = win
    planes = {p: clip(ev, t0, t1) for p, ev in trace["device"].items()}
    planes = {p: ev for p, ev in planes.items() if ev}
    if not planes:
        return None
    first = planes[sorted(planes)[0]]
    return {
        "t0": t0, "t1": t1, "window_s": t1 - t0,
        "busy_s": sum(busy_seconds(ev) for ev in planes.values()) / len(planes),
        "events": first,
        "breakdown": {
            "device_ops": top_ops(first),
            "idle_gaps": attribute_gaps(idle_gaps(first, t0, t1),
                                        trace["host"]),
        },
    }
