"""What each prefill chunk did, read from the profile, and the four
per-layer metrics on it (PR 35).

The engine says what a chunk does where it knows it. A span's scalar
``args`` ride in the profile as the event's statistics
(``paddle_tpu.observability.trace``), so these sit on the host line, on the
device line's clock:

| span | statistics | read by |
|---|---|---|
| `engine.prefill.chunk` (inside `engine.prefill`, around the call of the chunk executable) | `rid`, `start`, `tokens` (real ones), `padded` (the rung the graph ran at), `last` | `decode.engine.prefill_padded_share`; with its execution on the device line `decode.device.prefill_ms_per_ktoken`, `decode.device.prefill_attention_roofline` |
| `engine.prefill.first_token` (the fetch of a last chunk's logits and the first token) | `requests`, `behind` (1: a decode step was enqueued behind the chunks first) | `decode.engine.first_token_idle_share` |

A chunk span is paired with its execution of ``chunk_pure`` on the device
line in order, first in first out: the device runs what it is given in the
order it is given. An execution that ended before the oldest unpaired span
opened was dispatched before the profiler started and is none of any span's.
A pair whose execution the traced window cuts, or leaves out, is dropped on
BOTH sides, so that the work counted is the work timed.

Everything below ``load`` works on plain lists, as ``trace_reduce`` and
``program_spans`` do. A record without a trace, of a program without these
spans (the parent's), or without ``run["model"]`` reads as nothing: every
reader returns None and does not raise.
"""

from __future__ import annotations

import glob
import json
import os

from . import peaks, program_spans, trace_reduce

ROOT = program_spans.ROOT

CHUNK = "engine.prefill.chunk"
FIRST_TOKEN = "engine.prefill.first_token"
#: the chunk program, as the device line names it (``jit_chunk_pure``)
MODULE = "chunk_pure"
#: the attention kernels of a chunk: Llama's multi-query kernel over pages,
#: and the flash fold over one request's rows of the three later models
ATTENTION = r"(paged_prefill_attention|chunk_attention)\S* custom-call "


# --- the profile --------------------------------------------------------------

def load(path):
    """``{"host_args": [(name, start_s, dur_s, statistics)], "chunk_runs":
    [(start_s, dur_s)], "window": (t0, t1) or None}`` from an
    ``.xplane.pb``: the two spans above on the host line that carries the
    benchmark's step spans, every execution of the chunk program on the
    first device's "XLA Modules" line (the device whose operations
    ``trace_reduce.reduce`` keeps), and the window of the step spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    runs, lines = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs[plane.name] = [
                        (e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events if MODULE in e.name.split("(")[0]]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                lines.append([(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                               dict(e.stats) if e.name in (CHUNK, FIRST_TOKEN)
                               else None) for e in line.events])
    host = next((ln for ln in lines
                 if any(e[0] == trace_reduce.STEP_SPAN for e in ln)), [])
    return {"host_args": [e for e in host if e[3] is not None],
            "chunk_runs": runs[sorted(runs)[0]] if runs else [],
            "window": trace_reduce.window_of([e[:3] for e in host])}


_PARSED = {}   # path of the one profile parsed in this process -> load(path)


def from_record(run):
    """``(host_args, chunk_runs)`` of the record's trace, or None. By
    ``program_spans.host_line``'s rule: the newest profile under
    ``benchmarks_out/*/trace/`` (the run has just written it), taken only
    if its window of ``bench.step`` spans is the record's own; a record
    that brings ``trace.host_args`` (and ``trace.chunk_runs``) itself is
    believed."""
    tr = run.get("trace")
    if not tr:
        return None
    if tr.get("host_args") is not None:
        return tr["host_args"], tr.get("chunk_runs") or []
    files = glob.glob(os.path.join(ROOT, "benchmarks_out", "*", "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _PARSED:
        _PARSED.clear()
        _PARSED[path] = load(path)
    got = _PARSED[path]
    if got["window"] != (tr.get("t0"), tr.get("t1")):
        return None
    return got["host_args"], got["chunk_runs"]


# --- on plain lists -----------------------------------------------------------

def spans(host_args, name):
    """``[(start_s, end_s, statistics)]`` of the spans called ``name``, by
    time."""
    return sorted(((s, s + d, st) for n, s, d, st in host_args if n == name),
                  key=lambda e: e[:2])


def pairs(chunks, runs, t0, t1):
    """``[(statistics, run start, run end)]``: each chunk span of
    ``spans(..., CHUNK)`` with its execution among ``runs`` (``[(start_s,
    dur_s)]``), first in first out, less the pairs whose execution does
    not lie whole inside ``[t0, t1]``."""
    out, i = [], 0
    for a, b in sorted((s, s + d) for s, d in runs):
        if i == len(chunks):
            break
        if b <= chunks[i][0]:
            continue            # dispatched before the profiler started
        if t0 <= a and b <= t1:
            out.append((chunks[i][2], a, b))
        i += 1
    return out


def padded_share(chunks):
    """Of the tokens the chunk graphs computed, the percent that were
    padding. None without chunks."""
    padded = sum(st["padded"] for _, _, st in chunks)
    if not padded:
        return None
    return 100.0 * (padded - sum(st["tokens"] for _, _, st in chunks)) / padded


def run_seconds(events, paired, op=None):
    """Device seconds of the operations matching ``op`` in the chunk
    program that started inside a paired execution."""
    ops = sorted(trace_reduce.select(events, op, MODULE), key=lambda e: e[1])
    total, i = 0.0, 0
    for _, a, b in paired:
        while i < len(ops) and ops[i][1] < a:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < b:
            j += 1
        total += trace_reduce.busy_seconds(ops[i:j])
        i = j
    return total


# --- what a chunk's attention has to do ---------------------------------------

def visible_pairs(start, tokens, window=None):
    """Query-key pairs of ``tokens`` real queries at positions ``start``
    on: the query at position ``p`` sees ``p + 1`` keys under a causal
    mask, at most ``window`` of them under a window mask. Padding rows
    count nothing."""
    if window is None or start + tokens <= window:
        return tokens * start + tokens * (tokens + 1) // 2
    rising = max(0, window - start - 1)   # queries that still see p + 1 < window
    return (rising * start + rising * (rising + 1) // 2
            + (tokens - rising) * window)


#: the keys a configuration has to share with the record to be its own
_WIDTHS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
           "head_dim", "v_head_dim", "swa_num_attention_heads",
           "swa_head_dim", "swa_v_head_dim", "sliding_window")


def _config_of(run):
    """The configuration file of the record's kind that agrees with every
    number of ``run["model"]`` it also states: what holds the lists a
    record drops (a layer pattern). None if no file does."""
    model = run["model"]
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("kind") == run.get("kind") and all(
                cfg[k] == v for k, v in model.items()
                if k in cfg and k in _WIDTHS):
            return cfg
    return None


def _llama(run):
    m = run["model"]
    d = m["head_dim"]
    return [(m["num_hidden_layers"], m["num_attention_heads"], d, d, None)]


def _mimo_v2(run):
    m, cfg = run["model"], _config_of(run)
    if cfg is None:
        return None
    window = sum(1 for x in
                 cfg["hybrid_layer_pattern"][:m["num_hidden_layers"]] if x)
    return [(m["num_hidden_layers"] - window, m["num_attention_heads"],
             m["head_dim"], m["v_head_dim"], None),
            (window, m["swa_num_attention_heads"], m["swa_head_dim"],
             m["swa_v_head_dim"], m["sliding_window"])]


def _joyai_flash(run):
    # a chunk attends EXPANDED: every head its own 128 + 64 key, 128 value
    m = run["model"]
    return [(m["num_hidden_layers"], m["num_attention_heads"],
             m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"],
             None)]


def _nemotron_h(run):
    m = run["model"]
    blocks = m["hybrid_override_pattern"][:m["num_hidden_layers"]].count("*")
    d = m["head_dim"]
    return [(blocks, m["num_attention_heads"], d, d, None)]


#: by the record's ``kind``: ``[(layers, query heads, q/k width, v width,
#: window or None)]`` of the layers that attend, at the published widths
ATTENDING = {"serve": _llama, "serve_mimo_v2": _mimo_v2,
             "serve_joyai_flash": _joyai_flash,
             "serve_nemotron_h": _nemotron_h}


def attention_flops(layers, chunks):
    """FLOPs the chunks' attention needs: a query-key pair costs a
    multiply-add over the q/k width for its score and one over the v width
    for its value, in every query head of every layer that attends."""
    return sum(2 * n * heads * (qk + v) * visible_pairs(
        st["start"], st["tokens"], window)
        for n, heads, qk, v, window in layers for st in chunks)


# --- from a run's record ------------------------------------------------------

def _paired(run):
    got = from_record(run)
    if not got:
        return None
    tr = run["trace"]
    out = pairs(spans(got[0], CHUNK), got[1], tr["t0"], tr["t1"])
    return out or None


def prefill_padded_share(run):
    """``decode.engine.prefill_padded_share``: over the chunk spans that
    opened inside the traced window."""
    got = from_record(run)
    if not got:
        return None
    tr = run["trace"]
    return padded_share([c for c in spans(got[0], CHUNK)
                         if tr["t0"] <= c[0] < tr["t1"]])


def first_token_idle_share(run):
    """``decode.engine.first_token_idle_share``: seconds the device stood
    idle (gaps of at least ``trace_reduce.SEAM_S``) while the host waited
    for a last chunk's logits and made the first token, over the traced
    window's seconds, percent. None without such a span."""
    got = from_record(run)
    tr = run.get("trace")
    if not got or not tr.get("events"):
        return None
    waits = spans(got[0], FIRST_TOKEN)
    if not waits:
        return None
    t0, t1 = tr["t0"], tr["t1"]
    gaps = trace_reduce.idle_gaps(tr["events"], t0, t1)
    return 100.0 * sum(program_spans.idle_inside(gaps, a, b)
                       for a, b, _ in waits) / (t1 - t0)


def prefill_ms_per_ktoken(run):
    """``decode.device.prefill_ms_per_ktoken``: device milliseconds of the
    paired executions over thousands of REAL prompt tokens of their spans."""
    paired = _paired(run)
    if not paired or not run["trace"].get("events"):
        return None
    tokens = sum(st["tokens"] for st, _, _ in paired)
    took = run_seconds(run["trace"]["events"], paired)
    return 1e3 * took / (tokens / 1e3) if tokens and took > 0 else None


def prefill_attention_roofline(run):
    """``decode.device.prefill_attention_roofline``: least seconds for the
    paired chunks' attention (FLOPs over the chip's bf16 peak; a chunk's
    attention is bound by its products, not its bytes) over the seconds
    its kernels took in those executions, percent."""
    paired = _paired(run)
    kind = (run.get("device") or {}).get("kind")
    attending = ATTENDING.get(run.get("kind"))
    if (not paired or not run["trace"].get("events") or not run.get("model")
            or attending is None or kind not in peaks.PEAKS):
        return None
    layers = attending(run)
    took = run_seconds(run["trace"]["events"], paired, ATTENTION)
    if not layers or took <= 0:
        return None
    least = attention_flops(layers, [st for st, _, _ in paired]) \
        / peaks.peaks_for(kind)["bf16_flops"]
    return 100.0 * least / took
