"""The engine's step phases as spans (ISSUE 25): the same names on every
decode path (a steady call of the per-step path holds the next step's
prepare and dispatch and this step's fetch and emit: ISSUE 28), what a
prefill chunk says of itself and the first token's own phase (ISSUE 35),
nothing recorded and nothing built with tracing off, the spans and their
args in a real ``jax.profiler`` session on the CPU, the Pallas kernels'
names in the lowered text, and ``add_request(arrival_t=)`` as the due time."""

import glob
import re
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import LLMEngine, SamplingParams
from paddle_tpu.observability import metrics, trace
from paddle_tpu.observability.trace import TRACER

DECODE_PHASES = ["engine.decode.prepare", "engine.decode.dispatch",
                 "engine.decode.fetch", "engine.decode.emit"]
#: what a call of the per-step path may hold (ISSUE 28): this step made
#: now and the next one ahead of its fetch (the first call after a break);
#: the next one alone, this one being in flight (every steady call); no
#: dispatch at all, this one being in flight and the last
PER_STEP = [DECODE_PHASES[:2] + DECODE_PHASES, DECODE_PHASES,
            DECODE_PHASES[:1] + DECODE_PHASES[2:]]
#: a phase that holds a chunk, and the child span around the chunk
#: executable's call (ISSUE 35): no phase, it lies INSIDE ``engine.prefill``
PREFILL, CHUNK = "engine.prefill", "engine.prefill.chunk"
FIRST_TOKEN = "engine.prefill.first_token"
PATHS = {
    "per-step": {},
    "speculative": {"spec_tokens": 3},   # draft_model: the model itself
}


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(7)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


@pytest.fixture
def tracer():
    TRACER.clear()
    TRACER.enable()
    yield TRACER
    TRACER.disable()
    TRACER.clear()


def _engine(model, **kw):
    if "spec_tokens" in kw:
        kw["draft_model"] = model
    return LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=4,
                     **kw)


def _chunks(events):
    """The chunk spans by time, each checked to lie inside a phase
    ``engine.prefill``."""
    phases = [e for e in events if e["name"] == PREFILL]
    out = sorted((e for e in events if e["name"] == CHUNK),
                 key=lambda e: e["ts"])
    for c in out:
        assert c["cat"] == "engine" and sum(
            p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
            for p in phases) == 1
    return out


def _submit(eng, lengths=(5, 11, 17), new=6, **kw):
    rng = np.random.RandomState(0)
    vocab = eng.model.config.vocab_size
    return [eng.add_request(rng.randint(0, vocab, n).astype(np.int32),
                            SamplingParams(max_new_tokens=new), **kw)
            for n in lengths]


def _steps(events):
    """[(engine.step event, [the phases inside it])], by time."""
    evs = sorted((e for e in events if e["name"].startswith("engine.")
                  and e["name"] != CHUNK), key=lambda e: e["ts"])
    steps = [(e, []) for e in evs if e["name"] == "engine.step"]
    for e in evs:
        if e["name"] == "engine.step":
            continue
        holders = [s for s in steps
                   if s[0]["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= s[0]["ts"] + s[0]["dur"] + 1e-3]
        assert len(holders) == 1, f"{e['name']} lies in no engine.step"
        holders[0][1].append(e)
    return steps


@pytest.mark.parametrize("path", list(PATHS))
def test_every_step_is_cut_into_the_same_phases(model, tracer, path):
    with _engine(model, **PATHS[path]) as eng:
        name = eng._name
        _submit(eng)
        n_steps = 0
        while eng.has_work():
            eng.step()
            n_steps += 1
    steps = _steps(tracer.events())
    assert len(steps) == n_steps > 3
    prefills, first, shapes = 0, [], []
    for k, (step, inside) in enumerate(steps, start=1):
        names = [e["name"] for e in inside]
        assert step["args"] == {"engine": name, "step": k}
        # a phase carries the step's args; the first token's adds its own
        assert all(e["cat"] == "engine" and step["args"] == {
            k: v for k, v in e["args"].items()
            if e["name"] != FIRST_TOKEN or k not in ("requests", "behind")}
            for e in inside)
        # the phases follow each other: none starts before the last ended
        for a, b in zip(inside, inside[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a["name"], b["name"])
        assert names[0] == "engine.admit" and names[-1] == "engine.bookkeeping"
        decode = [n for n in names if n.startswith("engine.decode.")]
        if path == "speculative":
            assert decode == ["engine.decode.prepare", "engine.decode.draft",
                              *DECODE_PHASES]
        else:
            assert decode in PER_STEP
            shapes.append(PER_STEP.index(decode))
        prefills += names.count(PREFILL)
        first += [(e["args"]["requests"], e["args"]["behind"])
                  for e in inside if e["name"] == FIRST_TOKEN]
        if FIRST_TOKEN in names:
            assert PREFILL in names      # such a call holds its chunk too
        assert set(names) <= {"engine.admit", PREFILL, FIRST_TOKEN,
                              "engine.bookkeeping", "engine.decode.draft",
                              *DECODE_PHASES}
    if path == "per-step":
        # two dispatches in the first call, one in every call after it
        # until the last, which only fetches what the one before it made
        assert shapes == [0] + [1] * (n_steps - 2) + [2]
    # one chunk a prompt, in the steps that admit them, and a first token a
    # prompt: at once behind its chunk with nothing in flight (the first
    # call, and every call of the speculative path); the two prompts admitted
    # beside a step in flight get theirs behind the call's decode dispatch,
    # which enqueued a step behind their chunks (ISSUE 34)
    assert prefills == 3 == len(_chunks(tracer.events()))
    assert first == ([(1, 0), (1, 1), (1, 1)] if path == "per-step"
                     else [(1, 0)] * 3)
    assert PREFILL in [e["name"] for e in steps[0][1]]
    assert PREFILL not in [e["name"] for e in steps[-1][1]]
    # request spans keep the request id as their shared identifier
    queued = [e for e in tracer.events() if e["name"] == "request.queued"]
    assert sorted(e["args"]["rid"] for e in queued) == sorted(
        e["tid"] for e in queued) and len(queued) == 3


def test_trace_report_tables_the_phases_of_an_export(model, tracer, tmp_path):
    """``scripts/trace_report.py`` is the reader of the tracer's export:
    its span table has a row for each phase, one event a step."""
    import importlib.util
    import json
    import os

    with _engine(model) as eng:
        _submit(eng, new=3)
        n_steps = 0
        while eng.has_work():
            eng.step()
            n_steps += 1
    path = tracer.export(str(tmp_path / "t.json"))
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", script)
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    doc = json.load(open(path))
    agg = report.aggregate_spans(doc["traceEvents"])
    # every step was dispatched once and fetched once; the first call
    # prepared two (ISSUE 28), and so did the second: its step's one row
    # ended there, so nothing could go ahead of its prefill's fetch, and the
    # dispatch was tried again behind it, as before ISSUE 34
    for name in ("engine.step", "engine.admit", "engine.bookkeeping",
                 *DECODE_PHASES[1:]):
        assert agg[name]["count"] == n_steps, name
    assert agg["engine.decode.prepare"]["count"] == n_steps + 2
    # a chunk a prompt, the executable's call a child span of each, and a
    # first token a prompt under its own name
    assert agg[PREFILL]["count"] == agg[CHUNK]["count"] == 3
    assert agg[FIRST_TOKEN]["count"] == 3
    assert "engine.decode.fetch" in report.build_report(trace_doc=doc)


def test_the_names_the_benchmark_reads_are_the_engines(model, tracer):
    """``benchmarks/harness/program_spans.py`` finds the phases by name:
    its constants are the names a steady call records, each once, and the
    phases tile the call's ``engine.step``."""
    from benchmarks.harness import program_spans as ps

    with _engine(model) as eng:
        _submit(eng, lengths=(5,), new=8)
        for _ in range(3):
            eng.step()
        tracer.clear()
        eng.step()
        assert eng.metrics()["decode_steps_ahead"] == 3
    (step, inside), = _steps(tracer.events())
    names = [e["name"] for e in inside]
    assert step["name"] == ps.STEP
    assert names == ["engine.admit", *DECODE_PHASES, "engine.bookkeeping"]
    assert set(ps.PREPARE) | {ps.FETCH} <= set(names)
    assert ps.PREFILL == "engine.prefill" and ps._PREFIX == "engine."
    assert [n for n in names if n not in ps.PREPARE and n != ps.FETCH] == [
        "engine.decode.dispatch", "engine.decode.emit"]
    # the phases tile the step: what lies between two of them, and around
    # them inside the step, is a few calls of the tracer
    covered = sum(e["dur"] for e in inside)
    assert 0 <= step["dur"] - covered < max(0.2 * step["dur"], 200.0)
    parsed = ps.steps_of([(e["name"], e["ts"] * 1e-6, e["dur"] * 1e-6)
                          for e in tracer.events()])
    assert len(ps.decode_only(parsed)) == 1
    assert ps.span_ms(parsed[0], ps.PREPARE) > 0


def test_a_first_token_has_a_phase_of_its_own_on_both_ways(model, tracer):
    """ISSUE 34: the chunk is enqueued under ``engine.prefill`` before the
    prepare, the next decode step is dispatched, and only then are the
    chunk's logits fetched and the first token emitted. ISSUE 35: that
    wait has a name of its own, ``engine.prefill.first_token`` (one name,
    one meaning), also where it follows its chunk at once because nothing
    is in flight (the first call). Either call still holds
    ``engine.prefill``, so by ``benchmarks/harness/program_spans.py``'s
    rule it is no decode-only step."""
    from benchmarks.harness import program_spans as ps

    with _engine(model, ingest_async=False) as eng:
        first, = _submit(eng, lengths=(5,), new=8)
        outs = eng.step()
        assert [o.rid for o in outs] == [first, first]    # nothing in flight
        (_, alone), = _steps(tracer.events())
        assert [e["name"] for e in alone] == [
            "engine.admit", PREFILL, FIRST_TOKEN, *DECODE_PHASES[:2],
            *DECODE_PHASES, "engine.bookkeeping"]
        assert (alone[2]["args"]["requests"], alone[2]["args"]["behind"]) \
            == (1, 0)
        eng.step()
        tracer.clear()
        second, = _submit(eng, lengths=(11,), new=4)
        outs = eng.step()
        assert [o.rid for o in outs] == [second, first]   # first tokens first
        m = eng.metrics()
        assert (m["prefills"], m["prefill_ends_behind_decode"]) == (2, 1)
        eng.step()
    (step, inside), (after, inside_after) = _steps(tracer.events())
    assert [e["name"] for e in inside] == [
        "engine.admit", PREFILL, "engine.decode.prepare",
        "engine.decode.dispatch", FIRST_TOKEN, "engine.decode.fetch",
        "engine.decode.emit", "engine.bookkeeping"]
    assert [e["name"] for e in inside_after] == [
        "engine.admit", *DECODE_PHASES, "engine.bookkeeping"]
    # the request's own span closes with its first token, in that phase,
    # which says that a step was enqueued behind the chunk it waits for
    fetch, = [e for e in inside if e["name"] == FIRST_TOKEN]
    assert fetch["args"] == {**step["args"], "requests": 1, "behind": 1}
    chunk, = _chunks(tracer.events())
    assert (chunk["args"]["rid"], chunk["args"]["last"]) == (second, 1)
    prefill, = [e for e in tracer.events() if e["name"] == "request.prefill"]
    assert prefill["tid"] == second
    assert fetch["ts"] <= prefill["ts"] + prefill["dur"] <= \
        fetch["ts"] + fetch["dur"] + 1e-3
    parsed = ps.steps_of([(e["name"], e["ts"] * 1e-6, e["dur"] * 1e-6)
                          for e in tracer.events()])
    assert len(parsed) == 2 and len(parsed[0][ps.PREFILL]) == 1
    assert ps.decode_only(parsed) == [parsed[1]]


def test_a_chunk_says_what_it_did(model, tracer):
    """ISSUE 35: ``engine.prefill.chunk`` lies around the chunk
    executable's call and carries ``tokens`` real tokens from ``start`` in
    a graph of ``padded``, a rung; the chunks of a request tile its prompt;
    ``metrics()`` sums the same two numbers whether anything traces or not."""
    lengths = (5, 29, 70)
    with LLMEngine(model, num_blocks=64, block_size=8, max_batch_size=4,
                   max_prefill_tokens_per_step=32) as eng:
        rungs = eng.prefill_buckets
        rids = _submit(eng, lengths=lengths, new=3)
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        text = metrics.to_prometheus_text()
        assert "serving_prefill_tokens_total" in text
        assert "serving_prefill_padded_tokens_total" in text
    chunks = [c["args"] for c in _chunks(tracer.events())]
    assert all(set(c) == {"rid", "start", "tokens", "padded", "last"}
               for c in chunks)
    for rid, n in zip(rids, lengths):
        mine = [c for c in chunks if c["rid"] == rid]
        at = 0
        for c in mine:              # in order, each from where the last ended
            assert c["start"] == at and 0 < c["tokens"] <= c["padded"]
            assert c["padded"] in rungs and c["padded"] <= 32
            at += c["tokens"]
        assert at == n and [c["last"] for c in mine] == \
            [0] * (len(mine) - 1) + [1]
    assert len([c for c in chunks if c["rid"] == rids[2]]) >= 3
    assert m["prefill_chunks"] == len(chunks)
    assert m["prefill_tokens"] == sum(c["tokens"] for c in chunks) \
        == sum(lengths)
    assert m["prefill_padded_tokens"] == sum(c["padded"] for c in chunks) \
        > m["prefill_tokens"]


def test_a_state_kind_adds_no_span_name(tracer):
    """ISSUE 33: the slots operand is made inside ``engine.decode.prepare``
    and a chunk's slot inside ``engine.prefill``; admission grows no phase. A
    step committed by ``_drain`` between two calls is a fetch and an emit
    under an ``engine.step`` of their own, without a step number."""
    from paddle_tpu.models import NemotronHForCausalLM, nemotron_h_tiny

    paddle.seed(7)
    net = NemotronHForCausalLM(nemotron_h_tiny())
    net.eval()
    with LLMEngine(net, num_blocks=64, block_size=4, max_batch_size=4,
                   max_prefill_tokens_per_step=8) as eng:
        _submit(eng, lengths=(5, 19), new=6)
        for _ in range(5):
            eng.step()
        assert eng._ahead is not None
        eng._drain()
        while eng.has_work():
            eng.step()
        assert eng.metrics()["decode_steps_sync_by_reason"]["commit"] == 1
    steps = _steps(tracer.events())
    names = {e["name"] for _, inside in steps for e in inside}
    assert names == {"engine.admit", PREFILL, FIRST_TOKEN,
                     "engine.bookkeeping", *DECODE_PHASES}
    committed = [[e["name"] for e in inside] for step, inside in steps
                 if "step" not in step["args"]]
    assert committed == [["engine.decode.fetch", "engine.decode.emit"]]


def test_a_tick_without_work_is_no_step(model, tracer):
    with _engine(model) as eng:
        assert eng.step() == []
    (step, inside), = _steps(tracer.events())
    assert step["args"] == {"engine": step["args"]["engine"]}   # no number
    assert [e["name"] for e in inside] == ["engine.admit"]


def test_with_tracing_off_nothing_is_recorded_or_built(model, monkeypatch):
    """The disabled path: ``span()`` hands back the one shared no-op,
    ``engine.step()`` appends no event, no ``add_complete`` call is even
    made (so no ``args`` dict is built for one), no ``span()`` call is
    handed an ``args`` dict (they are built under ``live()``), and
    ``trace.py`` itself allocates nothing. The two prefill counters count
    all the same."""
    TRACER.disable()
    TRACER.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert not trace.live()
    assert trace.span("x") is trace.span("y", cat="engine") is trace._NOOP
    opened, real_span = [], trace.span

    def span(name, cat="host", tid=None, args=None):
        assert args is None, f"{name}: args built with tracing off"
        opened.append(name)
        return real_span(name, cat=cat, tid=tid, args=args)

    monkeypatch.setattr(trace, "span", span)

    def refuse(*a, **kw):
        raise AssertionError("add_complete called with the tracer off")

    monkeypatch.setattr(trace, "add_complete", refuse)
    monkeypatch.setattr(TRACER, "add_complete", refuse)
    only = [tracemalloc.Filter(True, trace.__file__)]

    def grown_in_trace_py(work):
        """Lines of ``trace.py`` that hold more memory after ``work()``."""
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            work()
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        return {s.traceback[0].lineno
                for s in after.filter_traces(only).compare_to(
                    before.filter_traces(only), "lineno") if s.size_diff > 0}

    with _engine(model) as eng:
        _submit(eng, new=3)
        eng.step()                       # compiles, imports

        def steps():
            _submit(eng, new=3)
            while eng.has_work():
                eng.step()
            for _ in range(100):
                trace.span("engine.step", cat="engine", args=None)
                trace.instant("x")

        grown = grown_in_trace_py(steps)
        # tracemalloc sees the whole process: a thread another test left
        # behind can be inside trace.py when a snapshot is taken (the
        # driver's whole run, PR 26). What the engine's steps and the calls
        # allocate there they allocate every time, on the same lines; a
        # bystander's frame is not there in run after run. So the SAME work
        # (requests handed in anew, stepped to the end) is repeated, and
        # only a line that grew every time counts
        for _ in range(3):
            if not grown:
                break
            grown &= grown_in_trace_py(steps)
        m = eng.metrics()
    assert grown == set()
    assert TRACER.events() == []
    assert {"engine.step", PREFILL, CHUNK, FIRST_TOKEN} <= set(opened)
    assert m["prefill_padded_tokens"] >= m["prefill_tokens"] > 0


def test_spans_land_in_a_profile_inside_the_callers_span(model, tmp_path):
    """Under a real ``jax.profiler`` session (CPU) the phases are events
    of exactly their names on the ``/host:CPU`` line that holds the
    caller's outer span, inside it, and the tracer's buffer stays empty."""
    TRACER.disable()
    TRACER.clear()
    with _engine(model) as eng:
        _submit(eng, lengths=(5,), new=8)
        for _ in range(3):
            eng.step()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert trace.span("x") is not trace._NOOP
            for _ in range(2):
                with jax.profiler.TraceAnnotation("outer"):
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    assert trace.span("x") is trace._NOOP
    assert TRACER.events() == []
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    line, = [ln for ln in lines if any(n == "outer" for n, _, _ in ln)]
    outer = [(a, b) for n, a, b in line if n == "outer"]
    assert len(outer) == 2
    for want in ("engine.step", "engine.admit", "engine.decode.prepare",
                 "engine.decode.dispatch", "engine.decode.fetch",
                 "engine.decode.emit", "engine.bookkeeping"):
        got = [(a, b) for n, a, b in line if n == want]
        assert len(got) == 2, want
        for (a, b), (oa, ob) in zip(got, outer):
            assert oa <= a and b <= ob, want
    step = [(a, b) for n, a, b in line if n == "engine.step"]
    emit = [(a, b) for n, a, b in line if n == "engine.decode.emit"]
    assert all(sa <= a and b <= sb for (a, b), (sa, sb) in zip(emit, step))


def test_a_spans_args_ride_in_the_profile_as_statistics(model, tmp_path):
    """ISSUE 35: under a ``jax.profiler`` session, with the tracer off, the
    scalars among a span's args are the statistics of an event still named
    as the span is, so the benchmark reads a chunk's counts on the device
    line's clock; what is no scalar stays out."""
    TRACER.disable()
    TRACER.clear()
    with _engine(model, ingest_async=False) as eng:
        _submit(eng, lengths=(5,), new=8)
        for _ in range(3):
            eng.step()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert trace.live() and not trace.enabled()
            with trace.span("x", args={"n": 3, "f": 0.5, "b": True,
                                       "s": "t#1,u", "none": None, "l": [1]}):
                pass
            rid, = _submit(eng, lengths=(11,), new=4)
            for _ in range(2):
                with jax.profiler.TraceAnnotation("outer"):
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    assert not trace.live() and TRACER.events() == []
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("x", CHUNK, FIRST_TOKEN, "engine.step"):
                    found.setdefault(e.name, []).append(dict(e.stats))
    # (the profile's own separators in a string are written "_")
    assert found["x"] == [{"n": 3, "f": 0.5, "b": 1, "s": "t_1_u"}]
    assert found[CHUNK] == [{"rid": rid, "start": 0, "tokens": 11,
                             "padded": 16, "last": 1}]
    (first,), steps = found[FIRST_TOKEN], found["engine.step"]
    assert (first["requests"], first["behind"]) == (1, 1)
    assert first["engine"] == steps[0]["engine"] == eng._name.replace("#", "_")
    # the step's number is known once the tick is seen to have work: the
    # phases from there on carry it, ``engine.step`` itself was entered before
    assert first["step"] == 4 and "step" not in steps[0]


def test_arrival_t_is_the_due_time(model, tracer):
    """A request due 250 ms before it was handed in: TTFT, the queued
    span and the queue wait all count from the due time."""
    with _engine(model) as eng:
        _submit(eng, lengths=(5, 7), new=2)   # compile before the clock matters
        while eng.has_work():
            eng.step()
        eng.reset_metrics()
        tracer.clear()
        due = time.perf_counter() - 0.25
        late, = _submit(eng, lengths=(5,), new=2, arrival_t=due)
        now, = _submit(eng, lengths=(7,), new=2)
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        assert eng.request(late).t_submit == int(due * 1e9)
        assert "serving_queue_wait_ms" in metrics.to_prometheus_text()
    assert m["queue_wait_ms"]["count"] == 2
    assert m["queue_wait_ms"]["max"] >= 250.0 > m["queue_wait_ms"]["min"]
    assert m["ttft_ms"]["max"] >= 250.0
    queued = {e["tid"]: e for e in tracer.events()
              if e["name"] == "request.queued"}
    assert queued[late]["dur"] >= 250e3 > queued[now]["dur"]


# --- kernels under names of their own -------------------------------------------

def _paged_decode(int8=False):
    from paddle_tpu.ops.pallas import paged_attention as pa

    pool = jnp.zeros((8, 16, 2, 128), jnp.int8 if int8 else jnp.float32)
    # int8 pools go to a call of their own, under the same name
    scales = (jnp.ones((8, 16, 2), jnp.float32),) * 2 if int8 else ()
    return (lambda q, k, v, t, n, *s: pa.paged_decode_attention_pallas(
        q, k, v, t, n, 0.088, *s)), (
        jnp.zeros((2, 4, 128), jnp.float32), pool, pool,
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)) + scales


def _paged_prefill():
    from paddle_tpu.ops.pallas import paged_attention as pa

    pool = jnp.zeros((8, 16, 2, 128), jnp.float32)
    return (lambda q, k, v, t, n, s: pa.paged_multiquery_attention_pallas(
        q, k, v, t, n, s, 0.088)), (
        jnp.zeros((1, 16, 4, 128), jnp.float32), pool, pool,
        jnp.zeros((1, 4), jnp.int32), jnp.full((1,), 16, jnp.int32),
        jnp.zeros((1,), jnp.int32))


def _flash(grad):
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.zeros((2, 256, 128), jnp.float32)
    bq, bk = fa._block_sizes(256, 256)

    def fwd(q, k, v):
        return fa._flash_mha(q, k, v, 0.125, True, bq, bk)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))(q, k, v)

    return (bwd if grad else fwd), (q, q, q)


def _rms_norm():
    from paddle_tpu.ops.pallas import rms_norm as rn

    x = jnp.zeros((16, 128), jnp.float32)
    return (lambda x, y, w: rn._fwd(x, y, w, 1e-6)), (
        x, x, jnp.ones((1, 128), jnp.float32))


def _layer_norm():
    from paddle_tpu.ops.pallas import rms_norm as rn

    x = jnp.zeros((16, 128), jnp.float32)
    w = jnp.ones((1, 128), jnp.float32)
    return (lambda x, y, w, b: rn._ln_fwd(x, y, w, b, 1e-6)), (x, x, w, w)


def _moe_ffn():
    from paddle_tpu.ops.pallas import moe_ffn as mf

    return mf._ffn_fwd_arrays, (
        jnp.zeros((2, 128, 128), jnp.float32),
        jnp.zeros((2, 128, 256), jnp.float32),
        jnp.zeros((2, 128, 256), jnp.float32),
        jnp.zeros((2, 256, 128), jnp.float32))


def _chunk_attention(window=None):
    from paddle_tpu.ops.pallas import paged_attention as pa

    name = "chunk_attention_" + ("window" if window else "global")
    sink = jnp.zeros((4,), jnp.float32) if window else None

    def fn(q, k, v):
        return pa.chunk_attention_pallas(q, k, v, 8, 0, 24, 0.3, window=window,
                                         sink=sink, name=name)

    return fn, (jnp.zeros((16, 4, 32), jnp.float32),
                jnp.zeros((32, 2, 32), jnp.float32),
                jnp.zeros((32, 2, 16), jnp.float32))


def _paged_decode_window():
    from paddle_tpu.ops.pallas import paged_attention as pa

    def fn(q, k, v, t, l):
        return pa.paged_decode_attention_pallas(
            q, k, v, t, l, 0.3, window=8, ring=True,
            sink=jnp.zeros((4,), jnp.float32), num_kv_heads=2,
            name="paged_decode_attention_window")

    return fn, (jnp.zeros((2, 4, 32), jnp.float32),
                jnp.zeros((8, 8, 32), jnp.float32),
                jnp.zeros((8, 8, 16), jnp.float32),
                jnp.zeros((2, 3), jnp.int32), jnp.ones((2,), jnp.int32))


def _paged_decode_latent():
    from paddle_tpu.ops.pallas import paged_attention as pa

    def fn(q, pool, t, l):
        return pa.paged_decode_attention_latent_pallas(q, pool, t, l, 0.3, 32)

    return fn, (jnp.zeros((2, 4, 48), jnp.float32),
                jnp.zeros((8, 4, 48), jnp.float32),
                jnp.zeros((2, 3), jnp.int32), jnp.ones((2,), jnp.int32))


def _grouped_swiglu(gated=True):
    from paddle_tpu.ops.pallas import grouped_ffn as gf

    def fn(x, order, w):
        items = jnp.zeros((2,), jnp.int32)
        return gf.grouped_swiglu(
            x, order, items, items, jnp.full((2,), 4, jnp.int32), jnp.int32(1),
            [(w, w, w.T) if gated else (w, w.T)], rows=16, top_k=2)

    return fn, (jnp.zeros((4, 128), jnp.float32), jnp.arange(8, dtype=jnp.int32),
                jnp.zeros((128, 128), jnp.float32))


def _mamba2_decode():
    from paddle_tpu.ops.pallas import mamba2

    def fn(state, x, dt, b):
        return mamba2.mamba2_decode_update(
            state, jnp.asarray([1, 2], jnp.int32), x, dt, -jnp.ones((4,)), b, b)

    return fn, (jnp.zeros((3, 4, 16, 8), jnp.float32), jnp.zeros((2, 4, 8)),
                jnp.zeros((2, 4)), jnp.zeros((2, 2, 16)))


def _gated_delta_decode():
    from paddle_tpu.ops.pallas import gated_delta

    def fn(state, q, v, g):
        return gated_delta.gated_delta_decode_update(
            state, jnp.asarray([1, 2], jnp.int32), q, q, v, g, g)

    return fn, (jnp.zeros((3, 4, 16, 8), jnp.float32), jnp.zeros((2, 2, 16)),
                jnp.zeros((2, 4, 8)), jnp.zeros((2, 4)))


#: one entry a ``pl.pallas_call`` site; a site that takes its name from its
#: wrapper is listed once more under each name the serving path gives it
#: (the fp decode site, ``_decode_call``, serves K / V and latent pages; the
#: grouped expert site serves experts with a gate and without)
KERNELS = [
    ("chunk_attention_global", _chunk_attention),
    ("paged_decode_attention", _paged_decode),
    ("paged_decode_attention", lambda: _paged_decode(int8=True)),
    ("paged_decode_attention_latent", _paged_decode_latent),
    ("paged_prefill_attention", _paged_prefill),
    ("flash_attention_fwd", lambda: _flash(False)),
    ("flash_attention_bwd_dq", lambda: _flash(True)),
    ("flash_attention_bwd_dkv", lambda: _flash(True)),
    ("rms_norm_fwd", _rms_norm),
    ("layer_norm_fwd", _layer_norm),
    ("moe_ffn", _moe_ffn),
    ("moe_grouped_swiglu", _grouped_swiglu),
    ("moe_grouped_relu2", lambda: _grouped_swiglu(gated=False)),
    ("mamba2_decode_update", _mamba2_decode),
    ("gated_delta_decode_update", _gated_delta_decode),
]


def _carries(text, name):
    """Whether a lowered text (``debug_info=True``) holds a Pallas call
    named ``name`` (a call that is a jit of its own starts its name stack
    with the name)."""
    return bool(re.search(r'loc\("(?:[^"]*[/(])?' + name + r'[/)]', text))


@pytest.mark.parametrize("name,make", KERNELS, ids=[k[0] for k in KERNELS])
def test_the_lowered_text_carries_the_kernels_name(name, make, monkeypatch):
    """The name a ``pl.pallas_call`` is given enters the name stack of
    everything it lowers to (interpret mode here; on the TPU it becomes the
    HLO instruction's own name, ``%paged_decode_attention.16``)."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    fn, args = make()
    assert _carries(jax.jit(fn).lower(*args).as_text(debug_info=True), name)


def test_every_pallas_call_has_a_name():
    import os

    import paddle_tpu.ops.pallas as pkg

    root = os.path.dirname(pkg.__file__)
    calls = 0
    for f in sorted(os.listdir(root)):
        if not f.endswith(".py"):
            continue
        src = open(os.path.join(root, f)).read()
        for m in re.finditer(r"pl\.pallas_call\(", src):
            calls += 1
            # the call's own argument list runs to the first line that
            # closes it at the call's indentation
            body = src[m.end():].split("\n    )", 1)[0]
            # ... or hands on its wrapper's ``name``, whose default is a
            # literal (the serving dispatch names a kernel by the kind of
            # layer it serves, ISSUE 27)
            head = src[:m.start()].rsplit("\ndef ", 1)[-1]
            assert re.search(r'\bname="[a-z_0-9]+"', body) or (
                re.search(r"\bname=name\b", body)
                and re.search(r'\bname="[a-z_0-9]+"\):', head)), (f, body[:80])
    # ``_decode_call`` and the grouped expert call are listed under both
    # their names
    assert calls == len(KERNELS) - 2


@pytest.mark.parametrize("name,make", [
    ("chunk_attention_window", lambda: _chunk_attention(8)),
    ("paged_decode_attention_window", _paged_decode_window)],
    ids=["chunk-window", "decode-window"])
def test_a_layer_kind_names_its_kernels(name, make, monkeypatch):
    """Window and global layers run the same two call sites under names of
    their own, so that a trace tells them apart."""
    test_the_lowered_text_carries_the_kernels_name(name, make, monkeypatch)


# --- which kernel a Llama engine's chunk program carries (ISSUE 36) -------------

def _program_kernels(text):
    return {name for name in ("chunk_attention", "paged_prefill_attention")
            if _carries(text, name)}


def _chunk_text(eng, rung):
    c = eng.cache
    if eng._prefill_jit is None:
        eng._build_jits()
    return eng._prefill_jit.lower(
        [p._data for p in eng._params], jnp.zeros((1, rung), jnp.int32),
        np.int32(0), np.int32(rung), jnp.zeros(eng.max_pages, jnp.int32),
        c.k, c.v, c.k_scale, c.v_scale).as_text(debug_info=True)


@pytest.mark.parametrize("kv_dtype,kernel,share", [
    (None, "chunk_attention", 1), ("int8", "paged_prefill_attention", 0)],
    ids=["bf16-in-a-row", "int8-page-by-page"])
def test_a_llama_chunk_program_reads_by_its_pools_dtype(
        model, monkeypatch, kv_dtype, kernel, share):
    """A Llama engine's chunk program carries the chunk kernel over
    unquantized pools (the request's pages in a row) and the page-by-page
    multi-query kernel over int8 codes, never both; ``metrics()`` counts the
    chunks of the first kind."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    with _engine(model, kv_dtype=kv_dtype,
                 max_prefill_tokens_per_step=16) as eng:
        assert _program_kernels(
            _chunk_text(eng, eng.prefill_buckets[0])) == {kernel}
        _submit(eng, lengths=(5, 29), new=2)
        while eng.has_work():
            eng.step()
        m = eng.metrics()
        # (a counter nothing has added to has no series)
        assert not share or "serving_prefill_chunks_in_a_row_total" in \
            metrics.to_prometheus_text()
    assert m["prefill_chunks"] >= 3
    assert m["prefill_chunks_in_a_row"] == share * m["prefill_chunks"]


def test_the_verify_program_keeps_the_multi_query_kernel(model, monkeypatch):
    """``[B, k+1]`` rows of many requests are no chunk of one request: the
    speculative verify step reads page by page as before, beside a chunk
    program (the target's and the draft's) that reads in a row."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    with _engine(model, spec_tokens=3) as eng:
        c, B, K = eng.cache, eng.max_batch_size, 3
        eng._build_jits()
        text = eng._verify_jit.lower(
            [p._data for p in eng._params], jnp.zeros((B, K + 1), jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.zeros((B, eng.max_pages), jnp.int32),
            jnp.zeros((B, K), jnp.int32), c.k, c.v, c.k_scale,
            c.v_scale).as_text(debug_info=True)
        assert _program_kernels(text) == {"paged_prefill_attention"}
        assert _program_kernels(
            _chunk_text(eng, eng.prefill_buckets[0])) == {"chunk_attention"}
