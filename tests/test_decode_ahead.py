"""Decode dispatch-ahead (ISSUE 28): ``LLMEngine`` enqueues decode step
k+1 behind step k before it fetches step k's tokens, feeding k's greedy
tokens to k+1 on the device.

The synchronous engine these cases compare with is the SAME class with the
one decision overridden (``Synchronous._dispatch_ahead``): what is left
then is the path the engine takes by itself beside a sampled row, under
pool pressure or on a draft model. Every case plays one
script of submissions and events, call by call, on both, and wants the
same tokens a request, the same reasons, the same rows of logits.

A prefill that ends beside a step in flight (ISSUE 34) has its logits
fetched behind the call's decode dispatch: the cases named in ``ENDS`` play
the ways a last chunk can meet a step, and the counter
``prefill_ends_behind_decode`` says in which the order engaged."""

import itertools
import os
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.inference.serving import (EngineClosedError, LLMEngine,
                                          SamplingParams, load_prefix_store)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny
from paddle_tpu.observability import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness import reference_mimo_v2 as ref  # noqa: E402

#: every waiting request is admitted in the call it is found in, so that a
#: script's events meet the same state on both engines: one prefill a call
#: (the engine's default) makes requests JOIN beside a step in flight, a
#: call later than on the synchronous engine, which the cases that are
#: about joining ask for
LLAMA = dict(num_blocks=64, block_size=8, max_batch_size=4,
             max_prefills_per_step=4, ingest_async=False)
MIMO = dict(num_blocks=96, block_size=4, max_batch_size=4, max_model_len=96,
            prefill_buckets=[8, 16, 32, 64, 96], max_prefills_per_step=4,
            ingest_async=False)


class Synchronous(LLMEngine):
    """Never a step ahead: every decode step is dispatched, fetched and
    emitted inside its own call."""

    def _dispatch_ahead(self, cur):
        return None


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def llama(seed=7):
    paddle_tpu.seed(seed)
    net = LlamaForCausalLM(llama_tiny())
    net.eval()
    return net


def mimo(seed=3):
    paddle_tpu.seed(seed)
    net = MiMoV2ForCausalLM(mimo_v2_tiny())
    net.eval()
    return net


def prompts_of(lengths, seed=0, vocab=160):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


def compiles_counter():
    """Executables JAX builds from here on, eager operations included."""
    box = [0]

    def on(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return box


COMPILES = compiles_counter()


class Played:
    """What one engine made of a script."""

    def __init__(self):
        self.calls = []        # [[(request index, token, finished, reason)]]
        self.tokens = {}       # request index -> its output tokens
        self.reasons = {}      # request index -> finish reason
        self.cached = {}       # request index -> num_cached when it ended
        self.logits = {}       # (call, request index) -> last_logits then
        self.metrics = None
        self.kept = {}         # what the script's events put aside


def play(cls, net, engine, requests, events=None):
    """Run ``requests`` (``[(prompt, SamplingParams fields, call it is
    handed in before)]``) through ``cls(net, **engine)``. ``events`` maps a
    call's number to ``fn(eng, rids, played)``, run before that call. The
    engine is stepped until it has no work and nothing is left to hand in."""
    events = events or {}
    out = Played()
    with cls(net, **engine) as eng:
        rids, index = {}, {}
        for call in itertools.count():
            for k, (prompt, fields, at) in enumerate(requests):
                if at == call:
                    rids[k] = eng.add_request(prompt, SamplingParams(**fields))
                    index[rids[k]] = k
            if call in events:
                events[call](eng, rids, out)
            pending = any(at > call for _, _, at in requests)
            if not eng.has_work() and not pending and max(events, default=-1) <= call:
                break
            got = []
            for o in eng.step():
                k = index[o.rid]
                got.append((k, o.token, o.finished, o.finish_reason))
                req = eng.request(o.rid)
                if req.last_logits is not None:
                    out.logits[(call, k)] = np.array(req.last_logits)
            out.calls.append(got)
        out.kept["in_flight_at_end"] = eng._ahead is not None
        for k, rid in rids.items():
            req = eng._requests.get(rid)
            if req is not None:
                out.tokens[k] = list(req.output_tokens)
                out.reasons[k] = req.finish_reason()
                out.cached[k] = req.num_cached
        out.metrics = eng.metrics()
        out.kept["free"] = eng.cache.allocator.num_free
    return out


def both(net, engine, requests, events=None):
    return (play(LLMEngine, net, engine, requests, events),
            play(Synchronous, net, engine, requests, events))


def adds_up(m):
    """Every emitted decode step of the plain path is counted once, ahead
    or under the reason it was not."""
    by = m["decode_steps_sync_by_reason"]
    assert m["decode_steps_sync"] == sum(by.values())
    assert m["decode_steps_ahead"] + m["decode_steps_sync"] == m["host_syncs"]
    assert set(by) <= {"idle", "sampled", "evict", "drain", "path"}


def same_requests(a, s):
    assert a.tokens == s.tokens
    assert a.reasons == s.reasons
    assert a.metrics["tokens_out"] == s.metrics["tokens_out"]
    adds_up(a.metrics)
    adds_up(s.metrics)
    assert s.metrics["decode_steps_ahead"] == 0
    assert s.metrics["decode_rows_discarded"] == 0
    # a prefill's end is fetched behind a dispatch only where one was made
    assert s.metrics["prefill_ends_behind_decode"] == 0
    assert a.metrics["prefills"] == s.metrics["prefills"] or \
        a.metrics["evictions"]
    assert a.metrics["prefill_ends_behind_decode"] <= a.metrics["prefills"]
    assert a.kept["free"] == s.kept["free"]            # nothing leaked


# --------------------------------------------------------------------------
# one script, two engines
# --------------------------------------------------------------------------

def _mixed(net):
    """Six requests of mixed finish lengths through four slots, one prefill
    a call: rows finish by length while a step is in flight and requests
    join beside one."""
    ps = prompts_of((5, 11, 17, 9, 23, 6))
    reqs = [(p, dict(max_new_tokens=n), 0)
            for p, n in zip(ps, (6, 1, 3, 12, 14, 2))]
    return dict(LLAMA, max_prefills_per_step=1), reqs, None


def _at_once(net):
    """Everything admitted in the first call: no request joins beside a
    step in flight, so the calls themselves are the same."""
    ps = prompts_of((5, 11, 7, 9), seed=1)
    reqs = [(p, dict(max_new_tokens=n), 0)
            for p, n in zip(ps, (7, 3, 10, 5))]
    return LLAMA, reqs, None


def _eos(net):
    """EOS comes while the next step is in flight: the row is discarded."""
    ps = prompts_of((5, 11, 7), seed=2)
    plain = play(Synchronous, net, LLAMA,
                 [(p, dict(max_new_tokens=12), 0) for p in ps])
    reqs = [(p, dict(max_new_tokens=12, eos_token_id=plain.tokens[k][3 + k]), 0)
            for k, p in enumerate(ps)]
    return LLAMA, reqs, None


def _cancel(net):
    ps = prompts_of((5, 11, 7), seed=3)
    reqs = [(p, dict(max_new_tokens=12), 0) for p in ps]

    def cancel(eng, rids, out):
        out.kept["cancelled_with"] = list(eng.request(rids[1]).output_tokens)
        assert eng.cancel(rids[1])

    return LLAMA, reqs, {5: cancel}


def _deadline(net):
    ps = prompts_of((5, 11, 7), seed=4)
    reqs = [(p, dict(max_new_tokens=12), 0) for p in ps]

    def expire(eng, rids, out):
        eng.request(rids[0]).deadline = time.time() - 1.0

    return LLAMA, reqs, {5: expire}


def _evict(net):
    """A pool too small for three requests to run to their ends: room for
    the next step takes an eviction, so it is not dispatched ahead."""
    ps = prompts_of((6, 7, 5), seed=5)
    reqs = [(p, dict(max_new_tokens=14), 0) for p in ps]
    return dict(LLAMA, num_blocks=9, block_size=4, max_batch_size=3,
                max_prefills_per_step=1), reqs, None


def _prefix_cow(net):
    """Shared prefixes, two prompts equal to the last token of a full
    block: admission shares blocks, a write diverges from one."""
    rng = np.random.default_rng(6)
    shared = rng.integers(0, 160, size=16).astype(np.int32)
    tails = [rng.integers(0, 160, size=n).astype(np.int32) for n in (3, 5, 0, 0)]
    reqs = [(np.concatenate([shared, t]), dict(max_new_tokens=9), k)
            for k, t in enumerate(tails)]
    return dict(LLAMA, enable_prefix_cache=True,
                max_prefills_per_step=1), reqs, None


def _chunked_join(net):
    """Long prompts prefilled eight tokens a call beside a decoding batch:
    chunks run between a step in flight and the next, and the request joins
    with its first token from the host."""
    ps = prompts_of((5, 7, 30, 21), seed=7)
    reqs = [(ps[0], dict(max_new_tokens=16), 0),
            (ps[1], dict(max_new_tokens=14), 0),
            (ps[2], dict(max_new_tokens=6), 3),
            (ps[3], dict(max_new_tokens=5), 4)]
    return dict(LLAMA, max_prefill_tokens_per_step=8,
                max_prefills_per_step=1), reqs, None


def _int8(net):
    ps = prompts_of((5, 11, 7, 13), seed=8)
    reqs = [(p, dict(max_new_tokens=n), 0)
            for p, n in zip(ps, (9, 4, 12, 6))]
    return dict(LLAMA, kv_dtype="int8"), reqs, None


def _tier(net):
    """Decode pressure preempts a request into the host tier and revives
    it by page import, both beside steps in flight."""
    ps = prompts_of((8, 8, 8), seed=9)
    reqs = [(p, dict(max_new_tokens=20), 0) for p in ps]
    return dict(LLAMA, num_blocks=5, block_size=8, max_batch_size=2,
                kv_host_blocks=32, max_prefills_per_step=1), reqs, None


def _rings(net):
    """The second model's window rings turn a page every four tokens and
    send the page behind them back while the step that read it is in
    flight; prompts cross the window and the chunk."""
    ps = prompts_of((5, 21, 38, 12), seed=10)
    reqs = [(p, dict(max_new_tokens=n), 0)
            for p, n in zip(ps, (14, 9, 6, 11))]
    return dict(MIMO, max_prefill_tokens_per_step=16,
                max_prefills_per_step=1), reqs, None


def _rings_cancel(net):
    ps = prompts_of((14, 21, 9), seed=11)
    reqs = [(p, dict(max_new_tokens=16), 0) for p in ps]
    return MIMO, reqs, {7: lambda eng, rids, out: eng.cancel(rids[0])}


def _mimo_mixed(net):
    """``_mixed`` on the second model: rows finish by length and requests
    join beside a step in flight while the rings of the others turn."""
    ps = prompts_of((5, 21, 38, 12, 9, 17), seed=14)
    reqs = [(p, dict(max_new_tokens=n), 0)
            for p, n in zip(ps, (14, 1, 6, 11, 9, 2))]
    return dict(MIMO, max_prefills_per_step=1), reqs, None


def _mimo_eos(net):
    """``_eos`` on the second model: the discarded row had turned its ring
    and sent a page back before its request was found gone."""
    ps = prompts_of((14, 21, 9), seed=15)
    plain = play(Synchronous, net, MIMO,
                 [(p, dict(max_new_tokens=16), 0) for p in ps])
    reqs = [(p, dict(max_new_tokens=16, eos_token_id=plain.tokens[k][5 + k]), 0)
            for k, p in enumerate(ps)]
    return MIMO, reqs, None


def _mimo_deadline(net):
    ps = prompts_of((14, 21, 9), seed=16)
    reqs = [(p, dict(max_new_tokens=16), 0) for p in ps]

    def expire(eng, rids, out):
        eng.request(rids[0]).deadline = time.time() - 1.0

    return MIMO, reqs, {7: expire}


def _mimo_chunked_join(net):
    """``_chunked_join`` on the second model: a chunk turns the joining
    request's ring in prefill between a step in flight and the next."""
    ps = prompts_of((5, 7, 38, 25), seed=17)
    reqs = [(ps[0], dict(max_new_tokens=20), 0),
            (ps[1], dict(max_new_tokens=18), 0),
            (ps[2], dict(max_new_tokens=6), 3),
            (ps[3], dict(max_new_tokens=5), 4)]
    return dict(MIMO, max_prefill_tokens_per_step=8,
                max_prefills_per_step=1), reqs, None


# -- a prefill's end beside a step in flight (ISSUE 34) ----------------------

def _arrival(net):
    """One request arrives into a decoding batch: its one chunk is enqueued
    behind the step in flight, the next step is dispatched for the two rows
    that decode, and only then are the chunk's logits fetched."""
    ps = prompts_of((5, 11, 9), seed=23)
    reqs = [(ps[0], dict(max_new_tokens=12), 0),
            (ps[1], dict(max_new_tokens=10), 0),
            (ps[2], dict(max_new_tokens=6), 3)]
    return LLAMA, reqs, None


def _two_ends(net):
    """Two short prompts whose last chunks share a call: both enqueued,
    one dispatch, then both fetches."""
    ps = prompts_of((5, 11, 9, 7), seed=24)
    reqs = [(ps[0], dict(max_new_tokens=12), 0),
            (ps[1], dict(max_new_tokens=10), 0),
            (ps[2], dict(max_new_tokens=6), 3),
            (ps[3], dict(max_new_tokens=5), 3)]
    return LLAMA, reqs, None


def _long_prompt(net):
    """A long prompt eight tokens a call: its middle chunks fetch nothing,
    decode steps run between them, and the last one ends in a call of its
    own beside a step in flight."""
    ps = prompts_of((5, 3, 30), seed=25)
    reqs = [(ps[0], dict(max_new_tokens=16), 0),
            (ps[1], dict(max_new_tokens=14), 0),
            (ps[2], dict(max_new_tokens=5), 2)]
    return dict(LLAMA, max_prefill_tokens_per_step=8), reqs, None


def _one_token(net):
    """The arriving request wants one token: it ends at the deferred fetch,
    after the next step was dispatched without it, and never decodes."""
    ps = prompts_of((5, 11, 9), seed=26)
    reqs = [(ps[0], dict(max_new_tokens=12), 0),
            (ps[1], dict(max_new_tokens=10), 0),
            (ps[2], dict(max_new_tokens=1), 3)]
    return LLAMA, reqs, None


def _sampled_arrival(net):
    """A sampled request arrives beside greedy rows: while it prefills
    nobody sees a sampled row, so the next step goes ahead of its fetch;
    once it is ready the batch takes the synchronous path."""
    ps = prompts_of((5, 11, 9), seed=27)
    reqs = [(ps[0], dict(max_new_tokens=12), 0),
            (ps[1], dict(max_new_tokens=10), 0),
            (ps[2], dict(max_new_tokens=6, do_sample=True, temperature=1.2,
                         seed=5), 3)]
    return LLAMA, reqs, None


def _beside_sampled(net):
    """A greedy request arrives beside a sampled row: nothing is in flight
    there, so its first token is fetched at once, as ever."""
    ps = prompts_of((5, 11, 9), seed=28)
    reqs = [(ps[0], dict(max_new_tokens=6, do_sample=True, temperature=1.2,
                         seed=5), 0),
            (ps[1], dict(max_new_tokens=16), 0),
            (ps[2], dict(max_new_tokens=6), 3)]
    return LLAMA, reqs, None


def _idle_arrival(net):
    """An arrival when nothing is in flight: the engine had run dry."""
    ps = prompts_of((5, 11), seed=29)
    reqs = [(ps[0], dict(max_new_tokens=3), 0),
            (ps[1], dict(max_new_tokens=4), 8)]
    return LLAMA, reqs, None


def _last_row_ends(net):
    """The one decoding row ends by length on the step in flight as another
    request arrives: nothing can go ahead of the fetch, so the first token
    is fetched right after the refusal and the dispatch tried again with the
    new row, the order of ISSUE 28: it decodes a call later, not two."""
    ps = prompts_of((5, 9), seed=32)
    reqs = [(ps[0], dict(max_new_tokens=5), 0),
            (ps[1], dict(max_new_tokens=6), 3)]
    return LLAMA, reqs, None


def _every_row_leaves(net):
    """Both decoding requests are cancelled with their rows in flight, and a
    third arrives before the next call: its chunk meets a step in flight of
    which no row stands, and the call goes on as with none."""
    ps = prompts_of((5, 11, 9), seed=33)
    reqs = [(ps[0], dict(max_new_tokens=12), 0),
            (ps[1], dict(max_new_tokens=10), 0),
            (ps[2], dict(max_new_tokens=6), 4)]

    def cancel(eng, rids, out):
        out.kept["was_in_flight"] = eng._ahead is not None
        assert eng.cancel(rids[0]) and eng.cancel(rids[1])

    return LLAMA, reqs, {4: cancel}


def _mimo_arrival(net):
    """``_arrival`` on the second model: the step dispatched before the
    first token leaves the new request's ring and pages as the chunk wrote
    them (its row reads the null block), while the others' rings turn."""
    ps = prompts_of((5, 21, 38), seed=30)
    reqs = [(ps[0], dict(max_new_tokens=14), 0),
            (ps[1], dict(max_new_tokens=12), 0),
            (ps[2], dict(max_new_tokens=7), 3)]
    return MIMO, reqs, None


CASES = {"mixed-finish-lengths": (llama, _mixed),
         "all-at-once": (llama, _at_once),
         "eos-in-flight": (llama, _eos),
         "cancel-in-flight": (llama, _cancel),
         "deadline-in-flight": (llama, _deadline),
         "eviction-pressure": (llama, _evict),
         "prefix-copy-on-write": (llama, _prefix_cow),
         "chunked-prefill-joins": (llama, _chunked_join),
         "int8-pools": (llama, _int8),
         "tier-revival": (llama, _tier),
         "rings-turn": (mimo, _rings),
         "rings-turn-cancel": (mimo, _rings_cancel),
         "mimo-mixed-finish-lengths": (mimo, _mimo_mixed),
         "mimo-eos-in-flight": (mimo, _mimo_eos),
         "mimo-deadline-in-flight": (mimo, _mimo_deadline),
         "mimo-chunked-prefill-joins": (mimo, _mimo_chunked_join),
         "arrival-beside-a-step": (llama, _arrival),
         "two-ends-in-one-call": (llama, _two_ends),
         "long-prompt-ends-alone": (llama, _long_prompt),
         "one-token-arrival": (llama, _one_token),
         "sampled-arrival": (llama, _sampled_arrival),
         "arrival-beside-a-sampled-row": (llama, _beside_sampled),
         "arrival-into-an-idle-engine": (llama, _idle_arrival),
         "arrival-as-the-last-row-ends": (llama, _last_row_ends),
         "arrival-as-every-row-leaves": (llama, _every_row_leaves),
         "mimo-arrival-beside-a-step": (mimo, _mimo_arrival)}

#: the cases in which a prefill ends in a call of its own, handed in while
#: others decode or have just stopped (ISSUE 34): for each request that
#: arrives, how many calls after its first token's its first decode step
#: comes out. Two where the next decode step was dispatched before the
#: chunk's logits were fetched, which the counter counts; one where that
#: dispatch was made right after the fetch; none with nothing in flight.
ENDS = {"arrival-beside-a-step": {2: 2}, "two-ends-in-one-call": {2: 2, 3: 2},
        "long-prompt-ends-alone": {2: 2}, "one-token-arrival": {2: 2},
        "sampled-arrival": {2: 2}, "arrival-beside-a-sampled-row": {2: 0},
        "arrival-into-an-idle-engine": {1: 0},
        "arrival-as-the-last-row-ends": {1: 1},
        "arrival-as-every-row-leaves": {2: 0},
        "mimo-arrival-beside-a-step": {2: 2}}


#: the cases in which no request joins beside a step in flight (all are
#: admitted by the first call): there the CALLS are the same, token for token
ALIGNED = {"all-at-once", "eos-in-flight", "cancel-in-flight",
           "deadline-in-flight", "int8-pools", "rings-turn-cancel",
           "mimo-eos-in-flight", "mimo-deadline-in-flight"}


@pytest.fixture(scope="module")
def played():
    """Each case played once on both engines, whatever asks for it."""
    done = {}

    def get(case):
        if case not in done:
            build, script = CASES[case]
            net = build()
            engine, reqs, events = script(net)
            done[case] = both(net, engine, reqs, events)
        return done[case]

    return get


@pytest.mark.parametrize("case", list(CASES))
def test_the_same_tokens_as_the_synchronous_engine(played, case):
    a, s = played(case)
    same_requests(a, s)
    assert a.metrics["decode_steps_ahead"] > 0
    assert not a.kept["in_flight_at_end"]


@pytest.mark.parametrize("case", list(CASES))
def test_a_call_returns_its_own_steps_tokens(played, case):
    """``step()`` call k returns step k's tokens: a token a decoding
    request a call, never two and never none while it decodes, and a
    request's first decode step is the one after its prefill's call or,
    beside a step in flight, the one after that."""
    a, s = played(case)
    for run in (a, s):
        seen = {}
        for call, outs in enumerate(run.calls):
            for k, tok, _, _ in outs:
                if tok >= 0:
                    seen.setdefault(k, []).append(call)
        for k, calls in seen.items():
            gaps = np.diff(calls)
            # the first token is the prefill's; its call may hold the first
            # decode too (0), or the decode joins one call on (1), or two
            # where the prefill ended beside a step in flight and the next
            # was dispatched before its fetch (ISSUE 34): from then on one
            # a call, but for a preemption's wait
            late = {"eviction-pressure", "tier-revival"}
            assert (gaps[1:] == 1).all() or case in late, (case, k, calls)
            assert len(gaps) == 0 or gaps[0] in (0, 1, 2) or case in late
    if case in ALIGNED:
        assert a.calls == s.calls
        assert a.metrics["host_syncs"] == s.metrics["host_syncs"]
    # a step ahead can add a call to a request's life, never take tokens
    assert len(a.calls) >= len(s.calls)
    assert len(a.calls) - len(s.calls) <= len(a.tokens)


def test_what_each_case_is_there_for(played):
    a, s = played("mixed-finish-lengths")
    m = a.metrics
    assert m["decode_steps_sync_by_reason"] == {"idle": 1}
    assert m["decode_rows_discarded"] == 0      # a finish by length is seen ahead
    assert m["decode_steps_ahead"] >= m["host_syncs"] - 1

    a, s = played("eos-in-flight")
    assert set(a.reasons.values()) == {"eos"}
    assert 1 <= a.metrics["decode_rows_discarded"] <= 3
    assert a.cached == s.cached                 # num_cached as if it never ran

    a, s = played("cancel-in-flight")
    assert a.reasons[1] == "cancelled" and a.metrics["decode_rows_discarded"] == 1
    assert a.tokens[1] == a.kept["cancelled_with"] == s.kept["cancelled_with"]

    a, s = played("deadline-in-flight")
    assert a.reasons[0] == "timeout" and a.metrics["decode_rows_discarded"] == 1
    assert a.metrics["deadline_expired"] == 1
    assert [o for outs in a.calls for o in outs if o[3] == "timeout"] == \
        [(0, -1, True, "timeout")]

    a, s = played("eviction-pressure")
    assert a.metrics["evictions"] >= 1 and s.metrics["evictions"] >= 1
    assert a.metrics["decode_steps_sync_by_reason"].get("evict", 0) >= 1

    a, s = played("prefix-copy-on-write")
    assert a.metrics["prefix_blocks_reused"] >= 4
    assert a.metrics["prefix_blocks_reused"] == s.metrics["prefix_blocks_reused"]

    a, s = played("chunked-prefill-joins")
    assert a.metrics["prefill_chunks"] == s.metrics["prefill_chunks"] > 6

    a, s = played("tier-revival")
    assert a.metrics["kv_spills"] >= 1 and a.metrics["kv_revives"] >= 1
    assert a.metrics["decode_steps_sync_by_reason"].get("evict", 0) >= 1

    a, s = played("rings-turn")
    assert a.metrics["window_blocks_released"] > 0
    assert a.metrics["window_blocks_released"] == s.metrics["window_blocks_released"]
    assert a.metrics["global_blocks_in_use"] == a.metrics["window_blocks_in_use"] == 0

    a, s = played("rings-turn-cancel")
    assert a.reasons[0] == "cancelled" and a.metrics["decode_rows_discarded"] == 1
    assert a.metrics["window_blocks_in_use"] == 0

    # the second model's own cases: what the first model's show, with rings
    # that turned on both engines alike and every page back at the end
    for case in CASES:
        if case.startswith("mimo-"):
            a, s = played(case)
            assert a.metrics["window_blocks_released"] == \
                s.metrics["window_blocks_released"] > 0
            assert a.metrics["global_blocks_in_use"] == \
                a.metrics["window_blocks_in_use"] == 0
    a, s = played("mimo-mixed-finish-lengths")
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1}
    assert a.metrics["decode_rows_discarded"] == 0
    a, s = played("mimo-eos-in-flight")
    assert set(a.reasons.values()) == {"eos"}
    assert 1 <= a.metrics["decode_rows_discarded"] <= 3
    assert a.cached == s.cached
    a, s = played("mimo-deadline-in-flight")
    assert a.reasons[0] == "timeout" and a.metrics["decode_rows_discarded"] == 1
    a, s = played("mimo-chunked-prefill-joins")
    assert a.metrics["prefill_chunks"] == s.metrics["prefill_chunks"] > 6


@pytest.mark.parametrize("case", list(ENDS))
def test_a_prefills_end_beside_a_step_in_flight(played, case):
    """ISSUE 34. The first token comes out of the call that ran the last
    chunk, before that call's own step's tokens, on both engines in the
    same call; where the next step was dispatched ahead of the fetch the
    request decodes first two calls on, and the counter says so."""
    a, s = played(case)
    assert a.metrics["prefill_ends_behind_decode"] == \
        list(ENDS[case].values()).count(2)
    calls_of = {}
    for name, run in (("ahead", a), ("sync", s)):
        seen = calls_of[name] = {}
        for call, outs in enumerate(run.calls):
            order = [k for k, *_ in outs]
            new = [k for k in dict.fromkeys(order) if k not in seen]
            # first tokens stand before the tokens of the call's step
            assert order[:len(new)] == new, (case, name, call)
            for k in order:
                seen.setdefault(k, []).append(call)
    first = {name: {k: c[0] for k, c in seen.items()}
             for name, seen in calls_of.items()}
    assert first["ahead"] == first["sync"]
    for k, calls in calls_of["ahead"].items():
        if len(calls) > 1:
            # whoever the first call admits decodes in it
            assert calls[1] - calls[0] == ENDS[case].get(k, 0), (k, calls)


def test_what_each_prefills_end_is_there_for(played):
    a, s = played("arrival-beside-a-step")
    m = a.metrics
    assert m["prefills"] == 3
    assert m["decode_steps_sync_by_reason"] == {"idle": 1}
    assert m["decode_rows_discarded"] == 0

    a, s = played("two-ends-in-one-call")
    assert a.metrics["prefills"] == 4
    assert sorted(k for k, *_ in a.calls[3][:2]) == [2, 3]

    a, s = played("long-prompt-ends-alone")
    assert a.metrics["prefill_chunks"] == s.metrics["prefill_chunks"] == 2 + 4
    # the two that decode got a token in every call the long prompt's
    # chunks ran in: a decode step between two chunks, and beside the last
    for call in range(2, 6):
        assert {k for k, *_ in a.calls[call]} == ({0, 1, 2} if call == 5
                                                 else {0, 1})

    a, s = played("one-token-arrival")
    assert a.tokens[2] == s.tokens[2] and len(a.tokens[2]) == 1
    assert a.reasons[2] == "length" and a.metrics["decode_rows_discarded"] == 0

    a, s = played("sampled-arrival")
    by = a.metrics["decode_steps_sync_by_reason"]
    assert by["sampled"] >= 4 and by["idle"] == 1
    assert len(a.tokens[2]) == 6

    a, s = played("arrival-beside-a-sampled-row")
    by = a.metrics["decode_steps_sync_by_reason"]
    assert by["sampled"] >= 4 and a.metrics["prefills"] == 3

    a, s = played("arrival-into-an-idle-engine")
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 2}
    assert a.calls == s.calls

    a, s = played("arrival-as-the-last-row-ends")
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1}
    assert a.metrics["decode_steps_ahead"] == a.metrics["host_syncs"] - 1

    a, s = played("arrival-as-every-row-leaves")
    assert a.kept["was_in_flight"] and not s.kept["was_in_flight"]
    assert a.metrics["decode_rows_discarded"] == 2
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 2}
    assert {a.reasons[0], a.reasons[1]} == {"cancelled"}

    a, s = played("mimo-arrival-beside-a-step")
    assert a.metrics["window_blocks_released"] == \
        s.metrics["window_blocks_released"] > 0
    assert a.metrics["global_blocks_in_use"] == \
        a.metrics["window_blocks_in_use"] == 0

    # where room for the next step takes an eviction nothing goes ahead of
    # the fetch: not every prefill of that case engaged
    a, s = played("eviction-pressure")
    assert a.metrics["prefill_ends_behind_decode"] < a.metrics["prefills"]


def test_the_second_models_tokens_are_the_float32_references():
    """Against code that shares nothing with the engine: each token the
    engine chose with steps in flight is the argmax of the reference's row
    for the tokens before it."""
    net = mimo()
    _, reqs, _ = _rings(net)
    a = play(LLMEngine, net, MIMO, reqs)
    w = {n: p._data for n, p in net.named_parameters()}
    import dataclasses
    model = dataclasses.asdict(net.config)
    for k, (p, _, _) in enumerate(reqs):
        t = a.tokens[k]
        want = np.asarray(ref.logits(
            w, np.concatenate([p, t])[None].astype(np.int32), model,
            experts_held=net.config.experts_held))[0]
        assert [int(want[len(p) - 1 + j].argmax()) for j in range(len(t))] == t


def test_greedy_rows_beside_a_sampled_row_take_the_synchronous_path():
    """The condition itself: with a ``do_sample`` request in the batch
    nothing is dispatched ahead, and the greedy requests' tokens are what
    they are without it."""
    net = llama()
    ps = prompts_of((5, 11, 7), seed=12)
    reqs = [(p, dict(max_new_tokens=10), 0) for p in ps]
    alone = play(LLMEngine, net, LLAMA, reqs)
    beside = play(LLMEngine, net, LLAMA, reqs + [
        (ps[0], dict(max_new_tokens=30, do_sample=True, temperature=1.2,
                     seed=5), 0)])
    assert {k: beside.tokens[k] for k in alone.tokens} == alone.tokens
    m = beside.metrics
    adds_up(m)
    assert m["decode_steps_sync_by_reason"]["sampled"] >= 25
    assert m["decode_steps_ahead"] <= 3     # before the sampled row was ready
    assert alone.metrics["decode_steps_sync_by_reason"] == {"idle": 1}


@pytest.mark.parametrize("path", ["speculative", "prefill-only"])
def test_the_other_decode_paths_are_not_touched(path):
    net = llama()
    kw = {"speculative": dict(draft_model=net, spec_tokens=2),
          "prefill-only": dict(prefill_only=True)}[path]
    ps = prompts_of((5, 11), seed=13)
    with LLMEngine(net, **LLAMA, **kw) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=9)) for p in ps]
        for _ in range(4):
            eng.step()
            assert eng._ahead is None
        m = eng.metrics()
        assert m["decode_steps_ahead"] == 0
        # nothing is ever in flight here: every first token fetched at once
        assert (m["prefills"], m["prefill_ends_behind_decode"]) == (2, 0)
        if path == "prefill-only":
            assert m["decode_steps_sync"] == 0
            assert all(len(eng.request(r).output_tokens) == 1 for r in rids)
        else:
            assert m["decode_steps_sync_by_reason"] == {
                "path": m["decode_steps_sync"]}
            assert m["decode_steps_sync"] >= 2


# --------------------------------------------------------------------------
# rows of logits
# --------------------------------------------------------------------------

@pytest.mark.parametrize("capture", ["on", "off", "flipped"])
@pytest.mark.parametrize("build", [llama, mimo], ids=["llama", "mimo"])
def test_last_logits_are_the_synchronous_engines_bit_for_bit(build, capture):
    """``capture_logits`` rides along: the rows are fetched with the step's
    tokens after the next dispatch. Whatever the setting, and flipped
    between calls, every token and every kept row is the synchronous
    engine's; a call's row belongs to the token that call returned."""
    net = build()
    engine = MIMO if build is mimo else LLAMA
    ps = prompts_of((5, 13, 22), seed=14)
    reqs = [(p, dict(max_new_tokens=n), 0) for p, n in zip(ps, (9, 12, 7))]
    events = None
    if capture == "flipped":
        def flip(eng, rids, out):
            eng.capture_logits = not eng.capture_logits
        events = {c: flip for c in (2, 3, 6, 9)}
    engine = dict(engine, capture_logits=capture != "off")
    a, s = both(net, engine, reqs, events)
    same_requests(a, s)
    assert a.calls == s.calls
    assert a.logits.keys() == s.logits.keys()
    for key in a.logits:
        assert np.array_equal(a.logits[key], s.logits[key]), key
    assert (len(a.logits) == 0) == (capture == "off")
    if capture == "on":
        for (call, k), row in a.logits.items():
            tok = [t for kk, t, _, _ in a.calls[call] if kk == k][-1]
            assert int(row.argmax()) == tok
        v = row.shape[0]
        per_step = engine["max_batch_size"] * (v + 1) * 4
        assert a.metrics["decode_fetch_bytes"] == a.metrics["host_syncs"] * per_step
    if capture == "off":
        assert a.metrics["decode_fetch_bytes"] == \
            a.metrics["host_syncs"] * engine["max_batch_size"] * 4


def rows_by_token(run):
    """``{(request, how many tokens it had): last_logits then}``: the row
    kept after a call belongs to the last token the call returned."""
    have, out = {}, {}
    for call, outs in enumerate(run.calls):
        for k, *_ in outs:
            have[k] = have.get(k, 0) + 1
        for k in {o[0] for o in outs}:
            out[(k, have[k])] = run.logits[(call, k)]
    return out


@pytest.mark.parametrize("build", [llama, mimo], ids=["llama", "mimo"])
def test_a_first_tokens_row_fetched_behind_the_dispatch_is_bit_for_bit(build):
    """ISSUE 34 under ``capture_logits``: the row an arriving request's
    first token was chosen from is fetched after the next decode step was
    dispatched, and it and every decode row after it are the synchronous
    engine's to the last bit, token for token (the calls differ: that
    request decodes a call later)."""
    net = build()
    engine, reqs, _ = (_mimo_arrival if build is mimo else _arrival)(net)
    a, s = both(net, dict(engine, capture_logits=True), reqs)
    same_requests(a, s)
    assert a.metrics["prefill_ends_behind_decode"] == 1
    ra, rs = rows_by_token(a), rows_by_token(s)
    # a call that returns a first token and a decode token keeps the second's
    # row only: with nothing in flight both engines, the synchronous one always
    assert set(rs) == set(ra) - {(2, 1)} and (2, 1) in ra
    for key, row in rs.items():
        assert np.array_equal(ra[key], row), key
    for (k, n), row in ra.items():
        assert int(row.argmax()) == a.tokens[k][n - 1]


# --------------------------------------------------------------------------
# readers and writers of the pools beside a step in flight: enqueued behind
# it, so it stays in flight; only a reload of the weights drops it
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_export_right_after_an_ahead_dispatch(kv_dtype):
    """``export_kv_pages`` with a step in flight: the pages and their
    ``covered`` are the synchronous engine's at the same call (what the
    step in flight wrote lies past them), the step stays in flight and
    becomes the next call's decode, the tokens go on the same."""
    net = llama()
    engine = dict(LLAMA, kv_dtype=kv_dtype)
    ps = prompts_of((5, 11), seed=15)
    reqs = [(p, dict(max_new_tokens=12), 0) for p in ps]

    def export(eng, rids, out):
        out.kept["was_in_flight"] = ahead = eng._ahead
        out.kept["pages"] = eng.export_kv_pages(rids[1])
        assert eng._ahead is ahead

    a, s = both(net, engine, reqs, {5: export})
    same_requests(a, s)
    assert a.calls == s.calls
    assert a.kept["was_in_flight"] is not None and s.kept["was_in_flight"] is None
    pa, ps_ = a.kept["pages"], s.kept["pages"]
    assert pa["covered"] == ps_["covered"]
    n = pa["covered"]
    for name in ("k", "v") + (("k_scale", "v_scale") if kv_dtype else ()):
        flat_a = pa[name].reshape(pa[name].shape[0], -1, *pa[name].shape[3:])
        flat_s = ps_[name].reshape(flat_a.shape)
        assert np.array_equal(flat_a[:, :n], flat_s[:, :n]), name
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1}
    assert a.metrics["decode_rows_discarded"] == 0


def test_import_beside_a_step_in_flight():
    """The decode side of a handoff: pages come in while the engine has a
    step in flight for the requests it already runs."""
    net = llama()
    ps = prompts_of((5, 11, 9), seed=16)
    with LLMEngine(net, prefill_only=True, **LLAMA) as pre:
        rid = pre.add_request(ps[2], SamplingParams(max_new_tokens=8))
        first, = pre.step()
        pages = pre.export_kv_pages(rid)
    handed = np.concatenate([ps[2], [first.token]]).astype(np.int32)
    reqs = [(p, dict(max_new_tokens=14), 0) for p in ps[:2]]

    def admit(eng, rids, out):
        out.kept["was_in_flight"] = eng._ahead is not None
        rids[2] = eng.add_request_with_pages(
            handed, pages, SamplingParams(max_new_tokens=7))

    def played_with(cls):
        out = Played()
        with cls(net, **LLAMA) as eng:
            rids = {k: eng.add_request(p, SamplingParams(**f))
                    for k, (p, f, _) in enumerate(reqs)}
            for call in itertools.count():
                if call == 4:
                    admit(eng, rids, out)
                if not eng.has_work():
                    break
                eng.step()
            out.tokens = {k: list(eng.request(r).output_tokens)
                          for k, r in rids.items()}
            out.metrics = eng.metrics()
        return out

    a, s = played_with(LLMEngine), played_with(Synchronous)
    assert a.tokens == s.tokens
    colocated = play(Synchronous, net, LLAMA, [(ps[2], dict(max_new_tokens=8), 0)])
    assert [first.token] + a.tokens[2] == colocated.tokens[0]
    # the import is enqueued behind the step in flight, which stays: no
    # step is made twice and no call goes without its decode
    assert a.kept["was_in_flight"]
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1}
    assert a.metrics["decode_rows_discarded"] == 0
    assert a.metrics["host_syncs"] == s.metrics["host_syncs"]
    adds_up(a.metrics)


def test_a_reload_of_the_weights_drops_the_step_in_flight(tmp_path):
    """The step in flight ran on the old weights: after ``reload_weights``
    the next token is the new weights', as on the synchronous engine."""
    from paddle_tpu.inference.serving import save_llama_artifact

    path = str(tmp_path / "other")
    save_llama_artifact(llama(seed=8), path)
    ps = prompts_of((5, 11), seed=17)
    reqs = [(p, dict(max_new_tokens=12), 0) for p in ps]
    runs = []
    for cls in (LLMEngine, Synchronous):
        runs.append(play(cls, llama(), LLAMA, reqs, {
            5: lambda eng, rids, out: eng.reload_weights(path)}))
    a, s = runs
    same_requests(a, s)
    unswapped = play(Synchronous, llama(), LLAMA, reqs)
    assert a.tokens != unswapped.tokens
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1, "drain": 1}


def test_a_store_save_beside_a_step_in_flight(tmp_path):
    """The chains a save exports are full blocks the index names; a step
    made ahead writes into none of those, so it stays in flight."""
    net = llama()
    engine = dict(LLAMA, enable_prefix_cache=True, kv_host_blocks=32)
    ps = prompts_of((24, 17), seed=18)
    reqs = [(p, dict(max_new_tokens=10), 0) for p in ps]

    def save(eng, rids, out):
        out.kept["was_in_flight"] = ahead = eng._ahead
        out.kept["saved"] = eng.save_prefix_store()
        assert eng._ahead is ahead
        out.kept["store"] = load_prefix_store(
            eng._store_path, fingerprint=eng._store_fingerprint,
            geometry=eng._store_geometry, instance=eng._name)

    # a store each: the second engine would boot from the first one's
    a, s = (play(cls, net, dict(engine, prefix_store_path=str(tmp_path / name)),
                 reqs, {4: save})
            for cls, name in ((LLMEngine, "ahead"), (Synchronous, "sync")))
    same_requests(a, s)
    assert a.kept["was_in_flight"] is not None and s.kept["was_in_flight"] is None
    assert a.kept["saved"] == s.kept["saved"] >= 3
    sa, ss = dict(a.kept["store"]), dict(s.kept["store"])
    assert sa.keys() == ss.keys()
    for h in sa:
        for name in ("k", "v"):
            assert np.array_equal(sa[h][name], ss[h][name]), (h, name)
    assert a.metrics["decode_steps_sync_by_reason"] == {"idle": 1}
    assert a.metrics["decode_rows_discarded"] == 0


# --------------------------------------------------------------------------
# counts, compiles, teardown
# --------------------------------------------------------------------------

@pytest.mark.parametrize("build", [llama, mimo], ids=["llama", "mimo"])
def test_nothing_compiles_after_the_warm_up(build):
    """One decode executable serves a step made now and a step made
    ahead, joined rows and fed rows: a second burst of the same shapes
    builds nothing, JAX's eager operations included."""
    net = build()
    engine = MIMO if build is mimo else LLAMA
    ps = prompts_of((5, 13, 22, 9, 30), seed=19)
    with LLMEngine(net, **engine) as eng:
        def burst():
            rids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                    for p, n in zip(ps, (6, 9, 4, 11, 3))]
            while eng.has_work():
                eng.step()
            for r in rids:
                eng.release(r)

        burst()
        before = COMPILES[0]
        stats0 = dict(paddle_tpu.jit.cache_stats()[eng._decode_name])
        burst()
        assert COMPILES[0] == before
        stats1 = paddle_tpu.jit.cache_stats()[eng._decode_name]
        assert stats1["compiles"] == stats0["compiles"] == 1
        m = eng.metrics()
        assert m["decode_steps_sync_by_reason"] == {"idle": 2}
        assert m["decode_steps_ahead"] > 12


@pytest.mark.parametrize("build", [llama, mimo], ids=["llama", "mimo"])
def test_nothing_compiles_where_prefills_end_beside_steps_in_flight(build):
    """ISSUE 34 moved a fetch, not a shape: requests handed in call by call,
    so that their prefills end beside steps in flight (one, two in a call,
    a chunked one), build nothing the second time round."""
    net = build()
    engine = dict(MIMO if build is mimo else LLAMA, max_prefill_tokens_per_step=16)
    ps = prompts_of((5, 13, 9, 7, 30), seed=31)
    with LLMEngine(net, **engine) as eng:
        def burst():
            rids = []
            for call in itertools.count():
                for p, n, at in zip(ps, (14, 12, 6, 5, 4), (0, 0, 2, 2, 3)):
                    if at == call:
                        rids.append(eng.add_request(
                            p, SamplingParams(max_new_tokens=n)))
                if call > 3 and not eng.has_work():
                    break
                eng.step()
            for r in rids:
                eng.release(r)

        burst()
        before = COMPILES[0]
        m0 = eng.metrics()
        burst()
        assert COMPILES[0] == before
        m = eng.metrics()
        # the second prompt's last chunk, the two handed in before call 2
        # and the long one's last chunk, each time round
        assert m["prefill_ends_behind_decode"] == \
            2 * m0["prefill_ends_behind_decode"] == 8
        assert m["decode_steps_sync_by_reason"] == {"idle": 2}


@pytest.mark.parametrize("kind", ["llama", "llama-int8", "llama-tp2", "mimo"])
def test_the_decode_executable_lowers_from_the_engines_own_operands(kind):
    """``chip_smoke.py`` reads the decode executable's compiled text; the
    engine names that executable's operands itself
    (``decode_abstract_args``, built by the function that builds a step's),
    so the smoke follows a change of them. The smoke's own function builds
    its text from them here, under a plan too."""
    import chip_smoke
    from paddle_tpu.distributed.plan import Plan

    net = mimo() if kind == "mimo" else llama()
    engine = dict(MIMO if kind == "mimo" else LLAMA)
    if kind == "llama-int8":
        engine["kv_dtype"] = "int8"
    if kind == "llama-tp2":
        engine["plan"] = Plan.build({"tp": 2}, ["tp"])
    ps = prompts_of((5, 11), seed=22)
    with LLMEngine(net, **engine) as eng:
        for p in ps:
            eng.add_request(p, SamplingParams(max_new_tokens=6))
        while eng.has_work():
            eng.step()
        assert eng.metrics()["decode_steps_ahead"] > 0
        args = eng.decode_abstract_args()
        B = eng.max_batch_size
        assert args[1].shape == (B, 2) and args[2].shape == (B,)
        assert args[8].shape == (B,) and args[8].dtype == np.int32
        outs = eng._decode_jit.lower(*args).out_info
        assert outs[0].shape == (B, net.config.vocab_size)
        assert outs[1].shape == (B,)
        if kind != "mimo":      # the smoke serves Llama
            text = chip_smoke.executable_text(eng)
            assert "ENTRY" in text
            assert "ENTRY" in chip_smoke.executable_text(
                eng, eng.prefill_buckets[0])


def test_a_sharded_engine_feeds_its_tokens_back_in_one_layout():
    """Under a tp plan the greedy tokens are pinned replicated and the
    zeros that stand in for them are put the same way: the step made now
    and the step made ahead are one executable there too."""
    from paddle_tpu.distributed.plan import Plan

    net = llama(seed=5)
    plan = Plan.build({"tp": 2}, ["tp"])
    ps = prompts_of((8, 5), seed=20)
    with LLMEngine(net, num_blocks=16, block_size=8, max_batch_size=2,
                   max_model_len=64, ingest_async=False, plan=plan) as eng:
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=10)) for p in ps]
        eng.step()
        eng.step()                      # a step made now, two made ahead
        before = COMPILES[0]
        while eng.has_work():
            eng.step()
        assert COMPILES[0] == before
        got = [list(eng.request(r).output_tokens) for r in rids]
        assert eng.metrics()["decode_steps_ahead"] >= 8
    plain = play(Synchronous, llama(seed=5), dict(
        num_blocks=16, block_size=8, max_batch_size=2, max_model_len=64,
        ingest_async=False), [(p, dict(max_new_tokens=10), 0) for p in ps])
    assert got == [plain.tokens[0], plain.tokens[1]]


def test_has_work_release_and_close_with_a_step_in_flight():
    net = llama()
    ps = prompts_of((5, 11), seed=21)
    eng = LLMEngine(net, **LLAMA)
    rids = [eng.add_request(p, SamplingParams(max_new_tokens=20)) for p in ps]
    for _ in range(3):
        eng.step()
    assert eng._ahead is not None and eng.has_work()
    free = eng.cache.allocator.num_free
    # a request that ends while its row is in flight can be released
    assert eng.cancel(rids[0])
    eng.release(rids[0])
    outs = eng.step()
    assert [o.rid for o in outs] == [rids[1]]
    assert eng.cache.allocator.num_free > free
    # nothing left to run: the step in flight is forgotten, not waited for
    assert eng.cancel(rids[1])
    assert eng._ahead is not None and not eng.has_work()
    assert eng.step() == [] and eng._ahead is None
    m = eng.metrics()
    assert m["decode_rows_discarded"] == 2
    adds_up(m)
    # and a close with one in flight leaves nothing behind
    eng.add_request(ps[0], SamplingParams(max_new_tokens=20))
    eng.step()
    eng.step()
    assert eng._ahead is not None
    name = eng._name
    eng.close()
    assert eng._ahead is None and not eng.has_work()
    assert eng.cache.allocator.num_free == eng.cache.num_blocks - 1
    with pytest.raises(EngineClosedError):
        eng.step()
    for counter in ("serving_decode_steps_ahead_total",
                    "serving_decode_steps_sync_total",
                    "serving_decode_rows_discarded_total"):
        assert all(dict(labels).get("instance") != name
                   for labels in metrics.REGISTRY.get(counter).labels())


def test_the_counters_are_registry_series_and_reset_with_the_others():
    net = llama()
    ps = prompts_of((5, 11), seed=22)
    with LLMEngine(net, **LLAMA) as eng:
        eng.generate(ps, SamplingParams(max_new_tokens=6))
        m = eng.metrics()
        inst = eng._name
        assert metrics.REGISTRY.get("serving_decode_steps_ahead_total").value(
            instance=inst) == m["decode_steps_ahead"] > 0
        assert metrics.REGISTRY.get("serving_decode_steps_sync_total").value(
            instance=inst, reason="idle") == 1
        assert metrics.REGISTRY.get("serving_decode_rows_discarded_total").value(
            instance=inst) == 0
        assert "serving_decode_steps_sync_total" in metrics.to_prometheus_text()
        # a third prompt beside the step the two left in flight (ISSUE 34)
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=9)) for p in ps]
        eng.step()
        eng.step()
        eng.add_request(ps[0], SamplingParams(max_new_tokens=2))
        eng.step()
        behind = metrics.REGISTRY.get("serving_prefill_ends_behind_decode_total")
        assert behind.value(instance=inst) == 1 == \
            eng.metrics()["prefill_ends_behind_decode"]
        assert "last chunk" in behind.help
        eng.reset_metrics()
        m = eng.metrics()
        assert m["decode_steps_ahead"] == m["decode_steps_sync"] == 0
        assert m["prefill_ends_behind_decode"] == m["prefills"] == 0
        assert m["decode_steps_sync_by_reason"] == {}


def test_reserve_ahead_never_evicts_and_never_copies():
    """The scheduler's part: room for the step after the one in flight
    only out of free blocks; nobody evicted, nothing copied or retracted
    where that does not do."""
    from paddle_tpu.inference.serving import (BlockAllocator, PrefixCache,
                                              Request, Scheduler)

    alloc = BlockAllocator(6)                       # five usable blocks
    pc = PrefixCache(alloc, 4)
    sched = Scheduler(alloc, 4, 2, max_prefills_per_step=2, prefix_cache=pc)
    a = Request(np.arange(1, 8, dtype=np.int32), SamplingParams(max_new_tokens=9))
    b = Request(np.arange(1, 8, dtype=np.int32), SamplingParams(max_new_tokens=9))
    sched.waiting.extend([a, b])
    assert len(sched.pick_prefills()) == 2          # two blocks each
    for r in (a, b):
        r.prefilling, r.num_cached = False, 7
    version = sched.version
    # inside what they hold: nothing to take
    assert sched.reserve_ahead([(a, 7), (b, 7)]) and sched.version == version
    # one free block and two rows that need one: refused at the second;
    # the first keeps what it took, the block the next call's
    # ``ensure_decode_room`` would give it first
    assert not sched.reserve_ahead([(a, 8), (b, 8)])
    assert (len(a.blocks), len(b.blocks), alloc.num_free) == (3, 2, 0)
    assert sched.reserve_ahead([(a, 8)]) and len(a.blocks) == 3
    # none left: refused, nothing moved, nobody evicted
    before = (list(a.blocks), list(b.blocks), sched.version)
    assert not sched.reserve_ahead([(a, 9), (b, 8)])
    assert (a.blocks, b.blocks, sched.version) == before
    assert a.state == b.state == "running" and not sched.waiting
    # a write into a block the prefix index still names, or one that is
    # shared, is the synchronous path's to retract or copy
    pc.register(a.tokens, a.blocks, 4)
    assert pc.registered(a.blocks[0])
    assert not sched.reserve_ahead([(a, 2)])
    assert pc.registered(a.blocks[0])
    alloc.acquire([b.blocks[1]])
    assert not sched.reserve_ahead([(b, 7)])
