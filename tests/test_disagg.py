"""Disaggregated prefill/decode serving tests (ISSUE 15): KV-page
export/import + the engine's prefill-only / preloaded-admission halves
(greedy determinism across the handoff, incl. int8 KV and prefix
sharing), the router's two-stage placement with CRC-framed handoff
recovery against fake replica handles (zombie dedup, corrupt-frame
retries, mid-transfer failover, degrade-to-colocated, backpressure,
session-affinity fixes, idle backoff), deadline/lifecycle edges across
the handoff, and a real 1-prefill+1-decode subprocess fleet smoke. The
full storm (prefill SIGKILL mid-transfer + decode hang under load) is
scripts/chaos_serve.py --drill disagg, wired slow-tier below."""

from __future__ import annotations

import base64
import os
import time
import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (
    EngineClosedError, KVTransferError, LLMEngine, RequestTimeoutError,
    SamplingParams, pack_kv_pages, unpack_kv_pages,
)
from paddle_tpu.inference.serving.fleet import Router
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.observability import metrics as om

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE_KW = dict(num_blocks=64, block_size=8, max_batch_size=4)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    return model


def _prompts(n=3, seed=3, lens=(5, 11, 16)):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 512, ln).astype(np.int32) for ln in lens[:n]]


def _prefill_one(pre, prompt, max_new):
    """Run one prompt through a prefill-only engine; returns
    (first StepOutput, exported pages) and frees the request."""
    rid = pre.add_request(prompt, SamplingParams(max_new_tokens=max_new))
    first = None
    while first is None:
        for out in pre.step():
            assert out.rid == rid
            first = out
    pages = None
    if not first.finished:
        pages = pre.export_kv_pages(rid)
        pre.cancel(rid, reason="handoff")
    pre.release(rid)
    return first, pages


def _disagg_outputs(model, prompts, max_new, engine_kw, roundtrip=True):
    """In-process two-engine handoff: prefill-only engine exports each
    prompt's pages (optionally through the pack/unpack wire format),
    a second engine imports and decodes. Returns full token arrays."""
    pre = LLMEngine(model, ingest_async=False, prefill_only=True,
                    **engine_kw)
    dec = LLMEngine(model, ingest_async=False, **engine_kw)
    outs = []
    try:
        for p in prompts:
            first, pages = _prefill_one(pre, p, max_new)
            p2 = np.concatenate(
                [p, np.asarray([first.token], np.int32)])
            if first.finished:
                outs.append(p2)
                continue
            if roundtrip:
                pages = unpack_kv_pages(pack_kv_pages(pages))
            rid2 = dec.add_request_with_pages(
                p2, pages, SamplingParams(max_new_tokens=max_new - 1))
            toks = list(p2)
            for out in dec.stream():
                if out.rid == rid2 and out.token >= 0:
                    toks.append(out.token)
            dec.release(rid2)
            outs.append(np.asarray(toks, np.int32))
    finally:
        pre.close()
        dec.close()
    return outs


# ---------------------------------------------------------------------------
# page export / import / wire format
# ---------------------------------------------------------------------------

class TestPageWireFormat:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_pack_unpack_roundtrip(self, tiny_model, kv_dtype):
        kw = dict(ENGINE_KW, kv_dtype=kv_dtype)
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **kw)
        try:
            first, pages = _prefill_one(pre, _prompts(1)[0], 4)
            back = unpack_kv_pages(pack_kv_pages(pages))
            assert back["covered"] == pages["covered"]
            assert back["block_size"] == pages["block_size"]
            assert back["kv_dtype"] == kv_dtype
            np.testing.assert_array_equal(back["k"], pages["k"])
            np.testing.assert_array_equal(back["v"], pages["v"])
            if kv_dtype == "int8":
                np.testing.assert_array_equal(back["k_scale"],
                                              pages["k_scale"])
                np.testing.assert_array_equal(back["v_scale"],
                                              pages["v_scale"])
        finally:
            pre.close()

    def test_unpack_rejects_garbage(self):
        with pytest.raises(ValueError):
            unpack_kv_pages(b"not a page payload")

    def test_import_validates_geometry(self, tiny_model):
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        dec = LLMEngine(tiny_model, ingest_async=False,
                        **dict(ENGINE_KW, kv_dtype="int8"))
        dec16 = LLMEngine(tiny_model, ingest_async=False,
                          **dict(ENGINE_KW, block_size=16))
        try:
            first, pages = _prefill_one(pre, _prompts(1)[0], 4)
            p2 = np.concatenate(
                [_prompts(1)[0], np.asarray([first.token], np.int32)])
            sp = SamplingParams(max_new_tokens=3)
            with pytest.raises(ValueError, match="kv_dtype"):
                dec.add_request_with_pages(p2, pages, sp)
            with pytest.raises(ValueError, match="block_size"):
                dec16.add_request_with_pages(p2, pages, sp)
            bad = dict(pages, covered=pages["covered"] + 1)
            with pytest.raises(ValueError, match="cover"):
                dec16.add_request_with_pages(p2, bad, sp)
            shaved = dict(pages)
            shaved["k"] = pages["k"][..., :4]
            with pytest.raises(ValueError, match="fit this pool"):
                pre.cache.import_request_pages([1, 2], shaved)
            # int8 payload missing its scale rows: typed rejection at
            # admission, BEFORE any pool array moves
            pre8 = LLMEngine(tiny_model, ingest_async=False,
                             prefill_only=True,
                             **dict(ENGINE_KW, kv_dtype="int8"))
            try:
                f8, pages8 = _prefill_one(pre8, _prompts(1)[0], 4)
                p8 = np.concatenate(
                    [_prompts(1)[0], np.asarray([f8.token], np.int32)])
                bad8 = {k: v for k, v in pages8.items()
                        if k != "k_scale"}
                with pytest.raises(ValueError, match="missing"):
                    dec.add_request_with_pages(p8, bad8, sp)
                # the wire format rejects it too (version-skew guard)
                with pytest.raises(ValueError, match="missing"):
                    unpack_kv_pages(pack_kv_pages(bad8))
            finally:
                pre8.close()
        finally:
            pre.close()
            dec.close()
            dec16.close()


# ---------------------------------------------------------------------------
# engine-level handoff: greedy determinism (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestEngineDisaggDeterminism:
    @pytest.mark.parametrize("kv_dtype,prefix", [
        (None, False), ("int8", False), (None, True), ("int8", True),
    ])
    def test_disagg_bit_exact_vs_colocated(self, tiny_model, kv_dtype,
                                           prefix):
        """Disagg on vs off produces IDENTICAL token ids — incl. with
        int8 KV quantization and prefix sharing enabled (the imported
        pages are byte-identical to local prefill output, so every
        downstream path composes unchanged)."""
        kw = dict(ENGINE_KW, kv_dtype=kv_dtype,
                  enable_prefix_cache=prefix)
        prompts = _prompts(3)
        if prefix:
            # two prompts sharing a full-block prefix: follower
            # admissions exercise sharing against IMPORTED blocks too
            prompts[1] = np.concatenate(
                [prompts[0][:8], prompts[1]]).astype(np.int32)
            prompts[2] = np.concatenate(
                [prompts[0][:8], prompts[2][:5]]).astype(np.int32)
        with LLMEngine(tiny_model, ingest_async=False, **kw) as eng:
            refs = eng.generate(prompts, SamplingParams(max_new_tokens=8))
        outs = _disagg_outputs(tiny_model, prompts, 8, kw)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(o, r)

    def test_first_token_finishes_without_pages(self, tiny_model):
        """max_new_tokens=1: the prefill's first token IS the whole
        stream — no decode stage, no transfer needed."""
        with LLMEngine(tiny_model, ingest_async=False,
                       **ENGINE_KW) as eng:
            refs = eng.generate(_prompts(1),
                                SamplingParams(max_new_tokens=1))
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        try:
            first, pages = _prefill_one(pre, _prompts(1)[0], 1)
            assert first.finished and pages is None
            assert first.finish_reason == "length"
            np.testing.assert_array_equal(
                np.concatenate([_prompts(1)[0], [first.token]]), refs[0])
        finally:
            pre.close()

    def test_preloaded_eviction_reprefills_bit_exact(self, tiny_model):
        """An imported-pages request evicted under pool pressure
        re-prefills from its full prefix through the normal staged path
        — outputs stay bit-identical to a pressure-free engine."""
        prompts = _prompts(2, lens=(16, 12))
        max_new = 10
        with LLMEngine(tiny_model, ingest_async=False,
                       **ENGINE_KW) as eng:
            refs = eng.generate(prompts,
                                SamplingParams(max_new_tokens=max_new))
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        # pool sized so both requests admit but growth forces eviction
        dec = LLMEngine(tiny_model, ingest_async=False,
                        **dict(ENGINE_KW, num_blocks=7))
        try:
            outs = {}
            rids = {}
            for i, p in enumerate(prompts):
                first, pages = _prefill_one(pre, p, max_new)
                p2 = np.concatenate([p, [first.token]]).astype(np.int32)
                rid = dec.add_request_with_pages(
                    p2, pages,
                    SamplingParams(max_new_tokens=max_new - 1))
                rids[rid] = i
                outs[i] = list(p2)
            for out in dec.stream():
                if out.token >= 0:
                    outs[rids[out.rid]].append(out.token)
            assert dec.metrics()["evictions"] >= 1
            for i, r in enumerate(refs):
                np.testing.assert_array_equal(
                    np.asarray(outs[i], np.int32), r)
        finally:
            pre.close()
            dec.close()

    def test_preloaded_queues_on_exhaustion_then_admits(self, tiny_model):
        """Preloaded admission respects the same block accounting: no
        free blocks -> queue (typed counter), admit when they free."""
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        dec = LLMEngine(tiny_model, ingest_async=False,
                        **dict(ENGINE_KW, num_blocks=8, max_batch_size=2))
        try:
            p0 = _prompts(1, lens=(24,))[0]
            hog = dec.add_request(p0, SamplingParams(max_new_tokens=32))
            # run the hog until it holds 6 of the 7 usable blocks
            while dec.request(hog).num_tokens <= 41:
                dec.step()
            p1 = _prompts(1, seed=9, lens=(9,))[0]
            first, pages = _prefill_one(pre, p1, 4)
            p2 = np.concatenate([p1, [first.token]]).astype(np.int32)
            rid = dec.add_request_with_pages(
                p2, pages, SamplingParams(max_new_tokens=3))
            dec.step()
            assert dec.request(rid).state == "waiting"
            assert dec.metrics()["queued_on_exhaustion"] >= 1
            toks = list(p2)
            for out in dec.stream():
                if out.rid == rid and out.token >= 0:
                    toks.append(out.token)
            assert dec.request(rid).finished
            assert len(toks) == len(p2) + 3
            dec.release(rid)
            dec.release(hog)
            assert dec.cache.allocator.num_free == 7
        finally:
            pre.close()
            dec.close()


# ---------------------------------------------------------------------------
# prefill-only engine contract
# ---------------------------------------------------------------------------

class TestPrefillOnlyEngine:
    def test_never_decodes_and_never_compiles_decode(self, tiny_model):
        from paddle_tpu.jit import cache_stats

        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        try:
            rid = pre.add_request(_prompts(1)[0],
                                  SamplingParams(max_new_tokens=16))
            emitted = []
            for _ in range(6):
                emitted += [o for o in pre.step()]
            # exactly ONE token (the prefill's first) ever emerges
            assert len(emitted) == 1 and emitted[0].rid == rid
            assert len(pre.request(rid).output_tokens) == 1
            row = cache_stats().get(pre._decode_name)
            assert not row or row.get("compiles", 0) == 0
            pre.cancel(rid)
            pre.release(rid)
            assert pre.cache.allocator.num_free == \
                ENGINE_KW["num_blocks"] - 1
        finally:
            pre.close()

    def test_rejects_draft_model_and_imported_pages(self, tiny_model):
        with pytest.raises(ValueError, match="prefill_only"):
            LLMEngine(tiny_model, prefill_only=True,
                      draft_model=tiny_model, **ENGINE_KW)
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        try:
            with pytest.raises(ValueError, match="never decode"):
                pre.add_request_with_pages(
                    _prompts(1)[0], {"covered": 4},
                    SamplingParams(max_new_tokens=2))
        finally:
            pre.close()

    def test_export_requires_decode_ready(self, tiny_model):
        eng = LLMEngine(tiny_model, ingest_async=False, **ENGINE_KW)
        try:
            rid = eng.add_request(_prompts(1)[0],
                                  SamplingParams(max_new_tokens=4))
            with pytest.raises(ValueError, match="decode-ready"):
                eng.export_kv_pages(rid)  # still waiting, not prefilled
            eng.cancel(rid)
            eng.release(rid)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# deadline + lifecycle edges across the handoff (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

class TestHandoffDeadlineLifecycle:
    def _pages(self, tiny_model, max_new=6):
        pre = LLMEngine(tiny_model, ingest_async=False, prefill_only=True,
                        **ENGINE_KW)
        try:
            p = _prompts(1)[0]
            first, pages = _prefill_one(pre, p, max_new)
            return np.concatenate([p, [first.token]]).astype(np.int32), \
                pages
        finally:
            pre.close()

    def test_expired_deadline_rejected_before_any_state(self, tiny_model):
        p2, pages = self._pages(tiny_model)
        dec = LLMEngine(tiny_model, ingest_async=False, **ENGINE_KW)
        try:
            free0 = dec.cache.allocator.num_free
            with pytest.raises(RequestTimeoutError):
                dec.add_request_with_pages(
                    p2, pages, SamplingParams(max_new_tokens=5),
                    deadline=time.time() - 1.0)
            assert dec.cache.allocator.num_free == free0
            assert not dec.scheduler.waiting and not dec.has_work()
        finally:
            dec.close()

    def test_deadline_between_prefill_and_decode_admission(self,
                                                           tiny_model):
        """The satellite edge: deadline expires AFTER the prefill
        worker handed off but BEFORE decode admission — the waiting
        request aborts typed, its never-imported pages are dropped, and
        the allocator never saw it."""
        p2, pages = self._pages(tiny_model)
        dec = LLMEngine(tiny_model, ingest_async=False,
                        **dict(ENGINE_KW, max_batch_size=1))
        try:
            # a running request keeps the engine stepping while the
            # preloaded one waits
            hog = dec.add_request(_prompts(1, seed=8, lens=(6,))[0],
                                  SamplingParams(max_new_tokens=20))
            dec.step()
            rid = dec.add_request_with_pages(
                p2, pages, SamplingParams(max_new_tokens=5),
                deadline=time.time() + 0.05)
            time.sleep(0.08)
            ends = [o for o in dec.step()
                    if o.rid == rid and o.finished]
            assert ends and ends[0].finish_reason == "timeout"
            assert dec.request(rid).preloaded is None  # pages dropped
            assert dec.metrics()["deadline_expired"] == 1
            dec.cancel(hog)
            dec.release(hog)
            dec.release(rid)
            assert dec.cache.allocator.num_free == \
                ENGINE_KW["num_blocks"] - 1
        finally:
            dec.close()

    def test_engine_close_with_pending_pages_leaks_nothing(self,
                                                           tiny_model):
        p2, pages = self._pages(tiny_model)
        dec = LLMEngine(tiny_model, ingest_async=False, **ENGINE_KW)
        rid = dec.add_request_with_pages(
            p2, pages, SamplingParams(max_new_tokens=5))
        dec.close()
        assert dec.cache.allocator.num_free == ENGINE_KW["num_blocks"] - 1
        with pytest.raises(EngineClosedError):
            dec.add_request_with_pages(p2, pages,
                                       SamplingParams(max_new_tokens=5))
        with pytest.raises(EngineClosedError):
            dec.step()
        assert rid is not None


# ---------------------------------------------------------------------------
# router: fakes (no subprocesses)
# ---------------------------------------------------------------------------

class FakeHandle:
    def __init__(self, hid, role="both"):
        self.id = hid
        self.role = role
        self.ready = True
        self.ready_info = {"e": "ready", "replica": hid, "role": role}
        self.alive = True
        self.retired = False
        self.sent = []
        self.inbox = []

    def send(self, obj):
        if not self.alive:
            return False
        self.sent.append(obj)
        return True

    def events(self):
        out, self.inbox = self.inbox, []
        for ev in out:
            if ev.get("e") == "ready":
                self.ready = True
                self.ready_info = ev
        return out

    def ops(self, op):
        return [s for s in self.sent if s.get("op") == op]


class FakeSupervisor:
    def __init__(self, roles):
        self.handles = [FakeHandle(i, r) for i, r in enumerate(roles)]
        self.deaths = []
        self.shut = False

    def check(self, now=None):
        out, self.deaths = self.deaths, []
        return out

    def retire(self, i):
        h = self.handles[i]
        h.retired = True
        h.alive = False

    def shutdown(self):
        self.shut = True

    def die(self, i, leftover=()):
        h = self.handles[i]
        h.alive = False
        self.deaths.append({"replica": i, "reason": "crash", "rc": -9,
                            "events": list(leftover)})
        self.handles[i] = FakeHandle(i, h.role)
        self.handles[i].ready = False  # booting respawn

    def feed(self, i, ev):
        self.handles[i].inbox.append(ev)


def make_split_fleet(roles=("prefill", "decode", "decode"), **kw):
    kw.setdefault("engine_kwargs", {"max_batch_size": 4})
    sup = FakeSupervisor(list(roles))
    return Router(supervisor=sup, **kw), sup


PROMPT = np.arange(1, 7, dtype=np.int32)
BLOB = (b"fake-kv-page-payload" * 37)


def frame_events(gid, hid, blob=BLOB, nframes=3, corrupt_seq=None,
                 first_tok=7, drop_seq=None):
    size = max(1, -(-len(blob) // nframes))
    chunks = [blob[i:i + size] for i in range(0, len(blob), size)]
    evs = []
    for seq, ch in enumerate(chunks):
        if seq == drop_seq:
            continue
        data = ch
        if seq == corrupt_seq:
            data = bytes([ch[0] ^ 0xFF]) + ch[1:]
        evs.append({"e": "kvpage", "gid": gid, "hid": hid, "seq": seq,
                    "total": len(chunks), "crc": zlib.crc32(ch),
                    "data": base64.b64encode(data).decode()})
    evs.append({"e": "kvdone", "gid": gid, "hid": hid,
                "first_tok": first_tok, "fin": False, "reason": None,
                "frames": len(chunks), "crc": zlib.crc32(blob)})
    return evs


def tok_ev(gid, gen, toks, fin=False, reason=None):
    return {"e": "tok", "gid": gid, "gen": gen, "toks": list(toks),
            "fin": fin, "reason": reason if fin else None}


class TestRouterTwoStage:
    def test_handoff_flow_end_to_end(self):
        fleet, sup = make_split_fleet()
        try:
            gid = fleet.submit(PROMPT, max_new=5, session="t1",
                               deadline_s=60.0)
            fleet.step()
            pf = sup.handles[0].ops("prefill")
            assert len(pf) == 1 and pf[0]["hid"] == 1 \
                and pf[0]["max_new"] == 5
            assert pf[0]["prompt"] == PROMPT.tolist()
            deadline = fleet.request(gid).deadline
            assert pf[0]["deadline"] == pytest.approx(deadline)
            for ev in frame_events(gid, 1):
                sup.feed(0, ev)
            fleet.step()
            # first token accepted, pages shipped to ONE decode replica
            assert fleet.tokens(gid) == [7]
            dec = next(h for h in sup.handles[1:] if h.ops("kvpage"))
            sub = dec.ops("submit_pages")
            assert len(sub) == 1
            assert sub[0]["prompt"] == PROMPT.tolist() + [7]
            assert sub[0]["max_new"] == 4
            # deadline carried UNCHANGED across the handoff
            assert sub[0]["deadline"] == pytest.approx(deadline)
            # frames CRC-consistent on the way down
            for f in dec.ops("kvpage"):
                assert zlib.crc32(base64.b64decode(f["data"])) == f["crc"]
            # session pinned to the DECODE replica (satellite)
            assert fleet._sessions["t1"] == dec.id
            sup.feed(dec.id, tok_ev(gid, fleet.request(gid).generation,
                                    [8, 9, 10, 11], fin=True,
                                    reason="length"))
            fleet.step()
            assert fleet.result(gid).tolist() == \
                PROMPT.tolist() + [7, 8, 9, 10, 11]
            m = fleet.metrics()
            assert m["prefill_handoffs"] == 1
            assert m["kv_pages_transferred"] == 3
            assert m["handoff_failovers"] == 0
        finally:
            fleet.close()

    def test_kvdone_fin_completes_without_decode_stage(self):
        fleet, sup = make_split_fleet()
        try:
            gid = fleet.submit(PROMPT, max_new=1)
            fleet.step()
            sup.feed(0, {"e": "kvdone", "gid": gid, "hid": 1,
                         "first_tok": 42, "fin": True, "reason": "length",
                         "frames": 0, "crc": 0})
            fleet.step()
            assert fleet.result(gid).tolist() == PROMPT.tolist() + [42]
            assert not any(h.ops("submit_pages") for h in sup.handles)
            assert fleet.metrics()["prefill_handoffs"] == 1
        finally:
            fleet.close()

    def test_zombie_stale_hid_cannot_double_deliver(self):
        fleet, sup = make_split_fleet(("prefill", "prefill", "decode"))
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            src = next(i for i in (0, 1)
                       if sup.handles[i].ops("prefill"))
            # a couple of frames arrive, then the prefill worker dies
            evs = frame_events(gid, 1)
            for ev in evs[:2]:
                sup.feed(src, ev)
            fleet.step()
            sup.die(src)
            fleet.step()
            other = 1 - src
            assert sup.handles[other].ops("prefill")[0]["hid"] == 2
            assert fleet.metrics()["handoff_failovers"] == 1
            assert fleet.request(gid).frames == {}  # discarded atomically
            # the zombie's remaining frames + kvdone (stale hid 1) are
            # dropped — no token, no pages, no double handoff
            for ev in evs[2:]:
                sup.feed(src, ev)
            fleet.step()
            assert fleet.tokens(gid) == []
            assert fleet.metrics()["prefill_handoffs"] == 0
            # the re-driven transfer (hid 2) completes normally
            for ev in frame_events(gid, 2, first_tok=9):
                sup.feed(other, ev)
            fleet.step()
            assert fleet.tokens(gid) == [9]
            sup.feed(2, tok_ev(gid, fleet.request(gid).generation,
                               [1, 2, 3], fin=True, reason="length"))
            fleet.step()
            assert fleet.result(gid).tolist() == \
                PROMPT.tolist() + [9, 1, 2, 3]
        finally:
            fleet.close()

    def test_corrupt_frame_retries_then_typed_error(self):
        fleet, sup = make_split_fleet(max_kv_retries=2)
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            for attempt in range(1, 4):
                fleet.step()  # dispatch prefill (hid == attempt)
                assert sup.handles[0].ops("prefill")[-1]["hid"] == attempt
                for ev in frame_events(gid, attempt, corrupt_seq=1):
                    sup.feed(0, ev)
                fleet.step()  # corrupt frame -> handoff voided
            with pytest.raises(KVTransferError) as ei:
                fleet.result(gid)
            assert ei.value.retries == 3
            m = fleet.metrics()
            assert m["kv_transfer_retries"] == 2  # within-budget re-drives
            assert fleet.request(gid).state == "failed"
            # the registry series agrees
            assert om.REGISTRY.get(
                "fleet_kv_transfer_retries_total").value(
                instance=fleet._name) == 2
        finally:
            fleet.close()

    def test_missing_frame_voids_handoff(self):
        fleet, sup = make_split_fleet()
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            for ev in frame_events(gid, 1, drop_seq=1):
                sup.feed(0, ev)
            fleet.step()
            assert fleet.tokens(gid) == []  # incomplete -> no first token
            assert fleet.metrics()["kv_transfer_retries"] == 1
            assert sup.handles[0].ops("prefill")[-1]["hid"] == 2
        finally:
            fleet.close()

    def test_decode_side_rejection_redrives_prefill(self):
        """The decode worker's typed KVTransferError err event re-drives
        the prefill under the same budget — never fails the request
        outright on a transient."""
        fleet, sup = make_split_fleet()
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            for ev in frame_events(gid, 1):
                sup.feed(0, ev)
            fleet.step()
            dec = next(h for h in sup.handles[1:]
                       if h.ops("submit_pages"))
            sup.feed(dec.id, {"e": "err", "gid": gid,
                              "kind": "KVTransferError",
                              "msg": "payload CRC mismatch"})
            fleet.step()
            assert not fleet.request(gid).finished
            assert fleet.metrics()["kv_transfer_retries"] == 1
            # and the prefill was re-dispatched with a fresh handoff id
            assert sup.handles[0].ops("prefill")[-1]["hid"] == 2
        finally:
            fleet.close()

    def test_decode_side_rejections_exhaust_the_budget(self):
        """Regression: the budget re-arms only when a decode worker ACKS
        the pages (first tok), not at kvdone — a decode side that keeps
        rejecting deliveries must eventually exhaust the retry budget
        into a typed KVTransferError instead of re-driving the prefill
        forever."""
        fleet, sup = make_split_fleet(("prefill", "decode"),
                                      max_kv_retries=2)
        try:
            gid = fleet.submit(PROMPT, max_new=4)
            for attempt in range(1, 4):
                fleet.step()
                hid = sup.handles[0].ops("prefill")[-1]["hid"]
                assert hid == attempt
                for ev in frame_events(gid, hid):
                    sup.feed(0, ev)
                fleet.step()  # complete handoff -> pages to the decoder
                sup.feed(1, {"e": "err", "gid": gid,
                             "kind": "KVTransferError",
                             "msg": "frames evicted under load"})
                fleet.step()
            with pytest.raises(KVTransferError) as ei:
                fleet.result(gid)
            assert ei.value.retries == 3
            assert fleet.metrics()["kv_transfer_retries"] == 2
        finally:
            fleet.close()

    def test_decode_death_replays_two_stage_with_same_deadline(self):
        fleet, sup = make_split_fleet(("prefill", "decode", "decode"))
        try:
            gid = fleet.submit(PROMPT, max_new=6, deadline_s=60.0)
            original = fleet.request(gid).deadline
            fleet.step()
            for ev in frame_events(gid, 1):
                sup.feed(0, ev)
            fleet.step()
            dec = next(h for h in sup.handles[1:]
                       if h.ops("submit_pages"))
            sup.feed(dec.id, tok_ev(gid, fleet.request(gid).generation,
                                    [8, 9]))
            fleet.step()
            sup.die(dec.id)
            fleet.step()
            # replay goes BACK through stage 1 (prompt + all emitted),
            # deadline unchanged
            replay = sup.handles[0].ops("prefill")[-1]
            assert replay["hid"] == 2
            assert replay["prompt"] == PROMPT.tolist() + [7, 8, 9]
            assert replay["max_new"] == 3
            assert replay["deadline"] == pytest.approx(original)
            assert fleet.metrics()["redispatches"] == 1
        finally:
            fleet.close()

    def test_degrade_to_colocated_when_no_prefill_healthy(self):
        fleet, sup = make_split_fleet(("prefill", "decode", "decode"))
        try:
            fleet.supervisor.retire(0)
            with pytest.warns(RuntimeWarning, match="no healthy prefill"):
                fleet.submit(PROMPT, max_new=4)
                fleet.step()
            # placed as a COLOCATED submit on a decode replica
            subs = [h for h in sup.handles[1:] if h.ops("submit")]
            assert len(subs) == 1
            assert not any(h.ops("prefill") for h in sup.handles)
            # one-shot: the second degrade does not warn again
            import warnings as _w

            with _w.catch_warnings():
                _w.simplefilter("error")
                fleet.submit(PROMPT, max_new=4)
                fleet.step()
        finally:
            fleet.close()

    def test_backpressure_pauses_transfers_then_sheds_typed(self):
        from paddle_tpu.inference.serving import FleetOverloadedError

        fleet, sup = make_split_fleet(("prefill", "decode"),
                                      max_pending_handoffs=1, max_queue=1)
        try:
            g1 = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            # handoff 1 in flight; request 2 must NOT start a transfer
            g2 = fleet.submit(PROMPT, max_new=4)
            fleet.step()
            assert len(sup.handles[0].ops("prefill")) == 1
            assert fleet.request(g2).state == "queued"
            # the bounded admission queue sheds the next one — typed,
            # never silent growth
            with pytest.raises(FleetOverloadedError):
                fleet.submit(PROMPT, max_new=4)
            # transfer completes -> the paused request proceeds
            for ev in frame_events(g1, 1):
                sup.feed(0, ev)
            fleet.step()
            sup.feed(1, tok_ev(g1, fleet.request(g1).generation,
                               [1, 2, 3], fin=True, reason="length"))
            fleet.step()
            fleet.step()
            assert len(sup.handles[0].ops("prefill")) == 2
        finally:
            fleet.close()

    def test_stage1_head_cannot_deadlock_stage2_behind_it(self):
        """Regression: a stage-1 replay requeued IN FRONT of a
        pages-ready request (decode-death ordering) must not deadlock —
        the stage-2 request behind the backpressure-blocked head is the
        only thing that can drain the pending-handoff count, so it
        places even from behind the head."""
        fleet, sup = make_split_fleet(("prefill", "decode"),
                                      max_pending_handoffs=1,
                                      max_inflight_per_replica=1)
        try:
            ga = fleet.submit(PROMPT, max_new=6)
            fleet.step()
            for ev in frame_events(ga, 1):
                sup.feed(0, ev)
            fleet.step()  # ga pages -> placed on the decode replica
            assert sup.handles[1].ops("submit_pages")
            sup.feed(1, tok_ev(ga, fleet.request(ga).generation, [8]))
            fleet.step()  # ack: ga's buffered pages dropped
            assert fleet.request(ga).pages is None
            gb = fleet.submit(PROMPT, max_new=6)
            fleet.step()  # pending handoffs 0 -> gb's prefill starts
            for ev in frame_events(gb, 1):
                sup.feed(0, ev)
            fleet.step()
            # decode replica full (inflight cap 1): gb waits QUEUED
            # with verified pages -> pending handoffs at the bound
            assert fleet.request(gb).state == "queued"
            assert fleet.request(gb).pages is not None
            # ga's decode replica dies: ga requeues as a stage-1 replay
            # IN FRONT of pages-ready gb; the respawn comes back ready
            sup.die(1)
            fleet.step()
            sup.handles[1].ready = True
            # pre-fix: head ga blocks on the pending-handoff count that
            # only gb (behind it) can reduce — the fleet wedges with a
            # healthy idle decode worker
            for _ in range(4):
                fleet.step()
            sub = sup.handles[1].ops("submit_pages")
            assert len(sub) == 1 and sub[0]["gid"] == gb
            sup.feed(1, tok_ev(gb, fleet.request(gb).generation,
                               [9, 10, 11, 12, 13], fin=True,
                               reason="length"))
            fleet.step()
            fleet.step()
            # ...which drained the buffer and unblocked ga's replay
            replays = sup.handles[0].ops("prefill")
            assert len(replays) == 3 and replays[-1]["gid"] == ga
            assert replays[-1]["prompt"] == PROMPT.tolist() + [7, 8]
            for ev in frame_events(ga, fleet.request(ga).hid,
                                   first_tok=20):
                sup.feed(0, ev)
            fleet.step()
            fleet.step()
            sup.feed(1, tok_ev(ga, fleet.request(ga).generation,
                               [21, 22, 23], fin=True, reason="length"))
            fleet.step()
            assert fleet.result(ga).tolist() == \
                PROMPT.tolist() + [7, 8, 20, 21, 22, 23]
            assert fleet.result(gb).tolist() == \
                PROMPT.tolist() + [7, 9, 10, 11, 12, 13]
        finally:
            fleet.close()

    def test_close_mid_transfer_typed_guards(self):
        fleet, sup = make_split_fleet()
        gid = fleet.submit(PROMPT, max_new=4)
        fleet.step()
        for ev in frame_events(gid, 1)[:2]:
            sup.feed(0, ev)
        fleet.step()
        fleet.close()
        assert sup.shut
        with pytest.raises(EngineClosedError):
            fleet.submit(PROMPT, max_new=4)
        with pytest.raises(EngineClosedError):
            fleet.step()
        for metric in ("fleet_kv_pages_transferred_total",
                       "fleet_kv_transfer_retries_total",
                       "fleet_prefill_handoffs_total",
                       "fleet_handoff_failovers_total"):
            snap = om.REGISTRY.snapshot().get(metric, {"series": {}})
            assert not any(fleet._name in k for k in snap["series"]), \
                metric


class TestSessionAffinityFixes:
    def test_sessions_invalidated_on_dead_replica(self):
        """A dead replica's session pins are dropped on recovery — the
        next session request places least-loaded instead of steering at
        the corpse/cold respawn (ISSUE 15 satellite)."""
        fleet, sup = make_split_fleet(("both", "both"))
        try:
            gid = fleet.submit(PROMPT, max_new=4, session="s")
            fleet.step()
            src = next(i for i, h in enumerate(sup.handles)
                       if h.ops("submit"))
            assert fleet._sessions["s"] == src
            sup.feed(src, tok_ev(gid, 1, [1, 2, 3, 4], fin=True,
                                 reason="length"))
            fleet.step()
            sup.die(src)
            fleet.step()
            assert "s" not in fleet._sessions
            # respawn comes back ready but HOT (load report): without
            # invalidation the stale pin would beat least-loaded and
            # steer the session at the cold slot anyway
            sup.handles[src].ready = True
            sup.feed(src, {"e": "load", "kv": 0.9, "occ": 0.9})
            fleet.step()
            fleet.submit(PROMPT, max_new=4, session="s")
            fleet.step()
            assert len(sup.handles[1 - src].ops("submit")) == 1
        finally:
            fleet.close()

    def test_session_pin_never_points_at_prefill_worker(self):
        fleet, sup = make_split_fleet(("prefill", "decode"))
        try:
            # forge a stale pin at the prefill worker: placement must
            # ignore it (the prefix cache lives on decode replicas)
            fleet._sessions["s"] = 0
            gid = fleet.submit(PROMPT, max_new=4, session="s")
            fleet.step()
            for ev in frame_events(gid, 1):
                sup.feed(0, ev)
            fleet.step()
            assert sup.handles[1].ops("submit_pages")
            assert fleet._sessions["s"] == 1
        finally:
            fleet.close()


class TestIdleBackoff:
    def test_idle_join_sleeps_instead_of_spinning(self):
        """ISSUE 15 satellite: an idle join(timeout=...) must back off
        exponentially — bounded step() calls, not a 5 ms busy-poll (and
        certainly not a hot spin)."""
        fleet, sup = make_split_fleet(("both",),
                                      idle_backoff=(0.002, 0.05))
        try:
            fleet.submit(PROMPT, max_new=4)
            fleet.step()
            calls = {"n": 0}
            orig = fleet.step

            def counting_step():
                calls["n"] += 1
                return orig()

            fleet.step = counting_step
            t0 = time.perf_counter()
            with pytest.raises(TimeoutError):
                fleet.join(timeout=0.4)
            wall = time.perf_counter() - t0
            assert wall >= 0.35
            # a busy spin would make tens of thousands of calls; the
            # backoff caps it near wall/floor at worst, wall/ceiling
            # once saturated
            assert calls["n"] < 220, calls["n"]
        finally:
            fleet.close()

    def test_backoff_helper_floor_ceiling(self):
        from paddle_tpu.inference.serving.fleet.router import _IdleBackoff

        b = _IdleBackoff(floor=0.001, ceiling=0.004)
        assert b._delay == 0.001
        b.idle()
        b.idle()
        b.idle()
        assert b._delay == 0.004  # clamped at the ceiling
        b.idle()
        assert b._delay == 0.004
        b.reset()
        assert b._delay == 0.001


# ---------------------------------------------------------------------------
# real split fleet (subprocess smoke; the storm is the slow-tier drill)
# ---------------------------------------------------------------------------

class TestRealDisaggFleet:
    def test_split_fleet_bit_exact_and_clean(self, tmp_path, tiny_model):
        from paddle_tpu.inference.serving import save_llama_artifact

        artifact = str(tmp_path / "model")
        save_llama_artifact(tiny_model, artifact)
        kw = dict(num_blocks=48, block_size=8, max_batch_size=2)
        prompts = _prompts(2, seed=4, lens=(5, 11))
        with LLMEngine(tiny_model, ingest_async=False, **kw) as eng:
            refs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        fleet = Router(artifact=artifact, n_replicas=2, engine_kwargs=kw,
                       roles=["prefill", "decode"],
                       log_dir=str(tmp_path / "logs"))
        try:
            gids = [fleet.submit(p, max_new=6) for p in prompts]
            fleet.join(timeout=180)
            for gid, ref in zip(gids, refs):
                np.testing.assert_array_equal(fleet.result(gid), ref)
            m = fleet.metrics()
            assert m["prefill_handoffs"] == len(prompts)
            assert m["kv_pages_transferred"] >= len(prompts)
            assert m["kv_transfer_retries"] == 0
            assert m["handoff_failovers"] == 0
            for i, role in enumerate(("prefill", "decode")):
                s = fleet.replica_stats(i)
                assert s["role"] == role
                assert s["blocks_free"] == kw["num_blocks"] - 1
                assert s["running"] == 0 and s["waiting"] == 0
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# fault-site + roles registration
# ---------------------------------------------------------------------------

class TestWiring:
    def test_new_fault_sites_registered(self):
        from paddle_tpu.utils import fault_injection as fi

        assert "serve.prefill_crash" in fi.SITES
        assert "serve.kv_transfer_corrupt" in fi.SITES
        # armable (boolean sites probed via should_fire)
        with fi.inject("serve.prefill_crash", every_n=3) as inj:
            assert not fi.should_fire("serve.prefill_crash")
            assert not fi.should_fire("serve.prefill_crash")
            assert fi.should_fire("serve.prefill_crash")
            assert inj.fires == 1
        with fi.inject("serve.kv_transfer_corrupt", max_fires=1):
            assert fi.should_fire("serve.kv_transfer_corrupt")
            assert not fi.should_fire("serve.kv_transfer_corrupt")

    def test_supervisor_validates_roles(self):
        from paddle_tpu.inference.serving.fleet import ReplicaSupervisor

        # both raise BEFORE any worker process spawns
        with pytest.raises(ValueError, match="roles"):
            ReplicaSupervisor(2, {}, roles=["prefill"])
        with pytest.raises(ValueError, match="unknown replica roles"):
            ReplicaSupervisor(1, {}, roles=["llama"])

    def test_typed_error_exported(self):
        from paddle_tpu.inference.serving import fleet as fleet_mod

        assert issubclass(KVTransferError, RuntimeError)
        assert hasattr(fleet_mod, "KVTransferError")
        e = KVTransferError("boom", gid=3, retries=4)
        assert e.gid == 3 and e.retries == 4


# ---------------------------------------------------------------------------
# slow tier: the storm + the bench acceptance
# ---------------------------------------------------------------------------

def _chaos_env():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    return env


@pytest.mark.slow
class TestChaosDisaggDrill:
    def test_drill_disagg(self, tmp_path):
        """ISSUE 15 acceptance: prefill-worker SIGKILL mid-transfer +
        decode-worker hang mid-stream over a 2-prefill+2-decode fleet,
        every output bit-identical to the colocated single-engine
        baseline, fleet_handoff_failovers_total > 0, allocators clean
        via the stats RPC — plus the corrupt-transfer burst completing
        through the retry budget."""
        import subprocess
        import sys as _sys

        r = subprocess.run(
            [_sys.executable, os.path.join(REPO, "scripts",
                                           "chaos_serve.py"),
             "--drill", "disagg", "--fleet", "4", "--out",
             str(tmp_path)],
            env=_chaos_env(), cwd=REPO, capture_output=True, text=True,
            timeout=560)
        assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
        assert "SERVE DRILL PASSED" in r.stdout


@pytest.mark.slow
class TestDisaggBenchAcceptance:
    def test_disagg_itl_at_or_under_colocated(self):
        """ISSUE 15 bench acceptance: on the long-prompt mix, the
        disagg fleet's decode-worker ITL p99 comes in at or under the
        colocated arm's (decode workers never prefill), bit-exact."""
        import sys as _sys

        sys_path = os.path.join(REPO, "scripts")
        if sys_path not in _sys.path:
            _sys.path.insert(0, sys_path)
        import bench_serving as bsv

        res = bsv.run_disagg_ab(tiny=True, seed=0, fleet=3)
        assert res["bit_exact"], res
        assert res["disagg"]["prefill_handoffs"] >= res["num_requests"]
        assert res["itl_p99_ratio"] is not None
        assert res["itl_p99_ratio"] <= 1.0, res
