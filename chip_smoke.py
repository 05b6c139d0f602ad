"""chip_smoke.py — does the system still start on the chip?

One process, one pass over the main path through the entry points a user
calls, at the full width of two presets the repo supports (weights random,
from a seed):

* **train**: ``llama_125m`` bf16, batch 16 x seq 1024, the ``bench.py``
  flagship build through ``paddle.incubate.fused_train_step``.
* **serve**: ``llama_1b`` bf16 (16 heads x 128: the one preset whose shapes
  reach the paged Pallas kernels) through ``LLMEngine.add_request`` /
  ``stream()``, fp then int8 KV, logits checked against the model's plain
  full forward on the same chip.
* host/device latencies, the compile-cache placement, a device FFT.
* the paged decode kernel alone against the lax fallback at the benchmark's
  serving geometry (Mistral-7B's 32 heads over 8 kv heads), ragged contexts
  around every chunk boundary, NaN in every pool slot no live token holds.
* with four or more devices: the same two models under a ``Plan``
  (dp2 x tp2 + zero1 training, tp4 serving) in place of the one-chip serve
  phases, asserting that arrays really leave device 0.

It is a smoke, not a benchmark: its times are printed for orientation and
none of them is a performance claim. It exits non-zero, printing no result
line, when JAX reports no TPU, when ``PT_PALLAS_INTERPRET=1`` would swap
the kernels for the interpreter, when the repo is not importable, or when
any phase fails. The last stdout line of a passing run is one JSON object.
"""

from __future__ import annotations

import faulthandler
import gc
import importlib
import json
import math
import os
import sys
import time
import traceback

import numpy as np

SEED = 0

# --- tolerances -------------------------------------------------------------
# Metric: max|engine - reference| / max|reference| over one logits row, the
# repo's own measure (tests/test_quantized_serving.py LOGIT_REL_TOL).
# Engine and reference run the same bf16 weights on the same chip but round
# differently: the Pallas kernels keep f32 probabilities where the XLA
# reference rounds them to bf16, and every matmul runs at another shape, so
# in another tile order. bf16 keeps 8 significand bits (2^-8 = 0.4% per
# rounding) and the residual stream crosses 22 layers. Measured on the v5e
# (PR 21 chip runs): 0.034 worst over prefill rows, 0.033 over decode rows.
# A wrong page, mask or head moves a row by 0.3-1.0.
FP_LOGIT_TOL = 0.06
# int8 KV adds the per-row quantisation error that the repo's tolerance
# contract bounds at 0.08 (DESIGN_DECISIONS "Tolerance contract"), on top
# of the bf16 drift above. Measured on the v5e: 0.045 prefill, 0.052 decode.
INT8_LOGIT_TOL = 0.08 + FP_LOGIT_TOL
# Plan vs one chip, per-step training loss: same data, same seed, bf16
# weights; only the reduction order of the dp/tp collectives differs.
PLAN_LOSS_TOL = 0.02
# The decode kernel alone against the float32 fallback, max|kernel - ref| /
# max|ref| over one request's [H, D] output (phase_decode_kernel).
DECODE_KERNEL_TOL = 0.02


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def bail(reason):
    print(f"chip_smoke: {reason}", file=sys.stderr)
    sys.exit(2)


def log(msg):
    print(msg, flush=True)


# --- compile accounting -----------------------------------------------------

class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits through
    jax.monitoring — every executable JAX builds in this process, eager
    ops included, not only the engine's own CountingJit rows."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def mosaic_calls(compiled_text):
    """(forward, backward) Mosaic custom calls in a compiled module's HLO;
    a backward kernel carries autodiff's ``transpose(`` in its op name."""
    lines = [ln for ln in compiled_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    bwd = sum("transpose(" in ln for ln in lines)
    return len(lines) - bwd, bwd


# --- phases -----------------------------------------------------------------

def phase_latencies():
    """The three host/device numbers the repo's notes lean on, each ended
    by block_until_ready."""
    import jax
    import jax.numpy as jnp

    bump = jax.jit(lambda x: x + 1.0)
    small = jnp.zeros((), jnp.float32)
    logits = jnp.zeros((8, 32000), jnp.float32)
    bump(small).block_until_ready()
    bump(logits).block_until_ready()

    def fetch_s(x, whole):
        # a fresh device value each time: a fetched array caches its host
        # copy, so fetching the same one twice measures nothing.
        # ``whole`` times dispatch + wait + fetch, else the fetch alone.
        t0 = time.perf_counter()
        y = bump(x)
        y.block_until_ready()
        if not whole:
            t0 = time.perf_counter()
        np.asarray(y)
        return time.perf_counter() - t0

    def median_us(x, whole=False):
        return round(float(np.median(
            [fetch_s(x, whole) for _ in range(50)])) * 1e6, 1)

    n = 500

    def chain():
        y = small
        for _ in range(n):
            y = bump(y)
        y.block_until_ready()

    chain()
    t0 = time.perf_counter()
    chain()
    per_call = round((time.perf_counter() - t0) / n * 1e6, 1)
    out = {"fetch_scalar_us": median_us(small),
           "fetch_8x32000_f32_us": median_us(logits),
           "dispatch_ready_fetch_scalar_us": median_us(small, whole=True),
           "dispatch_per_call_us": per_call}
    log(f"[latency] {json.dumps(out)}")


def build_trainer(plan=None):
    """The bench.py flagship build (bench.make_llama), seeded."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.fused_train_step import FusedTrainStep
    from paddle_tpu.models import LlamaForCausalLM, llama_125m

    paddle.seed(SEED)
    cfg = llama_125m()
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_of(out):
        return out[0] if isinstance(out, (tuple, list)) else out

    if plan is None:
        step = paddle.incubate.fused_train_step(model, opt, loss_fn=loss_of)
    else:
        step = FusedTrainStep(model, opt, loss_fn=loss_of, plan=plan)
    return cfg, model, step


def phase_train(plan=None, batch=16, seq=1024, warmup=2, steps=6):
    import paddle_tpu as paddle

    tag = "train" if plan is None else f"train[{plan!r}]"
    cfg, model, step = build_trainer(plan)
    rng = np.random.RandomState(SEED)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    t0 = time.perf_counter()
    first = step(ids, labels)
    first._data.block_until_ready()
    cold_s = time.perf_counter() - t0
    losses = [first] + [step(ids, labels) for _ in range(warmup - 1)]
    losses[-1]._data.block_until_ready()
    t0 = time.perf_counter()
    losses += [step(ids, labels) for _ in range(steps)]
    losses[-1]._data.block_until_ready()
    warm_s = (time.perf_counter() - t0) / steps
    losses = [float(np.asarray(l._data)) for l in losses]

    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    fwd, bwd = mosaic_calls(step._lower(ids, labels).compile().as_text())
    layers = cfg.num_hidden_layers
    log(f"[{tag}] llama_125m bf16 batch {batch} x seq {seq}: first step "
        f"{cold_s:.1f} s (compile included), then {warm_s * 1e3:.1f} ms/step "
        f"over {steps} steps; attention path {fa.LAST_PATH}; Mosaic calls "
        f"in the compiled step: {fwd} fwd + {bwd} bwd ({layers} layers)")
    log(f"[{tag}] losses " + " ".join(f"{l:.4f}" for l in losses))
    check(all(math.isfinite(l) for l in losses), f"{tag}: non-finite loss")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
          f"{tag}: first loss {losses[0]:.3f} is not near ln(vocab) = "
          f"{math.log(cfg.vocab_size):.3f}")
    check(losses[-1] < losses[0], f"{tag}: loss did not fall")
    check(fa.LAST_PATH == "pallas",
          f"{tag}: attention took {fa.LAST_PATH!r}, not the Pallas kernel")
    check(fwd == layers and bwd == 2 * layers,
          f"{tag}: expected {layers} fwd + {2 * layers} bwd Mosaic calls, "
          f"found {fwd} + {bwd}")
    if plan is not None:
        w = model.llama.layers[0].self_attn.q_proj.weight._data
        used = bytes_in_use()
        log(f"[{tag}] q_proj on {len(w.sharding.device_set)} devices, shard "
            f"{w.addressable_shards[0].data.shape}; bytes in use {used}")
        check(len(w.sharding.device_set) == plan.mesh.devices.size,
              f"{tag}: q_proj lives on {len(w.sharding.device_set)} devices")
        check(min(used) > 0.5 * max(used) and min(used) > 2 ** 27,
              f"{tag}: devices do not hold their share: {used}")
    return {"losses": losses, "cold_s": round(cold_s, 1),
            "ms_per_step": round(warm_s * 1e3, 1)}


#: (prompt length, tokens to generate): three prefill buckets (64, 128 and
#: the 2048 top rung), ragged decode lengths
REQUESTS = ((1100, 6), (40, 12), (50, 5), (100, 9), (120, 16), (33, 7),
            (128, 4), (60, 10))


def make_prompts(vocab):
    rng = np.random.RandomState(SEED + 1)
    return [rng.randint(0, vocab, n).astype(np.int32) for n, _ in REQUESTS]


def run_burst(eng, prompts, lengths, capture=None):
    """Submit every prompt, drain stream(); returns (rids, {rid: tokens}).
    With ``capture``, each emission's logits row lands in
    ``capture[rid][emission index]``. A step that finishes a prefill also
    decodes that request once, and ``last_logits`` keeps only the newest
    row — so emission 0 is trustworthy only from a request that stops
    there (``lengths`` all 1); longer requests skip it."""
    from paddle_tpu.inference.serving import SamplingParams

    rids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, lengths)]
    emitted = {r: 0 for r in rids}
    for out in eng.stream():
        j = emitted[out.rid]
        emitted[out.rid] += 1
        if capture is not None and (j > 0 or out.finished):
            capture.setdefault(out.rid, {})[j] = \
                eng.request(out.rid).last_logits.copy()
    toks = {}
    for r, n in zip(rids, lengths):
        req = eng.request(r)
        check(req.finished and len(req.output_tokens) == n,
              f"request {r} finished={req.finished} with "
              f"{len(req.output_tokens)}/{n} tokens")
        toks[r] = list(req.output_tokens)
        eng.release(r)
    return rids, toks


def reference_logits(model, prompts, toks_by_req):
    """The model's plain full forward over prompt + generated tokens, padded
    (causal: padding after the end changes nothing before it) to two
    lengths that are not multiples of 128, so the reference attention is
    the XLA path, independent of every Pallas kernel. Returns, per
    request, logits rows [n_generated, V] f32: row j is what emission j
    was sampled from."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    seqs = [np.concatenate([p, np.asarray(t, np.int32)])
            for p, t in zip(prompts, toks_by_req)]
    out = [None] * len(seqs)
    short = [i for i, s in enumerate(seqs) if len(s) <= 200]
    groups = [(short, 200)] + [([i], 1150) for i in range(len(seqs))
                               if i not in short]
    with paddle.no_grad():
        for idx, width in groups:
            ids = np.zeros((len(idx), width), np.int32)
            for row, i in enumerate(idx):
                ids[row, :len(seqs[i])] = seqs[i]
            logits = model(paddle.to_tensor(ids))._data
            for row, i in enumerate(idx):
                n0, n = len(prompts[i]), len(toks_by_req[i])
                out[i] = np.asarray(
                    logits[row, n0 - 1:n0 - 1 + n].astype(jnp.float32))
    return out


def executable_text(eng, prompt_bucket=None):
    """Compiled HLO of one engine executable at the shapes the burst used
    (abstract inputs: the live pools were donated): the prefill at
    ``prompt_bucket``, or the decode step, whose operands the engine names
    itself (``decode_abstract_args``)."""
    import jax
    import jax.numpy as jnp

    if prompt_bucket is None:
        return eng._decode_jit.lower(
            *eng.decode_abstract_args()).compile().as_text()

    def abstract(tree):
        # single-device arrays stay unplaced, as jit treats them at a call
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if len(x.sharding.device_set) > 1
                else None), tree)

    c = eng.cache
    params = abstract([p._data for p in eng._params])
    pools = abstract([c.k, c.v, c.k_scale, c.v_scale])
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return eng._prefill_jit.lower(
        params, i32(1, prompt_bucket), i32(), i32(), i32(eng.max_pages),
        *pools).compile().as_text()


def phase_serve(model, kv_dtype, tol, counter, plan=None):
    from paddle_tpu import jit as pjit
    from paddle_tpu.inference.serving import LLMEngine, SamplingParams
    from paddle_tpu.io import native

    tag = f"serve kv={kv_dtype or 'bf16'}" + (f" {plan!r}" if plan else "")
    cfg = model.config
    prompts = make_prompts(cfg.vocab_size)
    layers = cfg.num_hidden_layers
    eng = LLMEngine(model, num_blocks=512, max_batch_size=8,
                    kv_dtype=kv_dtype, capture_logits=True, plan=plan)
    try:
        lengths = [n for _, n in REQUESTS]
        t0 = time.perf_counter()
        captured = {}
        rids, toks = run_burst(eng, prompts, lengths, captured)
        cold_s = time.perf_counter() - t0
        log(f"[{tag}] {len(rids)} requests answered, prompts "
            f"{[n for n, _ in REQUESTS]} -> prefill buckets "
            f"{sorted({eng._bucket_for(n) for n, _ in REQUESTS})} of ladder "
            f"{eng.prefill_buckets}; ingest: "
            f"{'thread' if eng._ingest and not eng._ingest._dead else 'synchronous'}"
            f"; native collate: {'on' if native.available() else 'numpy'}")

        # second identical burst: nothing may compile
        before = counter.compiles
        t0 = time.perf_counter()
        _, toks2 = run_burst(eng, prompts, lengths)
        warm_s = time.perf_counter() - t0
        recompiles = counter.compiles - before
        stats = {n: s["compiles"] for n, s in pjit.cache_stats().items()
                 if n in (eng._prefill_name, eng._decode_name)}
        log(f"[{tag}] first burst {cold_s:.1f} s (compiles included), "
            f"second {warm_s:.1f} s with {recompiles} compiles; engine "
            f"executables compiled: {stats}")
        check(recompiles == 0,
              f"{tag}: {recompiles} compiles in the second identical burst")
        check(list(toks.values()) == list(toks2.values()),
              f"{tag}: second burst produced different tokens")

        # the executables hold the Mosaic kernels, one per layer
        for name, text in (
                ("prefill@2048", executable_text(eng, 2048)),
                ("decode", executable_text(eng))):
            n = sum(mosaic_calls(text))
            log(f"[{tag}] {name}: {n} Mosaic calls ({layers} layers)")
            check(n == layers, f"{tag}: {name} holds {n} Mosaic calls, "
                               f"expected {layers}")

        # logits against the plain full forward; the prefill rows come
        # from a burst of one-token requests (see run_burst)
        log(f"[{tag}] one-token burst for the prefill logits")
        first = {}
        rids1, toks1 = run_burst(eng, prompts, [1] * len(prompts), first)
        for r, r1 in zip(rids, rids1):
            check(toks1[r1] == toks[r][:1],
                  f"{tag}: one-token request disagrees with the burst")
            captured[r][0] = first[r1][0]
        log(f"[{tag}] reference full forward")
        ref = reference_logits(model, prompts, [toks[r] for r in rids])
        worst = {"prefill": 0.0, "decode": 0.0}
        agree = total = 0
        for i, r in enumerate(rids):
            for j, row in captured[r].items():
                check(row.shape == (cfg.vocab_size,)
                      and np.isfinite(row).all(),
                      f"{tag}: request {i} emission {j} logits not finite")
                rel = float(np.abs(row - ref[i][j]).max()
                            / (np.abs(ref[i][j]).max() + 1e-9))
                kind = "prefill" if j == 0 else "decode"
                worst[kind] = max(worst[kind], rel)
                agree += int(row.argmax() == ref[i][j].argmax())
                total += 1
        top = float(np.abs(captured[rids[0]][0] - ref[0][0]).max()
                    / (np.abs(ref[0][0]).max() + 1e-9))
        log(f"[{tag}] logits vs full forward over {total} emissions: worst "
            f"rel delta prefill {worst['prefill']:.4f} (top-bucket prompt "
            f"{top:.4f}), decode {worst['decode']:.4f}; tolerance {tol}; "
            f"greedy agreement {agree}/{total}")
        check(max(worst.values()) < tol,
              f"{tag}: logits off by {max(worst.values()):.4f} > {tol}")

        if kv_dtype is None and plan is None:
            # one export/import round trip through the donated pool: a
            # request is exported mid-decode and its continuation must
            # match the original's
            p = prompts[2]
            a = eng.add_request(p, SamplingParams(max_new_tokens=8))
            while not eng.request(a).output_tokens:
                eng.step()
            done = list(eng.request(a).output_tokens)
            pages = eng.export_kv_pages(a)
            for _ in eng.stream():
                pass
            want = list(eng.request(a).output_tokens)
            b = eng.add_request_with_pages(
                np.concatenate([p, done]).astype(np.int32), pages,
                SamplingParams(max_new_tokens=len(want) - len(done)))
            for _ in eng.stream():
                pass
            got = list(eng.request(b).output_tokens)
            log(f"[{tag}] page export -> import round trip: "
                f"{want[len(done):]} vs {got}")
            check(got == want[len(done):],
                  f"{tag}: imported pages decode differently")
        return {"cold_s": round(cold_s, 1), "warm_s": round(warm_s, 1),
                "worst": worst}
    finally:
        eng.close()


def phase_fft():
    import jax.numpy as jnp
    import paddle_tpu as paddle

    rng = np.random.RandomState(SEED + 2)
    x = rng.randn(4, 1024).astype(np.float32)
    spec = paddle.fft.rfft(paddle.to_tensor(x))
    check(jnp.iscomplexobj(spec._data)
          and next(iter(spec._data.devices())).platform == "tpu",
          "fft: the spectrum is not a complex array on the TPU")
    err = np.abs(np.asarray(spec._data) - np.fft.rfft(x)).max()
    st = paddle.signal.stft(paddle.to_tensor(x), n_fft=128, hop_length=32)
    pad = np.pad(x, ((0, 0), (64, 64)), mode="reflect")
    frames = np.stack([pad[:, i:i + 128]
                       for i in range(0, pad.shape[1] - 127, 32)], -1)
    err2 = np.abs(np.asarray(st._data) - np.fft.rfft(frames, axis=-2)).max()
    log(f"[fft] rfft max err {err:.2e}, stft max err {err2:.2e} "
        f"(device complex path)")
    check(err < 1e-2 and err2 < 1e-2, "fft: device result off numpy's")


def phase_decode_kernel():
    """The paged decode kernel against the lax fallback at the benchmark's
    serving geometry (Mistral-7B: 32 heads over 8 kv heads x 128, pages of
    16, 256 pages a request), ragged contexts around every chunk boundary.
    The kernel reads pools that hold NaN wherever no live token sits (a
    page's unwritten slots, the null page, free pages); the reference
    reads the same pools clean, in float32."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.serving.paged_attention import _lax_fallback
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, h, hkv, d, blk, p, n = 32, 32, 8, 128, 16, 256, 6145
    chunk, vmem = pa._decode_chunk(blk, hkv, h, d, 2, p)
    t = chunk * blk
    rng = np.random.RandomState(SEED + 3)
    lens = np.concatenate([
        [1, blk - 1, blk, t - 1, t, t + 1, 2 * t + blk + 3, p * blk],
        rng.randint(1, 1500, b - 8)]).astype(np.int32)
    pages = -(-lens // blk)
    check(pages.sum() < n, "decode kernel: the pool is too small")
    order = rng.permutation(np.arange(1, n))
    tables = np.zeros((b, p), np.int32)          # unused slots: the null page
    live = np.zeros((n, blk), bool)
    at = 0
    for i in range(b):
        tables[i, :pages[i]] = order[at:at + pages[i]]
        at += pages[i]
        live[tables[i, :pages[i]]] = True
        live[tables[i, pages[i] - 1], lens[i] - (pages[i] - 1) * blk:] = False
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (n, blk, hkv, d), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (n, blk, hkv, d), jnp.bfloat16)
    scale = 1.0 / math.sqrt(d)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(_lax_fallback, static_argnums=5)(
            q[:, None].astype(jnp.float32), k_pool.astype(jnp.float32),
            v_pool.astype(jnp.float32), jnp.asarray(tables),
            jnp.asarray(lens), scale)[:, 0])
    dead = jnp.asarray(~live)[:, :, None, None]
    got = np.asarray(jax.jit(pa.paged_decode_attention_pallas,
                             static_argnums=5)(
        q, jnp.where(dead, jnp.nan, k_pool), jnp.where(dead, jnp.nan, v_pool),
        jnp.asarray(tables), jnp.asarray(lens), scale).astype(jnp.float32))
    check(np.isfinite(got).all(), "decode kernel: non-finite output "
          f"in rows {sorted(set(np.argwhere(~np.isfinite(got))[:, 0]))}")
    err = np.abs(got - want)
    rel = err.max(axis=(1, 2)) / np.abs(want).max(axis=(1, 2))
    log(f"[decode kernel] chunk {chunk} pages ({vmem / 2**20:.2f} MiB of "
        f"VMEM planned), contexts {lens.min()}..{lens.max()}: max abs err "
        f"{err.max():.2e}, max relative err {rel.max():.2e} "
        f"(row {int(rel.argmax())}, context {int(lens[rel.argmax()])})")
    # a bf16 output keeps 8 significand bits (2^-9 = 0.2% a rounding) and p
    # enters the PV dot in bf16; a wrong page, mask or head reads 0.3-1.0
    check(rel.max() < DECODE_KERNEL_TOL,
          f"decode kernel: relative error {rel.max():.3e} over "
          f"{DECODE_KERNEL_TOL}")


def build_server_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_1b

    paddle.seed(SEED)
    model = LlamaForCausalLM(llama_1b())
    model.bfloat16()
    model.eval()
    return model


def bytes_in_use():
    import jax

    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def phase_four_chips(counter, one_chip_losses):
    from paddle_tpu.distributed.plan import Plan

    plan = Plan.build({"dp": 2, "tp": 2},
                      ["dp", "tp", ("zero1", {"axis": "dp"})])
    got = phase_train(plan=plan)
    drift = max(abs(a - b) for a, b in zip(got["losses"], one_chip_losses))
    log(f"[four chips] per-step loss vs one chip: max |delta| {drift:.4f} "
        f"(band {PLAN_LOSS_TOL})")
    check(drift < PLAN_LOSS_TOL, f"planned losses drift {drift:.4f}")

    plan = Plan.build({"tp": 4}, ["tp"])
    model = build_server_model()
    res = phase_serve(model, None, FP_LOGIT_TOL, counter, plan=plan)
    w = model.llama.layers[0].self_attn.q_proj.weight._data
    used = bytes_in_use()
    log(f"[four chips] tp=4 serving: q_proj on "
        f"{len(w.sharding.device_set)} devices, shard shape "
        f"{w.addressable_shards[0].data.shape}; bytes in use {used}")
    check(len(w.sharding.device_set) == 4, "q_proj not on four devices")
    check(min(used) > 0.5 * max(used) and min(used) > 2 ** 28,
          f"devices do not hold their share: {used}")
    return res


def main():
    # a compiler abort (SIGABRT inside libtpu) otherwise dies without
    # saying which Python line asked for the compile
    faulthandler.enable()
    if os.environ.get("PT_PALLAS_INTERPRET") == "1":
        bail("PT_PALLAS_INTERPRET=1 replaces the Mosaic kernels with the "
             "interpreter; unset it to check the chip")
    try:
        import jax
        devices = jax.devices()
    except (ImportError, RuntimeError) as e:
        bail(f"JAX found no usable backend: {e}")
    if devices[0].platform != "tpu":
        bail(f"needs a TPU; JAX reports platform {devices[0].platform!r} "
             f"({devices[0].device_kind})")
    try:
        import jaxlib
        import paddle_tpu  # noqa: F401
        from paddle_tpu.jit.cache import place_compile_cache
    except ImportError as e:
        bail(f"the paddle_tpu package is not importable from "
             f"{os.getcwd()}: {e}")
    from importlib.metadata import version

    t_start = time.perf_counter()
    cache_dir = place_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    counter = CompileCounter()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"[device] {json.dumps(device)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {version('libtpu')}")
    log(f"[compile cache] {cache_dir} "
        f"({'set from outside' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'placed by the repo'}), "
        f"{entries} entries at start "
        f"({'warm' if entries else 'cold'})")

    failed = []

    def run(name, fn, *args, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kw)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED")
        finally:
            gc.collect()  # drop the phase's device buffers before the next
            log(f"[{name}] {time.perf_counter() - t0:.1f} s")

    run("latency", phase_latencies)
    train = run("train", phase_train)
    run("fft", phase_fft)
    run("decode kernel", phase_decode_kernel)
    if len(devices) >= 4:
        log("[four chips] four or more devices: running the planned "
            "phases; the one-chip serve phases are left to a one-chip run")
        if train is not None:
            run("four chips", phase_four_chips, counter, train["losses"])
    else:
        log(f"[four chips] skipped: {len(devices)} device(s)")
        model = run("build llama_1b", build_server_model)
        if model is not None:
            run("serve bf16", phase_serve, model, None, FP_LOGIT_TOL,
                counter)
            run("serve int8", phase_serve, model, "int8", INT8_LOGIT_TOL,
                counter)
    log(f"[set-up] whole run {time.perf_counter() - t_start:.1f} s; "
        f"{counter.compiles} executables requested, "
        f"{counter.cache_hits} served by the persistent cache")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
