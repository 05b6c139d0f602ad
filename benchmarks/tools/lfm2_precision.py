"""The readings the two limits of lfm2-8b-a1b-serve's check lie between
(PERF.md): the check's twelve rows against the float32 reference routed
as the engine routed (``serve_lfm2.compare_rows``), with the engine as the
configuration states it and with one thing computed in the precision below,
or one thing of what a request carries lost. Each variant wraps a function of
the program from here; the program has no hook for it:

* ``weights_through_int8``: every bf16 matrix rounded to int8 codes a
  column and back (weight-only int8), the reference keeping the bf16 ones;
* ``kv_through_int8``: every K and V row of the two attention layers
  through int8 codes and back (one scale a head, ``quantize_kv_rows``, the
  engine's own int8 KV arithmetic) before it is written and attended over;
* ``conv_through_int8``: what the short convolution reads of a token, ``B *
  x~`` (what its tail carries from step to step and from chunk to chunk),
  through int8 codes and back, one scale a token;
* ``tail_zeroed_at_chunks``: a prefill chunk that is not a request's first
  starts its convolution from zeros, as a first chunk does: the tail is not
  carried over the boundary (a fault, not a precision: it must fail far
  outside the tolerance).

One model a seed, an engine a variant. Run from the root of a checkout, on
the chip:
    python3 benchmarks/tools/lfm2_precision.py <variant>[,<variant>...] <seed> [<seed> ...]
(``stated`` is the engine as it is.) Prints one JSON line a (seed, variant):
the verdict, the worst row, the widest turn of the routing and every row's
error."""
import gc, json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import common, serve_lfm2 as runner  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "lfm2-8b-a1b-serve.json")
common.require_tpu(1)
print("[cache]", common.place_cache(), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import paged_attention as spa  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows  # noqa: E402

STATES = (spa.DecodeAttnState, spa.ChunkAttnState)
STATED = {"attend": [cls.attend for cls in STATES],
          "shortconv": [cls.shortconv for cls in STATES],
          "shifted": spa.ChunkAttnState._shifted}


def through_int8(a):
    codes, scale = quantize_kv_rows(a)
    return (codes.astype(jnp.float32) * scale[..., None]).astype(a.dtype)


def attend_through_int8(attend):
    def f(self, q, k, v, scale, sink=None):
        return attend(self, q, through_int8(k), through_int8(v), scale,
                      sink=sink)
    return f


def shortconv_through_int8(shortconv):
    def f(self, bx, conv_w):
        return shortconv(self, through_int8(bx), conv_w)
    return f


def shifted_tail_zeroed(self, rows, slot):
    """``ChunkAttnState._shifted`` with the rows the slot's tail gave zeroed:
    shifted array ``j`` holds the tail in its first ``conv_rows - j`` rows."""
    out, fresh, real = STATED["shifted"](self, rows, slot)
    keep = self.spec.conv_rows
    return ([a.at[:keep - j].set(0) if j < keep else a
             for j, a in enumerate(out)], fresh, real)


@jax.jit
def column_int8(w):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True) / 127.0
    codes = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (jnp.clip(codes, -127, 127) * scale).astype(w.dtype)


def put(variant):
    """Stand the variant's functions in the program's places (``stated``
    and an unknown name put the program's own back)."""
    for cls, attend, conv in zip(STATES, STATED["attend"],
                                 STATED["shortconv"]):
        cls.attend = attend_through_int8(attend) \
            if variant == "kv_through_int8" else attend
        cls.shortconv = shortconv_through_int8(conv) \
            if variant == "conv_through_int8" else conv
    spa.ChunkAttnState._shifted = shifted_tail_zeroed \
        if variant == "tail_zeroed_at_chunks" else STATED["shifted"]


model = runner.model_sizes(config)
spec = dict(config["check"])
variants = sys.argv[1].split(",")
for seed in map(int, sys.argv[2:]):
    for variant in variants:
        t0 = time.time()
        net = runner.build_model(model, seed, config.get("dtype", "bfloat16"))
        net.eval()
        if variant == "weights_through_int8":
            for _, p in net.named_parameters():
                if p._data.ndim == 2 and p._data.dtype == jnp.bfloat16:
                    p._data = column_int8(p._data)
        put(variant)
        eng = LLMEngine(net, capture_logits=True, **config["engine"])
        try:
            checked = runner.engine_rows(eng, model, seed, spec)
        finally:
            eng.close()
            put("stated")
            del eng            # 5.4 GB of pages: gone before the reference
            gc.collect()
        if variant == "weights_through_int8":
            # the reference keeps the weights the configuration states
            net = runner.build_model(model, seed, config.get("dtype", "bfloat16"))
            net.eval()
        t1 = time.time()
        check = runner.check_logits(net, model, checked, spec)
        print(json.dumps({"seed": seed, "variant": variant,
                          "engine_s": round(t1 - t0, 1),
                          "reference_s": round(time.time() - t1, 1), **check}),
              flush=True)
        del net, checked
        gc.collect()
