"""The readings the two limits of qwen3-next-serve's check lie between
(PERF.md, PR 37): the check's twelve rows against the float32 reference routed
as the engine routed (``serve_qwen3_next.compare_rows``), with the engine as
the configuration states it and with one thing computed in the precision
below (the configuration file's ``check.tolerance_from`` has the readings).
Each variant wraps a function of the program from here; the program has no
hook for it:

* ``weights_through_int8``: every bf16 matrix rounded to int8 codes a
  column and back (weight-only int8), the reference keeping the bf16 ones;
* ``delta_inputs_through_int8``: what the recurrence reads of a token, ``q``,
  ``k`` and ``v`` after the convolution (what an attention layer would cache),
  through int8 codes and back, one scale a head (``quantize_kv_rows``, the
  engine's own int8 KV arithmetic);
* ``kv_through_int8``: every K and V row of the three attention layers
  through int8 codes and back before it is written and attended over;
* ``chunk_products_highest``: a chunk's delta rule (``gated_delta_chunk``)
  with ALL its matrix products at precision "highest" instead of one bfloat16
  pass outside the inverse (the configuration's ``assumed`` states the
  default): what that assumption costs the rows.

One model a seed, an engine a variant. Run from the root of a checkout, on
the chip:
    python3 benchmarks/tools/qwen3_next_precision.py <variant>[,<variant>...] <seed> [<seed> ...]
(``stated`` is the engine as it is.) Prints one JSON line a (seed, variant):
the verdict, the worst row, the widest turn of the routing and every row's
error."""
import gc, json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import common, serve_qwen3_next as runner  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "qwen3-next-serve.json")
common.require_tpu(1)
print("[cache]", common.place_cache(), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import paged_attention as spa  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows  # noqa: E402
from paddle_tpu.ops.pallas import gated_delta  # noqa: E402

STATES = (spa.DecodeAttnState, spa.ChunkAttnState)
STATED = {"split": spa._conv_and_split_qkv,
          "chunk": gated_delta.gated_delta_chunk,
          "attend": [cls.attend for cls in STATES]}


def through_int8(a):
    codes, scale = quantize_kv_rows(a)
    return (codes.astype(jnp.float32) * scale[..., None]).astype(a.dtype)


def split_through_int8(spec, shifted, conv_w):
    return tuple(through_int8(a) for a in STATED["split"](spec, shifted, conv_w))


def attend_through_int8(attend):
    def f(self, q, k, v, scale, sink=None):
        return attend(self, q, through_int8(k), through_int8(v), scale,
                      sink=sink)
    return f


def chunk_highest(*args, **kw):
    with jax.default_matmul_precision("highest"):
        # the jit's cache is keyed by the precision in force
        return STATED["chunk"](*args, **kw)


@jax.jit
def column_int8(w):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True) / 127.0
    codes = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (jnp.clip(codes, -127, 127) * scale).astype(w.dtype)


def put(variant):
    """Stand the variant's functions in the program's places (``stated``
    and an unknown name put the program's own back)."""
    spa._conv_and_split_qkv = split_through_int8 \
        if variant == "delta_inputs_through_int8" else STATED["split"]
    gated_delta.gated_delta_chunk = chunk_highest \
        if variant == "chunk_products_highest" else STATED["chunk"]
    for cls, attend in zip(STATES, STATED["attend"]):
        cls.attend = attend_through_int8(attend) \
            if variant == "kv_through_int8" else attend


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
            del eng            # 8 GB of states and pages: gone before the reference
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
