"""The readings a ``check.tolerance`` of mimo-v2-flash-serve lies between
(PERF.md, PR 27): the check's rows against the float32 reference with the
engine as the configuration states it, and with one thing computed in the
precision below:

* ``kv_through_int8``: every K and V row rounded to int8 codes and back
  (``quantize_kv_rows``, the engine's own int8 KV arithmetic) before it is
  written and attended over;
* ``weights_through_int8``: every bf16 matrix rounded to int8 codes a
  column and back (weight-only int8), the reference keeping the bf16 ones;
* ``router_in_bf16``: the router's weights and correction bias rounded to
  bf16 (what casting the whole model would do).

One model a seed, an engine a variant. Run from the root of a checkout, on
the chip:
    python3 benchmarks/tools/mimo_precision.py <variant>[,<variant>...] <seed> [<seed> ...]
Prints one JSON line a (seed, variant): the verdict, the worst row, and
every row's margin, error and the experts it was routed otherwise by, in the
order of the reference's margin."""
import gc, json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import common, serve_mimo_v2 as runner  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "mimo-v2-flash-serve.json")
common.require_tpu(1)
print("[cache]", common.place_cache(), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import paged_attention as spa  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows  # noqa: E402


def through_int8(x):
    codes, scale = quantize_kv_rows(x)
    return (codes.astype(jnp.float32) * scale[..., None]).astype(x.dtype)


def rounded(attend):
    def f(self, q, k, v, scale, sink=None):
        return attend(self, q, through_int8(k), through_int8(v), scale, sink=sink)
    return f


@jax.jit
def column_int8(w):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True) / 127.0
    codes = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (jnp.clip(codes, -127, 127) * scale).astype(w.dtype)


def lower(net, variant):
    """Round ``net``'s parameters in place as ``variant`` says."""
    for name, p in net.named_parameters():
        a = p._data
        if variant == "weights_through_int8" and a.ndim == 2 \
                and a.dtype == jnp.bfloat16:
            p._data = column_int8(a)
        elif variant == "router_in_bf16" and ".router." in name:
            p._data = a.astype(jnp.bfloat16).astype(a.dtype)


STATES = (spa.DecodeAttnState, spa.ChunkAttnState)
stated = [cls.attend for cls in STATES]
model = runner.model_sizes(config)
spec = config["check"]
variants = sys.argv[1].split(",")
for seed in map(int, sys.argv[2:]):
    for variant in variants:
        t0 = time.time()
        net = runner.build_model(model, seed, config.get("dtype", "bfloat16"))
        net.eval()
        lower(net, variant)
        for cls, attend in zip(STATES, stated):
            cls.attend = rounded(attend) if variant == "kv_through_int8" else attend
        eng = LLMEngine(net, capture_logits=True, **config["engine"])
        try:
            checked = runner.engine_rows(eng, model, seed, spec)
        finally:
            eng.close()
            del eng            # 5 GB of pools: gone before the reference
            gc.collect()
        if variant in ("weights_through_int8", "router_in_bf16"):
            # the reference keeps the weights the configuration states
            del net
            gc.collect()
            net = runner.build_model(model, seed, config.get("dtype", "bfloat16"))
        t1 = time.time()
        check = runner.check_logits(common.named_weights(net), model, checked, spec)
        print(json.dumps({"seed": seed, "variant": variant,
                          "engine_s": round(t1 - t0, 1),
                          "reference_s": round(time.time() - t1, 1), **check}),
              flush=True)
        del net, checked
        gc.collect()
