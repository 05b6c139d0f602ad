"""The readings the two limits of nemotron3-nano-serve's check lie between
(PERF.md, PR 33): the check's twelve rows against the float32 reference routed
as the engine routed (``serve_nemotron_h.compare_rows``), with the engine as
the configuration states it and with one thing computed otherwise (the
configuration file's ``check.tolerance_from`` has the readings). Each variant
wraps a function of the program from here; the program has no hook for it:

* ``state_in_bf16``: the recurrent state ``h`` rounded to bfloat16 wherever
  it goes back into its slot, after every decode step and every chunk (the
  configuration keeps it in float32). By ``lax.reduce_precision``: a pair of
  converts there and back is folded away by XLA:TPU (excess precision is
  allowed), which the first readings showed by equalling ``stated`` to the
  last digit;
* ``state_through_int8``: ``h`` through int8 codes and back there, one scale
  a row of the state dim's lanes (``quantize_kv_rows``' arithmetic);
* ``scan_inputs_through_int8``: what the recurrence reads of a token, ``x``,
  ``B`` and ``C`` after the convolution (what an attention layer would cache
  as values, keys and queries), through int8 codes and back, one scale a head
  or group (``quantize_kv_rows``, the engine's own int8 KV arithmetic);
* ``weights_through_int8``: every bf16 matrix rounded to int8 codes a
  column and back (weight-only int8), the reference keeping the bf16 ones;
* ``scan_products_highest``: a chunk's scan (``ssd_chunk_scan``) with its
  matrix products at precision "highest" instead of the chip's default for
  float32 factors, one bfloat16 pass (the configuration's ``assumed`` states
  the default): what that assumption costs the rows.

One model a seed, an engine a variant. Run from the root of a checkout, on
the chip:
    python3 benchmarks/tools/nemotron_precision.py <variant>[,<variant>...] <seed> [<seed> ...]
(``stated`` is the engine as it is.) Prints one JSON line a (seed, variant):
the verdict, the worst row, the widest turn of the routing and every row's
error."""
import gc, json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import common, serve_nemotron_h as runner  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "nemotron3-nano-serve.json")
common.require_tpu(1)
print("[cache]", common.place_cache(), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import paged_attention as spa  # noqa: E402

from paddle_tpu.ops.pallas import mamba2  # noqa: E402

STATED = {"decode": mamba2.mamba2_decode_update, "stored": mamba2.to_stored,
          "scan": mamba2.ssd_chunk_scan, "split": spa._conv_and_split}


def h_through_bf16(h):
    return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)


def h_through_int8(h):
    scale = jnp.maximum(jnp.max(jnp.abs(h), -1, keepdims=True) / 127.0, 1e-30)
    return jnp.clip(jnp.round(h / scale), -127, 127) * scale


def state_rounded(rounded):
    """The two places a state goes back into its slot: the decode update's
    result (every slot's, in place) and what a chunk stores."""
    def decode(*args, **kw):
        y, h = STATED["decode"](*args, **kw)
        return y, rounded(h)

    return {"mamba2_decode_update": decode,
            "to_stored": lambda h, pack: rounded(STATED["stored"](h, pack))}


def split_through_int8(spec, shifted, conv_w, conv_b):
    from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows

    def rounded(a):
        codes, scale = quantize_kv_rows(a)
        return (codes.astype(jnp.float32) * scale[..., None]).astype(a.dtype)

    return tuple(rounded(a) for a in STATED["split"](spec, shifted, conv_w,
                                                     conv_b))


def scan_highest(*args):
    with jax.default_matmul_precision("highest"):
        return STATED["scan"](*args)


#: variant -> (module, {name: what stands in its place})
WRAPS = {"state_in_bf16": (mamba2, state_rounded(h_through_bf16)),
         "state_through_int8": (mamba2, state_rounded(h_through_int8)),
         "scan_products_highest": (mamba2, {"ssd_chunk_scan": scan_highest}),
         "scan_inputs_through_int8": (spa, {"_conv_and_split":
                                            split_through_int8})}


@jax.jit
def column_int8(w):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True) / 127.0
    codes = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (jnp.clip(codes, -127, 127) * scale).astype(w.dtype)


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
        module, wraps = WRAPS.get(variant, (None, {}))
        kept = {name: getattr(module, name) for name in wraps}
        for name, fn in wraps.items():
            setattr(module, name, fn)
        eng = LLMEngine(net, capture_logits=True, **config["engine"])
        try:
            checked = runner.engine_rows(eng, model, seed, spec)
        finally:
            eng.close()
            for name, fn in kept.items():
                setattr(module, name, fn)
            del eng            # 6 GB of states and pages: gone before the reference
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
