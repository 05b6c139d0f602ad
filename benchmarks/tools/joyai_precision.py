"""The readings a ``check.tolerance`` of joyai-llm-flash-serve lies between
(PERF.md, PR 31): the check's rows (the engine's twelve and the prediction
module's four) against the float32 reference with the engine as the
configuration states it, and with one thing computed in the precision below:

* ``rows_through_int8``: every cached latent row rounded to int8 codes and
  back (``quantize_kv_rows``, the engine's own int8 KV arithmetic, one scale
  a row) before it is written and attended over;
* ``weights_through_int8``: every bf16 matrix rounded to int8 codes a
  column and back (weight-only int8), the reference keeping the bf16 ones.

One model a seed, an engine a variant. Run from the root of a checkout, on
the chip:
    python3 benchmarks/tools/joyai_precision.py <variant>[,<variant>...] <seed> [<seed> ...]
(``stated`` is the engine as it is.) Prints one JSON line a (seed, variant):
the verdict, the worst row, the prediction module's worst, and every row's
margin, error and the experts it was routed otherwise by, in the order of
the reference's margin."""
import gc, json, os, sys, time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import common, serve_joyai_flash as runner  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "joyai-llm-flash-serve.json")
common.require_tpu(1)
print("[cache]", common.place_cache(), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import paged_attention as spa  # noqa: E402
from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows  # noqa: E402

stated_row = spa._AttnState._latent_row


def row_through_int8(self, row):
    codes, scale = quantize_kv_rows(row)
    return stated_row(self, (codes.astype(jnp.float32)
                             * scale[..., None]).astype(row.dtype))


@jax.jit
def column_int8(w):
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True) / 127.0
    codes = jnp.round(w.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return (jnp.clip(codes, -127, 127) * scale).astype(w.dtype)


model = runner.model_sizes(config)
spec = config["check"]
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
        spa._AttnState._latent_row = (row_through_int8
                                      if variant == "rows_through_int8"
                                      else stated_row)
        eng = LLMEngine(net, capture_logits=True, **config["engine"])
        try:
            checked = runner.engine_rows(eng, model, seed, spec)
        finally:
            eng.close()
            del eng            # 9 GB of pools: gone before the reference
            gc.collect()
        module_rows = runner.mtp_rows
        if variant == "weights_through_int8":
            # the reference keeps the weights the configuration states; the
            # prediction module's rows come from the rounded ones, over the
            # reference's hidden state as in every check
            stated = runner.build_model(model, seed, config.get("dtype", "bfloat16"))
            stated.eval()
            prompts, toks, _, _ = checked
            rounded = module_rows(net, common.named_weights(stated), model,
                                  prompts, toks, spec)
            net = stated
            del stated
            gc.collect()
            runner.mtp_rows = lambda *a, **k: rounded
        t1 = time.time()
        try:
            check = runner.check_logits(net, model, checked, spec)
        finally:
            runner.mtp_rows = module_rows
        print(json.dumps({"seed": seed, "variant": variant,
                          "engine_s": round(t1 - t0, 1),
                          "reference_s": round(time.time() - t1, 1), **check}),
              flush=True)
        del net, checked
        gc.collect()
