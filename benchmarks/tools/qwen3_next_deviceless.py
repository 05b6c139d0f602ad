"""Size ``qwen3-next-serve`` for a v5e without the chip: the decode step
and every prefill chunk rung of the configuration as its runner builds them,
compiled by XLA:TPU + Mosaic for a described device, with their bytes.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/qwen3_next_deviceless.py [rung ...]

Nothing runs and nothing is allocated: the model's weights come from
``jax.eval_shape`` over the runner's own constructor, the cache's arrays are
made as shapes, and every operand is described on a device of a ``v5e:2x2``
topology. While the engine is built and traced ``jax.default_backend``
answers "tpu", so that the Pallas kernels are taken as on the chip
(``scripts/chip_calls/pr29_lowered_text.py``'s method). Prints one JSON line
a program: XLA's ``memory_analysis`` and the Mosaic kernels in its text. No
number this prints is a measurement.
"""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.runners import serve_qwen3_next as runner  # noqa: E402
from paddle_tpu.inference.serving import LLMEngine  # noqa: E402
from paddle_tpu.inference.serving import kv_cache  # noqa: E402

config = bench_run.load_json("benchmarks", "configs", "qwen3-next-serve.json")
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one_chip = SingleDeviceSharding(topo.devices[0])
sds = jax.ShapeDtypeStruct


def on_chip(tree):
    return jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype, sharding=one_chip), tree)


def abstract_model(model):
    """The runner's model with a shape in every parameter's place; the rotary
    tables, which a graph closes over as constants, are computed."""
    from paddle_tpu.models.llama import _rope_cache

    real_jit = jax.jit
    jax.jit = lambda f, **kw: (lambda *a: jax.eval_shape(f, *a))
    try:
        net = runner.build_model(model, 1, config.get("dtype", "bfloat16"))
    finally:
        jax.jit = real_jit
    c = net.config
    cos, sin = _rope_cache(c.max_position_embeddings, c.rotary_dim,
                           c.rope_theta)
    net.model.rope_cos._data = jnp.asarray(cos)
    net.model.rope_sin._data = jnp.asarray(sin)
    return net


class _ShapesOnly:
    """``jnp`` to the cache's constructor, its ``zeros`` a shape."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def zeros(shape, dtype=jnp.float32):
        return sds(tuple(shape), jnp.dtype(dtype))


def kernels(text):
    return sorted(set(
        ln.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
        for ln in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln))


def report(name, traced, resident):
    compiled = traced.lower().compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    if os.environ.get("DEVICELESS_TEXT"):
        with open(os.path.join(os.environ["DEVICELESS_TEXT"], name + ".txt"),
                  "w") as f:
            f.write(text)
    print(json.dumps({
        "program": name, "arguments": m.argument_size_in_bytes,
        "temp": m.temp_size_in_bytes, "outputs": m.output_size_in_bytes,
        "aliased": m.alias_size_in_bytes,
        "total": m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes,
        **resident, "kernels": kernels(text)}), flush=True)


def main(rungs):
    model = runner.model_sizes(config)
    net = abstract_model(model)
    net.eval()
    params = sum(int(np.prod(p._data.shape)) for p in net._unique_params())
    weight_bytes = sum(int(np.prod(p._data.shape)) * p._data.dtype.itemsize
                       for p in net._unique_params())
    real_backend, real_jnp = jax.default_backend, kv_cache.jnp
    jax.default_backend = lambda: "tpu"
    kv_cache.jnp = _ShapesOnly()
    try:
        eng = LLMEngine(net, capture_logits=True, **config["engine"])
        eng._build_jits()
        c, B = eng.cache, eng.max_batch_size
        pools = [c.k, c.v]
        state = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for sp, k, v in zip(c.layout, c.k, c.v)
                    if sp.kind == "state" for a in (k, v))
        pages = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for sp, k, v in zip(c.layout, c.k, c.v)
                    if sp.paged for a in (k, v))
        resident = {"parameters": params, "weight_bytes": weight_bytes,
                    "state_bytes": state, "kv_pool_bytes": pages}
        i32 = lambda *shape: sds(shape, jnp.int32, sharding=one_chip)  # noqa: E731
        weights = on_chip([p._data for p in eng._params])
        extras = lambda slots: [None, slots, on_chip(eng._graph_extras()[-1])]  # noqa: E731
        decode = [weights, i32(B, 2), i32(B), i32(B, eng.max_pages),
                  *on_chip(pools), [], [], i32(B), *extras(i32(B))]
        report("decode_step", eng._decode_jit._jit.trace(*decode), resident)
        for rung in rungs:
            chunk = [weights, i32(1, rung), i32(), i32(), i32(eng.max_pages),
                     *on_chip(pools), [], [], *extras(i32(1))]
            report(f"prefill_{rung}", eng._prefill_jit._jit.trace(*chunk),
                   resident)
    finally:
        jax.default_backend, kv_cache.jnp = real_backend, real_jnp


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [
        b for b in config["engine"]["prefill_buckets"]
        if b <= config["engine"]["max_prefill_tokens_per_step"]])
