"""PR 33: the text of ``decode_pure`` and of ``chunk_pure`` at the 2048 bucket,
lowered for a v5e without the chip, for the three serving configurations the
benchmark had (``mistral7b-serve``, ``mimo-v2-flash-serve``,
``joyai-llm-flash-serve``) as their runners build them: one sha256 a graph.
Since PR 34 the fourth too (``nemotron3-nano-serve``, whose graphs take a
state kind's slots), where the checkout has its runner.

    JAX_PLATFORMS=cpu python scripts/chip_calls/pr33_lowered_text.py \
        --repo <checkout> --out <dir>

Run once on the parent's checkout and once on the change's
(``pr33_lowered_text.sh`` does both and compares). ``pr29_lowered_text.py``'s
method (its docstring says what is hashed and why locations are stripped),
but next to nothing is allocated: the large weights are shapes
(``jax.eval_shape`` over the runner's own constructor) and so are the cache's
pools, so the engines are
built at their REAL sizes in a few hundred MB of host memory. Nothing runs:
no number this prints is a measurement.
"""

import argparse
import hashlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from pr29_lowered_text import strip_kernel_locations  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    os.chdir(args.repo)
    os.makedirs(args.out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    strip_kernel_locations()

    from benchmarks.runners import common, serve_joyai_flash, serve_mimo_v2
    from paddle_tpu.inference.serving import LLMEngine, kv_cache

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    sds = jax.ShapeDtypeStruct

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype, sharding=one_chip), tree)

    i32 = lambda *shape: sds(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    real = jax.default_backend, jax.jit, kv_cache.jnp

    class ShapesOnly:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def zeros(shape, dtype=jnp.float32):
            return sds(tuple(shape), jnp.dtype(dtype))

    def shapes_of_the_large(f, **kw):
        """``jax.jit`` to a runner's ``build_model``: every leaf over 64 MB
        comes back as its shape; the small ones (norms, and the rope tables,
        which a graph closes over as constants) are computed, the draws of
        the large ones dropped from that program as dead code."""
        def run(*a):
            out = list(jax.eval_shape(f, *a))
            small = [i for i, x in enumerate(out)
                     if x.size * x.dtype.itemsize <= 64 << 20]
            got = real[1](lambda *a: [f(*a)[i] for i in small])(*a)
            for i, x in zip(small, got):
                out[i] = x
            return out
        return run

    builders = {
        "mistral7b-serve": (common.model_sizes, common.build_model),
        "mimo-v2-flash-serve": (serve_mimo_v2.model_sizes,
                                serve_mimo_v2.build_model),
        "joyai-llm-flash-serve": (serve_joyai_flash.model_sizes,
                                  serve_joyai_flash.build_model)}
    if os.path.exists("benchmarks/runners/serve_nemotron_h.py"):
        from benchmarks.runners import serve_nemotron_h
        builders["nemotron3-nano-serve"] = (serve_nemotron_h.model_sizes,
                                            serve_nemotron_h.build_model)
    hashes = {}
    for name, (sizes, build) in builders.items():
        with open(f"benchmarks/configs/{name}.json") as f:
            config = json.load(f)
        jax.jit = shapes_of_the_large
        try:
            net = build(sizes(config), 1, config.get("dtype", "bfloat16"))
        finally:
            jax.jit = real[1]
        net.eval()
        jax.default_backend = lambda: "tpu"
        kv_cache.jnp = ShapesOnly()
        try:
            eng = LLMEngine(net, capture_logits=True, **config["engine"])
            eng._build_jits()
            c, B = eng.cache, eng.max_batch_size
            w = c.window
            weights = on_chip([p._data for p in eng._params])
            pools = on_chip([c.k, c.v])
            # (window row, counters) before PR 33, (window row, slots,
            # counters) since: the slots are None without a state kind
            extras = eng._graph_extras(None)
            state = getattr(c, "state_slots", None) is not None

            def behind_the_row(n_slots):
                return ([i32(n_slots) if state else None]
                        * (len(extras) - 2) + [on_chip(extras[-1])])
            width = eng.max_pages + (w.ring if w is not None else 0)
            graphs = {
                "decode_pure": eng._decode_jit._jit.trace(
                    weights, i32(B, 2), i32(B), i32(B, width), *pools, [], [],
                    i32(B), *([None] + behind_the_row(B) if extras else [])),
                "chunk_pure@2048": eng._prefill_jit._jit.trace(
                    weights, i32(1, 2048), i32(), i32(), i32(eng.max_pages),
                    *pools, [], [], *([
                        i32(w.n_tail + min(w.ring, 2048 // eng.block_size) + 1)
                        if w is not None else None] + behind_the_row(1)
                        if extras else []))}
            for graph, traced in graphs.items():
                text = traced.lower().as_text()
                key = f"{name} {graph} lowered"
                hashes[key] = hashlib.sha256(text.encode()).hexdigest()
                with open(os.path.join(args.out, key.replace(" ", ".").replace(
                        "@", "_") + ".txt"), "w") as f:
                    f.write(text)
                print(f"{hashes[key]}  {len(text):>9} bytes  {key}",
                      flush=True)
        finally:
            jax.default_backend, kv_cache.jnp = real[0], real[2]
        del eng, net
    with open(os.path.join(args.out, "sha256.json"), "w") as f:
        json.dump(hashes, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
