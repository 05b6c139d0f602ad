"""PR 29: the text of ``decode_pure`` and of ``chunk_pure`` at the 2048 bucket,
lowered for a v5e without the chip, for ``mistral7b-serve`` and
``mimo-v2-flash-serve`` as their runners build them: one sha256 a graph.

    JAX_PLATFORMS=cpu python scripts/chip_calls/pr29_lowered_text.py \
        --repo <checkout> --out <dir> [--compile]

Run once on the parent's checkout and once on the change's (``--repo``: this
file need not exist there); ``pr29_lowered_text.sh`` does both and compares.
The engine is built on the CPU at the real size; while it is built and traced
``jax.default_backend`` answers "tpu", so that pools are donated and the paged
Pallas kernels are taken as on the chip, and every operand is described on a
device of a ``v5e:2x2`` topology, so that the lowering is the TPU's. With
``--compile`` the optimized HLO of XLA:TPU is hashed too. Nothing runs: no
number this prints is a measurement.

What is hashed is the program without its source locations, since a line
deleted anywhere above a traced statement moves them: the lowered text is
printed without debug info, a Mosaic kernel's body (serialized with its
locations inside the custom call) goes through MLIR's ``strip-debuginfo``
before it is serialized, and the compiled text loses its stack frame tables
and each instruction's ``stack_frame_id``.
"""

import argparse
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def without_locations(compiled_text):
    """An optimized HLO module's text less the ``FileNames`` ..
    ``StackFrames`` tables at its head and every ``stack_frame_id``."""
    head, sep, rest = compiled_text.partition("\nFileNames\n")
    if sep:
        _, sep2, body = rest.partition("\nStackFrames\n")
        frames, _, body = body.partition("\n\n")
        if not sep2 or not all(re.fullmatch(r"\d+ \{.*\}", ln)
                               for ln in frames.splitlines()):
            raise ValueError("the stack frame tables are not laid out as "
                             "this reader expects")
        compiled_text = head + "\n\n" + body
    return re.sub(r" stack_frame_id=\d+", "", compiled_text)


def strip_kernel_locations():
    """Make jax serialize every Mosaic kernel without its locations."""
    from jax._src import tpu_custom_call
    from jax._src.lib.mlir import passmanager

    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def stripped(module, **kwargs):
        with module.context:
            passmanager.PassManager.parse(
                "builtin.module(strip-debuginfo)").run(module.operation)
        return serialize(module, **kwargs)

    tpu_custom_call._lower_mosaic_module_to_asm = stripped


def graphs(eng, one_chip):
    """``{name: traced}`` of the decode step and the 2048-token chunk."""
    import jax
    import jax.numpy as jnp

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    decode = on_chip(eng.decode_abstract_args())
    c = eng.cache
    chunk = [on_chip([p._data for p in eng._params]), i32(1, 2048), i32(),
             i32(), i32(eng.max_pages),
             *on_chip([c.k, c.v, c.k_scale, c.v_scale])]
    if c.window is not None or eng._counter_names:
        # the request's window row (``WindowPages.chunk_row``'s length)
        # and the counter array, as _run_chunk passes them
        w = c.window
        chunk += [i32(w.n_tail + min(w.ring, 2048 // eng.block_size) + 1),
                  on_chip(eng._graph_extras(None)[1])]
    return {"decode_pure": eng._decode_jit._jit.trace(*decode),
            "chunk_pure@2048": eng._prefill_jit._jit.trace(*chunk)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--config", action="append",
                    help="a serving configuration (default: both)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    os.chdir(args.repo)
    os.makedirs(args.out, exist_ok=True)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    strip_kernel_locations()

    from benchmarks.runners import common, serve_mimo_v2
    from paddle_tpu.inference.serving import LLMEngine

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    builders = {
        "mistral7b-serve": (common.model_sizes, common.build_model),
        "mimo-v2-flash-serve": (serve_mimo_v2.model_sizes,
                                serve_mimo_v2.build_model)}
    real_backend = jax.default_backend
    hashes = {}
    for name in args.config or builders:
        sizes, build = builders[name]
        with open(f"benchmarks/configs/{name}.json") as f:
            config = json.load(f)
        net = build(sizes(config), 1, config.get("dtype", "bfloat16"))
        net.eval()
        jax.default_backend = lambda: "tpu"
        try:
            eng = LLMEngine(net, capture_logits=True, **config["engine"])
            eng._build_jits()
            for graph, traced in graphs(eng, one_chip).items():
                lowered = traced.lower()
                texts = {"lowered": lowered.as_text()}
                if args.compile:
                    texts["compiled"] = without_locations(
                        lowered.compile().as_text())
                for kind, text in texts.items():
                    key = f"{name} {graph} {kind}"
                    hashes[key] = hashlib.sha256(text.encode()).hexdigest()
                    path = os.path.join(
                        args.out, key.replace(" ", ".").replace("@", "_"))
                    with open(path + ".txt", "w") as f:
                        f.write(text)
                    print(f"{hashes[key]}  {len(text):>9} bytes  {key}",
                          flush=True)
        finally:
            jax.default_backend = real_backend
        del eng, net
    with open(os.path.join(args.out, "sha256.json"), "w") as f:
        json.dump(hashes, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
