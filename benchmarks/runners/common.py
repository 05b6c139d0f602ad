"""What both runners share: the device check, the model from the seed,
compile counting, memory and the traced window."""

from __future__ import annotations

import glob
import os
import shutil
import sys

import jax
import numpy as np


def require_tpu(chips):
    """The device as JAX reports it, or exit: no CPU fallback."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: JAX found no usable backend: {e}", file=sys.stderr)
        raise SystemExit(2)
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX reports "
              f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(2)
    return devs


#: the keys of the source's config.json that size the model
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def model_sizes(config):
    """The model's sizes from a configuration file, whose top level holds
    the source's ``config.json`` keys; ``head_dim`` is worked out."""
    model = {k: config[k] for k in MODEL_KEYS}
    if config.get("sliding_window") is not None:
        raise ValueError("LlamaForCausalLM computes no sliding window")
    model["head_dim"] = model["hidden_size"] // model["num_attention_heads"]
    return model


def llama_config(model):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(**{k: model[k] for k in MODEL_KEYS})


def build_model(model, seed, dtype="bfloat16"):
    """``LlamaForCausalLM`` with every weight drawn on the device in ONE
    jitted call from the seed, in the dtype it is served or trained in
    (bfloat16; float32 is for the CPU rehearsal). The constructor runs
    under the trace with the framework's generator lifted to the traced
    key, so its initialisers become operations of that one program (the
    key is an argument: one executable for every seed); the arrays that
    come out replace the tracers the constructor left behind."""
    from paddle_tpu.core import rng
    from paddle_tpu.models import LlamaForCausalLM

    cfg = llama_config(model)
    gen = rng.default_generator()
    box = {}

    def make(key):
        gen.manual_seed(0)
        with gen.traced_base(key):
            net = LlamaForCausalLM(cfg)
            if dtype == "bfloat16":
                net.bfloat16()
        box["net"] = net
        return [t._data for t in _leaves(net)]

    arrays = jax.jit(make)(jax.random.key(np.uint32(seed & 0xFFFFFFFF)))
    net = box["net"]
    for t, a in zip(_leaves(net), arrays):
        t._data = a
    return net


def _leaves(net):
    """Parameters, then buffers (the rope tables): all that the
    constructor creates."""
    return net._unique_params() + [b for _, b in net.named_buffers()]


def named_weights(net):
    return {name: p._data for name, p in net.named_parameters()}


class CompileCounter:
    """Every executable JAX builds or loads in this process, eager
    operations included (``chip_smoke.CompileCounter``'s method)."""

    def __init__(self):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def place_cache():
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else the program's own fixed ``<checkout>/.jax_cache``. Small programs
    are kept too, so that a second run of a cell compiles nothing."""
    from paddle_tpu.jit.cache import place_compile_cache

    path = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(devs, chips):
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def start_trace(out_dir):
    """Start the profiler, writing under ``out_dir``. Python frames are
    not traced: they slow the host under measurement."""
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def stop_trace(out_dir):
    """Stop the profiler; the reduced trace, or None if none can be read."""
    from ..harness import trace_reduce

    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return (trace_reduce.reduce(trace_reduce.load_xplane(files[0]))
            if files else None)


def step_span():
    from ..harness.trace_reduce import STEP_SPAN

    return jax.profiler.TraceAnnotation(STEP_SPAN)
