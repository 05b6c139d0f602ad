"""paddle.jit — dygraph-to-static compilation.

Reference: python/paddle/jit/ — ``to_static`` (api.py:171), ``StaticFunction``
(dy2static/program_translator.py:324), ``CacheKey`` (:192), SOT bytecode
tracer (jit/sot/), ``PartialProgramLayer`` (dy2static/partial_program.py:151)
executing via PirInterpreter.

TPU-native redesign (SURVEY.md §3.3): there is no AST rewriting, no bytecode
hook, no ProgramDesc and no interpreter. The dygraph op layer is already
pure-JAX underneath, so "to static" = run the Python function once with
tracer-backed Tensors inside ``jax.jit`` — the whole model becomes ONE XLA
executable (forward), and its backward is the jit of the program-level
``jax.vjp``. The CacheKey maps to the jit cache key (input shapes/dtypes +
training mode). Python control flow is evaluated at trace time exactly like
the reference's AST path converts it — data-dependent control flow should use
``paddle.where``/masking (the reference converts to cond/while ops; a
``lax.cond`` bridge can be added per-case).
"""

from __future__ import annotations

import functools
import threading
import traceback
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from ..core import rng as rng_mod
from ..core import state
from ..core.engine import Edge, GradNode
from ..core.flags import flag_value, register_flag
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from ..profiler.utils import RecordEvent
from ..static.input_spec import InputSpec
from . import cache as cache_mod
from .cache import (BucketSpec, CountingJit, cache_stats,  # noqa: F401
                    get_shape_buckets, reset_cache_stats, set_shape_buckets)
from . import hlo_audit  # noqa: F401

__all__ = ["to_static", "not_to_static", "save", "load", "TranslatedLayer",
           "enable_to_static", "ignore_module", "cache_stats",
           "reset_cache_stats", "set_shape_buckets", "get_shape_buckets",
           "BucketSpec", "CountingJit", "hlo_audit"]

_TO_STATIC_ENABLED = True

# SOT-style graceful degradation (reference: jit/sot eval-frame fallback,
# paddle/fluid/pybind/eval_frame.c:411): when tracing hits data-dependent
# control flow the whole function cannot express, fall back to running the
# function eagerly (per-call, uncompiled) with a one-time actionable warning.
# FLAGS_to_static_fallback=0 turns the fallback into a hard framework error
# carrying the same diagnostic.
register_flag("to_static_fallback", True,
              help="fall back to eager when to_static tracing hits "
                   "data-dependent control flow (SOT semantics)")

_TRACER_LEAK_ERRORS = (
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerBoolConversionError,
    jax.errors.TracerIntegerConversionError,
    jax.errors.ConcretizationTypeError,
)


def _user_frame(exc):
    """The deepest traceback frame in user code — i.e. not in an installed
    library (site-packages/dist-packages) and not in paddle_tpu itself.
    REPL/exec frames (``<stdin>``, ``<string>``) count as user code."""
    import paddle_tpu

    pkg_dir = paddle_tpu.__file__.rsplit("/", 1)[0]
    best = None
    for frame in traceback.extract_tb(exc.__traceback__):
        f = frame.filename
        if "site-packages/" in f or "dist-packages/" in f:
            continue
        if f.startswith(pkg_dir):
            continue
        best = frame
    return best


def _tracer_leak_message(fn_name, exc):
    frame = _user_frame(exc)
    where = (f'  File "{frame.filename}", line {frame.lineno}, in '
             f"{frame.name}\n"
             + (f"    {frame.line}\n" if frame.line else "")
             if frame is not None else "  (offending line inside a library "
             "call — see the chained JAX traceback)\n")
    return (
        f"to_static could not compile `{fn_name}`: a Python branch or loop "
        "depends on a Tensor VALUE, which is unknown while tracing (the "
        "whole function is compiled ONCE by XLA).\n"
        f"{where}"
        "Fix one of these ways:\n"
        "  1. paddle.static.nn.cond(pred, true_fn, false_fn) — compiles "
        "BOTH branches, differentiable.\n"
        "  2. paddle.static.nn.while_loop(cond_fn, body_fn, loop_vars) — "
        "data-dependent trip count.\n"
        "  3. paddle.where(mask, a, b) — elementwise select, usually "
        "fastest on TPU.\n"
        "  4. mark the whole function @paddle.jit.not_to_static BEFORE "
        "to_static wraps it, to always run it eagerly.\n"
        f"(original: {type(exc).__name__})")


def enable_to_static(flag: bool):
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(flag)


def ignore_module(modules):
    pass


def not_to_static(fn=None):
    if fn is None:
        return not_to_static
    fn._not_to_static = True
    return fn


class _CacheEntry:
    __slots__ = ("fwd", "bwd", "out_tree", "n_params", "params", "buffers")

    def __init__(self, fwd, bwd, out_tree, params, buffers):
        self.fwd = fwd
        self.bwd = bwd
        self.out_tree = out_tree
        self.params = params
        self.buffers = buffers


class StaticFunction:
    """Compiled wrapper over a dygraph function/Layer method.

    Reference parity: program_cache-like behavior via per-shape cache;
    ``concrete_program``/``rollback`` style helpers exposed minimally.
    """

    def __init__(self, function, input_spec=None, instance=None,
                 shape_buckets=None, bucket_args=None, **kwargs):
        self._dygraph_function = function
        self._input_spec = input_spec
        self._instance = instance
        self._cache: dict = {}
        self._shape_buckets = BucketSpec.normalize(shape_buckets)
        # None = dominant-length auto rule; a set of positional indices /
        # kw names = pad exactly those inputs (the escape hatch when a
        # fixed-size field's width can coincide with a sequence length)
        self._bucket_args = (None if bucket_args is None
                             else frozenset(bucket_args))
        functools.update_wrapper(self, function)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction(self._dygraph_function, self._input_spec,
                               instance=instance,
                               shape_buckets=self._shape_buckets,
                               bucket_args=self._bucket_args)
        bound._cache = self._cache
        return bound

    # ---- cache key ----
    def _key(self, layer, args, kwargs, bucket_spec=None, lengths=None,
             selected=None):
        """``bucket_spec``/``lengths``/``selected``: shape-level bucketing
        — the key is computed from the shapes the compiled executable WOULD
        see, without materializing any padding (the eager-fallback lookup
        stays allocation-free). Must mirror the bucketize selection exactly:
        dominant-length rule when ``selected`` is None, otherwise per-leaf
        pad-up inside the explicitly selected top-level inputs."""

        def spec(x, active=True):
            if isinstance(x, Tensor):
                shape = tuple(x._data.shape)
                if bucket_spec is not None and active and x.stop_gradient:
                    if selected is None:
                        shape = cache_mod.bucketed_call_shape(
                            shape, bucket_spec, lengths)
                    else:
                        shape = cache_mod.bucketed_call_shape(
                            shape, bucket_spec,
                            cache_mod.infer_call_lengths([x._data],
                                                         bucket_spec))
                return ("T", shape, str(x.dtype), x.stop_gradient)
            if isinstance(x, (np.ndarray, jax.Array)):
                return ("A", tuple(x.shape), str(x.dtype))
            if isinstance(x, (list, tuple)):
                return tuple(spec(v, active) for v in x)
            if isinstance(x, dict):
                return tuple(sorted((k, spec(v, active))
                                    for k, v in x.items()))
            return ("P", x)

        args_spec = tuple(
            spec(a, selected is None or i in selected)
            for i, a in enumerate(args))
        kwargs_spec = tuple(sorted(
            (k, spec(v, selected is None or k in selected))
            for k, v in kwargs.items()))
        training = layer.training if isinstance(layer, Layer) else None
        return (id(layer) if layer is not None else 0, training,
                state.STATE.amp_level, args_spec, kwargs_spec)

    def _collect_layer(self):
        inst = self._instance
        if isinstance(inst, Layer):
            return inst
        if isinstance(self._dygraph_function, Layer):
            return self._dygraph_function
        return None

    def _call_eager(self, *args, **kwargs):
        if self._instance is not None:
            return self._dygraph_function(self._instance, *args, **kwargs)
        return self._dygraph_function(*args, **kwargs)

    @property
    def _stats_name(self):
        # qualified name so two layers' `forward` methods don't share a
        # cache_stats row
        return getattr(self, "__qualname__", None) or self.__name__

    def _call_eager_counted(self, *args, **kwargs):
        """Eager (uncompiled) execution of a fallen-back shape key: counted
        in cache_stats and marked as a profiler span so the 10-100x
        per-call cliff is visible, not silent."""
        span = cache_mod.record_eager_fallback(self._stats_name)
        try:
            return self._call_eager(*args, **kwargs)
        finally:
            span.end()

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            return self._call_eager(*args, **kwargs)
        # eager fallbacks must see the ORIGINAL inputs: padding only pays
        # inside a compiled executable, and would change user-visible shapes
        orig_args, orig_kwargs = args, kwargs
        spec = (self._shape_buckets if self._shape_buckets is not None
                else get_shape_buckets())
        selected = self._bucket_args
        lengths = (cache_mod.infer_tree_lengths((args, kwargs), spec)
                   if spec is not None and selected is None else None)
        layer = self._collect_layer()
        # key from shape-level bucketing: every length inside a bucket
        # shares one executable, and a known-eager key short-circuits
        # below WITHOUT ever materializing pad copies
        key = self._key(layer, args, kwargs, spec, lengths, selected)
        entry = self._cache.get(key)
        if entry == "eager":  # earlier fallback for this shape key
            return self._call_eager_counted(*orig_args, **orig_kwargs)
        if spec is not None:
            if selected is None:
                (args, kwargs), n_pad = cache_mod.bucketize_tree(
                    (args, kwargs), spec, lengths)
            else:
                n_pad = 0
                new_args = list(args)
                for i in range(len(new_args)):
                    if i in selected:
                        new_args[i], n = cache_mod.bucketize_tree(
                            new_args[i], spec, per_leaf=True)
                        n_pad += n
                args = tuple(new_args)
                kwargs = dict(kwargs)
                for k in list(kwargs):
                    if k in selected:
                        kwargs[k], n = cache_mod.bucketize_tree(
                            kwargs[k], spec, per_leaf=True)
                        n_pad += n
            cache_mod.record_bucket_pads(self._stats_name, n_pad)
        if entry is not None:
            cache_mod.record_hit(self._stats_name)

        # flatten dynamic (tensor) leaves out of args
        flat_args, arg_tree = jax.tree.flatten(
            (args, kwargs),
            is_leaf=lambda x: isinstance(x, Tensor))
        dyn_idx = [i for i, a in enumerate(flat_args)
                   if isinstance(a, (Tensor, jax.Array, np.ndarray))]
        dyn_arrays = [flat_args[i]._data if isinstance(flat_args[i], Tensor)
                      else jnp.asarray(flat_args[i]) for i in dyn_idx]
        arg_requires = [isinstance(flat_args[i], Tensor)
                        and not flat_args[i].stop_gradient for i in dyn_idx]

        if entry is None:
            try:
                with RecordEvent(f"jit::compile::{self.__name__}"):
                    entry = self._trace(layer, arg_tree, flat_args, dyn_idx)
                cache_mod.record_compile(
                    self._stats_name,
                    cache_mod.shape_signature(dyn_arrays))
            except _TRACER_LEAK_ERRORS as e:
                msg = _tracer_leak_message(self.__name__, e)
                if not flag_value("to_static_fallback", True):
                    raise RuntimeError(msg) from e
                warnings.warn(msg + "\nFalling back to EAGER execution for "
                              "this function (uncompiled; set "
                              "FLAGS_to_static_fallback=0 to make this an "
                              "error). Note: the function body partially "
                              "executed once during the failed trace — "
                              "non-idempotent Python side effects (appends, "
                              "counters) before the offending line ran "
                              "twice, and values stashed during the trace "
                              "are unusable tracers.", stacklevel=2)
                entry = "eager"
            self._cache[key] = entry

        if entry == "eager":
            return self._call_eager_counted(*orig_args, **orig_kwargs)

        params = entry.params
        key_arr = rng_mod.DEFAULT_GENERATOR.next_key()
        param_arrays = [p._data for p in params]
        out_flat = entry.fwd(param_arrays, dyn_arrays, key_arr)
        outs = jax.tree.unflatten(entry.out_tree, out_flat)

        requires_grad = state.grad_enabled() and (
            any(not p.stop_gradient for p in params) or any(arg_requires))
        node = None
        if requires_grad:
            edges = [Edge.from_tensor(p) for p in params]
            dyn_tensors = [flat_args[i] for i in dyn_idx]
            edges += [Edge.from_tensor(t) if isinstance(t, Tensor)
                      else Edge(stop=True) for t in dyn_tensors]
            out_avals = [(tuple(o.shape), o.dtype) for o in out_flat]

            bwd_fn = entry.bwd

            def node_bwd(primals, cts):
                p_arrays, d_arrays, k = primals
                grads_p, grads_d = bwd_fn(p_arrays, d_arrays, k, list(cts))
                return tuple(grads_p) + tuple(grads_d)

            node = GradNode(
                f"to_static_{self.__name__}", node_bwd,
                (param_arrays, dyn_arrays, key_arr), edges, out_avals, True)

        def wrap(arr, i):
            t = Tensor._wrap(arr)
            t.stop_gradient = not requires_grad
            if node is not None:
                t._node = node
                t._out_idx = i
            return t

        wrapped_flat = [wrap(a, i) for i, a in enumerate(out_flat)]
        return jax.tree.unflatten(entry.out_tree, wrapped_flat)

    # ---- tracing ----
    def _trace(self, layer, arg_tree, flat_args, dyn_idx):
        params = list()
        if layer is not None:
            seen = set()
            for _, p in layer.named_parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    params.append(p)
            buffers = [b for _, b in layer.named_buffers()]
        else:
            buffers = []
        fn = self._dygraph_function
        instance = self._instance
        rng_counter = rng_mod.DEFAULT_GENERATOR._counter

        def pure_fn(param_arrays, dyn_arrays, key):
            # pin the rng op-counter so every retrace folds in the same
            # sequence (randomness varies per call via the traced `key` arg);
            # only the int counter is touched — never rebuild keys in-trace
            gen = rng_mod.DEFAULT_GENERATOR
            saved_counter = gen._counter
            gen._counter = rng_counter
            old_param_data = [p._data for p in params]
            new_flat = list(flat_args)
            for i, arr in zip(dyn_idx, dyn_arrays):
                orig = flat_args[i]
                t = Tensor._wrap(arr)
                if isinstance(orig, Tensor):
                    t.stop_gradient = orig.stop_gradient
                new_flat[i] = t
            args2, kwargs2 = jax.tree.unflatten(arg_tree, new_flat)
            try:
                for p, arr in zip(params, param_arrays):
                    p._data = arr
                with state.trace_guard(), gen.traced_base(key):
                    if instance is not None:
                        out = fn(instance, *args2, **kwargs2)
                    else:
                        out = fn(*args2, **kwargs2)
            finally:
                for p, arr in zip(params, old_param_data):
                    p._data = arr
                gen._counter = saved_counter
            out_flat, out_tree = jax.tree.flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            arrays = [o._data if isinstance(o, Tensor) else jnp.asarray(o)
                      for o in out_flat]
            pure_fn._out_tree = out_tree
            return arrays

        fwd = jax.jit(pure_fn)

        def bwd(param_arrays, dyn_arrays, key, cts):
            _, vjp = jax.vjp(lambda ps, ds: pure_fn(ps, ds, key),
                             param_arrays, dyn_arrays)
            return vjp(cts)

        bwd_j = jax.jit(bwd)

        # trace once eagerly (abstract) to get out_tree
        dyn_arrays = [flat_args[i]._data if isinstance(flat_args[i], Tensor)
                      else jnp.asarray(flat_args[i]) for i in dyn_idx]
        jax.eval_shape(pure_fn, [p._data for p in params], dyn_arrays,
                       jax.random.key(0))
        out_tree = pure_fn._out_tree
        return _CacheEntry(fwd, bwd_j, out_tree, params, buffers)

    @property
    def concrete_program(self):
        return self._cache

    def rollback(self):
        return self._dygraph_function


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, shape_buckets=None, bucket_args=None, **kwargs):
    """Reference: python/paddle/jit/api.py:171.

    ``shape_buckets`` (extension): pad-up bucket boundaries applied to the
    inputs before the compile-cache lookup — ``[64, 128, 256]`` buckets axis
    1, ``{axis: boundaries}`` is explicit. Caps the compile count for
    variable-length streams at O(buckets); see paddle.jit.set_shape_buckets
    for the process-global form and paddle.jit.cache_stats() for telemetry.

    ``bucket_args``: which inputs to pad. Default (None) is the
    dominant-length rule — the first tensor carrying the bucketed axis
    defines the call's length, and only tensors matching it pad. Pass an
    iterable of positional indices / kw names when a fixed-size field's
    width can coincide with a sequence length (e.g. 13 dense features and
    seq_len 13), which would otherwise mis-pad that field on exactly that
    length.
    """

    def decorate(fn):
        if isinstance(fn, Layer):
            # wrap the layer's forward; calling the layer still works because
            # we return a layer-like callable
            if getattr(type(fn).forward, "_not_to_static", False):
                return fn
            sf = StaticFunction(type(fn).forward, input_spec, instance=fn,
                                shape_buckets=shape_buckets,
                                bucket_args=bucket_args)
            fn.forward = sf
            return fn
        if getattr(fn, "_not_to_static", False):
            return fn
        return StaticFunction(fn, input_spec, shape_buckets=shape_buckets,
                              bucket_args=bucket_args)

    if function is not None:
        return decorate(function)
    return decorate


# --------------------------------------------------------------------------
# jit.save / jit.load — serialized compiled programs via jax.export
# (replaces the reference's ProgramDesc+params format,
#  python/paddle/jit/translated_layer.py)
# --------------------------------------------------------------------------

def save(layer, path, input_spec=None, **configs):
    import os
    import pickle

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if isinstance(layer, StaticFunction):
        fn = layer
        layer_obj = fn._collect_layer()
    elif isinstance(layer, Layer):
        layer_obj = layer
        fn = None
    else:
        layer_obj = None
        fn = layer

    assert input_spec or layer_obj is not None, "input_spec required"
    specs = input_spec or []
    specs = [s if isinstance(s, InputSpec) else InputSpec.from_tensor(s)
             for s in specs]

    params = ([(n, p) for n, p in layer_obj.named_parameters()]
              if layer_obj else [])
    buffers = ([(n, b) for n, b in layer_obj.named_buffers()]
               if layer_obj else [])
    consts = params + buffers
    const_arrays = [np.asarray(p._data) for _, p in consts]

    was_training = layer_obj.training if layer_obj else False
    if layer_obj:
        layer_obj.eval()

    def infer_fn(const_arrays_, *input_arrays):
        old = [p._data for _, p in consts]
        try:
            for (_, p), arr in zip(consts, const_arrays_):
                p._data = arr
            tensors = [Tensor._wrap(a) for a in input_arrays]
            with state.trace_guard():
                if layer_obj is not None:
                    out = layer_obj(*tensors)
                else:
                    out = fn(*tensors)
        finally:
            for (_, p), arr in zip(consts, old):
                p._data = arr
        out_flat, tree = jax.tree.flatten(
            out, is_leaf=lambda x: isinstance(x, Tensor))
        infer_fn._tree = tree
        return [o._data if isinstance(o, Tensor) else jnp.asarray(o)
                for o in out_flat]

    # dynamic (None/-1) dims become symbolic so the loaded program accepts
    # any size there (reference InputSpec semantics)
    scope = jax.export.SymbolicScope()
    example_inputs = []
    sym_counter = [0]

    def dim_str(s):
        if s == -1:
            sym_counter[0] += 1
            return f"_d{sym_counter[0]}"
        return str(s)

    for sp in specs:
        if any(s == -1 for s in sp.shape):
            shape = jax.export.symbolic_shape(
                ",".join(dim_str(s) for s in sp.shape), scope=scope)
        else:
            shape = tuple(sp.shape)
        example_inputs.append(jax.ShapeDtypeStruct(shape, sp.dtype))
    exported = jax.export.export(jax.jit(infer_fn))(
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in const_arrays],
        *example_inputs)
    payload = {
        "stablehlo": exported.serialize(),
        "consts": const_arrays,
        "const_names": [n for n, _ in consts],
        "specs": [(sp.shape, sp.dtype.name, sp.name) for sp in specs],
    }
    base = path
    with open(base + ".pdmodel", "wb") as f:
        pickle.dump(payload, f, protocol=4)
    from ..framework.io import save as fsave

    if layer_obj is not None:
        fsave(layer_obj.state_dict(), base + ".pdiparams")
        if was_training:
            layer_obj.train()


class TranslatedLayer(Layer):
    """Loaded compiled program (reference: translated_layer.py TranslatedLayer)."""

    def __init__(self, exported, consts, specs):
        super().__init__()
        self._exported = exported
        self._consts = consts
        self._specs = specs

    def forward(self, *inputs):
        arrays = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        outs = self._exported.call(self._consts, *arrays)
        outs = [Tensor._wrap(o) for o in outs]
        return outs[0] if len(outs) == 1 else outs


def load(path, **configs):
    import pickle

    with open(path + ".pdmodel", "rb") as f:
        payload = pickle.load(f)
    exported = jax.export.deserialize(payload["stablehlo"])
    consts = [jnp.asarray(a) for a in payload["consts"]]
    return TranslatedLayer(exported, consts, payload["specs"])


_SOT_VERBOSITY = {"code_level": 0, "verbosity": 0}


def set_code_level(level=100, also_to_stdout=False):
    """Reference jit/sot debug knob (python/paddle/jit/sot/utils/envs.py):
    controls how much translated code is dumped. The trace-based to_static
    here has no bytecode translation stage; the setting is recorded and
    honored by to_static's trace logging."""
    _SOT_VERBOSITY["code_level"] = int(level)


def set_verbosity(level=0, also_to_stdout=False):
    _SOT_VERBOSITY["verbosity"] = int(level)
