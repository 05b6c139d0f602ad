"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas.

The public namespace mirrors ``paddle.*`` (reference: python/paddle/__init__.py)
so reference users can switch with an import swap. The compute path is jax
arrays + XLA; parallelism is device meshes + GSPMD/shard_map; fused kernels are
Pallas. See SURVEY.md at the repo root for the design mapping.
"""

from __future__ import annotations

__version__ = "0.1.0"

from .core import dtype as _dtype_mod
from .core.dtype import (  # noqa: F401
    bfloat16, bool, complex64, complex128, float16, float32, float64,
    float8_e4m3fn, float8_e5m2, int8, int16, int32, int64, uint8,
    get_default_dtype, set_default_dtype,
)
from .core.device import (  # noqa: F401
    set_device, get_device, device_count, is_compiled_with_cuda,
    is_compiled_with_xpu,
)
from .core.rng import seed, get_rng_state, set_rng_state  # noqa: F401
from .core.tensor import Tensor, to_tensor  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from . import device  # noqa: F401

from .ops import *  # noqa: F401,F403  (installs Tensor methods)
from . import ops as _ops_pkg

from .autograd import (  # noqa: F401
    no_grad, enable_grad, grad, set_grad_enabled, is_grad_enabled,
)
from . import autograd  # noqa: F401
from . import nn  # noqa: F401
from .nn.layer.layers import ParamAttr  # noqa: F401
from .core.tensor import Parameter  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from .regularizer import L1Decay, L2Decay  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import framework  # noqa: F401
from . import incubate  # noqa: F401
from . import jit  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401
from . import vision  # noqa: F401
from . import metric  # noqa: F401
from . import hapi  # noqa: F401
from . import fft  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import signal  # noqa: F401
from . import onnx  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import text  # noqa: F401
from .hapi import Model, callbacks  # noqa: F401
from .framework.io import CheckpointCorruptionError, load, save  # noqa: F401
from .core.exceptions import (  # noqa: F401
    TrainDivergenceError, TrainStallError,
)
from .io.streaming import (  # noqa: F401
    StreamCorruptionError, StreamReadError,
)


def in_dynamic_mode():
    return True


def in_dynamic_or_pir_mode():
    return True


def is_tensor(x):
    return isinstance(x, Tensor)


def disable_static(*a, **k):
    return None


def enable_static(*a, **k):
    return None


def disable_signal_handler():
    return None


# ---- long-tail top-level parity surface (reference python/paddle/__init__.py)
from .core.device import (  # noqa: F401,E402
    CPUPlace, CUDAPinnedPlace, CUDAPlace, TPUPlace,
)
from .hapi.flops import flops, summary  # noqa: F401,E402
from .core.rng import (  # noqa: F401,E402
    get_rng_state as get_cuda_rng_state,
    set_rng_state as set_cuda_rng_state,
)
from .distributed.parallel import DataParallel  # noqa: F401,E402
from .distributed.checkpoint.manager import (  # noqa: F401,E402
    CheckpointManager, PlanMismatchError)

#: paddle.dtype — callable canonicalizer (the reference exposes the VarType
#: class; under JAX a dtype IS its canonical string/np form)
dtype = _dtype_mod.convert_dtype


class LazyGuard:
    """Reference LazyGuard defers parameter memory until first forward
    (python/paddle/base/dygraph/base.py). JAX arrays are lazy buffers
    already — kept as a no-op context for API parity."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr prints through numpy; delegate (reference
    tensor/to_string.py)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not bool(sci_mode)
    _np.set_printoptions(**kw)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Standalone parameter factory (reference tensor/creation.py
    create_parameter -> LayerHelper.create_parameter)."""
    import numpy as _np

    from .core import dtype as _dt
    from .nn.initializer import Constant, XavierNormal

    dt = _dt.convert_dtype(dtype)
    init = default_initializer or (Constant(0.0) if is_bias
                                   else XavierNormal())
    data = init(tuple(int(s) for s in shape), dt)
    return Parameter(_np.asarray(data, dt))


def check_shape(shape, op_name="", expected_shape_type=(list, tuple),
                expected_element_type=(int,), expected_tensor_dtype=None):
    """Shape-argument validator (reference base/data_feeder.py:227). The
    reference skips it in dygraph mode; eager here is the only mode, so it
    validates types when called explicitly and is otherwise inert."""
    if isinstance(shape, Tensor):
        return
    if not isinstance(shape, expected_shape_type):
        raise TypeError(f"{op_name}: shape must be {expected_shape_type}, "
                        f"got {type(shape).__name__}")
    for item in shape:
        if not isinstance(item, expected_element_type + (Tensor,)):
            raise TypeError(f"{op_name}: shape element must be "
                            f"{expected_element_type}, got "
                            f"{type(item).__name__}")


def batch(reader, batch_size, drop_last=False):
    """Legacy minibatch reader decorator (reference base/reader ecosystem):
    wraps a sample generator into a batch generator."""

    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == int(batch_size):
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched
