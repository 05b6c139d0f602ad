"""Eager Tensor.

TPU-native analog of the reference's eager Tensor
(paddle/fluid/pybind/eager.cc + paddle/phi/core/dense_tensor.h:37 +
paddle/fluid/eager/autograd_meta.h). A Tensor is a thin mutable handle over an
immutable ``jax.Array`` plus autograd metadata. Because jax arrays are
immutable, in-place ops (``add_`` …) rebind ``_data``; any GradNode holding the
old array stays valid — the reference needs TensorWrapper/version-counter
machinery (tensor_wrapper.h) for this, here it falls out of functional purity.

Op methods (``t.matmul``, ``t.sum`` …) are installed by the ops package at
import time (see ops/__init__.py), mirroring how the reference generates
``core.eager.ops`` methods from YAML.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from . import state
from .device import get_default_device


class Tensor:
    __slots__ = (
        "_data",
        "stop_gradient",
        "grad",
        "_node",
        "_out_idx",
        "name",
        "persistable",
        "_hooks",
        "_placement",  # optional distributed placement annotation
        "__weakref__",
        "__dict__",
    )

    _name_counter = 0

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True, name=None):
        if data is None:
            data = jnp.zeros((), dtypes.get_default_dtype())
        self._data = _to_jax(data, dtype)
        self.stop_gradient = stop_gradient
        self.grad = None
        self._node = None
        self._out_idx = 0
        self.name = name or f"tensor_{Tensor._bump()}"
        self.persistable = False
        self._hooks = []
        self._placement = None

    @classmethod
    def _bump(cls):
        cls._name_counter += 1
        return cls._name_counter

    @staticmethod
    def _wrap(arr) -> "Tensor":
        t = Tensor.__new__(Tensor)
        t._data = arr
        t.stop_gradient = True
        t.grad = None
        t._node = None
        t._out_idx = 0
        t.name = f"tensor_{Tensor._bump()}"
        t.persistable = False
        t._hooks = []
        t._placement = None
        return t

    # ---- metadata ----
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    ndimension = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    def numel(self):
        return self.size

    @property
    def place(self):
        try:
            devs = self._data.devices()
            return str(next(iter(devs)))
        except Exception:
            return str(get_default_device())

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from .. import ops

        return ops.transpose(self, perm=list(range(self.ndim))[::-1])

    # ---- value access ----
    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *idx):
        a = np.asarray(self._data)
        return a.item(*idx) if idx else a.item()

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of a Tensor with more than one element is ambiguous"
            )
        return bool(self.item())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}{grad_info},\n"
            f"       {np.array2string(np.asarray(self._data), prefix='       ')})"
        )

    # ---- autograd ----
    def backward(self, grad_tensor=None, retain_graph=False):
        from .engine import run_backward

        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        if self._node is not None:
            self._node.output_hooks.setdefault(self._out_idx, []).append(hook)
        else:
            self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    if self._node is not None:
                        self._node.output_hooks[self._out_idx].remove(hook)
                    else:
                        self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def _accumulate_grad(self, g):
        if self.grad is None:
            self.grad = Tensor._wrap(g)
        else:
            self.grad._data = self.grad._data + g

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def detach(self):
        t = Tensor._wrap(self._data)
        t.stop_gradient = True
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from .. import ops

        return ops.assign(self)

    # ---- mutation (in-place rebind) ----
    def set_value(self, value):
        self._data = _to_jax(value, self.dtype)
        return self

    def copy_(self, other, blocking=True):
        data = other._data if isinstance(other, Tensor) else _to_jax(other, None)
        self._data = jnp.asarray(data, self.dtype)
        return self

    def _rebind(self, arr):
        self._data = arr
        return self

    # ---- conversion ----
    def astype(self, dtype):
        from .. import ops

        return ops.cast(self, dtype=dtypes.convert_dtype(dtype))

    cast = astype

    def to(self, *args, **kwargs):
        # paddle Tensor.to(device|dtype): device strings move data (cpu =
        # real host offload via device_put; gpu maps to the accelerator),
        # anything else is a dtype cast
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a.lower().split(":")[0] in (
                    "cpu", "tpu", "gpu", "cuda"):
                out = out._to_device(a.lower().split(":")[0])
                continue
            try:
                out = out.astype(dtypes.convert_dtype(a))
            except TypeError:
                continue
        return out

    def _copy_with_meta(self, arr):
        """Wrap a device-moved copy carrying this tensor's user-visible
        metadata: paddle preserves stop_gradient (a trainable tensor moved
        across devices must NOT come back silently detached), persistable
        and name across device copies."""
        t = Tensor._wrap(arr)
        t.stop_gradient = self.stop_gradient
        t.persistable = self.persistable
        t.name = self.name
        return t

    def _to_device(self, kind):
        import jax

        # a GSPMD-sharded array must NOT be collapsed onto one device (OOM
        # for large tables, sharding layout lost) — keep it where it is
        try:
            if len(self._data.sharding.device_set) > 1:
                import warnings

                warnings.warn(
                    f"Tensor.to({kind!r}) on a multi-device sharded array "
                    "is a no-op (moving it would gather onto one device); "
                    "use distributed.checkpoint for host snapshots",
                    stacklevel=4)
                return self
        except AttributeError:
            pass
        if kind == "cpu":
            return self._copy_with_meta(jax.device_put(
                self._data, jax.devices("cpu")[0]))
        # gpu/cuda naming maps onto the accelerator backend on this
        # framework (one XLA device namespace)
        try:
            dev = jax.devices()[0]
        except Exception:
            return self
        if dev.platform == "cpu" and kind in ("gpu", "cuda", "tpu"):
            import warnings

            warnings.warn(f"Tensor.to({kind!r}): no accelerator backend is "
                          "available; tensor stays on cpu", stacklevel=3)
            return self
        return self._copy_with_meta(jax.device_put(self._data, dev))

    def cpu(self):
        """Host offload: a copy of this tensor on the CPU device (paddle
        Tensor.cpu). Note the copy is committed to the host — move it back
        with ``.cuda()``/``.to('tpu')`` before mixing it into device
        compute."""
        return self._to_device("cpu")

    def cuda(self, *a, **k):
        return self._to_device("cuda")

    def pin_memory(self):
        return self

    # ---- python protocol / operators: installed by ops package ----
    def __getitem__(self, idx):
        from ..ops import indexing

        return indexing.getitem(self, idx)

    def __setitem__(self, idx, value):
        from ..ops import indexing

        indexing.setitem_(self, idx, value)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def _to_jax(data, dtype):
    dtype = dtypes.convert_dtype(dtype)
    if isinstance(data, Tensor):
        arr = data._data
        return jnp.asarray(arr, dtype) if dtype is not None and np.dtype(arr.dtype) != dtype else arr
    if isinstance(data, jax.Array):
        return jnp.asarray(data, dtype) if dtype is not None else data
    if isinstance(data, np.ndarray):
        if dtype is None and data.dtype == np.float64:
            dtype = dtypes.get_default_dtype()
        return jnp.asarray(data, dtype)
    if isinstance(data, (bool, int, float, complex)) or np.isscalar(data):
        if dtype is None:
            if isinstance(data, bool):
                dtype = np.dtype("bool")
            elif isinstance(data, int):
                dtype = dtypes.int64 if abs(int(data)) > 2**31 - 1 else dtypes.int32
            elif isinstance(data, float):
                dtype = dtypes.get_default_dtype()
        return jnp.asarray(data, dtype)
    if isinstance(data, (list, tuple)):
        arr = np.asarray(data)
        if dtype is None and arr.dtype == np.float64:
            dtype = dtypes.get_default_dtype()
        return jnp.asarray(arr, dtype)
    raise TypeError(f"cannot convert {type(data)} to Tensor")


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor analog (python/paddle/tensor/creation.py)."""
    t = Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
    return t


class Parameter(Tensor):
    """Trainable tensor (python/paddle/base/framework.py Parameter)."""

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v
