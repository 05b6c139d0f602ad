"""Device management.

TPU-native replacement for the reference's Place/DeviceContext machinery
(paddle/phi/common/place.h, paddle/phi/backends/device_manager.h:134). On JAX
there is no per-op stream plumbing: a "device" is a ``jax.Device`` and placement
is expressed via shardings; this module keeps the ``paddle.set_device``/
``get_device`` UX and resolves default placement for new tensors.
"""

from __future__ import annotations

import functools

import jax

_current_device_str: str | None = None


@functools.lru_cache(maxsize=None)
def _platform_devices(platform: str):
    try:
        return tuple(jax.devices(platform))
    except RuntimeError:
        return ()


def _default_platform() -> str:
    return jax.default_backend()


def set_device(device: str):
    """paddle.set_device analog. Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'."""
    global _current_device_str
    name = device.lower()
    plat, _, idx = name.partition(":")
    if plat == "tpu":
        plat = jax.default_backend() if jax.default_backend() != "cpu" else "tpu"
    if plat == "gpu":
        plat = "cuda"
    devs = _platform_devices(plat)
    if not devs:
        # Accept the accelerator alias even when running on the CPU backend
        # (CI / virtual-device tests).
        devs = _platform_devices(_default_platform())
    if not devs:
        raise RuntimeError(f"no devices for '{device}'")
    i = int(idx) if idx else 0
    _current_device_str = name
    jax.config.update("jax_default_device", devs[min(i, len(devs) - 1)])
    return devs[min(i, len(devs) - 1)]


def get_device() -> str:
    if _current_device_str is not None:
        return _current_device_str
    backend = jax.default_backend()
    if backend == "cpu":
        return "cpu"
    return f"{backend}:0"


def get_default_device() -> jax.Device:
    d = jax.config.jax_default_device
    return d if d is not None else jax.devices()[0]


def device_count(platform: str | None = None) -> int:
    return len(jax.devices(platform)) if platform else len(jax.devices())


def is_compiled_with_cuda() -> bool:  # API parity; always False on TPU builds
    return False


def is_compiled_with_xpu() -> bool:
    return False


class _Place:
    """Reference Place classes (paddle/phi/common/place.h) kept as tags;
    under XLA, placement is a sharding/device attribute, not an allocator
    choice. Tensors constructed with any Place land on the default device;
    CPUPlace additionally pins host-side numpy semantics in io paths."""

    _kind = "undefined"

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self._kind}:{self.device_id})" \
            if self._kind != "cpu" else "Place(cpu)"

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == getattr(other, "device_id", 0))

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(_Place):
    _kind = "cpu"


class CUDAPlace(_Place):
    """Accepted for API parity; resolves to the accelerator (TPU) device."""

    _kind = "gpu"


class CUDAPinnedPlace(_Place):
    _kind = "gpu_pinned"


class TPUPlace(_Place):
    _kind = "tpu"
