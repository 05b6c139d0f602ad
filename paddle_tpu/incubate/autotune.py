"""paddle.incubate.autotune — kernel/layout/dataloader tuning config.

Reference: python/paddle/incubate/autotune.py:24 (set_config with kernel /
layout / dataloader sections; the kernel section drives cuDNN exhaustive
algorithm search, phi/kernels/autotune/).

TPU-native mapping (each honest, not a silent no-op):

* kernel: XLA's autotuner always runs at compile time (it IS the
  exhaustive-search cache the reference builds at step time). Enabling the
  section additionally turns on jax's persistent compilation cache so the
  tuned executables survive process restarts — the durable analog of the
  reference's algorithm cache.
* layout: XLA chooses layouts during compilation; nothing to toggle. The
  setting is recorded and readable.
* dataloader: sets the default ``num_workers`` hint that ``paddle.io``'s
  DataLoader uses when constructed with ``num_workers=0`` and tuning is on.
"""

from __future__ import annotations

import json
import os

_CONFIG = {
    "kernel": {"enable": False, "tuning_range": [1, 10]},
    "layout": {"enable": False},
    "dataloader": {"enable": False, "num_workers": None},
}

__all__ = ["set_config", "get_config"]


def set_config(config=None):
    """reference autotune.py:24 — dict or path to a json file."""
    if config is None:
        for section in _CONFIG.values():
            section["enable"] = True
        _apply()
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    for key, val in config.items():
        if key not in _CONFIG:
            raise ValueError(f"unknown autotune section {key!r}; "
                             f"expected one of {sorted(_CONFIG)}")
        _CONFIG[key].update(val)
    _apply()


def get_config():
    return {k: dict(v) for k, v in _CONFIG.items()}


def _apply():
    if _CONFIG["kernel"]["enable"]:
        from ..jit.cache import place_compile_cache

        place_compile_cache()


def tuned_num_workers():
    """DataLoader hint (None = tuning off or unset)."""
    if not _CONFIG["dataloader"]["enable"]:
        return None
    n = _CONFIG["dataloader"]["num_workers"]
    return n if n is not None else min(4, os.cpu_count() or 1)
