"""ctypes bindings to the C++ data-pipeline core (csrc/prefetch.cpp).

Builds the shared library on demand with g++, cached next to the source
under a name keyed by the source's hash — a binary built from any other
``prefetch.cpp`` has another name and is never loaded (after a copy or a
checkout, mtimes say nothing). Every entry point degrades gracefully:
``available()`` is False when no toolchain exists and callers fall back to
the numpy path.

Why native: ctypes foreign calls release the GIL, so batch collation and
image normalization run concurrently with Python-side sample loading and
with the training loop — the role the reference fills with C++ DataFeed
(paddle/fluid/framework/data_feed.cc) and worker processes
(python/paddle/io/dataloader/worker.py).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "lib", "collate_samples", "normalize_image_batch",
           "Ring"]

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SRC = os.path.join(_CSRC, "prefetch.cpp")

_lib = None
_tried = False
_build_lock = threading.Lock()


def _lib_path():
    """Where the binary built from the CURRENT source lives (None when the
    source is absent)."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_CSRC, f"libpaddle_tpu_native.{digest}.so")


def _build(lib_path):
    # compile to a private temp file and atomically rename into place, so a
    # sibling launcher rank never dlopens a half-written .so
    tmp = lib_path + f".tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-pthread", "-shared",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    except (OSError, subprocess.SubprocessError):
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    # binaries of earlier sources are dead weight now
    for old in glob.glob(os.path.join(_CSRC, "libpaddle_tpu_native.*.so")):
        if old != lib_path:
            try:
                os.remove(old)
            except OSError:
                pass
    return True


def lib_ready():
    """The already-loaded CDLL or None — never builds (hot-path probe)."""
    return _lib


def warm(background=True):
    """Ensure the library is built/loaded. With background=True the g++ run
    happens on a daemon thread so callers (DataLoader init) never block; the
    hot path keeps using the numpy fallback until the library is ready."""
    if _lib is not None or _tried:
        return
    if background:
        threading.Thread(target=lib, daemon=True).start()
    else:
        lib()


def lib():
    """The loaded CDLL or None (builds on first call if needed)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _build_lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib_path = _lib_path()
        if lib_path is None:
            return None
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            L = ctypes.CDLL(lib_path)
        except OSError:
            return None
        L.pt_collate.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int]
        L.pt_img_normalize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int]
        L.pt_ring_new.restype = ctypes.c_void_p
        L.pt_ring_new.argtypes = [ctypes.c_int64]
        L.pt_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.c_int64]
        L.pt_ring_pop.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.c_int64]
        L.pt_ring_size.restype = ctypes.c_int64
        L.pt_ring_size.argtypes = [ctypes.c_void_p]
        L.pt_ring_close.argtypes = [ctypes.c_void_p]
        L.pt_ring_free.argtypes = [ctypes.c_void_p]
        _lib = L
    return _lib


def available():
    return lib() is not None


def collate_samples(samples, n_threads=4):
    """np.stack(samples) computed by the native parallel-memcpy collator.
    samples: list of same-shape/dtype contiguous ndarrays. Returns None if
    the native path can't apply (caller falls back to np.stack)."""
    L = lib()
    if L is None or not samples:
        return None
    first = samples[0]
    if not isinstance(first, np.ndarray):
        return None
    shape, dtype = first.shape, first.dtype
    if dtype == object:
        return None
    arrs = []
    for s in samples:
        if not isinstance(s, np.ndarray) or s.shape != shape \
                or s.dtype != dtype:
            return None
        arrs.append(np.ascontiguousarray(s))
    out = np.empty((len(arrs),) + shape, dtype)
    sample_bytes = first.nbytes
    # thread count scaled to the work: one thread per ~4MB of batch
    total = sample_bytes * len(arrs)
    n_threads = max(1, min(int(n_threads), total >> 22))
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
    L.pt_collate(ptrs, len(arrs), sample_bytes,
                 out.ctypes.data_as(ctypes.c_void_p), int(n_threads))
    return out


def normalize_image_batch(images, mean, std, n_threads=4):
    """HWC uint8 images -> NCHW float32 normalized, fused in C++.
    images: list of [H, W, C] uint8 arrays (same shape). Returns None if
    inapplicable."""
    L = lib()
    if L is None or not images:
        return None
    first = images[0]
    if not isinstance(first, np.ndarray) or first.dtype != np.uint8 \
            or first.ndim != 3:
        return None
    h, w, c = first.shape
    arrs = []
    for im in images:
        if not isinstance(im, np.ndarray) or im.shape != (h, w, c) \
                or im.dtype != np.uint8:
            return None
        arrs.append(np.ascontiguousarray(im))
    mean = np.asarray(mean, np.float32).reshape(-1)
    std = np.asarray(std, np.float32).reshape(-1)
    if mean.size != c or std.size != c:
        return None
    out = np.empty((len(arrs), c, h, w), np.float32)
    ptrs = (ctypes.c_void_p * len(arrs))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
    L.pt_img_normalize_batch(
        ptrs, out.ctypes.data_as(ctypes.c_void_p), len(arrs), h, w, c,
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), int(n_threads))
    return out


class Ring:
    """Blocking MPMC token ring (the prefetch queue between C-side-friendly
    producers and the consumer). Tokens are uint64 ids the Python side maps
    to objects."""

    def __init__(self, capacity):
        L = lib()
        if L is None:
            raise RuntimeError("native library unavailable")
        self._L = L
        self._h = L.pt_ring_new(int(capacity))

    def push(self, token, timeout_ms=-1):
        return self._L.pt_ring_push(self._h, int(token), int(timeout_ms))

    def pop(self, timeout_ms=-1):
        tok = ctypes.c_uint64()
        rc = self._L.pt_ring_pop(self._h, ctypes.byref(tok), int(timeout_ms))
        return rc, tok.value

    def __len__(self):
        return self._L.pt_ring_size(self._h)

    def close(self):
        self._L.pt_ring_close(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._L.pt_ring_close(self._h)
                self._L.pt_ring_free(self._h)
                self._h = None
        except Exception:
            pass
