"""ctypes bindings for the native C++ sum-tree core.

Compiles ``_native/sum_tree.cc`` with g++ on first use (cached .so next to
the source, keyed by source mtime) and exposes ``NativeSumTree`` with the
exact interface of the numpy ``SumTree`` — the replay buffer takes either via
its ``sum_tree_cls`` parameter.  If no compiler is available the import still
succeeds, ``native_available()`` returns False and one
``native_core_unavailable`` event names the cause; callers fall back to numpy.

pybind11 is not in this image, so the boundary is a C ABI + ctypes — zero
copies (numpy arrays passed as raw pointers), no Python objects crossing.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from ape_x_dqn_tpu.utils.metrics import emit_event

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_HERE, "_native", "sum_tree.cc")
_SO = os.path.join(_HERE, "_native", "sum_tree.so")

_lib = None
_lib_err: str | None = None
_lock = threading.Lock()


def _build() -> None:
    # Compile to a private temp file, then atomically rename over the .so:
    # two processes racing on first use must never dlopen a half-written
    # artifact (rename is atomic within a directory on POSIX), and a failed
    # compile must not leave a bad .so that poisons every later run.
    tmp = f"{_SO}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        "-o", tmp, _SRC,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.rename(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global _lib, _lib_err
    with _lock:
        if _lib is not None or _lib_err is not None:
            return _lib
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                _build()
            lib = ctypes.CDLL(_SO)
            lib.st_create.restype = ctypes.c_void_p
            lib.st_create.argtypes = [ctypes.c_int64]
            lib.st_destroy.argtypes = [ctypes.c_void_p]
            lib.st_total.restype = ctypes.c_double
            lib.st_total.argtypes = [ctypes.c_void_p]
            lib.st_max.restype = ctypes.c_double
            lib.st_max.argtypes = [ctypes.c_void_p]
            lib.st_set.restype = ctypes.c_int32
            lib.st_set.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
            ]
            lib.st_get.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
            ]
            lib.st_sample.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
            ]
            _lib = lib
        except Exception as e:  # compiler missing, build failure, load failure
            _lib_err = f"{type(e).__name__}: {e}"
            # Said once, out loud: callers fall back to the numpy tree.
            emit_event("native_core_unavailable", core="sum_tree",
                       error=_lib_err, fallback="numpy SumTree")
        return _lib


def native_available() -> bool:
    return _load() is not None


def native_error() -> str | None:
    _load()
    return _lib_err


def _i64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


class NativeSumTree:
    """Drop-in replacement for ``sum_tree.SumTree`` backed by the C++ core."""

    def __init__(self, capacity: int):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native sum-tree unavailable: {_lib_err}")
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = int(capacity)
        self._lib = lib
        self._handle = lib.st_create(self.capacity)
        if not self._handle:
            raise MemoryError("st_create failed")

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.st_destroy(handle)
            self._handle = None

    @property
    def total(self) -> float:
        return float(self._lib.st_total(self._handle))

    def max_priority(self) -> float:
        return float(self._lib.st_max(self._handle))

    def get(self, indices: np.ndarray) -> np.ndarray:
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        out = np.empty(idx.shape[0], dtype=np.float64)
        self._lib.st_get(self._handle, idx.shape[0], _i64(idx), _f64(out))
        return out

    def set(self, indices: np.ndarray, priorities: np.ndarray) -> None:
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        pri = np.ascontiguousarray(priorities, dtype=np.float64)
        if idx.size == 0:
            return
        rc = self._lib.st_set(self._handle, idx.shape[0], _i64(idx), _f64(pri))
        if rc == -1:
            raise IndexError("sum-tree index out of range")
        if rc == -2:
            raise ValueError("priorities must be finite and non-negative")

    def sample(self, targets: np.ndarray) -> np.ndarray:
        tgt = np.ascontiguousarray(targets, dtype=np.float64)
        out = np.empty(tgt.shape[0], dtype=np.int64)
        self._lib.st_sample(self._handle, tgt.shape[0], _f64(tgt), _i64(out))
        return out

    def sample_stratified(self, batch_size: int, rng: np.random.Generator) -> np.ndarray:
        from ape_x_dqn_tpu.replay.sum_tree import stratified_targets

        return self.sample(stratified_targets(self.total, batch_size, rng))


def default_sum_tree_cls():
    """Native core when the toolchain allows, numpy otherwise."""
    if native_available():
        return NativeSumTree
    from ape_x_dqn_tpu.replay.sum_tree import SumTree

    return SumTree
