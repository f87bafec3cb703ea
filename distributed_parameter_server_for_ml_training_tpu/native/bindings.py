"""ctypes bindings for native/ps_core.cpp (builds on demand with make)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

_LIB = None
_LIB_LOCK = threading.Lock()

# Search order for libps_core.so: explicit override, the source checkout's
# native/ dir (built there on first use — the binary is not committed), or
# alongside this module (where installed images copy it — a pip-installed
# package has no ../../native).
_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))
_SO_CANDIDATES = [
    os.environ.get("DPS_NATIVE_LIB", ""),
    os.path.join(_NATIVE_DIR, "libps_core.so"),
    os.path.join(os.path.dirname(__file__), "libps_core.so"),
]


def _find_so() -> str | None:
    for p in _SO_CANDIDATES:
        if p and os.path.isfile(p):
            return p
    return None


def _build() -> bool:
    if not os.path.isfile(os.path.join(_NATIVE_DIR, "Makefile")):
        return False
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=120)
        return _find_so() is not None
    except (subprocess.SubprocessError, OSError) as e:
        # The library is built from source (no binary is committed), so a
        # failed build is why the native backend is unavailable: say so.
        detail = getattr(e, "stderr", b"") or b""
        print(f"native: `make -C {_NATIVE_DIR}` failed: {e}\n"
              f"{detail.decode(errors='replace')}", file=sys.stderr)
        return False


def load_library() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        if _find_so() is None and not _build():
            return None
        lib = ctypes.CDLL(_find_so())

        u16p = ctypes.POINTER(ctypes.c_uint16)
        f32p = ctypes.POINTER(ctypes.c_float)
        i64 = ctypes.c_int64

        lib.dps_fp32_to_fp16.argtypes = [f32p, u16p, i64]
        lib.dps_fp16_to_fp32.argtypes = [u16p, f32p, i64]
        lib.dps_fp32_to_bf16.argtypes = [f32p, u16p, i64]
        lib.dps_bf16_to_fp32.argtypes = [u16p, f32p, i64]
        lib.dps_store_create.argtypes = [i64, f32p, ctypes.c_float]
        lib.dps_store_create.restype = ctypes.c_void_p
        lib.dps_store_destroy.argtypes = [ctypes.c_void_p]
        lib.dps_store_step.argtypes = [ctypes.c_void_p]
        lib.dps_store_step.restype = i64
        lib.dps_store_rejected.argtypes = [ctypes.c_void_p]
        lib.dps_store_rejected.restype = i64
        lib.dps_store_fetch.argtypes = [ctypes.c_void_p, f32p]
        lib.dps_store_fetch.restype = i64
        lib.dps_store_load.argtypes = [ctypes.c_void_p, f32p, i64]
        lib.dps_store_push_fp16.argtypes = [ctypes.c_void_p, u16p, i64, i64]
        lib.dps_store_push_fp16.restype = i64
        lib.dps_store_push_fp32.argtypes = [ctypes.c_void_p, f32p, i64, i64]
        lib.dps_store_push_fp32.restype = i64
        i64p = ctypes.POINTER(i64)
        i8p = ctypes.POINTER(ctypes.c_int8)
        lib.dps_store_push_int8.argtypes = [
            ctypes.c_void_p, i8p, f32p, i64p, i64, i64, i64]
        lib.dps_store_push_int8.restype = i64
        lib.dps_store_stash_fp16.argtypes = [ctypes.c_void_p, i64, u16p]
        lib.dps_store_stash_fp32.argtypes = [ctypes.c_void_p, i64, f32p]
        lib.dps_store_stash_int8.argtypes = [
            ctypes.c_void_p, i64, i8p, f32p, i64p, i64]
        lib.dps_store_apply_mean.argtypes = [ctypes.c_void_p, i64p, i64]
        lib.dps_store_apply_mean.restype = i64
        lib.dps_store_free_slot.argtypes = [ctypes.c_void_p, i64]
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return load_library() is not None


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _u16p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16))


def _i8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int8))


def fp32_to_fp16(src: np.ndarray) -> np.ndarray:
    """Multithreaded fp32->fp16 cast (worker.py:264-268's compression, in
    C++). Falls back to numpy when the library is absent."""
    lib = load_library()
    src = np.ascontiguousarray(src, np.float32)
    if lib is None:
        return src.astype(np.float16)
    out = np.empty(src.shape, np.uint16)
    lib.dps_fp32_to_fp16(_f32p(src.reshape(-1)), _u16p(out.reshape(-1)),
                         src.size)
    return out.view(np.float16)


def fp16_to_fp32(src: np.ndarray) -> np.ndarray:
    lib = load_library()
    src = np.ascontiguousarray(src)
    if src.dtype != np.float16:
        raise TypeError(src.dtype)
    if lib is None:
        return src.astype(np.float32)
    out = np.empty(src.shape, np.float32)
    lib.dps_fp16_to_fp32(_u16p(src.view(np.uint16).reshape(-1)),
                         _f32p(out.reshape(-1)), src.size)
    return out


def fp32_to_bf16(src: np.ndarray) -> np.ndarray:
    """Multithreaded fp32->bfloat16 cast (RNE, bit-for-bit ml_dtypes) for
    the fetch-side codec; ml_dtypes fallback when the library is absent."""
    import ml_dtypes

    lib = load_library()
    src = np.ascontiguousarray(src, np.float32)
    if lib is None:
        return src.astype(ml_dtypes.bfloat16)
    out = np.empty(src.shape, np.uint16)
    lib.dps_fp32_to_bf16(_f32p(src.reshape(-1)), _u16p(out.reshape(-1)),
                         src.size)
    return out.view(ml_dtypes.bfloat16)


def bf16_to_fp32(src: np.ndarray) -> np.ndarray:
    import ml_dtypes

    lib = load_library()
    src = np.ascontiguousarray(src)
    if src.dtype != ml_dtypes.bfloat16:
        raise TypeError(src.dtype)
    if lib is None:
        return src.astype(np.float32)
    out = np.empty(src.shape, np.float32)
    lib.dps_bf16_to_fp32(_u16p(src.view(np.uint16).reshape(-1)),
                         _f32p(out.reshape(-1)), src.size)
    return out
