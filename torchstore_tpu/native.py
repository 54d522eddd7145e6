"""ctypes bindings for the native data-path library (native/libtsnative.so).

This module builds the library from ``native/tsnative.cc`` itself: at first
use where it is missing, and again whenever it is older than the source
(the ``.so`` is git-ignored, so a fresh checkout always builds). The loaded
library must report ``VERSION``, the one ``tsnative.cc`` produces and these
bindings are written for; a build that fails, a library that does not load
and a version that differs all raise. Only a host WITHOUT the toolchain
(no ``make``/``g++``, no Makefile — e.g. a wheel install) runs on numpy,
with a warning: the store stays fully functional, just slower. Gated by
``StoreConfig.use_native`` / TORCHSTORE_TPU_USE_NATIVE.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
from typing import Optional

import numpy as np

from torchstore_tpu.config import default_config
from torchstore_tpu.logging import get_logger

logger = get_logger("torchstore_tpu.native")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtsnative.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "tsnative.cc")

# What native/tsnative.cc's ts_version() returns.
VERSION = 3

# Below this size the ctypes call overhead beats the threading win.
PARALLEL_THRESHOLD = 8 * 1024 * 1024

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _stale() -> bool:
    """True when the library is missing or older than its source."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return not os.path.exists(_LIB_PATH)


def _build() -> bool:
    """(Re)build a missing or stale library, under a cross-process file lock
    so N actor processes starting together don't race `make` (a loser could
    otherwise dlopen a half-written .so). Called from initialize()/volume
    startup, not from the transfer hot path. Returns False only where there
    is nothing to build with; a build that runs and fails raises."""
    if (
        not os.path.exists(os.path.join(_NATIVE_DIR, "Makefile"))
        or not os.access(_NATIVE_DIR, os.W_OK)
        or shutil.which("make") is None
        or shutil.which(os.environ.get("CXX", "g++")) is None
    ):
        return False
    import fcntl

    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        if _stale():  # not already rebuilt by another process
            try:
                # -B: staleness is decided above, not by make's own rule.
                subprocess.run(
                    ["make", "-B", "-C", _NATIVE_DIR],
                    check=True,
                    capture_output=True,
                    text=True,
                    timeout=120,
                )
            except subprocess.CalledProcessError as exc:
                raise RuntimeError(
                    f"building {_LIB_PATH} failed:\n{exc.stderr}"
                ) from exc
    return True


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if not default_config().use_native:
        return None
    if _stale() and not _build() and not os.path.exists(_LIB_PATH):
        logger.warning(
            "no native library and no toolchain to build it in %s; "
            "copies run on numpy",
            _NATIVE_DIR,
        )
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    lib.ts_version.restype = ctypes.c_uint32
    version = lib.ts_version()
    if version != VERSION:
        raise RuntimeError(
            f"{_LIB_PATH} is version {version}, these bindings need "
            f"{VERSION}: remove it so it is rebuilt from tsnative.cc"
        )
    lib.ts_parallel_memcpy.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
    ]
    lib.ts_parallel_memcpy.restype = None
    lib.ts_copy_2d.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
    ]
    lib.ts_copy_2d.restype = None
    lib.ts_read_fd.argtypes = [ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64]
    lib.ts_read_fd.restype = ctypes.c_int64
    lib.ts_write_fd.argtypes = [ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64]
    lib.ts_write_fd.restype = ctypes.c_int64
    # Multi-threaded page prefault (the provisioning subsystem's prewarm).
    lib.ts_prefault.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int]
    lib.ts_prefault.restype = ctypes.c_int
    # Batched scatter memcpy (the one-sided warm get's landing loop).
    lib.ts_copy_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_int,
    ]
    lib.ts_copy_batch.restype = None
    _lib = lib
    logger.info("native data path loaded (%s)", _LIB_PATH)
    return _lib


def available() -> bool:
    return get_lib() is not None


def _addr(arr: np.ndarray) -> int:
    return arr.__array_interface__["data"][0]


def fast_copy(dst: np.ndarray, src: np.ndarray) -> None:
    """np.copyto with a multi-threaded native path for large contiguous
    same-dtype copies (the store's hot memcpy). Shapes must match exactly:
    landing copies never broadcast — a silent broadcast would paper over a
    stale-metadata fetch (e.g. a location cache that missed a same-key
    shape change) with wrong data."""
    if dst.shape != src.shape:
        raise ValueError(
            f"landing-copy shape mismatch: dst {dst.shape} vs src {src.shape}"
        )
    lib = get_lib()
    if (
        lib is not None
        and dst.dtype == src.dtype
        and dst.shape == src.shape
        and dst.nbytes >= PARALLEL_THRESHOLD
        and dst.flags["C_CONTIGUOUS"]
        and src.flags["C_CONTIGUOUS"]
    ):
        lib.ts_parallel_memcpy(_addr(dst), _addr(src), dst.nbytes, 0)
        return
    np.copyto(dst, src)


def copy_into(dst: np.ndarray, src: np.ndarray) -> None:
    """Best copy path for a landing: contiguous native memcpy, then the
    native strided row-block path, then numpy. Never broadcasts (see
    fast_copy)."""
    if dst.shape != src.shape:
        raise ValueError(
            f"landing-copy shape mismatch: dst {dst.shape} vs src {src.shape}"
        )
    if (
        dst.flags["C_CONTIGUOUS"]
        and src.flags["C_CONTIGUOUS"]
        and dst.dtype == src.dtype
        and dst.shape == src.shape
    ):
        fast_copy(dst, src)
        return
    if fast_copy_2d(dst, src):
        return
    np.copyto(dst, src)


def copy_batch(
    dst_addrs: np.ndarray,
    src_addrs: np.ndarray,
    lens: np.ndarray,
    nthreads: int = 0,
) -> bool:
    """Batched scatter memcpy: one GIL-free native call lands ``len(lens)``
    independent (dst, src, len) copies, byte-balanced across threads. The
    caller OWNS eligibility: every pair must be same-size, both sides
    C-contiguous, and non-overlapping (the landing layer checks this).
    Arrays must be uint64 and C-contiguous. Returns False when the library
    is absent — the caller runs its per-pair Python loop."""
    lib = get_lib()
    if lib is None:
        return False
    n = len(lens)
    if n == 0:
        return True
    lib.ts_copy_batch(
        dst_addrs.ctypes.data, src_addrs.ctypes.data, lens.ctypes.data,
        n, nthreads,
    )
    return True


def prefault(addr: int, length: int, nthreads: int = 0) -> bool:
    """Multi-threaded prefault of ``length`` bytes at ``addr`` (one write per
    page, spread over ``nthreads``; 0 = auto). Returns True when the native
    path ran; False means the caller must fall back to touching pages itself
    (numpy-only host). Used by the provisioning subsystem to pre-allocate
    tmpfs segment pages off the first-sync critical path."""
    lib = get_lib()
    if lib is None:
        return False
    if length <= 0:
        return True
    lib.ts_prefault(addr, length, nthreads)
    return True


def fast_copy_2d(dst: np.ndarray, src: np.ndarray) -> bool:
    """Row-block strided copy (2D, same row length, contiguous rows).
    Returns False when the pattern doesn't apply (caller uses numpy)."""
    lib = get_lib()
    if (
        lib is None
        or dst.ndim != 2
        or src.shape != dst.shape
        or dst.dtype != src.dtype
        or dst.strides[1] != dst.itemsize
        or src.strides[1] != src.itemsize
        or dst.nbytes < PARALLEL_THRESHOLD
    ):
        return False
    lib.ts_copy_2d(
        _addr(dst), dst.strides[0], _addr(src), src.strides[0],
        dst.shape[1] * dst.itemsize, dst.shape[0], 0,
    )
    return True
