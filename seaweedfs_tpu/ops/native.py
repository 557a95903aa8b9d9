"""ctypes binding to the native C++ core (native/rs_core.cpp).

Builds the shared library on first use (g++ via native/Makefile), and again
whenever the source, the Makefile or the host CPU differ from what the
library on disk was built from, and exposes the CPU-side GF(2^8) matrix
kernel and CRC32C. This is the build's
counterpart of the reference's native dependencies (klauspost/reedsolomon,
klauspost/crc32 — seaweedfs go.mod:44-45).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libseaweedtpu.so")
_STAMP_PATH = _SO_PATH + ".stamp"

_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[Exception] = None
_lock = threading.Lock()


class NativeUnavailable(RuntimeError):
    pass


def _build_stamp() -> str:
    """What the library on disk must have been built from: the source,
    the Makefile (its flags include -march=native) and this host's CPU.
    A tree copied to another machine carries the old binary along, and a
    -march=native build from a different CPU dies with SIGILL."""
    h = hashlib.sha256()
    for name in ("rs_core.cpp", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags", "Features")):
                    h.update(line.encode())
                    if line.startswith(("flags", "Features")):
                        break  # first core is enough
    except OSError:
        h.update(platform.processor().encode())
    return h.hexdigest()


def _ensure_built() -> None:
    """Rebuild unless the stamp beside the library names the present
    source on the present host. The flock serialises concurrent first
    uses (a test run spawning several servers) so nobody dlopens a
    half-written file."""
    want = _build_stamp()
    with open(_STAMP_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            with open(_STAMP_PATH) as f:
                have = f.read().strip()
        except OSError:
            have = ""
        if have == want and os.path.exists(_SO_PATH):
            return
        subprocess.run(["make", "-B", "-C", _NATIVE_DIR, "libseaweedtpu.so"],
                       check=True, capture_output=True, text=True)
        with open(_STAMP_PATH, "w") as f:
            f.write(want + "\n")


def _load() -> ctypes.CDLL:
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            # failed once (missing toolchain etc.) — don't re-spawn make on
            # every coder resolution
            raise NativeUnavailable(str(_load_error)) from _load_error
        try:
            _ensure_built()
        except (subprocess.CalledProcessError, OSError) as e:
            detail = getattr(e, "stderr", None) or str(e)
            _load_error = NativeUnavailable(
                f"cannot build native core: {detail}")
            raise _load_error from e
        lib = ctypes.CDLL(_SO_PATH)
        lib.gf_matrix_apply.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_size_t,
        ]
        lib.gf_matrix_apply.restype = None
        lib.crc32c_update.argtypes = [ctypes.c_uint32,
                                      ctypes.POINTER(ctypes.c_uint8),
                                      ctypes.c_size_t]
        lib.crc32c_update.restype = ctypes.c_uint32
        lib.crc32c_needle_value.argtypes = [ctypes.c_uint32]
        lib.crc32c_needle_value.restype = ctypes.c_uint32
        _lib = lib
        return lib


def available() -> bool:
    try:
        _load()
        return True
    except NativeUnavailable:
        return False


def gf_matrix_apply(matrix: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """matrix [R, C] uint8, inputs [C, n] uint8 -> [R, n] uint8."""
    lib = _load()
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    inputs = np.ascontiguousarray(inputs, dtype=np.uint8)
    rows, cols = matrix.shape
    assert inputs.shape[0] == cols, (matrix.shape, inputs.shape)
    n = inputs.shape[1]
    out = np.empty((rows, n), dtype=np.uint8)
    in_ptrs = (ctypes.c_void_p * cols)(
        *[inputs[c].ctypes.data for c in range(cols)])
    out_ptrs = (ctypes.c_void_p * rows)(
        *[out[r].ctypes.data for r in range(rows)])
    lib.gf_matrix_apply(
        matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        rows, cols, in_ptrs, out_ptrs, n)
    return out


def crc32c(data: bytes, crc: int = 0) -> int:
    lib = _load()
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    return lib.crc32c_update(crc, buf, len(data))


def crc32c_needle_value(crc: int) -> int:
    return _load().crc32c_needle_value(crc)
