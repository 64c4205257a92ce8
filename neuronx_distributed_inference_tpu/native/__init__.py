"""Native (C++) host-runtime components, loaded via ctypes
(native-equiv of the reference's external C++ runtime pieces — SURVEY §2.10;
pybind11 is unavailable in this image, so the C ABI + ctypes is the binding).

The shared library is compiled on first use with the system toolchain into
``_build/`` under a name that carries the SHA-256 of its sources, so the
library that runs is always the one the checked-out sources describe — a
binary left behind by another commit is never picked up. Set
``NXDI_TPU_NATIVE=0`` to use the pure-Python implementations; with the
native path enabled, a build that fails raises :class:`NativeBuildError`
rather than degrading."""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("nxdi_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "_build")
_SOURCES = ["block_allocator.cpp"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class NativeBuildError(RuntimeError):
    """The native library was expected (``NXDI_TPU_NATIVE`` not 0) and could
    not be built."""


def native_enabled() -> bool:
    return os.environ.get("NXDI_TPU_NATIVE", "1") not in ("0", "false")


def library_path() -> str:
    """``_build/libnxdi_native_<sha256 of the sources>.so``."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"libnxdi_native_{h.hexdigest()[:16]}.so")


def _compile(lib_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           *(os.path.join(_DIR, s) for s in _SOURCES), "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired) as e:
        err = getattr(e, "stderr", b"") or b""
        raise NativeBuildError(
            f"native build failed ({e}); fix the toolchain or set "
            f"NXDI_TPU_NATIVE=0 for the Python allocator: "
            f"{err.decode(errors='replace')}") from e
    os.replace(tmp, lib_path)        # atomic: a reader never sees half a .so
    for stale in glob.glob(os.path.join(_BUILD_DIR, "libnxdi_native*.so")):
        if stale != lib_path:
            os.remove(stale)
    logger.info("native: built %s", lib_path)


def load_library() -> Optional[ctypes.CDLL]:
    """Build (when no library matches the sources) and dlopen the native
    library. None only when the native path is disabled."""
    global _lib
    if not native_enabled():
        return None
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib_path = library_path()
        if not os.path.exists(lib_path):
            _compile(lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.nxdi_alloc_create.restype = ctypes.c_void_p
        lib.nxdi_alloc_create.argtypes = [ctypes.c_int, ctypes.c_int,
                                          ctypes.c_int]
        lib.nxdi_alloc_destroy.argtypes = [ctypes.c_void_p]
        lib.nxdi_alloc_allocate.restype = ctypes.c_int
        lib.nxdi_alloc_allocate.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int)]
        lib.nxdi_alloc_extend.restype = ctypes.c_int
        lib.nxdi_alloc_extend.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.nxdi_alloc_free.restype = ctypes.c_int
        lib.nxdi_alloc_free.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_int),
                                        ctypes.c_int]
        lib.nxdi_alloc_invalidate.restype = ctypes.c_int
        lib.nxdi_alloc_invalidate.argtypes = [ctypes.c_void_p,
                                              ctypes.POINTER(ctypes.c_int),
                                              ctypes.c_int]
        lib.nxdi_alloc_num_free.restype = ctypes.c_int
        lib.nxdi_alloc_num_free.argtypes = [ctypes.c_void_p]
        lib.nxdi_alloc_probe.restype = ctypes.c_int
        lib.nxdi_alloc_probe.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
        _lib = lib
        return _lib
