"""ctypes binding for the native host runtime (native/tmhash.cpp).

Builds the shared library on demand with g++ (the environment's native
toolchain; no pybind11) into the repo's native/ dir, caching the .so next
to its source.  The .so is git-ignored, so a checkout never carries one:
it is rebuilt when absent or when the source's SHA-256 differs from the
one recorded beside it at build time (mtimes say nothing after a copy).
Every entry point degrades to None when the toolchain or library is
unavailable — callers fall back to hashlib paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

from tendermint_tpu.utils.log import get_logger

log = get_logger("nativelib")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_SRC = os.path.join(_NATIVE_DIR, "tmhash.cpp")
_SO = os.path.join(_NATIVE_DIR, "libtmhash.so")
_SO_SRC_HASH = _SO + ".src.sha256"     # hash of the source _SO was built from

# "built" | "reused" | "unavailable" once get() has run, else None
build_status: str | None = None


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _up_to_date(src_hash: str) -> bool:
    try:
        with open(_SO_SRC_HASH) as f:
            return os.path.exists(_SO) and f.read().strip() == src_hash
    except OSError:
        return False


def _build(src_hash: str) -> bool:
    try:
        r = subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-pthread", "-shared",
             "-o", _SO, _SRC],
            capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            log.warn("native build failed", err=r.stderr[-500:])
            return False
        with open(_SO_SRC_HASH, "w") as f:
            f.write(src_hash + "\n")
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warn("native build unavailable", err=str(e))
        return False


def get() -> ctypes.CDLL | None:
    """The loaded library, building it if needed; None when unavailable."""
    global _lib, _tried, build_status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        build_status = "unavailable"
        if not os.path.exists(_SRC):
            return None
        src_hash = _src_hash()
        reused = _up_to_date(src_hash)
        if not reused and not _build(src_hash):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.warn("native lib load failed", err=str(e))
            return None
        build_status = "reused" if reused else "built"
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.tm_leaf_hashes.argtypes = [u8p, ctypes.c_uint64,
                                       ctypes.c_uint64, u8p,
                                       ctypes.c_uint32]
        lib.tm_merkle_roots.argtypes = [u8p, ctypes.c_uint64,
                                        ctypes.c_uint64, ctypes.c_uint64,
                                        u8p, ctypes.c_uint32]
        _lib = lib
        return _lib


def _threads() -> int:
    return min(16, os.cpu_count() or 1)


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def leaf_hashes(msgs: np.ndarray) -> np.ndarray | None:
    """uint8[N, L] -> 0x00-prefixed sha256 digests uint8[N, 32]."""
    lib = get()
    if lib is None:
        return None
    msgs = np.ascontiguousarray(msgs, dtype=np.uint8)
    n, ln = msgs.shape
    out = np.empty((n, 32), dtype=np.uint8)
    lib.tm_leaf_hashes(_u8p(msgs), n, ln, _u8p(out), _threads())
    return out


def merkle_roots(leaves: np.ndarray) -> np.ndarray | None:
    """uint8[T, N, L] equal-shape trees -> roots uint8[T, 32]
    (reference-shaped (n+1)//2 split, domain-separated)."""
    lib = get()
    if lib is None:
        return None
    leaves = np.ascontiguousarray(leaves, dtype=np.uint8)
    t, n, ln = leaves.shape
    out = np.empty((t, 32), dtype=np.uint8)
    lib.tm_merkle_roots(_u8p(leaves), t, n, ln, _u8p(out), _threads())
    return out
