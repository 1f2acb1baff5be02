"""ctypes binding for the native host runtime (native/tmhash.cpp, the
Merkle engine; native/tmlink.cpp, the peer link's receive loop).

Builds the shared library on demand with g++ (the environment's native
toolchain; no pybind11) into the repo's native/ dir, caching the .so next
to its source.  The .so is git-ignored, so a checkout never carries one:
it is rebuilt when absent or when the sources' SHA-256 differs from the
one recorded beside it at build time (mtimes say nothing after a copy).
Every entry point degrades to None when the toolchain or library is
unavailable — callers fall back to hashlib paths and the Python loop.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import weakref

import numpy as np

from tendermint_tpu.utils.log import get_logger

log = get_logger("nativelib")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_SRCS = [os.path.join(_NATIVE_DIR, f) for f in ("tmhash.cpp", "tmlink.cpp")]
_HEADERS = [os.path.join(_NATIVE_DIR, "sha256.h")]
_SO = os.path.join(_NATIVE_DIR, "libtmhash.so")
_SO_SRC_HASH = _SO + ".src.sha256"     # hash of the source _SO was built from

# "built" | "reused" | "unavailable" once get() has run, else None
build_status: str | None = None


def _src_hash() -> str:
    h = hashlib.sha256()
    for path in _SRCS + _HEADERS:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _up_to_date(src_hash: str) -> bool:
    try:
        with open(_SO_SRC_HASH) as f:
            return os.path.exists(_SO) and f.read().strip() == src_hash
    except OSError:
        return False


def _build(src_hash: str) -> bool:
    # under a name of this process's own, then renamed: a node and its
    # source child may both build a fresh checkout's at once, and
    # neither may load, or record as built, what the other is writing
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-pthread", "-shared",
             "-o", tmp, *_SRCS],
            capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            log.warn("native build failed", err=r.stderr[-500:])
            return False
        os.replace(tmp, _SO)
        with open(tmp, "w") as f:
            f.write(src_hash + "\n")
        os.replace(tmp, _SO_SRC_HASH)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warn("native build unavailable", err=str(e))
        return False
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def get() -> ctypes.CDLL | None:
    """The loaded library, building it if needed; None when unavailable."""
    global _lib, _tried, build_status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        build_status = "unavailable"
        if not all(os.path.exists(p) for p in _SRCS + _HEADERS):
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
        _bind_link(lib)
        _lib = lib
        return _lib


class _LinkEvent(ctypes.Structure):
    """`TmLinkEvent` of native/tmlink.cpp."""
    _fields_ = [("code", ctypes.c_int32), ("ch", ctypes.c_int32),
                ("arg", ctypes.c_uint64),
                ("msg", ctypes.POINTER(ctypes.c_uint8)),
                ("msg_len", ctypes.c_uint64), ("bytes", ctypes.c_uint64)]


def _bind_link(lib: ctypes.CDLL) -> None:
    vp, u8, u64 = ctypes.c_void_p, ctypes.c_uint8, ctypes.c_uint64
    for name, argtypes, restype in (
            ("tm_link_new", [ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
                             u64, ctypes.c_double, ctypes.c_double], vp),
            ("tm_link_add_channel", [vp, u8, u64], None),
            ("tm_link_feed", [vp, ctypes.c_char_p, u64,
                              ctypes.c_char_p, u64], None),
            ("tm_link_recv", [vp, ctypes.POINTER(_LinkEvent)],
             ctypes.c_int32),
            ("tm_link_receiving", [vp, u8], u64),
            ("tm_link_seq", [vp], u64),
            ("tm_link_stop", [vp], None),
            ("tm_link_close", [vp], None),
            ("tm_link_free", [vp], None)):
        fn = getattr(lib, name)
        fn.argtypes, fn.restype = argtypes, restype


class LinkReceiver:
    """The receive state of one peer link in native/tmlink.cpp: the
    socket's read side, the receive direction's keys and `seq`, the
    token bucket, a reassembly buffer a channel.  `recv()` is one
    GIL-free call that returns a MESSAGE later, not a packet later.

    One thread calls `recv`, `message` and `close`; `receiving` and
    `stop` are for any thread, for as long as this object lives."""

    # what recv() returns (the enum of tmlink.cpp)
    MSG, PING, PROGRESS, STOPPED, CLOSED, OS_ERROR = 1, 2, 3, 4, 5, 6
    BAD_MAC, BAD_FRAME_LEN, BAD_PACKET_TYPE = 7, 8, 9
    UNKNOWN_CHANNEL, OVER_CAPACITY = 10, 11

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._h = handle
        self._ev = _LinkEvent()
        self._recv = lib.tm_link_recv
        self._ev_ref = ctypes.byref(self._ev)
        # not at exit: a daemon receive thread may still be in the call
        weakref.finalize(self, lib.tm_link_free, handle).atexit = False

    @classmethod
    def open(cls, fd: int, key: bytes, mac_key: bytes, seq: int,
             rate: float, burst: float,
             channels: dict[int, int]) -> "LinkReceiver | None":
        """Over socket `fd` (dup'ed: the caller's stays the caller's),
        `channels` id -> recv_message_capacity; None when the library
        or the descriptor cannot be had."""
        lib = get()
        if lib is None or len(key) != 32 or len(mac_key) != 32:
            return None
        handle = lib.tm_link_new(fd, key, mac_key, seq, rate, burst)
        if not handle:
            return None
        rx = cls(lib, handle)
        for ch_id, capacity in channels.items():
            lib.tm_link_add_channel(handle, ch_id, capacity)
        return rx

    def feed(self, sealed: bytes, opened: bytes) -> None:
        """What the Python readers held at the hand-over: bytes off the
        socket still sealed, and opened bytes no packet had taken."""
        self._lib.tm_link_feed(self._h, sealed, len(sealed),
                               opened, len(opened))

    def recv(self) -> int:
        """Blocks, off the GIL, until a message is whole, a PING came,
        64 KiB or 100 ms went by inside a message, or the link ended.
        `ch`, `arg` and `bytes` (charged to the limiter since the last
        return) say the rest."""
        return self._recv(self._h, self._ev_ref)

    @property
    def ch(self) -> int:
        return self._ev.ch

    @property
    def arg(self) -> int:
        return self._ev.arg

    @property
    def bytes(self) -> int:
        return self._ev.bytes

    def message(self) -> bytes:
        """The message of the MSG event just returned."""
        return ctypes.string_at(self._ev.msg, self._ev.msg_len)

    def receiving(self, ch_id: int) -> int:
        """Bytes arrived of the message now being received on `ch_id`."""
        if not 0 <= ch_id < 256:        # a c_uint8 would wrap it
            return 0
        return self._lib.tm_link_receiving(self._h, ch_id)

    @property
    def seq(self) -> int:
        return self._lib.tm_link_seq(self._h)

    def stop(self) -> None:
        self._lib.tm_link_stop(self._h)

    def close(self) -> None:
        """The receive routine has ended: the socket's dup goes."""
        self._lib.tm_link_close(self._h)


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
