"""Byte transports: TCP listener/dialer and an in-memory pair.

Reference: `p2p/listener.go` (TCP accept loop) — UPnP port mapping is out
of scope for this framework (modern deployments pin ports).  The
in-memory transport backs `make_connected_switches`-style tests
(reference `p2p/switch.go:495-543`) with real socketpairs so the full
framing/encryption path is exercised without TCP setup.
"""

from __future__ import annotations

import queue
import socket
import threading

from tendermint_tpu.p2p.types import NetAddress
from tendermint_tpu.utils.log import get_logger

log = get_logger("p2p")

_RECV_SIZE = 1 << 16


class ReadBuffer:
    """Exact reads out of pieces that come in sizes of their own: what a
    socket hands over, or the frames of an encrypted link.  `fill(need)`
    brings the next piece when `need` bytes are still missing, and
    raises when no more can come.  One reader at a time."""

    __slots__ = ("_fill", "_buf", "_pos")

    def __init__(self, fill):
        self._fill = fill
        self._buf = b""
        self._pos = 0          # _buf is read up to here

    def read_exact(self, n: int) -> bytes:
        buf, pos = self._buf, self._pos
        end = pos + n
        if end > len(buf):
            pieces = [buf[pos:]]
            need = end - len(buf)
            while need > 0:
                piece = self._fill(need)
                pieces.append(piece)
                need -= len(piece)
            buf = self._buf = b"".join(pieces)
            pos, end = 0, n
        self._pos = end
        return buf[pos:end]

    def take(self) -> bytes:
        """What is held and not yet read, handed over: the buffer is
        empty afterwards (another reader goes on from here)."""
        rest = self._buf[self._pos:]
        self._buf, self._pos = b"", 0
        return rest


class StreamConn:
    """Blocking duplex byte stream over a socket with exact-read semantics."""

    def __init__(self, sock: socket.socket, label: str = ""):
        self._sock = sock
        self.label = label
        self._wlock = threading.Lock()
        self._closed = threading.Event()
        self._reader = ReadBuffer(self._recv)

    def _recv(self, need: int) -> bytes:
        # whatever has arrived, up to _RECV_SIZE: a reader that has
        # fallen behind finds its next reads in the buffer and does not
        # go to the socket, and hand the GIL over, twice a frame
        chunk = self._sock.recv(max(need, _RECV_SIZE))
        if not chunk:
            raise ConnectionError("connection closed")
        return chunk

    def read_exact(self, n: int) -> bytes:
        return self._reader.read_exact(n)

    def socket_fd(self) -> int | None:
        """The descriptor of the live, plain socket under this conn, for
        a reader that takes the read side over from `read_exact` (the
        native receive loop, utils/nativelib.LinkReceiver); else None."""
        if self.closed or type(self._sock) is not socket.socket:
            return None
        return self._sock.fileno()

    def take_buffered(self) -> bytes:
        """The bytes read off the socket that `read_exact` has not given
        out: that reader has them before it reads the socket."""
        return self._reader.take()

    def write(self, data: bytes) -> None:
        with self._wlock:
            self._sock.sendall(data)

    def close(self) -> None:
        if not self._closed.is_set():
            self._closed.set()
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self._sock.close()

    @property
    def closed(self) -> bool:
        return self._closed.is_set()


def dial(addr: NetAddress, timeout: float = 3.0) -> StreamConn:
    sock = socket.create_connection((addr.host, addr.port), timeout=timeout)
    sock.settimeout(None)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return StreamConn(sock, label=str(addr))


def mem_pair() -> tuple[StreamConn, StreamConn]:
    """Connected in-process pair exercising the real byte path."""
    a, b = socket.socketpair()
    return StreamConn(a, "mem:a"), StreamConn(b, "mem:b")


class Listener:
    """TCP accept loop feeding a queue (reference `p2p/listener.go`)."""

    def __init__(self, laddr: NetAddress, backlog: int = 16):
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        host = laddr.host or "0.0.0.0"
        self._sock.bind((host, laddr.port))
        self._sock.listen(backlog)
        port = self._sock.getsockname()[1]
        self.addr = NetAddress("tcp", host, port)
        self._conns: queue.Queue = queue.Queue()
        self._stopped = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name="p2p-accept")
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                sock, peer = self._sock.accept()
            except OSError:
                return
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conns.put(StreamConn(sock, label=f"{peer[0]}:{peer[1]}"))

    def accept(self, timeout: float | None = None) -> StreamConn | None:
        try:
            return self._conns.get(timeout=timeout)
        except queue.Empty:
            return None

    def close(self) -> None:
        self._stopped.set()
        try:
            self._sock.close()
        except OSError:
            pass
