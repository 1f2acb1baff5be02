"""The peer link's two receive loops, held equal.

`MConnection` reads a secret link that sits straight on a socket with
the native loop (native/tmlink.cpp: a message a GIL-free call) and every
other link with the Python loop (a packet a pass), which is the plain
reference here: every case runs on both, against the same expectation
written out by hand, so the same wire bytes give the same messages, the
same refusals with the same words, the same `seq`, the same bytes on the
meter.  The native cases skip only where there is no toolchain.
"""

import fcntl
import socket
import struct
import termios
import threading
import time

import pytest

from tendermint_tpu.p2p import ChannelDescriptor, MConnection, transport
from tendermint_tpu.p2p.connection import (FLAG_EOF, MAX_PACKET_PAYLOAD,
                                           PKT_MSG, PKT_PING, PKT_PONG)
from tendermint_tpu.p2p.fuzz import FuzzedConnection
from tendermint_tpu.p2p.secret import SecretConnection
from tendermint_tpu.types.keys import PrivKey
from tendermint_tpu.utils import nativelib, tracing
from tendermint_tpu.utils.metrics import REGISTRY

LOOPS = ["python",
         pytest.param("native", marks=pytest.mark.skipif(
             nativelib.get() is None,
             reason="native toolchain unavailable"))]
both_loops = pytest.mark.parametrize("loop", LOOPS)

CH_A, CH_B = 0x40, 0x41
CAPACITY = 1 << 20
BLOCK = 272_921             # a 1,000-tx block of the benchmark's third cell
MAX_FRAME = SecretConnection.MAX_FRAME
PING = bytes([PKT_PING])
PONG = bytes([PKT_PONG])


class PythonLoopSecret(SecretConnection):
    """The same link, of a class the native loop does not know: an
    `MConnection` over it runs the Python loop."""


def _descs(capacity=CAPACITY):
    return [ChannelDescriptor(id=CH_A, priority=5,
                              recv_message_capacity=capacity),
            ChannelDescriptor(id=CH_B, priority=1,
                              recv_message_capacity=capacity)]


def _payload(n: int, salt: int = 0) -> bytes:
    return bytes((i * 131 + salt * 7 + (i >> 8)) & 0xFF for i in range(n))


def packets(ch: int, msg: bytes, size: int = MAX_PACKET_PAYLOAD) -> list:
    """`msg` cut into MSG packets as the send routine cuts it."""
    out = []
    pos = 0
    while True:
        chunk = msg[pos:pos + size]
        pos += len(chunk)
        eof = pos >= len(msg)
        out.append(struct.pack(">BBBH", PKT_MSG, ch,
                               FLAG_EOF if eof else 0, len(chunk)) + chunk)
        if eof:
            return out


def metered(pkts) -> int:
    """What the limiter and the meter are charged for these packets."""
    return sum(len(p) for p in pkts if p[0] == PKT_MSG)


def _unread(sock: socket.socket) -> int:
    buf = bytearray(4)
    fcntl.ioctl(sock.fileno(), termios.FIONREAD, buf)
    return struct.unpack("i", buf)[0]


class Link:
    """A handshaken secret link over a socketpair.  The test holds the
    sending end (`tx`, and the raw socket under it, to put any bytes on
    the wire); the receiving end is an `MConnection` run by `loop`."""

    def __init__(self, loop: str, capacity=CAPACITY, recv_rate=0,
                 rx_wrap=None):
        self.loop = loop
        self.sock_tx, self.sock_rx = socket.socketpair()
        conns = (transport.StreamConn(self.sock_tx, "tx"),
                 transport.StreamConn(self.sock_rx, "rx"))
        self.rx_raw = conns[1] if rx_wrap is None else rx_wrap(conns[1])
        classes = (SecretConnection,
                   SecretConnection if loop == "native"
                   else PythonLoopSecret)
        ends, errs = {}, []

        def shake(i, conn):
            try:
                ends[i] = classes[i](conn, PrivKey.generate())
            except Exception as e:      # reported by the assertion below
                errs.append(e)
        threads = [threading.Thread(target=shake, args=(0, conns[0])),
                   threading.Thread(target=shake, args=(1, self.rx_raw))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        assert not errs and len(ends) == 2, errs
        self.tx, self.rx = ends[0], ends[1]
        self.got: list[tuple[int, bytes]] = []
        self.errors: list[Exception] = []
        self._cv = threading.Condition()
        self.mconn = MConnection(self.rx, _descs(capacity), self._on_receive,
                                 on_error=self._on_error, send_rate=0,
                                 recv_rate=recv_rate, flush_throttle=0.01)

    def _on_receive(self, ch_id, msg):
        with self._cv:
            self.got.append((ch_id, msg))
            self._cv.notify_all()

    def _on_error(self, exc):
        with self._cv:
            self.errors.append(exc)
            self._cv.notify_all()

    def start(self):
        self.mconn.start()
        assert (self.mconn._rx is not None) == (self.loop == "native")
        return self

    def seal(self, plaintext: bytes) -> bytes:
        """One frame as it goes on the wire; the sender's `seq` moves."""
        sealed = self.tx._send.seal(plaintext)
        return struct.pack(">I", len(sealed)) + sealed

    def put(self, wire: bytes, piece: int | None = None):
        """Raw bytes on the wire; with `piece`, that many at a time, the
        next only when the receiver has taken the last off its socket."""
        if piece is None:
            self.sock_tx.sendall(wire)
            return
        for pos in range(0, len(wire), piece):
            self.sock_tx.sendall(wire[pos:pos + piece])
            deadline = time.monotonic() + 10
            while _unread(self.sock_rx) and time.monotonic() < deadline:
                time.sleep(0)

    def wait(self, n_msgs=0, n_errors=0, timeout=20.0):
        with self._cv:
            ok = self._cv.wait_for(
                lambda: len(self.got) >= n_msgs
                and len(self.errors) >= n_errors, timeout)
        assert ok, (len(self.got), self.errors)

    def read_packet(self, timeout=5.0) -> bytes:
        """The next frame the receiving end's send routine wrote, opened
        with the sender's receive direction."""
        self.sock_tx.settimeout(timeout)
        n = struct.unpack(">I", self.tx._conn.read_exact(4))[0]
        return self.tx._recv.open(self.tx._conn.read_exact(n))

    def close(self):
        self.mconn.stop()
        self.tx.close()
        for t in self.mconn._threads:
            t.join(timeout=5)
            assert not t.is_alive()


@pytest.fixture
def link():
    made = []

    def make(loop, **kw):
        made.append(Link(loop, **kw))
        return made[-1]
    yield make
    for ln in made:
        ln.close()


# -- the same wire bytes, the same messages ---------------------------------

@both_loops
@pytest.mark.parametrize("n", [0, 1, 1_023, 1_024, 1_025, 18_900, 65_536,
                               BLOCK])
def test_a_message_of_every_length_arrives_whole(link, loop, n):
    """Sent by a real send routine, a packet a frame; the meter reads
    what the packets hold, and nothing is left in the channel."""
    ln = link(loop).start()
    msg = _payload(n, n)
    sender = MConnection(ln.tx, _descs(), lambda ch, m: None, send_rate=0,
                         flush_throttle=0.01)
    sending = threading.Thread(target=sender._send_routine, daemon=True)
    sending.start()                 # its receive side stays the test's
    try:
        assert sender.send(CH_A, msg)
        ln.wait(n_msgs=1)
    finally:
        sender._stopped.set()
        sending.join(timeout=5)
    assert ln.got == [(CH_A, msg)]
    assert ln.mconn.recv_monitor.total == metered(packets(CH_A, msg))
    assert ln.mconn.receiving(CH_A) == 0 and not ln.errors


def _interleaved():
    """Two channels' messages cut small and dealt in turn, a PING and a
    PONG between one message's packets, frames cut where packets are
    not: two packets in a frame, a packet over two frames, an empty
    frame.  -> (frames' plaintexts, messages in the order they end,
    PINGs)."""
    a1, a2 = _payload(2_500, 1), _payload(0, 2)
    b1, b2 = _payload(1_025, 3), _payload(700, 4)
    pa1, pb1 = packets(CH_A, a1, 1_000), packets(CH_B, b1, 400)
    pa2, pb2 = packets(CH_A, a2), packets(CH_B, b2, 699)
    order = [pa1[0], pb1[0], PING, pa1[1], PONG, pb1[1], pb1[2], pa1[2],
             pa2[0], PING, pb2[0], pb2[1]]
    frames = [order[0] + order[1],              # two packets, one frame
              order[2], order[3][:7], b"", order[3][7:],   # one over two
              b"".join(order[4:9]), order[9] + order[10][:3],
              order[10][3:] + order[11]]
    return frames, [(CH_B, b1), (CH_A, a1), (CH_A, a2), (CH_B, b2)], \
        2, metered(order)


@both_loops
def test_two_channels_interleaved_with_pings_between_packets(link, loop):
    ln = link(loop).start()
    frames, want, pings, charged = _interleaved()
    ln.put(b"".join(ln.seal(f) for f in frames))
    ln.wait(n_msgs=len(want))
    assert ln.got == want
    assert [ln.read_packet() for _ in range(pings)] == [PONG] * pings
    assert ln.mconn.recv_monitor.total == charged and not ln.errors


@both_loops
def test_a_ping_inside_a_message_is_answered_before_the_message_ends(
        link, loop):
    """The PONG is owed at once: the loop says so when it reads the
    PING, not when the message it interrupts is whole."""
    ln = link(loop).start()
    msg = _payload(3_000)
    pkts = packets(CH_A, msg)
    ln.put(ln.seal(pkts[0]) + ln.seal(PING) + ln.seal(pkts[1]))
    assert ln.read_packet() == PONG
    deadline = time.monotonic() + 5
    while ln.mconn.receiving(CH_A) < 2_048 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert ln.got == [] and ln.mconn.receiving(CH_A) == 2_048
    ln.put(ln.seal(pkts[2]))
    ln.wait(n_msgs=1)
    assert ln.got == [(CH_A, msg)]


@both_loops
@pytest.mark.parametrize("piece", [1, 3, 4_096, 1 << 20])
def test_whatever_the_socket_hands_over_at_a_time(link, loop, piece):
    ln = link(loop).start()
    frames, want, _, charged = _interleaved()
    tail = _payload(5_000, 9)
    wire = b"".join(ln.seal(f) for f in frames) + \
        b"".join(ln.seal(p) for p in packets(CH_B, tail))
    ln.put(wire, piece)
    ln.wait(n_msgs=len(want) + 1)
    assert ln.got == want + [(CH_B, tail)]
    assert ln.mconn.recv_monitor.total == \
        charged + metered(packets(CH_B, tail))


@both_loops
def test_bytes_left_in_both_read_buffers_at_the_hand_over(link, loop):
    """What the handshake's reads left behind goes first: opened bytes
    of a frame half read, and sealed frames already off the socket."""
    ln = link(loop)
    first, second = _payload(1_500, 1), _payload(40_000, 2)
    p1, p2 = packets(CH_A, first), packets(CH_B, second)
    # one frame holds three bytes of something else, then the first
    # packet's beginning; the rest follows in frames of their own
    wire = ln.seal(b"abc" + p1[0][:600]) + ln.seal(p1[0][600:] + p1[1]) + \
        b"".join(ln.seal(p) for p in p2[:20])
    ln.put(wire)
    assert ln.rx.read_exact(3) == b"abc"
    assert len(ln.rx._reader._buf) - ln.rx._reader._pos == 600
    assert len(ln.rx._conn._reader._buf) > ln.rx._conn._reader._pos
    ln.start()
    ln.put(b"".join(ln.seal(p) for p in p2[20:]))
    ln.wait(n_msgs=2)
    assert ln.got == [(CH_A, first), (CH_B, second)]
    assert ln.mconn.recv_monitor.total == metered(p1 + p2)


@pytest.mark.parametrize("loops", [("python", "native"),
                                   ("native", "python"),
                                   ("native", "native")],
                         ids="-".join)
@pytest.mark.skipif(nativelib.get() is None,
                    reason="native toolchain unavailable")
def test_ends_on_either_loop_exchange_a_block_both_ways_at_once(loops):
    """The wire is one: a Python-loop end and a native end talk, each
    receiving 272,921 bytes while it sends as many."""
    a, b = socket.socketpair()
    conns = (transport.StreamConn(a, "a"), transport.StreamConn(b, "b"))
    classes = [SecretConnection if lp == "native" else PythonLoopSecret
               for lp in loops]
    ends, threads = {}, []
    for i in (0, 1):
        threads.append(threading.Thread(
            target=lambda i=i: ends.__setitem__(
                i, classes[i](conns[i], PrivKey.generate()))))
        threads[-1].start()
    for t in threads:
        t.join(timeout=20)
    got, done, errors = {0: [], 1: []}, [threading.Event() for _ in loops], []

    def receiver(i):
        def on_receive(ch_id, msg):
            got[i].append((ch_id, msg))
            done[i].set()
        return on_receive
    mconns = [MConnection(ends[i], _descs(), receiver(i),
                          on_error=errors.append, send_rate=0, recv_rate=0,
                          flush_throttle=0.01) for i in (0, 1)]
    try:
        for i, m in enumerate(mconns):
            m.start()
            assert (m._rx is not None) == (loops[i] == "native")
        msgs = [_payload(BLOCK, 11), _payload(BLOCK, 12)]
        assert mconns[0].send(CH_A, msgs[0]) and mconns[1].send(CH_B, msgs[1])
        assert done[0].wait(30) and done[1].wait(30)
        assert got == {0: [(CH_B, msgs[1])], 1: [(CH_A, msgs[0])]}
        assert not errors
    finally:
        for m in mconns:
            m.stop()


# -- refusals: the same type, the same words, once --------------------------

class _Frames:
    """The frames of a link below `MConnection`, a packet's payload a
    frame, read by one loop or the other: what a refused frame does to
    `seq`, and whether the frame that was due still opens."""

    def __init__(self, ln: Link):
        self.ln = ln
        self.rx = None
        if ln.loop == "native":
            self.rx = ln.rx.native_receiver(0, 0, {CH_A: CAPACITY})
            assert self.rx is not None

    @property
    def seq(self) -> int:
        return self.rx.seq if self.rx is not None else self.ln.rx._recv.seq

    def next(self) -> bytes:
        """The payload of the one-packet message in the next frame."""
        if self.rx is None:
            pkt = self.ln.rx._read_frame()
            return pkt[5:]
        ev = self.rx.recv()
        if ev != self.rx.MSG:
            raise MConnection._native_error(ev, self.rx.ch, self.rx.arg)
        return self.rx.message()

    def close(self):
        if self.rx is not None:
            self.rx.close()


def _flip(frame: bytes, at: int) -> bytes:
    return frame[:at] + bytes([frame[at] ^ 0x20]) + frame[at + 1:]


@both_loops
@pytest.mark.parametrize("fault", ["ciphertext", "tag", "replayed",
                                   "swapped", "truncated"])
def test_a_refused_frame_leaves_seq_and_the_due_frame_still_opens(
        link, loop, fault):
    ln = link(loop)
    frames = _Frames(ln)
    try:
        one, two = (packets(CH_A, _payload(300, k))[0] for k in (1, 2))
        f0, f1 = ln.seal(one), ln.seal(two)
        if fault == "ciphertext":
            wire, opened_first = [_flip(f0, 4 + 100), f0, f1], 0
        elif fault == "tag":
            wire, opened_first = [_flip(f0, len(f0) - 1), f0, f1], 0
        elif fault == "replayed":
            wire, opened_first = [f0, f0, f1], 1
        elif fault == "swapped":
            wire, opened_first = [f1, f0, f1], 0
        else:       # a byte of the ciphertext gone, the length saying so
            cut = struct.pack(">I", len(f0) - 5) + f0[4:-17] + f0[-16:]
            wire, opened_first = [cut, f0, f1], 0
        ln.put(b"".join(wire))
        seq0 = frames.seq
        want = [one[5:], two[5:]]
        for _ in range(opened_first):
            assert frames.next() == want.pop(0)
        with pytest.raises(ValueError) as e:
            frames.next()
        assert str(e.value) == "secret connection: bad frame MAC"
        assert frames.seq == seq0 + opened_first
        assert [frames.next() for _ in want] == want
        assert frames.seq == seq0 + 2
    finally:
        frames.close()


def _raw_frame_of_length(n: int):
    return lambda ln: struct.pack(">I", n) + bytes(min(n, 64))


def _sealed(plaintext_of):
    return lambda ln: ln.seal(plaintext_of(ln))


REFUSALS = {
    # name -> (bytes on the wire, close the sender after them,
    #          exception type, its words); the channels hold 2,048 bytes
    "bad_mac": (lambda ln: _flip(ln.seal(packets(CH_A, b"x" * 50)[0]), 30),
                False, ValueError, "secret connection: bad frame MAC"),
    "frame_length_0": (_raw_frame_of_length(0), False, ValueError,
                       "secret connection: bad frame length 0"),
    "frame_length_15": (_raw_frame_of_length(15), False, ValueError,
                        "secret connection: bad frame length 15"),
    "frame_length_over_max": (
        _raw_frame_of_length(MAX_FRAME + 1), False, ValueError,
        f"secret connection: bad frame length {MAX_FRAME + 1}"),
    "unknown_packet_type": (_sealed(lambda ln: bytes([9]) + b"rest"),
                            False, ValueError, "unknown packet type 9"),
    "unknown_channel": (_sealed(lambda ln: packets(0x77, b"hello")[0]),
                        False, ValueError, "packet for unknown channel 119"),
    "one_byte_over_capacity": (
        lambda ln: b"".join(ln.seal(p)
                            for p in packets(CH_A, bytes(2_049))),
        False, ValueError, "message on channel 64 exceeds 2048 bytes"),
    "close_in_the_length": (lambda ln: b"\x00\x00", True, ConnectionError,
                            "connection closed"),
    "close_in_the_frame": (
        lambda ln: ln.seal(packets(CH_A, b"y" * 900)[0])[:500], True,
        ConnectionError, "connection closed"),
    "close_between_frames": (
        lambda ln: ln.seal(packets(CH_A, bytes(1_500))[0]), True,
        ConnectionError, "connection closed"),
}


@both_loops
@pytest.mark.parametrize("case", list(REFUSALS))
def test_a_refusal_has_the_same_type_and_words_on_both_loops(
        link, loop, case):
    wire_of, close_after, exc_type, words = REFUSALS[case]
    ln = link(loop, capacity=2_048).start()
    before = ln.seal(packets(CH_B, b"before")[0])
    ln.put(before + wire_of(ln))
    if close_after:
        ln.tx.close()
    ln.wait(n_msgs=1, n_errors=1)
    for t in ln.mconn._threads:
        t.join(timeout=5)
    assert ln.got == [(CH_B, b"before")]
    assert [(type(e), str(e)) for e in ln.errors] == [(exc_type, words)]
    assert ln.mconn._stopped.is_set() and ln.rx.closed


# -- the limiter, the clocks, the hand-offs ---------------------------------

@both_loops
def test_the_receive_limiter_holds(link, loop):
    rate = 400_000
    ln = link(loop, recv_rate=rate).start()
    msg = _payload(200_000)
    wire = b"".join(ln.seal(p) for p in packets(CH_A, msg))
    t0 = time.monotonic()
    writer = threading.Thread(target=ln.put, args=(wire,))
    writer.start()
    ln.wait(n_msgs=1)
    took = time.monotonic() - t0
    writer.join(timeout=5)
    charged = metered(packets(CH_A, msg))
    assert ln.got == [(CH_A, msg)]
    assert took >= (charged - rate / 5) / rate - 0.01, took
    assert ln.mconn.recv_monitor.total == charged


class _CountedMeter:
    """`recv_monitor.update` is called once a return to Python on the
    native loop, once a packet on the Python one."""

    def __init__(self, meter):
        self._meter = meter
        self.calls = 0
        self.total = 0

    def update(self, n):
        self.calls += 1
        self._meter.update(n)
        self.total = self._meter.total


@both_loops
def test_receiving_rises_through_a_block_and_is_zero_after_it(link, loop):
    ln = link(loop, recv_rate=500_000).start()
    msg = _payload(BLOCK)
    wire = b"".join(ln.seal(p) for p in packets(CH_A, msg))
    writer = threading.Thread(target=ln.put, args=(wire,))
    writer.start()
    seen = []
    deadline = time.monotonic() + 20
    while not ln.got and time.monotonic() < deadline:
        seen.append(ln.mconn.receiving(CH_A))
        time.sleep(0.002)
    writer.join(timeout=5)
    assert ln.got == [(CH_A, msg)]
    rising = [n for n in seen if n]
    assert rising == sorted(rising) and len(set(rising)) >= 5
    assert 0 < rising[0] < rising[-1] <= BLOCK
    assert ln.mconn.receiving(CH_A) == 0 and ln.mconn.receiving(CH_B) == 0
    assert ln.mconn.receiving(0x99) == 0 and ln.mconn.receiving(4_000) == 0


@both_loops
def test_python_is_entered_a_few_times_a_block_not_once_a_packet(link, loop):
    """The hand-off count, by construction: unthrottled, the whole block
    on the wire in one GIL-free `sendall`, every return to Python seen
    as its one call of the meter."""
    ln = link(loop)
    meter = ln.mconn.recv_monitor = _CountedMeter(ln.mconn.recv_monitor)
    ln.start()
    msg = _payload(BLOCK)
    pkts = packets(CH_A, msg)
    wire = b"".join(ln.seal(p) for p in pkts)
    writer = threading.Thread(target=ln.put, args=(wire,))
    writer.start()
    ln.wait(n_msgs=1)
    writer.join(timeout=5)
    assert ln.got == [(CH_A, msg)] and meter.total == metered(pkts)
    if loop == "native":
        assert meter.calls <= BLOCK // 65_536 + 2
    else:
        assert meter.calls == len(pkts) == 267


@pytest.mark.skipif(nativelib.get() is None,
                    reason="native toolchain unavailable")
@pytest.mark.parametrize("where", ["limiter", "recv"])
def test_stop_ends_a_native_call_within_a_fifth_of_a_second(link, where):
    """Asleep in the limiter for seconds, or blocked in `recv` on a
    silent socket: `stop()` ends either."""
    ln = link("native", recv_rate=1_000 if where == "limiter" else 0).start()
    if where == "limiter":
        # 5 KB against a bucket of 200 B at 1,000 B/s: a five second sleep
        ln.put(b"".join(ln.seal(p) for p in packets(CH_A, bytes(5_000))))
    time.sleep(0.3)
    recv = ln.mconn._threads[1]
    assert recv.is_alive() and not ln.got
    t0 = time.monotonic()
    ln.mconn.stop()
    recv.join(timeout=2)
    took = time.monotonic() - t0
    assert not recv.is_alive() and took < 0.2, took
    assert [type(e) for e in ln.errors] in ([], [ConnectionError])


# -- which loop runs, and how it says so -------------------------------------

class _NotASocket:
    """A socket behind a wrapper: a conn that is not a socket."""

    def __init__(self, sock):
        self._sock = sock

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _not_a_socket(conn):
    conn._sock = _NotASocket(conn._sock)
    return conn


def _counters():
    return (REGISTRY.link_msgs_native.value, REGISTRY.link_msgs_python.value)


def _instants(since: float):
    return [(s["name"], s["args"]) for s in tracing.RECORDER.since(since)
            if s["name"].startswith("link.recv.")]


@both_loops
def test_a_message_is_counted_and_recorded_under_its_loops_name(link, loop):
    ln = link(loop).start()
    t0, before = tracing.now_epoch(), _counters()
    ln.put(ln.seal(packets(CH_A, b"hello")[0]) + ln.seal(PING)
           + b"".join(ln.seal(p) for p in packets(CH_B, bytes(3_000))))
    ln.wait(n_msgs=2)
    native = int(loop == "native")
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == \
        (2 * native, 2 * (1 - native))
    assert _instants(t0) == [
        ("link.recv." + loop, {"ch": CH_A, "bytes": 5}),
        ("link.recv." + loop, {"ch": CH_B, "bytes": 3_000})]


@pytest.mark.parametrize("why", ["fuzzed", "not_a_socket", "no_library"])
def test_any_other_link_takes_the_python_loop(link, monkeypatch, why):
    """A fuzzed conn under the secret link, a conn that is not a socket,
    no library: today's loop, and `link_msgs_python` says so."""
    wrap = {"fuzzed": lambda c: FuzzedConnection(c, seed=1),
            "not_a_socket": _not_a_socket, "no_library": None}[why]
    if why == "no_library":
        monkeypatch.setattr(nativelib, "get", lambda: None)
    made = link("native", rx_wrap=wrap)
    made.loop = "python"            # what start() has to find
    t0, before = tracing.now_epoch(), _counters()
    made.start()
    made.put(made.seal(packets(CH_A, b"plain")[0]))
    made.wait(n_msgs=1)
    after = _counters()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
    assert _instants(t0) == [("link.recv.python", {"ch": CH_A, "bytes": 5})]
    assert made.got == [(CH_A, b"plain")]
