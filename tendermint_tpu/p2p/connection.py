"""MConnection: one multiplexed, rate-limited connection per peer.

Reference: `p2p/connection.go:66-695` — N priority channels over one
stream; the send routine picks the channel with the least
recentlySent/priority (weighted fair scheduling, `:341-395`); messages
are chunked into fixed-size packets with an EOF flag and reassembled per
channel on the receive side (`:397-483,677-694`); ping/pong keepalive;
token-bucket throttling at the configured send/recv rates (`:18-36`).

Wire framing (all big-endian):
    packet   := type(u8) body
    type 1   := MSG  body: channel(u8) flags(u8) len(u16) payload
    type 2   := PING (empty body)
    type 3   := PONG (empty body)
flags bit0 = EOF (last packet of the message).
"""

from __future__ import annotations

import os
import struct
import threading
import time
from collections import deque

from tendermint_tpu.p2p.types import ChannelDescriptor
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.metrics import REGISTRY
from tendermint_tpu.utils.nativelib import LinkReceiver

log = get_logger("p2p")

PKT_MSG, PKT_PING, PKT_PONG = 1, 2, 3
MAX_PACKET_PAYLOAD = 1024            # reference maxMsgPacketSize
FLAG_EOF = 0x01


class _RateLimiter:
    """Token bucket: blocks the caller to keep throughput <= rate B/s."""

    def __init__(self, rate: float, burst: float | None = None):
        self.rate = float(rate)
        self.burst = burst if burst is not None else self.rate / 5
        self._tokens = self.burst
        self._last = time.monotonic()
        self._lock = threading.Lock()

    def consume(self, n: int) -> None:
        if self.rate <= 0:
            return
        with self._lock:
            now = time.monotonic()
            self._tokens = min(self.burst,
                               self._tokens + (now - self._last) * self.rate)
            self._last = now
            self._tokens -= n
            wait = -self._tokens / self.rate if self._tokens < 0 else 0.0
        if wait > 0:
            time.sleep(wait)


class _Channel:
    """Send queue + recv reassembly buffer for one channel id
    (reference `p2p/connection.go:540-675`)."""

    def __init__(self, desc: ChannelDescriptor):
        self.desc = desc
        self.send_queue: deque[bytes] = deque()
        self.sending: bytes | None = None     # message partially sent
        self.sent_pos = 0
        self.recently_sent = 0.0
        self.recving = bytearray()

    def is_send_pending(self) -> bool:
        return self.sending is not None or bool(self.send_queue)

    def next_packet(self) -> tuple[bytes, bool]:
        """Pop up to MAX_PACKET_PAYLOAD of the in-flight message."""
        if self.sending is None:
            self.sending = self.send_queue.popleft()
            self.sent_pos = 0
        chunk = self.sending[self.sent_pos:self.sent_pos + MAX_PACKET_PAYLOAD]
        self.sent_pos += len(chunk)
        eof = self.sent_pos >= len(self.sending)
        if eof:
            self.sending = None
            self.sent_pos = 0
        return chunk, eof


class MConnection:
    """Owns a StreamConn (or secret/fuzzed wrapper) and two routines.

    `on_receive(ch_id, msg_bytes)` fires on the recv thread for each
    complete message; `on_error(exc)` fires once when the connection dies.
    """

    def __init__(self, conn, chan_descs: list[ChannelDescriptor],
                 on_receive, on_error=None,
                 send_rate: int = 512_000, recv_rate: int = 512_000,
                 ping_interval: float = 40.0,
                 flush_throttle: float = 0.1, label: str = ""):
        self.conn = conn
        self.on_receive = on_receive
        self.on_error = on_error
        self.label = label           # peer id/addr, for death reports
        self._channels = {d.id: _Channel(d) for d in chan_descs}
        self._send_limiter = _RateLimiter(send_rate)
        self._recv_limiter = _RateLimiter(recv_rate)
        self._ping_interval = ping_interval
        self._flush_throttle = flush_throttle
        self._send_cv = threading.Condition()
        self._pong_pending = 0   # PONGs owed; recv routine increments under
        #                          _send_cv, send routine drains and writes
        self._stopped = threading.Event()
        self._errored = False
        self._err_lock = threading.Lock()
        self._last_decay = time.monotonic()
        self._threads: list[threading.Thread] = []
        # the native receive loop's state, where start() finds the link
        # made for it; None: the Python loop below
        self._rx: LinkReceiver | None = None
        from tendermint_tpu.utils.flowrate import Meter
        self.send_monitor = Meter()
        self.recv_monitor = Meter()

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        # decided once, from what the link is made of: a secret link
        # straight over a socket has its packets read natively, a
        # message a call; any other (fuzzed, not a socket, no toolchain)
        # runs the Python loop, which is also the reference the tests
        # hold the native one equal to
        hand_over = getattr(self.conn, "native_receiver", None)
        if hand_over is not None:       # a SecretConnection: it decides
            lim = self._recv_limiter
            self._rx = hand_over(
                lim.rate, lim.burst,
                {ch.desc.id: ch.desc.recv_message_capacity
                 for ch in self._channels.values()})
        recv = (self._recv_routine if self._rx is None
                else self._recv_routine_native)
        for target, name in ((self._send_routine, "mconn-send"),
                             (recv, "mconn-recv")):
            t = threading.Thread(target=target, daemon=True, name=name)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stopped.set()
        if self._rx is not None:
            # ends a sleep in the native limiter; closing the conn shuts
            # the socket down, which ends a native recv
            self._rx.stop()
        with self._send_cv:
            self._send_cv.notify()
        self.conn.close()

    def _die(self, exc: Exception) -> None:
        with self._err_lock:
            if self._errored:
                return
            self._errored = True
        # stop() closes the socket, which makes the OTHER routine's
        # blocking read/write raise too — that second death is expected
        # and already deduped above.  A death after stop() was requested
        # is normal teardown (debug); anything else is a real peer error
        # and must be attributable even when no on_error is wired.
        if self._stopped.is_set():
            log.debug("connection closed", peer=self.label or "?",
                      cause=type(exc).__name__)
        else:
            log.error("connection died", peer=self.label or "?",
                      err=str(exc) or type(exc).__name__)
        self.stop()
        if self.on_error is not None:
            self.on_error(exc)

    # -- sending --------------------------------------------------------
    def send(self, ch_id: int, msg: bytes, timeout: float = 10.0) -> bool:
        """Queue a message; blocks while the channel queue is full
        (reference `sendBytes` blocking semantics)."""
        ch = self._channels.get(ch_id)
        if ch is None or self._stopped.is_set():
            return False
        deadline = time.monotonic() + timeout
        with self._send_cv:
            while len(ch.send_queue) >= ch.desc.send_queue_capacity:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._stopped.is_set():
                    return False
                self._send_cv.wait(remaining)
            ch.send_queue.append(msg)
            self._send_cv.notify()
        return True

    def try_send(self, ch_id: int, msg: bytes) -> bool:
        """Non-blocking send (reference `trySendBytes`)."""
        ch = self._channels.get(ch_id)
        if ch is None or self._stopped.is_set():
            return False
        with self._send_cv:
            if len(ch.send_queue) >= ch.desc.send_queue_capacity:
                return False
            ch.send_queue.append(msg)
            self._send_cv.notify()
        return True

    def can_send(self, ch_id: int) -> bool:
        ch = self._channels.get(ch_id)
        if ch is None:
            return False
        return len(ch.send_queue) < ch.desc.send_queue_capacity

    def _pick_channel(self) -> _Channel | None:
        """Least recentlySent/priority among channels with pending data
        (reference `sendPacketMsg` `:341-356`)."""
        best, best_ratio = None, None
        for ch in self._channels.values():
            if not ch.is_send_pending():
                continue
            ratio = ch.recently_sent / ch.desc.priority
            if best_ratio is None or ratio < best_ratio:
                best, best_ratio = ch, ratio
        return best

    def _decay(self) -> None:
        now = time.monotonic()
        if now - self._last_decay >= 2.0:
            for ch in self._channels.values():
                ch.recently_sent *= 0.8      # reference :561-565
            self._last_decay = now

    def _send_routine(self) -> None:
        last_ping = time.monotonic()
        try:
            while not self._stopped.is_set():
                with self._send_cv:
                    ch = self._pick_channel()
                    if ch is None and not self._pong_pending:
                        self._send_cv.wait(self._flush_throttle)
                        ch = self._pick_channel()
                    pongs, self._pong_pending = self._pong_pending, 0
                    if ch is not None:
                        chunk, eof = ch.next_packet()
                        ch.recently_sent += len(chunk)
                        self._send_cv.notify()
                    else:
                        chunk = None
                # all writes happen on this thread: concurrent writes from
                # the recv routine would interleave SecretConnection frame
                # sequence numbers and fail the peer's MAC check
                for _ in range(pongs):
                    self.conn.write(struct.pack(">B", PKT_PONG))
                if chunk is not None:
                    pkt = struct.pack(
                        ">BBBH", PKT_MSG, ch.desc.id,
                        FLAG_EOF if eof else 0, len(chunk)) + chunk
                    self._send_limiter.consume(len(pkt))
                    self.conn.write(pkt)
                    self.send_monitor.update(len(pkt))
                    REGISTRY.msgs_sent.inc()
                self._decay()
                now = time.monotonic()
                if now - last_ping >= self._ping_interval:
                    self.conn.write(struct.pack(">B", PKT_PING))
                    last_ping = now
        except Exception as e:
            self._die(e)

    # -- receiving ------------------------------------------------------
    def _recv_routine(self) -> None:
        try:
            while not self._stopped.is_set():
                t = struct.unpack(
                    ">B", self.conn.read_exact(1))[0]
                if t == PKT_PING:
                    with self._send_cv:
                        self._pong_pending += 1
                        self._send_cv.notify()
                    continue
                if t == PKT_PONG:
                    continue
                if t != PKT_MSG:
                    raise ValueError(f"unknown packet type {t}")
                ch_id, flags, ln = struct.unpack(
                    ">BBH", self.conn.read_exact(4))
                payload = self.conn.read_exact(ln) if ln else b""
                self._recv_limiter.consume(5 + ln)
                self.recv_monitor.update(5 + ln)
                ch = self._channels.get(ch_id)
                if ch is None:
                    raise ValueError(f"packet for unknown channel {ch_id}")
                ch.recving += payload
                if len(ch.recving) > ch.desc.recv_message_capacity:
                    raise ValueError(
                        f"message on channel {ch_id} exceeds "
                        f"{ch.desc.recv_message_capacity} bytes")
                if flags & FLAG_EOF:
                    msg = bytes(ch.recving)
                    ch.recving.clear()
                    REGISTRY.msgs_received.inc()
                    REGISTRY.link_msgs_python.inc()
                    tracing.instant("link.recv.python", ch=ch_id,
                                    bytes=len(msg))
                    self.on_receive(ch_id, msg)
        except Exception as e:
            self._die(e)

    def _recv_routine_native(self) -> None:
        """The loop above with its per-packet body in one GIL-free call
        (native/tmlink.cpp): Python runs once a message, a PING, or
        64 KiB / 100 ms of a long message, and raises what the loop
        above raises."""
        rx = self._rx
        try:
            while not self._stopped.is_set():
                ev = rx.recv()
                if rx.bytes:
                    self.recv_monitor.update(rx.bytes)
                if ev == rx.MSG:
                    msg = rx.message()
                    REGISTRY.msgs_received.inc()
                    REGISTRY.link_msgs_native.inc()
                    tracing.instant("link.recv.native", ch=rx.ch,
                                    bytes=len(msg))
                    self.on_receive(rx.ch, msg)
                elif ev == rx.PING:
                    with self._send_cv:
                        self._pong_pending += 1
                        self._send_cv.notify()
                elif ev == rx.STOPPED:
                    break
                elif ev != rx.PROGRESS:
                    raise self._native_error(ev, rx.ch, rx.arg)
        except Exception as e:
            self._die(e)
        finally:
            rx.close()

    @staticmethod
    def _native_error(ev: int, ch_id: int, arg: int) -> Exception:
        """The exception the Python loop raises where the native one
        returned `ev` (secret.py, transport.py and _recv_routine have
        the originals; tests/test_link_native.py holds them equal)."""
        if ev == LinkReceiver.CLOSED:
            return ConnectionError("connection closed")
        if ev == LinkReceiver.OS_ERROR:
            return OSError(arg, os.strerror(arg))
        return ValueError({
            LinkReceiver.BAD_MAC: "secret connection: bad frame MAC",
            LinkReceiver.BAD_FRAME_LEN:
                f"secret connection: bad frame length {arg}",
            LinkReceiver.BAD_PACKET_TYPE: f"unknown packet type {arg}",
            LinkReceiver.UNKNOWN_CHANNEL:
                f"packet for unknown channel {ch_id}",
            LinkReceiver.OVER_CAPACITY:
                f"message on channel {ch_id} exceeds {arg} bytes",
        }[ev])

    def receiving(self, ch_id: int) -> int:
        """Bytes that have arrived of the message now being received on
        `ch_id` (0 between messages): a reactor whose messages take
        seconds to cross the link reads its peer's progress here."""
        if self._rx is not None:
            return self._rx.receiving(ch_id)
        ch = self._channels.get(ch_id)
        return len(ch.recving) if ch is not None else 0

    def status(self) -> dict:
        """Flowrate + channel-occupancy snapshot (reference
        `ConnectionStatus`, p2p/connection.go:485-515: SendMonitor /
        RecvMonitor status plus per-channel state)."""
        return {
            "send_monitor": self.send_monitor.status(),
            "recv_monitor": self.recv_monitor.status(),
            "channels": {
                ch.desc.id: {
                    "priority": ch.desc.priority,
                    "send_queue_size": len(ch.send_queue),
                    "recently_sent": round(ch.recently_sent, 1),
                } for ch in self._channels.values()
            },
        }
