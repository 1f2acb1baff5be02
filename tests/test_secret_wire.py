"""The bytes of the encrypted peer link, pinned.

`SecretConnection`'s cipher was rewritten for speed (one C call over a
frame where a Python loop ran a byte); a node of this tree and a node of
any older tree must still talk to each other.  `RefDirection` below is
the byte loop as it stood, kept here as the plain reference: the new
`seal` has to equal it byte for byte and `open` has to be its inverse,
and a few stored digests keep the reference itself from drifting.
"""

import hashlib
import hmac
import socket
import struct
import threading

import pytest

from tendermint_tpu.p2p import ChannelDescriptor, MConnection, transport
from tendermint_tpu.p2p.secret import SecretConnection, _Direction
from tendermint_tpu.types.keys import PrivKey

KEY = bytes(range(32))
MAC_KEY = bytes(range(32, 64))
MAX_FRAME = SecretConnection.MAX_FRAME
BLOCK_BYTES = 272_921       # a 1,000-tx block of the benchmark's third cell


class RefDirection:
    """`_Direction` as it was before PR 30: SHA-256 in counter mode, a
    generator a byte for the XOR, `hmac.new` for the tag."""

    def __init__(self, key: bytes, mac_key: bytes, seq: int = 0):
        self.key = key
        self.mac_key = mac_key
        self.seq = seq

    def _keystream(self, n: int) -> bytes:
        out = []
        base = self.key + struct.pack(">Q", self.seq)
        for ctr in range((n + 31) // 32):
            out.append(hashlib.sha256(
                base + struct.pack(">I", ctr)).digest())
        return b"".join(out)[:n]

    def seal(self, plaintext: bytes) -> bytes:
        ks = self._keystream(len(plaintext))
        ct = bytes(a ^ b for a, b in zip(plaintext, ks))
        tag = hmac.new(self.mac_key,
                       struct.pack(">Q", self.seq) + ct,
                       hashlib.sha256).digest()[:16]
        self.seq += 1
        return ct + tag

    def open(self, ct_and_tag: bytes) -> bytes:
        ct, tag = ct_and_tag[:-16], ct_and_tag[-16:]
        want = hmac.new(self.mac_key,
                        struct.pack(">Q", self.seq) + ct,
                        hashlib.sha256).digest()[:16]
        if not hmac.compare_digest(tag, want):
            raise ValueError("secret connection: bad frame MAC")
        ks = self._keystream(len(ct))
        self.seq += 1
        return bytes(a ^ b for a, b in zip(ct, ks))


def _plaintext(n: int) -> bytes:
    """n fixed bytes with no period a 32-byte block could hide in."""
    out = hashlib.shake_128(b"secret-wire-plaintext").digest(n)
    assert len(out) == n
    return out


def _direction(seq: int, cls=_Direction):
    d = cls(KEY, MAC_KEY)
    d.seq = seq
    return d


SEQS = [0, 1, 2**32, 2**63]
LENGTHS = [0, 1, 31, 32, 33, 1_024, 1_029, 65_536, MAX_FRAME - 16]


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("seq", SEQS)
def test_seal_equals_the_byte_loop_and_open_inverts_it(seq, n):
    pt = _plaintext(n)
    want = _direction(seq, RefDirection).seal(pt)
    sender = _direction(seq)
    frame = sender.seal(pt)
    assert frame == want
    assert len(frame) == n + 16
    assert sender.seq == seq + 1
    # each side opens the other's frame
    receiver = _direction(seq)
    assert receiver.open(want) == pt
    assert receiver.seq == seq + 1
    assert _direction(seq, RefDirection).open(frame) == pt


# sha256 of `seal(_plaintext(n))` at KEY / MAC_KEY, (seq, n) -> digest, written
# down from the parent tree's `_Direction` (PR 29): with them the reference
# above cannot drift
GOLDEN = {
    (0, 0): "d42856bc987f203c51198ca36581e502fc4bcf9c684d12e7984fc25871ac13c6",
    (0, 1): "26f97b82ec272f29abe03089e80e7f734599162ae21a7ac060654334e4d813ca",
    (1, 33):
        "93f2b258c96bc47be95c13692309bd531fe1de6e7200687d9d4122965d4ae860",
    (2**32, 1_029):
        "ec1f13d69ff739846d72ff8094b4ec6a60ac5b96bf6646d24fddd7e4488e605c",
    (2**63, 65_536):
        "8458ec08d0557fdbd6c297fbff0be77c36734e238fe147a68de75b442ff23f5f",
}


@pytest.mark.parametrize("seq,n", list(GOLDEN))
def test_stored_digests_hold_the_reference(seq, n):
    frame = _direction(seq, RefDirection).seal(_plaintext(n))
    assert hashlib.sha256(frame).hexdigest() == GOLDEN[(seq, n)]
    assert _direction(seq).seal(_plaintext(n)) == frame


def test_a_stream_of_frames_matches_in_both_directions():
    """`seq` advances the same way on both implementations: 50 frames of
    mixed lengths sealed by one open on the other, either way round."""
    for seal_cls, open_cls in ((_Direction, RefDirection),
                               (RefDirection, _Direction)):
        tx, rx = _direction(0, seal_cls), _direction(0, open_cls)
        for i in range(50):
            pt = _plaintext((i * 211) % 1_500)
            assert rx.open(tx.seal(pt)) == pt
        assert tx.seq == rx.seq == 50


def _flip(frame: bytes, i: int) -> bytes:
    return frame[:i] + bytes([frame[i] ^ 0x01]) + frame[i + 1:]


@pytest.mark.parametrize("fault", [
    "ciphertext_bit", "tag_bit", "replayed", "swapped", "truncated",
    "wrong_key"])
def test_a_bad_frame_is_refused_and_seq_stays(fault):
    tx = _direction(7)
    first, second = tx.seal(_plaintext(1_029)), tx.seal(_plaintext(64))
    rx = _direction(7)
    if fault == "replayed":
        assert rx.open(first) == _plaintext(1_029)
        bad = first
    elif fault == "swapped":
        bad = second
    elif fault == "wrong_key":
        rx = _Direction(MAC_KEY, KEY)
        rx.seq = 7
        bad = first
    else:
        bad = {"ciphertext_bit": _flip(first, 500),
               "tag_bit": _flip(first, len(first) - 1),
               "truncated": first[:-1]}[fault]
    seq = rx.seq
    with pytest.raises(ValueError, match="bad frame MAC"):
        rx.open(bad)
    assert rx.seq == seq
    # and the frame that is due still opens
    due = first if seq == 7 else second
    if fault != "wrong_key":
        assert rx.open(due) == _plaintext(len(due) - 16)
        assert rx.seq == seq + 1


def test_mac_is_compared_in_constant_time_before_decryption(monkeypatch):
    """The order of `open` is part of the contract: the tag goes through
    `hmac.compare_digest`, and a frame that fails it costs no keystream."""
    from tendermint_tpu.p2p import secret
    calls = []
    real = hmac.compare_digest
    monkeypatch.setattr(secret.hmac, "compare_digest",
                        lambda a, b: calls.append("mac") or real(a, b))
    real_sha = hashlib.sha256
    monkeypatch.setattr(secret.hashlib, "sha256",
                        lambda *a: calls.append("ks") or real_sha(*a))
    frame = _direction(3).seal(_plaintext(64))
    del calls[:]
    rx = _direction(3)
    with pytest.raises(ValueError):
        rx.open(_flip(frame, 0))
    assert calls == ["mac"]
    assert rx.open(frame) == _plaintext(64)
    assert calls[:2] == ["mac", "mac"] and "ks" in calls[2:]


# -- the link: handshake, framing, short reads --------------------------------

class ChoppySock:
    """A socket whose `recv` hands over at most `k` bytes a call."""

    def __init__(self, sock: socket.socket, k: int):
        self._sock = sock
        self._k = k

    def recv(self, n: int) -> bytes:
        return self._sock.recv(min(n, self._k))

    def __getattr__(self, name):
        return getattr(self._sock, name)


class ParentSecretConnection(SecretConnection):
    """A node of the parent tree: the same handshake, every frame sealed
    and opened by the byte loop (key and `seq` carried over)."""

    @staticmethod
    def _as_ref(d):
        if isinstance(d, RefDirection):
            return d
        return RefDirection(d.key, d.mac_key, d.seq)

    def _write_frame(self, plaintext: bytes) -> None:
        self._send = self._as_ref(self._send)
        super()._write_frame(plaintext)

    def _read_frame(self) -> bytes:
        self._recv = self._as_ref(self._recv)
        return super()._read_frame()


@pytest.fixture
def secret_pair():
    """`secret_pair(recv_chunk, classes)` -> two ends of a handshaken link
    over a socketpair, and the raw conns under them; the second end's
    socket hands `recv` at most `recv_chunk` bytes at a time.  Every
    socket is closed when the test ends."""
    opened = []

    def make(recv_chunk: int | None = None,
             classes=(SecretConnection, SecretConnection)):
        a, b = socket.socketpair()
        if recv_chunk is not None:
            b = ChoppySock(b, recv_chunk)
        conns = (transport.StreamConn(a, "a"), transport.StreamConn(b, "b"))
        opened.extend(conns)
        out, errs = {}, []

        def shake(i):
            try:
                out[i] = classes[i](conns[i], PrivKey.generate())
            except Exception as e:      # the assertion below reports it
                errs.append(e)
        threads = [threading.Thread(target=shake, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        assert not errs and len(out) == 2, errs
        return out[0], out[1], conns

    yield make
    for conn in opened:
        conn.close()


@pytest.mark.parametrize("recv_chunk", [1, 3, 4_096, 1 << 20])
def test_reads_that_straddle_frames_under_short_reads(secret_pair,
                                                      recv_chunk):
    """Whatever the socket hands over at a time, and however many frames
    one `recv` brings, `read_exact` returns the same bytes in the same
    order, in sizes that cut across the frames."""
    tx, rx, _ = secret_pair(recv_chunk)
    frames = [_plaintext(n) for n in (1_029, 1, 0, 33, 5, 1_029, 700, 2_048)]
    for f in frames:
        tx.write(f)                 # all on the wire before the first read
    stream = b"".join(frames)
    pos = 0
    for n in (1, 4, 1_024, 0, 7, 1_500, 2, 1_029):
        assert rx.read_exact(n) == stream[pos:pos + n]
        pos += n
    assert rx.read_exact(len(stream) - pos) == stream[pos:]


@pytest.mark.parametrize("parent_end", [0, 1])
@pytest.mark.parametrize("recv_chunk", [None, 1_000])
def test_parent_and_this_tree_exchange_a_block_both_ways_at_once(
        secret_pair, parent_end, recv_chunk):
    """A parent-tree node and this tree's complete the handshake, and a
    272,921-byte message crosses `MConnection` over the link in both
    directions at the same time."""
    classes = [SecretConnection, SecretConnection]
    classes[parent_end] = ParentSecretConnection
    a, b, _ = secret_pair(recv_chunk, classes)
    assert a.remote_pub_key != b.remote_pub_key
    desc = [ChannelDescriptor(id=0x40, priority=5,
                              recv_message_capacity=1 << 20)]
    got = {"a": [], "b": []}
    done = {"a": threading.Event(), "b": threading.Event()}

    def receiver(name):
        def on_receive(ch_id, msg):
            got[name].append(msg)
            done[name].set()
        return on_receive
    errors = []
    ma = MConnection(a, desc, receiver("a"), on_error=errors.append,
                     send_rate=1 << 30, recv_rate=1 << 30)
    mb = MConnection(b, desc, receiver("b"), on_error=errors.append,
                     send_rate=1 << 30, recv_rate=1 << 30)
    ma.start()
    mb.start()
    try:
        to_b, to_a = _plaintext(BLOCK_BYTES), _plaintext(BLOCK_BYTES)[::-1]
        assert ma.send(0x40, to_b) and mb.send(0x40, to_a)
        assert done["a"].wait(60) and done["b"].wait(60), errors
        assert got == {"a": [to_a], "b": [to_b]}
        assert not errors
    finally:
        ma.stop()
        mb.stop()


@pytest.mark.parametrize("length,reason", [
    (15, "bad frame length 15"),
    (0, "bad frame length 0"),
    (MAX_FRAME + 1, f"bad frame length {MAX_FRAME + 1}"),
])
def test_a_frame_of_a_bad_length_is_refused(secret_pair, length, reason):
    tx, rx, (raw_tx, _) = secret_pair()
    raw_tx.write(struct.pack(">I", length) + b"\x00" * min(length, 64))
    seq = rx._recv.seq
    with pytest.raises(ValueError, match=reason):
        rx.read_exact(1)
    assert rx._recv.seq == seq


def test_a_corrupted_frame_on_the_wire_fails_its_mac(secret_pair):
    """What `FuzzedConnection`'s garbage mode relies on (p2p/fuzz.py)."""
    tx, rx, (raw_tx, _) = secret_pair()
    frame = tx._send.seal(b"x" * 100)
    raw_tx.write(struct.pack(">I", len(frame)) + _flip(frame, 50))
    with pytest.raises(ValueError, match="bad frame MAC"):
        rx.read_exact(100)


@pytest.mark.parametrize("cut", ["in_the_length", "in_the_frame",
                                 "between_frames"])
def test_a_close_mid_frame_is_a_connection_error_not_a_short_read(
        secret_pair, cut):
    tx, rx, (raw_tx, _) = secret_pair()
    tx.write(b"whole")
    frame = tx._send.seal(b"y" * 1_029)
    wire = struct.pack(">I", len(frame)) + frame
    raw_tx.write({"in_the_length": wire[:2], "in_the_frame": wire[:600],
                  "between_frames": b""}[cut])
    raw_tx.close()
    assert rx.read_exact(5) == b"whole"
    with pytest.raises(ConnectionError):
        rx.read_exact(1)
