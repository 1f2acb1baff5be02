"""P2P stack tests: x25519 vectors, secret connection, MConnection
framing/multiplexing, switch lifecycle, addrbook, PEX.

Modeled on the reference's `p2p/switch_test.go`, `connection_test.go`,
`secret_connection_test.go`, `addrbook_test.go`.
"""

import threading
import time

import pytest

from tendermint_tpu.p2p import (AddrBook, ChannelDescriptor, MConnection,
                                NetAddress, NodeInfo, PEXReactor,
                                PEX_CHANNEL, Reactor, SecretConnection,
                                SwitchError, connect_switches, dial,
                                make_switch, make_connected_switches,
                                mem_pair)
from tendermint_tpu.p2p.secret import x25519, x25519_keypair
from tendermint_tpu.p2p import transport
from tendermint_tpu.p2p import addrbook as addrbook_mod
from tendermint_tpu.types.keys import PrivKey


def _wait_for(cond, timeout=5.0, step=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


# -- x25519 -----------------------------------------------------------------

def test_x25519_rfc7748_vector():
    k = bytes.fromhex("a546e36bf0527c9d3b16154b82465edd"
                      "62144c0ac1fc5a18506a2244ba449ac4")
    u = bytes.fromhex("e6db6867583030db3594c1a424b15f7c"
                      "726624ec26b3353b10a903a6d0ab1c4c")
    want = bytes.fromhex("c3da55379de9c6908e94ea4df28d084f"
                         "32eccf03491c71f754b4075577a28552")
    assert x25519(k, u) == want


def test_x25519_dh_agreement():
    a_priv = bytes.fromhex("77076d0a7318a57d3c16c17251b26645"
                           "df4c2f87ebc0992ab177fba51db92c2a")
    b_priv = bytes.fromhex("5dab087e624a8a4b79e17f8b83800ee6"
                           "6f3bb1292618b6fd1c2f8b27ff88e0eb")
    base = (9).to_bytes(32, "little")
    a_pub, b_pub = x25519(a_priv, base), x25519(b_priv, base)
    want = bytes.fromhex("4a5d9d5ba4ce2de1728e3bf480350f25"
                         "e07e21c947d19e3376f09b3c1e161742")
    assert x25519(a_priv, b_pub) == want
    assert x25519(b_priv, a_pub) == want


# -- secret connection ------------------------------------------------------

def _secret_pair():
    c1, c2 = mem_pair()
    k1, k2 = PrivKey.generate(), PrivKey.generate()
    out = {}

    def mk(key, conn, kk):
        out[key] = SecretConnection(conn, kk)

    t1 = threading.Thread(target=mk, args=(1, c1, k1), daemon=True)
    t2 = threading.Thread(target=mk, args=(2, c2, k2), daemon=True)
    t1.start(); t2.start(); t1.join(5); t2.join(5)
    assert 1 in out and 2 in out, "secret handshake failed"
    return out[1], out[2], k1, k2


def test_secret_connection_roundtrip_and_identity():
    s1, s2, k1, k2 = _secret_pair()
    assert s1.remote_pub_key == k2.pub_key.bytes_
    assert s2.remote_pub_key == k1.pub_key.bytes_
    s1.write(b"hello over the wire")
    assert s2.read_exact(19) == b"hello over the wire"
    s2.write(b"x" * 5000)         # multi-frame reads
    assert s1.read_exact(5000) == b"x" * 5000


def test_secret_connection_frames_are_encrypted():
    c1, c2 = mem_pair()
    k1, k2 = PrivKey.generate(), PrivKey.generate()
    captured = []
    orig_write = c1.write

    def spy_write(data):
        captured.append(data)
        orig_write(data)
    c1.write = spy_write
    out = {}
    t1 = threading.Thread(
        target=lambda: out.setdefault(1, SecretConnection(c1, k1)),
        daemon=True)
    t2 = threading.Thread(
        target=lambda: out.setdefault(2, SecretConnection(c2, k2)),
        daemon=True)
    t1.start(); t2.start(); t1.join(5); t2.join(5)
    out[1].write(b"supersecret-payload")
    out[2].read_exact(19)
    wire = b"".join(captured)
    assert b"supersecret-payload" not in wire


def test_secret_connection_tamper_rejected():
    s1, s2, *_ = _secret_pair()
    # corrupt a frame in transit: write garbage directly to the raw conn
    s1._conn.write(b"\x00\x00\x00\x20" + b"\x00" * 32)
    with pytest.raises((ValueError, ConnectionError)):
        s2.read_exact(1)


# -- MConnection ------------------------------------------------------------

def _mconn_pair(descs=None, **kwargs):
    descs = descs or [ChannelDescriptor(id=1), ChannelDescriptor(id=2)]
    c1, c2 = mem_pair()
    r1, r2 = [], []
    m1 = MConnection(c1, descs, lambda ch, m: r1.append((ch, m)), **kwargs)
    m2 = MConnection(c2, descs, lambda ch, m: r2.append((ch, m)), **kwargs)
    m1.start(); m2.start()
    return m1, m2, r1, r2


def test_mconnection_roundtrip_multiplexed():
    m1, m2, r1, r2 = _mconn_pair()
    try:
        assert m1.send(1, b"on channel one")
        assert m1.send(2, b"on channel two")
        assert m2.send(1, b"reply")
        assert _wait_for(lambda: len(r2) == 2 and len(r1) == 1)
        assert (1, b"on channel one") in r2 and (2, b"on channel two") in r2
        assert r1 == [(1, b"reply")]
    finally:
        m1.stop(); m2.stop()


def test_mconnection_large_message_chunked():
    m1, m2, r1, r2 = _mconn_pair()
    try:
        big = bytes(range(256)) * 40   # 10240 B -> 10+ packets
        assert m1.send(1, big)
        assert _wait_for(lambda: len(r2) == 1)
        assert r2[0] == (1, big)
    finally:
        m1.stop(); m2.stop()


def test_mconnection_reports_what_has_arrived_of_an_unfinished_message():
    """`receiving(ch)` grows while a long message crosses a slow link
    and is 0 again once it is whole: a reactor reads its peer's progress
    there (the block pool's silence clock)."""
    m1, m2, r1, r2 = _mconn_pair(send_rate=40_000, recv_rate=40_000)
    try:
        big = bytes(range(256)) * 160  # 40 KB: a second at this rate
        assert m2.receiving(1) == 0 and m2.receiving(99) == 0
        assert m1.send(1, big)
        seen = []
        assert _wait_for(lambda: seen.append(m2.receiving(1)) or
                         len(r2) == 1, timeout=10)
        assert r2[0] == (1, big) and m2.receiving(1) == 0
        mid = [n for n in seen if n]
        assert mid and mid == sorted(mid) and 0 < mid[0] <= mid[-1] < len(big)
        assert m2.receiving(2) == 0
    finally:
        m1.stop(); m2.stop()


def test_mconnection_on_error_fires_on_close():
    errs = []
    c1, c2 = mem_pair()
    m1 = MConnection(c1, [ChannelDescriptor(id=1)], lambda ch, m: None,
                     on_error=lambda e: errs.append(e))
    m1.start()
    c2.close()
    m1.send(1, b"x")
    assert _wait_for(lambda: len(errs) >= 1)


def test_mconnection_unknown_channel_send_fails():
    m1, m2, *_ = _mconn_pair()
    try:
        assert not m1.send(99, b"nope")
    finally:
        m1.stop(); m2.stop()


# -- switch -----------------------------------------------------------------

class EchoReactor(Reactor):
    """Responds to every message with 'echo:'+msg on the same channel."""

    def __init__(self, ch_id=0x10):
        super().__init__()
        self.ch_id = ch_id
        self.received = []
        self.peers_added = []
        self.peers_removed = []

    def get_channels(self):
        return [ChannelDescriptor(id=self.ch_id)]

    def add_peer(self, peer):
        self.peers_added.append(peer.id)

    def remove_peer(self, peer, reason):
        self.peers_removed.append(peer.id)

    def receive(self, ch_id, peer, msg):
        self.received.append((peer.id, msg))
        if not msg.startswith(b"echo:"):
            peer.try_send(ch_id, b"echo:" + msg)


def test_switch_two_nodes_talk():
    r1, r2 = EchoReactor(), EchoReactor()
    sw1 = make_switch("net1", {"echo": r1})
    sw2 = make_switch("net1", {"echo": r2})
    sw1.start(); sw2.start()
    try:
        p12, p21 = connect_switches(sw1, sw2)
        assert sw1.n_peers() == 1 and sw2.n_peers() == 1
        assert r1.peers_added and r2.peers_added
        # authenticated identity matches the node key
        assert p12.id == sw2.node_info.id
        p12.send(0x10, b"ping over the mesh")
        assert _wait_for(lambda: len(r1.received) == 1)
        assert r1.received[0][1] == b"echo:ping over the mesh"
    finally:
        sw1.stop(); sw2.stop()


def test_switch_rejects_network_mismatch():
    sw1 = make_switch("chain-A", {"echo": EchoReactor()})
    sw2 = make_switch("chain-B", {"echo": EchoReactor()})
    sw1.start(); sw2.start()
    try:
        with pytest.raises(SwitchError):
            connect_switches(sw1, sw2)
        assert sw1.n_peers() == 0 and sw2.n_peers() == 0
    finally:
        sw1.stop(); sw2.stop()


def test_switch_broadcast_and_peer_removal():
    n = 4
    reactors = [EchoReactor() for _ in range(n)]
    sws = make_connected_switches("net", n, lambda i: {"echo": reactors[i]})
    try:
        assert all(sw.n_peers() == n - 1 for sw in sws)
        sent = sws[0].broadcast(0x10, b"allhands")
        assert len(sent) == n - 1
        assert _wait_for(lambda: all(len(r.received) >= 1
                                     for r in reactors[1:]))
        # kill a peer connection: both sides notice and clean up
        victim = sws[0].peers()[0]
        victim.mconn.conn.close()
        assert _wait_for(lambda: sws[0].n_peers() == n - 2)
    finally:
        for sw in sws:
            sw.stop()


def test_switch_over_real_tcp():
    from tendermint_tpu.config import P2PConfig
    cfg1 = P2PConfig(laddr="tcp://127.0.0.1:0", pex=False)
    cfg2 = P2PConfig(laddr="", pex=False)
    r1, r2 = EchoReactor(), EchoReactor()
    sw1 = make_switch("net", {"echo": r1}, cfg1)
    sw2 = make_switch("net", {"echo": r2}, cfg2)
    sw1.start(); sw2.start()
    try:
        addr = sw1._listener.addr
        sw2.dial_peer_async(addr)
        assert _wait_for(lambda: sw1.n_peers() == 1 and sw2.n_peers() == 1)
        peer = sw2.peers()[0]
        peer.send(0x10, b"tcp hello")
        assert _wait_for(lambda: len(r2.received) == 1)
        assert r2.received[0][1] == b"echo:tcp hello"
    finally:
        sw1.stop(); sw2.stop()


# -- addrbook + pex ---------------------------------------------------------

def test_addrbook_basics(tmp_path):
    path = str(tmp_path / "book.json")
    book = AddrBook(path)
    a1 = NetAddress.parse("tcp://10.0.0.1:26656")
    a2 = NetAddress.parse("tcp://10.0.0.2:26656")
    assert book.add_address(a1, "seed")
    assert not book.add_address(a1, "seed")      # dedupe
    assert book.add_address(a2, "seed")
    assert book.size() == 2
    book.mark_good(a1)
    assert book.has(a1)
    picked = {str(book.pick_address()) for _ in range(50)}
    assert picked <= {str(a1), str(a2)}
    book.mark_bad(a2)
    assert not book.has(a2)
    book.save()
    book2 = AddrBook(path)
    assert book2.size() == 1 and book2.has(a1)


def test_pex_exchanges_addresses():
    book1, book2 = AddrBook(), AddrBook()
    for i in range(5):
        book1.add_address(NetAddress.parse(f"tcp://10.1.0.{i + 1}:26656"))
    pex1, pex2 = PEXReactor(book1, ensure_interval=3600), \
        PEXReactor(book2, ensure_interval=3600)
    sw1 = make_switch("net", {"pex": pex1})
    sw2 = make_switch("net", {"pex": pex2})
    sw1.start(); sw2.start()
    try:
        # sw2 dials sw1 => sw1 sees an inbound peer and requests addrs;
        # meanwhile sw2 (outbound) does not.  Drive the exchange from sw2
        # manually: request addrs from its peer.
        connect_switches(sw2, sw1)
        peer = sw2.peers()[0]
        pex2._request_addrs(peer)
        assert _wait_for(lambda: book2.size() >= 5)
    finally:
        sw1.stop(); sw2.stop()


def test_addrbook_new_bucket_eviction_under_pressure():
    """Flooding one /16 from one source stays bounded by bucket size and
    evicts randomly WITHIN that bucket (reference addrbook.go expireNew /
    randomized eviction) — other groups are untouched."""
    book = AddrBook()
    keep = NetAddress.parse("tcp://192.168.0.1:26656")
    book.add_address(keep, "seed.example:26656")
    # same /16 + same source => one shared new bucket
    n = 3 * addrbook_mod.BUCKET_SIZE
    for i in range(n):
        book.add_address(
            NetAddress.parse(f"tcp://10.7.{i // 250}.{i % 250 + 1}:26656"),
            "10.99.0.1:26656")
    same_group = [e for e in book._entries.values()
                  if e.addr.host.startswith("10.7.")]
    assert len(same_group) <= addrbook_mod.BUCKET_SIZE
    assert book.has(keep)                 # pressure confined to the bucket
    buckets = {e.bucket for e in same_group}
    assert len(buckets) == 1              # all landed in one bucket


def test_addrbook_eviction_prefers_bad_entries():
    book = AddrBook()
    src = "10.99.0.1:26656"
    addrs = [NetAddress.parse(f"tcp://10.8.0.{i + 1}:26656")
             for i in range(addrbook_mod.BUCKET_SIZE)]
    for a in addrs:
        book.add_address(a, src)
    # one entry has failed MAX_FAILURES times and never succeeded
    bad = addrs[7]
    for _ in range(addrbook_mod.MAX_FAILURES):
        book.mark_attempt(bad)
    filler = NetAddress.parse("tcp://10.8.1.1:26656")
    # same group+src so it maps to the same (now full) bucket
    assert book.add_address(filler, src)
    assert not book.has(bad)              # the bad entry was the evictee
    assert book.has(filler)


def test_addrbook_promotion_and_demotion():
    """mark_good moves new->old; a full old bucket demotes a random old
    member back to a new bucket (reference moveToOld)."""
    book = AddrBook()
    src = "10.99.0.1:26656"
    n = addrbook_mod.BUCKET_SIZE + 1
    addrs = [NetAddress.parse(f"tcp://10.9.0.{i + 1}:26656")
             for i in range(n)]
    for a in addrs:
        book.add_address(a, src)
        book.mark_good(a)                 # all promote to the SAME old
    ents = [book._entries[a.dial_string()] for a in addrs]
    olds = [e for e in ents if e.old]
    news = [e for e in ents if not e.old]
    assert len(olds) == addrbook_mod.BUCKET_SIZE
    assert len(news) == 1                 # one demoted back to new
    # promotion resets the failure counter
    assert all(e.attempts == 0 for e in olds)


def test_addrbook_persistence_roundtrip_property(tmp_path):
    """Random books survive save/load with status, attempts and
    timestamps intact (reference JSON dump round-trip)."""
    import random as _random
    rng = _random.Random(42)
    path = str(tmp_path / "book.json")
    book = AddrBook(path)
    want = {}
    for i in range(200):
        a = NetAddress.parse(
            f"tcp://10.{rng.randrange(50)}.{rng.randrange(250)}."
            f"{rng.randrange(1, 250)}:{26656 + rng.randrange(4)}")
        if not book.add_address(a, f"10.99.0.{rng.randrange(1, 5)}:26656"):
            continue
        for _ in range(rng.randrange(3)):
            book.mark_attempt(a)
        if rng.random() < 0.4:
            book.mark_good(a)
        e = book._entries[a.dial_string()]
        want[a.dial_string()] = (e.old, e.attempts, e.last_success,
                                 e.last_attempt)
    book.save()
    loaded = AddrBook(path)
    assert loaded.size() == book.size()
    for key, (old, attempts, last_s, last_a) in want.items():
        e = loaded._entries[key]
        assert (e.old, e.attempts) == (old, attempts), key
        assert e.last_success == last_s and e.last_attempt == last_a
    # old/new split survives: picks still work
    assert loaded.pick_address() is not None
