"""The BlockPool's timeout rule on a fake clock: it times out peers that
stop delivering, not requests that wait their turn in a peer's queue.

P1  a request behind earlier ones to the same peer is not overdue by its
    age while that peer keeps delivering;
P2  a peer gone silent is re-requested elsewhere and evicted as before;
P3  a request a live peer passed over is still re-requested in bounded
    time;
P4  a bad block is refused and its deliverer evicted, whatever the peer
    delivered before.

The healthy scenario is the `catchup-1ktx-100v` deployment's boot: 300
requests over 16 peers, each peer one 273 KB block every 0.53 s."""

import types

import pytest

import tendermint_tpu.blockchain.pool as pool_mod
from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.utils import flowrate, tracing

BLOCK_BYTES = 273_000
BLOCK_S = 0.53                   # 273 KB at 512 KB/s


class FakeBlock:
    def __init__(self, height):
        self.height = height


class Clock:
    def __init__(self):
        self.t = 1_000.0

    def monotonic(self) -> float:
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    fake = types.SimpleNamespace(monotonic=c.monotonic)
    monkeypatch.setattr(pool_mod, "time", fake)
    monkeypatch.setattr(flowrate, "time", fake)
    return c


def new_pool(peers, tip=100_000):
    pool = BlockPool(start_height=1)
    evicted = []
    pool.on_evict = lambda pid, reason: evicted.append((pid, reason))
    for p in peers:
        pool.set_peer_height(p, tip)
    return pool, evicted


def instants_since(t_epoch: float, name: str) -> list[dict]:
    return [s["args"] for s in tracing.RECORDER.since(t_epoch)
            if s["ts"] >= t_epoch and s["name"] == name]


class Sources:
    """Peers that answer their requests in the order they came, one
    block every `block_s` seconds each, unless told to stay silent or to
    pass a height over."""

    def __init__(self, pool, clock, block_s=BLOCK_S):
        self.pool, self.clock, self.block_s = pool, clock, block_s
        self.queues: dict[str, list[int]] = {}
        self.done_at: dict[str, float] = {}
        self.silent: set[str] = set()
        self.skip: set[int] = set()
        self.late = 0

    def request(self, reqs):
        for h, p in reqs:
            self.queues.setdefault(p, []).append(h)

    def deliver_due(self):
        for p, q in self.queues.items():
            while q and q[0] in self.skip:
                q.pop(0)
            if not q or p in self.silent:
                self.done_at.pop(p, None)
                continue
            due = self.done_at.setdefault(p, self.clock.t + self.block_s)
            if self.clock.t >= due:
                h = q.pop(0)
                if self.pool.add_block(p, FakeBlock(h)):
                    self.pool.record_bytes(p, BLOCK_BYTES)
                else:
                    self.late += 1
                self.done_at.pop(p)
                if q:                 # back to back: the link stays full
                    self.done_at[p] = due + self.block_s

    def run(self, seconds, step=0.01, apply_per_s=0.0):
        """Advance the clock; the consumer pops `apply_per_s` contiguous
        blocks a second, as apply does."""
        owed = 0.0
        end = self.clock.t + seconds
        while self.clock.t < end:
            self.clock.t += step
            self.deliver_due()
            owed += apply_per_s * step
            n = min(int(owed), len(self.pool.peek_contiguous(int(owed))))
            if n:
                self.pool.pop(n)
                owed -= n
            owed = min(owed, 1.0)
            self.request(self.pool.schedule())


@pytest.mark.parametrize("apply_per_s", [0.0, 12.0, 40.0])
def test_p1_requests_that_wait_their_turn_are_not_overdue(clock,
                                                          apply_per_s):
    """16 peers, 300 in flight, one block a peer every 0.53 s: a peer
    holds ~19 requests, the last answered ~10 s after it was sent.  Over
    30 s nobody is re-requested, nobody evicted, no block arrives late:
    with apply stalled (boot), at the deployment's pace, and faster than
    the link."""
    t0 = tracing.now_epoch()
    peers = [f"peer-{i:02d}-" + "x" * 8 for i in range(16)]
    pool, evicted = new_pool(peers)
    src = Sources(pool, clock)
    first = pool.schedule()
    assert len(first) == pool_mod.MAX_PENDING
    src.request(first)
    assert max(len(q) for q in src.queues.values()) == 19
    src.run(30.0, apply_per_s=apply_per_s)
    assert instants_since(t0, "pool.rerequest") == []
    assert instants_since(t0, "pool.evict") == [] and evicted == []
    assert src.late == 0 and pool.num_peers() == 16
    # all that was asked for came: the link's 30 blocks/s where apply
    # outruns it, else what apply made room for
    st = pool.status()
    delivered = st["ready"] + st["next_height"] - 1
    assert delivered >= min(16 * int(29 / BLOCK_S),
                            pool_mod.MAX_PENDING + int(29 * apply_per_s))
    if apply_per_s > 16 / BLOCK_S:
        assert max(st["peer_idle_s"].values()) < 2 * BLOCK_S


@pytest.mark.parametrize("delivered_first", [0, 3])
def test_p2_a_silent_peer_is_rerequested_and_evicted(clock, delivered_first):
    """A peer with requests outstanding that delivers nothing for
    REQUEST_TIMEOUT is timed out, whether it never answered or answered
    a few and stopped: its requests go to the other peer in one pass
    (reason `silent`) and it is evicted for "request timeouts"."""
    t0 = tracing.now_epoch()
    pool, evicted = new_pool(["quiet", "steady"], tip=40)
    src = Sources(pool, clock)
    src.request(pool.schedule())
    held = list(src.queues["quiet"])
    assert len(held) == 20
    src.run(delivered_first * BLOCK_S + 0.02)
    src.silent.add("quiet")
    got = [h for h in held if pool._slots[h].block is not None]
    assert len(got) == delivered_first
    src.run(pool_mod.REQUEST_TIMEOUT - 0.1)
    assert instants_since(t0, "pool.rerequest") == [] and not evicted
    src.run(0.2 + delivered_first * BLOCK_S)
    redone = instants_since(t0, "pool.rerequest")
    assert sorted(a["height"] for a in redone) == held[delivered_first:]
    assert {(a["old"], a["new"], a["reason"]) for a in redone} == {
        ("quiet", "steady", "silent")}
    assert evicted == [("quiet", "request timeouts")]
    assert [a["reason"] for a in instants_since(t0, "pool.evict")] == [
        "request timeouts"]
    # the steady peer, 40 deep by now, is never timed out, and serves all
    src.run(40 * BLOCK_S)
    assert [b.height for b in pool.peek_contiguous(40)] == list(range(1, 41))
    assert len(instants_since(t0, "pool.rerequest")) == len(redone)
    assert pool.num_peers() == 1


def test_p3_a_request_a_live_peer_passed_over_is_rerequested(clock):
    """`busy` answers everything but one height, in order and without a
    pause, so its own clock never runs out; the height it passed over is
    asked of the other peer no later than REQUEST_TIMEOUT after it was
    sent (reason `skipped`), and nothing else of `busy`'s queue is."""
    t0 = tracing.now_epoch()
    pool, evicted = new_pool(["busy", "other"], tip=60)
    src = Sources(pool, clock)
    sent_at = clock.t
    src.request(pool.schedule())
    passed_over = src.queues["busy"][1]
    src.skip.add(passed_over)
    src.run(pool_mod.REQUEST_TIMEOUT - 0.05)
    assert instants_since(t0, "pool.rerequest") == []
    src.run(0.1)
    redone = instants_since(t0, "pool.rerequest")
    assert redone == [{"height": passed_over, "old": "busy", "new": "other",
                       "reason": "skipped"}]
    assert clock.t - sent_at <= pool_mod.REQUEST_TIMEOUT + 0.1
    src.skip.clear()
    src.run(32 * BLOCK_S)
    assert instants_since(t0, "pool.rerequest") == redone and not evicted
    assert [b.height for b in pool.peek_contiguous(60)] == list(range(1, 61))


def test_p3_a_passed_over_request_sent_late_waits_its_own_timeout(clock):
    """The bound is the request's own age: one sent 2 s ago and passed
    over now is re-requested 1 s from now, not at once."""
    t0 = tracing.now_epoch()
    pool, _ = new_pool(["busy", "other"], tip=4)
    first = dict(pool.schedule())
    mine = sorted(h for h, p in first.items() if p == "busy")
    clock.t += 2.0
    assert pool.add_block("busy", FakeBlock(mine[1]))      # passes mine[0]
    for h, p in first.items():
        if p == "other":
            assert pool.add_block("other", FakeBlock(h))
    assert pool.schedule() == []
    clock.t += pool_mod.REQUEST_TIMEOUT - 2.0 + 0.01
    assert pool.schedule() == [(mine[0], "other")]
    assert [a["reason"] for a in instants_since(t0, "pool.rerequest")] == [
        "skipped"]


def test_an_evicted_silent_peer_keeps_what_it_delivered(clock):
    """Eviction for silence drops the peer's UNDELIVERED slots (the next
    pass asks the others for them); blocks it had delivered stay."""
    pool, evicted = new_pool(["quiet", "steady"], tip=40)
    src = Sources(pool, clock)
    src.request(pool.schedule())
    src.run(4 * BLOCK_S + 0.02)
    delivered = {h for h, s in pool._slots.items()
                 if s.peer_id == "quiet" and s.block is not None}
    assert len(delivered) == 4
    # the chain grows: new requests reach `quiet` a second after its last
    # delivery, so they are younger than the timeout when it is evicted
    src.silent.add("quiet")
    src.run(1.0)
    pool.set_peer_height("quiet", 50)
    pool.set_peer_height("steady", 50)
    more = pool.schedule()
    fresh = [h for h, p in more if p == "quiet"]
    assert fresh
    src.request(more)
    while not evicted:
        clock.t += 0.01
        src.deliver_due()
        src.request(pool.schedule())
    assert evicted == [("quiet", "request timeouts")]
    left = {h: s for h, s in pool._slots.items() if s.peer_id == "quiet"}
    assert set(left) == {h for h in delivered if h >= pool.next_height}
    assert all(s.block is not None for s in left.values())
    assert not any(h in pool._slots for h in fresh)
    src.request(pool.schedule())
    assert all(pool._slots[h].peer_id == "steady" for h in fresh)


def test_p4_a_bad_block_still_bans_its_deliverer(clock):
    """`redo` drops the block, evicts the peer that delivered it and
    every block of that peer's, however well it had been delivering."""
    t0 = tracing.now_epoch()
    pool, evicted = new_pool(["liar", "honest"], tip=40)
    src = Sources(pool, clock)
    src.request(pool.schedule())
    src.run(6 * BLOCK_S)
    bad = min(h for h, s in pool._slots.items()
              if s.peer_id == "liar" and s.block is not None)
    pool.redo(bad)
    assert evicted == [("liar", f"bad block at height {bad}")]
    assert [a["height"] for a in instants_since(t0, "pool.redo")] == [bad]
    assert not any(s.peer_id == "liar" for s in pool._slots.values())
    assert "liar" not in pool.status()["peer_idle_s"]
    src.queues.pop("liar")
    src.run(42 * BLOCK_S)
    assert [b.height for b in pool.peek_contiguous(40)] == list(range(1, 41))
    assert instants_since(t0, "pool.rerequest") == []


def test_status_shows_each_peers_queue_and_silence(clock):
    pool, _ = new_pool(["a-peer", "b-peer"], tip=10)
    reqs = pool.schedule()
    st = pool.status()
    assert st["peer_outstanding"] == {"a-peer": 5, "b-peer": 5}
    assert st["peer_idle_s"] == {"a-peer": None, "b-peer": None}
    h = next(h for h, p in reqs if p == "a-peer")
    pool.add_block("a-peer", FakeBlock(h))
    clock.t += 1.25
    st = pool.status()
    assert st["peer_outstanding"] == {"a-peer": 4, "b-peer": 5}
    assert st["peer_idle_s"] == {"a-peer": 1.25, "b-peer": None}


# -- a block on the wire is its peer's progress ------------------------------

def _one_peer_with_requests(clock):
    pool, evicted = new_pool(["slow", "idle"], tip=20)
    pool.schedule()
    t0 = tracing.now_epoch()
    return pool, evicted, t0


@pytest.mark.parametrize("bytes_per_s,survives", [
    (65_000, True),       # a boot's trickle: a block every 4.2 s
    (10_500, True),       # just over the reference's minRecvRate
    (9_000, False),       # a drip under it
    (0, False)])          # nothing at all
def test_p1_a_block_on_the_wire_counts_as_its_peers_progress(
        clock, bytes_per_s, survives):
    """While a boot's compiles hold the GIL a peer's 273 KB block takes
    over four seconds to arrive.  The reactor reports what has arrived
    of it every tick: at `min_recv_rate` or more the peer is delivering
    and is not timed out; slower than that it is silent, as before."""
    pool, evicted, t0 = _one_peer_with_requests(clock)
    got = 0.0
    for _ in range(40):                       # 4 s, no whole block yet
        clock.t += 0.1
        got += bytes_per_s * 0.1
        pool.note_receiving({"slow": int(got), "idle": 0})
        pool.schedule()
    redone = instants_since(t0, "pool.rerequest")
    assert {a["old"] for a in redone} == ({"idle"} if survives
                                          else {"idle", "slow"})
    assert ("slow", "request timeouts") not in evicted if survives else \
        ("slow", "request timeouts") in evicted
    assert ("idle", "request timeouts") in evicted


def test_bytes_after_a_pause_count_only_at_the_rate_since_the_last_credit(
        clock):
    """2 KB after 2.9 s of nothing are 0.7 KB/s: no credit, the peer
    times out on schedule.  The same 2 KB within 0.1 s would have been."""
    pool, evicted, t0 = _one_peer_with_requests(clock)
    pool.note_receiving({"slow": 50_000})
    clock.t += 2.9
    pool.note_receiving({"slow": 52_000})
    clock.t += 0.11
    pool.schedule()
    assert ("slow", "request timeouts") in evicted
    # and a message that ended (the count fell) is no credit by itself
    pool2, evicted2, _ = _one_peer_with_requests(clock)
    pool2.note_receiving({"slow": 50_000})
    clock.t += 2.0
    pool2.note_receiving({"slow": 0})
    clock.t += 1.01
    pool2.schedule()
    assert ("slow", "request timeouts") in evicted2
    pool2.note_receiving({"nobody": 1 << 20})      # unknown peer: ignored
    assert "nobody" not in pool2.status()["peer_idle_s"]


def _stream(pool, clock, peer, seconds, bytes_per_s, refused=None,
            msg_bytes=BLOCK_BYTES, step=0.1):
    """`peer` sends message after message of `msg_bytes` at
    `bytes_per_s`, the reactor looking every `step`; each that ends is a
    decodable block nobody asked that peer for, which `add_block`
    refuses (the reactor's `pool.late_block`)."""
    got, height = 0.0, 1_000_000
    end = clock.t + seconds
    while clock.t < end:
        clock.t += step
        got += bytes_per_s * step
        while got >= msg_bytes:
            got -= msg_bytes
            height += 1
            assert not pool.add_block(peer, FakeBlock(height))
            if refused is not None:
                refused.append(height)
        pool.note_receiving({peer: int(got)})
        pool.schedule()


@pytest.mark.parametrize("bytes_per_s", [500_000, 12_000])
@pytest.mark.parametrize("accepted_first", [0, 3])
def test_p2_a_peer_that_streams_blocks_nobody_asked_for_is_silent(
        clock, accepted_first, bytes_per_s):
    """Bytes on the wire are no delivery until the pool accepts the
    block they make.  A peer that answers none of its requests and
    streams other blocks instead, at the link's rate or just over
    `min_recv_rate`, whether or not it had delivered before, holds its
    clock for `WIRE_HOLD` and no longer: it is re-requested and evicted
    `WIRE_HOLD + REQUEST_TIMEOUT` after its last accepted block, and
    every message that completes is refused."""
    pool, evicted = new_pool(["loud", "good"], tip=40)
    reqs = pool.schedule()
    mine = [h for h, p in reqs if p == "loud"]
    for h in mine[:accepted_first]:
        clock.t += BLOCK_S
        assert pool.add_block("loud", FakeBlock(h))
        pool.record_bytes("loud", BLOCK_BYTES)
    for h in (h for h, p in reqs if p == "good"):
        assert pool.add_block("good", FakeBlock(h))
    t0, start, refused = tracing.now_epoch(), clock.t, []
    limit = pool_mod.WIRE_HOLD + pool_mod.REQUEST_TIMEOUT
    _stream(pool, clock, "loud", limit - 0.2, bytes_per_s, refused)
    assert evicted == [] and instants_since(t0, "pool.rerequest") == []
    _stream(pool, clock, "loud", 0.4, bytes_per_s, refused)
    assert clock.t - start <= limit + 0.3
    assert evicted == [("loud", "request timeouts")]
    redone = instants_since(t0, "pool.rerequest")
    assert {a["height"] for a in redone} == set(mine[accepted_first:])
    assert {(a["old"], a["new"], a["reason"]) for a in redone} == {
        ("loud", "good", "silent")}
    assert (len(refused) > 10) == (bytes_per_s == 500_000)
    assert pool.num_peers() == 1 and pool.status()["ready"] == \
        len(reqs) - len(mine) + accepted_first


def test_a_block_that_outlasts_the_hold_is_rerequested_all_the_same(clock):
    """One 273 KB block at 13 KB/s takes 21 s: over `min_recv_rate`, so
    no drip, and its bytes hold the clock; but only for `WIRE_HOLD`.
    The request goes to another peer `WIRE_HOLD + REQUEST_TIMEOUT`
    after it was sent, where the parent gave it `REQUEST_TIMEOUT`."""
    pool, evicted, t0 = _one_peer_with_requests(clock)
    start = clock.t
    for h, p in [(s.height, s.peer_id) for s in pool._slots.values()]:
        if p == "idle":
            assert pool.add_block("idle", FakeBlock(h))
    _stream(pool, clock, "slow", 14.8, 13_000)
    assert instants_since(t0, "pool.rerequest") == [] and evicted == []
    _stream(pool, clock, "slow", 0.4, 13_000)
    assert clock.t - start == pytest.approx(15.2)
    assert {a["old"] for a in instants_since(t0, "pool.rerequest")} == {"slow"}
    assert evicted == [("slow", "request timeouts")]


def test_the_reactor_reads_each_peers_connection_before_it_schedules(clock):
    """`_send_requests` hands the pool what has arrived of each peer's
    unfinished block, from the p2p connection, on every tick."""
    from tendermint_tpu.blockchain.reactor import (BLOCKCHAIN_CHANNEL,
                                                   BlockchainReactor)

    class FakePeer:
        def __init__(self, pid):
            self.id, self.sent, self.partial, self.asked = pid, [], 0, []

        def receiving(self, ch_id):
            self.asked.append(ch_id)
            return self.partial

        def try_send(self, ch_id, raw):
            self.sent.append(raw)
            return True

    class FakeSwitch:
        def __init__(self, peers):
            self._peers = {p.id: p for p in peers}

        def peers(self):
            return list(self._peers.values())

        def get_peer(self, pid):
            return self._peers.get(pid)

    bc = BlockchainReactor.__new__(BlockchainReactor)
    bc.pool, _ = new_pool(["slow", "idle"], tip=20)
    slow, idle = FakePeer("slow"), FakePeer("idle")
    bc.switch = FakeSwitch([slow, idle])
    t0 = tracing.now_epoch()
    bc._send_requests()
    assert len(slow.sent) == len(idle.sent) == 10
    for _ in range(40):
        clock.t += 0.1
        slow.partial += 6_500
        bc._send_requests()
    assert set(slow.asked) == {BLOCKCHAIN_CHANNEL}
    assert {a["old"] for a in instants_since(t0, "pool.rerequest")} == {"idle"}
    # bytes on the wire hold the clock; only an accepted block is a delivery
    assert bc.pool.status()["peer_idle_s"]["slow"] is None


def test_blocks_the_reactor_receives_and_refuses_restart_no_clock(clock):
    """Through the reactor: `loud` answers none of its requests and
    sends a real, decodable block again and again (one that `good` was
    asked for and has delivered), its bytes growing in its connection
    between the reactor's looks.  Every one that completes is a
    `pool.late_block` and no strike, the bytes hold `loud`'s clock for
    `WIRE_HOLD`, and then its requests go to `good` and it is evicted:
    fast-sync cannot be stalled by a peer that only looks busy."""
    from chainutil import (build_chain, kvstore_app_hashes, make_genesis,
                           make_validators)
    from tendermint_tpu.blockchain import messages as BM
    from tendermint_tpu.blockchain.reactor import (BLOCKCHAIN_CHANNEL,
                                                   BlockchainReactor)
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB

    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, "wire-chain", 20,
                        app_hashes=kvstore_app_hashes(20))
    raws = {b.height: BM.encode_msg(BM.BlockResponse(b.encode()))
            for b, _ps, _seen in chain}

    class FakePeer:
        def __init__(self, pid):
            self.id, self.partial, self.asked = pid, 0, []

        def receiving(self, ch_id):
            return self.partial

        def try_send(self, ch_id, msg):
            self.asked.append(BM.decode_msg(msg).height)
            return True

    class FakeSwitch:
        def __init__(self, peers):
            self._peers = {p.id: p for p in peers}

        def peers(self):
            return list(self._peers.values())

        def get_peer(self, pid):
            return self._peers.get(pid)

        def stop_peer_for_error(self, peer, reason):
            self._peers.pop(peer.id, None)

    bc = BlockchainReactor(
        get_state(MemDB(), make_genesis("wire-chain", privs)),
        ClientCreator("kvstore").new_app_conns().consensus,
        BlockStore(MemDB()), fast_sync=True)
    good, loud = FakePeer("good"), FakePeer("loud")
    bc.set_switch(FakeSwitch([good, loud]))
    bc.pool.set_peer_height("good", 20)
    bc.pool.set_peer_height("loud", 20)
    bc._send_requests()
    mine, answered = list(loud.asked), list(good.asked)
    assert len(mine) == len(answered) == 10
    for h in answered:                        # `good` answers at once
        bc.receive(BLOCKCHAIN_CHANNEL, good, raws[h])
    t0, start = tracing.now_epoch(), clock.t
    raw = raws[answered[0]]                   # what `loud` sends instead
    limit = pool_mod.WIRE_HOLD + pool_mod.REQUEST_TIMEOUT
    refused = 0
    while bc.switch.get_peer("loud") is not None and \
            clock.t - start < 2 * limit:
        clock.t += 0.1
        loud.partial += 50_000                # 500 KB/s
        if loud.partial >= BLOCK_BYTES:       # the message completes
            loud.partial = 0
            bc.receive(BLOCKCHAIN_CHANNEL, loud, raw)
            refused += 1
        if clock.t - start < limit - 0.2:
            assert bc.pool.status()["peer_outstanding"]["loud"] == len(mine)
        bc._send_requests()
    assert limit - 0.2 <= clock.t - start <= limit + 0.2
    late = instants_since(t0, "pool.late_block")
    assert len(late) == refused >= 25 and {a["peer"] for a in late} == {"loud"}
    assert [a["reason"] for a in instants_since(t0, "pool.evict")] == [
        "request timeouts"]
    assert {(a["old"], a["new"], a["reason"]) for a in
            instants_since(t0, "pool.rerequest")} == {("loud", "good", "silent")}
    assert good.asked == answered + mine      # all asked of `good` now
    assert bc.pool.status()["ready"] == len(answered)


@pytest.mark.parametrize("reported", [True, False])
def test_a_block_on_the_wire_is_no_slow_drip(clock, reported):
    """The receive meter sees whole blocks, so between two 273 KB blocks
    that take 8 s each (a boot: 34 KB/s a peer) it decays under
    `min_recv_rate` and the slow-drip rule would evict a peer that is
    sending all the while.  With its bytes reported it stays; unreported
    (the meter alone) it goes, as a peer that drips whole tiny blocks
    still does (`tests/test_fastsync.py::test_pool_evicts_slow_drip_peer`)."""
    pool, evicted = new_pool(["starved"], tip=10)
    first = pool.schedule()
    clock.t += 0.5
    assert pool.add_block("starved", FakeBlock(first[0][0]))
    pool.record_bytes("starved", BLOCK_BYTES)
    got = 0
    for _ in range(79):                       # 7.9 s, the next block crossing
        clock.t += 0.1
        got += 3_400
        if reported:
            pool.note_receiving({"starved": got})
        pool.schedule()
    assert (evicted == []) if reported else (
        evicted == [("starved", "request timeouts")])


@pytest.mark.parametrize("reported", [True, False])
def test_peers_that_owed_nothing_during_a_stall_are_no_slow_drip(clock,
                                                                 reported):
    """The sync thread is away 29 s (a boot's window: seen on the chip)
    with every request answered, so the peers owe nothing and their
    meters decay to nothing.  Then a window is popped and each gets four
    requests, a block taking 1.2 s at the 225 KB/s a peer has of a busy
    link.  The reactor looked before it asked (nothing crossing: the
    next message is measured from that look), so the blocks on the wire
    are progress and nobody goes; on the meters alone the slow-drip rule
    evicts all 16 a second later, as the parent does."""
    peers = [f"peer-{i:02d}-" + "x" * 8 for i in range(16)]
    pool, evicted = new_pool(peers)
    for h, p in pool.schedule():
        assert pool.add_block(p, FakeBlock(h))
        pool.record_bytes(p, BLOCK_BYTES)
    clock.t += 29.0
    pool.pop(64)
    if reported:
        pool.note_receiving({p: 0 for p in peers})
    reqs = pool.schedule()
    assert len(reqs) == 64
    todo = {p: [h for h, q in reqs if q == p] for p in peers}
    got = dict.fromkeys(peers, 0.0)
    for _ in range(600):                      # 6 s
        clock.t += 0.01
        for p in peers:
            got[p] = got[p] + 2_250 if todo[p] else 0.0
            if got[p] >= BLOCK_BYTES:
                got[p] -= BLOCK_BYTES
                if pool.add_block(p, FakeBlock(todo[p].pop(0))):
                    pool.record_bytes(p, BLOCK_BYTES)
        if reported:
            pool.note_receiving({p: int(n) for p, n in got.items()})
        pool.schedule()
    if reported:
        assert evicted == [] and not any(todo.values())
    else:
        assert sorted(evicted) == [(p, "request timeouts") for p in peers]


@pytest.mark.parametrize("next_block_bytes,stays", [(150_000, True),
                                                    (20_000, False)])
def test_the_first_look_after_a_window_measures_from_the_last_delivery(
        clock, next_block_bytes, stays):
    """The reactor cannot look while a window applies (seconds).  Its
    first look after one credits the block now crossing by what has come
    since the peer's last DELIVERY, not since the last look: 150 KB in
    the 6 s since (25 KB/s) is a peer delivering, though the look before
    was 25 s ago and its meter has decayed; 20 KB (3 KB/s) is a drip."""
    pool, evicted = new_pool(["starved"], tip=10)
    reqs = pool.schedule()
    pool.note_receiving({"starved": 40_000})       # the last look
    clock.t += 19.0
    assert pool.add_block("starved", FakeBlock(reqs[0][0]))
    pool.record_bytes("starved", BLOCK_BYTES)
    clock.t += 6.0                                 # still applying
    pool.note_receiving({"starved": next_block_bytes})
    pool.schedule()
    assert (evicted == []) if stays else (
        evicted == [("starved", "request timeouts")])
