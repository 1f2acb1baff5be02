"""Fast-sync: BlockPool scheduling and the batched SYNC_LOOP end-to-end.

Modeled on the reference's `blockchain/pool_test.go` and the
`test/p2p/fast_sync` integration scenario: a fresh node downloads,
batch-verifies, and applies a chain served by peers, then hands off to
consensus.
"""

import threading
import time

import pytest

from tendermint_tpu.blockchain import messages as BM
from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.blockchain.reactor import (BLOCKCHAIN_CHANNEL,
                                               BlockchainReactor)
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.config import test_config as fast_config
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.p2p import connect_switches, make_switch
from tendermint_tpu.state import execution
from tendermint_tpu.state.state import get_state
from tendermint_tpu.utils.db import MemDB

from chainutil import (build_chain, kvstore_app_hashes, make_genesis,
                       make_validators)

CHAIN = "fastsync-chain"


@pytest.fixture(autouse=True)
def _python_backend():
    old = cb._current
    cb.set_backend("python")
    yield
    cb._current = old


# -- pool unit tests --------------------------------------------------------

class FakeBlock:
    def __init__(self, height):
        self.height = height


def test_pool_schedules_and_delivers():
    pool = BlockPool(start_height=1)
    pool.set_peer_height("p1", 10)
    pool.set_peer_height("p2", 5)
    reqs = pool.schedule()
    heights = sorted(h for h, _ in reqs)
    assert heights == list(range(1, 11))
    # p2 never asked beyond its height
    assert all(h <= 5 for h, p in reqs if p == "p2")
    # wrong peer delivering is rejected
    by_height = {h: p for h, p in reqs}
    wrong = "p1" if by_height[1] == "p2" else "p2"
    assert not pool.add_block(wrong, FakeBlock(1))
    assert pool.add_block(by_height[1], FakeBlock(1))
    assert pool.add_block(by_height[3], FakeBlock(3))
    # only contiguous blocks peek
    got = pool.peek_contiguous(5)
    assert [b.height for b in got] == [1]
    assert pool.add_block(by_height[2], FakeBlock(2))
    got = pool.peek_contiguous(5)
    assert [b.height for b in got] == [1, 2, 3]
    pool.pop(3)
    assert pool.next_height == 4
    assert not pool.is_caught_up()


def test_pool_timeout_redo_and_eviction(monkeypatch):
    import tendermint_tpu.blockchain.pool as pool_mod
    monkeypatch.setattr(pool_mod, "REQUEST_TIMEOUT", 0.05)
    monkeypatch.setattr(pool_mod, "MAX_PEER_TIMEOUTS", 2)
    evicted = []
    pool = BlockPool(start_height=1)
    pool.on_evict = lambda p, r: evicted.append(p)
    pool.set_peer_height("dead", 5)
    pool.set_peer_height("live", 5)

    def drive(reqs):
        # "live" answers immediately; "dead" never does
        for h, p in reqs:
            if p == "live":
                pool.add_block("live", FakeBlock(h))
    drive(pool.schedule())
    deadline = time.time() + 5
    while "dead" not in evicted and time.time() < deadline:
        drive(pool.schedule())
        time.sleep(0.02)
    assert evicted == ["dead"]
    drive(pool.schedule())
    deadline = time.time() + 5
    while len(pool.peek_contiguous(5)) < 5 and time.time() < deadline:
        drive(pool.schedule())
        time.sleep(0.02)
    # every height was eventually served by the live peer
    assert [b.height for b in pool.peek_contiguous(5)] == [1, 2, 3, 4, 5]


def test_timed_out_slot_is_rerequested_and_the_late_answer_dropped(
        monkeypatch):
    """A request that times out goes to another peer (one
    `pool.rerequest`); the first peer's answer then arrives for a slot
    that is no longer its own: `add_block` says no and the reactor
    counts one `pool.late_block` with the bytes that were thrown away."""
    import tendermint_tpu.blockchain.pool as pool_mod
    from tendermint_tpu.utils import tracing
    monkeypatch.setattr(pool_mod, "REQUEST_TIMEOUT", 0.05)
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    (block, _ps, _seen), = build_chain(privs, vs, CHAIN, 1,
                                       app_hashes=kvstore_app_hashes(1))
    state = get_state(MemDB(), gen)
    conns = ClientCreator("kvstore").new_app_conns()
    bc = BlockchainReactor(state, conns.consensus, BlockStore(MemDB()),
                           fast_sync=True)

    class FakePeer:
        def __init__(self, pid):
            self.id = pid

    t_start = tracing.now_epoch()
    bc.pool.set_peer_height("first-peer-id", 1)
    assert bc.pool.schedule() == [(1, "first-peer-id")]
    bc.pool.set_peer_height("second-peer-id", 1)
    time.sleep(0.08)
    assert bc.pool.schedule() == [(1, "second-peer-id")]
    raw = BM.encode_msg(BM.BlockResponse(block.encode()))
    assert not bc.pool.add_block("first-peer-id", block)
    bc.receive(BLOCKCHAIN_CHANNEL, FakePeer("first-peer-id"), raw)
    assert bc.pool.peek_contiguous(2) == []        # dropped, not stored
    bc.receive(BLOCKCHAIN_CHANNEL, FakePeer("second-peer-id"), raw)
    assert [b.height for b in bc.pool.peek_contiguous(2)] == [1]
    # a second answer for a slot already filled is late too
    bc.receive(BLOCKCHAIN_CHANNEL, FakePeer("second-peer-id"), raw)

    mine = [s for s in tracing.RECORDER.since(t_start)
            if s["ts"] >= t_start and s["name"].startswith("pool.")]
    assert [(s["name"], s["ph"], s["args"]) for s in mine] == [
        ("pool.rerequest", "i", {"height": 1, "old": "first-peer-i",
                                 "new": "second-peer-", "reason": "silent"}),
        ("pool.late_block", "i", {"height": 1, "peer": "first-peer-i",
                                  "bytes": len(raw)}),
        ("pool.late_block", "i", {"height": 1, "peer": "second-peer-",
                                  "bytes": len(raw)})]
    # neither is a refusal: nobody was evicted, nothing redone
    assert bc.pool.num_peers() == 2


def test_pool_caught_up():
    pool = BlockPool(start_height=11)
    assert not pool.is_caught_up()     # no peers yet
    pool.set_peer_height("p", 10)
    assert pool.is_caught_up()         # synced past the best peer
    pool.set_peer_height("p", 30)
    assert not pool.is_caught_up()


# -- the hand-over ----------------------------------------------------------

def _reactor_at_the_tip(step):
    """A reactor whose pool says it has caught up and whose sync step is
    `step(bc)`: the routine runs in the test's own thread, one pass."""
    privs, _vs = make_validators(4)
    state = get_state(MemDB(), make_genesis(CHAIN, privs))
    conns = ClientCreator("kvstore").new_app_conns()
    bc = BlockchainReactor(state, conns.consensus, BlockStore(MemDB()),
                           fast_sync=True)

    class TipPool:
        next_height = 1

        def is_caught_up(self):
            return True

    bc.pool = TipPool()
    bc._sync_step = lambda: step(bc)
    handed = []
    bc.on_caught_up = handed.append
    bc._pool_routine()
    return bc, handed


def test_a_reactor_stopped_in_its_last_window_does_not_hand_over():
    """`stop()` lands while the last window is applied: the routine ends
    without switching to consensus, so no live warm-up starts either."""
    def last_window(bc):
        bc.stop()
        return True
    bc, handed = _reactor_at_the_tip(last_window)
    assert handed == [] and not bc._switched


def test_a_reactor_still_hands_over_when_not_stopped():
    bc, handed = _reactor_at_the_tip(lambda bc: True)
    assert handed == [bc.state] and bc._switched


# -- e2e --------------------------------------------------------------------

N_BLOCKS = 24


def _source_node(chain, gen):
    """A served chain: store + state advanced to the chain tip."""
    state = get_state(MemDB(), gen)
    conns = ClientCreator("kvstore").new_app_conns()
    store = BlockStore(MemDB())
    for block, ps, seen in chain:
        store.save_block(block, ps, seen)
        execution.apply_block(state, None, conns.consensus, block,
                              ps.header, execution.MockMempool(),
                              check_last_commit=False)
    reactor = BlockchainReactor(state, conns.consensus, store,
                                fast_sync=False)
    sw = make_switch(CHAIN, {"blockchain": reactor}, moniker="source")
    return sw, state, store


def _sync_node(gen, batch_size=8):
    state = get_state(MemDB(), gen)
    conns = ClientCreator("kvstore").new_app_conns()
    store = BlockStore(MemDB())
    mp = Mempool(conns.mempool)
    cs = ConsensusState(fast_config().consensus, state.copy(),
                        conns.consensus, store, mp)
    cons_reactor = ConsensusReactor(cs, fast_sync=True)
    bc_reactor = BlockchainReactor(state, conns.consensus, store,
                                   fast_sync=True, batch_size=batch_size)
    bc_reactor.on_caught_up = cons_reactor.switch_to_consensus
    sw = make_switch(CHAIN, {"blockchain": bc_reactor,
                             "consensus": cons_reactor}, moniker="syncer")
    return sw, bc_reactor, cons_reactor, store


def _stop_nodes(bc, cons, *switches):
    """Stop the nets, then see the sync thread out.  A test may return
    while the last window is still being applied (a block is stored
    before it is applied); the sync thread then hands over to consensus
    AFTER `Switch.stop()` has stopped it, and the commit that hand-over
    verifies reaches the crypto plane's one worker once the module's
    fixture has put the default backend back: a `TpuBackend` comes up on
    the CPU and the next test's first window waits 30 s and more behind
    its table build (the driver's failed run, PR 29's tree: height 0 at
    the deadline with all 40 blocks in the pool)."""
    for sw in switches:
        sw.stop()
    if bc._thread is not None:
        bc._thread.join(timeout=30)
        assert not bc._thread.is_alive(), "sync thread still running"
    cons.cs.stop()


def test_fast_sync_end_to_end():
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    hashes = kvstore_app_hashes(N_BLOCKS)
    chain = build_chain(privs, vs, CHAIN, N_BLOCKS, app_hashes=hashes)
    src_sw, src_state, src_store = _source_node(chain, gen)
    sync_sw, bc, cons, sync_store = _sync_node(gen)
    src_sw.start(); sync_sw.start()
    try:
        connect_switches(sync_sw, src_sw)
        # the tip block can't be verified without a successor, so fast-sync
        # stops at N-1 and hands off to consensus
        # (a block is stored before it is applied: wait for both)
        deadline = time.time() + 30
        while (sync_store.height < N_BLOCKS - 1 or
               bc.state.app_hash != hashes[N_BLOCKS - 1]) and \
                time.time() < deadline:
            time.sleep(0.02)
        assert sync_store.height >= N_BLOCKS - 1, \
            f"synced only to {sync_store.height}: {bc.pool.status()}"
        # byte-identical blocks and matching app state
        for h in range(1, N_BLOCKS - 1):
            assert sync_store.load_block(h).hash() == \
                src_store.load_block(h).hash()
        assert bc.state.last_block_height >= N_BLOCKS - 1
        assert bc.state.app_hash == hashes[N_BLOCKS - 1]
        # the handoff happened: consensus took over at the sync tip
        deadline = time.time() + 5
        while cons.fast_sync and time.time() < deadline:
            time.sleep(0.02)
        assert bc._switched
        assert not cons.fast_sync
        assert cons.cs.height == bc.state.last_block_height + 1
    finally:
        _stop_nodes(bc, cons, src_sw, sync_sw)


def test_fast_sync_evicts_lying_peer():
    """A peer serving a tampered block must be evicted and the height
    re-requested from an honest peer; the sync still completes."""
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    hashes = kvstore_app_hashes(N_BLOCKS)
    chain = build_chain(privs, vs, CHAIN, N_BLOCKS, app_hashes=hashes)

    liar_sw, liar_state, liar_store = _source_node(chain, gen)
    liar_reactor = liar_sw.reactor("blockchain")
    orig_receive = liar_reactor.receive

    def lying_receive(ch_id, peer, raw):
        msg = BM.decode_msg(raw)
        if isinstance(msg, BM.BlockRequest) and msg.height == 3:
            block = liar_store.load_block(3)
            evil = bytearray(block.encode())
            evil[-1] ^= 0xFF               # corrupt a tx byte
            peer.try_send(BLOCKCHAIN_CHANNEL, BM.encode_msg(
                BM.BlockResponse(bytes(evil))))
            return
        orig_receive(ch_id, peer, raw)

    liar_reactor.receive = lying_receive
    honest_sw, _, honest_store = _source_node(chain, gen)
    sync_sw, bc, cons, sync_store = _sync_node(gen, batch_size=4)
    for sw in (liar_sw, honest_sw, sync_sw):
        sw.start()
    try:
        connect_switches(sync_sw, liar_sw)
        connect_switches(sync_sw, honest_sw)
        deadline = time.time() + 40
        while sync_store.height < N_BLOCKS - 1 and time.time() < deadline:
            time.sleep(0.02)
        assert sync_store.height >= N_BLOCKS - 1, \
            f"synced only to {sync_store.height}: {bc.pool.status()}"
        for h in range(1, N_BLOCKS - 1):
            assert sync_store.load_block(h).hash() == \
                honest_store.load_block(h).hash()
    finally:
        _stop_nodes(bc, cons, liar_sw, honest_sw, sync_sw)


def test_fast_sync_verify_ahead_overlap():
    """With several windows queued, the reactor must consume speculative
    lookahead verifications (device verify of window k+1 overlapping the
    apply of window k) and still land byte-identical state."""
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    n = 40
    hashes = kvstore_app_hashes(n)
    chain = build_chain(privs, vs, CHAIN, n, app_hashes=hashes)
    src_sw, src_state, src_store = _source_node(chain, gen)
    sync_sw, bc, cons, sync_store = _sync_node(gen, batch_size=4)
    # the sync takes its first step when the whole chain is in the pool:
    # a window that races its own download finds no next window in stock
    # and starts no look-ahead, whatever the link's pace that day
    stocked = threading.Event()
    sync_step = bc._sync_step

    def step_when_stocked():
        if not stocked.is_set():
            if len(bc.pool.peek_contiguous(n)) < n:
                return False
            stocked.set()
        return sync_step()

    bc._sync_step = step_when_stocked
    src_sw.start(); sync_sw.start()
    try:
        connect_switches(sync_sw, src_sw)
        # (a block is stored before it is applied: wait for both)
        deadline = time.time() + 30
        while (sync_store.height < n - 1 or
               bc.state.app_hash != hashes[n - 1]) and \
                time.time() < deadline:
            time.sleep(0.02)
        assert stocked.is_set(), bc.pool.status()
        assert sync_store.height >= n - 1, bc.pool.status()
        # 39 heights in ten windows with every next window in stock:
        # each but the first was verified ahead
        assert bc.lookahead_hits == 9, "speculative windows not consumed"
        for h in range(1, n - 1):
            assert sync_store.load_block(h).hash() == \
                src_store.load_block(h).hash()
        assert bc.state.app_hash == hashes[n - 1]
    finally:
        _stop_nodes(bc, cons, src_sw, sync_sw)


def test_pool_evicts_slow_drip_peer(monkeypatch):
    """Rate-based eviction (reference blockchain/pool.go:100-118
    minRecvRate): a peer that answers each request just inside the redo
    timeout — so the redo counter never fires — but at a trickle rate
    must be evicted; the honest fast peer keeps the window moving."""
    import tendermint_tpu.blockchain.pool as pool_mod
    monkeypatch.setattr(pool_mod, "STARVE_AGE", 0.15)
    evicted = []
    pool = BlockPool(start_height=1, min_recv_rate=10_240)
    pool.on_evict = lambda p, r: evicted.append((p, r))
    pool.set_peer_height("drip", 400)
    pool.set_peer_height("fast", 400)

    deadline = time.time() + 10
    drip_last = 0.0
    while not evicted and time.time() < deadline:
        for h, p in pool.schedule():
            if p == "fast":
                pool.add_block("fast", FakeBlock(h))
                pool.record_bytes("fast", 4096)   # ~healthy block size
        # drip answers ONE outstanding request every 0.2s with 40 bytes:
        # inside any redo timeout, far under 10 KB/s
        now = time.time()
        if now - drip_last >= 0.2:
            drip_last = now
            for h, s in list(pool._slots.items()):
                if s.peer_id == "drip" and s.block is None:
                    pool.add_block("drip", FakeBlock(h))
                    pool.record_bytes("drip", 40)
                    break
        time.sleep(0.02)
    assert evicted and evicted[0][0] == "drip", evicted
    assert "fast" in pool._peers       # honest peer survives
    # the window keeps advancing on the fast peer alone
    n0 = pool.next_height
    for h, p in pool.schedule():
        if p == "fast":
            pool.add_block("fast", FakeBlock(h))
    got = pool.peek_contiguous(64)
    assert len(got) > 0
    pool.pop(len(got))
    assert pool.next_height > n0


def test_net_info_exposes_flowrate():
    """net_info carries per-connection send/recv flowrate snapshots
    (reference p2p/connection.go:485-515 ConnectionStatus)."""
    privs, vs = make_validators(1)
    gen = make_genesis(CHAIN, privs)

    def node():
        st = get_state(MemDB(), gen)
        conns = ClientCreator("kvstore").new_app_conns()
        bs = BlockStore(MemDB())
        r = BlockchainReactor(st, conns.consensus, bs, fast_sync=False)
        return make_switch(CHAIN, {"blockchain": r})

    sw1, sw2 = node(), node()
    sw1.start(); sw2.start()
    try:
        connect_switches(sw1, sw2)
        info = sw1.net_info()
        assert info["n_peers"] == 1
        cstat = info["peers"][0]["connection_status"]
        assert "send_monitor" in cstat and "recv_monitor" in cstat
        assert cstat["recv_monitor"]["total_bytes"] >= 0
        assert "channels" in cstat
    finally:
        sw1.stop(); sw2.stop()


def test_pool_rate_eviction_spares_first_block(monkeypatch):
    """A peer that has not delivered its FIRST block yet must not be
    rate-evicted (the reference's curRate==0 exclusion): only the redo
    timeout judges silent peers."""
    import tendermint_tpu.blockchain.pool as pool_mod
    monkeypatch.setattr(pool_mod, "STARVE_AGE", 0.05)
    evicted = []
    pool = BlockPool(start_height=1, min_recv_rate=10_240)
    pool.on_evict = lambda p, r: evicted.append(p)
    pool.set_peer_height("fresh", 10)
    reqs = pool.schedule()
    assert reqs
    time.sleep(0.2)          # outstanding well past STARVE_AGE
    pool.schedule()
    assert not evicted, "evicted a peer that never got to deliver"
    # once it HAS delivered (trickle), the rate check applies
    h0 = reqs[0][0]
    pool.add_block("fresh", FakeBlock(h0))
    pool.record_bytes("fresh", 30)
    time.sleep(0.2)
    pool.schedule()
    assert evicted == ["fresh"]


def test_commit_power_error_blame_disambiguation():
    """Unit: CommitPowerError.foreign_votes separates 'block h tampered'
    (votes endorse another block) from 'commit pruned by successor'
    (votes endorse ours, too few present)."""
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 2, txs_per_block=1)
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.validator import CommitPowerError
    block, ps, seen = chain[0]
    bid = BlockID(block.hash(), ps.header)
    # pruned: drop half the votes -> short power, all remaining endorse us
    pruned = type(seen)(block_id=seen.block_id,
                        precommits=[seen.precommits[0], None,
                                    seen.precommits[2], None])
    with pytest.raises(CommitPowerError) as ei:
        vs.verify_commit(CHAIN, bid, 1, pruned)
    assert ei.value.foreign_votes is False
    # foreign: verify against a DIFFERENT block id -> valid votes endorse
    # "another" block
    other = BlockID(b"\x77" * 32, ps.header)
    with pytest.raises(CommitPowerError) as ei:
        vs.verify_commit(CHAIN, other, 1, seen)
    assert ei.value.foreign_votes is True


@pytest.mark.slow
def test_fast_sync_byzantine_pruned_commit_spares_honest_peer():
    """A byzantine peer serving blocks whose LastCommit was pruned below
    +2/3 must be evicted — and the HONEST peer that delivered the
    preceding block must not be (reference blame model: the commit for
    height h rides in block h+1, `blockchain/reactor.go:232-236`)."""
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    hashes = kvstore_app_hashes(N_BLOCKS)
    chain = build_chain(privs, vs, CHAIN, N_BLOCKS, app_hashes=hashes)

    byz_sw, _, byz_store = _source_node(chain, gen)
    byz_reactor = byz_sw.reactor("blockchain")
    orig_receive = byz_reactor.receive

    def pruning_receive(ch_id, peer, raw):
        msg = BM.decode_msg(raw)
        if isinstance(msg, BM.BlockRequest) and msg.height > 1:
            from tendermint_tpu.types.block import Block
            block = byz_store.load_block(msg.height)
            lc = block.last_commit
            keep = [v if i == 0 else None
                    for i, v in enumerate(lc.precommits)]   # 1/4 power
            evil = Block(header=block.header, txs=block.txs,
                         last_commit=type(lc)(block_id=lc.block_id,
                                              precommits=keep))
            peer.try_send(BLOCKCHAIN_CHANNEL, BM.encode_msg(
                BM.BlockResponse(evil.encode())))
            return
        orig_receive(ch_id, peer, raw)

    byz_reactor.receive = pruning_receive
    honest_sw, _, honest_store = _source_node(chain, gen)
    sync_sw, bc, cons, sync_store = _sync_node(gen, batch_size=4)
    evicted = []
    bc.pool.on_evict = lambda p, r: evicted.append(p)
    for sw in (byz_sw, honest_sw, sync_sw):
        sw.start()
    try:
        connect_switches(sync_sw, byz_sw)
        connect_switches(sync_sw, honest_sw)
        honest_id = honest_sw.node_info.id
        byz_id = byz_sw.node_info.id
        deadline = time.time() + 40
        while sync_store.height < N_BLOCKS - 1 and time.time() < deadline:
            time.sleep(0.02)
        assert sync_store.height >= N_BLOCKS - 1, \
            f"synced only to {sync_store.height}: {bc.pool.status()}"
        assert honest_id not in evicted, "honest peer was evicted"
        for h in range(1, N_BLOCKS - 1):
            assert sync_store.load_block(h).hash() == \
                honest_store.load_block(h).hash()
    finally:
        _stop_nodes(bc, cons, byz_sw, honest_sw, sync_sw)


# -- commits that stay in their wire bytes ------------------------------------

def test_fast_sync_of_100_validators_stores_the_bytes_object_commits_would(
        tmp_path):
    """A 100-validator chain fast-synced through the real reactor into
    sqlite: every commit reached the stores without a `Vote` being made
    (`Commit.decode` left it in its wire bytes), and every `H:`, `P:`,
    `C:` and `SC:` row is the row `BlockStore.save_block` writes for the
    same chain with commits built from votes.  The stored commits load
    back as what was signed, and a node restarted on those stores passes
    the handshake and rebuilds its last commit from the seen one."""
    from chainutil import fast_sync_in_process
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.utils.db import SQLiteDB
    from tendermint_tpu.utils.metrics import REGISTRY
    n, cid = 21, "wire-commit-chain"
    before = (REGISTRY.commits_decoded_wire.value,
              REGISTRY.commits_decoded_objects.value,
              REGISTRY.commits_decoded_wire_absent.value)
    bc = fast_sync_in_process(cid, n, 8, sqlite_dir=str(tmp_path),
                              n_vals=100)
    # the syncer decoded blocks 2..n from the wire; nothing took the
    # object path (the source serves stored bytes and decodes nothing
    # but what it loads to serve, which is wire-backed too), and no
    # commit of this chain holds a nil entry
    assert REGISTRY.commits_decoded_wire.value - before[0] >= n - 2
    assert REGISTRY.commits_decoded_objects.value == before[1]
    assert REGISTRY.commits_decoded_wire_absent.value == before[2]
    top = bc.store.height
    assert top >= n - 1

    privs, vs = make_validators(100)
    chain = build_chain(privs, vs, cid, n, app_hashes=kvstore_app_hashes(n))
    ref = BlockStore(MemDB())
    for block, ps, seen in chain[:top]:
        assert seen.wire_columns() is None          # built from votes
        ref.save_block(block, ps, seen)
    for prefix in (b"H:", b"P:", b"C:", b"SC:"):
        got = bc.store.db.iterate_prefix(prefix)
        assert got == ref.db.iterate_prefix(prefix), prefix
        assert len(got) >= top

    # the restart: both dbs opened anew, a fresh app
    bc.store.db.close()
    store = BlockStore(SQLiteDB(str(tmp_path / "blocks.db")))
    state = get_state(SQLiteDB(str(tmp_path / "state.db")),
                      make_genesis(cid, privs))
    assert store.height == top
    for h in range(1, top + 1):
        block, ps, seen = chain[h - 1]
        for got in (store.load_seen_commit(h),
                    store.load_block_commit(h) if h < top else None):
            if got is None:
                continue
            assert got.wire_columns() is not None
            assert got == seen and got.encode() == seen.encode()
            vs.verify_commit(cid, BlockID(block.hash(), ps.header), h, got)
        assert store.load_block(h).last_commit == block.last_commit
    conns = ClientCreator("kvstore").new_app_conns()
    Handshaker(state, store).handshake(conns)
    assert state.last_block_height == top
    assert state.app_hash == conns.query.info().last_block_app_hash
    cs = ConsensusState(fast_config().consensus, state, conns.consensus,
                        store, Mempool(conns.mempool))
    assert cs.last_commit.has_two_thirds_majority()
    assert cs.last_commit.make_commit() == chain[top - 1][2]


def _window_through_receive(blocks_encoded, batch_size):
    """A syncer fed `blocks_encoded` (heights 1..) through
    `BlockchainReactor.receive`, as a peer's answers arrive; returns the
    reactor with every block in its pool, not yet synced."""
    privs, _vs = make_validators(4)
    state = get_state(MemDB(), make_genesis(CHAIN, privs))
    conns = ClientCreator("kvstore").new_app_conns()
    bc = BlockchainReactor(state, conns.consensus, BlockStore(MemDB()),
                           fast_sync=True, batch_size=batch_size)

    class Peer:
        id = "source-peer"

    bc.pool.on_evict = lambda peer_id, reason: None
    bc.pool.set_peer_height(Peer.id, len(blocks_encoded))
    assert len(bc.pool.schedule()) == len(blocks_encoded)
    for enc in blocks_encoded:
        bc.receive(BLOCKCHAIN_CHANNEL, Peer,
                   BM.encode_msg(BM.BlockResponse(enc)))
    return bc


def test_a_regular_window_records_nothing_and_a_pruned_commit_one_instant():
    """What the flight recorder and the counter pair say of a 64-block
    window: nothing of full commits that stayed in their bytes (6,400
    lanes must not buy 64 ring writes on the receive threads); of one
    pruned commit, which stays in its bytes as well, one
    `commit.wire_absent` instant with its height and its nil entries,
    and the blame it always got (`pool.redo` of the successor, which
    carried it)."""
    from tendermint_tpu.types import Block, Commit
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 65,
                        app_hashes=kvstore_app_hashes(65))
    encoded = [block.encode() for block, _ps, _seen in chain]

    def since(t0, name):
        return [s.get("args") for s in tracing.RECORDER.since(t0)
                if s["name"] == name and s["ts"] >= t0]

    t0 = tracing.now_epoch()
    wire0 = REGISTRY.commits_decoded_wire.value
    objects0 = REGISTRY.commits_decoded_objects.value
    bc = _window_through_receive(encoded, 64)
    assert bc._sync_step() is True
    assert bc.state.last_block_height == 64
    # block 1 carries the empty commit, which counts on neither side
    assert REGISTRY.commits_decoded_wire.value - wire0 == 64
    assert REGISTRY.commits_decoded_objects.value == objects0
    assert since(t0, "commit.object_form") == []
    assert len(since(t0, "fastsync.decode")) == 65

    # block 40 arrives with its last commit (height 39's) pruned to one
    # vote, as `scenarios/injectors.py` serves it
    block = chain[39][0]
    lc = block.last_commit
    evil = Block(header=block.header, txs=block.txs, last_commit=Commit(
        block_id=lc.block_id,
        precommits=[v if i == 0 else None
                    for i, v in enumerate(lc.precommits)]))
    t0 = tracing.now_epoch()
    wire0 = REGISTRY.commits_decoded_wire.value
    bc = _window_through_receive(
        encoded[:39] + [evil.encode()] + encoded[40:], 64)
    # one vote of four left: its record is regular, so are the bytes
    assert REGISTRY.commits_decoded_wire.value - wire0 == 64
    assert REGISTRY.commits_decoded_objects.value == objects0
    assert since(t0, "commit.object_form") == []
    assert since(t0, "commit.wire_absent") == [{"height": 39, "absent": 3}]
    assert bc._sync_step() is False
    assert bc.state.last_block_height == 0
    assert [a["height"] for a in since(t0, "pool.redo")] == [40]
    assert since(t0, "commit.object_form") == []
    assert len(since(t0, "commit.wire_absent")) == 1


def test_a_synced_window_stores_one_marker_a_height_less_one():
    """64 heights through receive, verify and `apply_window` into the
    block store: the seen commit of h is block h + 1's `last_commit`, so
    every `C:` row but the first is the marker for the bytes of `SC:h-1`
    (`blockchain/store.py`), counted once on `/metrics` and once in the
    flight recorder, and every commit loads back as what was served."""
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY, prometheus_text
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 65,
                        app_hashes=kvstore_app_hashes(65))
    bc = _window_through_receive([b.encode() for b, _ps, _seen in chain], 64)
    aliased0 = REGISTRY.blockstore_commits_aliased.value
    t0 = tracing.now_epoch()
    assert bc._sync_step() is True
    assert bc.state.last_block_height == bc.store.height == 64
    assert REGISTRY.blockstore_commits_aliased.value - aliased0 == 63
    assert "\ntendermint_blockstore_commits_aliased %d\n" % \
        REGISTRY.blockstore_commits_aliased.value in prometheus_text()
    assert REGISTRY.snapshot()["blockstore_commits_aliased"] == \
        REGISTRY.blockstore_commits_aliased.value
    assert len([s for s in tracing.RECORDER.since(t0)
                if s["name"] == "store.commit_alias"
                and s["ts"] >= t0]) == 63
    rows = dict(bc.store.db.iterate_prefix(b"C:"))
    assert len(rows) == 64 and rows.pop(b"C:1") != b""
    assert set(rows.values()) == {b""}
    for h in range(1, 64):
        seen = chain[h - 1][2]
        for got in (bc.store.load_block_commit(h),
                    bc.store.load_seen_commit(h)):
            assert got == seen and got.encode() == seen.encode()
