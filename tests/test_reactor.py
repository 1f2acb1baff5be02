"""Consensus + mempool reactors over the real p2p stack.

The nets here converge through gossip only — no direct broadcast_cb
wiring — mirroring the reference's `consensus/reactor_test.go` and
`consensus/byzantine_test.go` (4 validators, one equivocating, honest
nodes still commit and capture evidence).
"""

import threading
import time

import pytest

from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config import test_config as fast_config
from tendermint_tpu.consensus.reactor import (ConsensusReactor,
                                              VOTE_CHANNEL)
from tendermint_tpu.consensus import messages as M
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.p2p import connect_switches, make_switch
from tendermint_tpu.state.state import get_state
from tendermint_tpu.types import Vote
from tendermint_tpu.types import events as ev
from tendermint_tpu.utils.db import MemDB

from chainutil import make_genesis, make_validators

CHAIN = "reactor-chain"


@pytest.fixture(autouse=True)
def _python_backend():
    old = cb._current
    cb.set_backend("python")
    yield
    cb._current = old


class NetNode:
    """Consensus core + reactors + switch, no RPC/CLI."""

    def __init__(self, priv, gen, moniker, cfg_factory=fast_config):
        cfg = cfg_factory()
        db = MemDB()
        st = get_state(db, gen)
        self.conns = ClientCreator("kvstore").new_app_conns()
        self.mempool = Mempool(self.conns.mempool)
        self.block_store = BlockStore(MemDB())
        self.cs = ConsensusState(cfg.consensus, st, self.conns.consensus,
                                 self.block_store, self.mempool,
                                 priv_validator=priv)
        self.cons_reactor = ConsensusReactor(self.cs)
        self.mp_reactor = MempoolReactor(self.mempool)
        self.switch = make_switch(CHAIN, {
            "consensus": self.cons_reactor,
            "mempool": self.mp_reactor,
        }, moniker=moniker)

    def start(self):
        self.switch.start()

    def stop(self):
        self.switch.stop()


def _make_net(n, connect=True, cfg_factory=fast_config):
    privs, vs = make_validators(n)
    gen = make_genesis(CHAIN, privs)
    nodes = [NetNode(privs[i], gen, f"node{i}", cfg_factory)
             for i in range(n)]
    for nd in nodes:
        nd.start()
    if connect:
        for i in range(n):
            for j in range(i + 1, n):
                connect_switches(nodes[i].switch, nodes[j].switch)
    return nodes, privs


def _wait_height(nodes, height, timeout=90.0):
    """Generous default: the property under test is convergence, not
    bounded latency on a loaded single-core host (a passing net returns
    in seconds; the budget only matters when scheduler noise stretches
    early rounds — the stress tier measures that regime separately)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(nd.block_store.height >= height for nd in nodes):
            return True
        time.sleep(0.02)
    return False


def test_four_nodes_converge_through_reactors():
    nodes, _ = _make_net(4)
    try:
        nodes[0].mempool.check_tx(b"gossip=me")
        assert _wait_height(nodes, 3), \
            f"heights: {[nd.block_store.height for nd in nodes]}"
        for h in range(1, 4):
            hashes = {nd.block_store.load_block(h).hash() for nd in nodes}
            assert len(hashes) == 1, f"disagreement at height {h}"
        # the tx was gossiped from node0's mempool and must COMMIT on a
        # non-submitting node (wait for inclusion: with skip_timeout_commit
        # the net can race several empty blocks ahead of the gossip hop)
        def committed_txs():
            return [tx for h in range(1, nodes[1].block_store.height + 1)
                    for tx in nodes[1].block_store.load_block(h).txs]
        deadline = time.time() + 15
        while b"gossip=me" not in committed_txs() and time.time() < deadline:
            time.sleep(0.05)
        assert b"gossip=me" in committed_txs()
    finally:
        for nd in nodes:
            nd.stop()


def test_late_joiner_catches_up_through_gossip():
    """3 of 4 nodes advance; the 4th connects late and must catch up via
    the catchup vote/part gossip paths (reference gossip routines'
    prs.Height < rs.Height branches)."""
    nodes, _ = _make_net(4, connect=False)
    try:
        for i in range(3):
            for j in range(i + 1, 3):
                connect_switches(nodes[i].switch, nodes[j].switch)
        assert _wait_height(nodes[:3], 3), \
            f"heights: {[nd.block_store.height for nd in nodes[:3]]}"
        late = nodes[3]
        assert late.block_store.height == 0
        for i in range(3):
            connect_switches(nodes[i].switch, late.switch)
        assert _wait_height([late], 3), \
            f"late joiner stuck at {late.block_store.height}"
        for h in range(1, 4):
            assert late.block_store.load_block(h).hash() == \
                nodes[0].block_store.load_block(h).hash()
    finally:
        for nd in nodes:
            nd.stop()


def test_sleeper_recovers_through_gossip():
    """Regression: a node that sleeps through commits must
    recover via consensus gossip alone, within seconds, without
    fast-sync.  The victim's consensus mutex is held from outside — its
    receive loop, gossip snapshots, and vote handling all block, exactly
    what a GIL/scheduler-starved node looks like — while the other three
    commit several heights; on release the catchup branches of the data
    and vote gossip routines (reference `consensus/reactor.go:427-464,
    588-608`) must feed it the missed blocks."""
    nodes, _ = _make_net(4)
    try:
        assert _wait_height(nodes, 1, timeout=60), \
            f"net never started: {[nd.block_store.height for nd in nodes]}"
        victim, trio = nodes[3], nodes[:3]
        base = max(nd.block_store.height for nd in trio)
        victim.cs._mtx.acquire()
        try:
            deadline = time.time() + 60
            while min(nd.block_store.height for nd in trio) < base + 4:
                assert time.time() < deadline, \
                    ("trio stalled while victim asleep: "
                     f"{[nd.block_store.height for nd in trio]}")
                time.sleep(0.05)
        finally:
            victim.cs._mtx.release()
        target = min(nd.block_store.height for nd in trio)
        assert _wait_height([victim], target), \
            (f"victim stuck at {victim.block_store.height}, "
             f"trio at {[nd.block_store.height for nd in trio]}")
        for h in range(1, target + 1):
            assert victim.block_store.load_block(h).hash() == \
                trio[0].block_store.load_block(h).hash()
    finally:
        for nd in nodes:
            nd.stop()


def test_byzantine_double_signer_evidence_and_safety():
    """Validator 0 equivocates: for every prevote it also signs and
    broadcasts a conflicting nil prevote (raw key, no HRS guard).  Honest
    nodes must capture DuplicateVoteEvidence AND keep committing — one
    byzantine voice among 4 equal-power validators cannot break safety
    (reference `consensus/byzantine_test.go:27-60`)."""
    nodes, privs = _make_net(4)
    byz = nodes[0]
    byz_priv = privs[0]
    evidence = []
    ev_lock = threading.Lock()
    for nd in nodes[1:]:
        nd.cs.evsw.subscribe("test", "EvidenceDoubleSign",
                             lambda e: (ev_lock.acquire(),
                                        evidence.append(e),
                                        ev_lock.release()))

    orig_sign_add = byz.cs._sign_add_vote

    def equivocating_sign_add(type_, block_id):
        orig_sign_add(type_, block_id)
        from tendermint_tpu.types import ZERO_BLOCK_ID, TYPE_PREVOTE
        if type_ != TYPE_PREVOTE or block_id.is_zero():
            return
        # conflicting nil prevote signed with the raw key (bypasses the
        # PrivValidator double-sign guard, like ByzantinePrivValidator)
        idx = byz.cs.validators.index_of(byz_priv.address)
        v = Vote(validator_address=byz_priv.address, validator_index=idx,
                 height=byz.cs.height, round=byz.cs.round, type=type_,
                 block_id=ZERO_BLOCK_ID)
        sig = byz_priv.priv_key.sign(v.sign_bytes(CHAIN))
        v = Vote(**{**v.__dict__, "signature": sig})
        byz.switch.broadcast(VOTE_CHANNEL,
                             M.encode_msg(M.VoteMessage(v)))

    byz.cs._sign_add_vote = equivocating_sign_add
    try:
        assert _wait_height(nodes[1:], 3), \
            f"honest heights: {[nd.block_store.height for nd in nodes[1:]]}"
        # hashes agree across honest nodes
        for h in range(1, 4):
            hashes = {nd.block_store.load_block(h).hash()
                      for nd in nodes[1:]}
            assert len(hashes) == 1
        # the byzantine validator double-signs EVERY height, but whether
        # one honest node sees both conflicting votes for the same round
        # is a race per height — wait for eventual capture while the net
        # keeps committing
        deadline = time.time() + 20
        while time.time() < deadline:
            with ev_lock:
                if evidence:
                    break
            time.sleep(0.05)
        with ev_lock:
            assert evidence, "no double-sign evidence captured"
        e = evidence[0]
        assert e.vote_a.validator_address == byz_priv.address
        assert e.vote_b.validator_address == byz_priv.address
        assert e.vote_a.block_id.key() != e.vote_b.block_id.key()
    finally:
        for nd in nodes:
            nd.stop()


def test_mempool_gossip_height_gates_fast_syncing_peer():
    """Per-tx height gating (reference mempool/reactor.go:111+): a peer
    whose consensus height is far behind a tx's admission height gets no
    push for it; once the peer's model catches up, the tx flows.  Old
    txs (admitted near the peer's height) are never starved by the
    POOL's moving height."""
    from tendermint_tpu.p2p import make_switch
    from tendermint_tpu.proxy import ClientCreator

    class FakePRS:
        height = 3

    class FakePS:
        prs = FakePRS()

    pools, switches = [], []
    for i in range(2):
        conns = ClientCreator("kvstore").new_app_conns()
        mp = Mempool(conns.mempool)
        pools.append(mp)
        switches.append(make_switch(CHAIN, {"mempool": MempoolReactor(mp)},
                                    moniker=f"m{i}"))
    for sw in switches:
        sw.start()
    try:
        p0, _ = connect_switches(switches[0], switches[1])
        p0.set("consensus", FakePS())     # node0's model of the peer
        pools[0]._height = 50
        pools[0].check_tx(b"new=tx")      # admission height 51, peer at 3
        time.sleep(0.5)
        assert b"new=tx" not in pools[1].txs_after(0), \
            "fresh tx pushed to lagging peer"
        # a tx admitted near the peer's height is NOT gated by the
        # pool's (high) current height
        pools[0]._height = 3
        pools[0].check_tx(b"old=tx")      # admission height 4
        deadline = time.time() + 5
        while b"old=tx" not in pools[1].txs_after(0) and \
                time.time() < deadline:
            time.sleep(0.02)
        assert b"old=tx" in pools[1].txs_after(0)
        # peer catches up: the gated tx now flows
        FakePRS.height = 51
        switches[0].reactor("mempool")._notify_work()
        deadline = time.time() + 5
        while b"new=tx" not in pools[1].txs_after(0) and \
                time.time() < deadline:
            time.sleep(0.02)
        assert b"new=tx" in pools[1].txs_after(0)
    finally:
        for sw in switches:
            sw.stop()


def test_catchup_model_rekeys_on_header_change():
    """D1 of the [25,25,0,25] stress wedge: the sender's PeerState bitmap
    tracked the peer's OWN later-round proposal header; catchup gossip
    then treated it as the committed block's bitmap and never re-sent
    the parts.  `init_proposal_block_parts` must RESET when the header
    differs (reference gossipDataRoutine reactor.go:427-464 re-inits on
    header mismatch)."""
    from tendermint_tpu.consensus.reactor import PeerState
    from tendermint_tpu.types.part_set import PartSetHeader

    ps = PeerState(peer=None)
    h_own = PartSetHeader(1, b"\x11" * 32)     # peer's own r2 proposal
    h_committed = PartSetHeader(1, b"\x22" * 32)
    ps.prs.height, ps.prs.round = 1, 2
    ps.init_proposal_block_parts(h_own)
    ps.set_has_part(1, 0)                       # model: delivered
    assert ps.prs.proposal_block_parts == [True]
    # catchup keys the model to the committed header: must reset
    ps.init_proposal_block_parts(h_committed)
    assert ps.prs.proposal_block_parts == [False]
    assert ps.prs.proposal_block_parts_header == h_committed
    # re-keying to the SAME header is a no-op (keeps delivered marks)
    ps.set_has_part(1, 0)
    ps.init_proposal_block_parts(h_committed)
    assert ps.prs.proposal_block_parts == [True]


def test_part_prefilter_passes_foreign_header_part():
    """D2 of the [25,25,0,25] stress wedge: the receiver's dedup
    prefilter dropped a catchup part because its CURRENT partset (its
    own later-round proposal) already held that index — same index is
    not identity.  A part whose proof roots at a different header must
    reach the core."""
    from tendermint_tpu.consensus.reactor import (ConsensusReactor,
                                                  DATA_CHANNEL)
    from tendermint_tpu.consensus.reactor import PeerState
    from tendermint_tpu.types.part_set import PartSet

    own = PartSet.from_data(b"my own round-2 proposal block bytes")
    committed = PartSet.from_data(b"the committed round-1 block bytes")
    assert own.header != committed.header

    class CoreStub:
        def __init__(self):
            self.added = []
            self.block_store = None

        def get_round_state(self):
            from types import SimpleNamespace
            return SimpleNamespace(height=1, round=2, step=8,
                                   proposal=None, votes=None,
                                   validators=None,
                                   proposal_block_parts=own,
                                   commit_round=1, last_commit=None,
                                   start_time=0)

        def add_proposal_block_part(self, height, round_, part, peer_id):
            self.added.append((height, part.index))

    class PeerStub:
        id = "ab" * 10

        def get(self, k):
            return self._ps

        def set(self, k, v):
            self._ps = v

    core = CoreStub()
    r = ConsensusReactor.__new__(ConsensusReactor)   # skip __init__
    r.cs = core
    r.fast_sync = False
    r.switch = None
    peer = PeerStub()
    ps = PeerState(peer=peer)
    ps.prs.height, ps.prs.round = 1, 2

    # a duplicate of OUR OWN partset's part: dropped (true duplicate)
    r._receive(DATA_CHANNEL, peer, ps,
               M.BlockPartMessage(1, 2, own.get_part(0)))
    assert core.added == []
    # the committed block's part at the same index: must pass through
    r._receive(DATA_CHANNEL, peer, ps,
               M.BlockPartMessage(1, 2, committed.get_part(0)))
    assert core.added == [(1, 0)]
