"""A node catches up on a chain whose validator set changes, through its
normal path and against the plain reference.

The chain is the benchmark builder's (`benchmark/lib/chain.py`: the sets
from `valset_at`, OpenSSL signatures, `RefKVStore`; it shares no code
with the program) under `{"change_every_blocks": 200, "swap": 1}` at 4
validators: 200 is no multiple of the 64-block window, so each set ends
in a window cut to 8 blocks.  A real `Node` (`--crypto-backend tpu
--fast-sync --proxy-app valset_kvstore`, on the CPU backend here) syncs
it from two source peers through the pool, the reactor, the look-ahead
and `apply_window`, while a second thread polls `/validators`.

One sync serves every test of the first part (a comb table builds 22-60
s a set on the CPU backend, three sets: the fixture has a time limit of
its own).  The backend's bookkeeping (which program a call is padded
into, what a table build records) is tested with the device programs
stubbed, in `tests/test_ed25519_grouped.py`."""

import os
import sys
import threading
import time

import pytest

from tendermint_tpu.blockchain import messages as BM
from tendermint_tpu.blockchain.reactor import (BLOCKCHAIN_CHANNEL,
                                               BlockchainReactor)
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.rpc.routes import Routes
from tendermint_tpu.state.state import get_state
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.db import MemDB

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.lib import cell, chain, control, source_child  # noqa: E402

SEED = 2**31 + 3601
N_VALS = 4
PLAN = {"change_every_blocks": 200, "swap": 1}
BLOCK = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
N_BLOCKS = 8 * 64 + 1            # sets of heights 1, 201 and 401
TIP = N_BLOCKS - 1               # the last block waits for a successor
CHAIN_ID = f"valset-churn-{SEED}"
SYNC_LIMIT_S = 900.0             # three table builds beside other workers
FULL = (256, 64)                 # a window's (lanes, templates) bucket


def _since(t0: float, name: str) -> list[dict]:
    return [s for s in tracing.RECORDER.since(t0)
            if s["name"] == name and s["ts"] >= t0]


def _set_index(pubs) -> int:
    """Which of the builder's sets these public keys are, whole and in
    set order; -1 if none."""
    if not _SETS:
        for k in range(3):
            _, vs = chain.valset_at(SEED, N_VALS, PLAN, 200 * k + 1)
            _SETS[tuple(v.pub_key.bytes_.hex() for v in vs.validators)] = k
    return _SETS.get(tuple(pubs), -1)


_SETS: dict[tuple, int] = {}


@pytest.fixture(scope="module")
def synced(tmp_path_factory):
    """The chain, built; the node, synced to the tip and handed over to
    consensus; what `/validators` answered meanwhile; the flight
    recorder's instant the node was started at."""
    seeds, vs = chain.valset_at(SEED, N_VALS, PLAN, 1)
    built = chain.build_chain(CHAIN_ID, seeds, vs, N_BLOCKS, BLOCK, SEED,
                              valset=PLAN)
    assert [h for h, _ in built["valsets"]] == [1, 201, 401]
    gen = chain.genesis_doc(chain.genesis_dict(CHAIN_ID, vs))
    sources = source_child.start_sources(
        CHAIN_ID, gen, source_child.ServedStore(built["encoded"]), 2)
    # as the benchmark's harness: no 40 MB table file a set
    table_dir = os.environ.get("TM_TABLE_CACHE_DIR")
    os.environ["TM_TABLE_CACHE_DIR"] = ""
    backend_was = cb._current
    node = None
    answers: list[tuple] = []
    polling = threading.Event()
    try:
        t0 = tracing.now_epoch()
        with pytest.MonkeyPatch.context() as mp:
            # one device, as on one chip: conftest gives the CPU eight,
            # and on a mesh a set's table is replicated and built whole
            import jax
            devices = jax.devices
            mp.setattr(jax, "devices", lambda *a, **kw: devices(*a, **kw)[:1])
            node, _cfg = cell.boot_node(
                str(tmp_path_factory.mktemp("churn-node")), gen,
                [str(sw._listener.addr) for sw in sources], "valset_kvstore")
        routes = Routes(node)

        def poll():
            while not polling.is_set():
                a = routes.validators({})
                answers.append((a["block_height"], tuple(
                    v["pub_key"] for v in a["validators"])))
                time.sleep(0.005)

        poller = threading.Thread(target=poll, daemon=True,
                                  name="validators-poller")
        bc = node.switch.reactor("blockchain")
        node.start()
        poller.start()
        # the hand-over at the tip starts the live warm-up: five programs
        # more to compile, which nothing here runs
        assert bc.request_when.wait(SYNC_LIMIT_S / 2), "boot warm-up"
        cb.get_backend().precompile_for_validators = lambda *a, **kw: None
        deadline = time.monotonic() + SYNC_LIMIT_S
        while not bc.handed_over and time.monotonic() < deadline:
            time.sleep(0.05)
        assert bc.handed_over, (bc.state.last_block_height,
                                bc.pool.status())
        time.sleep(0.1)               # a few answers after the hand-over
        polling.set()
        poller.join(timeout=10)
        yield {"built": built, "gen": gen, "node": node, "bc": bc,
               "answers": answers, "t0": t0,
               "after": routes.validators({})}
    finally:
        polling.set()
        if node is not None:
            node.stop()
        for sw in sources:
            sw.stop()
        cb._current = backend_was
        if table_dir is None:
            os.environ.pop("TM_TABLE_CACHE_DIR", None)
        else:
            os.environ["TM_TABLE_CACHE_DIR"] = table_dir


# -- (b) the sync, against the plain reference -------------------------------

def test_every_stored_hash_and_the_app_hash_are_the_builders(synced):
    built, node, bc = synced["built"], synced["node"], synced["bc"]
    assert bc.state.last_block_height == TIP == node.block_store.height
    stored = [node.block_store.load_block_meta(h).block_id.hash
              for h in range(1, TIP + 1)]
    assert stored == built["block_hash"][:TIP]
    assert bc.state.app_hash == built["app_hash"][TIP - 1]
    # the app itself, and not only the state's copy of its answer
    assert node.proxy_app.query.info().last_block_app_hash == \
        built["app_hash"][TIP - 1]


@pytest.mark.parametrize("height", [1, 200, 201, 400, 401, TIP + 1])
def test_the_node_holds_the_builders_set_at_every_boundary(synced, height):
    """The set the node saved for `height` (the one that signs it) is the
    builder's: the old one up to the height that carries the diffs, the
    new one from the next."""
    _, want = chain.valset_at(SEED, N_VALS, PLAN, height)
    got = synced["bc"].state.load_validators(height)
    assert got is not None and got.hash() == want.hash()
    assert [v.pub_key.bytes_ for v in got.validators] == \
        [v.pub_key.bytes_ for v in want.validators]
    assert got.total_voting_power() == N_VALS * chain.POWER


def test_the_spans_of_a_set_change_are_one_a_change(synced):
    t0 = synced["t0"]
    changes = [s["args"] for s in _since(t0, "state.valset_change")]
    assert changes == [{"height": 200, "joined": 1, "left": 1},
                       {"height": 400, "joined": 1, "left": 1}]
    cuts = [s["args"] for s in _since(t0, "fastsync.valset_cut")]
    # the look-ahead cuts the window; where its work was not taken, the
    # sync thread cuts the same window again
    assert {(c["height"], c["blocks"]) for c in cuts} == {(201, 8),
                                                          (401, 8)}
    assert 2 <= len(cuts) <= 4
    # one table a set reached, built and not loaded, none dropped
    builds = _since(t0, "tables.build")
    assert len(builds) == 3 and not _since(t0, "tables.load")
    assert all(b["args"]["v"] == N_VALS and b["args"]["bytes"] > 0 and
               b["dur"] > 0 for b in builds)
    assert not _since(t0, "tables.evict")
    # every set after the genesis set is derived from its predecessor
    # (one key joined, 15 columns kept), each inside its set's build; the
    # two programs for that were loaded once, on the warm-up thread that
    # the first window cut at a change started
    derives = _since(t0, "tables.derive")
    assert [d["args"] for d in derives] == [
        {"v": N_VALS, "joined": 1, "bytes": builds[0]["args"]["bytes"]}] * 2
    assert len(derives) == len(changes)
    for d, b in zip(derives, builds[1:]):
        assert b["ts"] <= d["ts"] and \
            d["ts"] + d["dur"] <= b["ts"] + b["dur"] + 1e-6
    loads = _since(t0, "tables.derive.load")
    assert [(ld["args"], ld["lane"]) for ld in loads] == [
        ({"v": N_VALS, "joined": 16}, "crypto-precompile")]
    assert loads[0]["ts"] + loads[0]["dur"] <= derives[0]["ts"]


def test_a_cut_window_runs_in_the_program_the_boot_warmed(synced):
    """After the first full window no program of `cell.KERNELS` compiles
    or loads: the 8-block windows at heights 193 and 393 (32 lanes, 8
    templates: a bucket of their own) are padded into the full window's,
    and each set's table is built by the program that built the first."""
    t0 = synced["t0"]
    ends = sorted((s["ts"] + s["dur"], s["args"])
                  for s in _since(t0, "fastsync.window"))
    assert ends[0][1] == {"window": 1, "blocks": 64}
    # up to the last window: the hand-over that follows it reconstructs
    # the last commit through a live-path program, which this fixture's
    # node did not warm
    late = [s["args"]["fn"] for s in _since(t0, "xla.compile")
            if ends[0][0] < s["ts"] + s["dur"] <= ends[-1][0]]
    assert not [fn for fn in late if fn in cell.KERNELS], late
    windows = {s["args"]["window"]: s["args"]["blocks"]
               for s in _since(t0, "fastsync.window")}
    assert windows[193] == 8 and windows[393] == 8
    assert {s["args"]["bucket"] for s in _since(t0, "verify.dispatch")} == {
        FULL[0]}
    assert 32 in {s["args"]["lanes"] for s in _since(t0, "verify.dispatch")}


# -- (c) /validators while the node syncs -------------------------------------

def test_every_validators_answer_is_one_of_the_builders_sets_whole(synced):
    answers = synced["answers"]
    assert len(answers) > 100
    index = [_set_index(pubs) for _h, pubs in answers]
    assert -1 not in index, answers[index.index(-1)]
    # the sets come in the chain's order, and the RPC moved WHILE the node
    # synced (the parent answered the genesis set until the hand-over)
    assert index == sorted(index) and set(index) == {0, 1, 2}
    for (height, _pubs), k in zip(answers, index):
        # the answer's set is the one after its height, give or take the
        # block being applied when the two fields were read
        assert k in {(h - 1) // 200 for h in (height, height + 1, height + 2)
                     if h >= 1}, (height, k)


def test_after_the_hand_over_validators_is_the_set_after_the_tip(synced):
    after, node = synced["after"], synced["node"]
    assert synced["bc"].handed_over and node.state is node.consensus.state
    _, want = chain.valset_at(SEED, N_VALS, PLAN, TIP + 1)
    assert after["block_height"] == TIP
    assert [v["pub_key"] for v in after["validators"]] == \
        [v.pub_key.bytes_.hex() for v in want.validators]
    assert {v["voting_power"] for v in after["validators"]} == {chain.POWER}
    status = node.status()
    assert status["validator_count"] == N_VALS
    assert status["latest_app_hash"] == \
        synced["built"]["app_hash"][TIP - 1].hex()


def test_while_it_syncs_the_node_answers_from_the_reactors_state():
    """`Node.state` is one place: the reactor's state until the hand-over
    has returned, consensus's after (and where no reactor syncs)."""
    from tendermint_tpu.node.node import Node

    class Switch:
        def __init__(self, bc):
            self.bc = bc

        def reactor(self, name):
            return self.bc if name == "blockchain" else None

    class Reactor:
        fast_sync, handed_over, state = True, False, "applied"

    node = Node.__new__(Node)
    node.consensus = type("C", (), {"state": "genesis"})()
    node.switch = None
    assert node.state == "genesis"
    node.switch = Switch(None)
    assert node.state == "genesis"
    node.switch = Switch(Reactor())
    assert node.state == "applied"
    node.switch.bc.handed_over = True
    assert node.state == "genesis"
    node.switch.bc.handed_over, node.switch.bc.fast_sync = False, False
    assert node.state == "genesis"


# -- (b) a forged signature inside a cut window; (e) the padded call ---------

class _Peer:
    id = "source-peer"


def _reactor_with(encoded: list[bytes], gen) -> BlockchainReactor:
    """A syncer from genesis with `encoded` (heights 1..) in its pool, as
    a peer's answers arrive; the crypto backend is the synced node's, so
    the tables of the chain's sets are resident."""
    conns = ClientCreator("valset_kvstore").new_app_conns()
    bc = BlockchainReactor(get_state(MemDB(), gen), conns.consensus,
                           BlockStore(MemDB()), fast_sync=True)

    bc.pool.on_evict = lambda peer_id, reason: None
    bc.pool.set_peer_height(_Peer.id, len(encoded))
    asked = 0
    while asked < len(encoded):       # 75 requests a peer at a time
        heights = [h for h, _peer in bc.pool.schedule()]
        assert heights, bc.pool.status()
        for h in heights:
            bc.receive(BLOCKCHAIN_CHANNEL, _Peer,
                       BM.encode_msg(BM.BlockResponse(encoded[h - 1])))
        asked += len(heights)
    return bc


def test_a_forged_signature_inside_a_cut_window_is_refused(synced):
    """Height 196's commit rides in block 197, inside the window cut at
    201; one bit of one signature flipped, the header untouched.  The
    three full windows before it apply; the cut window's verify call (32
    lanes padded into the 256-lane program) names the height, the block
    that carried the commit is asked for again, and nothing of the window
    is applied."""
    import dataclasses
    from tendermint_tpu.types import Block, Commit
    built = synced["built"]
    block = Block.decode_bytes(built["encoded"][196])
    lc = block.last_commit
    votes = list(lc.precommits)
    sig = bytearray(votes[2].signature)
    sig[5] ^= 0x01
    votes[2] = dataclasses.replace(votes[2], signature=bytes(sig))
    evil = Block(header=block.header, txs=block.txs, last_commit=Commit(
        block_id=lc.block_id, precommits=votes))
    assert evil.hash() == block.hash() and block.height == 197
    encoded = list(built["encoded"][:201])
    encoded[196] = evil.encode()
    bc = _reactor_with(encoded, synced["gen"])
    try:
        for tip in (64, 128, 192):
            assert bc._sync_step() is True
            assert bc.state.last_block_height == tip
        t0 = tracing.now_epoch()
        assert bc._sync_step() is False
        assert bc.state.last_block_height == 192 == bc.store.height
        assert [s["args"]["height"] for s in _since(t0, "pool.redo")] == [197]
        # refused by the padded program, and by no other: the look-ahead's
        # call ended before t0 or after it, the sync thread's after
        calls = [s["args"] for s in _since(t0, "verify.dispatch")]
        assert calls and all(c == {"lanes": 32, "bucket": FULL[0]}
                             for c in calls)
        # the honest block in its place is taken
        bc.receive(BLOCKCHAIN_CHANNEL, _Peer,
                   BM.encode_msg(BM.BlockResponse(built["encoded"][196])))
    finally:
        bc.stop()


@pytest.mark.parametrize("blocks", [8, 2])
def test_a_padded_call_gives_the_exact_buckets_verdicts_lane_for_lane(
        synced, blocks):
    """The real kernel, on the synced node's backend (the first set's
    table is resident, the full window's program warm): a window of
    `blocks` commits with one forged lane of each of the control's five
    kinds, through the padded program and through its own bucket's.
    Both are OpenSSL's verdicts, lane for lane."""
    be = cb.get_backend()
    seeds, vs = chain.valset_at(SEED, N_VALS, PLAN, 1)
    batch = control.build(SEED + blocks, seeds, blocks)
    n = blocks * N_VALS
    assert batch["forged"] == 5 == int((~batch["expect"]).sum())
    args = (vs.set_key(), vs.pubs_matrix(), batch["val_idx"],
            batch["tmpl_idx"], batch["templates"], batch["sigs"])
    # the smallest program that has run and fits: the full window's,
    # unless an earlier case of this test has warmed one between
    warm = be._warm_shape(N_VALS, batch["templates"].shape[1],
                          cb._bucket(n), cb._bucket(blocks))
    assert warm is not None and warm[0] > cb._bucket(n)
    assert warm == FULL or blocks != 8
    t0 = tracing.now_epoch()
    padded = be.verify_grouped_templated(*args)
    exact = be.verify_grouped_templated(*args, exact_bucket=True)
    buckets = [s["args"]["bucket"] for s in _since(t0, "verify.dispatch")]
    assert buckets == [warm[0], cb._bucket(n)]
    assert padded.shape == exact.shape == (n,)
    assert padded.tolist() == exact.tolist() == batch["expect"].tolist()
    assert not padded.all() and padded.any()
    assert not _since(t0, "tables.build")
    # now that its own bucket is warm, a call takes that: the smallest fit
    t1 = tracing.now_epoch()
    again = be.verify_grouped_templated(*args)
    assert [s["args"]["bucket"] for s in _since(t1, "verify.dispatch")] == [
        cb._bucket(n)]
    assert again.tolist() == padded.tolist()
