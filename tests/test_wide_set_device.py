"""`TpuBackend` against OpenSSL on a validator set at the far end of its
V bucket, and past every bucket the chip had run before PR 39.

**140 validators, V bucket 256**, the first bucket past every accepted
cell's (the benchmark's `catchup-300v` runs V bucket 512 on the chip),
forged lanes above column 128 among them.  It is in tier-1 since the
comb-table build carries a window's base point and not its row: the
file's first call (the table build and the programs compiled against it)
takes 158 s on the CPU backend, where the parent's build took 485 s and
the file held the same comparison at 60 validators, V bucket 64, instead
(builder's runs, PR 39).  The same file runs ON THE CHIP in seconds
(`python -m pytest tests/test_wide_set_device.py --noconftest`:
`tests/conftest.py` pins the CPU).

Every table-backed test shares ONE comb table (`--dist loadfile` keeps
a file on one worker).  The windows are 4 blocks (560 lanes): the CPU
backend's grouped convolution is pathological above 4,096 lanes, so the
reactor's own 64-block window is the chip's to run (`benchmark/run.py`).

The set, its keys, the chain and every expected verdict are the
benchmark builder's and OpenSSL's (`benchmark/lib/chain.py`,
`cryptography`): no code shared with the program but the wire format."""

import dataclasses
import os
import sys

import numpy as np
import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PublicKey

from tendermint_tpu.blockchain import messages as BM
from tendermint_tpu.blockchain.reactor import (BLOCKCHAIN_CHANNEL,
                                               BlockchainReactor)
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.state.state import get_state
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.db import MemDB
from tendermint_tpu.utils.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.lib import chain, control  # noqa: E402

SEED = 2**31 + 3903
WINDOW = 4                       # blocks a window
N_BLOCKS = 2 * WINDOW + 1        # two windows and the last commit's block
TIP = N_BLOCKS - 1
CHAIN_ID = f"wide-set-{SEED}"
BLOCK = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
# lanes forged by hand on top of the control's seeded ones: (column of
# the table, what is done to the lane), each in the first template whose
# lane the control left honest; a column is given from the middle of the
# set's V bucket (128) or from the set's end
BY_HAND = [("last", "sig"), ("mid", "sig"), (5, "sig"),
           ("mid+3", "signer"), ("mid-1", "sig")]


def openssl_verdicts(pubs, val_idx, tmpl_idx, templates, sigs):
    """OpenSSL's verdict a lane; a lane met before (a program's padding
    repeats lane 0) is not checked twice."""
    keys = [Ed25519PublicKey.from_public_bytes(p.tobytes()) for p in pubs]
    tm = [t.tobytes() for t in templates]
    seen: dict[tuple, bool] = {}
    out = np.zeros(len(val_idx), bool)
    for i, (v, t) in enumerate(zip(val_idx.tolist(), tmpl_idx.tolist())):
        lane = (v, t, sigs[i].tobytes())
        if lane not in seen:
            try:
                keys[v].verify(lane[2], tm[t])
                seen[lane] = True
            except (InvalidSignature, ValueError):
                seen[lane] = False
        out[i] = seen[lane]
    return out


def _since(t0: float, name: str) -> list[dict]:
    return [s for s in tracing.RECORDER.since(t0)
            if s["name"] == name and s["ts"] >= t0]


def column(n_vals: int, where) -> int:
    mid = cb._bucket(n_vals) // 2
    return {"last": n_vals - 1, "mid": mid, "mid+3": mid + 3,
            "mid-1": mid - 1, "mid+7": mid + 7}.get(where, where)


@pytest.fixture(scope="module", params=[140], ids=lambda n: f"{n}v")
def wide(request):
    """The set, its chain, the control batch with its OpenSSL verdicts,
    and the process's `TpuBackend` with the set's table built by the
    batch's own first call (templated, a cold set: the build, the
    verify program loaded beside it, and the call)."""
    n_vals = request.param
    seeds, vs = chain.make_validators(SEED, n_vals)
    built = chain.build_chain(CHAIN_ID, seeds, vs, N_BLOCKS, BLOCK, SEED)
    batch = control.build(SEED, seeds, WINDOW)
    lane = {(int(t), int(v)): i for i, (t, v) in enumerate(
        zip(batch["tmpl_idx"], batch["val_idx"]))}
    by_hand = {}
    for where, what in BY_HAND:
        v = column(n_vals, where)
        i = by_hand[where] = next(
            lane[(t, v)] for t in range(WINDOW)
            if batch["expect"][lane[(t, v)]])
        if what == "sig":
            batch["sigs"][i, 17] ^= 0x40
        else:                         # signed by v, claimed for v - 1
            batch["val_idx"][i] = v - 1
    pubs = vs.pubs_matrix()
    expect = openssl_verdicts(pubs, batch["val_idx"], batch["tmpl_idx"],
                              batch["templates"], batch["sigs"])
    assert int((~expect).sum()) == batch["forged"] + len(BY_HAND)
    table_dir = os.environ.get("TM_TABLE_CACHE_DIR")
    os.environ["TM_TABLE_CACHE_DIR"] = ""     # no 650 MB file a build
    backend_was = cb._current
    try:
        be = cb.set_backend("tpu")
        t0 = tracing.now_epoch()
        first = be.verify_grouped_templated(
            vs.set_key(), pubs, batch["val_idx"], batch["tmpl_idx"],
            batch["templates"], batch["sigs"])
        yield {"n": n_vals, "vs": vs, "built": built,
               "batch": batch, "expect": expect, "be": be, "first": first,
               "t0": t0, "pubs": pubs, "by_hand": by_hand,
               # the window's (lanes, templates) bucket: (1,024, 16)
               "program": (cb._bucket(WINDOW * n_vals), cb.MIN_BUCKET),
               "gen": chain.genesis_doc(chain.genesis_dict(CHAIN_ID, vs))}
    finally:
        cb._current = backend_was
        if table_dir is None:
            os.environ.pop("TM_TABLE_CACHE_DIR", None)
        else:
            os.environ["TM_TABLE_CACHE_DIR"] = table_dir


def test_the_templated_verdicts_are_openssls_lane_for_lane(wide):
    got, expect, n = wide["first"], wide["expect"], wide["n"]
    assert got.shape == expect.shape == (WINDOW * n,)
    assert got.tolist() == expect.tolist()
    # one table, of the set's V bucket, built once
    builds = _since(wide["t0"], "tables.build")
    assert [b["args"]["v"] for b in builds] == [n]
    assert builds[0]["args"]["bytes"] == 26 * 1024 * cb._bucket(n) * 96
    # the build program's trace and compile (or load) has a record of
    # its own, which ends where the build's begins
    loads = _since(wide["t0"], "tables.build.load")
    assert [ld["args"] for ld in loads] == [{"v": n}]
    assert loads[0]["ts"] + loads[0]["dur"] <= builds[0]["ts"] + 1e-3
    calls = _since(wide["t0"], "verify.dispatch")
    assert calls[0]["args"] == {"lanes": WINDOW * n,
                                "bucket": wide["program"][0]}


@pytest.mark.parametrize("where,what", BY_HAND)
def test_a_forged_lane_is_refused_and_its_neighbours_are_not(wide, where,
                                                              what):
    """Columns below and ABOVE the middle of the table: at 140
    validators, indices above 128, which no accepted cell's set
    reaches."""
    i = wide["by_hand"][where]
    got = wide["first"]
    assert not got[i] and not wide["expect"][i]
    near = [j for j in (i - 1, i + 1) if 0 <= j < len(got)
            and wide["expect"][j]]
    assert near and all(got[j] for j in near)


def test_the_plain_call_gives_the_same_verdicts(wide):
    batch = wide["batch"]
    got = wide["be"].verify_grouped(
        wide["vs"].set_key(), wide["pubs"], batch["val_idx"],
        batch["templates"][batch["tmpl_idx"]], batch["sigs"])
    assert got.tolist() == wide["expect"].tolist()


def test_one_commit_is_padded_into_the_windows_program(wide):
    """V lanes and one template are a bucket of their own, (256, 16):
    `_warm_shape` pads the call into the program that has run, and the
    padding (copies of lane 0, which is good) reaches no verdict and no
    count."""
    batch, be, n, program = (wide["batch"], wide["be"], wide["n"],
                             wide["program"])
    # the first block's lanes, every one checked against template 0 (the
    # control's wrong-template lanes among them are honest again)
    one, zeros = np.arange(n), np.zeros(n, np.int32)
    want = openssl_verdicts(wide["pubs"], batch["val_idx"][one], zeros,
                            batch["templates"], batch["sigs"][one])
    assert not want.all() and want.sum() > n // 2
    assert be._warm_shape(n, batch["templates"].shape[1], cb._bucket(n),
                          cb.MIN_BUCKET) == program
    real, padded = REGISTRY.sigs_requested, REGISTRY.verify_lanes_padded
    before = (real.value, padded.value, REGISTRY.sigs_verified.value)
    t0 = tracing.now_epoch()
    got = be.verify_grouped_templated(
        wide["vs"].set_key(), wide["pubs"], batch["val_idx"][one],
        zeros, batch["templates"][:1], batch["sigs"][one])
    assert got.tolist() == want.tolist()
    assert [s["args"] for s in _since(t0, "verify.dispatch")] == [
        {"lanes": n, "bucket": program[0]}]
    assert not _since(t0, "tables.build")
    assert not [s for s in _since(t0, "xla.compile")
                if s["args"]["fn"].startswith("jit(verify")]
    assert real.value - before[0] == n
    assert padded.value - before[1] == program[0]
    assert REGISTRY.sigs_verified.value - before[2] == int(want.sum())


# -- two windows through the real reactor ------------------------------------

PEERS = ("peer-a", "peer-b")


class _Peer:
    def __init__(self, id_):
        self.id = id_


def _reactor_with(encoded: list[bytes], gen):
    """(reactor, {height: id of the peer that delivered it}, evictions):
    a syncer from genesis, windows of 4 blocks, with `encoded` (heights
    1..) in its pool as two peers' answers arrive."""
    conns = ClientCreator("kvstore").new_app_conns()
    bc = BlockchainReactor(get_state(MemDB(), gen), conns.consensus,
                           BlockStore(MemDB()), fast_sync=True,
                           batch_size=WINDOW)
    evicted = []
    bc.pool.on_evict = lambda peer_id, reason: evicted.append(
        (peer_id, reason))
    for p in PEERS:
        bc.pool.set_peer_height(p, len(encoded))
    by = {}
    while len(by) < len(encoded):
        asked = bc.pool.schedule()
        assert asked, bc.pool.status()
        for h, peer_id in asked:
            by[h] = peer_id
            bc.receive(BLOCKCHAIN_CHANNEL, _Peer(peer_id),
                       BM.encode_msg(BM.BlockResponse(encoded[h - 1])))
    return bc, by, evicted


def test_two_windows_sync_to_the_builders_hashes(wide):
    built = wide["built"]
    bc, _by, evicted = _reactor_with(built["encoded"], wide["gen"])
    try:
        t0 = tracing.now_epoch()
        for tip in (WINDOW, 2 * WINDOW):
            assert bc._sync_step() is True
            assert bc.state.last_block_height == tip == bc.store.height
        assert not evicted
        assert [bc.store.load_block_meta(h).block_id.hash
                for h in range(1, TIP + 1)] == built["block_hash"][:TIP]
        assert bc.state.app_hash == built["app_hash"][TIP - 1]
        assert bc.state.validators.hash() == wide["vs"].hash()
        assert bc.state.validators.size() == wide["n"]
        # every window rode the program the fixture's call compiled,
        # against the one table
        calls = [s["args"] for s in _since(t0, "verify.dispatch")]
        assert calls and all(c == {"lanes": WINDOW * wide["n"],
                                   "bucket": wide["program"][0]}
                             for c in calls)
        assert not _since(t0, "tables.build")
    finally:
        bc.stop()


def test_a_forged_commit_is_refused_and_its_deliverer_blamed(wide):
    """The commit of height 6 rides in block 7, in the second window; one
    bit of one signature flipped (validator 135 of 140: the table's
    upper columns), the header untouched.  The
    first window applies, the second names the height, the peer that
    delivered block 7 is evicted and nothing of the window is applied."""
    from tendermint_tpu.types import Block, Commit
    built = wide["built"]
    block = Block.decode_bytes(built["encoded"][6])
    lc = block.last_commit
    votes = list(lc.precommits)
    v = column(wide["n"], "mid+7")
    sig = bytearray(votes[v].signature)
    sig[5] ^= 0x01
    votes[v] = dataclasses.replace(votes[v], signature=bytes(sig))
    evil = Block(header=block.header, txs=block.txs, last_commit=Commit(
        block_id=lc.block_id, precommits=votes))
    assert evil.hash() == block.hash() and block.height == 7
    encoded = list(built["encoded"])
    encoded[6] = evil.encode()
    bc, by, evicted = _reactor_with(encoded, wide["gen"])
    try:
        assert bc._sync_step() is True
        assert bc.state.last_block_height == WINDOW
        t0 = tracing.now_epoch()
        assert bc._sync_step() is False
        assert bc.state.last_block_height == WINDOW == bc.store.height
        assert [s["args"]["height"] for s in _since(t0, "pool.redo")] == [7]
        assert [p for p, _why in evicted] == [by[7]]
        assert "height 7" in evicted[0][1]
    finally:
        bc.stop()
