"""Domain-type tests: codec roundtrips, part sets, blocks, votes, quorums,
proposer rotation, commit verification, priv-validator safety.

Modelled on the reference's `types/*_test.go` suite (vote_set_test.go
quorum/conflict coverage, validator_set_test.go rotation, part_set_test.go
proof checks, priv_validator_test.go HRS guard).
"""

import os

import pytest

from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.types import (Block, BlockID, Commit, EMPTY_COMMIT,
                                  DoubleSignError, ErrVoteConflict, PartSet,
                                  PartSetHeader, PrivKey, PrivValidator,
                                  Proposal, TYPE_PRECOMMIT, TYPE_PREVOTE,
                                  Validator, ValidatorSet, Vote, VoteSet,
                                  ZERO_BLOCK_ID, txs_hash, txs_proof)
from tendermint_tpu.types.codec import Reader

CHAIN = "test-chain"


@pytest.fixture(autouse=True)
def _python_backend():
    """Types tests use the bigint backend: exact, no compile latency."""
    old = cb._current
    cb.set_backend("python")
    yield
    cb._current = old


def _valset(n, power=10):
    privs = [PrivValidator(PrivKey.generate()) for _ in range(n)]
    vs = ValidatorSet([Validator(p.pub_key, power) for p in privs])
    privs.sort(key=lambda p: p.address)
    return privs, vs


def _vote(priv, vs, height, round_, type_, block_id):
    idx = vs.index_of(priv.address)
    v = Vote(validator_address=priv.address, validator_index=idx,
             height=height, round=round_, type=type_, block_id=block_id)
    sig = priv.sign_vote(CHAIN, v)
    return Vote(**{**v.__dict__, "signature": sig})


def _block_id(seed=b"hh"):
    return BlockID(hash=seed.ljust(32, b"\x01"),
                   parts=PartSetHeader(2, seed.ljust(32, b"\x02")))


# -- part set --------------------------------------------------------------

def test_part_set_roundtrip():
    data = os.urandom(300_000)
    ps = PartSet.from_data(data, part_size=65536)
    assert ps.total == 5 and ps.is_complete()
    # reassemble into a fresh set from gossiped parts
    ps2 = PartSet(ps.header)
    for i in range(ps.total):
        assert ps2.add_part(ps.get_part(i))
    assert ps2.is_complete()
    assert ps2.assemble() == data


def test_part_set_rejects_invalid():
    ps = PartSet.from_data(b"x" * 200_000, part_size=65536)
    other = PartSet.from_data(b"y" * 200_000, part_size=65536)
    fresh = PartSet(ps.header)
    assert not fresh.add_part(other.get_part(0))      # wrong tree
    assert fresh.add_part(ps.get_part(1))
    assert not fresh.add_part(ps.get_part(1))         # duplicate


# -- block -----------------------------------------------------------------

def _make_block(height=1, last_commit=EMPTY_COMMIT,
                last_block_id=ZERO_BLOCK_ID):
    return Block.make(chain_id=CHAIN, height=height, time_ns=1_700_000_000,
                      txs=[b"tx1", b"tx2", b"tx3"], last_commit=last_commit,
                      last_block_id=last_block_id,
                      validators_hash=b"\x05" * 32, app_hash=b"\x06" * 20)


def test_block_roundtrip_and_hash():
    b = _make_block()
    b.validate_basic()
    enc = b.encode()
    b2 = Block.decode_bytes(enc)
    assert b2.hash() == b.hash() and b.hash()
    assert b2.header == b.header and b2.txs == b.txs
    # part set of the encoding reassembles to the same block
    ps = b.make_part_set(part_size=64)
    ps2 = PartSet(ps.header)
    for i in range(ps.total):
        assert ps2.add_part(ps.get_part(i))
    assert Block.decode_bytes(ps2.assemble()).hash() == b.hash()


def test_block_validate_basic_rejects():
    b = _make_block()
    object.__setattr__(b.header, "num_txs", 5)
    with pytest.raises(ValueError):
        b.validate_basic()


def test_tx_proof():
    txs = [b"a", b"bb", b"ccc", b"dddd"]
    b = Block.make(CHAIN, 1, 0, txs, EMPTY_COMMIT, ZERO_BLOCK_ID,
                   b"\x05" * 32, b"")
    pr = txs_proof(txs, 2)
    assert pr.validate(b.header.data_hash)
    assert not pr.validate(b"\x00" * 32)


# -- vote set --------------------------------------------------------------

def test_voteset_two_thirds():
    privs, vs = _valset(4)
    bid = _block_id()
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    assert vset.two_thirds_majority() is None
    for i, p in enumerate(privs[:2]):
        assert vset.add_vote(_vote(p, vs, 1, 0, TYPE_PREVOTE, bid))
    assert vset.two_thirds_majority() is None     # 20/40
    assert vset.add_vote(_vote(privs[2], vs, 1, 0, TYPE_PREVOTE, bid))
    maj = vset.two_thirds_majority()              # 30/40 > 2/3
    assert maj is not None and maj.key() == bid.key()


def test_voteset_nil_majority():
    privs, vs = _valset(3)
    vset = VoteSet(CHAIN, 2, 1, TYPE_PRECOMMIT, vs)
    for p in privs:
        vset.add_vote(_vote(p, vs, 2, 1, TYPE_PRECOMMIT, ZERO_BLOCK_ID))
    maj = vset.two_thirds_majority()
    assert maj is not None and maj.is_zero()
    with pytest.raises(ValueError):
        vset.make_commit()   # nil majority is not a commit


def test_voteset_rejects_bad_signature():
    privs, vs = _valset(2)
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    v = _vote(privs[0], vs, 1, 0, TYPE_PREVOTE, _block_id())
    forged = Vote(**{**v.__dict__, "signature": b"\x01" * 64})
    with pytest.raises(ValueError, match="signature"):
        vset.add_vote(forged)


def test_voteset_conflict_evidence():
    privs, vs = _valset(3)
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    v1 = _vote(privs[0], vs, 1, 0, TYPE_PREVOTE, _block_id(b"aa"))
    assert vset.add_vote(v1)
    # the same validator signs a different block: equivocation.  The HRS
    # guard in PrivValidator refuses, so forge via a raw key.
    pk = privs[0].priv_key
    idx = vs.index_of(privs[0].address)
    v2 = Vote(validator_address=privs[0].address, validator_index=idx,
              height=1, round=0, type=TYPE_PREVOTE, block_id=_block_id(b"bb"))
    v2 = Vote(**{**v2.__dict__, "signature": pk.sign(v2.sign_bytes(CHAIN))})
    with pytest.raises(ErrVoteConflict) as ei:
        vset.add_vote(v2)
    ev = ei.value.evidence
    assert ev.vote_a.block_id.key() != ev.vote_b.block_id.key()
    # duplicate of the original is a no-op, not a conflict
    assert vset.add_vote(v1) is False


def _forge_vote(priv, vs, height, round_, type_, block_id):
    """Sign with the raw key, bypassing the PrivValidator HRS guard —
    byzantine behavior for conflict tests."""
    idx = vs.index_of(priv.address)
    v = Vote(validator_address=priv.address, validator_index=idx,
             height=height, round=round_, type=type_, block_id=block_id)
    return Vote(**{**v.__dict__,
                   "signature": priv.priv_key.sign(v.sign_bytes(CHAIN))})


def test_conflicting_votes_not_retained_for_untracked_blocks():
    """Advisor regression: a byzantine validator signing many distinct block
    hashes must not grow per-VoteSet memory (reference vote_set.go:241-244
    forgets conflicting votes for untracked keys)."""
    privs, vs = _valset(4)
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    assert vset.add_vote(_vote(privs[0], vs, 1, 0, TYPE_PREVOTE,
                               _block_id(b"aa")))
    before = len(vset._votes_by_block)
    for i in range(50):
        spam = _forge_vote(privs[0], vs, 1, 0, TYPE_PREVOTE,
                           _block_id(b"s%02d" % i))
        with pytest.raises(ErrVoteConflict):
            vset.add_vote(spam)
    assert len(vset._votes_by_block) == before


def test_peer_maj23_commit_carries_full_two_thirds():
    """Advisor regression: when 2/3 forms partly from conflicting votes via
    the peer_maj23 path, make_commit must still extract a commit whose
    tallied power passes verify_commit (reference vote_set.go:219-223,267+)."""
    privs, vs = _valset(4)  # power 10 each, quorum > 26
    bid = _block_id(b"good")
    other = _block_id(b"evil")
    vset = VoteSet(CHAIN, 1, 0, TYPE_PRECOMMIT, vs)
    # privs[0] first precommits a different block (its canonical vote)...
    assert vset.add_vote(_vote(privs[0], vs, 1, 0, TYPE_PRECOMMIT, other))
    vset.add_vote(_vote(privs[1], vs, 1, 0, TYPE_PRECOMMIT, bid))
    vset.add_vote(_vote(privs[2], vs, 1, 0, TYPE_PRECOMMIT, bid))
    assert vset.two_thirds_majority() is None      # 20/40 for bid
    # ...a peer claims bid has 2/3, and privs[0]'s conflicting vote for bid
    # arrives: it must count toward bid AND be extractable
    vset.set_peer_maj23("peerA", bid)
    dup = _forge_vote(privs[0], vs, 1, 0, TYPE_PRECOMMIT, bid)
    with pytest.raises(ErrVoteConflict):
        vset.add_vote(dup)
    maj = vset.two_thirds_majority()
    assert maj is not None and maj.key() == bid.key()
    commit = vset.make_commit()
    vs.verify_commit(CHAIN, bid, 1, commit)        # full +2/3 present


def test_proof_short_aunts_returns_false():
    """Advisor regression: a proof with fewer aunts than the path depth must
    fail verification cleanly, not raise IndexError."""
    from tendermint_tpu.types.merkle import Proof, proofs
    rt, prs = proofs([b"a", b"b", b"c", b"d"])
    p = prs[2]
    truncated = Proof(p.total, p.index, p.leaf, p.aunts[:1])
    assert truncated.verify(rt) is False
    assert Proof(p.total, p.index, p.leaf, ()).verify(rt) is False


def test_verify_commit_rejects_bad_sig_on_other_block_precommit():
    """Advisor regression: a commit carrying a garbage signature on a
    precommit for a DIFFERENT block must be rejected, matching the
    reference's VerifyCommit which checks every non-nil signature."""
    privs, vs = _valset(4)
    bid = _block_id()
    vset = VoteSet(CHAIN, 5, 0, TYPE_PRECOMMIT, vs)
    for p in privs[:3]:
        vset.add_vote(_vote(p, vs, 5, 0, TYPE_PRECOMMIT, bid))
    commit = vset.make_commit()
    # splice in a non-tallied precommit for another block with a forged sig
    other = _block_id(b"zz")
    idx = vs.index_of(privs[3].address)
    garbage = Vote(validator_address=privs[3].address, validator_index=idx,
                   height=5, round=0, type=TYPE_PRECOMMIT, block_id=other,
                   signature=b"\x09" * 64)
    commit.precommits[idx] = garbage
    with pytest.raises(ValueError, match="signature"):
        vs.verify_commit(CHAIN, bid, 5, commit)


def test_malformed_votes_cannot_poison_batches():
    """Regression: wire-decoded votes with non-standard hash/sig lengths
    must be rejected individually, never crash or misalign batch lanes."""
    privs, vs = _valset(4)
    bid = _block_id()
    votes = [_vote(p, vs, 1, 0, TYPE_PREVOTE, bid) for p in privs]
    # 20-byte block hash (attacker-controlled via BlockID wire decode)
    evil_bid = BlockID(hash=b"\x01" * 20, parts=PartSetHeader(1, b"\x02" * 32))
    evil = Vote(validator_address=privs[1].address,
                validator_index=vs.index_of(privs[1].address), height=1,
                round=0, type=TYPE_PREVOTE, block_id=evil_bid,
                signature=b"\x00" * 64)
    short_sig = Vote(**{**votes[2].__dict__, "signature": b"\x00" * 63})
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    out = vset.add_votes_batched([votes[0], evil, short_sig, votes[3]])
    assert out[0] is True and out[3] is True
    assert isinstance(out[1], ValueError) and isinstance(out[2], ValueError)
    assert vset.sum() == 20
    with pytest.raises(ValueError):
        vset.add_vote(evil)
    # commit with a malformed precommit: clean structural error, no reshape
    for p in privs[:3]:
        vset2 = None
    vset2 = VoteSet(CHAIN, 1, 0, TYPE_PRECOMMIT, vs)
    for p in privs[:3]:
        vset2.add_vote(_vote(p, vs, 1, 0, TYPE_PRECOMMIT, bid))
    commit = vset2.make_commit()
    commit.precommits[0] = Vote(**{**commit.precommits[0].__dict__,
                                   "signature": b"\x00" * 63})
    with pytest.raises(ValueError, match="commit vote 0"):
        vs.verify_commit(CHAIN, bid, 1, commit)
    # sign_bytes refuses non-32-byte hashes outright
    with pytest.raises(ValueError, match="32 bytes"):
        evil.sign_bytes(CHAIN)


def test_voteset_batched_matches_scalar():
    privs, vs = _valset(4)
    bid = _block_id()
    votes = [_vote(p, vs, 1, 0, TYPE_PREVOTE, bid) for p in privs]
    bad = Vote(**{**votes[2].__dict__, "signature": b"\x02" * 64})
    vset = VoteSet(CHAIN, 1, 0, TYPE_PREVOTE, vs)
    out = vset.add_votes_batched([votes[0], votes[1], bad, votes[3]])
    assert out[0] is True and out[1] is True and out[3] is True
    assert isinstance(out[2], ValueError)
    assert vset.sum() == 30


# -- validator set ---------------------------------------------------------

def test_proposer_rotation_deterministic():
    privs, vs = _valset(4, power=10)
    vs2 = vs.copy()
    seq1 = []
    for _ in range(12):
        seq1.append(vs.proposer.address)
        vs.increment_accum(1)
    seq2 = []
    for _ in range(12):
        seq2.append(vs2.proposer.address)
        vs2.increment_accum(1)
    assert seq1 == seq2
    # equal power: every validator proposes equally often over 3 cycles
    from collections import Counter
    c = Counter(seq1)
    assert set(c.values()) == {3}


def test_proposer_rotation_weighted():
    privs = [PrivValidator(PrivKey.generate()) for _ in range(3)]
    vs = ValidatorSet([Validator(privs[0].pub_key, 100),
                       Validator(privs[1].pub_key, 1),
                       Validator(privs[2].pub_key, 1)])
    from collections import Counter
    c = Counter()
    for _ in range(102):
        c[vs.proposer.address] += 1
        vs.increment_accum(1)
    assert c[privs[0].address] == 100


def test_valset_updates():
    privs, vs = _valset(3, power=10)
    h0 = vs.hash()
    new_priv = PrivValidator(PrivKey.generate())
    vs.apply_updates([(new_priv.pub_key.bytes_, 7)])
    assert vs.size() == 4 and vs.total_voting_power() == 37
    assert vs.hash() != h0
    vs.apply_updates([(privs[0].pub_key.bytes_, 0)])
    assert vs.size() == 3 and vs.total_voting_power() == 27
    with pytest.raises(ValueError):
        vs.apply_updates([(privs[0].pub_key.bytes_, 0)])  # already gone


def test_verify_commit():
    privs, vs = _valset(4)
    bid = _block_id()
    vset = VoteSet(CHAIN, 5, 0, TYPE_PRECOMMIT, vs)
    for p in privs[:3]:
        vset.add_vote(_vote(p, vs, 5, 0, TYPE_PRECOMMIT, bid))
    commit = vset.make_commit()
    commit.validate_basic()
    vs.verify_commit(CHAIN, bid, 5, commit)          # ok
    with pytest.raises(ValueError, match="height"):
        vs.verify_commit(CHAIN, bid, 6, commit)
    with pytest.raises(ValueError, match="voting power"):
        other = _block_id(b"zz")
        vs.verify_commit(CHAIN, other, 5, commit)
    # tampered signature caught by the batch
    commit.precommits[0] = Vote(**{**commit.precommits[0].__dict__,
                                   "signature": b"\x03" * 64})
    with pytest.raises(ValueError, match="signature"):
        vs.verify_commit(CHAIN, bid, 5, commit)


def test_commit_codec_roundtrip():
    privs, vs = _valset(4)
    bid = _block_id()
    vset = VoteSet(CHAIN, 5, 2, TYPE_PRECOMMIT, vs)
    for p in privs[:3]:
        vset.add_vote(_vote(p, vs, 5, 2, TYPE_PRECOMMIT, bid))
    commit = vset.make_commit()
    r = Reader(commit.encode())
    c2 = Commit.decode(r)
    r.expect_done()
    assert c2.hash() == commit.hash()
    assert c2.round() == 2
    vs.verify_commit(CHAIN, bid, 5, c2)


# -- priv validator --------------------------------------------------------

def test_priv_validator_hrs_guard(tmp_path):
    path = str(tmp_path / "priv.json")
    pv = PrivValidator.generate(path)
    _, vs0 = _valset(1)
    bid = _block_id()
    v = Vote(validator_address=pv.address, validator_index=0, height=5,
             round=1, type=TYPE_PREVOTE, block_id=bid)
    sig = pv.sign_vote(CHAIN, v)
    # same HRS + same bytes: replay returns identical signature
    assert pv.sign_vote(CHAIN, v) == sig
    # same HRS, different bytes: double-sign refused
    v2 = Vote(**{**v.__dict__, "block_id": _block_id(b"qq")})
    with pytest.raises(DoubleSignError):
        pv.sign_vote(CHAIN, v2)
    # regression refused
    v3 = Vote(**{**v.__dict__, "height": 4})
    with pytest.raises(DoubleSignError):
        pv.sign_vote(CHAIN, v3)
    # persistence: reload carries the guard across restarts
    pv2 = PrivValidator.load(path)
    assert pv2.last_height == 5
    with pytest.raises(DoubleSignError):
        pv2.sign_vote(CHAIN, v2)
    # precommit after prevote at same H/R is a step advance: allowed
    v4 = Vote(**{**v.__dict__, "type": TYPE_PRECOMMIT})
    pv2.sign_vote(CHAIN, v4)


def test_proposal_sign_bytes_distinct():
    p1 = Proposal(height=3, round=0,
                  block_parts_header=PartSetHeader(4, b"\x07" * 32))
    p2 = Proposal(height=3, round=0,
                  block_parts_header=PartSetHeader(4, b"\x08" * 32))
    assert p1.sign_bytes(CHAIN) != p2.sign_bytes(CHAIN)
    assert len(p1.sign_bytes(CHAIN)) == 128
    # vote and proposal sign-bytes never collide (type byte)
    bid = _block_id()
    v = Vote(validator_address=b"\x01" * 20, validator_index=0, height=3,
             round=0, type=TYPE_PREVOTE, block_id=bid)
    assert v.sign_bytes(CHAIN) != p1.sign_bytes(CHAIN)


def test_wire_commit_roundtrip_and_lanes():
    """A commit decoded from the wire: lossless against the Vote form it
    was encoded from, and identical verify-lane output from
    commit_verify_lanes."""
    import numpy as np
    from chainutil import make_validators, make_commit
    from tendermint_tpu.types import BlockID, Commit
    from tendermint_tpu.types.codec import Reader
    from tendermint_tpu.types.part_set import PartSetHeader

    privs, vs = make_validators(8)
    bid = BlockID(b"\x11" * 32, PartSetHeader(2, b"\x22" * 32))
    commit = make_commit(privs, vs, "cc-chain", 5, bid)
    wc = Commit.decode(Reader(commit.encode()))
    assert wc.wire_columns() is not None
    assert (wc.height(), wc.round(), wc.size()) == (5, 0, 8)
    assert wc.num_sigs() == commit.num_sigs()

    # lanes match the object form exactly (templates content included)
    lo = vs.commit_verify_lanes("cc-chain", bid, 5, commit)
    lc = vs.commit_verify_lanes("cc-chain", bid, 5, wc)
    assert np.array_equal(lo[0][lo[1]], lc[0][lc[1]])   # per-lane msgs
    assert np.array_equal(lo[2], lc[2])                 # sigs
    assert np.array_equal(lo[3], lc[3])                 # powers
    assert np.array_equal(lo[4], lc[4])                 # idxs
    assert lo[5] == lc[5] == 0                          # foreign power

    # verify_commit accepts the wire form end to end
    vs.verify_commit("cc-chain", bid, 5, wc)

    # and the votes it makes on demand are the ones that were encoded
    assert wc.block_id == commit.block_id
    assert wc.precommits == commit.precommits
    assert wc.encode() == commit.encode()

    # a commit for ANOTHER block id: powers zero, foreign power total
    other = BlockID(b"\x33" * 32, PartSetHeader(2, b"\x44" * 32))
    lo2 = vs.commit_verify_lanes("cc-chain", other, 5, wc)
    assert lo2[3].sum() == 0 and lo2[5] == vs.total_voting_power()

    # sparse commit (missing votes) stays in its bytes too and keeps
    # lane alignment: a present vote's member is its POSITION
    commit.precommits[3] = None
    sparse = Commit.decode(Reader(commit.encode()))
    assert sparse.wire_columns()[5] == (3,) and sparse.num_sigs() == 7
    assert sparse == commit and sparse.encode() == commit.encode()
    ls = vs.commit_verify_lanes("cc-chain", bid, 5, sparse)
    assert list(ls[4]) == [i for i in range(8) if i != 3]
    lo3 = vs.commit_verify_lanes("cc-chain", bid, 5, commit)
    assert all(np.array_equal(a, b) for a, b in zip(ls, lo3))
    vs.verify_commit("cc-chain", bid, 5, sparse)


def test_accum_array_rotation_equivalence():
    """The array-resident accumulator rotation must match a plain
    per-object reference implementation over long sequences of
    increments, copies, and membership updates (accums live on the SET,
    objects are shared copy-on-write between copies — regression for the
    replay-hot rewrite)."""
    import random
    from tendermint_tpu.types.keys import PrivKey
    from tendermint_tpu.types.validator import Validator, ValidatorSet

    rng = random.Random(7)
    privs = [PrivKey.generate() for _ in range(7)]
    powers = [rng.randint(1, 50) for _ in range(7)]

    # reference model: dict addr -> [power, accum]
    class Ref:
        def __init__(self, pairs):
            self.m = {p.pub_key.address: [pw, 0] for p, pw in pairs}

        def increment(self, times):
            assert times == 1
            total = sum(pw for pw, _ in self.m.values())
            for ent in self.m.values():
                ent[1] += ent[0]
            # max accum, ties -> lowest address
            best = max(self.m.items(),
                       key=lambda kv: (kv[1][1],
                                       bytes(255 - b for b in kv[0])))
            best[1][1] -= total
            return best[0]

    vs = ValidatorSet([Validator(p.pub_key, pw)
                       for p, pw in zip(privs[:5], powers[:5])])
    ref = Ref(list(zip(privs[:5], powers[:5])))
    ref.increment(1)          # ValidatorSet.__init__ rotates once

    for step in range(60):
        k = rng.randint(1, 3)
        snap = vs.copy()      # frozen history (consensus keeps these)
        snap_accums = [snap.accum_of(i) for i in range(snap.size())]
        for _ in range(k):
            want = ref.increment(1)
        vs.increment_accum(k)
        assert vs.proposer.address == want, f"step {step}"
        # the frozen copy must be untouched by the original's rotation
        assert [snap.accum_of(i) for i in range(snap.size())] == \
            snap_accums, f"copy leaked at step {step}"
        if step == 30:
            # power change + new member: survivors keep accums, the
            # entrant starts at 0 (reference updateValidators)
            newp = privs[5]
            diffs = [(privs[0].pub_key.bytes_, powers[0] + 9),
                     (newp.pub_key.bytes_, 13)]
            before = {vs.validators[i].address: vs.accum_of(i)
                      for i in range(vs.size())}
            vs.apply_updates(diffs)
            for i, v in enumerate(vs.validators):
                if v.address in before:
                    assert vs.accum_of(i) == before[v.address]
                else:
                    assert vs.accum_of(i) == 0
            ref.m[privs[0].pub_key.address][0] = powers[0] + 9
            ref.m[newp.pub_key.address] = [13, 0]
    # encode/decode round-trips the array state
    from tendermint_tpu.types.codec import Reader
    vs2 = ValidatorSet.decode(Reader(vs.encode()))
    assert [vs2.accum_of(i) for i in range(vs2.size())] == \
        [vs.accum_of(i) for i in range(vs.size())]
    assert vs2.proposer.address == vs.proposer.address
