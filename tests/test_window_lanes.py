"""Property tests for the window-vectorized lane builder: for every
window of commits, `window_commit_lanes` must be BYTE-identical to the
per-block `commit_verify_lanes` + `merge_commit_lanes` path it fuses —
arrays, per-block tallies, and error blame all match.  This is the
license for the reactor's look-ahead to take the one-numpy-pass fast
path over commits that stayed in their wire bytes (`Commit.decode`):
the reference below walks the SAME commits decoded vote by vote.  Any
divergence here is a consensus-verification bug, not a perf
regression."""

import numpy as np
import pytest

from tendermint_tpu.types import BlockID, Commit, Vote, ZERO_BLOCK_ID
from tendermint_tpu.types.block import PartSetHeader
from tendermint_tpu.types.canonical import TYPE_PRECOMMIT
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.validator import (CommitFormatError,
                                            CommitPowerError,
                                            CommitSignatureError,
                                            ValidatorSet, Validator,
                                            merge_commit_lanes,
                                            window_commit_lanes,
                                            window_tally_check)
from tests.chainutil import (build_chain, make_validators, sign_vote)

CHAIN = "window-lanes-test"


def rand_bid(rng):
    return BlockID(rng.integers(0, 256, 32, dtype=np.uint8).tobytes(),
                   PartSetHeader(int(rng.integers(1, 5)),
                                 rng.integers(0, 256, 32,
                                              dtype=np.uint8).tobytes()))


def wire_commit(rng, vs, bid, height, round_=0) -> Commit:
    """A commit as `Commit.decode` leaves an honest peer's: every vote
    present for `bid`, random signatures, backed by its wire bytes."""
    commit = Commit.decode(Reader(Commit(block_id=bid, precommits=[
        Vote(validator_address=v.address, validator_index=i, height=height,
             round=round_, type=TYPE_PRECOMMIT, block_id=bid,
             signature=rng.integers(0, 256, 64, dtype=np.uint8).tobytes())
        for i, v in enumerate(vs.validators)]).encode()))
    assert commit.wire_columns() is not None
    return commit


def object_form(commit: Commit) -> Commit:
    """The same commit holding votes: what the parent's decoder made."""
    obj = Commit(block_id=commit.block_id,
                 precommits=list(commit.precommits))
    assert obj.wire_columns() is None
    return obj


def rand_wire_window(rng, vs, n_blocks, foreign_p=0.3):
    """Random window of wire-backed commits: random rounds, and a
    fraction of commits endorsing a foreign block."""
    items = []
    for h in range(1, n_blocks + 1):
        bid = rand_bid(rng)
        cbid = bid if rng.random() >= foreign_p else rand_bid(rng)
        items.append((bid, h, wire_commit(rng, vs, cbid, h,
                                          int(rng.integers(0, 3)))))
    return items


def per_block_reference(vs, items):
    """The scalar path the fast path must reproduce: the per-vote loop
    over the object form of every commit."""
    arrays = [vs.commit_verify_lanes(CHAIN, bid, h, object_form(c))
              for bid, h, c in items]
    merged = merge_commit_lanes(arrays)
    counts = np.asarray([len(a[4]) for a in arrays], dtype=np.int64)
    tallied = np.asarray([int(a[3].sum()) for a in arrays],
                         dtype=np.int64)
    foreign = np.asarray([a[5] for a in arrays], dtype=np.int64)
    return merged + (counts, tallied, foreign)


def assert_windows_equal(fast, ref):
    names = ("templates", "tmpl_idx", "sigs", "idxs", "counts",
             "tallied", "foreign")
    for name, f, r in zip(names, fast, ref):
        assert f.dtype == r.dtype, name
        assert f.shape == r.shape, name
        assert np.array_equal(f, r), name


@pytest.mark.parametrize("seed", range(8))
def test_wire_window_byte_identical(seed):
    rng = np.random.default_rng(seed)
    n_vals = int(rng.integers(1, 12))
    _, vs = make_validators(n_vals, seed=seed)
    items = rand_wire_window(rng, vs, int(rng.integers(1, 20)))
    assert_windows_equal(window_commit_lanes(vs, CHAIN, items),
                         per_block_reference(vs, items))


def test_wire_window_uneven_powers():
    """Tallied/foreign power must weight by validator power, not count."""
    rng = np.random.default_rng(99)
    privs, _ = make_validators(6, seed=1)
    vs = ValidatorSet([Validator(p.pub_key, 10 + 7 * i)
                       for i, p in enumerate(privs)])
    items = rand_wire_window(rng, vs, 10, foreign_p=0.5)
    assert_windows_equal(window_commit_lanes(vs, CHAIN, items),
                         per_block_reference(vs, items))


def test_real_chain_wire_vs_object_form():
    """A real signed chain: the wire-backed fast path and the
    object-form fallback must produce the same device batch."""
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 6)
    obj_items, wire_items = [], []
    for block, ps, seen in chain:
        bid = BlockID(block.hash(), ps.header)
        obj_items.append((bid, block.height, seen))
        dec = Commit.decode(Reader(seen.encode()))
        assert dec.wire_columns() is not None
        wire_items.append((bid, block.height, dec))
    fast = window_commit_lanes(vs, CHAIN, wire_items)
    ref = window_commit_lanes(vs, CHAIN, obj_items)   # fallback path
    assert_windows_equal(fast, ref)
    # unanimous same-block commits: full power tallied, nothing foreign
    assert (fast[5] == vs.total_voting_power()).all()
    assert (fast[6] == 0).all()


def test_mixed_window_falls_back_and_matches():
    """One commit with an absent AND a nil vote decodes vote by vote and
    routes the whole window through the per-block path; the result still
    equals the per-block reference."""
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 5)
    items = []
    for i, (block, ps, seen) in enumerate(chain):
        bid = BlockID(block.hash(), ps.header)
        if i == 2:
            # rebuild the commit with validator 0 absent and validator 1
            # voting nil: strays a wire-backed commit cannot represent
            votes = list(seen.precommits)
            votes[0] = None
            # fresh PrivValidator objects (same keys): the originals'
            # HRS double-sign guard rejects re-signing an old height
            fresh, _ = make_validators(4)
            by_idx = {vs.index_of(p.address): p for p in fresh}
            votes[1] = sign_vote(by_idx[1], vs, CHAIN, block.height, 0,
                                 TYPE_PRECOMMIT, ZERO_BLOCK_ID)
            seen = Commit(block_id=seen.block_id, precommits=votes)
        dec = Commit.decode(Reader(seen.encode()))
        assert (dec.wire_columns() is None) == (i == 2)
        items.append((bid, block.height, dec))
    fast = window_commit_lanes(vs, CHAIN, items)
    assert_windows_equal(fast, per_block_reference(vs, items))
    # the doctored block: 3 lanes (the nil vote still verifies), only 2
    # tallied, none foreign (nil votes never count as foreign)
    assert fast[4][2] == 3 and fast[5][2] == 20 and fast[6][2] == 0


def test_empty_window():
    _, vs = make_validators(3)
    out = window_commit_lanes(vs, CHAIN, [])
    assert all(len(a) == 0 for a in out)


def test_malformed_commit_raises_format_error_with_height():
    rng = np.random.default_rng(5)
    _, vs = make_validators(4, seed=2)
    items = rand_wire_window(rng, vs, 4, foreign_p=0.0)
    bid, h, c = items[2]
    items[2] = (bid, h, wire_commit(rng, vs, c.block_id, h + 9))
    with pytest.raises(CommitFormatError) as ei:
        window_commit_lanes(vs, CHAIN, items)
    assert ei.value.height == h


def test_tally_check_blames_first_failing_block():
    rng = np.random.default_rng(6)
    _, vs = make_validators(5, seed=4)
    items = []
    for h in range(1, 5):
        bid = rand_bid(rng)
        items.append((bid, h, wire_commit(rng, vs, bid, h)))
    _, _, _, _, counts, tallied, foreign = \
        window_commit_lanes(vs, CHAIN, items)
    total = vs.total_voting_power()

    # all lanes verify, all power present: no error
    ok = np.ones(int(counts.sum()), dtype=bool)
    window_tally_check(items, ok, counts, tallied, foreign, total)

    # a failed lane in block 3 (window order) blames height 3 with the
    # block-local lane index
    bad = ok.copy()
    bad[int(counts[:2].sum()) + 1] = False
    with pytest.raises(CommitSignatureError) as ei:
        window_tally_check(items, bad, counts, tallied, foreign, total)
    assert ei.value.height == 3 and ei.value.lane == 1

    # power shortfall in block 2 blames height 2
    short = tallied.copy()
    short[1] = total * 2 // 3
    with pytest.raises(CommitPowerError) as ei:
        window_tally_check(items, ok, counts, short, foreign, total)
    assert ei.value.height == 2
