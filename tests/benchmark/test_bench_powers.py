"""A chain whose voting powers move (`powers` of a traffic mix): which
power a member has at a height is one seeded function of the plan, the
builder's `val:` txs make exactly the sets that function gives, every
header holds the hash of its own height's powers, the program follows
such a chain to the builder's sets, the check compares powers; and what
the builder serves without the plan is byte for byte the parent's."""

import hashlib
import json
import os
import time
import types

import pytest

import benchutil
from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain
from tendermint_tpu.types import Block

EMPTY = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "powers-test.json")) as _f:
    MIX = json.load(_f)
# the chains of these tests: 6 validators, two redrawn every other height
N_VALS, N_BLOCKS, SEED = 6, 18, 2**31 + 47
PLAN = {"change_every_blocks": 2, "members": 2, "min": 1, "max": 60}
VALSET = {"change_every_blocks": 3, "swap": 1}
VALSET_APP = "valset_kvstore"
CHAIN_ID = "bench-powers"
# sha256 over the joined `encoded`, `block_hash` and `app_hash` lists of
# build_chain("bench-pin-47", ..., EMPTY, seed SEED) with no `powers` plan
# as the PARENT tree gives them (computed on `git archive 023c3bd`):
# (validators, blocks, valset plan, absent plan)
ABSENT = {"late_per_1000": 150, "down": 1, "down_for_blocks": 5}
PARENT = {
    "plain": (7, 12, None, None, (
        "405399e05c7ad219aeebe21b93a78ee7ad0b1d0e34910ed8ab4f3987c566e0bc",
        "843cabf6e881f66eb08ff48eb70028c7b4267feacd7b00c6b3b077f06a993722",
        "f8294564578d3331fc3fd2cd4e55dcf56ff6381752e6a6a4748836cea341d1d3")),
    "valset": (7, 14, {"change_every_blocks": 3, "swap": 1}, None, (
        "a35e684609d6a5afe0cfe5c96bb67f4df8c31f44419249caf6c4d82a503781ae",
        "86069a269e8c3c488f1c8678a54baa2519582c0a0a9d07a8eecaa6a8e3d60168",
        "4e1e8759126dca4c230126e207ea4d84c6410a0ef0bc8180bb76a09b4c36b707")),
    "absent": (7, 12, None, ABSENT, (
        "b264417741cdf457c498c45e146b99cdcab952176d6a45216b4f4ecac2881b13",
        "78d82c2c9928f6cbaab53c0b0b206ae1a93b8ecd82133a2bc3d798c4fbd3429a",
        "f8294564578d3331fc3fd2cd4e55dcf56ff6381752e6a6a4748836cea341d1d3")),
    "valset-absent": (7, 14, {"change_every_blocks": 3, "swap": 1}, ABSENT, (
        "69316f62137bc663f303fb95ab3ac88d97f26b34d364e6120d0ccebfffab9c12",
        "def210f0e9968e2f5d992cce49bfb6a91d9c6e701c4856c180467c5dc7b1c3ed",
        "4e1e8759126dca4c230126e207ea4d84c6410a0ef0bc8180bb76a09b4c36b707")),
}


def _digest(parts) -> str:
    return hashlib.sha256(b"".join(parts)).hexdigest()


def _powers(h, valset=None, plan=PLAN, seed=SEED, n=N_VALS) -> dict:
    """key index -> power at height h."""
    return dict(zip(chain.valset_members(seed, n, valset, h),
                    chain.powers_at(seed, n, valset, plan, h), strict=True))


# -- which power a member has -------------------------------------------------

def test_without_a_plan_every_member_has_the_one_power_at_every_height():
    for plan in (None, {}):
        for valset in (None, VALSET):
            for h in (1, 2, 3, 4, 700):
                assert chain.powers_at(SEED, N_VALS, valset, plan, h) == (
                    chain.POWER,) * N_VALS
    _seeds, vs = chain.valset_at(SEED, N_VALS, None, 9)
    assert [v.voting_power for v in vs.validators] == [chain.POWER] * N_VALS
    assert vs.hash() == chain.make_validators(SEED, N_VALS)[1].hash()


def test_powers_at_is_pure_seeded_and_moves_where_the_plan_says():
    seen = [chain.powers_at(SEED, N_VALS, None, PLAN, h)
            for h in range(1, 62)]
    # asked again, and out of order: the same
    assert seen == [chain.powers_at(SEED, N_VALS, None, PLAN, h)
                    for h in range(1, 62)]
    assert [chain.powers_at(SEED, N_VALS, None, dict(PLAN), h)
            for h in (40, 7, 61, 1)] == [seen[39], seen[6], seen[60], seen[0]]
    assert seen != [chain.powers_at(SEED + 1, N_VALS, None, PLAN, h)
                    for h in range(1, 62)]
    # the genesis set, and the height before the first change: POWER
    assert seen[0] == seen[1] == (chain.POWER,) * N_VALS
    for h in range(1, 61):
        old, new = seen[h - 1], seen[h]
        moved = [i for i in range(N_VALS) if old[i] != new[i]]
        if h % 2:
            assert not moved          # the diffs of an EVEN height move
        else:
            assert len(moved) <= 2    # a redraw may land on the old power
        assert all(1 <= p <= 60 for p in new)
    assert len(set(seen)) > 20
    drawn = {p for powers in seen[2:] for p in powers}
    assert min(drawn) >= 1 and max(drawn) <= 60 and len(drawn) > 15


def test_the_members_redrawn_and_their_powers_are_the_hashes_the_readme_states():
    for h in (2, 4, 18):
        pick = sorted(range(N_VALS), key=lambda i: hashlib.sha256(
            b"tm-bench/%d/power-pick/%d/%d" % (SEED, h, i)).digest())[:2]
        want = dict(_powers(h))
        for i in pick:
            want[i] = 1 + int.from_bytes(hashlib.sha256(
                b"tm-bench/%d/power/%d/%d" % (SEED, h, i)).digest()[:4],
                "big") % 60
        assert _powers(h + 1) == want


def test_an_epoch_every_height_over_a_long_chain_stays_cheap():
    """E = 1 over a cell's 18,113 heights: each height's powers are kept
    as they are made, so asking for all of them is one pass (100 hashes
    a height) and asking again is a lookup."""
    plan = {"change_every_blocks": 1, "members": 3, "min": 1, "max": 100}
    t0 = time.perf_counter()
    last = chain.powers_at(SEED, 100, None, plan, 18113)
    first_pass = time.perf_counter() - t0
    t0 = time.perf_counter()
    for h in range(18113, 0, -9):
        chain.powers_at(SEED, 100, None, plan, h)
    assert chain.powers_at(SEED, 100, None, plan, 18113) == last
    again = time.perf_counter() - t0
    assert first_pass < 60 and again < 5, (first_pass, again)
    assert len(last) == 100 and len(set(last)) > 30


def test_under_both_plans_a_key_that_joins_joins_at_power_and_keeps_its_draws():
    both = [_powers(h, VALSET) for h in range(1, 41)]
    for h in range(1, 40):
        old, new = both[h - 1], both[h]
        assert set(new) == set(chain.valset_members(SEED, N_VALS, VALSET,
                                                    h + 1))
        for i in set(new) - set(old):
            assert h % 3 == 0 and new[i] == chain.POWER and i >= N_VALS
        stayed = [i for i in new if i in old and new[i] != old[i]]
        assert not stayed or h % 2 == 0
    # a key that joined is redrawn later like any other
    joined = {i for p in both for i in p if i >= N_VALS}
    assert any(p[i] != chain.POWER for p in both for i in joined if i in p)
    # and the membership is the valset plan's, whatever the powers do
    assert [tuple(p) for p in both] == [
        chain.valset_members(SEED, N_VALS, VALSET, h) for h in range(1, 41)]


@pytest.mark.parametrize("plan", [
    dict(PLAN, change_every_blocks=0), dict(PLAN, members=0),
    dict(PLAN, members=N_VALS + 1), dict(PLAN, min=0),
    dict(PLAN, min=61), dict(PLAN, max=2**32), dict(PLAN, max=2.5),
    dict(PLAN, members=True), dict(PLAN, swap=1),
    {k: v for k, v in PLAN.items() if k != "max"}],
    ids=["epoch-under-1", "no-member", "more-members-than-the-set",
         "min-under-1", "max-under-min", "max-over-four-bytes",
         "not-whole", "not-a-number", "unknown-key", "a-key-left-out"])
def test_a_plan_that_cannot_be_run_is_refused_by_name(plan, monkeypatch):
    with pytest.raises(ValueError, match="powers plan"):
        chain.powers_at(SEED, N_VALS, None, plan, 1)
    seeds, vs = chain.valset_at(SEED, N_VALS, None, 1)
    with pytest.raises(ValueError, match="powers plan"):
        chain.build_chain(CHAIN_ID, seeds, vs, 2, EMPTY, SEED, powers=plan)
    # and a cell whose traffic file holds it fails before a child is
    # started or anything is booted
    monkeypatch.setattr(cell_mod.children_mod, "Children", lambda root: 1 / 0)
    cell = {"config": {"validators": N_VALS, "source_peers": 1},
            "traffic": {"block": EMPTY, "powers": plan}}
    with pytest.raises(ValueError, match="powers plan"):
        cell_mod.run_cell(REPO, cell, SEED, 1.0, False, time.monotonic())


def test_a_mix_that_states_both_an_absent_and_a_powers_plan_is_refused(
        monkeypatch):
    """`absent_at` cuts its list where MORE than 2/3 of the power still
    signs by counting heads of equal power; under uneven powers that
    count says nothing, so the two plans together are an error that
    names both, wherever a plan is first read."""
    absent = {"late_per_1000": 100}
    both = "absent plan .* together with powers plan"
    with pytest.raises(ValueError, match=both):
        chain.absent_at(SEED, N_VALS, None, absent, 1, PLAN)
    assert chain.absent_at(SEED, N_VALS, None, None, 1, PLAN) == ()
    seeds, vs = chain.valset_at(SEED, N_VALS, None, 1)
    with pytest.raises(ValueError, match=both):
        chain.build_chain(CHAIN_ID, seeds, vs, 2, EMPTY, SEED, absent=absent,
                          powers=PLAN)
    monkeypatch.setattr(cell_mod.children_mod, "Children", lambda root: 1 / 0)
    cell = {"config": {"validators": N_VALS, "source_peers": 1},
            "traffic": {"block": EMPTY, "absent": absent, "powers": PLAN}}
    with pytest.raises(ValueError, match=both):
        cell_mod.run_cell(REPO, cell, SEED, 1.0, False, time.monotonic())


# -- the chain the builder serves ----------------------------------------------

@pytest.mark.parametrize("workers", [0, 2, None],
                         ids=["in-process", "workers", "as-the-child"])
@pytest.mark.parametrize("mix", sorted(PARENT))
def test_without_a_powers_plan_the_chain_is_the_parents_byte_for_byte(
        mix, workers):
    n_vals, n_blocks, valset, absent, want = PARENT[mix]
    seeds, vs = chain.valset_at(SEED, n_vals, valset, 1)
    with chain.Signers(seeds, workers) as sg:
        built = chain.build_chain("bench-pin-47", seeds, vs, n_blocks, EMPTY,
                                  SEED, sg, valset=valset, absent=absent,
                                  powers=None)
    assert tuple(_digest(built[k]) for k in
                 ("encoded", "block_hash", "app_hash")) == want
    assert (min(built["signed"]) < n_vals) == bool(absent)


def _build(plan=PLAN, valset=None, workers=0, keep_objects=False, seed=SEED,
           n_vals=N_VALS, n_blocks=N_BLOCKS):
    seeds, vs = chain.valset_at(seed, n_vals, valset, 1, plan)
    with chain.Signers(seeds, workers) as sg:
        return chain.build_chain(CHAIN_ID, seeds, vs, n_blocks, EMPTY, seed,
                                 sg, keep_objects=keep_objects, valset=valset,
                                 powers=plan)


@pytest.fixture(scope="module")
def chains():
    return {"powers": _build(keep_objects=True),
            "powers-and-churn": _build(valset=VALSET, keep_objects=True)}


VALSETS = {"powers": None, "powers-and-churn": VALSET}


@pytest.mark.parametrize("name", sorted(VALSETS))
def test_every_header_holds_the_hash_of_its_own_heights_powers(chains, name):
    """...and every commit is signed by every member of that height's
    set, by OpenSSL over the program's canonical sign-bytes."""
    from tendermint_tpu.crypto import native
    built, valset = chains[name], VALSETS[name]
    assert built["encoded"] == _build(valset=valset, workers=2)["encoded"]
    assert built["encoded"] != _build(plan=None, valset=valset)["encoded"]
    assert built["encoded"] != _build(valset=valset,
                                      seed=SEED + 1)["encoded"]
    blocks = [Block.decode_bytes(e) for e in built["encoded"]]
    hashes = []
    for h, block in enumerate(blocks, 1):
        _seeds, vs = chain.valset_at(SEED, N_VALS, valset, h, PLAN)
        assert block.header.validators_hash == vs.hash()
        assert [v.voting_power for v in vs.validators] == [
            _powers(h, valset)[i] for i in _by_set_order(h, valset)]
        hashes.append(vs.hash())
        block.validate_basic()
        if h == 1:
            continue
        _seeds, signed_by = chain.valset_at(SEED, N_VALS, valset, h - 1, PLAN)
        votes = block.last_commit.precommits
        assert [v.validator_address for v in votes] == [
            v.address for v in signed_by.validators]
        assert all(native.verify_one(val.pub_key.bytes_,
                                     v.sign_bytes(CHAIN_ID), v.signature)
                   for v, val in zip(votes, signed_by.validators))
    # the powers move after every even height: most heights open a set
    # with a hash of its own, over the SAME keys where no member changes
    assert hashes[0] == hashes[1] and len(set(hashes)) >= N_BLOCKS // 2
    assert built["signed"] == [N_VALS] * N_BLOCKS
    # `valsets` holds a set of MEMBERS once, whatever its powers do
    assert [h for h, _ in built["valsets"]] == (
        [1, 4, 7, 10, 13, 16, 19] if valset else [1])


def _by_set_order(h, valset):
    """Key indices of the set of h in the set's own (address) order."""
    seeds, _vs = chain.valset_at(SEED, N_VALS, valset, h, PLAN)
    index_of = {chain.val_seed(SEED, i): i
                for i in chain.valset_members(SEED, N_VALS, valset, h)}
    return [index_of[s] for s in seeds]


@pytest.mark.parametrize("name", sorted(VALSETS))
def test_the_blocks_carry_the_diffs_and_the_diffs_make_the_plans_sets(
        chains, name):
    """After the kvstore tx: a leave and a join where the `valset` plan
    says, then one `val:<pubkey>/<new power>` for each member that stays
    and whose power moves; applied as upstream's `EndBlock` diffs they
    make the set `valset_at` gives the next height (`_next_set`, which
    the builder itself is held to at every height)."""
    built, valset = chains[name], VALSETS[name]
    app = chain.RefKVStore()
    for h in range(1, N_BLOCKS + 1):
        txs = Block.decode_bytes(built["encoded"][h - 1]).txs
        assert txs[:1] == chain.block_txs(EMPTY, SEED, h)
        assert txs[1:] == chain.valset_txs(SEED, N_VALS, valset, h, PLAN)
        swaps = chain.valset_txs(SEED, N_VALS, valset, h)
        moves = chain.power_txs(SEED, N_VALS, valset, PLAN, h)
        assert txs[1:] == swaps + moves
        assert len(swaps) == (2 if valset and h % 3 == 0 else 0)
        assert len(moves) <= (2 if h % 2 == 0 else 0)
        old, new = _powers(h, valset), _powers(h + 1, valset)
        assert [chain.parse_val_tx(t) for t in moves] == [
            (chain.pub_of(chain.val_seed(SEED, i)), new[i])
            for i in sorted(new) if i in old and old[i] != new[i]]
        for tx in txs:
            app.deliver_tx(tx)
        _seeds, vs = chain.valset_at(SEED, N_VALS, valset, h, PLAN)
        if app.diffs:
            got = chain._next_set(vs, app.diffs, SEED, N_VALS, valset, h,
                                  PLAN)[1]
            assert got.hash() == chain.valset_at(
                SEED, N_VALS, valset, h + 1, PLAN)[1].hash() != vs.hash()
            # the diffs of another height do not make that set
            with pytest.raises(RuntimeError, match="do not make the set"):
                chain._next_set(vs, app.diffs[:-1] + [
                    (app.diffs[-1][0], app.diffs[-1][1] + 1)], SEED, N_VALS,
                    valset, h, PLAN)
        else:
            assert old == new
        app.commit()
    assert sum(bool(chain.power_txs(SEED, N_VALS, valset, PLAN, h))
               for h in range(1, N_BLOCKS + 1)) >= 7


# -- the program against the builder --------------------------------------------

def _genesis(built):
    return chain.genesis_doc(chain.genesis_dict(CHAIN_ID,
                                                built["valsets"][0][1]))


def test_the_genesis_keeps_its_one_power():
    gen = chain.genesis_dict(CHAIN_ID, chain.valset_at(
        SEED, N_VALS, None, 1, PLAN)[1])
    assert gen["power"] == chain.POWER and len(gen["validators"]) == N_VALS
    assert {v.power for v in chain.genesis_doc(gen).validators} == {
        chain.POWER}


@pytest.mark.parametrize("name", sorted(VALSETS))
def test_apply_block_follows_the_chain_to_the_builders_powers(chains, name):
    """`apply_block` with its check of each block's LastCommit on, over
    an app that returns the `val:` txs as `EndBlock` diffs: after every
    height the state's set is the one the builder signs the next with,
    power for power."""
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    built, valset = chains[name], VALSETS[name]
    state = get_state(MemDB(), _genesis(built))
    conns = ClientCreator(VALSET_APP).new_app_conns()
    old = cb._current
    cb.set_backend("native")
    try:
        for h, (block, ps, _seen) in enumerate(built["objects"], 1):
            execution.apply_block(state, None, conns.consensus, block,
                                  ps.header, execution.MockMempool())
            _seeds, want = chain.valset_at(SEED, N_VALS, valset, h + 1, PLAN)
            assert state.validators.hash() == want.hash()
            assert [(v.pub_key.bytes_, v.voting_power)
                    for v in state.validators.validators] == [
                (v.pub_key.bytes_, v.voting_power) for v in want.validators]
            assert state.app_hash == built["app_hash"][h - 1]
    finally:
        cb._current = old


@pytest.mark.parametrize("name", sorted(VALSETS))
def test_fast_sync_follows_the_chain_through_a_cut_at_every_change(chains,
                                                                   name):
    """The chain from the benchmark's own source store through the real
    pool, reactor, look-ahead and `apply_window` (8-block windows; the
    program as it stands cuts one at every header with another
    `validators_hash`, which is the program's path and not the
    benchmark's rule): the node stores the builder's blocks and ends on
    the builder's powers and app hash."""
    built, valset = chains[name], VALSETS[name]
    tip = N_BLOCKS - 1                # the last block's commit is not served
    bc = benchutil.fast_sync(built, CHAIN_ID, VALSET_APP, tip)
    for h in range(1, tip + 1):
        assert bc.store.load_block_meta(h).block_id.hash == \
            built["block_hash"][h - 1]
    assert bc.state.app_hash == built["app_hash"][tip - 1]
    _seeds, want = chain.valset_at(SEED, N_VALS, valset, tip + 1, PLAN)
    assert bc.state.validators.hash() == want.hash()
    assert bc.state.validators.hash() != built["valsets"][0][1].hash()
    assert {v.voting_power for v in bc.state.validators.validators} != {
        chain.POWER}


# -- the source child's index -----------------------------------------------------

@pytest.mark.parametrize("valset", [None, VALSET], ids=["one-set", "churn"])
def test_source_child_index_keeps_its_keys_and_one_entry_a_set_of_members(
        tmp_path, valset):
    spec = {"seed": SEED, "chain_id": CHAIN_ID, "n_vals": N_VALS,
            "n_blocks": N_BLOCKS, "n_sources": 1, "traffic": EMPTY,
            "powers": PLAN}
    if valset:
        spec["valset"] = valset
    ready, index = benchutil.child_index(tmp_path, spec)
    assert set(ready) == {"ready", "genesis", "addrs", "build_s", "n_blocks",
                          "bytes"}
    assert set(index) == {"block_hash", "app_hash", "size", "signed",
                          "valsets"}
    built = _build(valset=valset)
    assert index["block_hash"] == [b.hex() for b in built["block_hash"]]
    assert index["app_hash"] == [b.hex() for b in built["app_hash"]]
    assert index["signed"] == [N_VALS] * N_BLOCKS
    assert [s["from_height"] for s in index["valsets"]] == (
        [1, 4, 7, 10, 13, 16, 19] if valset else [1])
    for s in index["valsets"]:
        _seeds, vs = chain.valset_at(SEED, N_VALS, valset, s["from_height"],
                                     PLAN)
        assert s["hash"] == vs.hash().hex()
        assert s["validators"] == [v.pub_key.bytes_.hex()
                                   for v in vs.validators]
    assert ready["genesis"]["power"] == chain.POWER
    assert ready["genesis"]["validators"] == index["valsets"][0]["validators"]


# -- the check ------------------------------------------------------------------

def _answered(vs) -> list[dict]:
    """`/validators` as the program's route answers it."""
    return [{"address": v.address.hex(), "pub_key": v.pub_key.bytes_.hex(),
             "voting_power": v.voting_power, "accum": 0}
            for v in vs.validators]


def test_the_validators_term_compares_each_key_with_its_power():
    _seeds, vs = chain.valset_at(SEED, N_VALS, None, 9, PLAN)
    _seeds, other = chain.valset_at(SEED + 1, N_VALS, None, 9, PLAN)
    _seeds, equal = chain.valset_at(SEED, N_VALS, None, 9, None)
    assert {v.voting_power for v in vs.validators} != {chain.POWER}
    assert cell_mod.set_answers_differ(_answered(vs), vs, vs) == []
    # the same keys at genesis powers: a node that never applied a diff
    assert [v.pub_key for v in equal.validators] == [
        v.pub_key for v in vs.validators]
    assert cell_mod.set_answers_differ(_answered(equal), equal, vs) == [
        "/validators", "state validators hash"]
    assert cell_mod.set_answers_differ(_answered(vs), equal, vs) == [
        "state validators hash"]
    assert cell_mod.set_answers_differ(_answered(equal), vs, vs) == [
        "/validators"]
    # one power off by one, the keys alike: the parent's term, which
    # compared keys only, could not see it
    near = _answered(vs)
    near[3]["voting_power"] += 1
    assert [v["pub_key"] for v in near] == [v["pub_key"]
                                            for v in _answered(vs)]
    assert cell_mod.set_answers_differ(near, vs, vs) == ["/validators"]
    # another seed's plan: other keys, other powers
    assert cell_mod.set_answers_differ(_answered(vs), vs, other) == [
        "/validators", "state validators hash"]
    # and without a plan the two terms are what they were
    _seeds, plain = chain.valset_at(SEED, N_VALS, None, 9)
    assert cell_mod.set_answers_differ(_answered(plain), plain, equal) == []


def test_the_runs_log_says_how_many_heights_changed_a_power():
    heights = list(range(3, 67))
    moved = sum(_powers(h) != _powers(h + 1) for h in heights)
    assert 20 <= moved <= 32          # every even height, but a same draw
    assert cell_mod.powers_report(SEED, N_VALS, None, PLAN, heights) == (
        f"powers: {moved} of 64 heights of the interval change a voting "
        f"power (plan {PLAN})")
    assert cell_mod.powers_report(SEED, N_VALS, None, None, heights) == (
        "powers: 0 of 64 heights of the interval change a voting power "
        "(plan None)")
    # a member that leaves or joins moves no power: the count is of
    # `power_txs`, not of every diff
    churn = cell_mod.powers_report(SEED, N_VALS, VALSET, None, heights)
    assert churn.startswith("powers: 0 of 64 ")


def test_the_rehearsal_mix_is_the_plan_the_issue_states():
    assert MIX["powers"] == {"change_every_blocks": 1, "members": 1,
                             "min": 1, "max": 30}
    assert MIX["block"] == EMPTY and "valset" not in MIX
    assert "absent" not in MIX
    chain.powers_at(SEED, 4, None, MIX["powers"], 1)
    seen = {chain.powers_at(SEED, 4, None, MIX["powers"], h)
            for h in range(1, 200)}
    assert len(seen) > 150


def test_run_cell_hands_the_plan_to_the_source_child(monkeypatch):
    """`run_cell` reads `powers` of the traffic file beside `valset` and
    `absent`: the source child's spec carries it (and carries none where
    the mix states none)."""
    specs = []

    class Kids:
        def __init__(self, root):
            pass

        def start(self, module, *args):
            if args:
                with open(args[0]) as f:
                    specs.append(json.load(f))
            raise KeyboardInterrupt     # far enough

        def stop_all(self):
            pass

    monkeypatch.setattr(cell_mod.children_mod, "Children", Kids)
    monkeypatch.setattr(cell_mod, "start_watchdog",
                        lambda kids: types.SimpleNamespace(cancel=lambda: 0))
    # a mix with the plan runs on an app that returns its `val:` txs as
    # diffs, one without on the default (`cell.app_fits_plans`, PR 49)
    for traffic, app in (({"powers": PLAN}, {"app": "valset_kvstore"}),
                         ({}, {})):
        cell = {"config": dict(app, validators=N_VALS, source_peers=1),
                "config_name": "c", "traffic_name": "t",
                "traffic": dict(traffic, block=EMPTY, chain={
                    "default": {"parent_blocks_per_s": 10, "warmup_s": 1}})}
        with pytest.raises(KeyboardInterrupt):
            cell_mod.run_cell(REPO, cell, SEED, 1.0, False, time.monotonic())
    assert specs[0]["powers"] == PLAN and "powers" not in specs[1]
    assert "valset" not in specs[0] and "absent" not in specs[0]
