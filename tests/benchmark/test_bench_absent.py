"""A chain whose commits miss precommits (`absent` of a traffic mix): who
is silent is one seeded function of the plan, every commit the builder
serves still holds more than 2/3 of its set's power, the program follows
such a chain to the builder's hashes, the index says what was signed and
the check sums that; and what the builder serves without the plan is byte
for byte the parent's."""

import hashlib
import time

import pytest
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PublicKey

import benchutil
from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain
from tendermint_tpu.types import Block
from tendermint_tpu.utils.metrics import REGISTRY

EMPTY = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
# the chains of these tests: 10 validators (at most 3 may be silent)
N_VALS, N_BLOCKS, SEED = 10, 18, 2**31 + 43
VALSET = {"change_every_blocks": 3, "swap": 2}
PLANS = {
    # every commit irregular, the down member another every 5 heights
    "down-and-late": {"late_per_1000": 150, "down": 1, "down_for_blocks": 5},
    # about half of the commits full: windows of both kinds of commit
    "late-only": {"late_per_1000": 60},
}
# sha256 over the joined `encoded`, `block_hash` and `app_hash` lists of
# build_chain("bench-pin-43", ..., EMPTY, seed SEED) with no `absent` plan
# as the PARENT tree gives them (computed on `git archive 16fec95`):
# (validators, blocks, valset plan)
PARENT = {
    "plain": (7, 12, None, (
        "88b3b08a90d147617a7c3b9e4f9ebb02a8ab6b2a9e77595a16911c55693398bc",
        "8d1015c480d012ac53e3ef480de375dd6b51337ce9975cec9d142ee9eae49018",
        "36ee75edd04bf05401b16f084061998d1e3eb92f542451606f503e1be88d6ea1")),
    "valset": (7, 14, {"change_every_blocks": 3, "swap": 1}, (
        "ec3f701c972748c4672940ea21d3b1afb947dc3f035a160b44f1dc7469fb709d",
        "06bdf3e61b14ed8daa2184a3e7152d233db3735858c8fdfcb3d8adce87dbc96a",
        "350bd17dc1e4f14f2bb94ae9c1c40a99d8614328aa9da2daadf9bb60b5834a9b")),
}


def _digest(parts) -> str:
    return hashlib.sha256(b"".join(parts)).hexdigest()


# -- who is silent ----------------------------------------------------------

def test_absent_at_is_seeded_sorted_and_a_subset_of_the_heights_members():
    plan = {"late_per_1000": 20, "down": 2, "down_for_blocks": 50}
    seen = [chain.absent_at(SEED, 100, None, plan, h) for h in range(1, 301)]
    assert seen == [chain.absent_at(SEED, 100, None, plan, h)
                    for h in range(1, 301)]                  # repeatable
    assert seen != [chain.absent_at(SEED + 1, 100, None, plan, h)
                    for h in range(1, 301)]
    for silent in seen:
        assert list(silent) == sorted(set(silent))
        assert set(silent) <= set(range(100)) and 2 <= len(silent) <= 33
    assert len({len(s) for s in seen}) > 1      # the late ones come and go
    # no plan, an empty plan, a plan of noughts: nobody
    for none in (None, {}, {"late_per_1000": 0, "down": 0}):
        assert chain.absent_at(SEED, 100, None, none, 7) == ()


def test_down_members_are_down_for_the_whole_epoch_and_others_the_next():
    plan = {"down": 3, "down_for_blocks": 40}
    epochs = [{chain.absent_at(SEED, 100, None, plan, h)
               for h in range(e * 40 + 1, e * 40 + 41)} for e in range(6)]
    assert all(len(e) == 1 and len(next(iter(e))) == 3 for e in epochs)
    assert len(set.union(*epochs)) > 1
    # with late ones beside them the down members are silent all the same
    both = dict(plan, late_per_1000=30)
    for h in (1, 40, 41, 200):
        assert set(chain.absent_at(SEED, 100, None, plan, h)) <= set(
            chain.absent_at(SEED, 100, None, both, h))


def test_late_draws_come_at_about_l_per_thousand():
    """100 members x 100 heights = 10,000 independent draws at 20 in a
    thousand: 200 expected, 14 the standard deviation."""
    plan = {"late_per_1000": 20}
    late = [len(chain.absent_at(SEED, 100, None, plan, h))
            for h in range(1, 101)]
    assert 150 <= sum(late) <= 250, sum(late)
    assert 0 in late or min(late) < max(late)


@pytest.mark.parametrize("n,most", [(4, 1), (6, 1), (7, 2), (10, 3),
                                    (100, 33), (300, 99)])
def test_never_a_third_of_the_power_or_more_is_silent(n, most):
    """Everybody late: the list is cut at the largest count that leaves
    MORE than 2/3 of the power signed, the down members first."""
    plan = {"late_per_1000": 1000, "down": 1, "down_for_blocks": 9}
    for h in (1, 9, 10, 55):
        silent = chain.absent_at(SEED, n, None, plan, h)
        assert len(silent) == most
        assert 3 * (n - len(silent)) * chain.POWER > 2 * n * chain.POWER
        assert not 3 * (n - most - 1) > 2 * n
        assert set(chain.absent_at(SEED, n, None, {
            "down": 1, "down_for_blocks": 9}, h)) <= set(silent)


def test_absent_at_composes_with_a_valset_plan_across_its_changes():
    """Members are named by key index: a key that left is never silent
    afterwards, a key that joined can be, and the down members of an
    epoch are taken from the set of the height."""
    plan = {"late_per_1000": 200, "down": 2, "down_for_blocks": 7}
    silent_keys = set()
    for h in range(1, 61):
        members = chain.valset_members(SEED, N_VALS, VALSET, h)
        silent = chain.absent_at(SEED, N_VALS, VALSET, plan, h)
        assert set(silent) <= set(members) and 2 <= len(silent) <= 3
        silent_keys |= set(silent)
    assert max(silent_keys) >= N_VALS          # a key that joined later
    assert chain.absent_at(SEED, N_VALS, VALSET, plan, 60) != \
        chain.absent_at(SEED, N_VALS, None, plan, 60)


@pytest.mark.parametrize("plan", [
    {"late_per_1000": -1}, {"late_per_1000": 1001}, {"late_per_1000": 2.5},
    {"down": -1, "down_for_blocks": 5}, {"down": 1, "down_for_blocks": 0},
    {"down": 1}, {"down": True, "down_for_blocks": 5},
    {"down": 4, "down_for_blocks": 5}, {"late_per_thousand": 20}],
    ids=["late-negative", "late-over-1000", "late-not-whole",
         "down-negative", "epoch-under-1", "down-without-an-epoch",
         "down-not-a-number", "down-leaves-no-two-thirds", "unknown-key"])
def test_a_plan_that_cannot_be_run_is_refused(plan, monkeypatch):
    with pytest.raises(ValueError, match="absent plan"):
        chain.absent_at(SEED, N_VALS, None, plan, 1)
    seeds, vs = chain.valset_at(SEED, N_VALS, None, 1)
    with pytest.raises(ValueError, match="absent plan"):
        chain.build_chain("bench-absent", seeds, vs, 2, EMPTY, SEED,
                          absent=plan)
    # and a cell whose traffic file holds it fails before a child is
    # started or anything is booted
    monkeypatch.setattr(cell_mod.children_mod, "Children", lambda root: 1 / 0)
    cell = {"config": {"validators": N_VALS, "source_peers": 1},
            "traffic": {"block": EMPTY, "absent": plan}}
    with pytest.raises(ValueError, match="absent plan"):
        cell_mod.run_cell(REPO, cell, SEED, 1.0, False, time.monotonic())


# -- the chain the builder serves ------------------------------------------

@pytest.mark.parametrize("workers", [0, 2, None],
                         ids=["in-process", "workers", "as-the-child"])
@pytest.mark.parametrize("mix", sorted(PARENT))
def test_without_an_absent_plan_the_chain_is_the_parents_byte_for_byte(
        mix, workers):
    n_vals, n_blocks, valset, want = PARENT[mix]
    seeds, vs = chain.valset_at(SEED, n_vals, valset, 1)
    with chain.Signers(seeds, workers) as sg:
        built = chain.build_chain("bench-pin-43", seeds, vs, n_blocks, EMPTY,
                                  SEED, sg, valset=valset, absent=None)
    assert tuple(_digest(built[k]) for k in
                 ("encoded", "block_hash", "app_hash")) == want
    assert built["signed"] == [n_vals] * n_blocks


def _build(plan, valset=None, workers=0, keep_objects=False, seed=SEED):
    seeds, vs = chain.valset_at(seed, N_VALS, valset, 1)
    with chain.Signers(seeds, workers) as sg:
        return chain.build_chain("bench-absent", seeds, vs, N_BLOCKS, EMPTY,
                                 seed, sg, keep_objects=keep_objects,
                                 valset=valset, absent=plan)


@pytest.fixture(scope="module")
def chains():
    return {name: _build(plan, keep_objects=True)
            for name, plan in PLANS.items()}


@pytest.mark.parametrize("valset", [None, VALSET], ids=["one-set", "churn"])
@pytest.mark.parametrize("name", sorted(PLANS))
def test_absent_chain_height_by_height_against_openssl(name, valset):
    """Every commit: None exactly where `absent_at` says, each vote that
    is there verifies under ITS member's key by OpenSSL, `signed` is
    their count and they hold more than 2/3 of the set's power."""
    plan = PLANS[name]
    built = _build(plan, valset, workers=2 if valset else 0)
    assert built["encoded"] == _build(plan, valset)["encoded"]
    assert built["encoded"] != _build(None, valset)["encoded"]
    blocks = [Block.decode_bytes(e) for e in built["encoded"]]
    assert [b.encode() for b in blocks] == built["encoded"]   # round trip
    silent_heights = 0
    for h in range(1, N_BLOCKS):
        block = blocks[h]                 # carries the commit of height h
        block.validate_basic()
        members = chain.valset_members(SEED, N_VALS, valset, h)
        _seeds, vs = chain.valset_at(SEED, N_VALS, valset, h)
        key_of = {chain.pub_of(chain.val_seed(SEED, i)): i for i in members}
        silent = set(chain.absent_at(SEED, N_VALS, valset, plan, h))
        votes = block.last_commit.precommits
        assert len(votes) == N_VALS == vs.size()
        power = 0
        for vote, val in zip(votes, vs.validators):
            if key_of[val.pub_key.bytes_] in silent:
                assert vote is None
                continue
            assert vote.validator_address == val.address
            Ed25519PublicKey.from_public_bytes(val.pub_key.bytes_).verify(
                vote.signature, vote.sign_bytes("bench-absent"))
            power += val.voting_power
        assert built["signed"][h - 1] == N_VALS - len(silent) == \
            block.last_commit.num_sigs()
        assert 3 * power > 2 * vs.total_voting_power()
        silent_heights += bool(silent)
    assert silent_heights == N_BLOCKS - 1 if "down" in plan else \
        0 < silent_heights < N_BLOCKS - 1
    # a vote moved to another position does not verify: the check above
    # holds each signature to its own member
    a, b = [v for v in blocks[2].last_commit.precommits if v][:2]
    with pytest.raises(InvalidSignature):
        Ed25519PublicKey.from_public_bytes(
            [v.pub_key.bytes_ for v in chain.valset_at(
                SEED, N_VALS, valset, 2)[1].validators][b.validator_index]
        ).verify(a.signature, a.sign_bytes("bench-absent"))


def test_a_commit_decodes_to_what_was_signed_and_back_to_its_bytes(chains):
    """Every served commit of a chain on which about half are full: it
    holds a precommit exactly where one was signed, encodes back to the
    bytes it was served in, and its decode is counted once, by one of
    the program's two counters.  A commit with EVERY precommit stays in
    its wire bytes (PR 34); which of the two forms one with a nil entry
    takes is the program's to choose, and is not held here."""
    built = chains["late-only"]
    for h in range(2, N_BLOCKS + 1):
        wire0 = REGISTRY.commits_decoded_wire.value
        objects0 = REGISTRY.commits_decoded_objects.value
        served = built["encoded"][h - 1]
        commit = Block.decode_bytes(served).last_commit
        full = built["signed"][h - 2] == N_VALS
        counted = (REGISTRY.commits_decoded_wire.value - wire0,
                   REGISTRY.commits_decoded_objects.value - objects0)
        assert counted in ((1, 0), (0, 1))
        if full:
            assert counted == (1, 0) and commit.wire_backed()
        assert commit.size() == N_VALS
        assert commit.bit_array().count(True) == built["signed"][h - 2]
        assert served.endswith(commit.encode())


# -- the program against the builder ---------------------------------------

def _genesis(built):
    return chain.genesis_doc(chain.genesis_dict("bench-absent",
                                                built["valsets"][0][1]))


@pytest.mark.parametrize("name", sorted(PLANS))
def test_apply_block_checks_the_absent_commits_and_follows_the_chain(
        chains, name):
    """`apply_block` with its check of each block's LastCommit on
    (`verify_commit`, commit by commit): the program takes every
    commit the builder made and reaches its app hashes."""
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    built = chains[name]
    state = get_state(MemDB(), _genesis(built))
    conns = ClientCreator("kvstore").new_app_conns()
    old = cb._current
    cb.set_backend("native")
    sigs0 = REGISTRY.sigs_verified.value
    try:
        for h, (block, ps, _seen) in enumerate(built["objects"], 1):
            execution.apply_block(state, None, conns.consensus, block,
                                  ps.header, execution.MockMempool())
            assert state.app_hash == built["app_hash"][h - 1]
    finally:
        cb._current = old
    # block h + 1 carries the commit of h: all but the last were checked
    assert REGISTRY.sigs_verified.value - sigs0 == sum(built["signed"][:-1])


@pytest.mark.parametrize("valset", [None, VALSET], ids=["one-set", "churn"])
@pytest.mark.parametrize("name", sorted(PLANS))
def test_fast_sync_follows_the_absent_chain_to_the_builders_hashes(name,
                                                                   valset):
    """The absent chain from the benchmark's own source store through the
    real pool, reactor, look-ahead and `apply_window` over 8-block
    windows: the node stores the builder's blocks and ends on its app
    hash, having decoded every served commit and verified what the
    chain holds and no more lanes than that a commit."""
    built = _build(PLANS[name], valset)
    decoded0 = (REGISTRY.commits_decoded_objects.value
                + REGISTRY.commits_decoded_wire.value)
    sigs0 = REGISTRY.sigs_verified.value
    tip = N_BLOCKS - 1                # the last block's commit is not served
    bc = benchutil.fast_sync(built, "bench-absent",
                             "valset_kvstore" if valset else "kvstore", tip)
    for h in range(1, tip + 1):
        assert bc.store.load_block_meta(h).block_id.hash == \
            built["block_hash"][h - 1]
        assert bc.store.load_seen_commit(h).num_sigs() == \
            built["signed"][h - 1]
    assert bc.state.app_hash == built["app_hash"][tip - 1]
    assert bc.state.validators.hash() == [
        s for f, s in built["valsets"] if f <= tip + 1][-1].hash()
    # the commit of every height was decoded, by one of the program's
    # two decoders (which one a commit with a nil entry takes is not
    # held: the stored `num_sigs` above say what it read)
    assert (REGISTRY.commits_decoded_objects.value
            + REGISTRY.commits_decoded_wire.value) - decoded0 >= tip
    # the check's floor, as `cell.precommit_limits` computes it, holds of
    # a sound node; a dropped look-ahead verifies some commits twice
    held, _at_tip = cell_mod.precommit_limits(
        {"signed": built["signed"]}, tip, tip)
    assert held == sum(built["signed"][:tip]) < tip * N_VALS
    assert REGISTRY.sigs_verified.value - sigs0 >= held


# -- the source child's index ----------------------------------------------

@pytest.mark.parametrize("name", ["no-plan", "down-and-late"])
def test_source_child_index_gains_signed_and_keeps_its_keys(tmp_path, name):
    plan = PLANS.get(name)
    spec = {"seed": SEED, "chain_id": "bench-absent", "n_vals": N_VALS,
            "n_blocks": N_BLOCKS, "n_sources": 1, "traffic": EMPTY,
            "valset": VALSET}
    if plan:
        spec["absent"] = plan
    ready, index = benchutil.child_index(tmp_path, spec)
    assert set(ready) == {"ready", "genesis", "addrs", "build_s", "n_blocks",
                          "bytes"}
    assert set(index) == {"block_hash", "app_hash", "size", "signed",
                          "valsets"}
    built = _build(plan, VALSET)
    assert index["block_hash"] == [b.hex() for b in built["block_hash"]]
    assert index["app_hash"] == [b.hex() for b in built["app_hash"]]
    assert index["size"] == [len(e) for e in built["encoded"]]
    assert index["signed"] == built["signed"] == [
        N_VALS - len(chain.absent_at(SEED, N_VALS, VALSET, plan, h))
        for h in range(1, N_BLOCKS + 1)]
    assert (min(index["signed"]) < N_VALS) == bool(plan)
    assert [s["from_height"] for s in index["valsets"]] == [
        1, 4, 7, 10, 13, 16, 19]


# -- the two checks that read `signed` --------------------------------------

def _compare(index, verified: int, synced: int, tip: int, answered: int):
    """The two comparisons of `run_cell` that read the index's `signed`,
    as it makes them: (Checks, the `/block precommits` term differs)."""
    held_synced, held_at_tip = cell_mod.precommit_limits(index, synced, tip)
    checks = cell_mod.Checks()
    checks.at_least("sigs_verified", "sigs_verified moved by", verified,
                    held_synced)
    return checks, answered != held_at_tip


def test_a_full_commit_chains_limits_are_what_they_were():
    """No plan: every `signed` is `validators`, so the floor is heights
    x validators and `/block`'s precommits are `validators`, none in the
    first block: the numbers the five accepted cells were held to."""
    index = {"signed": [100] * 500}
    assert cell_mod.precommit_limits(index, 320, 257) == (320 * 100, 100)
    assert cell_mod.precommit_limits(index, 0, 1) == (0, 0)
    assert cell_mod.precommit_limits(index, 1, 2) == (100, 100)
    checks, differs = _compare(index, 32000, 320, 257, 100)
    assert checks.ok and not differs
    assert checks.compared["sigs_verified"] == {
        "value": 32000, "at_least": 32000, "ok": True}


def test_a_node_one_signature_short_of_the_chains_count_is_not_correct(
        chains):
    """The floor is the chain's own count, height by height: commits 1 ..
    K for K heights synced, and the block at the tip carries the commit
    of the height before it."""
    signed = chains["late-only"]["signed"]
    assert len(set(signed)) > 1
    index = {"signed": signed}
    for synced in (1, 5, 17):
        held = sum(signed[:synced])
        assert cell_mod.precommit_limits(index, synced, synced)[0] == held
        assert _compare(index, held, synced, 2, signed[0])[0].ok
        short, _ = _compare(index, held - 1, synced, 2, signed[0])
        assert not short.ok and short.compared["sigs_verified"] == {
            "value": held - 1, "at_least": held, "ok": False}
    for tip in range(2, N_BLOCKS + 1):
        assert cell_mod.precommit_limits(index, 1, tip)[1] == signed[tip - 2]
    # an index that says every commit was full fails a sound node on such
    # a chain by both, as the parent's limits would
    full = {"signed": [N_VALS] * len(signed)}
    tip = next(t for t in range(2, N_BLOCKS) if signed[t - 2] < N_VALS)
    checks, differs = _compare(full, sum(signed[:17]), 17, tip,
                               signed[tip - 2])
    assert not checks.ok and differs


def test_the_runs_log_says_what_traffic_the_interval_was():
    signed = [100] * 64 + [99] + [100] * 63 + [100] * 64
    line = cell_mod.absent_report({"signed": [0] * 10 + signed},
                                  list(range(11, 203)), 100)
    assert line == ("precommits: 19199 of 19200 commit lanes of the interval "
                    "signed (99.995 %); 1 of 192 commits and 1 of 3 64-block "
                    "windows hold an absent precommit")
    assert "(100.000 %); 0 of 64 commits and 0 of 1 64-block" in \
        cell_mod.absent_report({"signed": [4] * 64}, list(range(1, 65)), 4)
