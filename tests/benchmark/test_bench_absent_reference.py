"""The program against the plain reference of upstream's `VerifyCommit`
(`refcommit.py`) on chains the benchmark's builder makes FROM THE TRAFFIC
FILE'S OWN PLAN (`absent-precommits.json`: two members down, 20 precommits
in 1,000 late) at 10 and 16 validators over 130 commits, two 64-block
windows and a cut one: `verify_commits_batched` window by window and
`ValidatorSet.verify_commit` commit by commit accept what the reference
accepts and refuse what it refuses, at the same height, by the same class
of error and, for a signature, at the same member.  And the commits a
node stores and serves after a fast-sync of such a chain hold the nil
entries where the chain does."""

import json
import os
from types import SimpleNamespace

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey

import benchutil
from benchutil import REPO
from benchmark.lib import chain
from refcommit import members_of, ref_verify_commit, ref_verify_window
from tendermint_tpu.types import Block, Commit, Vote
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.validator import (CommitFormatError,
                                            CommitPowerError,
                                            CommitSignatureError,
                                            verify_commits_batched)
from tendermint_tpu.utils.metrics import REGISTRY

with open(os.path.join(REPO, "benchmark", "traffic",
                       "absent-precommits.json")) as _f:
    MIX = json.load(_f)
PLAN, BLOCK = MIX["absent"], MIX["block"]
CHAIN_ID, SEED = "bench-absent-ref", 2**31 + 44
N_COMMITS = 130                    # 64 + 64 + 2: the last window is cut
WINDOW = 64
SIZES = (10, 16)


def _build(n_vals: int, n_blocks: int = N_COMMITS + 1):
    seeds, vs = chain.valset_at(SEED, n_vals, None, 1)
    with chain.Signers(seeds, 0) as sg:
        built = chain.build_chain(CHAIN_ID, seeds, vs, n_blocks, BLOCK, SEED,
                                  sg, keep_objects=True, absent=PLAN)
    return SimpleNamespace(built=built, vs=vs, seeds=seeds, n=n_vals,
                           members=members_of(vs))


@pytest.fixture(scope="module")
def chains():
    return {n: _build(n) for n in SIZES}


@pytest.fixture(autouse=True)
def native_backend():
    from tendermint_tpu.crypto import backend as cb
    old = cb._current
    cb.set_backend("native")
    yield
    cb._current = old


def served_items(c) -> list[tuple]:
    """[(block id, height, the commit of the height as a node decodes it
    from the successor's bytes)] for heights 1 .. N_COMMITS."""
    built = c.built
    items = []
    for h in range(1, N_COMMITS + 1):
        block, ps, _seen = built["objects"][h - 1]
        commit = Block.decode_bytes(built["encoded"][h]).last_commit
        items.append((block.block_id(ps), h, commit))
    return items


def windows(items: list[tuple]) -> list[list[tuple]]:
    return [items[i:i + WINDOW] for i in range(0, len(items), WINDOW)]


def _present(commit) -> list[int]:
    return [i for i, v in enumerate(commit.precommits) if v is not None]


def system_verdict(call, items: list[tuple]):
    """What the program's call says, in the reference's words; a
    signature error's lane (a position among the precommits that are
    there) turned into the member it belongs to."""
    try:
        call()
    except CommitSignatureError as e:
        commit = next(c for _b, h, c in items if h == e.height)
        return ("signature", e.height, _present(commit)[e.lane])
    except CommitPowerError as e:
        return ("power", e.height)
    except CommitFormatError as e:
        return ("format", e.height)
    return None


def single_verdict(c, item):
    """`ValidatorSet.verify_commit` on one commit; its structural
    refusals are bare ValueErrors that name no height."""
    bid, h, commit = item
    try:
        c.vs.verify_commit(CHAIN_ID, bid, h, commit)
    except CommitSignatureError as e:
        return ("signature", e.height, _present(commit)[e.lane])
    except CommitPowerError as e:
        return ("power", e.height)
    except ValueError:
        return ("format", h)
    return None


def both(c, items: list[tuple], at: int):
    """(the reference's, the program's) verdicts on the window `items`
    and on the one commit at index `at` of it; each pair has to agree."""
    ref_w = ref_verify_window(CHAIN_ID, c.members, items)
    sys_w = system_verdict(
        lambda: verify_commits_batched(c.vs, CHAIN_ID, items), items)
    bid, h, commit = items[at]
    ref_1 = ref_verify_commit(CHAIN_ID, c.members, bid, h, commit)
    sys_1 = single_verdict(c, items[at])
    assert ref_w == sys_w and ref_1 == sys_1, (ref_w, sys_w, ref_1, sys_1)
    assert ref_w == ref_1
    return ref_w


def edited(commit, edit) -> Commit:
    """The commit with `edit(votes)` applied to a copy of its votes, as a
    peer would send it: encoded and decoded again."""
    votes = list(commit.precommits)
    edit(votes)
    out = Commit.decode(Reader(Commit(block_id=commit.block_id,
                                      precommits=votes).encode()))
    return out


def with_commit(items, at: int, commit) -> list[tuple]:
    bid, h, _c = items[at]
    return items[:at] + [(bid, h, commit)] + items[at + 1:]


def signed_by(c, position: int, vote: Vote) -> Vote:
    """`vote` as the member at `position` of the set would have signed
    it (OpenSSL, the builder's key for that member)."""
    val = c.vs.validators[position]
    out = Vote(**{**vote.__dict__, "validator_index": position,
                  "validator_address": val.address, "signature": b""})
    key = Ed25519PrivateKey.from_private_bytes(c.seeds[position])
    return Vote(**{**out.__dict__,
                   "signature": key.sign(out.sign_bytes(CHAIN_ID))})


# -- sound commits -------------------------------------------------------------

@pytest.mark.parametrize("n_vals", SIZES)
def test_the_chain_is_the_plans_and_every_commit_holds_nil_entries(chains,
                                                                   n_vals):
    c = chains[n_vals]
    most = -(-n_vals // 3) - 1
    for bid, h, commit in served_items(c):
        silent = chain.absent_at(SEED, n_vals, None, PLAN, h)
        assert 2 <= len(silent) <= most        # the two that are down
        assert commit.num_sigs() == c.built["signed"][h - 1] == \
            n_vals - len(silent)
        assert not commit.wire_backed() and commit.block_id == bid
        keys = [chain.pub_of(chain.val_seed(SEED, i)) for i in silent]
        assert {i for i, v in enumerate(commit.precommits) if v is None} == \
            {i for i, m in enumerate(c.members) if m[1] in keys}


@pytest.mark.parametrize("n_vals", SIZES)
def test_sound_commits_are_accepted_by_both_by_window_and_by_commit(chains,
                                                                    n_vals):
    c = chains[n_vals]
    items = served_items(c)
    wins = windows(items)
    assert [len(w) for w in wins] == [64, 64, 2]
    sigs0 = REGISTRY.sigs_verified.value
    for w in wins:
        assert ref_verify_window(CHAIN_ID, c.members, w) is None
        assert system_verdict(
            lambda: verify_commits_batched(c.vs, CHAIN_ID, w), w) is None
    held = sum(c.built["signed"][:N_COMMITS])
    # a nil entry is not verified: the lanes are the precommits held
    assert REGISTRY.sigs_verified.value - sigs0 == held < N_COMMITS * n_vals
    for item in items:
        bid, h, commit = item
        assert ref_verify_commit(CHAIN_ID, c.members, bid, h, commit) is None
        assert single_verdict(c, item) is None
    assert REGISTRY.sigs_verified.value - sigs0 == 2 * held


# -- tampered commits ------------------------------------------------------------

def _forge(c, commit):
    k = _present(commit)[1]

    def edit(votes):
        sig = bytearray(votes[k].signature)
        sig[5] ^= 0x40
        votes[k] = Vote(**{**votes[k].__dict__, "signature": bytes(sig)})
    return edited(commit, edit), ("signature", k)


def _drop_to_two_thirds(c, commit):
    """Signed entries turned nil until no more than 2/3 of the power is
    left: the most that +2/3 refuses."""
    keep = (2 * c.n) // 3                    # 3 x keep <= 2 x n

    def edit(votes):
        for i in _present(commit)[keep:]:
            votes[i] = None
    return edited(commit, edit), ("power", None)


def _fill_verbatim(c, commit):
    """A nil entry filled with another member's vote as it stands."""
    hole = next(i for i, v in enumerate(commit.precommits) if v is None)
    donor = _present(commit)[0]

    def edit(votes):
        votes[hole] = votes[donor]
    return edited(commit, edit), ("format", None)


def _fill_readdressed(c, commit):
    """A nil entry filled with another member's signature under the
    silent member's own index and address: it verifies under no key of
    that position."""
    hole = next(i for i, v in enumerate(commit.precommits) if v is None)
    donor = _present(commit)[0]

    def edit(votes):
        votes[hole] = Vote(**{**votes[donor].__dict__,
                              "validator_index": hole,
                              "validator_address":
                                  c.vs.validators[hole].address})
    return edited(commit, edit), ("signature", hole)


def _another_round(c, commit):
    k = _present(commit)[2]

    def edit(votes):
        votes[k] = Vote(**{**votes[k].__dict__, "round": 3})
    return edited(commit, edit), ("format", None)


TAMPERS = {"forged-signature": _forge,
           "two-thirds-and-no-more": _drop_to_two_thirds,
           "nil-filled-verbatim": _fill_verbatim,
           "nil-filled-readdressed": _fill_readdressed,
           "another-round": _another_round}


@pytest.mark.parametrize("at", [(0, 17), (1, 63), (2, 1)],
                         ids=["first-window", "second-window", "cut-window"])
@pytest.mark.parametrize("tamper", sorted(TAMPERS))
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_tampered_commit_is_refused_by_both_alike(chains, n_vals, tamper,
                                                    at):
    c = chains[n_vals]
    win, k = at
    items = windows(served_items(c))[win]
    bid, h, commit = items[k]
    assert None in commit.precommits           # it has nil entries
    bad, (kind, member) = TAMPERS[tamper](c, commit)
    verdict = both(c, with_commit(items, k, bad), k)
    assert verdict == ((kind, h, member) if kind == "signature"
                       else (kind, h))
    if tamper == "two-thirds-and-no-more":
        assert 3 * bad.num_sigs() <= 2 * n_vals < 3 * (bad.num_sigs() + 1)


def test_exactly_two_thirds_of_the_power_is_not_more_than_two_thirds():
    """12 members of equal power: 8 signed is exactly 2/3, refused by
    both; 9 is accepted by both."""
    c = _build(12, 4)
    block, ps, _seen = c.built["objects"][1]
    commit = Block.decode_bytes(c.built["encoded"][2]).last_commit
    item = [(block.block_id(ps), 2, commit)]
    assert commit.num_sigs() >= 9
    for keep, want in ((9, None), (8, ("power", 2))):
        def edit(votes):
            for i in _present(commit)[keep:]:
                votes[i] = None
        bad = edited(commit, edit)
        assert bad.num_sigs() == keep
        assert both(c, with_commit(item, 0, bad), 0) == want


@pytest.mark.parametrize("forged", [False, True], ids=["sound", "forged"])
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_wire_commit_and_its_object_form_side_by_side_in_one_window(
        chains, n_vals, forged):
    """The commit of one height with its nil entries signed after all (a
    commit every member reached in time), once as `Commit.decode` leaves
    it, in its wire bytes, and once holding votes, between the chain's
    own commits: the window takes the per-block builder, the wire commit
    its columns whole, and both agree with the reference; with one
    signature of the WIRE commit forged, both refuse it at that member."""
    c = chains[n_vals]
    items = windows(served_items(c))[0]
    k = 30
    bid, h, commit = items[k]
    some = commit.precommits[_present(commit)[0]]

    def fill(votes):
        for i, v in enumerate(votes):
            if v is None:
                votes[i] = signed_by(c, i, some)
        if forged:
            sig = bytearray(votes[4].signature)
            sig[0] ^= 1
            votes[4] = Vote(**{**votes[4].__dict__, "signature": bytes(sig)})
    wire = edited(commit, fill)
    assert wire.wire_backed() and wire.num_sigs() == n_vals
    objects = Commit(block_id=wire.block_id,
                     precommits=list(wire.precommits))
    assert not objects.wire_backed() and objects == wire
    window = items[:k] + [(bid, h, wire), (bid, h, objects)] + items[k + 1:]
    want = ("signature", h, 4) if forged else None
    assert ref_verify_window(CHAIN_ID, c.members, window) == want
    assert system_verdict(
        lambda: verify_commits_batched(c.vs, CHAIN_ID, window),
        window) == want
    for item in window[k:k + 2]:
        assert ref_verify_commit(CHAIN_ID, c.members, *item) == want
        assert single_verdict(c, item) == want


# -- what the node stores and serves -----------------------------------------------

def test_the_stored_and_served_commits_keep_their_nil_entries(chains):
    """A CPU fast-sync of the 16-validator chain through the real pool,
    reactor and `apply_window`: at every height the seen commit and the
    successor's LastCommit the store loads back hold a nil entry exactly
    where the chain does, and `/block` and `/commit` answer the count."""
    from tendermint_tpu.rpc.routes import Routes
    c = chains[16]
    built, tip = c.built, N_COMMITS
    bc = benchutil.fast_sync(built, CHAIN_ID, "kvstore", tip)
    routes = Routes(SimpleNamespace(
        block_store=bc.store,
        config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False)))).table
    for h in range(1, tip + 1):
        seen = built["objects"][h - 1][2]
        holds = [v is not None for v in seen.precommits]
        assert holds.count(False) >= 2
        stored = [bc.store.load_seen_commit(h)]
        if h < tip:
            stored.append(bc.store.load_block_commit(h))
            assert bc.store.load_block(h + 1).last_commit.bit_array() == holds
        for got in stored:
            assert got.bit_array() == holds and got == seen
            assert got.encode() == seen.encode()
        assert routes["commit"]({"height": h})["precommits"] == \
            holds.count(True) == built["signed"][h - 1]
        if h > 1:
            assert routes["block"]({"height": h})["block"]["last_commit"][
                "precommits"] == built["signed"][h - 2]
