"""The tally of a commit against the voting powers of ITS OWN height: the
program against the plain reference of upstream's `VerifyCommit`
(`refcommit.py`, which tallies `(address, key, power)` members) on a chain
the benchmark's builder makes under a `powers` plan, six validators, two
of them redrawn to a power of 1 to 60 at every height.  Both sides read a
commit FROM THE SAME BYTES: the block that carries it, as served, with
precommits taken out (upstream's nil entry) by a re-encoding of the
commit.

A sound chain in which every member signs tallies under ANY powers, so a
benchmark run cannot show which powers a node tallied with; these cases
can, on the CPU.  Heads and power part ways: a commit of MORE than 2/3 of
the members and no more than 2/3 of the power is refused for its power,
one of fewer than 2/3 of the members and more than 2/3 of the power is
accepted; and of two adjacent heights whose powers differ, each with a
commit that only the OTHER height's powers would accept, both are
refused when each is verified against its own height's set.  No test here
says which path the program took to its answer."""

from itertools import combinations
from types import SimpleNamespace

import pytest

from benchmark.lib import chain
from refcommit import (members_of, ref_commit, ref_decode_block,
                       ref_verify_commit, ref_verify_window)
from tendermint_tpu.types import Block, Commit
from tendermint_tpu.types.validator import (CommitPowerError,
                                            CommitSignatureError,
                                            verify_commits_batched)

EMPTY = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
CHAIN_ID, SEED, N_VALS = "bench-powers-ref", 2**31 + 472, 6
PLAN = {"change_every_blocks": 1, "members": 2, "min": 1, "max": 60}
SEARCH = 120                       # heights looked through for the cases


def _set(h: int):
    return chain.valset_at(SEED, N_VALS, None, h, PLAN)[1]


def _power(vs, signers) -> int:
    return sum(vs.validators[i].voting_power for i in signers)


def _accepts(vs, signers) -> bool:
    return 3 * _power(vs, signers) > 2 * vs.total_voting_power()


def _subsets(sizes):
    return [s for k in sizes for s in combinations(range(N_VALS), k)]


def find_cases() -> dict:
    """Heights of the plan's chain, by `valset_at` alone: (a) a height and
    five of its six members that hold no more than 2/3 of its power; (b)
    a height and three members that hold more than 2/3; (c) two adjacent
    heights and for each a set of signers its own powers refuse and the
    other height's accept."""
    sets = {h: _set(h) for h in range(1, SEARCH + 2)}
    found = {}
    for h in range(2, SEARCH):
        vs = sets[h]
        if "many-heads" not in found:
            for s in _subsets([5]):
                if not _accepts(vs, s):
                    found["many-heads"] = (h, s)
                    break
        if "few-heads" not in found:
            for s in _subsets([3, 2]):
                if _accepts(vs, s):
                    found["few-heads"] = (h, s)
                    break
        if "adjacent" not in found and sets[h + 1].hash() != vs.hash():
            nxt = sets[h + 1]
            here = [s for s in _subsets([3, 4, 5])
                    if not _accepts(vs, s) and _accepts(nxt, s)]
            there = [s for s in _subsets([3, 4, 5])
                     if not _accepts(nxt, s) and _accepts(vs, s)]
            if here and there:
                found["adjacent"] = (h, here[0], there[0])
    return found


@pytest.fixture(scope="module")
def c():
    cases = find_cases()
    assert set(cases) == {"many-heads", "few-heads", "adjacent"}, cases
    n_blocks = max(v[0] for v in cases.values()) + 3
    seeds, vs = chain.valset_at(SEED, N_VALS, None, 1, PLAN)
    with chain.Signers(seeds, 0) as sg:
        built = chain.build_chain(CHAIN_ID, seeds, vs, n_blocks, EMPTY, SEED,
                                  sg, keep_objects=True, powers=PLAN)
    return SimpleNamespace(built=built, cases=cases)


@pytest.fixture(autouse=True)
def native_backend():
    from tendermint_tpu.crypto import backend as cb
    old = cb._current
    cb.set_backend("native")
    yield
    cb._current = old


def served(c, h: int, signers=None) -> tuple:
    """(block id, h, the BYTES of block h + 1, which carries the commit of
    h): as the builder served them, or with the commit re-encoded to hold
    a precommit at the positions `signers` only and a nil entry elsewhere."""
    block, ps, seen = c.built["objects"][h - 1]
    data = c.built["encoded"][h]
    if signers is not None:
        cut = Commit(block_id=seen.block_id, precommits=[
            v if i in signers else None
            for i, v in enumerate(seen.precommits)])
        data = data[:ref_decode_block(data).commit_at] + cut.encode()
    return (block.block_id(ps), h, data)


def in_words(call, present=None):
    """The program's answer in the reference's words; a signature
    error's lane is a position among the precommits that are there
    (`present`, by the reference's reading of the bytes)."""
    try:
        call()
    except CommitSignatureError as e:
        return ("signature", e.height, present[e.lane])
    except CommitPowerError as e:
        return ("power", e.height)
    except (ValueError, IndexError) as e:
        return ("format", getattr(e, "height", None))
    return None


def verdicts(vs, item) -> tuple:
    """(the reference's, the program's batched entry's, the program's
    commit-by-commit entry's) verdict on one served commit against the
    set `vs`, each side reading the commit from the item's bytes."""
    bid, h, data = item
    records = ref_commit(data)
    present = [i for i, e in enumerate(records.entries) if e is not None]
    commit = Block.decode_bytes(data).last_commit
    return (ref_verify_commit(CHAIN_ID, members_of(vs), bid, h, records),
            in_words(lambda: verify_commits_batched(
                vs, CHAIN_ID, [(bid, h, commit)]), present),
            in_words(lambda: vs.verify_commit(CHAIN_ID, bid, h, commit),
                     present))


def both(vs, item):
    got = verdicts(vs, item)
    assert got[0] == got[1] == got[2], got
    return got[0]


def test_the_cases_are_on_a_chain_whose_powers_are_uneven_and_move(c):
    h, many = c.cases["many-heads"]
    assert len(many) == 5 and 3 * len(many) > 2 * N_VALS
    h2, few = c.cases["few-heads"]
    assert len(few) <= 3 and 3 * len(few) < 2 * N_VALS
    a, here, there = c.cases["adjacent"]
    assert _set(a).hash() != _set(a + 1).hash()
    assert [v.pub_key for v in _set(a).validators] == [
        v.pub_key for v in _set(a + 1).validators]
    for height in (h, h2, a, a + 1):
        powers = [v.voting_power for v in _set(height).validators]
        assert len(set(powers)) > 2 and all(1 <= p <= 60 for p in powers)
        assert Block.decode_bytes(
            c.built["encoded"][height - 1]).header.validators_hash == \
            _set(height).hash()


def test_sound_commits_of_the_chain_are_accepted_under_their_own_powers(c):
    """Every member signed: any powers accept, the height's own among
    them; by window against one set only where the window is one set's."""
    for h in range(1, len(c.built["encoded"]) - 1):
        assert both(_set(h), served(c, h)) is None
    a = c.cases["adjacent"][0]
    items = [served(c, a), served(c, a + 1)]
    refs = [(bid, h, ref_commit(data)) for bid, h, data in items]
    # the reference's loop over a window takes ONE set, as upstream's
    # sync loop holds one state: it accepts two full commits under either
    assert ref_verify_window(CHAIN_ID, members_of(_set(a)), refs) is None


def test_more_than_two_thirds_of_the_heads_with_too_little_power_is_refused(c):
    h, signers = c.cases["many-heads"]
    vs = _set(h)
    assert 3 * len(signers) > 2 * N_VALS and not _accepts(vs, signers)
    assert both(vs, served(c, h, signers)) == ("power", h)
    # the same five precommits under equal powers (the genesis set, the
    # same keys) are +2/3: a tally by heads, or by a stale set, says yes
    equal = chain.valset_at(SEED, N_VALS, None, 1, PLAN)[1]
    assert {v.voting_power for v in equal.validators} == {chain.POWER}
    assert both(equal, served(c, h, signers)) is None


def test_fewer_than_two_thirds_of_the_heads_with_the_power_is_accepted(c):
    h, signers = c.cases["few-heads"]
    vs = _set(h)
    assert 3 * len(signers) < 2 * N_VALS and _accepts(vs, signers)
    assert both(vs, served(c, h, signers)) is None
    equal = chain.valset_at(SEED, N_VALS, None, 1, PLAN)[1]
    assert both(equal, served(c, h, signers)) == ("power", h)
    # and with its heaviest signer silent too it is short under its own
    heavy = max(signers, key=lambda i: vs.validators[i].voting_power)
    less = tuple(i for i in signers if i != heavy)
    assert not _accepts(vs, less)
    assert both(vs, served(c, h, less)) == ("power", h)


def test_adjacent_heights_are_each_tallied_against_their_own_powers(c):
    """Heights a and a + 1 hold the same six keys at other powers.  The
    commit of a holds the precommits of signers that a's powers refuse
    and a + 1's accept, the commit of a + 1 the other way round.  Each
    against ITS OWN set, through the program's batched entry (the call the
    reactor makes for a window, here a window of one set: one commit) and
    commit by commit: both refused for their power, at their own height.
    A program that tallied a run of heights against one set of powers,
    either height's, would accept one of the two."""
    a, here, there = c.cases["adjacent"]
    first, second = _set(a), _set(a + 1)
    item_a, item_b = served(c, a, here), served(c, a + 1, there)
    assert both(first, item_a) == ("power", a)
    assert both(second, item_b) == ("power", a + 1)
    # what makes the case: each passes under the other height's powers
    assert both(second, item_a) is None
    assert both(first, item_b) is None
    # handed over together, in order, as the windows a set change cuts
    # them into: the first refusal is the first height's
    windows = [(first, [item_a]), (second, [item_b])]
    program = [in_words(lambda vs=vs, items=items: verify_commits_batched(
        vs, CHAIN_ID, [(bid, h, Block.decode_bytes(data).last_commit)
                       for bid, h, data in items])) for vs, items in windows]
    reference = [ref_verify_window(
        CHAIN_ID, members_of(vs), [(bid, h, ref_commit(data))
                                   for bid, h, data in items])
        for vs, items in windows]
    assert program == reference == [("power", a), ("power", a + 1)]
    # one window against ONE set, whichever: one of the two slips through
    for vs, want in ((first, ("power", a)), (second, ("power", a + 1))):
        refs = [(bid, h, ref_commit(data)) for bid, h, data in
                (item_a, item_b)]
        assert ref_verify_window(CHAIN_ID, members_of(vs), refs) == want
        assert in_words(lambda vs=vs: verify_commits_batched(
            vs, CHAIN_ID, [(bid, h, Block.decode_bytes(data).last_commit)
                           for bid, h, data in (item_a, item_b)])) == want


def test_a_forged_precommit_is_named_before_any_power_is_counted(c):
    """The order of upstream's loop holds under uneven powers too: a
    signature that does not verify is the verdict even where the commit
    would be short of power without it."""
    h, signers = c.cases["many-heads"]
    bid, _h, data = served(c, h, signers)
    forged = bytearray(data)
    # the last byte of an entry is the last byte of its signature
    forged[ref_decode_block(data).entry_at[signers[1] + 1] - 1] ^= 1
    assert both(_set(h), (bid, h, bytes(forged))) == (
        "signature", h, signers[1])
