"""A commit keeps its wire bytes through nil entries; nobody can tell.

Over seeded presence patterns (the first entry nil, the last, runs, one
vote present, none) at 1, 4, 100 and 300 validators of uneven power, the
commit `Commit.decode` leaves in its bytes must be what the vote-by-vote
decoder gives of the same bytes (`tests/test_commit_wire.py`'s
`object_decode`) in every accessor, in the lanes of one commit and, for
windows that mix full, nil-holding and object-form commits, in the seven
arrays of `window_commit_lanes`, byte for byte.  A present record is
held to its POSITION among the entries: a marker swapped with a record
beside it, or two records swapped whole, are refused as by the
vote-by-vote path, never mended.  A marker byte other than 0 or 1 is no
commit on either path.  Signatures are random bytes: nothing here
verifies one (`tests/benchmark/test_bench_absent_reference.py` does,
against upstream's loop)."""

import sys
import time

import numpy as np
import pytest

from tendermint_tpu.types import BlockID, Commit, Vote, ZERO_BLOCK_ID
from tendermint_tpu.types import block as block_mod
from tendermint_tpu.types.canonical import TYPE_PRECOMMIT
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.keys import PubKey
from tendermint_tpu.types.validator import (CommitFormatError, Validator,
                                            ValidatorSet, merge_commit_lanes,
                                            window_commit_lanes)
from tests.test_commit_wire import (CHAIN, HEIGHT, LANE_NAMES, WINDOW_NAMES,
                                    assert_same, object_decode, outcome,
                                    rand_bid, votes_for)

SIZES = (1, 4, 100, 300)
ACCESSORS = ("size", "height", "round", "num_sigs", "bit_array",
             "is_commit", "hash", "validate_basic")


def _first(n, rng):
    return {0}


def _last(n, rng):
    return {n - 1}


def _both_ends(n, rng):
    return {0, n - 1}


def _run_at_the_start(n, rng):
    return set(range(max(1, n // 3)))


def _run_at_the_end(n, rng):
    return set(range(n - max(1, n // 3), n))


def _two_runs(n, rng):
    return set(range(n // 4, n // 4 + max(1, n // 8))) | \
        set(range(n // 2, n // 2 + max(1, n // 5)))


def _every_other(n, rng):
    return set(range(0, n, 2))


def _one_present(n, rng):
    return set(range(n)) - {int(rng.integers(0, n))}


def _only_the_first_present(n, rng):
    return set(range(1, n))


def _only_the_last_present(n, rng):
    return set(range(n - 1))


def _none_present(n, rng):
    return set(range(n))


def _a_few(n, rng):
    return {int(i) for i in rng.choice(n, size=max(1, n // 25),
                                       replace=False)}


def _a_third(n, rng):
    return {int(i) for i in rng.choice(n, size=max(1, n // 3),
                                       replace=False)}


PATTERNS = {f.__name__[1:]: f for f in (
    _first, _last, _both_ends, _run_at_the_start, _run_at_the_end,
    _two_runs, _every_other, _one_present, _only_the_first_present,
    _only_the_last_present, _none_present, _a_few, _a_third)}


@pytest.fixture(scope="module")
def sets():
    """Sets of uneven power under random keys (an address is a hash of
    the key; nothing signs)."""
    out = {}
    for n in SIZES:
        rng = np.random.default_rng(n)
        out[n] = ValidatorSet([Validator(PubKey(rng.bytes(32)), 10 + 7 * i)
                               for i in range(n)])
    return out


def nil_holding(rng, vs, bid, absent, **kw) -> Commit:
    """The commit a peer builds from votes, the entries at `absent` nil."""
    return Commit(block_id=bid, precommits=[
        None if i in absent else v
        for i, v in enumerate(votes_for(rng, vs, bid, **kw))])


def decoded(wire: bytes) -> Commit:
    return Commit.decode(Reader(wire))


def assert_same_commit(dec: Commit, ref: Commit, wire: bytes):
    """Every accessor, asked of the wire form BEFORE its votes are made."""
    for name in ACCESSORS:
        assert outcome(getattr(dec, name)) == outcome(getattr(ref, name)), \
            name
    assert dec.encode() == ref.encode() == wire
    assert dec.precommits == ref.precommits
    assert dec.precommits is dec.precommits          # made once, kept
    assert dec == ref and ref == dec


@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_commit_with_nil_entries_is_its_object_form_in_every_way(
        sets, n_vals, pattern):
    vs = sets[n_vals]
    rng = np.random.default_rng([n_vals, list(PATTERNS).index(pattern)])
    absent = PATTERNS[pattern](n_vals, rng)
    bid = rand_bid(rng)
    wire = nil_holding(rng, vs, bid, absent).encode()
    dec, ref = decoded(wire), object_decode(wire)
    assert not ref.wire_backed()
    # in its bytes wherever one record is left to be in them
    assert dec.wire_backed() == (len(absent) < n_vals)
    if dec.wire_backed():
        assert dec.wire_columns()[5] == tuple(sorted(absent))
        addrs, sigs = dec.wire_columns()[:2]
        assert len(addrs) // 20 == len(sigs) // 64 == n_vals - len(absent)
    assert_same_commit(decoded(wire), ref, wire)

    # the lanes of the one commit, for its block and for another
    for expect in (bid, rand_bid(rng)):
        got = outcome(lambda: vs.commit_verify_lanes(CHAIN, expect, HEIGHT,
                                                     dec))
        assert_same(got, outcome(lambda: vs.commit_verify_lanes(
            CHAIN, expect, HEIGHT, ref)), LANE_NAMES)
        if len(absent) < n_vals:
            assert got[0] == "ok"
            assert list(got[1][4]) == [i for i in range(n_vals)
                                       if i not in absent]
            power = sum(v.voting_power for i, v in enumerate(vs.validators)
                        if i not in absent)
            assert (int(got[1][3].sum()), got[1][5]) == (
                (power, 0) if expect == bid else (0, power))


# a window's commits by kind: every vote there, nil entries, and what no
# wire form holds (a precommit for the nil block beside nil entries)
KINDS = ("full", "nil", "object")


def window_of(rng, vs, kinds, foreign_p=0.25):
    """([(block id, height, wire)], the kinds): a window as served."""
    n = vs.size()
    items = []
    for h, kind in enumerate(kinds, start=1):
        bid = rand_bid(rng)
        cbid = bid if rng.random() >= foreign_p else rand_bid(rng)
        absent = set() if kind == "full" else _a_few(n, rng) if n > 1 \
            else set()
        commit = nil_holding(rng, vs, cbid, absent, height=h,
                             round_=int(rng.integers(0, 3)))
        if kind == "object" and n > 1:
            k = next(i for i in range(n) if i not in absent)
            votes = list(commit.precommits)
            votes[k] = Vote(**{**votes[k].__dict__,
                               "block_id": ZERO_BLOCK_ID})
            commit = Commit(block_id=cbid, precommits=votes)
        items.append((bid, h, commit.encode()))
    return items


def per_block_loop(vs, items):
    """The seven arrays as the per-block loop over commits decoded vote
    by vote gives them (`merge_commit_lanes` order)."""
    arrays = [vs.commit_verify_lanes(CHAIN, bid, h, object_decode(w))
              for bid, h, w in items]
    return merge_commit_lanes(arrays) + (
        np.asarray([len(a[4]) for a in arrays], dtype=np.int64),
        np.asarray([int(a[3].sum()) for a in arrays], dtype=np.int64),
        np.asarray([a[5] for a in arrays], dtype=np.int64))


@pytest.mark.parametrize("mix", [
    ("nil",), ("nil", "nil", "nil"), ("full", "nil"), ("nil", "full"),
    ("full", "nil", "full", "nil", "nil"), ("nil", "object", "full"),
    ("object", "nil"), ("full", "full", "object", "nil", "nil", "full")],
    ids="-".join)
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_mixed_window_is_the_per_block_loops_seven_arrays(sets, n_vals,
                                                            mix):
    vs = sets[n_vals]
    rng = np.random.default_rng([n_vals, len(mix), KINDS.index(mix[0])])
    items = window_of(rng, vs, mix)
    got = outcome(lambda: window_commit_lanes(
        vs, CHAIN, [(bid, h, decoded(w)) for bid, h, w in items]))
    assert got[0] == "ok"
    assert_same(got, ("ok", per_block_loop(vs, items)), WINDOW_NAMES)
    counts = got[1][4]
    assert int(counts.sum()) == len(got[1][2]) == len(got[1][3])


@pytest.mark.parametrize("seed", range(6))
def test_a_seeded_window_of_64_is_the_per_block_loops_seven_arrays(sets,
                                                                   seed):
    """A whole fast-sync window at 100 validators, ~4 nil entries a
    commit as the benchmark's chain has them, some commits full."""
    vs = sets[100]
    rng = np.random.default_rng(1000 + seed)
    kinds = [("full", "nil")[int(rng.random() < 0.9)] for _ in range(64)]
    items = window_of(rng, vs, kinds, foreign_p=0.1)
    got = window_commit_lanes(
        vs, CHAIN, [(bid, h, decoded(w)) for bid, h, w in items])
    assert_same(("ok", got), ("ok", per_block_loop(vs, items)), WINDOW_NAMES)
    assert 64 * 90 < len(got[2]) < 64 * 100


def _entry_offsets(wire: bytes, n: int) -> list[int]:
    """Where each entry's marker byte is (and the body's end), by the
    vote-by-vote decoder's own walk."""
    r = Reader(wire)
    BlockID.decode(r)
    assert r.u32() == n
    at = []
    for _ in range(n):
        at.append(r.pos)
        if r.u8():
            Vote.decode(r)
    return at + [r.pos]


def _swapped(wire: bytes, at: list[int], j: int, k: int) -> bytes:
    """Entries j < k of the body swapped whole."""
    return (wire[:at[j]] + wire[at[k]:at[k + 1]] + wire[at[j + 1]:at[k]]
            + wire[at[j]:at[j + 1]] + wire[at[k + 1]:])


@pytest.mark.parametrize("tamper", ["nil-with-its-successor",
                                    "nil-with-its-predecessor",
                                    "two-records", "two-records-apart"])
@pytest.mark.parametrize("n_vals", [4, 100])
def test_a_record_off_its_position_is_refused_and_never_mended(sets, n_vals,
                                                               tamper):
    """The count of present entries, the addresses and the signatures
    are all sound; only WHERE a record sits disagrees with its index.
    Both decoders read the same votes, and the lanes refuse them with
    the same words at the same height, alone and in a window."""
    vs = sets[n_vals]
    rng = np.random.default_rng([n_vals, len(tamper)])
    bid = rand_bid(rng)
    nil = n_vals // 2
    wire = nil_holding(rng, vs, bid, {nil}).encode()
    at = _entry_offsets(wire, n_vals)
    j, k = {"nil-with-its-successor": (nil, nil + 1),
            "nil-with-its-predecessor": (nil - 1, nil),
            "two-records": (0, 1),
            "two-records-apart": (0, n_vals - 1)}[tamper]
    bad = _swapped(wire, at, j, k)
    assert bad != wire and len(bad) == len(wire)
    dec, ref = decoded(bad), object_decode(bad)
    assert not dec.wire_backed()
    assert_same_commit(dec, ref, bad)
    want = outcome(lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, ref))
    assert want[0] == "raised" and "index" in want[1][1]
    assert outcome(lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT,
                                                  dec)) == want
    sound = decoded(wire)
    got = outcome(lambda: window_commit_lanes(
        vs, CHAIN, [(bid, HEIGHT, sound), (bid, HEIGHT, dec)]))
    assert got[0] == "raised" and got[1][0] is CommitFormatError
    assert got[1][2] == HEIGHT


@pytest.mark.parametrize("body,where", [
    ("nil", "on-a-nil-entry"), ("nil", "before-a-record"),
    ("nil", "before-the-first-record"), ("nil", "before-the-last-record"),
    ("full", "before-a-record"), ("full", "before-the-first-record"),
    ("full", "before-the-last-record")])
@pytest.mark.parametrize("marker", [2, 255])
def test_a_marker_byte_other_than_0_or_1_is_no_commit_on_either_path(
        sets, marker, body, where):
    """go-wire's pointer byte: read as a truth value, two byte strings
    would decode to one commit and `encode()` would not be the bytes
    served.  A full body leaves the big-integer compare for the
    vote-by-vote loop, a nil-holding one the walk: both refuse it."""
    n = 100
    vs = sets[n]
    rng = np.random.default_rng([marker, len(where)])
    bid = rand_bid(rng)
    absent = {40, 41, 77} if body == "nil" else set()
    wire = nil_holding(rng, vs, bid, absent).encode()
    at = _entry_offsets(wire, n)
    k = {"on-a-nil-entry": 41, "before-a-record": 42,
         "before-the-first-record": 0, "before-the-last-record": n - 1}[where]
    bad = wire[:at[k]] + bytes([marker]) + wire[at[k] + 1:]
    for decode in (decoded, object_decode):
        with pytest.raises(ValueError, match=f"entry {k}: marker byte "
                                             f"{marker}"):
            decode(bad)
    assert decoded(wire).wire_backed()


def test_a_count_the_body_cannot_hold_is_refused_at_the_buffers_end():
    """A count of a million over 5,000 nil markers and one record: the
    walk ends where the buffer does and the vote-by-vote loop says
    `truncated`; nothing is built at the size the count claims."""
    rng = np.random.default_rng(5)
    vs = ValidatorSet([Validator(PubKey(rng.bytes(32)), 1)])
    bid = rand_bid(rng)
    wire = nil_holding(rng, vs, bid, set()).encode()
    head = len(bid.encode())
    bad = (wire[:head] + (1_000_000).to_bytes(4, "big") + bytes(5_000)
           + wire[head + 4:])
    for decode in (decoded, object_decode):
        with pytest.raises(ValueError, match="truncated"):
            decode(bad)


# The bytes are a peer's to choose, and the blockchain channel takes a
# message of 32 MB: what the walk and the compare cost has to be linear
# in the body whatever the markers say, as the vote-by-vote decode is.

def alternating_wire(n: int, seed: int = 9, junk_at: int | None = None):
    """A commit of n entries, every even one nil and every odd one a
    sound record at its position (no set behind it: an address is 20
    random bytes); with `junk_at`, that record in another round.
    Returns (the bytes, the block id's length on the wire)."""
    rng = np.random.default_rng(seed)
    bid = rand_bid(rng)
    body = bytearray()
    for i in range(n):
        if i % 2 == 0:
            body += b"\x00"
            continue
        body += b"\x01" + Vote(
            validator_address=rng.bytes(20), validator_index=i,
            height=HEIGHT, round=2 if i == junk_at else 1,
            type=TYPE_PRECOMMIT, block_id=bid,
            signature=rng.bytes(64)).encode()
    head = bid.encode()
    return head + n.to_bytes(4, "big") + bytes(body), len(head)


def body_conversions(call) -> int:
    """How many times `call` turned bytes into an integer."""
    seen = []

    def profile(_frame, event, arg):
        if event == "c_call" and arg.__name__ == "from_bytes":
            seen.append(arg)
    sys.setprofile(profile)
    try:
        call()
    finally:
        sys.setprofile(None)
    return len(seen)


def quickest(call, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        call()
        best = min(best, time.perf_counter() - t0)
    return best


@pytest.mark.parametrize("n", [2_000, 6_000])
def test_an_alternating_body_of_thousands_is_its_object_form(n):
    """Half the entries nil, (n / 2 + 1) runs of one record: wire-backed,
    and the vote-by-vote decode of the same bytes in every way, the
    Merkle root over n leaves (half of them empty) among them."""
    wire, _ = alternating_wire(n)
    dec = decoded(wire)
    assert dec.wire_backed() and dec.num_sigs() == n // 2
    assert dec.wire_columns()[5] == tuple(range(0, n, 2))
    assert_same_commit(dec, object_decode(wire), wire)


@pytest.mark.parametrize("junk", [False, True], ids=["sound", "junk-record"])
def test_the_body_is_read_as_an_integer_a_fixed_number_of_times(junk):
    """Nothing of the body's size is built a nil entry: the big-integer
    compare converts the body, the pattern it is held to and the
    positions ONCE each, 2 nil entries or 2,000, and a body with one
    record of another round, which it refuses, costs no more than a
    sound one.  (A conversion a nil entry was quadratic in bytes a peer
    chooses, before anything is verified.)"""
    counts = []
    for n in (5, 4_000):
        wire, bid_len = alternating_wire(n, junk_at=3 if junk else None)

        def walk_and_compare(wire=wire, bid_len=bid_len, n=n):
            kept = block_mod._wire_with_nil_entries(wire, 0, n, bid_len)
            assert (kept is None) == junk
        block_mod._pinned.cache_clear()      # its two count, both times
        counts.append(body_conversions(walk_and_compare))
    assert counts[0] == counts[1] == 5, counts


@pytest.mark.parametrize("junk", [False, True], ids=["sound", "junk-record"])
def test_an_alternating_body_costs_less_than_its_vote_by_vote_decode(junk):
    """10,000 entries, 5,000 nil markers beside 5,000 records: walked
    and compared in less time than the vote-by-vote loop takes over the
    same bytes (a tenth of it, where nothing else runs; a pass a nil
    entry over the body took hundreds of times as long), sound or with
    a junk record the compare refuses."""
    n = block_mod._MAX_WALKED
    wire, bid_len = alternating_wire(n, junk_at=3 if junk else None)
    by_votes = quickest(lambda: object_decode(wire))
    kept = quickest(
        lambda: block_mod._wire_with_nil_entries(wire, 0, n, bid_len))
    assert kept < by_votes, (kept, by_votes)


def test_a_count_past_upstreams_most_votes_is_never_walked():
    """`_MAX_WALKED` entries with nil markers among them keep their
    bytes; one more and the commit decodes vote by vote, the same value,
    at the parent's cost: the walk keeps a position a nil entry, and a
    32 MB message of zeros under a count to match is a peer's to send."""
    most = block_mod._MAX_WALKED
    wire, bid_len = alternating_wire(most)
    assert decoded(wire).wire_backed()
    wire, bid_len = alternating_wire(most + 2)
    assert block_mod._nil_entries(wire, bid_len + 4, most + 2,
                                  block_mod._REC_FIXED + bid_len) is None
    dec = decoded(wire)
    assert not dec.wire_backed()
    assert_same_commit(dec, object_decode(wire), wire)
