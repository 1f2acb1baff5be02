"""A commit decoded from the wire stays in its bytes; nobody can tell.

`Commit.decode` leaves a body whose present records are regular (one
width, one (height, round, type, block id), each index its position
among the entries; nil entries between them or none) as a view of the
bytes it was read from, and decodes any other body vote by vote.  For
every shape a peer can send, the decoded commit must be what the
vote-by-vote decoder alone (`object_decode` below) gives: the same lanes
for the batch plane or the same error with the same message and height,
the same bytes back, the same answers to every accessor.  An entry's
marker byte is 0 or 1 for both.  Signatures are random bytes: nothing
here verifies one.  The presence patterns (first entry nil, last, runs,
one present, none) are `tests/test_commit_wire_absent.py`'s.
"""

import numpy as np
import pytest

from tendermint_tpu.types import (BlockID, Commit, Vote, ZERO_BLOCK_ID,
                                  TYPE_PRECOMMIT, TYPE_PREVOTE)
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.part_set import PartSetHeader
from tendermint_tpu.types.validator import (CommitFormatError,
                                            window_commit_lanes)
from tests.chainutil import make_validators

CHAIN = "commit-wire-test"
HEIGHT = 7
SIZES = (1, 4, 100, 128)
# shape -> is the decoded commit wire-backed?
SHAPES = {
    "all_present": True,
    "some_absent": True,                 # but a set of one: none is left
    "nil_vote": False,
    "foreign_vote": False,
    "foreign_commit_block_id": True,
    "wrong_index": False,
    "wrong_address": True,
    "marker_two": None,                  # does not decode at all
    "short_signature": False,
    "truncated_last_record": None,       # does not decode at all
    "wrong_height": True,
    "wrong_round_in_one_vote": False,
    "size_not_the_sets": True,
    "prevotes": True,
}
LANE_NAMES = ("templates", "tmpl_idx", "sigs", "powers", "idxs")
WINDOW_NAMES = ("templates", "tmpl_idx", "sigs", "idxs", "counts",
                "tallied", "foreign")


def object_decode(wire: bytes) -> Commit:
    """`Commit.decode` vote by vote, no other path: an entry's marker
    byte is 0 (nil) or 1 (a vote follows), as go-wire's pointer byte."""
    r = Reader(wire)
    block_id = BlockID.decode(r)
    votes = []
    for i in range(r.u32()):
        marker = r.u8()
        if marker > 1:
            raise ValueError(f"commit entry {i}: marker byte {marker}")
        votes.append(Vote.decode(r) if marker else None)
    r.expect_done()
    return Commit(block_id=block_id, precommits=votes)


def rand_bid(rng) -> BlockID:
    return BlockID(rng.bytes(32), PartSetHeader(int(rng.integers(1, 5)),
                                                rng.bytes(32)))


def votes_for(rng, vs, bid, height=HEIGHT, round_=1, type_=TYPE_PRECOMMIT):
    return [Vote(validator_address=v.address, validator_index=i,
                 height=height, round=round_, type=type_, block_id=bid,
                 signature=rng.bytes(64))
            for i, v in enumerate(vs.validators)]


def shaped_wire(shape: str, rng, vs, bid) -> bytes:
    """The bytes a peer sends for a commit of `shape` on `bid`."""
    n = vs.size()
    k = int(rng.integers(0, n))          # the vote the shape touches
    votes = votes_for(rng, vs, bid)
    commit_bid = bid

    def edit(**kw):
        votes[k] = Vote(**{**votes[k].__dict__, **kw})
    if shape == "some_absent":
        votes[k] = None
    elif shape == "nil_vote":
        edit(block_id=ZERO_BLOCK_ID)
    elif shape == "foreign_vote":
        edit(block_id=rand_bid(rng))
    elif shape == "foreign_commit_block_id":
        commit_bid = rand_bid(rng)
        votes = votes_for(rng, vs, commit_bid)
    elif shape == "wrong_index":
        edit(validator_index=k + 1)
    elif shape == "wrong_address":
        edit(validator_address=rng.bytes(20))
    elif shape == "short_signature":
        edit(signature=rng.bytes(63))
    elif shape == "wrong_height":
        votes = votes_for(rng, vs, bid, height=HEIGHT + 9)
    elif shape == "wrong_round_in_one_vote":
        edit(round=2)
    elif shape == "size_not_the_sets":
        votes.append(Vote(**{**votes[-1].__dict__, "validator_index": n,
                             "validator_address": rng.bytes(20)}))
    elif shape == "prevotes":
        votes = votes_for(rng, vs, bid, type_=TYPE_PREVOTE)
    wire = Commit(block_id=commit_bid, precommits=votes).encode()
    if shape == "marker_two":
        width = (len(wire) - len(bid.encode()) - 4) // n
        at = len(wire) - (n - k) * width
        assert wire[at] == 1
        wire = wire[:at] + b"\x02" + wire[at + 1:]
    elif shape == "truncated_last_record":
        wire = wire[:-int(rng.integers(1, 64))]
    return wire


def outcome(f):
    """What a call gives: its value, or its error's type, message and
    (a `CommitFormatError`'s) height."""
    try:
        return "ok", f()
    except ValueError as e:
        return "raised", (type(e), str(e), getattr(e, "height", None))


def assert_same(got, want, names):
    assert got[0] == want[0], (got, want)
    if got[0] == "raised":
        assert got[1] == want[1]
        return
    for name, g, w in zip(names, got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    assert len(got[1]) == len(want[1])
    for g, w in zip(got[1][len(names):], want[1][len(names):]):
        assert g == w and type(g) is type(w)       # foreign_power


@pytest.fixture(scope="module")
def sets():
    return {n: make_validators(n, seed=n % 7)[1] for n in SIZES}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("n_vals", SIZES)
def test_decoded_commit_is_the_object_form_in_every_way(sets, n_vals, shape):
    vs = sets[n_vals]
    rng = np.random.default_rng([n_vals, list(SHAPES).index(shape)])
    bid = rand_bid(rng)
    wire = shaped_wire(shape, rng, vs, bid)

    want = outcome(lambda: object_decode(wire))
    got = outcome(lambda: Commit.decode(Reader(wire)))
    assert got[0] == want[0]
    if SHAPES[shape] is None:
        assert got == want and got[0] == "raised"
        return
    dec, ref = got[1], want[1]
    # (a set of one has no other vote for its one vote to differ from,
    # and with its one vote absent no record to stay in)
    wire_backed = SHAPES[shape]
    if n_vals == 1 and shape in ("wrong_round_in_one_vote", "some_absent"):
        wire_backed = not wire_backed
    assert (dec.wire_columns() is not None) == wire_backed
    assert dec.wire_backed() == wire_backed
    assert ref.wire_columns() is None

    # the bytes back: the input's own where it stayed in them
    assert dec.encode() == ref.encode()
    if wire_backed:
        assert dec.encode() == wire

    # the lanes, one commit and a window (two regular commits around it)
    assert_same(
        outcome(lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, dec)),
        outcome(lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, ref)),
        LANE_NAMES)
    around = []
    for h in (HEIGHT - 1, HEIGHT + 1):
        b = rand_bid(rng)
        w = Commit(block_id=b,
                   precommits=votes_for(rng, vs, b, height=h)).encode()
        around.append((b, h, w))

    def window(decode, mid):
        (b0, h0, w0), (b2, h2, w2) = around
        return window_commit_lanes(vs, CHAIN, [
            (b0, h0, decode(w0)), (bid, HEIGHT, mid), (b2, h2, decode(w2))])
    got_w = outcome(lambda: window(lambda w: Commit.decode(Reader(w)), dec))
    assert_same(got_w, outcome(lambda: window(object_decode, ref)),
                WINDOW_NAMES)
    if got_w[0] == "raised":
        assert got_w[1][0] is CommitFormatError and got_w[1][2] == HEIGHT

    # every accessor, asked of the wire form BEFORE its votes are made
    for name in ("size", "height", "round", "num_sigs", "bit_array",
                 "is_commit", "hash", "validate_basic"):
        assert outcome(getattr(dec, name)) == outcome(getattr(ref, name)), \
            name
    assert dec.precommits == ref.precommits
    assert dec.precommits is dec.precommits          # made once, kept
    assert dec == ref and dec.encode() == ref.encode()


def test_decode_advances_the_reader_past_the_commit_only():
    """A commit inside a longer record (as in a block, or a message):
    both forms leave the reader where the commit ends."""
    _, vs = make_validators(4)
    rng = np.random.default_rng(3)
    bid = rand_bid(rng)
    regular = Commit(block_id=bid, precommits=votes_for(rng, vs, bid))
    votes = list(regular.precommits)
    votes[2] = None
    absent = Commit(block_id=bid, precommits=votes)
    # a nil VOTE (a precommit for no block) is a record of another width
    votes = list(regular.precommits)
    votes[2] = Vote(**{**votes[2].__dict__, "block_id": ZERO_BLOCK_ID})
    for_nil = Commit(block_id=bid, precommits=votes)
    for commit, wire_backed in ((regular, True), (absent, True),
                                (for_nil, False)):
        wire = commit.encode()
        for tail in (b"tail", b"\x00\x01\x00", b"\x01" * 200, b""):
            r = Reader(b"\xaa\xbb" + wire + tail)
            assert r.fixed(2) == b"\xaa\xbb"
            dec = Commit.decode(r)
            assert r.fixed(len(tail)) == tail and r.done()
            assert (dec.wire_columns() is not None) == wire_backed
            assert dec == commit and dec.encode() == wire
    empty = Commit.decode(Reader(Commit(ZERO_BLOCK_ID, []).encode()))
    assert empty.size() == 0 and not empty.is_commit()
    assert empty.wire_columns() is None


def test_the_counter_pair_and_the_instant_say_which_path_a_decode_took():
    """No record on the regular path of a full commit; one
    `commit.wire_absent` instant, with the commit's height and its nil
    entries, where a commit stays in its bytes through them; one
    `commit.object_form` instant, with the height and the reason, when
    a decoded commit takes the object path at the decoder or at the
    lane builder.  An empty commit (height 1's) has no votes to take
    either path."""
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    _, vs = make_validators(4)
    rng = np.random.default_rng(4)
    bid = rand_bid(rng)
    regular = Commit(block_id=bid, precommits=votes_for(rng, vs, bid))
    votes = list(regular.precommits)
    votes[1] = None
    pruned = Commit(block_id=bid, precommits=votes)
    stale = Commit(block_id=bid,
                   precommits=votes_for(rng, vs, bid, height=HEIGHT - 3))
    # irregular WITHOUT a nil entry: the word is what `_irregular` found
    votes = list(regular.precommits)
    votes[2] = Vote(**{**votes[2].__dict__, "round": 2})
    stray = Commit(block_id=bid, precommits=votes)
    votes = list(regular.precommits)
    votes[2] = Vote(**{**votes[2].__dict__, "block_id": ZERO_BLOCK_ID})
    for_nil = Commit(block_id=bid, precommits=votes)

    def moved(f):
        t0 = tracing.now_epoch()
        before = (REGISTRY.commits_decoded_wire.value,
                  REGISTRY.commits_decoded_objects.value)
        out = f()
        seen = [(s["args"]["height"], s["args"].get("reason",
                                                    s["args"].get("absent")))
                for s in tracing.RECORDER.since(t0)
                if s["name"] in ("commit.object_form", "commit.wire_absent")
                and s["ts"] >= t0]
        return out, (REGISTRY.commits_decoded_wire.value - before[0],
                     REGISTRY.commits_decoded_objects.value - before[1]), seen

    dec, counts, seen = moved(lambda: Commit.decode(Reader(regular.encode())))
    assert counts == (1, 0) and seen == []
    _, counts, seen = moved(
        lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, dec))
    assert counts == (0, 0) and seen == []
    # a nil entry: still the bytes, and the instant says how many
    wire_absent0 = REGISTRY.commits_decoded_wire_absent.value
    kept, counts, seen = moved(
        lambda: Commit.decode(Reader(pruned.encode())))
    assert counts == (1, 0) and seen == [(HEIGHT, 1)]
    assert REGISTRY.commits_decoded_wire_absent.value - wire_absent0 == 1
    _, counts, seen = moved(
        lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, kept))
    assert counts == (0, 0) and seen == []
    # every entry nil: no record to stay in
    none = Commit(block_id=bid, precommits=[None] * 4)
    _, counts, seen = moved(lambda: Commit.decode(Reader(none.encode())))
    assert counts == (0, 1) and seen == [(0, "absent")]
    _, counts, seen = moved(lambda: Commit.decode(Reader(stray.encode())))
    assert counts == (0, 1) and seen == [(HEIGHT, "votes")]
    _, counts, seen = moved(lambda: Commit.decode(Reader(for_nil.encode())))
    assert counts == (0, 1) and seen == [(HEIGHT, "length")]
    _, counts, seen = moved(
        lambda: Commit.decode(Reader(Commit(ZERO_BLOCK_ID, []).encode())))
    assert counts == (0, 0) and seen == []
    old, counts, seen = moved(lambda: Commit.decode(Reader(stale.encode())))
    assert counts == (1, 0) and seen == []
    assert REGISTRY.commits_decoded_wire_absent.value - wire_absent0 == 1
    with pytest.raises(ValueError, match="commit height 4 != 7"):
        _, counts, seen = moved(
            lambda: vs.commit_verify_lanes(CHAIN, bid, HEIGHT, old))
    assert [s["args"] for s in tracing.RECORDER.snapshot()
            if s["name"] == "commit.object_form"][-1] == \
        {"height": HEIGHT, "reason": "height"}


# -- the consumers off the hot path ------------------------------------------

class _ObjectFormStore:
    """A block store that answers with the commits a chain was built
    with (votes held, never decoded): what every consumer saw before."""

    def __init__(self, store, chain):
        self._store, self._chain = store, chain
        self.height = store.height

    def load_block(self, height):
        return self._chain[height - 1][0]

    def load_seen_commit(self, height):
        return self._chain[height - 1][2]

    def load_block_commit(self, height):
        return self._chain[height][0].last_commit

    def __getattr__(self, name):
        return getattr(self._store, name)


@pytest.fixture(scope="module")
def stored_chain():
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.utils.db import MemDB
    from tests.chainutil import (build_chain, kvstore_app_hashes,
                                 make_genesis)
    privs, vs = make_validators(4)
    chain = build_chain(privs, vs, CHAIN, 4,
                        app_hashes=kvstore_app_hashes(4))
    store = BlockStore(MemDB())
    for block, ps, seen in chain:
        store.save_block(block, ps, seen)
    assert store.load_seen_commit(4).wire_columns() is not None
    assert store.load_block(4).last_commit.wire_columns() is not None
    return make_genesis(CHAIN, privs), chain, store


@pytest.mark.parametrize("route", ["block", "commit"])
def test_rpc_answers_for_a_decoded_commit_as_for_its_object_form(
        stored_chain, route):
    from types import SimpleNamespace
    from tendermint_tpu.rpc.routes import Routes
    _gen, chain, store = stored_chain

    def routes(block_store):
        return Routes(SimpleNamespace(
            block_store=block_store,
            config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False))))
    decoded, objects = routes(store), routes(_ObjectFormStore(store, chain))
    for h in range(1, 5):        # 4 is the tip: its seen commit
        got = decoded.table[route]({"height": h})
        assert got == objects.table[route]({"height": h})
        commit = got["block"]["last_commit"] if route == "block" else got
        assert commit["precommits"] == (0 if route == "block" and h == 1
                                        else 4)


def test_consensus_rebuilds_its_last_commit_from_a_decoded_seen_commit(
        stored_chain):
    """`ConsensusState._reconstruct_last_commit` asks the decoded seen
    commit for its votes: the same votes, in the same vote set, as from
    the commit that was built from them."""
    from tendermint_tpu.config import test_config
    from tendermint_tpu.consensus.state import ConsensusState
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    gen, chain, store = stored_chain
    old = cb._current
    cb.set_backend("python")
    try:
        sets = []
        for block_store in (store, _ObjectFormStore(store, chain)):
            state = get_state(MemDB(), gen)
            conns = ClientCreator("kvstore").new_app_conns()
            for block, ps, _seen in chain:
                execution.apply_block(state, None, conns.consensus, block,
                                      ps.header, execution.MockMempool(),
                                      check_last_commit=False)
            cs = ConsensusState(test_config().consensus, state,
                                conns.consensus, block_store,
                                Mempool(conns.mempool))
            sets.append(cs.last_commit)
    finally:
        cb._current = old
    from_wire, from_objects = sets
    assert from_wire.has_two_thirds_majority()
    assert from_wire.make_commit() == from_objects.make_commit() == \
        chain[3][2]
    assert from_wire.make_commit().encode() == chain[3][2].encode()
