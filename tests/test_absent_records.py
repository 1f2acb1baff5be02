"""What the flight recorder and the registry say of a commit that misses
precommits, of the lane builder a fast-sync window took, and of an RPC
request: a full commit and a window of them write nothing new but the
window's one instant; a commit that keeps its wire bytes through nil
entries writes one `commit.wire_absent` instant and its counts, and its
window is vectorised like any other; nil entries beside something
irregular (a precommit for the nil block) give `commit.object_form` the
word `absent`, one `commit.decode.votes` and their count, and take the
window to the per-block builder; a light client's call names no
builder; a handled request is one `rpc.request`."""

import json
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import pytest

from tendermint_tpu.types import BlockID, Commit, Vote, ZERO_BLOCK_ID
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.validator import (CommitFormatError,
                                            verify_commits_batched,
                                            window_commit_lanes)
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.metrics import REGISTRY
from tests.chainutil import build_chain, make_validators

CHAIN = "absent-records-test"
N_VALS, N_BLOCKS = 7, 6          # at most 2 of 7 may be silent
NEW = ("commit.object_form", "commit.decode.votes", "commit.wire_absent",
       "fastsync.lanes.vectorised", "fastsync.lanes.per_block",
       "rpc.request")
COUNTERS = ("commit_precommits_absent", "lane_windows_vectorised",
            "lane_windows_per_block", "commits_decoded_objects",
            "commits_decoded_wire", "commits_decoded_wire_absent")


def moved(f):
    """(what `f` returned or raised, the new records it wrote as (name,
    args, is a span) in order, how far each counter moved)."""
    t0 = tracing.now_epoch()
    before = [getattr(REGISTRY, c).value for c in COUNTERS]
    try:
        out = f()
    except Exception as e:
        out = e
    recs = [(s["name"], s.get("args"), s["ph"] == tracing.PH_SPAN)
            for s in tracing.RECORDER.since(t0)
            if s["name"] in NEW and s["ts"] >= t0]
    return out, recs, {c: getattr(REGISTRY, c).value - b
                       for c, b in zip(COUNTERS, before)}


@pytest.fixture(scope="module")
def chain():
    privs, vs = make_validators(N_VALS)
    return vs, build_chain(privs, vs, CHAIN, N_BLOCKS)


def pruned(seen: Commit, silent, for_nil=()) -> Commit:
    """`seen` with the entries at `silent` nil, and the votes at
    `for_nil` precommits for the nil block (records of another width,
    which no wire form holds: signatures are not verified here)."""
    return Commit(block_id=seen.block_id, precommits=[
        None if i in silent else
        Vote(**{**v.__dict__, "block_id": ZERO_BLOCK_ID}) if i in for_nil
        else v for i, v in enumerate(seen.precommits)])


def decoded(commit: Commit) -> Commit:
    return Commit.decode(Reader(commit.encode()))


def window(chain, silent_at=None, for_nil=()):
    """[(block id, height, decoded commit)] of the chain, the commit of
    `silent_at`'s height decoded with two nil entries (and the votes at
    `for_nil` for the nil block)."""
    _vs, blocks = chain
    return [(BlockID(block.hash(), ps.header), block.height,
             decoded(pruned(seen, {1, 4}, for_nil)
                     if block.height == silent_at else seen))
            for block, ps, seen in blocks]


@pytest.fixture()
def native_backend():
    from tendermint_tpu.crypto import backend as cb
    old = cb._current
    cb.set_backend("native")
    yield
    cb._current = old


# -- Commit.decode -------------------------------------------------------------

def test_a_full_commit_writes_none_of_the_records(chain):
    _vs, blocks = chain
    commit, recs, counts = moved(lambda: decoded(blocks[2][2]))
    assert commit.wire_backed() and commit.wire_columns() is not None
    assert recs == [] and counts.pop("commits_decoded_wire") == 1
    assert not any(counts.values())


@pytest.mark.parametrize("silent", [{3}, {0, 6}], ids=["one", "two"])
def test_a_nil_entry_is_one_wire_absent_instant_and_its_counts(chain, silent):
    _vs, blocks = chain
    height = blocks[2][0].height
    commit, recs, counts = moved(
        lambda: decoded(pruned(blocks[2][2], silent)))
    assert commit.wire_backed() and commit.wire_columns()[5] == \
        tuple(sorted(silent))
    assert commit.bit_array() == [i not in silent for i in range(N_VALS)]
    # no per-vote loop, so no span; the instant names the commit
    assert recs == [("commit.wire_absent",
                     {"height": height, "absent": len(silent)}, False)]
    assert counts == {"commit_precommits_absent": len(silent),
                      "commits_decoded_wire": 1,
                      "commits_decoded_wire_absent": 1,
                      "commits_decoded_objects": 0,
                      "lane_windows_vectorised": 0,
                      "lane_windows_per_block": 0}


@pytest.mark.parametrize("silent", [{3}, {0, 6}], ids=["one", "two"])
def test_nil_entries_beside_a_vote_for_nil_are_the_absent_object_form(
        chain, silent):
    """What no wire form holds (here a precommit for the nil block, a
    shorter record) decodes vote by vote as before, and the nil entries
    beside it give the instant its word."""
    _vs, blocks = chain
    height = blocks[2][0].height
    commit, recs, counts = moved(
        lambda: decoded(pruned(blocks[2][2], silent, for_nil={2})))
    assert not commit.wire_backed() and commit.wire_columns() is None
    assert commit.bit_array() == [i not in silent for i in range(N_VALS)]
    # the loop's span ends before the instant that names the commit
    assert recs == [("commit.decode.votes", None, True),
                    ("commit.object_form",
                     {"height": height, "reason": "absent"}, False)]
    assert counts == {"commit_precommits_absent": len(silent),
                      "commits_decoded_wire": 0,
                      "commits_decoded_wire_absent": 0,
                      "commits_decoded_objects": 1,
                      "lane_windows_vectorised": 0,
                      "lane_windows_per_block": 0}


def test_a_commit_built_from_votes_is_not_wire_backed(chain):
    _vs, blocks = chain
    assert not blocks[0][2].wire_backed()


# -- window_commit_lanes ---------------------------------------------------------

def test_a_window_of_wire_commits_says_vectorised(chain, native_backend):
    items = window(chain)
    out, recs, counts = moved(
        lambda: verify_commits_batched(chain[0], CHAIN, items))
    assert out is None
    assert recs == [("fastsync.lanes.vectorised",
                     {"blocks": N_BLOCKS, "object_commits": 0}, False)]
    assert counts["lane_windows_vectorised"] == 1
    assert counts["lane_windows_per_block"] == 0


def test_a_commit_with_nil_entries_leaves_its_window_vectorised(
        chain, native_backend):
    items, recs, counts = moved(lambda: window(chain, silent_at=4))
    assert counts["commit_precommits_absent"] == 2
    assert counts["commits_decoded_wire_absent"] == 1
    assert [r[0] for r in recs] == ["commit.wire_absent"]
    out, recs, counts = moved(
        lambda: verify_commits_batched(chain[0], CHAIN, items))
    assert out is None              # 5 of 7 hold more than 2/3
    assert recs == [("fastsync.lanes.vectorised",
                     {"blocks": N_BLOCKS, "object_commits": 0}, False)]
    assert counts["lane_windows_vectorised"] == 1
    assert counts["lane_windows_per_block"] == 0


def test_one_object_form_commit_takes_the_window_per_block(chain,
                                                           native_backend):
    """A precommit for the nil block beside the nil entries: verified,
    not tallied (4 of 7 is short of +2/3), and its window per block."""
    items, recs, counts = moved(
        lambda: window(chain, silent_at=4, for_nil={2}))
    assert counts["commit_precommits_absent"] == 2
    assert [r[0] for r in recs] == ["commit.decode.votes",
                                    "commit.object_form"]
    out, recs, counts = moved(
        lambda: verify_commits_batched(chain[0], CHAIN, items))
    # the vote for nil was never signed as such: its lane fails first
    assert isinstance(out, ValueError) and out.height == 4
    assert recs == [("fastsync.lanes.per_block",
                     {"blocks": N_BLOCKS, "object_commits": 1}, False)]
    assert counts["lane_windows_per_block"] == 1
    assert counts["lane_windows_vectorised"] == 0


def test_a_wire_commit_a_check_refuses_is_per_block_with_no_object_commit(
        chain, native_backend):
    """The window leaves the vectorised pass for a wire-backed commit
    too (here a height that is not the commit's): `object_commits` says
    that no commit of it was decoded vote by vote."""
    items = window(chain)
    bid, h, c = items[3]
    items[3] = (bid, h + 50, c)
    out, recs, counts = moved(
        lambda: verify_commits_batched(chain[0], CHAIN, items))
    assert isinstance(out, CommitFormatError) and out.height == h + 50
    assert recs == [
        ("fastsync.lanes.per_block",
         {"blocks": N_BLOCKS, "object_commits": 0}, False),
        ("commit.object_form", {"height": h + 50, "reason": "height"},
         False)]
    assert counts["lane_windows_per_block"] == 1


@pytest.mark.parametrize("silent_at,for_nil", [(None, ()), (4, ()),
                                               (4, {2})],
                         ids=["wire", "wire-absent", "object"])
def test_a_light_clients_call_and_a_bare_builder_name_no_builder(
        chain, native_backend, silent_at, for_nil):
    items = window(chain, silent_at, for_nil)
    for call in (lambda: verify_commits_batched(chain[0], CHAIN, items,
                                                producer="light"),
                 lambda: window_commit_lanes(chain[0], CHAIN, items)):
        _out, recs, counts = moved(call)
        assert recs == []
        assert counts["lane_windows_vectorised"] == 0
        assert counts["lane_windows_per_block"] == 0


# -- the RPC ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def rpc(chain):
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.rpc.server import RPCServer
    from tendermint_tpu.utils.db import MemDB
    store = BlockStore(MemDB())
    for block, ps, seen in chain[1]:
        store.save_block(block, ps, seen)
    node = SimpleNamespace(
        block_store=store,
        config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False)))
    server = RPCServer(node, SimpleNamespace(laddr="tcp://127.0.0.1:0"))
    server.start()
    yield server
    server.stop()


def _get(url: str):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _answered(call, expect: int = 1):
    """`call`, then a wait for the handler thread, which writes its
    record after the answer the client has already read."""
    total = tracing.RECORDER.total
    out = call()
    deadline = time.monotonic() + (2.0 if expect else 0.1)
    while (tracing.RECORDER.total < total + expect
           or not expect) and time.monotonic() < deadline:
        time.sleep(0.005)
    return out


def test_a_handled_request_is_one_rpc_request_with_its_method(rpc):
    from tendermint_tpu.rpc.client import HTTPClient
    (code, got), recs, _ = moved(
        lambda: _answered(lambda: _get(rpc.addr + "/block?height=3")))
    assert code == 200 and got["result"]["block"]["header"]["height"] == 3
    assert recs == [("rpc.request", {"method": "block"}, True)]
    got, recs, _ = moved(
        lambda: _answered(lambda: HTTPClient(rpc.addr).commit(height=2)))
    assert got["precommits"] == N_VALS
    assert recs == [("rpc.request", {"method": "commit"}, True)]
    # a route that raises has answered all the same: one record
    (code, got), recs, _ = moved(
        lambda: _answered(lambda: _get(rpc.addr + "/block?height=99")))
    assert code == 500 and "no block" in got["error"]["message"]
    assert recs == [("rpc.request", {"method": "block"}, True)]


@pytest.mark.parametrize("path", ["/no_such_route", "/metrics", "/"])
def test_what_no_route_handles_writes_no_rpc_request(rpc, path):
    def fetch():
        try:
            with urllib.request.urlopen(rpc.addr + path, timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code
    code, recs, _ = moved(lambda: _answered(fetch, expect=0))
    assert code == (404 if path == "/no_such_route" else 200)
    assert recs == []
