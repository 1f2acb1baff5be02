"""`execution.apply_window` must be `apply_block` unrolled: same final
app hash and state, same per-block hook order, one state save a block
and byte-identical persisted state — the license for the reactor to
amortize the app lock across a fast-sync window."""

import sqlite3
import threading

import pytest

from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.state import execution
from tendermint_tpu.state.state import get_state
from tendermint_tpu.utils.db import MemDB
from tests.chainutil import (build_chain, kvstore_app_hashes,
                             make_genesis, make_validators)

CHAIN = "apply-window-test"
N = 6


@pytest.fixture()
def fixture():
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    chain = build_chain(privs, vs, CHAIN, N,
                        app_hashes=kvstore_app_hashes(N))
    return gen, chain


def _fresh(gen):
    db = MemDB()
    state = get_state(db, gen)
    conns = ClientCreator("kvstore").new_app_conns()
    return db, state, conns


@pytest.fixture(scope="module")
def long_chain():
    """One full reactor window (64 blocks) of the 4-validator chain."""
    privs, vs = make_validators(4)
    return make_genesis(CHAIN, privs), build_chain(
        privs, vs, CHAIN, 64, app_hashes=kvstore_app_hashes(64))


def _run(gen, chain, new_db, windowed):
    """Apply `chain` per block or as one window, the block store's hook
    before each block as the reactor has it; returns (state, state db,
    the order in which the hooks and the saves were seen)."""
    db = new_db("w" if windowed else "r")
    state = get_state(db, gen)
    conns = ClientCreator("kvstore").new_app_conns()
    order = []
    save = state.save
    state.save = lambda: (order.append(("save", state.last_block_height)),
                          save())[1]
    if windowed:
        assert execution.apply_window(
            state, None, conns.consensus,
            [(b, ps.header) for b, ps, _ in chain], execution.MockMempool(),
            before_block=lambda b, _psh: order.append(("before", b.height)),
            on_applied=lambda b: order.append(("applied", b.height))
        ) == len(chain)
    else:
        for b, ps, _seen in chain:
            order.append(("before", b.height))
            execution.apply_block(state, None, conns.consensus, b,
                                  ps.header, execution.MockMempool(),
                                  check_last_commit=False)
            order.append(("applied", b.height))
    return state, db, order


@pytest.mark.parametrize("db", ["memdb", "sqlite"])
@pytest.mark.parametrize("blocks", [1, 2, 64])
def test_apply_window_matches_per_block(long_chain, tmp_path, blocks, db):
    """A window of any length is `apply_block` a block: same app hash,
    same state, the same rows in the state db, and one save a block
    between that block's two hooks."""
    from tendermint_tpu.utils.db import SQLiteDB
    gen, chain = long_chain
    chain = chain[:blocks]

    def new_db(name):
        return (MemDB() if db == "memdb"
                else SQLiteDB(str(tmp_path / f"{name}.db")))

    ref_state, ref_db, ref_order = _run(gen, chain, new_db, windowed=False)
    state, got_db, order = _run(gen, chain, new_db, windowed=True)
    assert state.last_block_height == blocks
    assert state.app_hash == ref_state.app_hash
    assert state.encode() == ref_state.encode()
    assert got_db.iterate_prefix(b"") == ref_db.iterate_prefix(b"")
    assert len(got_db.iterate_prefix(b"")) > 2 * blocks
    assert order == ref_order == [
        (what, h) for h in range(1, blocks + 1)
        for what in ("before", "save", "applied")]


def test_apply_window_hooks_and_early_stop(fixture):
    gen, chain = fixture
    db, state, conns = _fresh(gen)
    before, applied_blocks = [], []
    n = execution.apply_window(
        state, None, conns.consensus,
        [(b, ps.header) for b, ps, _ in chain],
        execution.MockMempool(),
        before_block=lambda b, psh: before.append(b.height),
        on_applied=lambda b: applied_blocks.append(b.height),
        stop_when=lambda: len(applied_blocks) >= 3)
    assert n == 3
    assert before == [1, 2, 3]
    assert applied_blocks == [1, 2, 3]
    assert state.last_block_height == 3
    # stopping early leaves state saved at height 3
    from tendermint_tpu.state.state import State
    assert State.decode_bytes(db._d[b"stateKey"]).last_block_height == 3


def test_apply_window_empty():
    privs, vs = make_validators(4)
    gen = make_genesis(CHAIN, privs)
    db, state, conns = _fresh(gen)
    before = dict(db._d)
    assert execution.apply_window(
        state, None, conns.consensus, [],
        execution.MockMempool()) == 0
    # no spurious save of the untouched state
    assert db._d == before


def test_apply_window_validation_failure_keeps_prefix(fixture):
    gen, chain = fixture
    db, state, conns = _fresh(gen)
    items = [(b, ps.header) for b, ps, _ in chain]
    items[3] = (chain[4][0], chain[4][1].header)   # wrong height at slot 3
    with pytest.raises(ValueError, match="wrong height"):
        execution.apply_window(state, None, conns.consensus, items,
                               execution.MockMempool())
    # blocks before the bad one are applied and saved
    assert state.last_block_height == 3


# -- the stage records (flight recorder) -------------------------------------

STAGES = ["store_save", "validate", "abci_exec", "save_responses",
          "update_state", "abci_commit", "state_save", "advance"]
# sqlite transactions a block makes, by the stage that makes them
WRITES = {"store_save": 1, "save_responses": 1, "state_save": 1}
CLOCK = 2e-6       # an epoch timestamp holds a quarter of a microsecond


def _sqlite_apply(tmp_path, gen, chain, name, windowed, state_db=None,
                  conns=None):
    """Apply the first 3 blocks on sqlite stores, store saved before
    state as the reactor does; returns (state, state db, store db)."""
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.utils.db import SQLiteDB
    sdb = (state_db or SQLiteDB)(str(tmp_path / f"{name}-state.db"))
    bdb = SQLiteDB(str(tmp_path / f"{name}-blocks.db"))
    state = get_state(sdb, gen)
    store = BlockStore(bdb)
    conns = conns or ClientCreator("kvstore").new_app_conns()
    seen = {b.height: c for b, _ps, c in chain}
    parts = {b.height: ps for b, ps, _c in chain}
    if windowed:
        n = execution.apply_window(
            state, None, conns.consensus,
            [(b, ps.header) for b, ps, _ in chain[:3]],
            execution.MockMempool(),
            before_block=lambda b, _psh: store.save_block(
                b, parts[b.height], seen[b.height]))
        assert n == 3
    else:
        for b, ps, c in chain[:3]:
            store.save_block(b, ps, c)
            execution.apply_block(state, None, conns.consensus, b, ps.header,
                                  execution.MockMempool(),
                                  check_last_commit=False)
    return state, sdb, bdb


def test_apply_window_records_eight_contiguous_stages_a_block(fixture,
                                                              tmp_path):
    from tendermint_tpu.utils import tracing
    gen, chain = fixture
    t_start = tracing.now_epoch()
    state, sdb, bdb = _sqlite_apply(tmp_path, gen, chain, "w", windowed=True)
    me = [s for s in tracing.RECORDER.since(t_start)
          if s["ts"] >= t_start and
          s["tid"] == threading.current_thread().ident]
    stages = [s for s in me if s["name"].startswith("fastsync.apply.")]
    # each of the 8 once a block, in loop order, bare: no args, and no
    # category of their own (they nest under the reactor's apply span)
    assert [s["name"] for s in stages] == \
        [f"fastsync.apply.{st}" for st in STAGES] * 3
    assert all("args" not in s and "cat" not in s for s in stages)
    # contiguous: each starts where the one before it ended, so nothing
    # of a block is outside a stage
    for a, b in zip(stages, stages[1:]):
        assert abs(a["ts"] + a["dur"] - b["ts"]) < CLOCK, (a, b)
    # every sqlite transaction is a db.write inside its store's stage
    # (those before the first stage are the genesis state's)
    writes = [s for s in me if s["name"] == "db.write" and
              s["ts"] >= stages[0]["ts"] - CLOCK]
    inside = {st: 0 for st in STAGES}
    for w in writes:
        (host,) = [s for s in stages if s["ts"] - CLOCK <= w["ts"] and
                   w["ts"] + w["dur"] <= s["ts"] + s["dur"] + CLOCK]
        inside[host["name"].rsplit(".", 1)[1]] += 1
    assert inside == {st: 3 * WRITES.get(st, 0) for st in STAGES}
    assert all("cat" not in w and "args" not in w for w in writes)

    # and the records change nothing: the live path's apply_block, which
    # has none, leaves the same state, app hash and stored bytes
    ref_state, ref_sdb, ref_bdb = _sqlite_apply(tmp_path, gen, chain, "r",
                                                windowed=False)
    assert state.app_hash == ref_state.app_hash
    assert state.encode() == ref_state.encode()
    assert sdb.iterate_prefix(b"") == ref_sdb.iterate_prefix(b"")
    assert bdb.iterate_prefix(b"") == ref_bdb.iterate_prefix(b"")
    assert len(bdb.iterate_prefix(b"")) > 3 * 4


def test_apply_window_makes_three_transactions_a_block(fixture, tmp_path):
    """Block store, ABCI responses, state: on sqlite stores a block
    is three `db.write`, in that order in time
    (the responses are durable before the app commits, so they are not
    in the state's transaction)."""
    from tendermint_tpu.utils import tracing
    gen, chain = fixture
    t_start = tracing.now_epoch()
    _sqlite_apply(tmp_path, gen, chain, "t", windowed=True)
    me = [s for s in tracing.RECORDER.since(t_start)
          if s["ts"] >= t_start and
          s["tid"] == threading.current_thread().ident]
    starts = [s["ts"] for s in me
              if s["name"] == "fastsync.apply.store_save"]
    commits = [s["ts"] for s in me
               if s["name"] == "fastsync.apply.abci_commit"]
    assert len(starts) == len(commits) == 3
    writes = [s["ts"] for s in me if s["name"] == "db.write" and
              s["ts"] >= starts[0] - CLOCK]
    assert len(writes) == 3 * 3
    for i, (t0, t_commit) in enumerate(zip(starts, commits)):
        t1 = starts[i + 1] if i + 1 < len(starts) else float("inf")
        block = [w for w in writes if t0 - CLOCK <= w < t1 - CLOCK]
        assert len(block) == 3
        # two before the app commits (block, responses), the state after
        assert [w < t_commit for w in block] == [True, True, False]


def test_state_save_killed_between_its_rows_leaves_the_old_height(fixture,
                                                                  tmp_path):
    """`State.save` is one transaction.  A death after its first row
    (the state) and before its second (the next validators record)
    leaves both records of the height before, never the state without
    its validators record; the handshake then recovers from store =
    state + 1 with the app already committed."""
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.utils.db import SQLiteDB
    gen, chain = fixture

    class Killed(Exception):
        pass

    class DiesInThirdSave(SQLiteDB):
        """The state db of a node that dies inside the save of height
        3, after the state row and before `validatorsKey:4`: the batch
        that ends in that key reaches sqlite with every row after its
        first invalid, so that sqlite itself stops between the rows of
        the one statement."""

        def set_batch(self, kvs):
            if kvs[-1][0] != b"validatorsKey:4":
                return super().set_batch(kvs)
            try:
                super().set_batch(kvs[:1] + [(k, None) for k, _ in kvs[1:]])
            except sqlite3.IntegrityError as e:
                raise Killed(kvs[-1][0]) from e

    conns = ClientCreator("kvstore").new_app_conns()
    with pytest.raises(Killed):
        _sqlite_apply(tmp_path, gen, chain, "k", windowed=True,
                      state_db=DiesInThirdSave, conns=conns)

    # the restart: both dbs opened anew, the app as the crash left it
    sdb = SQLiteDB(str(tmp_path / "k-state.db"))
    store = BlockStore(SQLiteDB(str(tmp_path / "k-blocks.db")))
    state = get_state(sdb, gen)
    assert state.last_block_height == 2
    assert state.load_validators(3) is not None
    assert sdb.get(b"validatorsKey:4") is None
    assert state.load_abci_responses(3) is not None
    assert store.height == 3
    assert conns.query.info().last_block_height == 3
    # the handshake checks block 3's last commit: on the host, as
    # tests/test_replay.py does (the default backend compiles for it)
    old = cb._current
    cb.set_backend("python")
    try:
        Handshaker(state, store).handshake(conns)
    finally:
        cb._current = old
    assert state.last_block_height == 3
    ref_state, ref_sdb, _ = _sqlite_apply(tmp_path, gen, chain, "kr",
                                          windowed=False)
    assert state.encode() == ref_state.encode()
    assert sdb.iterate_prefix(b"") == ref_sdb.iterate_prefix(b"")


def test_memdb_records_no_db_write(fixture):
    from tendermint_tpu.utils import tracing
    gen, chain = fixture
    t_start = tracing.now_epoch()
    db, state, conns = _fresh(gen)
    execution.apply_window(state, None, conns.consensus,
                           [(b, ps.header) for b, ps, _ in chain],
                           execution.MockMempool())
    names = [s["name"] for s in tracing.RECORDER.since(t_start)
             if s["ts"] >= t_start]
    assert "db.write" not in names
    assert names.count("fastsync.apply.state_save") == N
