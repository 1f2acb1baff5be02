"""The stores' write path.  `SQLiteDB` reaches sqlite as one statement
in one transaction a write (autocommit; no caller commits), a batch is
atomic whatever its size, a write is there for every other connection
when the call returns, and each write is one `db.write` record.  What
a reader can see of it holds for `MemDB` too.  The last test pins each
connection's `synchronous` level, which is part of what the
benchmark's cells cost (PERF.md section 4)."""

import sqlite3
import threading

import pytest

from tendermint_tpu.utils import db as dbmod
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.db import MemDB, SQLiteDB

CHUNKED = 2 * dbmod._ROWS_A_STATEMENT + 7    # three statements, one ragged
JOIN_S = 20


@pytest.fixture(params=["sqlite", "memdb"])
def db(request, tmp_path):
    d = (SQLiteDB(str(tmp_path / "kv.db")) if request.param == "sqlite"
         else MemDB())
    yield d
    d.close()


@pytest.fixture()
def sdb(tmp_path):
    d = SQLiteDB(str(tmp_path / "kv.db"))
    yield d
    d.close()


def _on_thread(fn):
    """Run `fn` on a new thread (for SQLiteDB: on a connection of its
    own) and return what it returned."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()))
    t.start()
    t.join(JOIN_S)
    assert not t.is_alive()
    (res,) = out
    return res


def _elsewhere(db):
    """Every row as another thread's connection sees it now; for sqlite
    also as a connection that this module opened itself sees it."""
    rows = _on_thread(lambda: db.iterate_prefix(b""))
    if isinstance(db, SQLiteDB):
        def raw():
            conn = sqlite3.connect(db.path)
            try:
                return conn.execute(
                    "SELECT k, v FROM kv ORDER BY k").fetchall()
            finally:
                conn.close()
        assert _on_thread(raw) == rows
    return rows


def _rows(n):
    return [(b"k:%04d" % i, b"v%d" % i) for i in range(n)]


def _write(db, op):
    """One write of kind `op` over `_rows(50)`; returns what the row
    `k:0001` has to read afterwards."""
    if op == "set":
        db.set(b"k:0001", b"written")
    elif op == "set_batch":
        db.set_batch([(b"k:0001", b"written"), (b"z", b"1")])
    elif op == "chunked":
        db.set_batch([(b"k:0001", b"written")] + _rows(CHUNKED)[2:])
    else:
        db.delete(b"k:0001")
        return None
    return b"written"


OPS = ["set", "set_batch", "chunked", "delete"]


@pytest.mark.parametrize("op", OPS)
def test_write_is_visible_elsewhere_when_the_call_returns(db, op):
    db.set_batch(_rows(50))
    before = dict(_elsewhere(db))
    assert before == dict(_rows(50))
    want = _write(db, op)
    after = dict(_elsewhere(db))
    assert after.get(b"k:0001") == want
    assert _on_thread(lambda: db.get(b"k:0001")) == want
    # and nothing else moved but what the write named
    named = {"set": 0, "set_batch": 1, "chunked": CHUNKED - 50,
             "delete": -1}[op]
    assert len(after) == 50 + named
    assert all(after[k] == v for k, v in before.items()
               if k != b"k:0001")
    db.delete(b"never-there")
    assert dict(_elsewhere(db)) == after


@pytest.mark.parametrize("n", [0, 1, 6, CHUNKED])
def test_set_batch_lands_whole_and_the_last_write_of_a_key_wins(db, n):
    kvs = _rows(n)
    if n:
        # the first key once more at the end (in a chunked batch: in
        # another statement than its first write)
        kvs.append((kvs[0][0], b"again"))
    db.set_batch(kvs)
    want = dict(_rows(n))
    if n:
        want[kvs[0][0]] = b"again"
    assert _elsewhere(db) == sorted(want.items())
    # a later batch replaces rows of an earlier one
    db.set_batch([(k, b"new") for k, _ in kvs[:2]])
    want.update((k, b"new") for k, _ in kvs[:2])
    assert _elsewhere(db) == sorted(want.items())


@pytest.mark.parametrize("n", [6, CHUNKED])
def test_set_batch_with_an_invalid_last_row_leaves_none_of_its_rows(sdb, n):
    sdb.set(b"k:0000", b"before")
    kvs = _rows(n)
    kvs[-1] = (kvs[-1][0], None)
    with pytest.raises(sqlite3.IntegrityError):
        sdb.set_batch(kvs)
    assert _elsewhere(sdb) == [(b"k:0000", b"before")]
    # nothing is left open on the writer's connection, and it writes on
    assert not sdb._conn().in_transaction
    sdb.set_batch(_rows(2))
    assert _elsewhere(sdb) == _rows(2)


@pytest.mark.parametrize("op", OPS)
def test_write_succeeds_while_another_thread_holds_a_read_cursor(sdb, op):
    """RPC threads read while the fast-sync thread writes: under WAL a
    reader never blocks the writer, and keeps its snapshot."""
    sdb.set_batch(_rows(50))
    holding, release = threading.Event(), threading.Event()
    seen = []

    def reader():
        cur = sdb._conn().execute("SELECT k, v FROM kv ORDER BY k")
        seen.append(cur.fetchone())        # the read transaction is open
        holding.set()
        release.wait(JOIN_S)
        seen.extend(cur.fetchall())        # its snapshot, to the end

    t = threading.Thread(target=reader)
    t.start()
    try:
        assert holding.wait(JOIN_S)
        want = _write(sdb, op)
        got = sdb.get(b"k:0001")
    finally:
        release.set()
        t.join(JOIN_S)
    assert not t.is_alive()
    assert got == want
    assert seen == _rows(50)               # the reader kept its snapshot
    assert _on_thread(lambda: sdb.get(b"k:0001")) == want


@pytest.mark.parametrize("op, records", [
    ("set", 1), ("set_batch", 1), ("chunked", 1), ("delete", 1),
    ("empty", 0)])
def test_each_write_records_one_db_write(sdb, op, records):
    t_start = tracing.now_epoch()
    if op == "empty":
        sdb.set_batch([])
    else:
        _write(sdb, op)
    me = threading.current_thread().ident
    writes = [s for s in tracing.RECORDER.since(t_start)
              if s["name"] == "db.write" and s["ts"] >= t_start and
              s["tid"] == me]
    assert len(writes) == records
    assert all("cat" not in w and "args" not in w for w in writes)
    assert not sdb._conn().in_transaction


@pytest.mark.parametrize("thread, level", [("creating", 1), ("second", 2)])
def test_synchronous_level_of_each_connection_is_the_parents(sdb, thread,
                                                             level):
    """NORMAL (1) on the creating thread's connection, sqlite's default
    FULL (2) on every other thread's: every commit of the fast-sync
    thread syncs the WAL.  A speed-up may not come from here."""
    def read():
        conn = sdb._conn()
        return (conn.execute("PRAGMA synchronous").fetchone()[0],
                conn.execute("PRAGMA journal_mode").fetchone()[0],
                conn.execute("PRAGMA wal_autocheckpoint").fetchone()[0])
    run = (lambda fn: fn()) if thread == "creating" else _on_thread
    got = run(read)
    assert got == (level, "wal", 1000)

    def write_then_read():
        sdb.set_batch(_rows(CHUNKED))
        return read()
    assert run(write_then_read) == got     # a write leaves them there
