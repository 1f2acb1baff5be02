"""Key-value store abstraction: memdb and a durable sqlite backend.

Reference: tmlibs/db (goleveldb / memdb, selected by `DBBackend`,
`config/config.go:102,121`).  sqlite3 is the stdlib-native durable engine
here — single-writer, WAL-journaled, crash-safe, zero install — used for
the block store, state store, and tx index.
"""

from __future__ import annotations

import functools
import sqlite3
import threading
import time
from itertools import chain

from tendermint_tpu.utils.threadledger import tally_write, write_begins
from tendermint_tpu.utils.tracing import CAT_NONE, RECORDER, perf_to_epoch


class MemDB:
    """In-memory store (reference memdb): tests and throwaway nodes."""

    def __init__(self):
        self._d: dict[bytes, bytes] = {}
        self._lock = threading.Lock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            return self._d.get(key)

    def set(self, key: bytes, value: bytes) -> None:
        with self._lock:
            self._d[key] = value

    def set_batch(self, kvs: list[tuple[bytes, bytes]]) -> None:
        with self._lock:
            self._d.update(kvs)

    def delete(self, key: bytes) -> None:
        with self._lock:
            self._d.pop(key, None)

    def iterate_prefix(self, prefix: bytes):
        with self._lock:
            items = [(k, v) for k, v in self._d.items()
                     if k.startswith(prefix)]
        return sorted(items)

    def close(self) -> None:
        pass


# Rows one INSERT statement takes: 2 bound variables a row, well under
# the 999 a statement may bind in sqlite before 3.32 (32,766 since).
_ROWS_A_STATEMENT = 400


@functools.cache
def _insert_sql(rows: int) -> str:
    """The `INSERT OR REPLACE` of `rows` rows: one text a row count (at
    most `_ROWS_A_STATEMENT` of them), so that each connection's
    statement cache keeps the ones in use prepared."""
    return "INSERT OR REPLACE INTO kv VALUES " + ",".join(["(?,?)"] * rows)


def _insert(conn: sqlite3.Connection, kvs) -> None:
    conn.execute(_insert_sql(len(kvs)), tuple(chain.from_iterable(kvs)))


class SQLiteDB:
    """Durable store: one `kv` table in WAL mode, a connection a thread,
    every connection in sqlite's autocommit.

    A write is one statement in one transaction: `set`, `delete` and a
    `set_batch` that fits one statement are a single `execute`, which
    begins, writes and commits inside its own `sqlite3_step` (one
    release of the GIL); nobody calls `commit()`.  A write is durable
    and visible to every other connection when the call returns, and a
    batch is atomic whatever its size.

    `synchronous` belongs to a connection, not to the file: NORMAL on
    the connection of the thread that creates the store, sqlite's
    default FULL (the WAL synced at every commit) on every other
    thread's, the fast-sync thread's among them.  PERF.md section 4;
    tests/test_db.py holds both."""

    def __init__(self, path: str):
        self.path = path
        self._local = threading.local()
        conn = self._conn()
        conn.execute("CREATE TABLE IF NOT EXISTS kv "
                     "(k BLOB PRIMARY KEY, v BLOB NOT NULL)")
        conn.execute("PRAGMA journal_mode=WAL")
        conn.execute("PRAGMA synchronous=NORMAL")

    def _conn(self) -> sqlite3.Connection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = sqlite3.connect(self.path, isolation_level=None)
            self._local.conn = conn
        return conn

    def get(self, key: bytes) -> bytes | None:
        row = self._conn().execute("SELECT v FROM kv WHERE k=?",
                                   (key,)).fetchone()
        return row[0] if row else None

    def set(self, key: bytes, value: bytes) -> None:
        conn = self._conn()
        t0, cpu0 = time.perf_counter(), write_begins()
        conn.execute(_insert_sql(1), (key, value))
        _wrote(t0, cpu0)

    def set_batch(self, kvs: list[tuple[bytes, bytes]]) -> None:
        """All of `kvs` or none of it, in order (the last write of a
        repeated key wins); an empty batch is no transaction.  One
        statement where the rows fit one, else statements of
        `_ROWS_A_STATEMENT` rows inside one explicit transaction."""
        if not kvs:
            return
        conn = self._conn()
        t0, cpu0 = time.perf_counter(), write_begins()
        if len(kvs) <= _ROWS_A_STATEMENT:
            _insert(conn, kvs)
        else:
            conn.execute("BEGIN IMMEDIATE")
            try:
                for i in range(0, len(kvs), _ROWS_A_STATEMENT):
                    _insert(conn, kvs[i:i + _ROWS_A_STATEMENT])
                conn.execute("COMMIT")
            except BaseException:
                if conn.in_transaction:   # some errors roll back themselves
                    conn.execute("ROLLBACK")
                raise
        _wrote(t0, cpu0)

    def delete(self, key: bytes) -> None:
        conn = self._conn()
        t0, cpu0 = time.perf_counter(), write_begins()
        conn.execute("DELETE FROM kv WHERE k=?", (key,))
        _wrote(t0, cpu0)

    def iterate_prefix(self, prefix: bytes):
        hi = _prefix_upper_bound(prefix)
        if hi is None:   # prefix is all 0xff (or empty): no upper bound
            return self._conn().execute(
                "SELECT k, v FROM kv WHERE k >= ? ORDER BY k",
                (prefix,)).fetchall()
        return self._conn().execute(
            "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k",
            (prefix, hi)).fetchall()

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            conn.close()
            self._local.conn = None


def _wrote(t0: float, cpu0: float | None) -> None:
    """One `db.write` flight-recorder record around a transaction (the
    one statement, or BEGIN .. COMMIT of a chunked batch): what a
    store's caller spent in sqlite, so that its own encoding and hashing
    is the rest of its span.  Bookkeeping, so outside the attribution
    partition (CAT_NONE); MemDB has none.  The same seconds go to the
    thread ledger's tally, which splits them by the thread's CPU clock
    (`cpu0`, read at the start of the transactions it samples): on the
    CPU (sqlite and the kernel) and off it (the WAL's sync, and the wait
    to get the GIL back)."""
    wall = time.perf_counter() - t0
    RECORDER.record("db.write", perf_to_epoch(t0), wall, None, cat=CAT_NONE)
    tally_write(wall, cpu0)


def _prefix_upper_bound(prefix: bytes) -> bytes | None:
    """Smallest byte string greater than every key with this prefix."""
    p = bytearray(prefix)
    while p and p[-1] == 0xFF:
        p.pop()
    if not p:
        return None
    p[-1] += 1
    return bytes(p)


def new_db(backend: str, path: str | None = None):
    """Factory (reference `config/config.go:102` DBBackend)."""
    if backend == "memdb":
        return MemDB()
    if backend == "sqlite":
        assert path, "sqlite backend needs a path"
        return SQLiteDB(path)
    raise ValueError(f"unknown db backend {backend!r}")
