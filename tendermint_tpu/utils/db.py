"""Key-value store abstraction: memdb and a durable sqlite backend.

Reference: tmlibs/db (goleveldb / memdb, selected by `DBBackend`,
`config/config.go:102,121`).  sqlite3 is the stdlib-native durable engine
here — single-writer, WAL-journaled, crash-safe, zero install — used for
the block store, state store, and tx index.
"""

from __future__ import annotations

import sqlite3
import threading
import time

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


class SQLiteDB:
    """Durable store: one `kv` table, WAL mode, synchronous=NORMAL."""

    def __init__(self, path: str):
        self.path = path
        self._local = threading.local()
        conn = self._conn()
        conn.execute("CREATE TABLE IF NOT EXISTS kv "
                     "(k BLOB PRIMARY KEY, v BLOB NOT NULL)")
        conn.execute("PRAGMA journal_mode=WAL")
        conn.execute("PRAGMA synchronous=NORMAL")
        conn.commit()

    def _conn(self) -> sqlite3.Connection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = sqlite3.connect(self.path)
            self._local.conn = conn
        return conn

    def get(self, key: bytes) -> bytes | None:
        row = self._conn().execute("SELECT v FROM kv WHERE k=?",
                                   (key,)).fetchone()
        return row[0] if row else None

    def set(self, key: bytes, value: bytes) -> None:
        conn = self._conn()
        t0 = time.perf_counter()
        conn.execute("INSERT OR REPLACE INTO kv VALUES (?,?)", (key, value))
        conn.commit()
        _wrote(t0)

    def set_batch(self, kvs: list[tuple[bytes, bytes]]) -> None:
        conn = self._conn()
        t0 = time.perf_counter()
        conn.executemany("INSERT OR REPLACE INTO kv VALUES (?,?)", kvs)
        conn.commit()
        _wrote(t0)

    def delete(self, key: bytes) -> None:
        conn = self._conn()
        t0 = time.perf_counter()
        conn.execute("DELETE FROM kv WHERE k=?", (key,))
        conn.commit()
        _wrote(t0)

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


def _wrote(t0: float) -> None:
    """One `db.write` flight-recorder record around a transaction
    (execute + commit): what a store's caller spent in sqlite, so that
    its own encoding and hashing is the rest of its span.  Bookkeeping,
    so outside the attribution partition (CAT_NONE); MemDB has none."""
    RECORDER.record("db.write", perf_to_epoch(t0), time.perf_counter() - t0,
                    None, cat=CAT_NONE)


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
