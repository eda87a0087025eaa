"""Durable KV over sqlite3 (the RocksDBStore stand-in).

Same KeyValueDB contract; WAL-mode sqlite gives atomic batched writes
and ordered iteration.  Used by MonitorDBStore and file-store omap.

Every statement handed to sqlite is a call that gives the interpreter
up, and on a host whose daemons share one interpreter each hands it
round.  So a transaction goes to sqlite as one statement a run of like
ops, and a transaction that is one statement is its own sqlite
transaction (the connection is in autocommit mode): no BEGIN, no
COMMIT.  `calls` counts the statements.
"""

from __future__ import annotations

import sqlite3
import threading
from typing import Iterator

from .keyvaluedb import KeyValueDB, KVTransaction

_INSERT = "INSERT OR REPLACE INTO kv VALUES "
# an index search a key: `WHERE (prefix, key) IN (VALUES ...)` with more
# than one row scans the whole table (EXPLAIN QUERY PLAN, sqlite 3.40)
_DELETE = ("DELETE FROM kv WHERE rowid IN (SELECT kv.rowid FROM (VALUES %s)"
           " AS v CROSS JOIN kv ON kv.prefix=v.column1 AND kv.key=v.column2)")


class SqliteDB(KeyValueDB):
    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._conn: sqlite3.Connection | None = None
        self._full = True       # the connection's synchronous mode
        self._max_vars = 999    # bound parameters one statement takes
        self.calls = 0          # statements handed to sqlite

    def open(self) -> None:
        if getattr(self, "_conn", None) is not None:
            self._conn.close()     # mkfs-then-mount must not leak one
        self._conn = sqlite3.connect(self.path, check_same_thread=False,
                                     isolation_level=None)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute("PRAGMA synchronous=FULL")
        self._full = True
        self._max_vars = self._conn.getlimit(
            sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER)
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS kv ("
            " prefix TEXT NOT NULL, key TEXT NOT NULL, value BLOB,"
            " PRIMARY KEY (prefix, key))")

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def _statements(self, ops: list[tuple]) -> list[tuple[str, list]]:
        """The transaction as (sql, parameters): consecutive sets are
        one multi-row INSERT OR REPLACE (rows in op order, so a later
        row for a key wins), consecutive rms one DELETE, a run longer
        than one statement may bind is cut; runs keep the ops' order."""
        out: list[tuple[str, list]] = []
        i = 0
        while i < len(ops):
            op = ops[i][0]
            if op == "rm_prefix":
                out.append(("DELETE FROM kv WHERE prefix=?", [ops[i][1]]))
                i += 1
                continue
            width = 3 if op == "set" else 2
            j = i
            while j < len(ops) and ops[j][0] == op and \
                    (j - i + 1) * width <= self._max_vars:
                j += 1
            params: list = []
            for row in ops[i:j]:
                params.extend(row[1: 1 + width])
            if op == "set":
                sql = _INSERT + ",".join(["(?,?,?)"] * (j - i))
            else:
                sql = _DELETE % ",".join(["(?,?)"] * (j - i))
            out.append((sql, params))
            i = j
        return out

    def submit_transaction(self, txn: KVTransaction,
                           sync: bool = False) -> None:
        with self._lock:
            stmts = self._statements(txn.ops)
            if not stmts:
                return
            if sync != self._full:
                self._conn.execute("PRAGMA synchronous=" +
                                   ("FULL" if sync else "NORMAL"))
                self._full = sync
                self.calls += 1
            if len(stmts) == 1:     # its own sqlite transaction
                self.calls += 1
                self._conn.execute(*stmts[0])
                return
            self.calls += len(stmts) + 2
            self._conn.execute("BEGIN")
            try:
                for sql, params in stmts:
                    self._conn.execute(sql, params)
            except BaseException:
                self._conn.execute("ROLLBACK")
                raise
            self._conn.execute("COMMIT")

    def get(self, prefix: str, key: str) -> bytes | None:
        with self._lock:
            self.calls += 1
            row = self._conn.execute(
                "SELECT value FROM kv WHERE prefix=? AND key=?",
                (prefix, key)).fetchone()
        return bytes(row[0]) if row else None

    def prefixes(self) -> list[str]:
        with self._lock:
            rows = self._conn.execute(
                "SELECT DISTINCT prefix FROM kv").fetchall()
        return [r[0] for r in rows]

    def iterate(self, prefix: str, start: str = "",
                end: str | None = None) -> Iterator[tuple[str, bytes]]:
        with self._lock:
            self.calls += 1
            if end is None:
                rows = self._conn.execute(
                    "SELECT key, value FROM kv WHERE prefix=? AND key>=?"
                    " ORDER BY key", (prefix, start)).fetchall()
            else:
                rows = self._conn.execute(
                    "SELECT key, value FROM kv WHERE prefix=? AND key>=?"
                    " AND key<? ORDER BY key", (prefix, start, end)).fetchall()
        for k, v in rows:
            yield k, bytes(v)
