"""SqliteDB hands sqlite a transaction as one statement a run of like
ops (ISSUE 31): what a transaction leaves is what its ops say in their
order, as MemDB leaves it, under the durability mode asked for."""

import random
import sqlite3

import pytest

from ceph_tpu.kv import MemDB, SqliteDB


@pytest.fixture
def db(tmp_path):
    d = SqliteDB(str(tmp_path / "kv.db"))
    d.open()
    yield d
    d.close()


def traced(d):
    seen = []
    d._conn.set_trace_callback(seen.append)
    return seen


def rows(d):
    return {p: list(d.iterate(p, "")) for p in sorted(d.prefixes())}


def txn(d, ops):
    t = d.transaction()
    for op in ops:
        if op[0] == "set":
            t.set(op[1], op[2], op[3])
        elif op[0] == "rm":
            t.rmkey(op[1], op[2])
        else:
            t.rmkeys_by_prefix(op[1])
    return t


def test_four_sets_are_one_statement_and_no_pragma(db):
    db.submit_transaction(txn(db, [("set", "S", "first", b"1")]), sync=True)
    seen = traced(db)
    calls = db.calls
    db.submit_transaction(
        txn(db, [("set", "O", "shard", b"o" * 2000),
                 ("set", "O", "_pgmeta", b"l" * 20000),
                 ("set", "S", "freelist", b"f"), ("set", "S", "super", b"s")]),
        sync=True)
    assert len(seen) <= 3 and db.calls - calls == len(seen)
    assert not any("PRAGMA" in sql for sql in seen)
    assert sum(sql.startswith("INSERT") for sql in seen) == 1
    assert db._conn.execute("PRAGMA synchronous").fetchone()[0] == 2
    assert rows(db) == {
        "O": [("_pgmeta", b"l" * 20000), ("shard", b"o" * 2000)],
        "S": [("first", b"1"), ("freelist", b"f"), ("super", b"s")]}


@pytest.mark.parametrize("ops, left", [
    ([("set", "P", "k", b"1"), ("rm", "P", "k"), ("set", "P", "k", b"3")],
     [("k", b"3")]),
    ([("rm", "P", "k"), ("set", "P", "k", b"2")], [("k", b"2")]),
    ([("set", "P", "k", b"1"), ("set", "P", "k", b"2")], [("k", b"2")]),
    ([("set", "P", "k", b"1"), ("rm", "P", "k")], []),
    ([("set", "P", "a", b"1"), ("set", "P", "b", b"2"), ("rm_prefix", "P"),
      ("set", "P", "c", b"3")], [("c", b"3")]),
    ([("rm", "P", "old"), ("rm", "P", "gone"), ("rm", "Q", "old")], []),
], ids=["set-rm-set", "rm-set", "set-set", "set-rm", "rm_prefix", "rms"])
def test_op_order_decides(db, ops, left):
    db.submit_transaction(
        txn(db, [("set", "P", "old", b"0"), ("set", "Q", "keep", b"q")]),
        sync=True)
    seen = traced(db)
    db.submit_transaction(txn(db, ops), sync=True)
    seen = list(seen)
    assert list(db.iterate("P", "")) == \
        sorted(left + ([] if any(o[0] == "rm_prefix" or o[1:3] == ("P", "old")
                                 for o in ops) else [("old", b"0")]))
    assert list(db.iterate("Q", "")) == [("keep", b"q")]
    # a run of like ops is one statement; more than one is a BEGIN and
    # a COMMIT round them
    runs = 1 + sum(a[0] != b[0] or a[0] == "rm_prefix"
                   for a, b in zip(ops, ops[1:]))
    assert len(seen) == (runs if runs == 1 else runs + 2), seen


def test_a_run_longer_than_a_statement_binds_is_cut(db):
    db._conn.setlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER, 10)
    db._max_vars = 10
    ops = [("set", "P", f"k{i:03d}", b"v%d" % i) for i in range(25)] + \
          [("rm", "P", f"k{i:03d}") for i in range(0, 24, 2)]
    seen = traced(db)
    db.submit_transaction(txn(db, ops), sync=True)
    # three rows an INSERT, five keys a DELETE
    inserts = [s for s in seen if s.startswith("INSERT")]
    deletes = [s for s in seen if s.startswith("DELETE")]
    assert len(inserts) == 9 and len(deletes) == 3
    assert seen[0] == "BEGIN" and seen[-1] == "COMMIT"
    assert list(db.iterate("P", "")) == \
        [(f"k{i:03d}", b"v%d" % i) for i in range(25)
         if i % 2 or i == 24]


def test_a_run_of_rms_searches_the_index(db):
    t = txn(db, [("rm", "W", f"{i:016x}") for i in range(16)])
    (sql, params), = db._statements(t.ops)
    plan = [r[3] for r in
            db._conn.execute("EXPLAIN QUERY PLAN " + sql, params)]
    assert not any(step.startswith("SCAN kv") for step in plan), plan
    assert any("SEARCH kv USING" in step and "prefix=? AND key=?" in step
               for step in plan), plan


@pytest.mark.parametrize("seed", [1, 31, 2147483650])
def test_random_ops_leave_what_memdb_holds(db, seed):
    rng = random.Random(seed)
    mem = MemDB()
    db._conn.setlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER, 30)
    db._max_vars = 30
    for _ in range(40):
        ops = []
        for _ in range(rng.randrange(1, 30)):
            kind = rng.choices(["set", "rm", "rm_prefix"], [12, 6, 1])[0]
            prefix, key = rng.choice("OMS"), f"k{rng.randrange(12)}"
            ops.append((kind, prefix, key, rng.randbytes(rng.randrange(40))))
        sync = rng.random() < 0.5
        db.submit_transaction(txn(db, ops), sync=sync)
        mem.submit_transaction(txn(mem, ops), sync=sync)
        assert rows(db) == rows(mem)
        assert db._conn.execute("PRAGMA synchronous").fetchone()[0] == \
            (2 if sync else 1)


def test_sync_mode_follows_what_was_asked(db):
    seen = traced(db)
    one = [("set", "P", "k", b"v")]
    mode = "PRAGMA synchronous"
    assert db._conn.execute(mode).fetchone()[0] == 2    # opens in FULL
    db.submit_transaction(txn(db, one), sync=True)
    db.submit_transaction(txn(db, one), sync=True)
    assert not any("synchronous=" in s for s in seen)
    db.submit_transaction(txn(db, one))                 # sync=False
    assert db._conn.execute(mode).fetchone()[0] == 1
    db.submit_transaction(txn(db, one))
    assert [s for s in seen if "synchronous=" in s] == \
        ["PRAGMA synchronous=NORMAL"]
    db.submit_transaction(txn(db, one), sync=True)
    assert db._conn.execute(mode).fetchone()[0] == 2
    assert [s for s in seen if "synchronous=" in s] == \
        ["PRAGMA synchronous=NORMAL", "PRAGMA synchronous=FULL"]


def test_a_statement_that_fails_takes_the_transaction_with_it(db):
    db.submit_transaction(txn(db, [("set", "P", "k", b"old")]), sync=True)
    t = txn(db, [("set", "P", "k", b"new"), ("rm", "P", "other")])
    t.ops.append(("set", "P", "bad", object()))     # sqlite cannot bind it
    with pytest.raises(sqlite3.Error):
        db.submit_transaction(t, sync=True)
    assert list(db.iterate("P", "")) == [("k", b"old")]
    assert not db._conn.in_transaction
    db.submit_transaction(txn(db, [("set", "P", "k", b"next")]), sync=True)
    assert db.get("P", "k") == b"next"


def test_calls_count_statements_on_both_backends(db):
    mem = MemDB()
    for d in (db, mem):
        before = d.calls
        d.submit_transaction(txn(d, [("set", "P", "a", b"1"),
                                     ("set", "P", "b", b"2")]), sync=True)
        assert d.get("P", "a") == b"1" and d.get("P", "zz") is None
        assert list(d.iterate("P", "")) == [("a", b"1"), ("b", b"2")]
        assert d.calls - before == 4
    seen = traced(db)
    before = db.calls
    db.submit_transaction(txn(db, [("rm", "P", "a"), ("set", "P", "c", b"3")]),
                          sync=True)
    db.get("P", "c")
    assert db.calls - before == len(seen) == 5
