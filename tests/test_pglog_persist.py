"""The PG log in the store (ISSUE 41): keys under `_pgmeta`'s omap, the
ones that changed and no others, in the transaction of the data they
describe.  A PG here is the real one over a real store, its OSD a
stand-in that holds the store, the options and the counters."""

import time
from types import SimpleNamespace

import pytest

from ceph_tpu.osd.osdmap import PgId
from ceph_tpu.osd.pg import PG
from ceph_tpu.osd.pglog import (ENTRY_PREFIX, LOG_ATTR, LOG_META_KEY,
                                PGMETA, VER_KEY, PGLog, _is_log_key,
                                _parse_ev, entry_key, load_log,
                                persist_log)
from ceph_tpu.store import CrashPoint
from ceph_tpu.store import create as store_create
from ceph_tpu.store.objectstore import StoreError
from ceph_tpu.store.objectstore import Transaction as T
from ceph_tpu.utils import denc, faults
from ceph_tpu.utils.config import Config
from ceph_tpu.utils.perf_counters import PerfCountersBuilder

STORES = ["memstore", "kstore", "blockstore"]
OWNER = "osd.7"
PGID = PgId(1, 0)
CID = f"pg_{PGID}"


def _store(kind, path=""):
    s = store_create(kind, str(path) if path else "")
    s.owner = OWNER
    s.mkfs()
    s.mount()
    return s


def _osd(store, max_entries=0):
    conf = Config()
    if max_entries:
        conf.set_val("osd_pg_log_max_entries", max_entries)
    perf = (PerfCountersBuilder("osd")
            .add_u64_counter("pglog_keys_written")
            .add_u64_counter("pglog_bytes_written")
            .add_u64_counter("pglog_full_rewrites")
            .create_perf_counters())
    return SimpleNamespace(
        whoami=7, conf=conf, store=store, perf=perf,
        clock=SimpleNamespace(now=time.time),
        osdmap=SimpleNamespace(pools={}, epoch=1),
        witnessed_pool_birth=lambda pool: True)


def _pg(store, max_entries=0):
    """A PG booted over `store`: what a restart would find there."""
    return PG(_osd(store, max_entries), PGID)


def _entry(v, oid, op="modify", prior=None, epoch=1):
    return {"ev": (epoch, v), "oid": oid, "op": op, "prior": prior,
            "rollback": {"type": "stash"} if prior else None,
            "shard": 2}


def _write(pg, v, oid, op="modify", epoch=1):
    """One client write as a shard applies it: data, version and log
    entry in one transaction."""
    prior = pg.pglog.objects.get(oid)
    txn = T()
    if op == "delete":
        txn.try_remove(pg.cid, oid)
    else:
        txn.write(pg.cid, oid, 0, f"{oid}@{v}".encode())
        txn.setattr(pg.cid, oid, VER_KEY, repr((epoch, v)).encode())
    pg._log_and_apply(txn, _entry(v, oid, op, prior, epoch))
    return txn


def _state(log):
    return {"entries": list(log.entries), "objects": dict(log.objects),
            "deleted": dict(log.deleted), "missing": dict(log.missing),
            "tail": log.tail}


def _log_ops(txn):
    """(keys put, their bytes, keys removed) of `_pgmeta`'s omap."""
    put, rm = {}, []
    for op in txn.ops:
        if op[0] == "omap_set" and op[2] == PGMETA:
            put.update(op[3])
        elif op[0] == "omap_rm" and op[2] == PGMETA:
            rm.extend(op[3])
    return put, sum(len(k) + len(v) for k, v in put.items()), rm


def _stored_log_keys(store):
    return sorted(k for k in store.omap_get(CID, PGMETA)
                  if _is_log_key(k))


def _assert_store_is_memory(store, pg):
    """The store holds the live log, key for key and nothing else."""
    want = _state(pg.pglog)
    got = load_log(store, CID, max_entries=pg.pglog.max_entries)
    assert _state(got) == want
    keys = {entry_key(e["ev"]) for e in want["entries"]} | {LOG_META_KEY}
    for prefix, name in (("obj.", "objects"), ("del.", "deleted"),
                         ("mis.", "missing")):
        keys |= {prefix + oid for oid in want[name]}
    assert set(_stored_log_keys(store)) == keys
    assert _state(_pg(store, pg.pglog.max_entries).pglog) == want


# -- (i) a write's share of the log does not grow with the log ---------------


@pytest.mark.parametrize("bound", [0, 1000], ids=["below", "at-bound"])
@pytest.mark.parametrize("kind", STORES)
def test_write_carries_constant_log_bytes(kind, bound):
    store = _store(kind)
    pg = _pg(store, max_entries=bound)
    for v in range(1, 1501):
        pg.pglog.add(_entry(v, f"rbd_data.{v:016x}"))
    txn = T()
    pg._persist_log(txn)
    store.apply_transaction(txn)
    assert len(_log_ops(txn)[0]) >= (bound or 1500)
    sizes = []
    for v in range(1501, 1504):
        txn = _write(pg, v, f"rbd_data.{v:016x}")
        put, nbytes, rm = _log_ops(txn)
        assert len(put) + len(rm) <= 4 and nbytes < 1024, (put, rm)
        assert entry_key((1, v)) in put
        assert len(rm) == (1 if bound else 0)
        sizes.append((len(put), nbytes))
    assert len(set(sizes)) == 1                 # flat, write after write
    assert pg.osd.perf.value("pglog_full_rewrites") == 0
    _assert_store_is_memory(store, pg)
    # an overwrite: the same two keys, the index key rewritten
    put, nbytes, rm = _log_ops(_write(pg, 1504, "rbd_data.%016x" % 1503))
    assert len(put) + len(rm) <= 4 and nbytes < 1024
    _assert_store_is_memory(store, pg)


# -- (ii) a restarted PG has the live one's log ------------------------------


@pytest.mark.parametrize("kind", STORES)
def test_restart_round_trips_every_kind_of_change(kind):
    store = _store(kind)
    pg = _pg(store, max_entries=8)
    log = pg.pglog

    def persist():
        txn = T()
        pg._persist_log(txn)
        store.apply_transaction(txn)
        _assert_store_is_memory(store, pg)

    for v in range(1, 7):                       # adds
        _write(pg, v, f"o{v}")
    _assert_store_is_memory(store, pg)
    for v in range(7, 13):                      # a trim at the bound
        _write(pg, v, f"o{v % 4}")
    assert log.tail == (1, 4) and len(log.entries) == 8
    _assert_store_is_memory(store, pg)
    _write(pg, 13, "o1", op="delete")           # a delete
    assert "o1" in log.deleted and "o1" not in log.objects
    _assert_store_is_memory(store, pg)
    log.add(_entry(15, "late-newer"))
    log.add(_entry(14, "late"))                 # a late middle insert
    assert [e["ev"][1] for e in log.entries][-3:] == [13, 14, 15]
    persist()
    assert pg.osd.perf.value("pglog_full_rewrites") == 0
    log.merge_log([_entry(16, "claimed"), _entry(17, "o2")])
    assert log.missing == {"claimed": (1, 16), "o2": (1, 17)}
    persist()
    log.record_recovered((1, 16), "claimed")    # a recovered object
    log.record_recovered((1, 3), "old-push")    # below head: index only
    assert "claimed" not in log.missing
    assert log.objects["old-push"] == (1, 3)
    persist()
    log.rewind((1, 13))                         # a rewind
    assert log.head == (1, 13) and "late" not in log.objects
    persist()
    assert pg.osd.perf.value("pglog_full_rewrites") == 0
    log.entries = [dict(e) for e in log.entries[2:]]   # an adopted window
    log.tail = log.entries[0]["ev"]
    persist()
    assert pg.osd.perf.value("pglog_full_rewrites") == 1
    _write(pg, 18, "after")
    _assert_store_is_memory(store, pg)


@pytest.mark.parametrize("kind", ["kstore", "blockstore"])
def test_object_ops_do_not_scan_the_logs_keys(kind):
    """An overwrite's stash clone, a trim's remove and a rename look
    for the object's own omap rows: with a log of keys next to them in
    the KV's omap namespace, that scan must end where the object's
    rows do (it read the rest of the namespace, and was free only
    while the namespace was empty)."""
    store = _store(kind)
    pg = _pg(store)
    for v in range(1, 301):
        pg.pglog.add(_entry(v, f"o{v:04d}"))
    _write(pg, 301, "A")     # sorts before _pgmeta, as another PG's would
    store.apply_transaction(T().omap_setkeys(CID, "A", {"own": b"1"}))
    import sys
    omap_ns = sys.modules[type(store).__module__].P_OMAP
    scans = []
    scan = store.db.iterate

    def iterate(prefix, start="", end=None):
        rows = list(scan(prefix, start, end))
        if prefix == omap_ns:
            scans.append(len(rows))
        return iter(rows)

    store.db.iterate = iterate
    store.apply_transaction(T().clone(CID, "A", "A@1.301")
                            .collection_move_rename(CID, "A", CID, "B")
                            .remove(CID, "A@1.301"))
    assert scans and max(scans) <= 1, scans     # "own", and no more
    assert store.omap_get(CID, "B") == {"own": b"1"}
    assert len(store.omap_get(CID, PGMETA)) == 2 * 301 + 1
    assert not store.exists(CID, "A@1.301")


def test_keys_sort_as_versions_do():
    evs = [(1, 9), (1, 10), (2, 1), (10, 0), (9, 2 ** 40)]
    assert sorted(evs) == [
        ev for _k, ev in sorted((entry_key(ev), ev) for ev in evs)]
    assert all(k.startswith(ENTRY_PREFIX) and len(k) == 35
               for k in map(entry_key, evs))


def test_an_entry_is_encoded_once(monkeypatch):
    store = _store("memstore")
    pg = _pg(store)
    calls = []
    real = denc.dumps
    monkeypatch.setattr(
        denc, "dumps",
        lambda obj: (calls.append(obj) if isinstance(obj, dict)
                     and "oid" in obj else None, real(obj))[1])
    for v in range(1, 6):
        _write(pg, v, f"o{v}")
    pg.pglog._whole = True                      # every key again
    _write(pg, 6, "o6")
    assert pg.osd.perf.value("pglog_full_rewrites") == 1
    assert sorted(e["ev"][1] for e in calls) == [1, 2, 3, 4, 5, 6]
    assert _state(_pg(store).pglog) == _state(pg.pglog)


# -- (iii) a store that holds the old blob -----------------------------------


@pytest.mark.parametrize("fields", [3, 4, 5])
@pytest.mark.parametrize("kind", STORES)
def test_old_blob_loads_and_converts_at_the_first_write(kind, fields):
    old = PGLog()
    for v in range(1, 6):
        old.add(_entry(v, f"o{v}"))
    old.add(_entry(6, "o2", op="delete"))
    if fields > 3:
        old.tail = (1, 0)
    if fields > 4:
        old.merge_log([_entry(7, "claimed")])
    blob = denc.dumps((old.entries, dict(old.objects),
                       dict(old.deleted), old.tail,
                       dict(old.missing))[:fields])
    store = _store(kind)
    txn = (T().create_collection(CID).touch(CID, PGMETA)
           .setattr(CID, PGMETA, LOG_ATTR, blob)
           .setattr(CID, PGMETA, "les", b"3")
           .omap_setkeys(CID, PGMETA, {"hitsets": denc.dumps([])}))
    for v in range(1, 6):
        txn.write(CID, f"o{v}", 0, b"old")
    store.apply_transaction(txn)
    pg = _pg(store)
    assert _state(pg.pglog) == _state(old)
    assert pg.last_epoch_started == 3
    assert store.read(CID, "o3") == b"old"              # serves reads
    assert _stored_log_keys(store) == []                # not yet written
    _write(pg, 8, "o3")                                 # one write
    with pytest.raises(StoreError):
        store.getattr(CID, PGMETA, LOG_ATTR)
    assert pg.osd.perf.value("pglog_full_rewrites") == 1
    omap = store.omap_get(CID, PGMETA)
    assert omap["hitsets"] == denc.dumps([])            # not the log's
    assert store.getattr(CID, PGMETA, "les") == b"3"
    _assert_store_is_memory(store, pg)
    put, nbytes, _rm = _log_ops(_write(pg, 9, "o4"))    # and then O(1)
    assert len(put) == 2 and nbytes < 1024
    assert pg.osd.perf.value("pglog_full_rewrites") == 1
    _assert_store_is_memory(store, pg)


# -- (iv) a transaction that does not apply ----------------------------------


@pytest.mark.parametrize("kind", STORES)
def test_failed_transaction_is_not_believed_written(kind):
    store = _store(kind)
    pg = _pg(store)
    for v in range(1, 5):
        _write(pg, v, f"o{v}")
    before = _state(pg.pglog)
    bad = T().remove(pg.cid, "never-was")
    with pytest.raises(StoreError):
        pg._log_and_apply(bad, _entry(5, "o1", prior=(1, 1)))
    assert _state(pg.pglog) == before                   # un-recorded
    assert _state(load_log(store, CID)) == before       # nothing landed
    assert pg.pglog._unconfirmed
    txn = _write(pg, 5, "o2")                           # the next write
    assert len(_log_ops(txn)[0]) > 2                    # every key anew
    assert pg.osd.perf.value("pglog_full_rewrites") == 1
    assert not pg.pglog._unconfirmed
    _assert_store_is_memory(store, pg)
    assert len(_log_ops(_write(pg, 6, "o3"))[0]) == 2   # O(1) again
    _assert_store_is_memory(store, pg)


def test_transaction_never_applied_is_written_again():
    """The callers that swallow a StoreError (peering, recovery): what
    was drained into a lost transaction goes out with the next."""
    store = _store("memstore")
    store.apply_transaction(T().create_collection(CID))
    log = PGLog()
    for v in range(1, 4):
        log.add(_entry(v, f"o{v}"))
    lost = T()
    assert persist_log(log, store, CID, lost)[2] is False
    log.add(_entry(4, "o1"))
    txn = T()
    keys, _nbytes, whole = persist_log(log, store, CID, txn)
    assert whole and keys == 4 + 3 + 1
    store.apply_transaction(txn)
    assert _state(load_log(store, CID)) == _state(log)
    txn = T()
    log.add(_entry(5, "o2"))
    assert persist_log(log, store, CID, txn)[:3:2] == (2, False)


# -- (v) crashes --------------------------------------------------------------


def _blockstore(tmp_path):
    return _store("blockstore", tmp_path / "bs")


def _remount(tmp_path):
    s = store_create("blockstore", str(tmp_path / "bs"))
    s.owner = OWNER
    s.mount()
    return s


def _assert_head_has_its_data(store, log):
    head = log.entries[-1]
    assert _parse_ev(store.getattr(CID, head["oid"], VER_KEY)) == \
        head["ev"]
    assert store.read(CID, head["oid"]) == \
        f"{head['oid']}@{head['ev'][1]}".encode()
    # (a commit torn between its onodes and its omap rows leaves the
    # data ahead of the log, as it did when the log was an attr of a
    # later onode: never behind it)
    for oid, ev in log.objects.items():
        assert _parse_ev(store.getattr(CID, oid, VER_KEY)) >= ev, oid


def test_crash_before_the_append_leaves_the_log_as_it_was(tmp_path):
    store = _blockstore(tmp_path)
    pg = _pg(store)
    for v in range(1, 6):
        _write(pg, v, f"o{v % 3}")
    before = _state(pg.pglog)
    faults.get().reset(seed=0x5EED)
    faults.get().crash("pglog.append", 1.0, OWNER)
    try:
        with pytest.raises(CrashPoint):
            _write(pg, 6, "o1")
    finally:
        faults.get().reset()
    store.umount()
    store = _remount(tmp_path)
    reborn = _pg(store)
    assert _state(reborn.pglog) == before
    _assert_head_has_its_data(store, reborn.pglog)
    _write(reborn, 6, "o1")
    _assert_store_is_memory(store, reborn)
    store.umount()


@pytest.mark.parametrize("seed", [0x5EED, 0xA11CE, 0xBAD, 7])
def test_torn_kv_commit_leaves_a_log_whose_head_has_its_data(tmp_path,
                                                             seed):
    """A commit torn at a row boundary: onodes land before omap rows,
    and of the log's rows the entry lands last, so whatever the cut,
    no entry is there without its data and its index key."""
    store = _blockstore(tmp_path)
    pg = _pg(store, max_entries=4)
    for v in range(1, 7):
        _write(pg, v, f"o{v % 3}")
    before = _state(pg.pglog)
    faults.get().reset(seed=seed)
    faults.get().crash("wal.pre_kv_commit", 1.0, OWNER)
    try:
        with pytest.raises(CrashPoint):
            _write(pg, 7, "o1")
    finally:
        faults.get().reset()
    after = _state(pg.pglog)        # the dead PG had recorded it
    store.umount()
    store = _remount(tmp_path)
    reborn = _pg(store, max_entries=4)
    got = _state(reborn.pglog)
    assert got["entries"][-1]["ev"] in ((1, 6), (1, 7))
    if got["entries"][-1]["ev"] == (1, 7):
        # the entry landed, so everything before it in the commit did
        assert got["objects"] == after["objects"]
        assert got["tail"] == after["tail"]
    else:
        assert [e["ev"] for e in got["entries"]] == \
            [e["ev"] for e in before["entries"]]
    _assert_head_has_its_data(store, reborn.pglog)
    store.umount()


# -- the counters and the span args that say it engages ----------------------


def test_store_apply_span_carries_the_log_counts():
    from ceph_tpu.utils.clock import ManualClock
    from ceph_tpu.utils import optracker
    from ceph_tpu.utils.optracker import OpTracker
    store = _store("memstore")
    pg = _pg(store)
    trk = OpTracker(ManualClock(), history_size=16)
    for v in (1, 2):
        op = trk.create(f"osd_op(c:{v} o ['writefull'])", kind="client",
                        trace_id=f"c:{v}")
        with optracker.op_context(op):
            _write(pg, v, "o")
        op.finish()
    docs = trk.dump_historic_ops()["ops"]
    args = [s["args"] for d in docs for s in d["spans"]
            if s["name"] == "store_apply"]
    assert [a["log_persists"] for a in args] == [1, 1]
    assert [a["log_keys"] for a in args] == [3, 2]      # log_meta once
    assert all(0 < a["log_bytes"] < 1024 for a in args)
    perf = pg.osd.perf
    assert perf.value("pglog_keys_written") == 5
    assert perf.value("pglog_bytes_written") == \
        sum(a["log_bytes"] for a in args)

    # the benchmark's metric, through the reader that is there
    from benchmark import harness
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "osd.pglog_bytes_per_commit.write.json")
    reader = harness.load_module(harness.HERE, "readers", spec["reader"])
    said = []
    readings = SimpleNamespace(op_docs=docs, log=said.append)
    got = reader.read(readings, spec["params"])
    assert got == sum(a["log_bytes"] for a in args) / 2
    # and nothing where the program has no such args (the parent's)
    for d in docs:
        for s in d["spans"]:
            s.pop("args", None)
    assert reader.read(readings, spec["params"]) is None
