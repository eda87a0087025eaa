"""ObjectStore conformance suite, run against every backend.

The reference pattern (test/objectstore/store_test.cc): one suite,
parameterized over memstore/filestore; plus journal-replay crash tests
for the journaled backend.
"""

import os
import struct
import threading

import pytest

from ceph_tpu.store import (ENOENT, JournalFileStore, MemStore, StoreError,
                            Transaction, create)


@pytest.fixture(params=["memstore", "filestore", "kstore",
                        "kstore-disk", "blockstore", "blockstore-disk"])
def store(request, tmp_path):
    if request.param == "memstore":
        s = MemStore()
        yield s
    elif request.param == "blockstore":
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore()
        s.mkfs()
        yield s
        s.umount()
    elif request.param == "blockstore-disk":
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs"))
        s.mkfs()
        s.mount()
        yield s
        s.umount()
    elif request.param == "kstore":
        from ceph_tpu.store.kstore import KStore
        s = KStore()
        s.mkfs()
        yield s
        s.umount()
    elif request.param == "kstore-disk":
        from ceph_tpu.store.kstore import KStore
        s = KStore(str(tmp_path / "ks"))
        s.mkfs()
        s.mount()
        yield s
        s.umount()
    else:
        s = JournalFileStore(str(tmp_path / "fs"), commit_interval=60)
        s.mkfs()
        s.mount()
        yield s
        s.umount()


def T():
    return Transaction()


class TestConformance:
    def test_create_collection_and_write_read(self, store):
        store.apply_transaction(T().create_collection("c1")
                                .write("c1", "o1", 0, b"hello"))
        assert store.read("c1", "o1") == b"hello"
        assert store.stat("c1", "o1")["size"] == 5
        assert store.exists("c1", "o1")
        assert not store.exists("c1", "o2")

    def test_write_offset_extends_with_zeros(self, store):
        store.apply_transaction(T().create_collection("c")
                                .write("c", "o", 10, b"xy"))
        assert store.read("c", "o") == b"\x00" * 10 + b"xy"

    def test_overwrite_middle(self, store):
        store.apply_transaction(T().create_collection("c")
                                .write("c", "o", 0, b"aaaaaaaa")
                                .write("c", "o", 2, b"BB"))
        assert store.read("c", "o") == b"aaBBaaaa"

    def test_read_range(self, store):
        store.apply_transaction(T().create_collection("c")
                                .write("c", "o", 0, b"0123456789"))
        assert store.read("c", "o", 2, 3) == b"234"
        assert store.read("c", "o", 8, 100) == b"89"

    def test_write_accepts_views_and_ropes(self, store):
        """The zero-copy contract: every backend lands memoryview,
        numpy-backed-view and BufferList payloads bit-exactly (the EC
        fan-out hands stores shard VIEWS over the encode output)."""
        import numpy as np
        from ceph_tpu.utils.bufferlist import BufferList
        blob = bytes(range(256)) * 40
        arr = np.frombuffer(blob, dtype=np.uint8)
        rope = BufferList(blob[:100])
        rope.append(blob[100:])
        store.apply_transaction(
            T().create_collection("v")
            .write("v", "mv", 0, memoryview(blob))
            .write("v", "np", 0, memoryview(arr))
            .write("v", "rope", 0, rope)
            .write("v", "mid", 3, memoryview(blob)[5:50]))
        assert store.read("v", "mv") == blob
        assert store.read("v", "np") == blob
        assert store.read("v", "rope") == blob
        assert store.read("v", "mid") == b"\x00" * 3 + blob[5:50]
        # unaligned overwrite with a view (block rmw paths)
        store.apply_transaction(
            T().write("v", "mv", 7, memoryview(b"PATCH")))
        assert store.read("v", "mv") == blob[:7] + b"PATCH" + blob[12:]

    def test_zero_and_truncate(self, store):
        store.apply_transaction(T().create_collection("c")
                                .write("c", "o", 0, b"abcdefgh")
                                .zero("c", "o", 2, 3))
        assert store.read("c", "o") == b"ab\x00\x00\x00fgh"
        store.apply_transaction(T().truncate("c", "o", 4))
        assert store.read("c", "o") == b"ab\x00\x00"
        store.apply_transaction(T().truncate("c", "o", 6))
        assert store.read("c", "o") == b"ab\x00\x00\x00\x00"

    def test_remove_and_enoent(self, store):
        store.apply_transaction(T().create_collection("c").touch("c", "o"))
        store.apply_transaction(T().remove("c", "o"))
        with pytest.raises(StoreError) as ei:
            store.read("c", "o")
        assert ei.value.errno == ENOENT

    def test_clone(self, store):
        store.apply_transaction(T().create_collection("c")
                                .write("c", "src", 0, b"payload")
                                .setattr("c", "src", "a1", b"v1")
                                .omap_setkeys("c", "src", {"k": b"v"}))
        store.apply_transaction(T().clone("c", "src", "dst"))
        store.apply_transaction(T().write("c", "src", 0, b"CHANGED"))
        assert store.read("c", "dst") == b"payload"
        assert store.getattr("c", "dst", "a1") == b"v1"
        assert store.omap_get("c", "dst") == {"k": b"v"}

    @pytest.mark.parametrize("then", ["truncate", "remove", "move"])
    def test_clone_of_a_source_emptied_next(self, store, then):
        """The shapes an EC shard's rollback stash has (clone, then the
        source rewritten whole or removed) and a rename: a store may
        hand the data over instead of copying it; what is read
        afterwards is what a copy would have left."""
        body = bytes(range(256)) * 520 + b"tail"       # 130 blocks and a bit
        store.apply_transaction(T().create_collection("c")
                                .write("c", "src", 0, body)
                                .setattr("c", "src", "a1", b"v1")
                                .omap_setkeys("c", "src", {"k": b"v"}))
        if then == "truncate":
            store.apply_transaction(T().try_clone("c", "src", "dst")
                                    .truncate("c", "src", 0)
                                    .write("c", "src", 0, b"new body")
                                    .setattr("c", "src", "a2", b"v2"))
            assert store.read("c", "src") == b"new body"
            assert store.getattrs("c", "src") == {"a1": b"v1", "a2": b"v2"}
            assert store.omap_get("c", "src") == {"k": b"v"}
        elif then == "remove":
            store.apply_transaction(T().try_clone("c", "src", "dst")
                                    .try_remove("c", "src"))
            assert not store.exists("c", "src")
        else:
            store.apply_transaction(
                T().collection_move_rename("c", "src", "c", "dst"))
            assert not store.exists("c", "src")
        assert store.read("c", "dst") == body
        assert store.stat("c", "dst")["size"] == len(body)
        assert store.getattr("c", "dst", "a1") == b"v1"
        assert store.omap_get("c", "dst") == {"k": b"v"}
        # the stash restored and dropped (a rollback), then gone
        store.apply_transaction(T().try_clone("c", "dst", "src")
                                .try_remove("c", "dst"))
        assert store.read("c", "src") == body and not store.exists("c", "dst")
        # a clone whose source keeps its data is a copy
        store.apply_transaction(T().clone("c", "src", "kept")
                                .truncate("c", "src", 4))
        assert store.read("c", "kept") == body
        assert store.read("c", "src") == body[:4]

    def test_xattrs(self, store):
        store.apply_transaction(T().create_collection("c")
                                .setattr("c", "o", "n1", b"v1")
                                .setattr("c", "o", "n2", b"v2"))
        assert store.getattrs("c", "o") == {"n1": b"v1", "n2": b"v2"}
        store.apply_transaction(T().rmattr("c", "o", "n1"))
        with pytest.raises(StoreError):
            store.getattr("c", "o", "n1")

    def test_omap(self, store):
        store.apply_transaction(
            T().create_collection("c")
            .omap_setkeys("c", "o", {"a": b"1", "b": b"2", "c": b"3"}))
        assert store.omap_get_values("c", "o", ["a", "c", "zz"]) == {
            "a": b"1", "c": b"3"}
        store.apply_transaction(T().omap_rmkeys("c", "o", ["b"]))
        assert store.omap_get("c", "o") == {"a": b"1", "c": b"3"}
        store.apply_transaction(T().omap_clear("c", "o"))
        assert store.omap_get("c", "o") == {}

    def test_collection_list_sorted_after(self, store):
        t = T().create_collection("c")
        for name in ["obj3", "obj1", "obj5", "obj2"]:
            t.touch("c", name)
        store.apply_transaction(t)
        assert store.collection_list("c") == ["obj1", "obj2", "obj3", "obj5"]
        assert store.collection_list("c", start="obj2") == ["obj3", "obj5"]
        assert store.collection_list("c", start="obj1", max_count=2) == [
            "obj2", "obj3"]

    def test_collection_move_rename(self, store):
        store.apply_transaction(T().create_collection("c1")
                                .create_collection("c2")
                                .write("c1", "o", 0, b"data"))
        store.apply_transaction(
            T().collection_move_rename("c1", "o", "c2", "o2"))
        assert not store.exists("c1", "o")
        assert store.read("c2", "o2") == b"data"

    def test_commit_callbacks(self, store):
        fired = []
        t = T().create_collection("cb").write("cb", "o", 0, b"x")
        t.register_on_applied(lambda: fired.append("applied"))
        t.register_on_commit(lambda: fired.append("commit"))
        done = threading.Event()
        store.queue_transactions([t], on_commit=done.set)
        assert done.wait(5)
        assert "applied" in fired and "commit" in fired

    def test_list_collections(self, store):
        store.apply_transaction(T().create_collection("x")
                                .create_collection("y"))
        assert set(store.list_collections()) >= {"x", "y"}


class TestJournalReplay:
    def test_remount_preserves_state(self, tmp_path):
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=60)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o", 0, b"persisted")
                            .omap_setkeys("c", "o", {"k": b"v"}))
        s.umount()
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"persisted"
        assert s2.omap_get("c", "o") == {"k": b"v"}
        s2.umount()

    def test_crash_without_checkpoint_replays_journal(self, tmp_path):
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o", 0, b"journal-only"))
        # simulate crash: no umount/checkpoint, just drop the handle
        s._jf.close()
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"journal-only"
        s2.umount()

    def test_torn_tail_write_is_discarded(self, tmp_path):
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o", 0, b"good"))
        seq = s._next_seq
        s._jf.close()
        # append a torn entry: a valid header promising more payload
        # bytes than the crash let reach the disk
        with open(os.path.join(path, "journal"), "ab") as f:
            from ceph_tpu.ops.crc32c import crc32c
            from ceph_tpu.utils import denc
            blob = denc.dumps([[("write", "c", "o", 0, b"torn")]])
            f.write(struct.pack("<QQI", len(blob), seq, crc32c(0, blob)))
            f.write(blob[: len(blob) // 2])
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"good"
        assert s2.journal_stats()["journal_torn_tail_discards"] == 1
        # the unparseable tail was discarded ON DISK: appends resume a
        # clean record stream and a further remount halts nowhere
        s2.apply_transaction(T().write("c", "o2", 0, b"after"))
        s2._jf.close()
        s3 = JournalFileStore(path)
        s3.mount()
        assert s3.read("c", "o") == b"good"
        assert s3.read("c", "o2") == b"after"
        assert s3.journal_stats()["journal_torn_tail_discards"] == 0
        s3.umount()

    def test_bitflipped_record_halts_replay_cleanly(self, tmp_path):
        """A crc-failing record must stop replay at the last valid
        record — not crash, not apply garbage."""
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "keep", 0, b"kept"))
        start = s._journal_len
        s.apply_transaction(T().write("c", "lost", 0, b"lost"))
        s._jf.close()
        with open(os.path.join(path, "journal"), "r+b") as f:
            f.seek(start + 24)              # a payload byte of rec 2
            b = f.read(1)
            f.seek(start + 24)
            f.write(bytes([b[0] ^ 0x40]))
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "keep") == b"kept"
        assert not s2.exists("c", "lost")
        assert s2.journal_stats()["journal_bad_record_halts"] == 1
        s2.umount()

    def test_replay_tolerates_failed_live_ops(self, tmp_path):
        """The journal is a WAL: an op that failed at LIVE apply time
        (e.g. a client remove of a never-created object, NACKed with
        ENOENT) was still journaled first.  Replay must reach the
        same end state the live run did — not refuse to mount
        (the filestore crash-restart soak caught this)."""
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o", 0, b"keep"))
        with pytest.raises(StoreError):
            s.apply_transaction(T().remove("c", "ghost"))
        s.apply_transaction(T().write("c", "p", 0, b"after"))
        s._jf.close()                      # crash: no checkpoint
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"keep"
        assert s2.read("c", "p") == b"after"
        assert s2.journal_stats()["journal_records_replayed"] == 3
        s2.umount()

    def test_corrupt_snapshot_falls_back_to_full_replay(self, tmp_path):
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o", 0, b"snapshotted"))
        s._checkpoint()
        s.apply_transaction(T().write("c", "p", 0, b"journal-tail"))
        s.umount()
        with open(os.path.join(path, "snapshot"), "r+b") as f:
            f.seek(16)
            f.write(b"\xde\xad\xbe\xef")    # body corruption: crc fails
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"snapshotted"
        assert s2.read("c", "p") == b"journal-tail"
        assert s2.journal_stats()["snapshot_corrupt_fallbacks"] == 1
        s2.umount()

    def test_checkpoint_then_more_journal(self, tmp_path):
        path = str(tmp_path / "fs")
        s = JournalFileStore(path, commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c")
                            .write("c", "o1", 0, b"one"))
        s._checkpoint()
        s.apply_transaction(T().write("c", "o2", 0, b"two"))
        s._jf.close()  # crash after checkpoint + extra journal
        s2 = JournalFileStore(path)
        s2.mount()
        assert s2.read("c", "o1") == b"one"
        assert s2.read("c", "o2") == b"two"
        s2.umount()


class TestKV:
    def test_memdb_and_sqlite(self, tmp_path):
        from ceph_tpu.kv import MemDB, SqliteDB
        for db in (MemDB(), SqliteDB(str(tmp_path / "kv.db"))):
            db.open()
            t = db.transaction()
            t.set("p", "k1", b"v1")
            t.set("p", "k2", b"v2")
            t.set("q", "k1", b"other")
            db.submit_transaction(t)
            assert db.get("p", "k1") == b"v1"
            assert db.get("p", "nope") is None
            assert list(db.iterate("p")) == [("k1", b"v1"), ("k2", b"v2")]
            assert list(db.iterate("p", start="k2")) == [("k2", b"v2")]
            t2 = db.transaction()
            t2.rmkey("p", "k1")
            db.submit_transaction(t2)
            assert db.get("p", "k1") is None
            db.close()

    def test_sqlite_durability(self, tmp_path):
        from ceph_tpu.kv import SqliteDB
        path = str(tmp_path / "kv.db")
        db = SqliteDB(path)
        db.open()
        t = db.transaction()
        t.set("p", "k", b"v")
        db.submit_transaction(t, sync=True)
        db.close()
        db2 = SqliteDB(path)
        db2.open()
        assert db2.get("p", "k") == b"v"
        db2.close()

    def test_rm_prefix(self, tmp_path):
        from ceph_tpu.kv import MemDB
        db = MemDB()
        db.open()
        t = db.transaction()
        t.set("a", "k", b"1")
        t.set("b", "k", b"2")
        db.submit_transaction(t)
        t2 = db.transaction()
        t2.rmkeys_by_prefix("a")
        db.submit_transaction(t2)
        assert db.get("a", "k") is None
        assert db.get("b", "k") == b"2"


class TestKStoreDurability:
    def test_remount_preserves_everything(self, tmp_path):
        from ceph_tpu.store.kstore import KStore
        path = str(tmp_path / "kd")
        s = KStore(path)
        s.mkfs()
        s.mount()
        s.apply_transaction(
            T().create_collection("c").write("c", "o", 0, b"d" * 100000)
            .setattr("c", "o", "k", b"v").omap_setkeys("c", "o",
                                                       {"m": b"1"}))
        s.umount()
        s2 = KStore(path)
        s2.mount()
        assert s2.read("c", "o") == b"d" * 100000
        assert s2.getattr("c", "o", "k") == b"v"
        assert s2.omap_get("c", "o") == {"m": b"1"}
        s2.umount()

    def test_cluster_on_kstore(self, tmp_path):
        """OSDs run on the KV-backed store end to end."""
        import time
        from ceph_tpu.client import RadosError
        from ceph_tpu.vstart import MiniCluster
        c = MiniCluster(num_mons=1, num_osds=3, store_kind="kstore",
                        store_dir=str(tmp_path)).start()
        try:
            r = c.client()
            r.create_pool("kv", pg_num=4)
            io = r.open_ioctx("kv")
            end = time.time() + 20
            while True:
                try:
                    io.write_full("o", b"kv-backed!")
                    break
                except RadosError:
                    if time.time() > end:
                        raise
                    time.sleep(0.3)
            assert io.read("o") == b"kv-backed!"
        finally:
            c.stop()

    def test_omap_then_remove_in_one_txn(self, tmp_path):
        """Staged omap writes must be visible to later ops in the SAME
        transaction (regression: kstore wrote them past the staging)."""
        from ceph_tpu.store.kstore import KStore
        s = KStore()
        s.mkfs()
        s.apply_transaction(T().create_collection("c"))
        s.apply_transaction(
            T().omap_setkeys("c", "o", {"k": b"v"}).remove("c", "o"))
        s.apply_transaction(T().touch("c", "o"))
        assert s.omap_get("c", "o") == {}
        s.apply_transaction(
            T().omap_setkeys("c", "p", {"x": b"1"}).clone("c", "p", "p2"))
        assert s.omap_get("c", "p2") == {"x": b"1"}
        s.umount()

    def test_rmcoll_purges_omap(self, tmp_path):
        from ceph_tpu.store.kstore import KStore
        s = KStore()
        s.mkfs()
        s.apply_transaction(T().create_collection("d"))
        s.apply_transaction(T().omap_setkeys("d", "q", {"z": b"9"}))
        s.apply_transaction(T().remove_collection("d"))
        s.apply_transaction(T().create_collection("d").touch("d", "q"))
        assert s.omap_get("d", "q") == {}
        s.umount()

    def test_rmcoll_cancels_staged_ops_same_txn(self, tmp_path):
        from ceph_tpu.store.kstore import KStore
        s = KStore()
        s.mkfs()
        s.apply_transaction(
            T().create_collection("c").touch("c", "o")
            .write("c", "o", 0, b"x").remove_collection("c"))
        assert not s.collection_exists("c")
        assert not s.exists("c", "o")
        s.umount()


class TestBlockStore:
    """BlueStore-analog specifics: allocator, COW, deferred WAL,
    checksums (tests mirror store_test.cc's bluestore sections)."""

    def _mk(self, tmp_path, **kw):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs"), **kw)
        s.mkfs()
        s.mount()
        return s

    def test_allocator_coalesce_and_reuse(self):
        from ceph_tpu.store.blockstore import MIN_ALLOC, ExtentAllocator
        a = ExtentAllocator([[0, 8 * MIN_ALLOC]])
        e1 = a.allocate(2 * MIN_ALLOC)
        e2 = a.allocate(MIN_ALLOC)
        assert a.total_free() == 5 * MIN_ALLOC
        a.release(e1)
        a.release(e2)
        assert a.total_free() == 8 * MIN_ALLOC
        assert a.dump() == [[0, 8 * MIN_ALLOC]]   # coalesced back
        # splits across runs when no single run fits
        a2 = ExtentAllocator([[0, MIN_ALLOC], [10 * MIN_ALLOC, MIN_ALLOC]])
        got = a2.allocate(2 * MIN_ALLOC)
        assert sum(l for _, l in got) == 2 * MIN_ALLOC
        assert a2.total_free() == 0

    def test_overwrites_do_not_leak_space(self, tmp_path):
        import os
        from ceph_tpu.store.blockstore import GROW
        s = self._mk(tmp_path)
        s.apply_transaction(T().create_collection("c"))
        for i in range(200):
            s.apply_transaction(
                T().write("c", "o", 0, bytes([i % 251]) * 4096))
        s.umount()
        # 200 COW overwrites of one block must recycle freed blocks,
        # not grow the device past the first growth increment
        assert os.path.getsize(str(tmp_path / "bs" / "block")) <= GROW

    def test_remount_preserves_everything(self, tmp_path):
        from ceph_tpu.store.blockstore import BlockStore
        s = self._mk(tmp_path)
        payload = bytes(range(256)) * 2000          # multi-block
        s.apply_transaction(
            T().create_collection("c").write("c", "o", 0, payload)
            .setattr("c", "o", "k", b"v")
            .omap_setkeys("c", "o", {"m": b"1"}))
        s.umount()
        s2 = BlockStore(str(tmp_path / "bs"))
        s2.mount()
        assert s2.read("c", "o") == payload
        assert s2.getattr("c", "o", "k") == b"v"
        assert s2.omap_get("c", "o") == {"m": b"1"}
        s2.umount()

    def test_deferred_wal_replay_on_mount(self, tmp_path):
        """A small write whose device apply was lost (real one-shot
        crash at wal.post_kv_commit — KV committed, deferred applies
        never ran) must be recovered from the WAL at mount."""
        from ceph_tpu.store import CrashPoint
        from ceph_tpu.store.blockstore import BlockStore
        from ceph_tpu.utils import faults
        s = self._mk(tmp_path)
        s.owner = "osd.9"
        s.apply_transaction(T().create_collection("c"))
        faults.get().reset(seed=1)
        faults.get().crash("wal.post_kv_commit", 1.0, "osd.9")
        try:
            with pytest.raises(CrashPoint):
                s.apply_transaction(T().write("c", "o", 0, b"deferred!"))
            assert s.frozen
            s.dev.close()
            s.db.close()
            s2 = BlockStore(str(tmp_path / "bs"))
            s2.mount()
            assert s2.read("c", "o") == b"deferred!"
            assert s2.counters["wal_torn_extent_repairs"] >= 1
            assert s2.counters["wal_records_replayed"] == 1
            s2.umount()
        finally:
            faults.get().reset(seed=0)

    def test_csum_mismatch_surfaces_eio(self, tmp_path):
        from ceph_tpu.store import StoreError
        from ceph_tpu.store.blockstore import BlockStore
        s = self._mk(tmp_path)
        pattern = b"\xabPATTERN\xcd" * 500
        s.apply_transaction(
            T().create_collection("c").write("c", "o", 0, pattern))
        s.umount()
        block = str(tmp_path / "bs" / "block")
        with open(block, "r+b") as f:
            raw = f.read()
            at = raw.index(b"\xabPATTERN\xcd")
            f.seek(at)
            f.write(b"\xee")                        # silent corruption
        s2 = BlockStore(str(tmp_path / "bs"))
        s2.mount()
        with pytest.raises(StoreError) as ei:
            s2.read("c", "o")
        assert ei.value.errno == 5                  # EIO
        s2.umount()

    def test_zero_punches_holes(self, tmp_path):
        s = self._mk(tmp_path)
        s.apply_transaction(
            T().create_collection("c").write("c", "o", 0, b"x" * 16384))
        free_before = s.alloc.total_free()
        s.apply_transaction(T().zero("c", "o", 0, 8192))
        assert s.read("c", "o", 0, 8192) == b"\x00" * 8192
        assert s.read("c", "o", 8192, 8192) == b"x" * 8192
        # the two fully-zeroed blocks were deallocated
        assert s.alloc.total_free() >= free_before + 8192
        s.umount()

    def test_cluster_on_blockstore(self, tmp_path):
        """OSDs run on the raw-block store end to end."""
        import time
        from ceph_tpu.client import RadosError
        from ceph_tpu.vstart import MiniCluster
        c = MiniCluster(num_mons=1, num_osds=3, store_kind="blockstore",
                        store_dir=str(tmp_path)).start()
        try:
            r = c.client()
            r.create_pool("bp", pg_num=4)
            io = r.open_ioctx("bp")
            end = time.time() + 20
            while True:
                try:
                    io.write_full("o", b"block-backed!")
                    break
                except RadosError:
                    if time.time() > end:
                        raise
                    time.sleep(0.3)
            assert io.read("o") == b"block-backed!"
        finally:
            c.stop()


# ---------------------------------------------------------------------------
# BlockStore WAL / extent crash-point matrix (the durability-frontier
# sites, mirroring TestCrashPointMatrix in test_journal.py): every
# site proves its promise — acked writes bit-exact after remount,
# torn extent windows either old or new, never interleaved.
# ---------------------------------------------------------------------------


class TestBlockStoreCrashMatrix:
    OWNER = "osd.7"

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from ceph_tpu.utils import faults
        faults.get().reset(seed=0)
        yield
        faults.get().reset(seed=0)

    def _mk(self, tmp_path, **kw):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs"), **kw)
        s.owner = self.OWNER
        s.mkfs()
        s.mount()
        return s

    def _remount(self, tmp_path):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs"))
        s.mount()
        return s

    def _arm(self, site, seed=0x5EED, reorder=False):
        from ceph_tpu.utils import faults
        faults.get().reset(seed=seed)
        faults.get().crash(site, 1.0, self.OWNER)
        if reorder:
            faults.get().fsync_reorder(1.0, self.OWNER)

    def _crash_write(self, s, oid, payload):
        from ceph_tpu.store import CrashPoint
        acked = []
        t = T().write("c", oid, 0, payload)
        t.register_on_commit(lambda: acked.append(oid))
        with pytest.raises(CrashPoint):
            s.queue_transactions([t])
        assert not acked, "a crashed write must never ack"
        assert s.frozen
        s.umount()

    @pytest.mark.parametrize("site", ["wal.pre_kv_commit",
                                      "wal.post_kv_commit",
                                      "wal.mid_apply"])
    @pytest.mark.parametrize("seed", [0x5EED, 0xA11CE])
    def test_wal_sites_old_or_new_never_interleaved(self, tmp_path,
                                                    site, seed):
        """Deferred (WAL-riding) overwrites through every WAL site:
        the base object and the prior payload stay bit-exact, the
        victim reads whole-old or whole-new — a mix of generations is
        the one forbidden outcome."""
        old = b"OLD." * 1024                      # 4 KiB: deferred
        new = b"NEWER..." * 512
        s = self._mk(tmp_path)
        s.apply_transaction(T().create_collection("c")
                            .write("c", "base", 0, b"base-bytes")
                            .write("c", "victim", 0, old))
        self._arm(site, seed=seed)
        self._crash_write(s, "victim", new)
        from ceph_tpu.utils import faults
        assert not faults.get().rules(), "crash rules are one-shot"
        s2 = self._remount(tmp_path)
        assert s2.read("c", "base") == b"base-bytes"
        got = s2.read("c", "victim")
        if site == "wal.pre_kv_commit":
            # the KV commit tore: whichever onode generation landed,
            # its payload must be WHOLE
            assert got in (old, new), "interleaved generations"
        else:
            # past the KV commit point: the write is durable even
            # though it never acked — replay must finish the job
            assert got == new
            assert s2.counters["wal_records_replayed"] >= 1
        s2.umount()

    def test_mid_cow_torn_extent_reads_old(self, tmp_path):
        """A direct (big, COW) overwrite torn mid-extent-copy: the
        committed onode still points at the old blocks, so every read
        after remount returns the OLD payload whole — the torn bytes
        sit in never-referenced blocks."""
        from ceph_tpu.store.blockstore import MIN_ALLOC
        old = bytes(range(256)) * (MIN_ALLOC // 8)    # many blocks
        new = b"\xeeNEW" * (len(old) // 4)
        s = self._mk(tmp_path, deferred_max=1024)     # force direct
        s.apply_transaction(T().create_collection("c")
                            .write("c", "victim", 0, old))
        self._arm("alloc.mid_cow")
        self._crash_write(s, "victim", new)
        s2 = self._remount(tmp_path)
        assert s2.read("c", "victim") == old
        # and the store keeps working: the allocator was repaired or
        # consistent, so new writes never corrupt surviving objects
        s2.apply_transaction(T().write("c", "fresh", 0, b"x" * 8192))
        assert s2.read("c", "victim") == old
        s2.umount()

    def test_pre_trim_crash_is_idempotent(self, tmp_path):
        """Crash between the deferred-apply fsync and the WAL trim:
        every record replays idempotently over already-applied state."""
        from ceph_tpu.store.blockstore import WAL_FLUSH_EVERY
        s = self._mk(tmp_path)
        s.apply_transaction(T().create_collection("c"))
        payloads = {}
        self._arm("wal.pre_trim")
        from ceph_tpu.store import CrashPoint
        try:
            for i in range(WAL_FLUSH_EVERY + 1):
                payloads[f"o{i}"] = f"payload-{i}-".encode() * 100
                s.apply_transaction(
                    T().write("c", f"o{i}", 0, payloads[f"o{i}"]))
        except CrashPoint:
            pass
        assert s.frozen, "the trim-site crash must have fired"
        s.umount()
        s2 = self._remount(tmp_path)
        for oid, data in payloads.items():
            if s2.exists("c", oid):
                assert s2.read("c", oid) == data
        # every write whose commit ACKED before the crash must be there
        assert s2.counters["wal_records_replayed"] >= 1
        s2.umount()

    def test_torn_kv_commit_keeps_other_objects_safe(self, tmp_path):
        """The torn-KV window's worst case is allocator damage (a
        block both referenced and free).  After remount the freelist
        verification must have made reuse safe: hammering new writes
        never corrupts the surviving objects."""
        s = self._mk(tmp_path)
        keep = {f"k{i}": f"keep-{i}-".encode() * 200 for i in range(4)}
        t = T().create_collection("c")
        for oid, data in keep.items():
            t.write("c", oid, 0, data)
        s.apply_transaction(t)
        self._arm("wal.pre_kv_commit", seed=0xBAD)
        self._crash_write(s, "victim", b"V" * 3000)
        s2 = self._remount(tmp_path)
        for i in range(50):
            s2.apply_transaction(
                T().write("c", f"churn{i % 7}", 0,
                          bytes([i % 251]) * 4096))
        for oid, data in keep.items():
            assert s2.read("c", oid) == data, f"{oid} corrupted"
        s2.umount()

    def test_torn_kv_commit_is_seed_deterministic(self, tmp_path):
        outcomes = []
        for run in range(2):
            sub = tmp_path / f"run{run}"
            sub.mkdir()
            s = self._mk(sub)
            s.apply_transaction(T().create_collection("c")
                                .write("c", "v", 0, b"OLD" * 700))
            self._arm("wal.pre_kv_commit", seed=0xABCD)
            self._crash_write(s, "v", b"NEW" * 700)
            s2 = self._remount(sub)
            outcomes.append((s2.read("c", "v"),
                             s2.counters["freelist_repairs"]))
            s2.umount()
        assert outcomes[0] == outcomes[1]

    def test_fsync_reorder_window_wal_applies(self, tmp_path):
        """The reordering model: deferred device applies buffered
        between fsync barriers survive as a SUBSET (durable B, lost
        earlier A).  Replay must still leave every committed write
        bit-exact — the WAL records outlive the lost device bytes."""
        from ceph_tpu.utils import faults
        s = self._mk(tmp_path)
        s.apply_transaction(T().create_collection("c"))
        # the reorder rule is armed BEFORE the buffered writes so
        # their pre-images are tracked; the crash rule comes last
        faults.get().reset(seed=0x5EED)
        faults.get().fsync_reorder(1.0, self.OWNER)
        payloads = {}
        for i in range(6):                   # buffered, un-fsync'd
            payloads[f"r{i}"] = f"reorder-{i}-".encode() * 150
            s.apply_transaction(
                T().write("c", f"r{i}", 0, payloads[f"r{i}"]))
        faults.get().crash("wal.post_kv_commit", 1.0, self.OWNER)
        self._crash_write(s, "r6", b"last-one" * 100)
        assert s.counters["fsync_reorder_windows"] == 1
        s2 = self._remount(tmp_path)
        for oid, data in payloads.items():
            assert s2.read("c", oid) == data, \
                f"{oid} lost to the reorder window"
        # r6's KV commit landed (post_kv_commit), so replay makes it
        # durable too
        assert s2.read("c", "r6") == b"last-one" * 100
        assert s2.counters["wal_torn_extent_repairs"] >= 1
        s2.umount()


# ---------------------------------------------------------------------------
# BlockStore extents (ISSUE 29): a run of whole blocks is one
# allocation, one checksum call and one device write; since ISSUE 44
# one run of the onode's map and one WAL target too.  The checksums and
# the crash plane keep block granularity.
# ---------------------------------------------------------------------------


def _seeded(n, seed):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _count_pwrites(s):
    """Record each write call the store makes on its device: (offset,
    bytes), a gather write of a run being one call."""
    from ceph_tpu.store.blockstore import _Device
    calls = []

    def pwrite(off, data):
        calls.append((off, len(data)))
        _Device.pwrite(s.dev, off, data)

    def pwritev(off, pieces):
        calls.append((off, sum(len(p) for p in pieces)))
        _Device.pwritev(s.dev, off, pieces)
    s.dev.pwrite, s.dev.pwritev = pwrite, pwritev
    return calls


def _blocks(head):
    """An onode's block map a block at a time, {block#: [poff, crc32c]}
    in the map's order: what the decoded onode was before it held runs
    (and the plain-dict KV form still is)."""
    import numpy as np
    from ceph_tpu.store.blockstore import MIN_ALLOC
    return {blk + i: [poff + i * MIN_ALLOC, csum]
            for blk, n, poff, csums in head["runs"]
            for i, csum in enumerate(
                np.frombuffer(csums, dtype="<u4").tolist())}


def _dump_onode_as_before(head, form):
    """An onode as the stores before ISSUE 44 wrote it: "dict", the
    block map a plain dict through the generic encoder (before ISSUE
    29), or "packed", sixteen bytes a block (ISSUE 29 to 43); the
    parent's `dump_onode`, kept here."""
    import numpy as np
    from ceph_tpu.store.blockstore import _MAP_ENTRY
    from ceph_tpu.utils import denc
    blocks = _blocks(head)
    if form == "dict":
        return denc.dumps({"size": head["size"], "xattrs": head["xattrs"],
                           "blocks": blocks})
    rows = np.empty(len(blocks), dtype=_MAP_ENTRY)
    if blocks:
        rows["blk"] = np.fromiter(blocks, dtype="<u8", count=len(blocks))
        ents = np.array(list(blocks.values()), dtype="<u8")
        rows["poff"], rows["csum"] = ents[:, 0], ents[:, 1]
    return denc.dumps({"size": head["size"], "xattrs": head["xattrs"],
                       "map": memoryview(rows.view(np.uint8))})


def _extents(head):
    """Runs of blocks that lie one behind the other on the device, in
    logical order: the map's runs, for an object without holes."""
    return len(head["runs"])


def _block_at_a_time_store(path):
    """A BlockStore with the parent's write path (ISSUE 29's "before"),
    kept here to write stores the way they were written: one allocation,
    one checksum call and one device write a 4 KiB block."""
    from ceph_tpu.store.blockstore import MIN_ALLOC, BlockStore

    class Old(BlockStore):
        def _put_run(self, st, head, blk, data, deferred):
            for i in range(len(data) // MIN_ALLOC):
                super()._put_run(
                    st, head, blk + i,
                    bytes(data[i * MIN_ALLOC: (i + 1) * MIN_ALLOC]),
                    deferred)

        def _write_staged(self, extents, tracked):
            calls = 0
            for poff, data in extents:
                for at in range(0, len(data), MIN_ALLOC):
                    self._dev_write(poff + at, data[at: at + MIN_ALLOC],
                                    tracked)
                    calls += 1
            return calls

    return Old(path)


class TestBlockStoreExtents:
    OWNER = "osd.7"

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from ceph_tpu.utils import faults
        faults.get().reset(seed=0)
        yield
        faults.get().reset(seed=0)

    def _mk(self, tmp_path, disk=True, **kw):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs") if disk else "", **kw)
        s.owner = self.OWNER
        s.mkfs()
        if disk:
            s.mount()
        s.apply_transaction(T().create_collection("c"))
        return s

    def _remount(self, tmp_path):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs"))
        s.mount()
        return s

    # (a) ----------------------------------------------------------------

    @pytest.mark.parametrize("disk", [False, True], ids=["mem", "disk"])
    def test_shard_file_is_one_device_write(self, tmp_path, disk):
        from ceph_tpu.ops.crc32c import crc32c
        from ceph_tpu.store.blockstore import MIN_ALLOC
        s = self._mk(tmp_path, disk=disk)
        payload = _seeded(512 * 1024, 29)
        calls = _count_pwrites(s)
        s.apply_transaction(T().write("c", "shard", 0, memoryview(payload)))
        assert len(calls) == 1 and calls[0][1] == len(payload)
        head = s._committed_onode("c", "shard")
        assert [run[:2] for run in head["runs"]] == [(0, 128)]
        blocks = _blocks(head)
        assert sorted(blocks) == list(range(128))
        for blk, (poff, csum) in blocks.items():
            block = payload[blk * MIN_ALLOC: (blk + 1) * MIN_ALLOC]
            assert csum == crc32c(0, block), blk
            assert s.dev.pread(poff, MIN_ALLOC) == block, blk
        assert s.read("c", "shard") == payload
        assert s.read("c", "shard", 5000, 300000) == payload[5000:305000]
        # one byte flipped under the store, in block 77
        at = blocks[77][0] + 1234
        s.dev.pwrite(at, bytes([s.dev.pread(at, 1)[0] ^ 0x40]))
        with pytest.raises(StoreError) as ei:
            s.read("c", "shard")
        assert ei.value.errno == 5 and "block 77" in str(ei.value)
        assert s.read("c", "shard", 0, 77 * MIN_ALLOC) == \
            payload[:77 * MIN_ALLOC]
        s.umount()

    @pytest.mark.parametrize("form", ["dict", "packed"])
    def test_onode_block_map_is_packed_and_the_old_form_still_reads(
            self, tmp_path, form):
        """The KV holds an onode's block map packed, a run an entry; a
        store whose onodes were written in a form before (the map a
        plain dict through the generic encoder; one packed entry a
        block) mounts, verifies its free list and reads, and rewrites
        an onode it touches."""
        from ceph_tpu.store.blockstore import (P_ONODE, dump_onode,
                                               load_onode)
        s = self._mk(tmp_path)
        body = _seeded(300 * 1024 + 77, 33)
        s.apply_transaction(T().write("c", "obj", 0, body)
                            .setattr("c", "obj", "a", b"v"))
        s.apply_transaction(T().touch("c", "empty"))
        head = s._committed_onode("c", "obj")
        blob = s.db.get(P_ONODE, "c/obj")
        assert load_onode(blob) == head
        assert [run[:2] for run in head["runs"]] == [(0, 76)]
        assert len(blob) < 76 * 4 + 24 + 64 and b"blocks" not in blob
        assert load_onode(dump_onode(s._committed_onode("c", "empty"))) \
            == {"size": 0, "xattrs": {}, "runs": []}
        # the same onodes as a store of before this form left them
        kvt = s.db.transaction()
        for oid in ("obj", "empty"):
            old = _dump_onode_as_before(s._committed_onode("c", oid), form)
            assert load_onode(old) == s._committed_onode("c", oid)
            kvt.set(P_ONODE, f"c/{oid}", old)
        s.db.submit_transaction(kvt, sync=True)
        s.umount()
        s2 = self._remount(tmp_path)
        assert s2.counters["freelist_repairs"] == 0
        assert s2.read("c", "obj") == body
        assert s2.getattr("c", "obj", "a") == b"v"
        assert s2.stat("c", "empty") == {"size": 0}
        s2.apply_transaction(T().write("c", "obj", 4096, b"x" * 4096))
        now = s2.db.get(P_ONODE, "c/obj")
        assert b"blocks" not in now and b"map" not in now
        assert [run[:2] for run in load_onode(now)["runs"]] == \
            [(0, 1), (1, 1), (2, 74)]
        assert s2.read("c", "obj") == \
            body[:4096] + b"x" * 4096 + body[8192:]
        s2.umount()

    @pytest.mark.parametrize("then", ["truncate", "remove"])
    def test_rollback_stash_takes_the_blocks(self, tmp_path, then):
        """An EC shard's stash before a whole rewrite or a delete
        (try_clone, then truncate to nothing or remove): the stash gets
        the shard's blocks where they lie; the only device write is the
        new body's, no block is read, and a remount reads both."""
        s = self._mk(tmp_path)
        old, new = _seeded(512 * 1024, 31), _seeded(512 * 1024, 32)
        s.apply_transaction(T().write("c", "shard", 0, old)
                            .setattr("c", "shard", "hinfo", b"h1"))
        lay = list(s._committed_onode("c", "shard")["runs"])
        calls = _count_pwrites(s)
        reads = []
        pread = s.dev.pread
        s.dev.pread = lambda off, n: reads.append(n) or pread(off, n)
        txn = T().try_clone("c", "shard", "stash")
        if then == "truncate":
            txn.truncate("c", "shard", 0).write("c", "shard", 0, new) \
               .setattr("c", "shard", "hinfo", b"h2")
        else:
            txn.try_remove("c", "shard")
        s.apply_transaction(txn)
        s.dev.pread = pread
        assert not reads
        assert [n for _off, n in calls] == \
            ([len(new)] if then == "truncate" else [])
        assert s._committed_onode("c", "stash")["runs"] == lay
        assert s.getattr("c", "stash", "hinfo") == b"h1"
        s.umount()
        s2 = self._remount(tmp_path)
        assert s2.read("c", "stash") == old
        if then == "truncate":
            assert s2.read("c", "shard") == new
            assert s2.getattr("c", "shard", "hinfo") == b"h2"
            live = {e[0] for o in ("shard", "stash") for e in
                    _blocks(s2._committed_onode("c", o)).values()}
            assert len(live) == 256
        else:
            assert not s2.exists("c", "shard")
        # the stash trimmed: its blocks are free again and reused
        s2.apply_transaction(T().try_remove("c", "stash"))
        s2.apply_transaction(T().write("c", "next", 0, old))
        assert s2.read("c", "next") == old
        s2.umount()

    @pytest.mark.parametrize("case", ["short", "over_iov_max"])
    def test_gather_write_of_a_run_lands_whole(self, tmp_path, monkeypatch,
                                               case):
        """Extents staged one behind the other on the device (here a
        block a write of one transaction) go out in one gather write: a
        short one is finished, and more buffers than one call takes are
        still one run."""
        import os
        from ceph_tpu.store import blockstore
        s = self._mk(tmp_path)
        real, seen = os.pwritev, []

        def pwritev(fd, bufs, off):
            seen.append(len(bufs))
            if case == "short" and len(seen) == 1:
                return real(fd, [bufs[0], bufs[1][:100]], off)
            return real(fd, bufs, off)
        monkeypatch.setattr(os, "pwritev", pwritev)
        n = 20 if case == "short" else blockstore.IOV_MAX + 256
        payload = _seeded(n * blockstore.MIN_ALLOC, 11)
        calls = _count_pwrites(s)
        t = T()
        for at in range(0, len(payload), blockstore.MIN_ALLOC):
            t.write("c", "o", at, payload[at: at + blockstore.MIN_ALLOC])
        s.apply_transaction(t)
        monkeypatch.undo()
        assert [run[:2] for run in
                s._committed_onode("c", "o")["runs"]] == [(0, n)]
        if case == "short":
            assert seen == [20] and len(calls) == 1 + 20
        else:
            assert seen == [blockstore.IOV_MAX, 256] and len(calls) == 1
        s.umount()
        s2 = self._remount(tmp_path)
        assert s2.read("c", "o") == payload
        s2.umount()

    # (b) ----------------------------------------------------------------

    @pytest.mark.parametrize("blocks", [40, 128])
    def test_fragmented_free_list_one_write_an_extent(self, tmp_path,
                                                      blocks):
        from ceph_tpu.store.blockstore import MIN_ALLOC
        s = self._mk(tmp_path)
        t = T()
        for i in range(64):
            t.write("c", f"f{i:02d}", 0, bytes([i]) * MIN_ALLOC)
        s.apply_transaction(t)
        t = T()
        for i in range(0, 64, 2):               # interleaved removes
            t.remove("c", f"f{i:02d}")
        s.apply_transaction(t)
        holes = [e for e in s.alloc.dump() if e[1] == MIN_ALLOC]
        assert len(holes) >= 31
        payload = _seeded(blocks * MIN_ALLOC, blocks)
        calls = _count_pwrites(s)
        s.apply_transaction(T().write("c", "big", 0, payload))
        head = s._committed_onode("c", "big")
        assert sum(run[1] for run in head["runs"]) == blocks
        assert len(calls) == _extents(head) > 31
        assert sum(n for _off, n in calls) == len(payload)
        assert s.read("c", "big") == payload
        for i in range(1, 64, 2):               # the neighbours stand
            assert s.read("c", f"f{i:02d}") == bytes([i]) * MIN_ALLOC
        s.umount()
        s2 = self._remount(tmp_path)
        assert s2.read("c", "big") == payload
        s2.umount()

    # (c) ----------------------------------------------------------------

    @pytest.mark.parametrize("offset", [0, 1, 4095, 4096, 6000])
    def test_head_and_tail_fragments_equal_a_model(self, tmp_path, offset):
        s = self._mk(tmp_path)
        model = bytearray(_seeded(90000, 1))
        s.apply_transaction(T().write("c", "o", 0, bytes(model)))
        for i, length in enumerate([1, 100, 4096, 4097, 8192, 12288 + 17,
                                    65536 + 100, 3 * 4096 - offset % 4096]):
            data = _seeded(length, 100 + i)
            calls = _count_pwrites(s)
            s.apply_transaction(T().write("c", "o", offset, data))
            if len(model) < offset + length:
                model.extend(b"\x00" * (offset + length - len(model)))
            model[offset: offset + length] = data
            assert s.read("c", "o") == bytes(model), (offset, length)
            # every byte written reached the device in whole blocks
            assert all(n % 4096 == 0 for _off, n in calls)
            first, last = offset // 4096, (offset + length - 1) // 4096
            assert sum(n for _off, n in calls) == (last - first + 1) * 4096
        # a hole, then a write that ends inside a block beyond it
        data = _seeded(5000, 7)
        s.apply_transaction(T().write("c", "o", 200000 + offset, data))
        model.extend(b"\x00" * (200000 + offset + 5000 - len(model)))
        model[200000 + offset: 200000 + offset + 5000] = data
        assert s.read("c", "o") == bytes(model)
        s.umount()
        s2 = self._remount(tmp_path)
        assert s2.read("c", "o") == bytes(model)
        s2.umount()

    # (d) ----------------------------------------------------------------

    @pytest.mark.parametrize("then", ["truncate0", "remove", "overwrite"])
    def test_blocks_freed_in_their_own_txn_stay_off_the_device(
            self, tmp_path, then):
        s = self._mk(tmp_path)
        free_before = s.alloc.total_free()
        size_before = s.dev.size
        a, b = _seeded(512 * 1024, 3), _seeded(512 * 1024, 4)
        t = T().write("c", "o", 0, a)
        if then == "truncate0":
            t.truncate("c", "o", 0)
        elif then == "remove":
            t.remove("c", "o")
        else:
            t.write("c", "o", 0, b)
        calls = _count_pwrites(s)
        s.apply_transaction(t)
        grown = s.dev.size - size_before
        if then == "overwrite":
            assert sum(n for _off, n in calls) == len(b)
            assert len(calls) == _extents(s._committed_onode("c", "o"))
            assert s.read("c", "o") == b
            assert s.alloc.total_free() == free_before + grown - len(b)
        else:
            assert calls == []
            assert s.alloc.total_free() == free_before + grown
            if then == "remove":
                assert not s.exists("c", "o")
            else:
                assert s.read("c", "o") == b""
        s.umount()

    # (e) ----------------------------------------------------------------

    @pytest.mark.parametrize("case", ["replay", "middle_free"])
    def test_deferred_run_is_one_wal_record(self, tmp_path, case):
        from ceph_tpu.store import CrashPoint
        from ceph_tpu.store.blockstore import MIN_ALLOC, P_WAL
        from ceph_tpu.utils import denc, faults
        s = self._mk(tmp_path)
        payload = _seeded(32 * 1024, 5)
        if case == "replay":
            faults.get().reset(seed=1)
            faults.get().crash("wal.post_kv_commit", 1.0, self.OWNER)
            calls = _count_pwrites(s)
            with pytest.raises(CrashPoint):
                s.apply_transaction(T().write("c", "o", 0, payload))
            assert calls == []                 # the applies never ran
            records = list(s.db.iterate(P_WAL, ""))
            assert len(records) == 1
            writes = denc.loads(records[0][1])["writes"]
            assert [len(d) for _o, d in writes] == [8 * MIN_ALLOC]
            assert b"".join(d for _o, d in writes) == payload
            s.umount()
            s2 = self._remount(tmp_path)
            assert s2.counters["wal_records_replayed"] == 1
            assert s2.counters["wal_torn_extent_repairs"] == 1
            assert s2.read("c", "o") == payload
            s2.umount()
            return
        calls = _count_pwrites(s)
        s.apply_transaction(T().write("c", "o", 0, payload))
        assert len(calls) == 1 and calls[0][1] == len(payload)
        head = s._committed_onode("c", "o")
        assert len(s._wal_applied) == 1 and s._wal_extents == \
            [(poff, n * MIN_ALLOC) for _b, n, poff, _c in head["runs"]]
        record = s._wal_applied[0]
        flushed = []
        real = s._flush_deferred

        def flush():
            # the trim must come BEFORE the freed block can be reused
            flushed.append(s.alloc.total_free())
            real()
        s._flush_deferred = flush
        free_before = s.alloc.total_free()
        patch = _seeded(MIN_ALLOC, 6)           # frees block 3 of 8
        s.apply_transaction(T().write("c", "o", 3 * MIN_ALLOC, patch))
        assert flushed and flushed[0] == free_before - MIN_ALLOC
        assert record not in s._wal_applied
        assert s.db.get(P_WAL, record) is None
        assert s.read("c", "o") == \
            payload[:3 * MIN_ALLOC] + patch + payload[4 * MIN_ALLOC:]
        s.umount()

    # (f) ----------------------------------------------------------------

    @pytest.mark.parametrize("site", ["alloc.mid_cow", "wal.mid_apply"])
    @pytest.mark.parametrize("seed", [0x5EED, 0xA11CE])
    def test_crash_sites_tear_one_block_of_a_run(self, tmp_path, site,
                                                 seed):
        from ceph_tpu.store import CrashPoint
        from ceph_tpu.store.blockstore import MIN_ALLOC
        from ceph_tpu.utils import faults
        deferred = site == "wal.mid_apply"
        s = self._mk(tmp_path,
                     deferred_max=(1 << 20) if deferred else 1024)
        old, new = _seeded(512 * 1024, 8), _seeded(512 * 1024, 9)
        s.apply_transaction(T().write("c", "victim", 0, old))
        if deferred:
            s._flush_deferred()         # old is durable, nothing buffered
        faults.get().reset(seed=seed)
        faults.get().fsync_reorder(1.0, self.OWNER)
        faults.get().crash(site, 1.0, self.OWNER)
        blocks = []
        real = s._dev_write

        def dev_write(poff, data, tracked):
            assert tracked
            blocks.append(len(data))
            real(poff, data, tracked)
        s._dev_write = dev_write
        calls = _count_pwrites(s)
        with pytest.raises(CrashPoint):
            s.apply_transaction(T().write("c", "victim", 0, new))
        assert s.frozen and not faults.get().rules()
        # the run went to the device a block at a time: a seeded number
        # whole, then exactly one torn, and the reordering model rolled
        # blocks back, never a run
        assert blocks[:-1] == [MIN_ALLOC] * (len(blocks) - 1)
        assert blocks[-1] < MIN_ALLOC and len(blocks) <= 128
        assert all(n <= MIN_ALLOC for _off, n in calls)
        assert s.counters["fsync_reorder_windows"] == 1
        s.umount()
        s2 = self._remount(tmp_path)
        got = s2.read("c", "victim")
        assert got in (old, new), "a mix of generations"
        assert got == (new if deferred else old)
        if deferred:
            assert s2.counters["wal_records_replayed"] == 1
            assert s2.counters["wal_torn_extent_repairs"] >= 1
        s2.apply_transaction(T().write("c", "fresh", 0, b"x" * 8192))
        assert s2.read("c", "victim") == got
        s2.umount()

    # (g) ----------------------------------------------------------------

    @staticmethod
    def _history(s):
        """A few transactions of every shape the write path has."""
        s.apply_transaction(T().create_collection("c"))
        s.apply_transaction(T().write("c", "shard", 0, _seeded(512 * 1024, 1))
                            .setattr("c", "shard", "hinfo", b"h")
                            .omap_setkeys("c", "shard", {"k": b"v"}))
        s.apply_transaction(T().write("c", "small", 0, _seeded(32 * 1024, 2)))
        s.apply_transaction(T().write("c", "shard", 6000, _seeded(70000, 3)))
        s.apply_transaction(T().write("c", "small", 4095, _seeded(4098, 4)))
        s.apply_transaction(T().remove("c", "small")
                            .write("c", "again", 100, _seeded(300000, 5)))
        s.apply_transaction(T().zero("c", "shard", 8192, 20000)
                            .truncate("c", "again", 250001)
                            .clone("c", "again", "copy"))

    @pytest.mark.parametrize("case", ["mounts_and_reads", "identical"])
    def test_store_written_a_block_at_a_time(self, tmp_path, case):
        """A store directory the parent's write path made mounts and
        reads under this one; and both paths leave the same onodes, the
        same free list and the same block file."""
        from ceph_tpu.store.blockstore import BlockStore
        old = _block_at_a_time_store(str(tmp_path / "old"))
        old.mkfs()
        old.mount()
        calls = _count_pwrites(old)
        self._history(old)
        assert max(n for _off, n in calls) == 4096
        want = {o: old.read("c", o) for o in ("shard", "again", "copy")}
        old.umount()
        if case == "mounts_and_reads":
            s = BlockStore(str(tmp_path / "old"))
            s.mount()
            for oid, data in want.items():
                assert s.read("c", oid) == data
            assert not s.exists("c", "small")
            assert s.getattr("c", "shard", "hinfo") == b"h"
            s.apply_transaction(
                T().write("c", "shard", 4096, _seeded(8192, 6)))
            data = bytearray(want["shard"])
            data[4096: 4096 + 8192] = _seeded(8192, 6)
            assert s.read("c", "shard") == bytes(data)
            s.umount()
            return
        new = BlockStore(str(tmp_path / "new"))
        new.mkfs()
        new.mount()
        self._history(new)
        new.umount()
        rows = []
        for name in ("old", "new"):
            s = BlockStore(str(tmp_path / name))
            s.mount()
            rows.append({p: list(s.db.iterate(p, "")) for p in "SCOMW"})
            s.umount()
        assert rows[0] == rows[1]
        with open(tmp_path / "old" / "block", "rb") as f, \
                open(tmp_path / "new" / "block", "rb") as g:
            assert f.read() == g.read()


# ---------------------------------------------------------------------------
# BlockStore's block map holds runs (ISSUE 44): a shard file is one run
# from the write to the read; a partial overwrite, a hole punch or a
# truncate splits the run it touches; the map is canonical whatever the
# path that made it; every block is still held to its own checksum.
# ---------------------------------------------------------------------------


def _pattern(blk, n=4096):
    """Block content no library's stream decides: the fixtures below
    carry its checksums."""
    return bytes((i * 7 + blk * 13 + (i >> 8)) % 251 for i in range(n))


# One onode as the parent commit (PR 43) left it in the KV, in both
# forms stores hold: "packed" is its `dump_onode`'s value (sixteen
# bytes a block, in the order the blocks were written), "dict" the
# generic encoding of the decoded head (stores from before ISSUE 29).
# The object: size 40960, blocks {5, 2, 3, 0, 7, 8, 9} at the device
# offsets of _OLD_LAYOUT, block b holding _pattern(b) but 5 (its first
# 1000 bytes, zero-padded) and 8 (_pattern(18)); xattr a=v.
_OLD_LAYOUT = {5: 0, 2: 8192, 3: 12288, 0: 16384, 7: 20480, 8: 4096,
               9: 28672}
_OLD_ONODES = {
    "packed": (
        b"\t\x03\x06\x04size\x03\x80\x80\x05\x06\x06xattrs\t\x01\x06\x01a"
        b"\x05\x01v\x06\x03map\x05p\x05\x00\x00\x00\x00\x00\x00\x00\x00\x00"
        b"\x00\x00\x11R\xa3%\x02\x00\x00\x00\x00 \x00\x00\x00\x00\x00\x00\x93"
        b"\xf7(\x1c\x03\x00\x00\x00\x000\x00\x00\x00\x00\x00\x00+)\r\xfd\x00"
        b"\x00\x00\x00\x00@\x00\x00\x00\x00\x00\x00\xf4\x0fp'\x07\x00\x00\x00"
        b"\x00P\x00\x00\x00\x00\x00\x00\xff\xe1\x0c\x95\x08\x00\x00\x00\x00"
        b"\x10\x00\x00\x00\x00\x00\x00hS!\x93\t\x00\x00\x00\x00p\x00\x00\x00"
        b"\x00\x00\x00\xff\xf0\xd6\xc3"),
    "dict": (
        b"\t\x03\x06\x04size\x03\x80\x80\x05\x06\x06xattrs\t\x01\x06\x01a"
        b"\x05\x01v\x06\x06blocks\t\x07\x03\n\x07\x02\x03\x00\x03\xa2\xc8\x9a"
        b"\xda\x04\x03\x04\x07\x02\x03\x80\x80\x01\x03\xa6\xde\xc7\xc2\x03\x03"
        b"\x06\x07\x02\x03\x80\xc0\x01\x03\xd6\xa4\xe9\xd0\x1f\x03\x00\x07\x02"
        b"\x03\x80\x80\x02\x03\xe8\xbf\x80\xf7\x04\x03\x0e\x07\x02\x03\x80\xc0"
        b"\x02\x03\xfe\x87\xe7\xd0\x12\x03\x10\x07\x02\x03\x80@\x03\xd0\xcd\x8a"
        b"\xb2\x12\x03\x12\x07\x02\x03\x80\xc0\x03\x03\xfe\xc3\xb7\xbd\x18"),
}
_OLD_FREELIST = (b"\x07\x02\x07\x02\x03\x80\x80\x03\x03\x80@\x07\x02\x03\x80"
                 b"\x80\x04\x03\x80\x80|")
_OLD_SUPER = (b"\t\x02\x06\tmin_alloc\x03\x80@\x06\x08dev_size\x03\x80\x80"
              b"\x80\x01")


class _MapModel:
    """What the store should hold, the plain way: an object is a size,
    its xattrs and {block#: bytes of one block}; a block that is not
    there is a hole."""
    B = 4096

    def __init__(self):
        self.objs = {}

    def obj(self, oid):
        return self.objs.setdefault(oid, {"size": 0, "blocks": {}})

    def write(self, oid, offset, data, zero=False):
        o, B = self.obj(oid), self.B
        pos = 0
        while pos < len(data):
            blk, boff = divmod(offset + pos, B)
            take = min(len(data) - pos, B - boff)
            cur = bytearray(o["blocks"].get(blk, bytes(B)))
            cur[boff: boff + take] = data[pos: pos + take]
            if zero and not any(cur):
                o["blocks"].pop(blk, None)
            else:
                o["blocks"][blk] = bytes(cur)
            pos += take
        o["size"] = max(o["size"], offset + len(data))

    def truncate(self, oid, size):
        o, B = self.obj(oid), self.B
        if size < o["size"]:
            for blk in [b for b in o["blocks"] if b * B >= size]:
                del o["blocks"][blk]
            blk, keep = divmod(size, B)
            if keep and blk in o["blocks"]:
                kept = o["blocks"][blk][:keep]
                if any(kept):
                    o["blocks"][blk] = kept + bytes(B - keep)
                else:
                    del o["blocks"][blk]
        o["size"] = size

    def clone(self, src, dst):
        o = self.objs[src]
        self.objs[dst] = {"size": o["size"], "blocks": dict(o["blocks"])}

    def read(self, oid):
        o, B = self.objs[oid], self.B
        out = bytearray(o["size"])
        for blk, data in o["blocks"].items():
            piece = data[: max(0, o["size"] - blk * B)]
            out[blk * B: blk * B + len(piece)] = piece
        return bytes(out)


class TestBlockStoreRuns:

    def _mk(self, tmp_path, disk=True):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs") if disk else "")
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c"))
        return s

    # -- the map against a plain model ---------------------------------------

    @staticmethod
    def _agrees(s, model, rng):
        import numpy as np
        from ceph_tpu.store.blockstore import MIN_ALLOC
        held = []
        for oid, o in model.objs.items():
            want = model.read(oid)
            assert s.stat("c", oid) == {"size": o["size"]}, oid
            assert s.read("c", oid) == want, oid
            if want:
                lo = int(rng.integers(0, len(want)))
                n = int(rng.integers(1, len(want) - lo + 1))
                assert s.read("c", oid, lo, n) == want[lo: lo + n], \
                    (oid, lo, n)
                buf = np.full(n + 3, 0xAA, dtype=np.uint8)
                assert s.read_into("c", oid, buf[:n], lo) == n
                assert buf.tobytes() == want[lo: lo + n] + b"\xaa" * 3
            runs = s._committed_onode("c", oid)["runs"]
            assert set(_blocks({"runs": runs})) == set(o["blocks"]), oid
            for (blk, n, poff, csums), nxt in zip(runs, runs[1:] + [None]):
                assert n > 0 and len(csums) == 4 * n
                held.append((poff, n * MIN_ALLOC))
                # sorted, apart, and joined wherever they could be
                assert nxt is None or blk + n < nxt[0] or (
                    blk + n == nxt[0] and poff + n * MIN_ALLOC != nxt[2])
        assert sorted(s.collection_list("c")) == sorted(model.objs)
        # the free list is the device less what the maps hold
        taken = sorted(held + [tuple(e) for e in s.alloc.dump()])
        assert all(a + n <= b for (a, n), (b, _m) in zip(taken, taken[1:]))
        assert sum(n for _a, n in taken) == s.dev.size

    @pytest.mark.parametrize("seed,disk", [(1, False), (2, False),
                                           (3, False), (4, False),
                                           (5, True), (6, True)])
    def test_random_history_equals_a_block_model(self, tmp_path, seed, disk):
        """Writes, zeros, truncates, clones, clones that take (an EC
        shard's stash) and moves, one to four a transaction so that an
        op meets what the ops before it staged: after every commit the
        reads, the sizes, the block sets and the free list are the
        model's, and the map is sorted and canonical."""
        import numpy as np
        rng = np.random.default_rng(seed)
        s = self._mk(tmp_path, disk=disk)
        model = _MapModel()
        names = [f"o{i}" for i in range(5)]
        span = 40 * 4096

        def some_bytes(n):
            return rng.integers(0, 256, n, dtype=np.uint8).tobytes()

        def extent():
            off = int(rng.integers(0, span))
            if rng.random() < 0.5:
                off -= off % 4096
            n = int(rng.choice([1, 100, 4096, 8192, 5000, 40000, 70000]))
            if rng.random() < 0.5:
                n = max(1, n - n % 4096)
            return off, n

        for step in range(60):
            t = T()
            for _ in range(int(rng.integers(1, 5))):
                kind = rng.choice(["write", "write", "write", "zero",
                                   "truncate", "clone", "stash", "move",
                                   "remove"])
                oid = str(rng.choice(names))
                have = oid in model.objs
                other = str(rng.choice([n for n in names if n != oid]))
                if kind == "write":
                    off, n = extent()
                    data = some_bytes(n)
                    t.write("c", oid, off, data)
                    model.write(oid, off, data)
                elif kind == "zero":
                    off, n = extent()
                    t.zero("c", oid, off, n)
                    model.write(oid, off, bytes(n), zero=True)
                elif kind == "truncate":
                    size = int(rng.integers(0, span))
                    t.truncate("c", oid, size)
                    model.truncate(oid, size)
                elif kind == "clone" and have:
                    t.clone("c", oid, other)
                    model.clone(oid, other)
                elif kind == "stash" and have:
                    # the copy takes the blocks: the next op empties or
                    # removes the source
                    t.try_clone("c", oid, other)
                    model.clone(oid, other)
                    if rng.random() < 0.5:
                        t.truncate("c", oid, 0)
                        model.truncate(oid, 0)
                    else:
                        t.try_remove("c", oid)
                        del model.objs[oid]
                elif kind == "move" and have:
                    t.collection_move_rename("c", oid, "c", other)
                    model.clone(oid, other)
                    del model.objs[oid]
                elif kind == "remove" and have:
                    t.remove("c", oid)
                    del model.objs[oid]
            s.apply_transaction(t)
            self._agrees(s, model, rng)
        assert s.journal_stats()["runs_per_onode"] > 1
        s.umount()
        if disk:
            from ceph_tpu.store.blockstore import BlockStore
            s2 = BlockStore(str(tmp_path / "bs"))
            s2.mount()
            assert s2.counters["freelist_repairs"] == 0
            self._agrees(s2, model, rng)
            s2.umount()

    # -- stores of before ----------------------------------------------------

    @pytest.mark.parametrize("form", ["packed", "dict"])
    def test_parents_onode_bytes_mount_and_read(self, tmp_path, form):
        from ceph_tpu.store.blockstore import (MIN_ALLOC, P_ONODE, P_SUPER,
                                               BlockStore, load_onode)
        s = self._mk(tmp_path)
        s.umount()
        # the parent's store, put down as it left it: its device, its
        # superblock and free list, its onode
        s = BlockStore(str(tmp_path / "bs"))
        s.db.open()
        s.dev.open()
        s.dev.grow(1 << 20)
        content = {blk: _pattern(blk) for blk in _OLD_LAYOUT}
        content[5] = _pattern(5, 1000) + bytes(MIN_ALLOC - 1000)
        content[8] = _pattern(18)
        for blk, poff in _OLD_LAYOUT.items():
            s.dev.pwrite(poff, content[blk])
        s.dev.flush()
        kvt = s.db.transaction()
        kvt.set(P_ONODE, "c/obj", _OLD_ONODES[form])
        kvt.set(P_SUPER, "freelist", _OLD_FREELIST)
        kvt.set(P_SUPER, "super", _OLD_SUPER)
        s.db.submit_transaction(kvt, sync=True)
        s.dev.close()
        s.db.close()
        want = bytearray(40960)
        for blk, data in content.items():
            want[blk * MIN_ALLOC: (blk + 1) * MIN_ALLOC] = data
        want = bytes(want)
        assert load_onode(_OLD_ONODES[form])["runs"] == \
            load_onode(_OLD_ONODES["packed"])["runs"]
        s = BlockStore(str(tmp_path / "bs"))
        s.mount()
        assert s.counters["freelist_repairs"] == 0
        assert s.stat("c", "obj") == {"size": 40960}
        assert s.getattr("c", "obj", "a") == b"v"
        assert s.read("c", "obj") == want
        assert s.read("c", "obj", 8000, 20000) == want[8000:28000]
        assert [run[:3] for run in s._committed_onode("c", "obj")["runs"]] \
            == [(0, 1, 16384), (2, 2, 8192), (5, 1, 0), (7, 1, 20480),
                (8, 1, 4096), (9, 1, 28672)]
        # a write rewrites the onode in today's form; the rest stands
        s.apply_transaction(T().write("c", "obj", 3 * MIN_ALLOC + 7, b"new"))
        want = want[:3 * MIN_ALLOC + 7] + b"new" + want[3 * MIN_ALLOC + 10:]
        assert s.read("c", "obj") == want
        s.umount()
        s = BlockStore(str(tmp_path / "bs"))
        s.mount()
        assert s.read("c", "obj") == want
        blob = s.db.get(P_ONODE, "c/obj")
        assert b"map" not in blob and b"blocks" not in blob
        s.umount()

    # -- every block against its own checksum ---------------------------------

    @pytest.mark.parametrize("how", ["read", "read_into", "range",
                                     "clone", "rmw"])
    def test_flipped_byte_in_block_77_is_eio_naming_it(self, tmp_path, how):
        import numpy as np
        from ceph_tpu.store.blockstore import MIN_ALLOC
        s = self._mk(tmp_path)
        payload = _seeded(512 * 1024, 44)
        s.apply_transaction(T().write("c", "shard", 0, payload))
        (_blk, n, poff, _csums), = s._committed_onode("c", "shard")["runs"]
        assert n == 128
        at = poff + 77 * MIN_ALLOC + 4000
        s.dev.pwrite(at, bytes([s.dev.pread(at, 1)[0] ^ 0x01]))
        with pytest.raises(StoreError) as ei:
            if how == "read":
                s.read("c", "shard")
            elif how == "read_into":
                s.read_into("c", "shard", np.empty(len(payload), np.uint8))
            elif how == "range":
                s.read("c", "shard", 77 * MIN_ALLOC + 10, 5)
            elif how == "clone":
                s.apply_transaction(T().clone("c", "shard", "copy"))
            else:
                s.apply_transaction(
                    T().write("c", "shard", 77 * MIN_ALLOC + 1, b"x"))
        assert ei.value.errno == 5 and "block 77" in str(ei.value)
        # the blocks either side read; nothing of a failed txn stays
        assert s.read("c", "shard", 0, 77 * MIN_ALLOC) == \
            payload[:77 * MIN_ALLOC]
        assert s.read("c", "shard", 78 * MIN_ALLOC) == \
            payload[78 * MIN_ALLOC:]
        assert not s.exists("c", "copy")
        s.umount()

    # -- what a whole-file read and a shard commit cost the interpreter --------

    @staticmethod
    def _lines_run(fn):
        """Lines of blockstore.py the interpreter ran inside `fn`: a
        loop a block shows as some hundreds of them."""
        import sys
        from ceph_tpu.store import blockstore
        seen = [0]

        def tracer(frame, event, _arg):
            if frame.f_code.co_filename != blockstore.__file__:
                return None
            if event == "line":
                seen[0] += 1
            return tracer
        sys.settrace(tracer)
        try:
            fn()
        finally:
            sys.settrace(None)
        return seen[0]

    @pytest.mark.parametrize("what", ["read", "read_into", "commit"])
    def test_whole_file_costs_a_run_not_its_blocks(self, tmp_path, what):
        """In the style of ISSUE 36's count (crc32c_combine 1,397 -> 0):
        a shard file's read makes no copy of it (the device read's
        buffer is what comes back, or the caller's own is filled) and
        runs the same few lines for 128 blocks as for 1,024; so does
        its commit.  The parent ran some 900 lines a 128-block read."""
        import tracemalloc
        import numpy as np
        s = self._mk(tmp_path)
        # (room first: the device grows a MiB a step of the allocator)
        s.apply_transaction(T().write("c", "room", 0, bytes(6 << 20)))
        s.apply_transaction(T().remove("c", "room"))
        lines = {}
        for blocks in (128, 1024):
            payload = _seeded(blocks * 4096, blocks)
            oid = f"f{blocks}"

            def commit():
                s.apply_transaction(
                    T().write("c", oid, 0, payload)
                    .setattr("c", oid, "hinfo", b"h" * 40))
            if what == "commit":
                lines[blocks] = self._lines_run(commit)
                assert s.read("c", oid) == payload
                continue
            commit()
            buf = np.empty(len(payload), dtype=np.uint8)
            if what == "read":
                def read():
                    return s.read("c", oid)
            else:
                def read():
                    s.read_into("c", oid, buf)
                    return buf
            assert bytes(read()) == payload
            before = s.journal_stats()
            tracemalloc.start()
            got = read()
            _now, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            # at most the file itself, once (none of it for read_into)
            assert peak <= (len(payload) if what == "read" else 0) + 32768
            lines[blocks] = self._lines_run(read)
            after = s.journal_stats()
            assert after["reads"] - before["reads"] == 2
            assert after["reads_whole_run"] - before["reads_whole_run"] == 2
            assert bytes(got) == payload
        assert lines[128] == lines[1024] < (400 if what == "commit"
                                            else 120), lines
        print(what, lines)
        stats = s.journal_stats()
        if what != "commit":
            assert stats["read_whole_run_share"] == 1.0
        s.umount()

    def test_a_resident_shard_file_is_a_few_objects_to_the_collector(
            self, tmp_path):
        """The decoded onode of a shard file was 129 containers the
        cycle collector tracks (a dict of 128 lists); as one run it is
        its head, its xattrs and its list of runs."""
        import gc
        s = self._mk(tmp_path, disk=False)
        for i in range(20):
            s.apply_transaction(T().write("c", f"s{i}", 0,
                                          _seeded(512 * 1024, i))
                                .setattr("c", f"s{i}", "hinfo", b"h"))
        gc.collect()        # a tuple of ints and bytes is untracked now

        def tracked(obj, seen):
            if id(obj) in seen or not gc.is_tracked(obj):
                return 0
            seen.add(id(obj))
            return 1 + sum(tracked(r, seen) for r in gc.get_referents(obj))
        heads = list(s._onodes._heads.values())
        assert len(heads) >= 20
        assert sum(tracked(h, set()) for h in heads) <= 3 * len(heads)
        assert s._onodes._weight == sum(
            1 + sum(run[1] for run in h["runs"]) for h in heads)
        s.umount()

    def test_wal_span_and_perf_dump_say_runs(self, tmp_path):
        from ceph_tpu.utils import optracker
        from ceph_tpu.utils.clock import ManualClock
        s = self._mk(tmp_path)
        trk = optracker.OpTracker(ManualClock(), history_size=8)

        def wal_args(txn):
            op = trk.create("w")
            with optracker.op_context(op):
                s.apply_transaction(txn)
            op.finish()
            doc = trk.dump_historic_ops()["ops"][-1]
            (wal,) = [sp for sp in doc["spans"] if sp["name"] == "wal"]
            return wal["args"]
        args = wal_args(T().write("c", "big", 0, _seeded(4 << 20, 1))
                        .touch("c", "meta"))
        assert (args["onodes"], args["runs"], args["blocks"],
                args["dev_writes"]) == (2, 1, 1024, 1)
        # a 4 KiB write into it splits its run in three (the RBD write
        # into a 4 MiB object): three runs, one block, one device write
        args = wal_args(T().write("c", "big", 300 * 4096, b"x" * 4096))
        assert (args["onodes"], args["runs"], args["blocks"],
                args["dev_writes"]) == (1, 3, 1, 1)
        stats = s.journal_stats()
        assert stats["onodes_committed"] == 3 and stats["runs_committed"] == 4
        assert stats["runs_per_onode"] == pytest.approx(4 / 3)
        s.umount()


# ---------------------------------------------------------------------------
# BlockStore's decoded onodes (ISSUE 31): what the store keeps beside
# the KV is written at the commit point only, a txn edits copies, and
# nothing decoded outlives a crash, a freeze or a mount.
# ---------------------------------------------------------------------------


_CACHE_CASES = (
    ["raises_halfway", "installed_at_commit_only", "absent_is_a_select",
     "remove", "rmcoll", "move", "lru_bound", "mount_and_freeze",
     "torn_kv_prefix", "torn_kv_subset", "readers_see_commits_only",
     "wal_span_counts"] +
    ["panic:" + site for site in
     ("wal.pre_kv_commit", "wal.post_kv_commit", "wal.mid_apply",
      "wal.pre_trim", "alloc.mid_cow", "store.pre_apply",
      "store.post_apply", "pglog.append")])


class TestBlockStoreOnodeCache:
    OWNER = "osd.7"

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        from ceph_tpu.utils import faults
        faults.get().reset(seed=0)
        yield
        faults.get().reset(seed=0)

    def _mk(self, tmp_path, kv, **kw):
        from ceph_tpu.store.blockstore import BlockStore
        s = BlockStore(str(tmp_path / "bs") if kv == "sqlite" else "", **kw)
        s.owner = self.OWNER
        s.mkfs()
        s.mount()
        s.apply_transaction(T().create_collection("c"))
        return s

    def _remount(self, tmp_path, kv, dead):
        """A new store over what the dead one left: its directory, or
        (no path) its MemDB and its in-memory device."""
        from ceph_tpu.store.blockstore import BlockStore
        if kv == "sqlite":
            s = BlockStore(str(tmp_path / "bs"))
        else:
            s = BlockStore()
            s.db, s.dev = dead.db, dead.dev
        s.mount()
        return s

    @staticmethod
    def _kv_head(s, oid, cid="c"):
        """The onode as the KV holds it, round the store."""
        from ceph_tpu.store.blockstore import P_ONODE, load_onode
        blob = s.db.get(P_ONODE, f"{cid}/{oid}")
        return None if blob is None else load_onode(blob)

    def _agrees_with_kv(self, s, oids, data=True):
        """`data=False` for a dead store: the blocks of a commit whose
        deferred writes wait for the replay do not read yet."""
        for oid in oids:
            want = self._kv_head(s, oid)
            assert s.exists("c", oid) == (want is not None), oid
            if want is not None:
                assert s._committed_onode("c", oid) == want, oid
                assert s.stat("c", oid) == {"size": want["size"]}
                assert s.getattrs("c", oid) == want["xattrs"]
                if data:
                    assert len(s.read("c", oid)) == want["size"]

    def test_crash_sites_are_all_cases(self):
        from ceph_tpu.store.blockstore import BlockStore
        assert {"panic:" + site for site in BlockStore().crash_sites()} == \
            {c for c in _CACHE_CASES if c.startswith("panic:")}

    @pytest.mark.parametrize("case", _CACHE_CASES)
    @pytest.mark.parametrize("kv", ["sqlite", "memdb"])
    def test_cache_rule(self, tmp_path, kv, case):
        if case.startswith("panic:"):
            return self._panic_then_remount(tmp_path, kv, case[6:])
        getattr(self, "_case_" + case)(tmp_path, kv)

    # -- a txn edits copies, and installs at the commit point ----------------

    def _case_raises_halfway(self, tmp_path, kv):
        import copy
        s = self._mk(tmp_path, kv)
        old = _seeded(20000, 1)
        s.apply_transaction(T().write("c", "o", 0, old)
                            .setattr("c", "o", "gen", b"1"))
        head = s._committed_onode("c", "o")
        was = copy.deepcopy(head)
        free = s.alloc.total_free()
        with pytest.raises(StoreError) as ei:
            s.apply_transaction(T().write("c", "o", 100, _seeded(9000, 2))
                                .truncate("c", "o", 5)
                                .setattr("c", "o", "gen", b"2")
                                .write("c", "fresh", 0, b"x" * 5000)
                                .remove("c", "never-was"))
        assert ei.value.errno == ENOENT
        # the head a reader held was not edited, and reads are at the
        # old generation, from the cache or from the KV
        assert head == was
        assert s.read("c", "o") == old
        assert s.getattrs("c", "o") == {"gen": b"1"}
        assert not s.exists("c", "fresh")
        assert s.alloc.total_free() == free
        self._agrees_with_kv(s, ["o", "fresh"])
        s.umount()

    def _case_installed_at_commit_only(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        s.apply_transaction(T().write("c", "o", 0, b"a" * 6000))
        old = s._committed_onode("c", "o")
        at_commit = []
        real = s.db.submit_transaction

        def submit(kvt, sync=False):
            # nothing of the txn is visible before the KV has it
            at_commit.append((s._onodes.get("c/o"), s._onodes.get("c/new"),
                              "d" in s._collections(), sync))
            real(kvt, sync=sync)
        s.db.submit_transaction = submit
        s.apply_transaction(T().create_collection("d")
                            .write("c", "o", 0, b"b" * 9000)
                            .write("c", "new", 0, b"n" * 100))
        # (the trim of the WAL record the old blocks rode is a KV
        # commit of its own, before the txn's)
        assert at_commit and \
            all(seen == (old, None, False, True) for seen in at_commit)
        assert at_commit[-1][0] is old and old["size"] == 6000
        now = s._committed_onode("c", "o")
        assert now is not old and now["size"] == 9000
        assert now is s._onodes.get("c/o")        # written through
        assert s._onodes.get("c/new")["size"] == 100
        assert s.collection_exists("d")
        assert s.list_collections() == ["c", "d"]
        calls = s.db.calls
        assert s.read("c", "o") == b"b" * 9000
        assert s.stat("c", "new") == {"size": 100}
        assert s.db.calls == calls                # no KV call for either
        self._agrees_with_kv(s, ["o", "new"])
        s.umount()

    def _case_absent_is_a_select(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        before = len(s._onodes)
        for _ in (1, 2):                # no negative entries
            calls = s.db.calls
            assert not s.exists("c", "nobody")
            with pytest.raises(StoreError):
                s.stat("c", "nobody")
            assert s.db.calls == calls + 2
        assert len(s._onodes) == before
        stats = s.journal_stats()
        assert stats["onode_lookups"] >= 4 and stats["onode_hits"] == 0
        s.umount()

    # -- evictions -------------------------------------------------------------

    def _case_remove(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        s.apply_transaction(T().write("c", "o", 0, b"1" * 5000))
        assert s._onodes.get("c/o") is not None
        s.apply_transaction(T().remove("c", "o"))
        assert s._onodes.get("c/o") is None and not s.exists("c", "o")
        with pytest.raises(StoreError):
            s.read("c", "o")
        s.apply_transaction(T().write("c", "o", 0, b"2" * 10))
        assert s.read("c", "o") == b"2" * 10
        self._agrees_with_kv(s, ["o"])
        s.umount()

    def _case_rmcoll(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        s.apply_transaction(T().create_collection("d")
                            .write("d", "a", 0, b"a" * 5000)
                            .write("d", "b", 0, b"b" * 5000)
                            .write("c", "keep", 0, b"k"))
        assert s.read("d", "a") == b"a" * 5000
        s.apply_transaction(T().remove_collection("d"))
        assert s._onodes.get("d/a") is None and s._onodes.get("d/b") is None
        assert not s.collection_exists("d")
        assert s.list_collections() == ["c"]
        with pytest.raises(StoreError):
            s.collection_list("d")
        with pytest.raises(StoreError):
            s.apply_transaction(T().write("d", "a", 0, b"late"))
        s.apply_transaction(T().create_collection("d")
                            .write("d", "a", 0, b"again"))
        assert s.read("d", "a") == b"again" and not s.exists("d", "b")
        assert s.read("c", "keep") == b"k"
        s.umount()

    def _case_move(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        data = _seeded(70000, 3)
        s.apply_transaction(T().create_collection("d")
                            .write("c", "src", 0, data)
                            .setattr("c", "src", "x", b"y"))
        assert s.read("c", "src") == data
        s.apply_transaction(
            T().collection_move_rename("c", "src", "d", "dst"))
        assert s._onodes.get("c/src") is None and not s.exists("c", "src")
        calls = s.db.calls
        assert s.read("d", "dst") == data
        assert s.getattr("d", "dst", "x") == b"y"
        assert s.db.calls == calls
        with pytest.raises(StoreError):
            s.apply_transaction(
                T().collection_move_rename("d", "dst", "nowhere", "z"))
        assert s.read("d", "dst") == data
        s.umount()

    def _case_lru_bound(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        s._onodes.limit = 40            # ten onodes of three blocks
        want = {}
        for i in range(30):
            want[f"o{i:02d}"] = _seeded(3 * 4096, i)
            s.apply_transaction(T().write("c", f"o{i:02d}", 0,
                                          want[f"o{i:02d}"]))
            assert s._onodes._weight <= 40 and len(s._onodes) <= 10
        assert s._onodes._weight == sum(
            1 + sum(run[1] for run in h["runs"])
            for h in s._onodes._heads.values())
        # the newest is resident, the oldest went and reads back
        calls = s.db.calls
        assert s.read("c", "o29") == want["o29"]
        assert s.db.calls == calls
        assert s._onodes.get("c/o00") is None
        assert s.read("c", "o00") == want["o00"]
        assert s.db.calls == calls + 1
        assert s._onodes.get("c/o00") is not None
        # a look-up keeps an onode from going first
        s.stat("c", "o21")
        for i in range(30, 38):
            s.apply_transaction(T().write("c", f"n{i}", 0, b"z" * 12288))
        assert s._onodes.get("c/o21") is not None
        assert s._onodes.get("c/o22") is None
        # an onode larger than the bound is not kept, and still reads
        big = _seeded(64 * 4096, 99)
        s.apply_transaction(T().write("c", "big", 0, big))
        assert len(s._onodes) == 0 and s._onodes._weight == 0
        assert s.read("c", "big") == big
        for oid, data in want.items():
            assert s.read("c", oid) == data
        s.umount()

    def _case_mount_and_freeze(self, tmp_path, kv):
        s = self._mk(tmp_path, kv)
        data = _seeded(30000, 5)
        s.apply_transaction(T().write("c", "o", 0, data))
        assert len(s._onodes) >= 1
        s.umount()
        s2 = self._remount(tmp_path, kv, s)
        assert len(s2._onodes) == 0 and s2._colls is None
        assert s2.read("c", "o") == data            # a miss fills it
        assert s2.journal_stats()["onode_hits"] == 0
        assert s2.read("c", "o") == data
        assert s2.journal_stats()["onode_hits"] == 1
        s2.freeze()
        assert len(s2._onodes) == 0
        calls = s2.db.calls
        assert s2.read("c", "o") == data            # from the KV
        assert s2.exists("c", "o")
        assert s2.db.calls == calls + 2 and len(s2._onodes) == 0
        from ceph_tpu.store import CrashPoint
        with pytest.raises(CrashPoint):
            s2.apply_transaction(T().write("c", "o", 0, b"late"))
        assert len(s2._onodes) == 0 and s2.read("c", "o") == data
        s2.umount()

    # -- crashes ---------------------------------------------------------------

    def _poison(self, s):
        """A head the KV does not hold, put where a surviving cache
        would serve it from."""
        s._onodes.put("c/bystander", {"size": 3, "runs": [],
                                      "xattrs": {"poison": b"1"}})
        assert s.getattrs("c", "bystander") == {"poison": b"1"}

    def _panic_then_remount(self, tmp_path, kv, site):
        from ceph_tpu.store import CrashPoint
        from ceph_tpu.utils import faults
        direct = site == "alloc.mid_cow"
        s = self._mk(tmp_path, kv, deferred_max=1024 if direct else 65536)
        old, new = _seeded(16384, 1), _seeded(16384, 2)
        s.apply_transaction(T().write("c", "victim", 0, old)
                            .write("c", "bystander", 0, b"by")
                            .setattr("c", "bystander", "gen", b"1"))
        assert s.read("c", "victim") == old         # resident
        self._poison(s)
        faults.get().reset(seed=0x5EED)
        faults.get().crash(site, 1.0, self.OWNER)
        acked = []
        t = T().write("c", "victim", 0, new).setattr("c", "victim", "g", b"2")
        t.register_on_commit(lambda: acked.append(1))
        try:
            s.queue_transactions([t])
            # the one site outside a store transaction: the PG's log
            # append, after its transaction was acknowledged
            assert site == "pglog.append" and acked
            s._maybe_crash(site)
            raise AssertionError("the crash rule did not fire")
        except CrashPoint:
            pass
        assert s.frozen and s.crash_site == site
        assert not faults.get().rules()
        assert not acked or site == "pglog.append"
        # the dead store answers from the KV: the poisoned head is gone
        assert len(s._onodes) == 0
        assert s.getattrs("c", "bystander") == {"gen": b"1"}
        self._agrees_with_kv(s, ["victim", "bystander"], data=False)
        assert len(s._onodes) == 0
        s.umount()
        s2 = self._remount(tmp_path, kv, s)
        assert len(s2._onodes) == 0
        got = s2.read("c", "victim")
        if site in ("store.pre_apply", "alloc.mid_cow", "wal.pre_trim"):
            # (the overwrite frees blocks an applied WAL record names,
            # so the trim, and its crash site, come before the commit)
            assert got == old
        elif site == "wal.pre_kv_commit":
            assert got in (old, new)
        else:
            assert got == new and s2.getattr("c", "victim", "g") == b"2"
        assert s2.getattrs("c", "bystander") == {"gen": b"1"}
        self._agrees_with_kv(s2, ["victim", "bystander"])
        s2.apply_transaction(T().write("c", "victim", 0, b"after"))
        assert s2.read("c", "victim")[:5] == b"after"
        s2.umount()

    def _torn_kv(self, tmp_path, kv, reorder):
        from ceph_tpu.store import CrashPoint
        from ceph_tpu.utils import faults
        s = self._mk(tmp_path, kv)
        oids = [f"t{i}" for i in range(6)]
        t = T()
        for oid in oids:
            t.write("c", oid, 0, oid.encode() * 1000).setattr(
                "c", oid, "gen", b"1")
        s.apply_transaction(t)
        self._poison(s)
        outcomes = set()
        faults.get().reset(seed=0xA11CE)
        faults.get().crash("wal.pre_kv_commit", 1.0, self.OWNER)
        if reorder:
            faults.get().fsync_reorder(1.0, self.OWNER)
        t = T()
        for oid in oids:
            t.write("c", oid, 0, oid.upper().encode() * 1000).setattr(
                "c", oid, "gen", b"2")
        t.remove("c", "bystander")
        with pytest.raises(CrashPoint):
            s.apply_transaction(t)
        # some of the commit's rows landed and some did not: whichever,
        # the dead store and the remounted one say what the KV says
        for oid in oids:
            outcomes.add(self._kv_head(s, oid)["xattrs"]["gen"])
        assert outcomes == {b"1", b"2"}, "the commit did not tear"
        assert len(s._onodes) == 0
        self._agrees_with_kv(s, oids, data=False)
        s.umount()
        s2 = self._remount(tmp_path, kv, s)
        # (of a subset the WAL record may be the row that was lost:
        # then the new onodes' blocks do not read, as at the parent)
        self._agrees_with_kv(s2, oids, data=not reorder)
        for oid in oids:
            gen = s2.getattr("c", oid, "gen")
            if gen == b"1" or not reorder:
                assert s2.read("c", oid) == (
                    oid if gen == b"1" else oid.upper()).encode() * 1000
        s2.umount()

    def _case_torn_kv_prefix(self, tmp_path, kv):
        self._torn_kv(tmp_path, kv, reorder=False)

    def _case_torn_kv_subset(self, tmp_path, kv):
        self._torn_kv(tmp_path, kv, reorder=True)

    # -- readers beside a committer ---------------------------------------------

    def _case_readers_see_commits_only(self, tmp_path, kv):
        import sys
        import time
        s = self._mk(tmp_path, kv)
        size = 3 * 4096 + 100

        def commit(gen):
            s.apply_transaction(
                T().write("c", "o", 0, bytes([gen % 251]) * size)
                .setattr("c", "o", "a", str(gen).encode())
                .setattr("c", "o", "b", str(gen).encode()))
        commit(0)
        started, done = [0], [0]
        stop = threading.Event()
        faults_seen = []

        def reader(kind):
            try:
                while not stop.is_set():
                    lo = done[0]
                    if kind == "data":
                        got = s.read("c", "o")
                        assert len(got) == size and got == got[:1] * size, \
                            "a mix of generations"
                        gens = [g for g in range(lo, started[0] + 1)
                                if g % 251 == got[0]]
                        assert gens, (lo, got[0], started[0])
                    else:
                        attrs = s.getattrs("c", "o")
                        assert attrs["a"] == attrs["b"], attrs
                        assert lo <= int(attrs["a"]) <= started[0]
            except BaseException as e:      # noqa: BLE001 - reported below
                faults_seen.append(e)
        threads = [threading.Thread(target=reader, args=(k,), daemon=True)
                   for k in ("data", "attrs")]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            end = time.monotonic() + 1.5
            gen = 0
            while time.monotonic() < end and not faults_seen:
                gen += 1
                started[0] = gen
                commit(gen)
                done[0] = gen
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=20)
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not faults_seen, faults_seen[0]
        assert gen >= 5
        stats = s.journal_stats()
        assert stats["onode_hits"] > gen        # readers and txns both hit
        s.umount()

    # -- the counts on the wal span ----------------------------------------------

    def _case_wal_span_counts(self, tmp_path, kv):
        from ceph_tpu.utils import optracker
        from ceph_tpu.utils.clock import ManualClock
        s = self._mk(tmp_path, kv)
        s.apply_transaction(T().touch("c", "_pgmeta"))
        trk = optracker.OpTracker(ManualClock(), history_size=8)

        def traced(txn):
            op = trk.create("w")
            before = s.journal_stats()
            with optracker.op_context(op):
                s.apply_transaction(txn)
            op.finish()
            doc = trk.dump_historic_ops()["ops"][-1]
            (wal,) = [sp for sp in doc["spans"] if sp["name"] == "wal"]
            after = s.journal_stats()
            moved = {k: after[k] - before[k] for k in
                     ("kv_calls", "commits", "onode_lookups", "onode_hits")}
            assert {k: wal["args"][k] for k in moved} == moved
            return wal["args"]

        def shard_txn(oid):
            # the shape of an EC shard's sub-op
            return (T().truncate("c", oid, 0)
                    .write("c", oid, 0, _seeded(512 * 1024, 7))
                    .setattr("c", oid, "hinfo", b"h" * 40)
                    .setattr("c", oid, "ver", b"v" * 8)
                    .setattr("c", "_pgmeta", "log", b"L" * 20000))
        # a new object: its one SELECT finds nothing, the PG's meta
        # object is resident, and the commit is one statement
        args = traced(shard_txn("new.s3"))
        assert args["blocks"] == 128 and args["dev_writes"] == 1
        assert args["kv_calls"] == 2 and args["commits"] == 1
        assert args["onode_lookups"] == 2 and args["onode_hits"] == 1
        # an overwrite: both resident, the commit alone crosses
        args = traced(shard_txn("new.s3"))
        assert args["kv_calls"] == 1
        assert args["onode_lookups"] == 2 and args["onode_hits"] == 2
        s.umount()
