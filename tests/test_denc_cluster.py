"""The codec's two walks under a running cluster and under its stores
(ISSUE 51): a healthy cluster's writes, reads and a deep scrub never
leave the compiled walk (`perf dump`, block `denc`), and what one walk
wrote to a store the other mounts and reads: the bytes on disk are one
format, whichever walk wrote them."""

import time

import pytest

from ceph_tpu import native
from ceph_tpu.client import RadosError
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils import denc
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
}
OBJECTS = {f"obj-{i}": bytes([i]) * (20_000 + 977 * i) for i in range(5)}


@pytest.fixture(autouse=True)
def _needs_both_walks():
    if native.get_ext() is None:
        pytest.skip("the native tier's extension cannot be built here "
                    "(no g++, or no Python.h): one walk only")


def _settle(cluster, io) -> None:
    """Write until the new pool's PGs are active."""
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            io.remove_object("settle")
            return
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)


def test_a_healthy_cluster_stays_on_the_compiled_walk():
    """Block `denc` of every daemon's `perf dump` (and the client's):
    over writes, reads and a deep scrub of an EC pool `python_calls`
    stands still and `native_calls` grows."""
    cluster = MiniCluster(num_mons=1, num_osds=4,
                          conf=Config(dict(CONF))).start()
    try:
        rados = cluster.client()
        rados.create_ec_pool("walks", "walks-prof",
                             {"plugin": "tpu", "k": 2, "m": 1}, pg_num=1)
        io = rados.open_ioctx("walks")
        _settle(cluster, io)

        def blocks() -> list[dict]:
            out = [osd.asok.execute("perf dump")["denc"]
                   for osd in cluster.osds.values()]
            out.append(cluster.mons[0].asok.execute("perf dump")["denc"])
            out.append(rados.perf_dump()["denc"])
            return out

        before = blocks()
        for block in before:
            assert set(block) == {"native_calls", "python_calls",
                                  "value_callbacks"}
        for oid, body in OBJECTS.items():
            io.write_full(oid, body)
        for oid, body in OBJECTS.items():
            assert io.read(oid) == body
        m = cluster.leader().osdmon.osdmap
        (pgid,) = [p for p in m.all_pgs() if p.pool == io.pool_id]
        _up, acting = m.pg_to_up_acting_osds(pgid)
        result = cluster.osds[acting[0]].pgs[pgid].scrub(deep=True)
        assert result["inconsistent"] == []
        after = blocks()
        # the counters are the process's: every daemon shows the same
        # walk, and none of them ever took the Python one
        assert {b["python_calls"] for b in after} == \
            {b["python_calls"] for b in before}
        assert min(b["native_calls"] for b in after) > \
            max(b["native_calls"] for b in before) + 10 * len(OBJECTS)
        assert denc.counters()["python_calls"] == after[0]["python_calls"]
    finally:
        cluster.stop()


def _use(monkeypatch, walk: str) -> None:
    if walk == "python":
        monkeypatch.setattr(native, "get_ext", lambda: None)
    else:
        monkeypatch.undo()


def _fill(store, cid: str) -> None:
    store.apply_transaction(
        Transaction().create_collection(cid)
        .write(cid, "big", 0, b"d" * 300_000)
        .write(cid, "small", 0, b"s" * 900)
        .setattr(cid, "big", "hinfo",
                 denc.dumps({"crcs": [1, 2**40, 3], "size": 300_000}))
        .setattr(cid, "small", "_", b"v" * 70)
        .omap_setkeys(cid, "small", {"k1": b"1", "k2": b"22"}))
    store.apply_transaction(
        Transaction().write(cid, "big", 100_000, b"e" * 5_000)
        .truncate(cid, "small", 500))


def _check(store, cid: str) -> None:
    want = b"d" * 100_000 + b"e" * 5_000 + b"d" * 195_000
    assert store.read(cid, "big") == want
    assert store.read(cid, "small") == b"s" * 500
    assert denc.loads(store.getattr(cid, "big", "hinfo")) == \
        {"crcs": [1, 2**40, 3], "size": 300_000}
    assert store.getattr(cid, "small", "_") == b"v" * 70
    assert store.omap_get(cid, "small") == {"k1": b"1", "k2": b"22"}


def _open(kind: str, path: str):
    if kind == "blockstore":
        from ceph_tpu.store.blockstore import BlockStore
        return BlockStore(path)
    if kind == "kstore":
        from ceph_tpu.store.kstore import KStore
        return KStore(path)
    from ceph_tpu.store.filestore import JournalFileStore
    return JournalFileStore(path)


@pytest.mark.parametrize("kind", ["blockstore", "kstore", "filestore"])
@pytest.mark.parametrize("writer,reader", [("python", "native"),
                                           ("native", "python")])
def test_a_store_one_walk_wrote_the_other_mounts(
        tmp_path, monkeypatch, kind, writer, reader):
    """mkfs and two transactions under one walk (the fallback's bytes
    are the bytes every earlier build wrote), then mount, read, write
    on and read again under the other."""
    path = str(tmp_path / kind)
    _use(monkeypatch, writer)
    store = _open(kind, path)
    store.mkfs()
    store.mount()
    _fill(store, "c1")
    _check(store, "c1")
    store.umount()
    _use(monkeypatch, reader)
    calls = denc.counters()
    store = _open(kind, path)
    store.mount()
    _check(store, "c1")
    _fill(store, "c2")
    store.umount()
    served = {k: v - calls[k] for k, v in denc.counters().items()}
    other = "python_calls" if reader == "native" else "native_calls"
    mine = "native_calls" if reader == "native" else "python_calls"
    assert served[other] == 0 and served[mine] > 0
    _use(monkeypatch, writer)
    store = _open(kind, path)
    store.mount()
    _check(store, "c1")
    _check(store, "c2")
    store.umount()


def test_both_walks_write_the_same_store(tmp_path, monkeypatch):
    """The same transactions under either walk leave the same keys and
    the same values in the store's KV."""
    from ceph_tpu.store.blockstore import BlockStore
    kvs = {}
    for walk in ("native", "python"):
        _use(monkeypatch, walk)
        store = BlockStore(str(tmp_path / walk))
        store.mkfs()
        store.mount()
        _fill(store, "c1")
        kvs[walk] = {(p, k): v for p in store.db.prefixes()
                     for k, v in store.db.iterate(p, "")}
        store.umount()
    assert kvs["native"].keys() == kvs["python"].keys()
    assert len(kvs["native"]) > 5
    for key, blob in kvs["native"].items():
        assert blob == kvs["python"][key], key


@pytest.mark.parametrize("writer,reader", [("python", "native"),
                                           ("native", "python")])
def test_osds_restart_onto_the_other_walk(tmp_path, monkeypatch,
                                          writer, reader):
    """A cluster on blockstore written under one walk; every OSD then
    comes back under the other on the SAME store (superblock, PG log
    and onodes reload), serves what was written and takes more."""
    _use(monkeypatch, writer)
    cluster = MiniCluster(num_mons=1, num_osds=3, store_kind="blockstore",
                          store_dir=str(tmp_path),
                          conf=Config(dict(CONF))).start()
    try:
        rados = cluster.client()
        rados.create_pool("cross", pg_num=2)
        io = rados.open_ioctx("cross")
        _settle(cluster, io)
        for oid, body in OBJECTS.items():
            io.write_full(oid, body)
        _use(monkeypatch, reader)
        for osd_id in sorted(cluster.osds):
            cluster.restart_osd(osd_id)
        for oid, body in OBJECTS.items():
            assert io.read(oid) == body
        io.write_full("after", b"a" * 9_000)
        assert io.read("after") == b"a" * 9_000
    finally:
        cluster.stop()
