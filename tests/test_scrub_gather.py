"""A PG scrub asks every acting OSD for its scan at once, scans its own
shards while they work, then gathers (ISSUE 43; PG::chunky_scrub's
NEW_CHUNK / BUILD_MAP / WAIT_REPLICAS): the peers' scans run side by
side, a silent peer costs its own scan and nothing else, and the scrub
CRC's row buckets are all compiled when the first one is."""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

OBJECTS = 6
OBJECT_BYTES = 16 * 1024
WIDTH = 4           # acting OSDs of either pool: three peers a scrub
SLEEP = 0.5
WARM = 120.0        # device fns compile on background threads


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=5, conf=Config({
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
        "osd_op_history_size": 4096,
    })).start()
    yield c
    c.stop()


class Rig:
    """One pool of one PG, its objects, and who holds what."""

    def __init__(self, cluster, kind: str):
        self.kind = kind
        rados = cluster.client()
        name = f"gather-{kind}"
        if kind == "ec":
            rados.create_ec_pool(name, f"{name}-prof", {
                "plugin": "tpu", "k": 2, "m": 2, "host_cutover": 1},
                pg_num=1)
        else:
            rados.create_pool(name, pg_num=1, size=WIDTH)
        self.io = rados.open_ioctx(name)
        end = time.time() + 60
        while True:
            try:
                self.io.write_full("settle", b"s")
                self.io.remove_object("settle")
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)
        self.oids = [f"o{i}" for i in range(OBJECTS)]
        for oid in self.oids:
            self.rewrite(oid)
        m = cluster.leader().osdmon.osdmap
        (self.pgid,) = [p for p in m.all_pgs()
                        if p.pool == self.io.pool_id]
        _up, acting = m.pg_to_up_acting_osds(self.pgid)
        self.acting = list(acting)
        assert len(self.acting) == WIDTH
        self.primary = cluster.osds[self.acting[0]]
        self.peers = [cluster.osds[o] for o in self.acting[1:]]
        self.pg = self.primary.pgs[self.pgid]

    def rewrite(self, oid: str) -> None:
        """Every copy (shard file) of `oid` anew, whatever a test did
        to one of them."""
        self.io.write_full(oid, oid.encode() * (OBJECT_BYTES // len(oid)))

    def name_on(self, oid: str, osd) -> str:
        """The file `osd` keeps of `oid`."""
        if self.kind == "rep":
            return oid
        return f"{oid}.s{self.acting.index(osd.whoami)}"

    def corrupt(self, oid: str, osd) -> str:
        name = self.name_on(oid, osd)
        osd.store.apply_transaction(
            Transaction().write(self.pg.cid, name, 3, b"\xbe\xef"))
        return name

    def remove(self, oid: str, osd) -> str:
        name = self.name_on(oid, osd)
        osd.store.apply_transaction(
            Transaction().remove(self.pg.cid, name))
        return name

    def wait_span(self) -> dict:
        """The `scrub.peer_wait` span of the PG's newest scrub."""
        doc = max((d for d in self.primary.op_tracker.dump_historic_ops()
                   ["ops"] if d.get("kind") == "scrub"
                   and f"pg_scrub({self.pgid} " in d["description"]),
                  key=lambda d: d["mstart"])
        (wait,) = [s for s in doc["spans"]
                   if s["name"] == "scrub.peer_wait"]
        return wait


@pytest.fixture(scope="module")
def rigs(cluster):
    made: dict = {}

    def get(kind: str) -> Rig:
        if kind not in made:
            made[kind] = Rig(cluster, kind)
        return made[kind]

    return get


def _before_each_scan(monkeypatch, osds, before) -> None:
    """`before()` runs on each of `osds`' own op worker, ahead of its
    scan."""
    for osd in osds:
        def scan(pg, deep, orig=osd._scan_pg):
            before()
            return orig(pg, deep)
        monkeypatch.setattr(osd, "_scan_pg", scan)


def _findings(rig: Rig, result: dict) -> list:
    """A scrub's findings in one form for both pool kinds:
    (file, osd, what) a damaged or absent copy."""
    out = []
    for item in result["inconsistent"]:
        if rig.kind == "ec":
            out.append((item["object"], item["osd"],
                        "missing" if item.get("missing") else "bad"))
            continue
        copies = item["copies"]
        good = max(set(map(repr, copies.values())),
                   key=list(map(repr, copies.values())).count)
        for osd, v in copies.items():
            if repr(v) != good:
                out.append((item["object"], osd,
                            "missing" if v is None else "bad"))
    return sorted(out)


def case_delayed(rig: Rig, monkeypatch) -> None:
    """Each peer's scan takes SLEEP longer: the scrub takes one such
    sleep and not one a peer."""
    _before_each_scan(monkeypatch, rig.peers, lambda: time.sleep(SLEEP))
    t0 = time.monotonic()
    result = rig.pg.scrub(deep=True)
    wall = time.monotonic() - t0
    assert result["inconsistent"] == []
    assert SLEEP <= wall < 2 * SLEEP, wall
    wait = rig.wait_span()
    assert wait["args"] == {"peers": WIDTH - 1, "answered": WIDTH - 1,
                            "late": 0}


def case_silent(rig: Rig, monkeypatch) -> None:
    """One peer never answers: the scrub returns at the deadline with
    the other peers' findings, and what the silent peer holds is not
    called missing."""
    silent, talking = rig.peers[-1], rig.peers[0]
    monkeypatch.setattr(rig.primary, "SCAN_TIMEOUT", 1.0)

    def swallow(conn, msg, req=None, orig=silent.send_osd_reply):
        if getattr(msg, "op", "") != "scanned":
            orig(conn, msg, req)
    monkeypatch.setattr(silent, "send_osd_reply", swallow)
    bad = rig.corrupt(rig.oids[1], talking)
    t0 = time.monotonic()
    result = rig.pg.scrub(deep=True)
    wall = time.monotonic() - t0
    assert 1.0 <= wall < 5.0, wall
    assert _findings(rig, result) == [(bad, talking.whoami, "bad")]
    if rig.kind == "rep":
        (item,) = result["inconsistent"]
        assert sorted(item["copies"]) == sorted(
            o for o in rig.acting if o != silent.whoami)
    wait = rig.wait_span()
    assert wait["args"] == {"peers": WIDTH - 1, "answered": WIDTH - 2,
                            "late": 1}
    assert 0.5 <= wait["t1"] - wait["t0"] <= wall


def case_damaged(rig: Rig, monkeypatch) -> None:
    """All peers' scans are in flight at once (each waits for the
    others before it starts), and the scrub still flags exactly the
    one damaged file and the one a holder lacks.  The daemons share
    this process's interpreter, so their scans take turns at the
    store: no two read at the same time."""
    gate = threading.Barrier(len(rig.peers))
    broken = []
    reading, most, count = [0], [0], threading.Lock()
    for osd in [rig.primary] + rig.peers:
        def read(*args, orig=osd.store.read, **kw):
            with count:
                reading[0] += 1
                most[0] = max(most[0], reading[0])
            try:
                time.sleep(0.002)
                return orig(*args, **kw)
            finally:
                with count:
                    reading[0] -= 1
        monkeypatch.setattr(osd.store, "read", read)

    def meet():
        try:
            gate.wait(5.0)
        except threading.BrokenBarrierError:
            broken.append(1)
    _before_each_scan(monkeypatch, rig.peers, meet)
    bad = rig.corrupt(rig.oids[2], rig.peers[0])
    gone = rig.remove(rig.oids[3], rig.peers[1])
    result = rig.pg.scrub(deep=True)
    assert not broken, "the peers' scans did not overlap"
    assert most[0] == 1
    assert _findings(rig, result) == sorted([
        (bad, rig.peers[0].whoami, "bad"),
        (gone, rig.peers[1].whoami, "missing")])


@pytest.mark.parametrize("case", [case_delayed, case_silent, case_damaged],
                         ids=lambda c: c.__name__[5:])
@pytest.mark.parametrize("kind", ["ec", "rep"])
def test_gathered_scrub(rigs, monkeypatch, kind, case):
    rig = rigs(kind)
    try:
        case(rig, monkeypatch)
    finally:
        monkeypatch.undo()
        for oid in rig.oids:
            rig.rewrite(oid)
    assert rig.pg.scrub(deep=True)["inconsistent"] == []


def test_many_gathers_at_once_each_get_their_own_answers(rigs):
    """More askers than cores on ONE daemon's RPC table, the
    interpreter switching threads every 10 us: every asker gets the
    answers to its own asks, and the table is empty afterwards."""
    import sys

    from ceph_tpu.osd.messages import MPGInfo
    rig = rigs("rep")
    osd = rig.primary
    wrong = []

    def ask(rounds: int) -> None:
        for _ in range(rounds):
            asks = {p.whoami: MPGInfo(
                op="scan", pgid=str(rig.pgid), deep=False, trace="",
                epoch=osd.osdmap.epoch) for p in rig.peers}
            tids = osd._send_calls(asks)
            got = osd._wait_calls(tids, time.monotonic() + 30.0)
            if sorted(got) != sorted(tids) or any(
                    got[o].rpc_tid != tids[o] or got[o].op != "scanned"
                    or sorted(got[o].info) != rig.oids for o in got):
                wrong.append((tids, got))

    every = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask, args=(5,), daemon=True)
                   for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(every)
    assert not any(t.is_alive() for t in threads)
    assert not wrong, wrong[:1]
    with osd._rpc_cv:
        assert not osd._rpc


# ---------------------------------------------------------------------------
# every row bucket of a size is compiled when the first one is
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size,first_rows", [(3008, 3), (1504, 40)])
def test_first_miss_of_a_size_warms_every_row_bucket(monkeypatch, size,
                                                     first_rows):
    monkeypatch.setattr(ec_pipeline, "_crc_device_dead", False)
    pipe = ec_pipeline.EcDevicePipeline(depth=2, coalesce_wait=0.001,
                                        device_shards=1)
    chan = ec_pipeline.crc_channel(size, max_coalesce=64)
    rng = np.random.default_rng(size)

    def crcs_of(rows: int) -> str:
        batch = rng.integers(0, 256, size=(rows, size), dtype=np.uint8)
        path, (out,) = pipe.submit(chan, batch.copy()).result(60)
        np.testing.assert_array_equal(
            np.asarray(out), crc_mod.crc32c_batch(batch))
        return path

    started = []
    failures = ec_pipeline.warm_stats()["warm_failures"]
    start = ec_pipeline.start_warm_thread
    monkeypatch.setattr(
        ec_pipeline, "start_warm_thread",
        lambda target, name: (started.append(name), start(target, name)))
    try:
        # the first batch of the size misses: the host serves it, and
        # ONE warm thread compiles its bucket and every other one
        assert crcs_of(first_rows) == "host"
        assert started == ["ec-crc-warm"]
        assert ec_pipeline.wait_warmups(WARM)
        ready = sorted(shape[0] for sz, shape, _dev
                       in ec_pipeline._crc_ready if sz == size)
        assert ready == [1, 2, 4, 8, 16, 32, 64]
        # so a batch of any bucket is device-served at once, and no
        # further warm thread starts
        for rows in (64, 1, first_rows, 17):
            assert crcs_of(rows) == "dev"
        assert started == ["ec-crc-warm"]
        assert ec_pipeline.warm_stats()["warm_failures"] == failures
    finally:
        pipe.stop()
