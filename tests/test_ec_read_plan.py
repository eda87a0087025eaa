"""An EC read asks the shards its plan names and no others (ISSUE 39):
the first gather of a read goes to the positions the codec's
`minimum_to_decode` answers over the live ones — a healthy pool's read
the data chunks, which decode nothing — and the read widens to every
other acting holder only where that set did not give the object.  One
rule for every code: the five pools the benchmark's profiles serve."""

import contextlib
import threading
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.references import lrc as lrc_ref
from benchmark.references import shec as shec_ref
from ceph_tpu.client import RadosError
from ceph_tpu.ops import hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pglog import VER_KEY, shard_oid
from ceph_tpu.store import Transaction
from ceph_tpu.utils import faults
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

UNIT = 4096
CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    # nothing is marked down but by the test's own `osd down`: a
    # killed daemon stays "up" in the map however far the clock runs
    "osd_heartbeat_grace": 3600.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 3600.0,
    "osd_op_history_size": 4096,
}
# name -> (profile, positions, how many the code promises to lose)
CODES = {
    "rs-k8m3": ({"technique": "reed_sol_van", "k": 8, "m": 3}, 11, 3),
    "rs-k2m1": ({"technique": "reed_sol_van", "k": 2, "m": 1}, 3, 1),
    "cauchy-k6m3": ({"technique": "cauchy_good", "k": 6, "m": 3,
                     "packetsize": 32}, 9, 3),
    "shec-k8m4c3": ({"technique": "shec_multiple", "k": 8, "m": 4,
                     "c": 3}, 12, 3),
    "lrc-k4m2l3": ({"technique": "lrc", "k": 4, "m": 2, "l": 3}, 8, 2),
}


class Pool:
    """One code's cluster, its single PG and two objects."""

    def __init__(self, name: str):
        profile, self.n, self.lose = CODES[name]
        self.name = name
        self.cluster = MiniCluster(num_mons=1, num_osds=self.n,
                                   conf=Config(CONF)).start()
        self.rados = self.cluster.client()
        self.rados.create_ec_pool(
            "planned", "planned-prof",
            dict(profile, plugin="tpu", host_cutover=1, stripe_unit=UNIT),
            pg_num=1)
        self.io = self.rados.open_ioctx("planned")
        k = int(profile["k"])
        self.payloads = {
            oid: np.random.default_rng(3900 + i).integers(
                0, 256, k * UNIT * 3 - 100, dtype=np.uint8).tobytes()
            for i, oid in enumerate(("obj0", "obj1"))}
        for oid, data in self.payloads.items():
            self.retry(lambda: self.io.write_full(oid, data))
        m = self.cluster.leader().osdmon.osdmap
        self.pgid = m.object_to_pg(self.io.pool_id, "obj0")
        self.acting = list(m.pg_to_up_acting_osds(self.pgid)[1])
        self.primary = self.cluster.osds[self.acting[0]]
        self.pg = self.primary.pgs[self.pgid]
        self.codec = self.pg._ec_codec()
        self.k = k
        # a healthy read's plan, and the planned source the tests
        # spoil: the first that is not the primary's own position
        self.plan = ecutil.minimum_shards(self.codec, range(self.n))
        self.source = [p for p in self.plan if p != 0][0]

    def retry(self, call, seconds=60.0):
        end = time.time() + seconds
        while True:
            try:
                return call()
            except RadosError:
                if time.time() > end:
                    raise
                self.cluster.tick(0.3)

    def cold(self):
        hbm_cache.get().clear()

    def gathers(self, oid):
        """The `gather_wait` args of the newest client read of `oid`."""
        docs = [d for d in
                self.primary.op_tracker.dump_historic_ops()["ops"]
                if d["kind"] == "client" and f" {oid} " in d["description"]
                and "'read'" in d["description"]]
        doc = max(docs, key=lambda d: d["mstart"])
        return doc, [s["args"] for s in doc["spans"]
                     if s["name"] == "gather_wait"]

    def widened(self) -> int:
        return self.primary.asok.execute("perf dump")["osd"][
            "ec_read_widened"]

    def accepts(self, chunks) -> bool:
        """The plain reference's word on a set of positions."""
        chunks = set(chunks)
        if self.name.startswith("shec"):
            return shec_ref.plan(range(8), sorted(chunks),
                                 shec_ref.coding_matrix(8, 4, 3)) is not None
        if self.name.startswith("lrc"):
            _mapping, whole, local = lrc_ref.layout(4, 2, 3)
            grew = True
            while grew:
                grew = False
                for layer in reversed([whole] + local):
                    member = {p for p, ch in enumerate(layer) if ch != "_"}
                    if 0 < len(member - chunks) <= layer.count("c"):
                        chunks |= member
                        grew = True
            return {p for p, ch in enumerate(whole) if ch == "D"} <= chunks
        return len(chunks) >= self.k        # MDS: any k

    @contextlib.contextmanager
    def fetches(self):
        """The positions each of the primary's gathers asks, in order."""
        asked, real = [], self.primary.ec_fetch_shards

        def spy(pgid, oid, targets, **kw):
            asked.append(sorted(s for s, _o in targets))
            return real(pgid, oid, targets, **kw)

        self.primary.ec_fetch_shards = spy
        try:
            yield asked
        finally:
            del self.primary.ec_fetch_shards


class Readings:
    """What the benchmark hands a reader, of one daemon's docs."""

    def __init__(self, docs):
        self.op_docs, self.said = list(docs), []

    def log(self, msg):
        self.said.append(msg)


@pytest.fixture(scope="module", params=list(CODES))
def pool(request):
    p = Pool(request.param)
    yield p
    faults.get().reset()
    p.cluster.stop()


@pytest.fixture
def cold(pool):
    pool.cold()
    yield
    faults.get().reset()


def test_healthy_read_asks_the_plan_and_decodes_nothing(pool, cold):
    """(i) the plan's remote positions are asked and no others; no
    decode is dispatched or planned; the bytes come back."""
    pipe = ec_pipeline.stats()["dev_dispatches"]
    plans = pool.codec.stat_counters().get("decode_plan_misses", 0)
    assert pool.io.read("obj0") == pool.payloads["obj0"]
    doc, (args,) = pool.gathers("obj0")
    assert args["widened"] == 0
    assert args["asked"] == args["used"] == pool.k - 1
    assert args["chunks"] == pool.plan
    assert args["chunks"] == ecutil.chunk_shards(pool.codec)[:pool.k]
    assert "ec.plan" not in [s["name"] for s in doc["spans"]]
    assert ec_pipeline.stats()["dev_dispatches"] == pipe
    assert pool.codec.stat_counters().get("decode_plan_misses", 0) == plans
    assert pool.widened() == 0


def test_gather_used_share_reads_one_where_the_plan_sufficed(pool, cold):
    """`osd.gather_used_share.read`, a data file on the reader that
    was there: `used` over `asked` of a read's gathers, every step."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "osd.gather_used_share.read"]
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             entry["name"] + ".json")
    assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
        {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")}
    assert entry["moves"] == "read_mibps" and spec["limits"]
    reader = harness.load_module(harness.HERE, "readers", spec["reader"])
    pool.io.read("obj0")
    doc, _args = pool.gathers("obj0")
    assert reader.read(Readings([doc]), spec["params"]) == 1.0
    faults.get().store_eio("osd.*", f"obj0.s{pool.source}")
    pool.io.read("obj0")
    doc, (first, second) = pool.gathers("obj0")
    assert reader.read(Readings([doc]), spec["params"]) == pytest.approx(
        (first["used"] + second["used"]) / (pool.n - 1))
    assert second["used"] <= second["asked"]


def test_excluded_position_is_planned_around(pool, cold):
    """A caller's `exclude` (scrub repair): the same call over what is
    left, in one gather; an own shard the plan does not name (here
    every one, with position 0 excluded) is not asked of anyone."""
    with pool.fetches() as asked:
        data = pool.pg._ec_read_local("obj0", exclude={0})
    assert bytes(data) == pool.payloads["obj0"]
    left = [p for p in range(pool.n) if p != 0]
    # (one gather; a host stalled past a sub-read's window adds a widened)
    assert asked[:1] == [ecutil.minimum_shards(pool.codec, left)]
    assert pool.accepts(asked[0]) and 0 not in asked[0]


def test_cache_served_read_is_checked_on_the_device(pool):
    """A read served from the HBM cache folds the resident stripes'
    CRCs on the device, under the dispatch spans a decode has, and
    serves only stripes that still have the CRCs they were written
    with: spoiled ones drop the entry and the shards are read."""
    data = np.random.default_rng(39).integers(
        0, 256, pool.k * UNIT * 2, dtype=np.uint8).tobytes()
    end = time.time() + 120
    while True:     # a lane whose fused encode is not warm host-serves
        pool.retry(lambda: pool.io.write_full("objc", data))
        served = hbm_cache.stats()["read_bytes_served"]
        assert pool.io.read("objc") == data
        if hbm_cache.stats()["read_bytes_served"] > served:
            break
        assert time.time() < end, "objc never became cache-served"
        time.sleep(0.2)
    assert ec_pipeline.wait_warmups(120.0)
    before = hbm_cache.stats()
    assert pool.io.read("objc") == data
    doc, gathers = pool.gathers("objc")
    after = hbm_cache.stats()
    assert not gathers
    assert after["verified"] == before["verified"] + 1
    spans = {s["name"]: s for s in doc["spans"]}
    assert spans["ec.device_compute"]["args"]["stripes"] == 2
    assert spans["ec.d2h"]["t0"] == spans["ec.device_compute"]["t1"]
    ent = hbm_cache.get().lookup(pool.pg.cid, "objc")
    seg = ent.segs[0]
    seg.data = seg.data.at[seg.row0 + 1, 0, 7].add(1)
    assert pool.io.read("objc") == data
    _doc, gathers = pool.gathers("objc")
    assert [g["widened"] for g in gathers] == [0]
    assert hbm_cache.stats()["verify_fail"] == after["verify_fail"] + 1
    assert hbm_cache.get().lookup(pool.pg.cid, "objc") is None


def test_store_error_on_a_planned_source_widens(pool, cold):
    """(ii) a planned source answers an error: a second gather, of
    the holders not asked yet, and the same bytes."""
    before = pool.widened()
    faults.get().store_eio("osd.*", f"obj0.s{pool.source}")
    assert pool.io.read("obj0") == pool.payloads["obj0"]
    _doc, (first, second) = pool.gathers("obj0")
    assert (first["widened"], second["widened"]) == (0, 1)
    assert first["asked"] == pool.k - 1 and first["used"] == pool.k - 2
    assert second["asked"] == pool.n - pool.k
    assert pool.source not in second["chunks"]
    assert pool.accepts(second["chunks"])
    # (a client under load may resend the read: each send widens)
    assert pool.widened() >= before + 1


def test_own_planned_shard_unreadable_starts_widened(pool, cold):
    """The primary's own planned shard file answers an error: no
    planned gather goes out, and the one that does asks every other
    acting holder, the plan's among them."""
    before = pool.widened()
    faults.get().store_eio("osd.*", "obj0.s0")
    assert pool.io.read("obj0") == pool.payloads["obj0"]
    _doc, (args,) = pool.gathers("obj0")
    assert args["widened"] == 1 and args["asked"] == pool.n - 1
    assert 0 not in args["chunks"] and pool.accepts(args["chunks"])
    # (a client under load may resend the read: each send widens)
    assert pool.widened() >= before + 1


def test_need_ver_read_widens_past_a_source_that_is_behind(pool, cold):
    """(iv) a version-gated read (rebuild): a planned source that has
    not applied the version is passed by, and a set that mixes
    versions still serves nothing."""
    oid = "obj1"
    cur = tuple(pool.pg.pglog.objects[oid])
    older, newer = (cur[0], cur[1] - 1), (cur[0], cur[1] + 1)

    def stamp(position, ver):
        pool.cluster.osds[pool.acting[position]].store.apply_transaction(
            Transaction().setattr(pool.pg.cid, shard_oid(oid, position),
                                  VER_KEY, repr(ver).encode()))

    rest = [p for p in range(pool.n) if p not in pool.plan]
    try:
        with pool.fetches() as asked:
            stamp(pool.source, older)
            data = pool.pg._ec_read_local(oid, need_ver=cur)
        assert bytes(data) == pool.payloads[oid]
        assert asked == [[p for p in pool.plan if p != 0], rest]
        # every shard the widened step can add claims a newer
        # generation: no set it completes is of one version
        for p in rest:
            stamp(p, newer)
        assert pool.pg._ec_read_local(oid, need_ver=cur) is None
    finally:
        for p in [pool.source] + rest:
            stamp(p, cur)
    pool.cold()
    assert bytes(pool.pg._ec_read_local(oid, need_ver=cur)) == \
        pool.payloads[oid]


def test_killed_source_not_marked_down_widens(pool, cold):
    """(ii) the planned source's daemon is gone and the map has not
    noticed: the read waits that sub-read's window, then widens."""
    before = pool.widened()
    pool.cluster.kill_osd(pool.acting[pool.source])
    out = {}
    reader = threading.Thread(
        target=lambda: out.update(data=pool.io.read("obj0")))
    reader.start()
    end = time.time() + 60
    while reader.is_alive() and time.time() < end:
        time.sleep(0.2)
        pool.cluster.clock.advance(1.0)     # the sub-read's RPC window
    reader.join(1.0)
    assert out.get("data") == pool.payloads["obj0"]
    _doc, (first, second) = pool.gathers("obj0")
    assert (first["widened"], second["widened"]) == (0, 1)
    assert first["used"] == first["asked"] - 1
    assert pool.source not in second["chunks"]
    # (the clock's jumps may make the client resend, too)
    assert pool.widened() >= before + 1


def test_osds_down_first_step_asks_the_degraded_plan(pool, cold):
    """(iii) as many OSDs down as the code promises to lose: the first
    gather asks the plan over the live positions and it serves."""
    victims = [p for p in pool.plan if p != 0][:pool.lose]
    pool.rados.mon_command({"prefix": "osd pool set", "pool": "planned",
                            "var": "min_size", "val": str(pool.k)})
    for p in victims:
        pool.cluster.kill_osd(pool.acting[p])
        pool.cluster.mark_osd_down(pool.acting[p])
    for p in victims:
        pool.cluster.wait_for_osd_down(pool.acting[p], timeout=60)
    pool.retry(lambda: pool.io.read("obj1"))        # over the peering
    pool.cold()
    before = pool.widened()
    assert pool.io.read("obj0") == pool.payloads["obj0"]
    _doc, (args,) = pool.gathers("obj0")
    live = [p for p in range(pool.n) if p not in victims]
    assert args["widened"] == 0
    assert args["chunks"] == ecutil.minimum_shards(pool.codec, live)
    assert args["asked"] == args["used"] == len(args["chunks"]) - 1
    assert pool.accepts(args["chunks"])
    assert not set(args["chunks"]) & set(victims)
    assert pool.widened() == before
