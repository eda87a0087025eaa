"""The writeback tier as PR 40 leaves it: an agent that works by bytes
and ratios on its own thread (ReplicatedPG::agent_choose_mode /
agent_work, OSDService::agent_entry), a full tier that holds writes
back, the operator's cache-flush / cache-try-flush / cache-evict, and
the spans and counters of all of it - at small sizes on the CPU: a
k=2 m=1 base, 64 KiB objects, an 8-object image, a 3-object tier.

The plain reference is `benchmark/references/rbd_wbtier.py`, which
imports nothing of the program.
"""

import threading
import time

import numpy as np
import pytest

from benchmark.references import rbd_wbtier
from ceph_tpu import rbd
from ceph_tpu.client import RadosError
from ceph_tpu.osd import cache_tier
from ceph_tpu.osd.osdmap import Pool
from ceph_tpu.osd.pglog import DIRTY_KEY, HINFO_KEY
from ceph_tpu.utils import denc
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

OB = 65536                      # object bytes
IO = 4096
CONFIG = {"pool_profile": {"plugin": "tpu", "technique": "reed_sol_van",
                           "k": "2", "m": "1"},
          "stripe_unit": 4096, "tier": {"size": 3}}
COUNTERS = ("tier_promote", "tier_flush", "tier_evict", "tier_dirty",
            "tier_clean", "tier_try_flush_fail", "tier_flush_fail",
            "tier_promote_fail", "agent_wake", "agent_flush",
            "agent_evict", "tier_full_waits", "tier_evict_dirty",
            "tier_full_admit")


def make_cluster():
    conf = Config({"mon_tick_interval": 0.5, "osd_heartbeat_interval": 0.5,
                   "osd_heartbeat_grace": 8.0,
                   "mon_osd_min_down_reporters": 2,
                   "osd_op_history_size": 5000,
                   "objecter_op_timeout": 60.0})
    return MiniCluster(num_mons=1, num_osds=3, conf=conf).start()


@pytest.fixture(scope="module")
def cluster():
    c = make_cluster()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def rados(cluster):
    return cluster.client()


def mon(rados, cmd: dict, expect: int = 0) -> str:
    rv, out, _ = rados.mon_command(cmd)
    assert rv == expect, f"{cmd}: rv={rv} out={out}"
    return out


def settle(rados, cluster, pool: str):
    io = rados.open_ioctx(pool)
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            io.remove_object("settle")
            return io
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)


def wait_for(cluster, pred, what: str, timeout: float = 30.0):
    end = time.time() + timeout
    while time.time() < end:
        if pred():
            return
        cluster.tick(0.2)
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def make_tier(rados, cluster, name: str, objects: int = 0, **settings):
    """An EC k=2 m=1 base `<name>` behind a size-3 writeback tier
    `<name>-cache` of ONE PG whose target is `objects` objects of 64
    KiB (0: no target, so no agent); returns (base ioctx BEFORE the
    overlay is set, tier ioctx)."""
    base, cache = name, name + "-cache"
    rados.create_ec_pool(base, f"p_{base}", dict(
        CONFIG["pool_profile"], stripe_unit="4096"), pg_num=4)
    rados.create_pool(cache, pg_num=1, size=3)
    bio = settle(rados, cluster, base)
    settle(rados, cluster, cache)
    mon(rados, {"prefix": "osd tier add", "pool": base, "tierpool": cache})
    mon(rados, {"prefix": "osd tier cache-mode", "pool": cache,
                "mode": "writeback"})
    if objects:
        settings["target_max_bytes"] = objects * OB
    for var, val in settings.items():
        mon(rados, {"prefix": "osd pool set", "pool": cache, "var": var,
                    "val": str(val)})
    return bio, rados.open_ioctx(cache)


def overlay(rados, cluster, name: str):
    mon(rados, {"prefix": "osd tier set-overlay", "pool": name,
                "overlaypool": name + "-cache"})
    pid = rados.monc.osdmap.pool_by_name(name).id
    wait_for(cluster,
             lambda: rados.monc.osdmap.pools[pid].write_tier >= 0
             and all(o.osdmap.pools[pid].write_tier >= 0
                     for o in cluster.osds.values()), "the overlay")
    return rados.open_ioctx(name)


def tier_pg(cluster, name: str):
    """(primary OSD, its PG object) of the tier's one PG."""
    pid = next(iter(cluster.osds.values())).osdmap.pool_by_name(
        name + "-cache").id
    for osd in cluster.osds.values():
        for pgid, pg in osd.pgs.items():
            if pgid.pool == pid and pg.is_primary:
                return osd, pg
    raise AssertionError("no tier primary")


def counters(cluster) -> dict:
    out = dict.fromkeys(COUNTERS, 0)
    for osd in cluster.osds.values():
        dump = osd.asok.execute("perf dump")["osd"]
        for k in COUNTERS:
            out[k] += dump[k]
    return out


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in COUNTERS
            if after[k] != before[k]}


def base_files(cluster, name: str, oid: str) -> list:
    """The base's three positions of `oid`: (bytes, stored crc)."""
    m = next(iter(cluster.osds.values())).osdmap
    pgid = m.object_to_pg(m.pool_by_name(name).id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    out = []
    for shard, o in enumerate(acting):
        osd = cluster.osds[o]
        cid = osd.pgs[pgid].cid
        fname = f"{oid}.s{shard}"
        hinfo = denc.loads(osd.store.getattr(cid, fname, HINFO_KEY))
        out.append((bytes(osd.store.read(cid, fname)), int(hinfo["crc"])))
    return out


class HeldFlushes:
    """Holds back the tier's flush writes (and whiteout deletes) on
    their way to the base until `release()`."""

    def __init__(self, cluster):
        self.cluster, self.held, self.holding = cluster, [], True
        self.lock = threading.Lock()
        self.orig = {}
        for osd in cluster.osds.values():
            self.orig[osd.whoami] = osd.base_pool_op
            osd.base_pool_op = self._wrap(osd.base_pool_op)

    def _wrap(self, orig):
        def base_pool_op(pool_id, oid, ops, done, **kw):
            with self.lock:
                if self.holding and ops[0][0] in ("writefull", "delete"):
                    self.held.append(
                        lambda: orig(pool_id, oid, ops, done, **kw))
                    return
            orig(pool_id, oid, ops, done, **kw)
        return base_pool_op

    def release(self):
        with self.lock:
            self.holding = False
            held, self.held = self.held, []
        for go in held:
            go()

    def restore(self):
        self.release()
        for osd in self.cluster.osds.values():
            if osd.whoami in self.orig:
                osd.base_pool_op = self.orig[osd.whoami]


# -- (b) the modes -----------------------------------------------------------


def pool_with(**kw) -> Pool:
    return Pool(7, "t", pg_num=8, **kw)


# a PG's share: 8 objects of 4 MiB (32 MiB); dirty from 3.2, high from
# 4.8, evicting from 6.4, full at 8
REGIONS = [
    # objects, dirty -> flush, evict
    ("below_dirty", 5, 3, "idle", "idle"),
    ("low", 5, 4, "low", "idle"),
    ("high", 6, 5, "high", "idle"),
    ("some_evict", 7, 2, "idle", "some"),
    ("full", 8, 5, "high", "full"),
]


@pytest.mark.parametrize("by", ["bytes", "objects"])
@pytest.mark.parametrize("region,objects,dirty,flush,evict", REGIONS,
                         ids=[r[0] for r in REGIONS])
def test_agent_choose_mode_regions(by, region, objects, dirty, flush,
                                   evict):
    pool = pool_with(target_max_bytes=256 << 20) if by == "bytes" \
        else pool_with(target_max_objects=64)
    got = cache_tier.agent_choose_mode(pool, objects, objects * (4 << 20),
                                       dirty)
    assert got == (flush, evict)
    # a mode that is on stays on a little below its ratio (the slop),
    # and both targets together give what the fuller one gives
    both = pool_with(target_max_bytes=256 << 20, target_max_objects=6400)
    assert cache_tier.agent_choose_mode(
        both, objects, objects * (4 << 20), dirty) == (flush, evict)
    # no target: no agent
    assert cache_tier.agent_choose_mode(
        pool_with(), objects, objects * (4 << 20), dirty) == \
        ("idle", "idle")


def test_agent_choose_mode_hysteresis():
    pool = pool_with(target_max_objects=800)      # 100 a PG
    choose = cache_tier.agent_choose_mode
    assert choose(pool, 50, 50, 40)[0] == "idle"          # at the ratio
    assert choose(pool, 50, 50, 41)[0] == "low"
    assert choose(pool, 50, 50, 40, flush_mode="low")[0] == "low"
    assert choose(pool, 50, 50, 39, flush_mode="low")[0] == "idle"
    assert choose(pool, 81, 81, 0)[1] == "idle"
    assert choose(pool, 82, 82, 0)[1] == "some"
    assert choose(pool, 80, 80, 0, evict_mode="some")[1] == "some"
    assert choose(pool, 78, 78, 0, evict_mode="some")[1] == "idle"
    assert choose(pool, 100, 100, 0)[1] == "full"


def test_agent_worker_keeps_its_bound_under_contention(monkeypatch):
    """The OSD's agent thread alone, with fake PGs: many threads wake
    it while completions come back on others; it never has more than
    `osd_agent_max_ops` in flight, loses no completion, and stops."""
    import sys
    from types import SimpleNamespace

    agent = None
    seen = {"max": 0, "started": 0, "done": 0}
    guard = threading.Lock()

    class FakePG:
        def agent_work(self, room, low_room):
            assert room >= 1 and low_room <= room
            for _ in range(room):
                agent.op_started()
                with guard:
                    seen["started"] += 1
                    seen["max"] = max(seen["max"], agent.ops)
                threading.Timer(0.001, finish).start()
            return room

    def finish():
        with guard:
            seen["done"] += 1
        agent.op_finished()

    osd = SimpleNamespace(
        whoami=0, pg_lock=threading.Lock(),
        pgs={n: FakePG() for n in range(6)},
        conf=SimpleNamespace(osd_agent_max_ops=4, osd_agent_max_low_ops=2),
        log=SimpleNamespace(error=lambda *a: seen.setdefault("err", a)))
    monkeypatch.setattr(cache_tier, "AGENT_DELAY_S", 0.05)
    agent = cache_tier.TierAgent(osd)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        stop = time.monotonic() + 1.0

        def waker(n):
            while time.monotonic() < stop:
                agent.enqueue(n % 6)

        wakers = [threading.Thread(target=waker, args=(n,), daemon=True)
                  for n in range(24)]
        for t in wakers:
            t.start()
        for t in wakers:
            t.join(10.0)
        assert not any(t.is_alive() for t in wakers)
        for n in range(6):
            agent.dequeue(n)
        end = time.monotonic() + 10.0
        while agent.ops and time.monotonic() < end:
            time.sleep(0.01)
    finally:
        sys.setswitchinterval(old)
        agent.stop()
    assert "err" not in seen
    assert seen["started"] == seen["done"] > 100 and agent.ops == 0
    assert 1 <= seen["max"] <= 4
    assert not agent._thread.is_alive()


def test_pool_set_validates_the_ratios(cluster, rados):
    rados.create_pool("ratios", pg_num=1)
    for var, val, rv in [
            ("cache_target_full_ratio", "1.2", -22),
            ("cache_target_dirty_ratio", "-0.1", -22),
            ("cache_target_dirty_high_ratio", "0.3", -22),   # under 0.4
            ("cache_target_dirty_ratio", "0.7", -22),        # over 0.6
            ("target_max_bytes", "-1", -22),
            ("cache_min_flush_age", "-1", -22),
            ("cache_target_dirty_high_ratio", "0.9", 0),
            ("cache_target_dirty_ratio", "0.7", 0),
            ("target_max_bytes", "1048576", 0),
            ("cache_min_evict_age", "2.5", 0)]:
        mon(rados, {"prefix": "osd pool set", "pool": "ratios",
                    "var": var, "val": val}, expect=rv)
    pool = rados.monc.osdmap.pool_by_name("ratios")
    wait_for(cluster, lambda: rados.monc.osdmap.pool_by_name(
        "ratios").cache_min_evict_age == 2.5, "the map")
    pool = rados.monc.osdmap.pool_by_name("ratios")
    assert (pool.cache_target_dirty_ratio,
            pool.cache_target_dirty_high_ratio,
            pool.cache_target_full_ratio, pool.target_max_bytes) == \
        (0.7, 0.9, 0.8, 1048576)


# -- (a), (e, second half) seeded writes against the reference --------------


def test_seeded_writes_against_the_reference(cluster, rados):
    objects = 8
    bio, tio = make_tier(rados, cluster, "img", objects=3)
    ref = rbd_wbtier.Image(objects * OB, OB)
    rng = np.random.default_rng(40)
    for n in range(objects):
        data = rng.bytes(OB)
        ref.write(n * OB, data)
        bio.write_full(rbd.data_oid("vol", n), data)
    io = overlay(rados, cluster, "img")
    rbd.RBD(io).create("vol", objects * OB, order=16)
    before = counters(cluster)
    with rbd.Image(io, "vol") as image:
        for i in range(120):
            block = int(rng.integers(0, objects * OB // IO))
            data = rng.bytes(IO)
            image.write(block * IO, data)
            ref.write(block * IO, data)
            if i % 8 == 0:           # through the overlay, mid-run
                n = int(rng.integers(0, objects))
                assert image.read(n * OB, OB) == ref.object(n), (i, n)
        for n in range(objects):
            assert image.read(n * OB, OB) == ref.object(n), n
    _osd, pg = tier_pg(cluster, "img")
    # the tier never held more than its target
    assert pg._tier_bytes <= 3 * OB + 4096
    assert rados.cache_flush_evict_all("img-cache") == 0
    assert tio.list_objects() == []
    assert pg.tier_status()["dirty"] == 0 == pg.tier_status()["objects"]
    for n in range(objects):
        assert base_files(cluster, "img", rbd.data_oid("vol", n)) == \
            rbd_wbtier.stored(ref.object(n), CONFIG), n
    got = moved(before, counters(cluster))
    # the agent did the work (evicts happened, by the agent), nothing
    # dirty was ever evicted, nothing entered a full tier
    assert got["agent_evict"] >= 8 and got["agent_flush"] >= 8
    assert got["tier_promote"] >= 8 and got["tier_evict"] >= 8
    assert "tier_evict_dirty" not in got and "tier_full_admit" not in got
    assert "tier_flush_fail" not in got and "tier_promote_fail" not in got


# -- (c) a full tier holds writes back --------------------------------------


def test_write_to_a_full_tier_waits_for_an_evict(cluster, rados):
    make_tier(rados, cluster, "full", objects=3)
    io = overlay(rados, cluster, "full")
    _osd, pg = tier_pg(cluster, "full")
    hold = HeldFlushes(cluster)
    before = counters(cluster)
    try:
        for n in range(3):                     # all dirty, none flushable
            io.write_full(f"o{n}", bytes([n]) * OB)
        assert pg.tier_status()["evict_mode"] == "full"
        done = threading.Event()

        def fourth():
            io.write_full("o3", b"\x03" * OB)
            done.set()

        t = threading.Thread(target=fourth, daemon=True)
        t.start()
        wait_for(cluster, lambda: pg.tier_status()["full_waiting"] == 1,
                 "the write to park")
        assert not done.wait(1.0)              # held back, not failed
        st = pg.tier_status()
        assert (st["objects"], st["bytes"]) == (3, 3 * OB)
        hold.release()
        assert done.wait(30.0)
    finally:
        hold.restore()
    t.join(5.0)
    assert io.read("o3") == b"\x03" * OB
    st = pg.tier_status()
    assert st["objects"] <= 3 and st["bytes"] <= 3 * OB
    got = moved(before, counters(cluster))
    # (the objecter may have sent the parked write again: a wait each)
    assert got["tier_full_waits"] >= 1 and got["tier_evict"] >= 1
    assert "tier_full_admit" not in got and "tier_evict_dirty" not in got
    docs = [d for o in cluster.osds.values()
            for d in o.asok.execute("dump_historic_ops")["ops"]]
    waits = [s for d in docs if d["kind"] == "client"
             and " o3 ['writefull']" in d["description"]
             for s in d["spans"] if s["name"] == "tier.full_wait"]
    assert waits and waits[0]["args"]["mode"] == "full"
    assert max(s["t1"] - s["t0"] for s in waits) >= 1.0
    agent = [d for d in docs if d["kind"] == "tier_agent"]
    assert any(s["name"] == "tier.evict" and s["args"]["bytes"] == OB
               for d in agent for s in d["spans"])


def test_promotes_sized_by_guess_never_pass_the_target(cluster, rados):
    """A promote's room is taken before its size is known, at the size
    of the largest object the PG has seen: where that was a 10-byte
    header, six 64 KiB promotes are admitted into room for three.  The
    room is looked at again when the copies arrive."""
    bio, _tio = make_tier(rados, cluster, "guess", objects=3)
    for n in range(6):
        bio.write_full(f"g{n}", bytes([n + 1]) * OB)
    io = overlay(rados, cluster, "guess")
    _osd, pg = tier_pg(cluster, "guess")
    hold = HeldFlushes(cluster)
    before = counters(cluster)
    try:
        io.write_full("hdr", b"0123456789")
        threads = [threading.Thread(
            target=lambda n=n: io.write(f"g{n}", b"w" * IO, offset=IO),
            daemon=True) for n in range(6)]
        for t in threads:
            t.start()
        wait_for(cluster, lambda: pg.tier_status()["full_waiting"] >= 1
                 and pg.tier_status()["promoting"] == 0,
                 "the late copies to be turned away")
        st = pg.tier_status()
        assert st["bytes"] <= 3 * OB + 10 and st["objects"] <= 4
        assert "tier_full_admit" not in moved(before, counters(cluster))
        hold.release()
        for t in threads:
            t.join(60.0)
    finally:
        hold.restore()
    for n in range(6):
        assert io.read(f"g{n}") == bytes([n + 1]) * IO + b"w" * IO \
            + bytes([n + 1]) * (OB - 2 * IO), n
    got = moved(before, counters(cluster))
    assert "tier_full_admit" not in got and "tier_evict_dirty" not in got
    assert got["tier_promote"] > 6          # the turned-away came again


# -- (d), (e) the operator's ops, a write over a flush in flight -------------


def errno_of(fn, *args) -> int:
    try:
        fn(*args)
        return 0
    except RadosError as e:
        return e.errno


def test_operator_ops_on_clean_dirty_flushing_absent(cluster, rados):
    bio, tio = make_tier(rados, cluster, "ops")        # no target: no agent
    bio.write_full("cold", b"c" * OB)
    io = overlay(rados, cluster, "ops")
    _osd, pg = tier_pg(cluster, "ops")
    before = counters(cluster)
    # absent: the tier holds no such object (the base's copy is not it)
    assert [errno_of(f, "cold") for f in (
        tio.cache_flush, tio.cache_try_flush, tio.cache_evict)] == [2, 2, 2]
    # dirty
    io.write("cold", b"D" * IO, offset=IO)             # promotes, dirties
    want = b"c" * IO + b"D" * IO + b"c" * (OB - 2 * IO)
    assert errno_of(tio.cache_evict, "cold") == 16      # EBUSY
    assert errno_of(tio.cache_try_flush, "cold") == 0   # flushes it
    assert pg.tier_status()["dirty"] == 0
    assert base_files(cluster, "ops", "cold") == \
        rbd_wbtier.stored(want, CONFIG)
    # clean
    assert errno_of(tio.cache_flush, "cold") == 0       # nothing to do
    assert errno_of(tio.cache_try_flush, "cold") == 0
    assert moved(before, counters(cluster)) == {
        "tier_promote": 1, "tier_dirty": 1, "tier_flush": 1,
        "tier_clean": 1}
    assert errno_of(tio.cache_evict, "cold") == 0
    assert tio.list_objects() == [] and io.read("cold") == want
    # being flushed: try-flush and evict say EBUSY, flush waits
    io.write("cold", b"E" * IO, offset=0)
    want = b"E" * IO + want[IO:]
    hold = HeldFlushes(cluster)
    try:
        out = {}
        t = threading.Thread(
            target=lambda: out.update(flush=errno_of(tio.cache_flush,
                                                     "cold")), daemon=True)
        t.start()
        wait_for(cluster, lambda: pg.tier_status()["flushing"] == 1,
                 "the flush to start")
        assert errno_of(tio.cache_try_flush, "cold") == 16
        assert errno_of(tio.cache_evict, "cold") == 16
        # (e) a write acknowledged while its object is being flushed
        io.write("cold", b"F" * IO, offset=2 * IO)
        want = want[:2 * IO] + b"F" * IO + want[3 * IO:]
        flushes = counters(cluster)["tier_flush"]
        hold.release()
        t.join(30.0)
    finally:
        hold.restore()
    # the blocking flush saw the overtaken one through and flushed
    # again: the object is clean, and the base holds the last write
    assert out == {"flush": 0}
    assert counters(cluster)["tier_flush"] == flushes + 1
    assert pg.tier_status()["dirty"] == 0
    assert io.read("cold") == want
    assert base_files(cluster, "ops", "cold") == \
        rbd_wbtier.stored(want, CONFIG)
    got = moved(before, counters(cluster))
    assert got["tier_try_flush_fail"] == 1
    assert "tier_evict_dirty" not in got     # refused by the index first
    # a deleted object: the whiteout is flushed as a delete, then gone
    io.remove_object("cold")
    assert errno_of(tio.cache_evict, "cold") == 16
    assert errno_of(tio.cache_flush, "cold") == 0
    assert tio.list_objects() == []
    assert errno_of(bio.stat, "cold") == 2 == errno_of(io.read, "cold")


# -- (g) spans and counters of one promote, one flush, one evict -------------


def test_spans_and_counters_of_one_cycle(cluster, rados):
    bio, tio = make_tier(rados, cluster, "trace")      # no agent
    bio.write_full("x", b"x" * OB)
    io = overlay(rados, cluster, "trace")
    before = counters(cluster)
    io.write("x", b"y" * IO, offset=3 * IO)
    io.write("x", b"z" * IO, offset=5 * IO)             # a hit
    tio.cache_flush("x")
    tio.cache_evict("x")
    assert moved(before, counters(cluster)) == {
        "tier_promote": 1, "tier_dirty": 1, "tier_flush": 1,
        "tier_clean": 1, "tier_evict": 1}
    docs = [d for o in cluster.osds.values()
            for d in o.asok.execute("dump_historic_ops")["ops"]]
    writes = sorted((d for d in docs if d["kind"] == "client"
                     and " x ['write']" in d["description"]),
                    key=lambda d: d["mstart"])
    assert len(writes) == 2
    spans = [{s["name"]: s for s in d["spans"]} for d in writes]
    assert spans[0]["tier.lookup"]["args"] == {
        "hit": 0, "mode": "idle", "bytes": IO}
    assert spans[1]["tier.lookup"]["args"]["hit"] == 1
    assert "tier.promote_wait" in spans[0] \
        and "tier.promote_wait" not in spans[1]
    assert "replica_wait" in spans[0] and "replica_wait" in spans[1]
    (promote,) = [d for d in docs if d["kind"] == "tier_promote"
                  and d["description"].endswith(" x)")]
    assert promote["trace_id"] == writes[0]["trace_id"]
    by = {s["name"]: s for s in promote["spans"]}
    assert by["base_read"]["args"]["result"] == 0
    assert by["install"]["args"]["bytes"] == OB
    assert by["install"]["t0"] >= by["base_read"]["t1"]
    assert by["replica_wait"]["t1"] <= by["install"]["t1"]
    # the promote lies inside the client's wait for it
    wait = spans[0]["tier.promote_wait"]
    assert wait["t0"] <= by["base_read"]["t0"] \
        and by["install"]["t1"] <= wait["t1"]
    (flush,) = [d for d in docs if d["kind"] == "tier_flush"
                and d["description"].endswith(" x)")]
    by = {s["name"]: s for s in flush["spans"]}
    assert by["tier_read"]["args"]["bytes"] == OB
    assert by["base_write"]["args"] == {"bytes": OB, "mode": "idle",
                                        "result": 0}
    assert by["tier_read"]["t1"] <= by["base_write"]["t0"]
    # the base's side of both: client ops of this OSD on the base pool
    assert any(d["kind"] == "client" and " x ['writefull'" in
               d["description"] and d["description"].startswith(
                   "osd_op(osd.") for d in docs)


def test_failed_base_op_counts_and_requeues(cluster, rados):
    bio, tio = make_tier(rados, cluster, "fail")
    bio.write_full("f", b"f" * OB)
    io = overlay(rados, cluster, "fail")
    osd, pg = tier_pg(cluster, "fail")
    before = counters(cluster)
    orig = osd.osdmap.pg_primary
    calls = []

    def no_primary(pgid):
        if pgid.pool != pg.pgid.pool and not calls:
            calls.append(pgid)
            return None                  # once: the base has no primary
        return orig(pgid)

    osd.osdmap.pg_primary = no_primary
    try:
        io.write("f", b"g" * IO)         # the objecter sends it again
    finally:
        osd.osdmap.pg_primary = orig
    assert calls and io.read("f") == b"g" * IO + b"f" * (OB - IO)
    got = moved(before, counters(cluster))
    assert got["tier_promote_fail"] == 1 and got["tier_promote"] == 2
    # a flush that fails leaves the object dirty, and says so
    calls.clear()
    osd.osdmap.pg_primary = no_primary
    try:
        assert errno_of(tio.cache_flush, "f") == 110
    finally:
        osd.osdmap.pg_primary = orig
    assert pg.tier_status()["dirty"] == 1
    assert moved(before, counters(cluster))["tier_flush_fail"] == 1
    tio.cache_flush("f")
    assert pg.tier_status()["dirty"] == 0


# -- (f) the tier PG's primary dies between the ack and the flush ------------


def test_primary_killed_between_ack_and_flush():
    cluster = make_cluster()
    try:
        rados = cluster.client()
        bio, tio = make_tier(rados, cluster, "kill")   # no agent: dirty
        bio.write_full("k", b"k" * OB)                 # stays dirty
        io = overlay(rados, cluster, "kill")
        io.write("k", b"ACKED" + b"!" * (IO - 5), offset=2 * IO)
        want = b"k" * (2 * IO) + b"ACKED" + b"!" * (IO - 5) \
            + b"k" * (OB - 3 * IO)
        osd, pg = tier_pg(cluster, "kill")
        assert pg.tier_status()["dirty"] == 1
        # every replica's copy says dirty: how a new primary will know
        assert all(DIRTY_KEY in o.store.getattrs(pg.cid, "k")
                   for o in cluster.osds.values())
        victim = osd.whoami
        cluster.kill_osd(victim)
        cluster.mark_osd_down(victim)
        cluster.wait_for_osd_down(victim, timeout=60)
        end = time.time() + 60
        while True:
            try:
                assert io.read("k") == want
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)
        new_osd, new_pg = tier_pg(cluster, "kill")
        assert new_osd.whoami != victim
        # the new primary rebuilt its counts from the collection
        assert new_pg.tier_status()["dirty"] == 1
        assert rados.cache_flush_evict_all("kill-cache") == 0
        m = new_osd.osdmap
        pgid = m.object_to_pg(m.pool_by_name("kill").id, "k")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        stored = rbd_wbtier.stored(want, CONFIG)
        seen = 0
        for shard, o in enumerate(acting):
            if o not in cluster.osds:
                continue
            store, cid = cluster.osds[o].store, cluster.osds[o].pgs[pgid].cid
            assert bytes(store.read(cid, f"k.s{shard}")) == stored[shard][0]
            seen += 1
        assert seen >= 2
    finally:
        cluster.stop()
