"""Cache tiering end-to-end (MiniCluster): writeback promote / flush /
whiteout / evict semantics plus the mon-side tiering guards.

Models the reference's agent + promote behavior
(osd/ReplicatedPG.cc: agent_work :12031, agent_maybe_flush :12250,
agent_maybe_evict :12313, maybe_handle_cache/promote_object) and the
OSDMonitor _check_become_tier validation.
"""

import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

from ceph_tpu.osd.pg import DIRTY_KEY, WHITEOUT_KEY
from ceph_tpu.store.objectstore import StoreError


@pytest.fixture(scope="module")
def cluster():
    conf = Config({
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,   # agent tick cadence
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
    })
    c = MiniCluster(num_mons=1, num_osds=3, conf=conf).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def rados(cluster):
    return cluster.client()


def _pool_id(cluster, name: str) -> int:
    return cluster.osds[0].osdmap.pool_by_name(name).id


def _pool_objects(cluster, pool_id: int) -> dict:
    """{oid: (data, attrs)} for live (non-whiteout) objects of a
    replicated pool, inspected directly in the primaries' stores."""
    out = {}
    for osd in cluster.osds.values():
        for pgid, pg in list(osd.pgs.items()):
            if pgid.pool != pool_id or not pg.is_primary:
                continue
            try:
                names = osd.store.collection_list(pg.cid)
            except StoreError:
                continue
            for n in names:
                if n.startswith("_pgmeta") or "@" in n:
                    continue
                try:
                    attrs = osd.store.getattrs(pg.cid, n)
                    data = osd.store.read(pg.cid, n)
                except StoreError:
                    continue
                if WHITEOUT_KEY in attrs:
                    continue
                out[n] = (data, attrs)
    return out


def _ec_pool_objects(cluster, pool_id: int) -> set:
    """Base-object names present (as shards) in an EC pool."""
    out = set()
    for osd in cluster.osds.values():
        for pgid, pg in list(osd.pgs.items()):
            if pgid.pool != pool_id:
                continue
            try:
                names = osd.store.collection_list(pg.cid)
            except StoreError:
                continue
            out |= {n.rsplit(".s", 1)[0] for n in names
                    if ".s" in n and "@" not in n
                    and not n.startswith("_pgmeta")}
    return out


def _wait_for(cluster, pred, what: str, timeout: float = 30.0):
    end = time.time() + timeout
    while time.time() < end:
        if pred():
            return
        cluster.tick(0.5)
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def _settle(rados, cluster, pool: str, **kw):
    ctx = rados.open_ioctx(pool)
    end = time.time() + 60
    while True:
        try:
            ctx.write_full("settle", b"s")
            return ctx
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)


def _mon(rados, cmd: dict, expect: int = 0):
    rv, out, _ = rados.mon_command(cmd)
    assert rv == expect, f"{cmd}: rv={rv} out={out}"
    return out


def _setup_tier(rados, cluster, base: str, cache: str,
                base_ec: bool = False, mode: str = "writeback"):
    if base_ec:
        rados.create_ec_pool(base, f"p_{base}",
                             {"plugin": "tpu", "k": 2, "m": 1})
    else:
        rados.create_pool(base, pg_num=4)
    rados.create_pool(cache, pg_num=4)
    # both pools must serve I/O before tiering links them
    _settle(rados, cluster, base)
    _settle(rados, cluster, cache)
    _mon(rados, {"prefix": "osd tier add", "pool": base,
                 "tierpool": cache})
    _mon(rados, {"prefix": "osd tier cache-mode", "pool": cache,
                 "mode": mode})
    _agent_on(rados, cache)
    _mon(rados, {"prefix": "osd tier set-overlay", "pool": base,
                 "overlaypool": cache})


def _agent_on(rados, cache: str, objects: int = 4000):
    """A pool with no target has no agent: give this one a target it
    never fills, and a dirty ratio of 0, so that whatever is dirty is
    flushed."""
    _mon(rados, {"prefix": "osd pool set", "pool": cache,
                 "var": "target_max_objects", "val": str(objects)})
    _mon(rados, {"prefix": "osd pool set", "pool": cache,
                 "var": "cache_target_dirty_ratio", "val": "0"})


class TestWritebackTier:
    def test_write_lands_in_tier_then_flushes_to_base(self, cluster,
                                                      rados):
        _setup_tier(rados, cluster, "wb-base", "wb-cache")
        # the agent is woken by the write itself: an age keeps the
        # object dirty long enough to be looked at
        _mon(rados, {"prefix": "osd pool set", "pool": "wb-cache",
                     "var": "cache_min_flush_age", "val": "3.0"})
        _wait_for(cluster, lambda: all(
            o.osdmap.pool_by_name("wb-cache").cache_min_flush_age == 3.0
            for o in cluster.osds.values()), "the age in every map")
        base_id = _pool_id(cluster, "wb-base")
        cache_id = _pool_id(cluster, "wb-cache")
        io = rados.open_ioctx("wb-base")      # overlay redirects
        io.write_full("hot", b"cached-bytes")
        # the write must be in the TIER, dirty, before any flush
        tier_objs = _pool_objects(cluster, cache_id)
        assert "hot" in tier_objs
        data, attrs = tier_objs["hot"]
        assert data == b"cached-bytes"
        assert DIRTY_KEY in attrs
        assert io.read("hot") == b"cached-bytes"
        # agent flushes to the base and clears DIRTY
        _wait_for(cluster,
                  lambda: "hot" in _pool_objects(cluster, base_id),
                  "flush to base")
        assert _pool_objects(cluster, base_id)["hot"][0] == \
            b"cached-bytes"
        _wait_for(cluster,
                  lambda: DIRTY_KEY not in _pool_objects(
                      cluster, cache_id).get("hot", (b"", {}))[1],
                  "dirty cleared after flush")

    def test_promote_on_read_miss(self, cluster, rados):
        rados.create_pool("pr-base", pg_num=4)
        rados.create_pool("pr-cache", pg_num=4)
        base_io = _settle(rados, cluster, "pr-base")
        _settle(rados, cluster, "pr-cache")
        base_io.write_full("cold", b"only-in-base")
        _mon(rados, {"prefix": "osd tier add", "pool": "pr-base",
                     "tierpool": "pr-cache"})
        _mon(rados, {"prefix": "osd tier cache-mode",
                     "pool": "pr-cache", "mode": "writeback"})
        _mon(rados, {"prefix": "osd tier set-overlay",
                     "pool": "pr-base", "overlaypool": "pr-cache"})
        cache_id = _pool_id(cluster, "pr-cache")
        io = rados.open_ioctx("pr-base")
        # read through the overlay: miss -> promote -> served
        assert io.read("cold") == b"only-in-base"
        assert "cold" in _pool_objects(cluster, cache_id)
        # promoted copy is CLEAN (no re-flush of unchanged data)
        assert DIRTY_KEY not in _pool_objects(
            cluster, cache_id)["cold"][1]

    def test_partial_write_promotes_then_applies(self, cluster, rados):
        rados.create_pool("pw-base", pg_num=4)
        rados.create_pool("pw-cache", pg_num=4)
        base_io = _settle(rados, cluster, "pw-base")
        _settle(rados, cluster, "pw-cache")
        base_io.write_full("doc", b"0123456789")
        _mon(rados, {"prefix": "osd tier add", "pool": "pw-base",
                     "tierpool": "pw-cache"})
        _mon(rados, {"prefix": "osd tier cache-mode",
                     "pool": "pw-cache", "mode": "writeback"})
        _mon(rados, {"prefix": "osd tier set-overlay",
                     "pool": "pw-base", "overlaypool": "pw-cache"})
        io = rados.open_ioctx("pw-base")
        io.write("doc", b"AB", offset=2)      # needs the base bytes
        assert io.read("doc") == b"01AB456789"

    def test_delete_whiteout_propagates_to_base(self, cluster, rados):
        _setup_tier(rados, cluster, "del-base", "del-cache")
        base_id = _pool_id(cluster, "del-base")
        cache_id = _pool_id(cluster, "del-cache")
        io = rados.open_ioctx("del-base")
        io.write_full("gone", b"soon")
        _wait_for(cluster,
                  lambda: "gone" in _pool_objects(cluster, base_id),
                  "flush before delete")
        io.remove_object("gone")
        # logically deleted NOW, even though the base still has it
        with pytest.raises(RadosError):
            io.read("gone")
        # the whiteout flush deletes the base copy, then retires itself
        _wait_for(cluster,
                  lambda: "gone" not in _pool_objects(cluster, base_id),
                  "whiteout propagated to base")
        _wait_for(cluster,
                  lambda: "gone" not in _pool_objects(cluster, cache_id),
                  "whiteout retired from tier")
        with pytest.raises(RadosError):
            io.read("gone")

    def test_evict_cold_then_repromote(self, cluster, rados):
        _setup_tier(rados, cluster, "ev-base", "ev-cache")
        _mon(rados, {"prefix": "osd pool set", "pool": "ev-cache",
                     "var": "target_max_objects", "val": "2"})
        base_id = _pool_id(cluster, "ev-base")
        cache_id = _pool_id(cluster, "ev-cache")
        io = rados.open_ioctx("ev-base")
        for i in range(6):
            io.write_full(f"e{i}", bytes([65 + i]) * 64)
        _wait_for(cluster,
                  lambda: all(f"e{i}" in _pool_objects(cluster, base_id)
                              for i in range(6)),
                  "all flushed to base")
        _wait_for(cluster,
                  lambda: len([o for o in _pool_objects(
                      cluster, cache_id) if o.startswith("e")]) <= 2,
                  "evicted down to target")
        # evicted objects re-promote transparently
        for i in range(6):
            assert io.read(f"e{i}") == bytes([65 + i]) * 64

    def test_ec_base_pool_with_replicated_cache(self, cluster, rados):
        """The headline tiering shape: EC cold pool fronted by a
        replicated cache (EC pools can't take partial overwrites, the
        tier absorbs them)."""
        _setup_tier(rados, cluster, "ecb-base", "ecb-cache",
                    base_ec=True)
        base_id = _pool_id(cluster, "ecb-base")
        io = rados.open_ioctx("ecb-base")
        io.write_full("bulk", b"Z" * 8192)
        io.write("bulk", b"yy", offset=1)   # partial: tier absorbs it
        assert io.read("bulk") == b"Z" + b"yy" + b"Z" * 8189
        _wait_for(cluster,
                  lambda: "bulk" in _ec_pool_objects(cluster, base_id),
                  "flush to EC base")
        # a base copy EXISTING can be the first version's flush racing
        # the partial overwrite (the agent flushes on its own tick):
        # wait until the cache copy is CLEAN — the latest version
        # flushed — before dropping the overlay, or the still-dirty
        # v2 is orphaned in the no-longer-consulted tier and the
        # direct base read below serves v1 forever
        cache_id = _pool_id(cluster, "ecb-cache")

        def _flushed_clean() -> bool:
            ent = _pool_objects(cluster, cache_id).get("bulk")
            return ent is None or DIRTY_KEY not in ent[1]

        _wait_for(cluster, _flushed_clean, "latest version flushed")
        # drop the overlay: reads now hit the EC base directly
        _mon(rados, {"prefix": "osd tier remove-overlay",
                     "pool": "ecb-base"})
        _wait_for(cluster,
                  lambda: rados.open_ioctx("ecb-base") is not None,
                  "map propagated")
        end = time.time() + 30
        while True:
            try:
                assert io.read("bulk") == b"Z" + b"yy" + b"Z" * 8189
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)

    def test_hit_sets_rotate_and_stay_bounded(self, cluster, rados):
        _setup_tier(rados, cluster, "hs-base", "hs-cache")
        _mon(rados, {"prefix": "osd pool set", "pool": "hs-cache",
                     "var": "hit_set_period", "val": "1.0"})
        _mon(rados, {"prefix": "osd pool set", "pool": "hs-cache",
                     "var": "hit_set_count", "val": "3"})
        cache_id = _pool_id(cluster, "hs-cache")
        io = rados.open_ioctx("hs-base")
        for i in range(10):
            io.write_full(f"h{i}", b"x")
            cluster.tick(0.6)
        sets = []
        for osd in cluster.osds.values():
            for pgid, pg in osd.pgs.items():
                if pgid.pool == cache_id and pg.hit_sets:
                    sets.append(pg.hit_sets)
        assert sets, "no hit sets recorded"
        assert all(len(hs) <= 3 for hs in sets)
        recorded = set()
        for hs in sets:
            for _ts, oids in hs:
                recorded |= oids
        assert any(o.startswith("h") for o in recorded)


class TestModeSwitch:
    def test_mode_switch_does_not_strand_dirty_data(self, cluster,
                                                    rados):
        """Leaving writeback (here: -> none) with dirty objects in the
        tier must still flush them — stranding acked updates in a
        de-activated cache would be silent data loss."""
        _setup_tier(rados, cluster, "ms-base", "ms-cache")
        base_id = _pool_id(cluster, "ms-base")
        io = rados.open_ioctx("ms-base")
        io.write_full("stranded", b"must-reach-base")
        # immediately de-activate the cache before the agent flushed
        _mon(rados, {"prefix": "osd tier cache-mode",
                     "pool": "ms-cache", "mode": "none"})
        _mon(rados, {"prefix": "osd tier remove-overlay",
                     "pool": "ms-base"})
        _wait_for(cluster,
                  lambda: "stranded" in _pool_objects(cluster, base_id),
                  "dirty flushed after mode switch")
        assert _pool_objects(cluster, base_id)["stranded"][0] == \
            b"must-reach-base"


class TestTierGuards:
    def test_tier_chain_rejected(self, cluster, rados):
        rados.create_pool("g-a", pg_num=4)
        rados.create_pool("g-b", pg_num=4)
        rados.create_pool("g-c", pg_num=4)
        _mon(rados, {"prefix": "osd tier add", "pool": "g-a",
                     "tierpool": "g-b"})
        # b is a tier of a: chaining c under b must fail
        rv, out, _ = rados.mon_command(
            {"prefix": "osd tier add", "pool": "g-b", "tierpool": "g-c"})
        assert rv == -22, out
        # and a pool cannot tier itself
        rv, out, _ = rados.mon_command(
            {"prefix": "osd tier add", "pool": "g-c", "tierpool": "g-c"})
        assert rv == -22, out

    def test_pool_set_min_size_validated(self, cluster, rados):
        rados.create_pool("g-sz", pg_num=4)
        rv, out, _ = rados.mon_command(
            {"prefix": "osd pool set", "pool": "g-sz",
             "var": "min_size", "val": "5"})
        assert rv == -22, out
        rv, out, _ = rados.mon_command(
            {"prefix": "osd pool set", "pool": "g-sz",
             "var": "size", "val": "0"})
        assert rv == -22, out
        rv, out, _ = rados.mon_command(
            {"prefix": "osd pool set", "pool": "g-sz",
             "var": "min_size", "val": "2"})
        assert rv == 0, out
