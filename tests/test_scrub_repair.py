"""Scrub repair: corrupt/missing copies heal back to clean.

Reference scenarios: test/osd/osd-scrub-repair.sh
(TEST_corrupt_and_repair_replicated, TEST_corrupt_and_repair_jerasure
at :201,221) and PGBackend::be_select_auth_object (PGBackend.cc:501) —
authoritative-copy selection then repair writes, driven by a
`ceph pg repair` command.
"""

import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.store.objectstore import StoreError, Transaction
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster


@pytest.fixture(scope="module")
def cluster():
    conf = Config({
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
    })
    c = MiniCluster(num_mons=1, num_osds=3, conf=conf).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def rados(cluster):
    return cluster.client()


def _settle(rados, cluster, pool, **kw):
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


def _primary_pg(cluster, pool_id, oid):
    m = cluster.osds[0].osdmap
    pgid = m.object_to_pg(pool_id, oid)
    primary = m.pg_primary(pgid)
    return pgid, cluster.osds[primary].pgs[pgid]


def _holders(cluster, pgid):
    m = cluster.osds[0].osdmap
    _up, acting = m.pg_to_up_acting_osds(pgid)
    return acting


class TestReplicatedRepair:
    def test_corrupt_replica_heals(self, cluster, rados):
        rados.create_pool("rep-fix", pg_num=4)
        io = _settle(rados, cluster, "rep-fix")
        io.write_full("victim", b"pristine-content")
        pgid, pg = _primary_pg(cluster, io.pool_id, "victim")
        acting = _holders(cluster, pgid)
        # corrupt a NON-primary replica on disk (silent bitrot)
        replica = cluster.osds[acting[1]]
        replica.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "victim", 2, b"\xbe\xef"))
        dirty = pg.scrub(deep=True)
        assert dirty["inconsistent"], "scrub missed the corruption"
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert replica.store.read(f"pg_{pgid}", "victim") == \
            b"pristine-content"
        assert io.read("victim") == b"pristine-content"

    def test_corrupt_primary_copy_pulls_from_majority(self, cluster,
                                                      rados):
        rados.create_pool("rep-pri", pg_num=4)
        io = _settle(rados, cluster, "rep-pri")
        io.write_full("primary-bad", b"the-true-bytes")
        pgid, pg = _primary_pg(cluster, io.pool_id, "primary-bad")
        acting = _holders(cluster, pgid)
        primary = cluster.osds[acting[0]]
        primary.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "primary-bad", 0,
                                b"XXXX"))
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert primary.store.read(f"pg_{pgid}", "primary-bad") == \
            b"the-true-bytes"

    def test_missing_replica_copy_is_pushed(self, cluster, rados):
        rados.create_pool("rep-miss", pg_num=4)
        io = _settle(rados, cluster, "rep-miss")
        io.write_full("lost", b"re-replicate-me")
        pgid, pg = _primary_pg(cluster, io.pool_id, "lost")
        acting = _holders(cluster, pgid)
        replica = cluster.osds[acting[2]]
        replica.store.apply_transaction(
            Transaction().remove(f"pg_{pgid}", "lost"))
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert replica.store.read(f"pg_{pgid}", "lost") == \
            b"re-replicate-me"


class TestECRepair:
    @pytest.fixture(scope="class")
    def io(self, cluster, rados):
        rados.create_ec_pool("ec-fix", "fix_k2m1",
                             {"plugin": "tpu", "k": 2, "m": 1})
        return _settle(rados, cluster, "ec-fix")

    def test_corrupt_shard_rebuilds(self, cluster, rados, io):
        io.write_full("shardbad", bytes(range(256)) * 32)
        pgid, pg = _primary_pg(cluster, io.pool_id, "shardbad")
        acting = _holders(cluster, pgid)
        # corrupt shard 1 on its holder
        holder = cluster.osds[acting[1]]
        good = holder.store.read(f"pg_{pgid}", "shardbad.s1")
        holder.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "shardbad.s1", 7,
                                b"\x00\xff\x00"))
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert holder.store.read(f"pg_{pgid}", "shardbad.s1") == good
        assert io.read("shardbad") == bytes(range(256)) * 32

    def test_missing_shard_file_rebuilds(self, cluster, rados, io):
        io.write_full("sharddel", b"Q" * 9000)
        pgid, pg = _primary_pg(cluster, io.pool_id, "sharddel")
        acting = _holders(cluster, pgid)
        holder = cluster.osds[acting[2]]
        holder.store.apply_transaction(
            Transaction().remove(f"pg_{pgid}", "sharddel.s2"))
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert holder.store.exists(f"pg_{pgid}", "sharddel.s2")

    def test_corrupt_primary_shard_excluded_from_decode(self, cluster,
                                                        rados, io):
        payload = b"ABCD" * 4000
        io.write_full("pribad", payload)
        pgid, pg = _primary_pg(cluster, io.pool_id, "pribad")
        acting = _holders(cluster, pgid)
        primary = cluster.osds[acting[0]]
        primary.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "pribad.s0", 0,
                                b"garbage!"))
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        assert io.read("pribad") == payload


class TestECRepairIsARebuild:
    """Scrub repair reads for the positions it found bad and pushes
    what the decode gave (PR 48): the same call a rebuild makes, no
    object put together, nothing re-encoded; file and hinfo land bit
    for bit as the write laid them out."""

    @pytest.fixture(scope="class")
    def io(self, cluster, rados):
        rados.create_ec_pool("ec-fix2", "fix2_k2m1",
                             {"plugin": "tpu", "k": 2, "m": 1})
        return _settle(rados, cluster, "ec-fix2")

    @pytest.mark.parametrize("pos", [0, 1, 2],
                             ids=["data-own", "data-peer", "parity"])
    def test_a_corrupt_shard_lands_bit_exact(self, cluster, io, pos):
        from ceph_tpu.ops import hbm_cache
        from ceph_tpu.osd import ecutil
        from ceph_tpu.osd.pglog import HINFO_KEY
        from ceph_tpu.utils import copyaudit
        oid = f"rebuilt{pos}"
        io.write_full(oid, bytes(range(251)) * 67)
        pgid, pg = _primary_pg(cluster, io.pool_id, oid)
        cid, soid = f"pg_{pgid}", f"{oid}.s{pos}"
        holder = cluster.osds[_holders(cluster, pgid)[pos]]
        good = bytes(holder.store.read(cid, soid))
        hinfo = holder.store.getattr(cid, soid, HINFO_KEY)
        holder.store.apply_transaction(
            Transaction().write(cid, soid, 11, b"\xff\x00\xff\x00"))
        assert bytes(holder.store.read(cid, soid)) != good
        hbm_cache.get().clear()     # the gather's way, not the cache's
        reads, real = [], pg._ec_read_local
        rebuilds, real_rebuild = [], ecutil.rebuild_shards

        def read_spy(oid_, **kw):
            reads.append((oid_, sorted(kw["exclude"]), kw["want"]))
            return real(oid_, **kw)

        def rebuild_spy(codec, sinfo, shards, lost, size, qos=None):
            rebuilds.append((sorted(shards), list(lost)))
            return real_rebuild(codec, sinfo, shards, lost, size, qos=qos)

        pg._ec_read_local = read_spy
        ecutil.rebuild_shards = rebuild_spy
        staged = copyaudit.snapshot()["sites"].get("ec.stage",
                                                   {"copies": 0})
        try:
            result = pg.scrub(deep=True, repair=True)
        finally:
            del pg._ec_read_local
            ecutil.rebuild_shards = real_rebuild
        assert result["repaired"] >= 1
        assert result["clean_after_repair"], result
        # one read for the bad position, from the two others
        assert reads == [(oid, [pos], [pos])]
        assert rebuilds == [([p for p in range(3) if p != pos], [pos])]
        assert copyaudit.snapshot()["sites"].get(
            "ec.stage", {"copies": 0})["copies"] == staged["copies"]
        assert bytes(holder.store.read(cid, soid)) == good
        assert holder.store.getattr(cid, soid, HINFO_KEY) == hinfo
        assert io.read(oid) == bytes(range(251)) * 67

    def test_the_cache_is_asked_first(self, cluster, io):
        """Where the HBM cache still holds the object the repair takes
        the bad shard's rows from it and reads nothing."""
        oid = "cachefirst"
        io.write_full(oid, b"Z" * 20000)
        pgid, pg = _primary_pg(cluster, io.pool_id, oid)
        holder = cluster.osds[_holders(cluster, pgid)[1]]
        holder.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", f"{oid}.s1", 3, b"\x01\x02"))
        order = []
        real_push = pg.osd._ec_push_shards
        real_read = pg._ec_read_local

        def push_spy(pg_, oid_, version, missing, rebuilt=None):
            order.append(("push", rebuilt is not None))
            return real_push(pg_, oid_, version, missing, rebuilt)

        def read_spy(oid_, **kw):
            order.append(("read", kw.get("want")))
            return real_read(oid_, **kw)

        pg.osd._ec_push_shards = push_spy
        pg._ec_read_local = read_spy
        try:
            result = pg.scrub(deep=True, repair=True)
        finally:
            del pg.osd._ec_push_shards
            del pg._ec_read_local
        assert result["clean_after_repair"], result
        assert order[0] == ("push", False)
        # no cache entry on this pool's host-served writes: the read
        # for the position follows, and its push
        assert order in ([("push", False)],
                         [("push", False), ("read", [1]), ("push", True)])
        assert io.read(oid) == b"Z" * 20000


class TestRepairCommand:
    def test_pg_repair_mon_command(self, cluster, rados):
        rados.create_pool("cmd-fix", pg_num=4)
        io = _settle(rados, cluster, "cmd-fix")
        io.write_full("cmdobj", b"command-driven-repair")
        pgid, pg = _primary_pg(cluster, io.pool_id, "cmdobj")
        acting = _holders(cluster, pgid)
        replica = cluster.osds[acting[1]]
        replica.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "cmdobj", 0, b"BAD"))
        rv, out, _ = rados.mon_command(
            {"prefix": "pg repair", "pgid": str(pgid)})
        assert rv == 0, out
        assert "repair" in out
        end = time.time() + 30
        while True:
            try:
                if replica.store.read(f"pg_{pgid}", "cmdobj") == \
                        b"command-driven-repair":
                    break
            except StoreError:
                pass
            if time.time() > end:
                raise AssertionError("pg repair command never healed")
            cluster.tick(0.3)
            time.sleep(0.05)

    def test_pg_scrub_command_bad_pgid(self, cluster, rados):
        rv, out, _ = rados.mon_command(
            {"prefix": "pg repair", "pgid": "nonsense"})
        assert rv == -22


class TestScheduledScrub:
    """Automatic interval-driven scrubs (OSD::sched_scrub,
    osd/OSD.cc:1054): corruption is caught — and with auto_repair,
    healed — without any `pg scrub` command."""

    @pytest.fixture(scope="class")
    def sched_cluster(self):
        conf = Config({
            "mon_tick_interval": 0.5,
            "osd_heartbeat_interval": 0.3,
            "osd_heartbeat_grace": 8.0,
            "mon_osd_min_down_reporters": 2,
            # aggressive schedule: shallow every 1s, deep every 2s
            "osd_scrub_min_interval": 1.0,
            "osd_deep_scrub_interval": 2.0,
            "osd_scrub_auto_repair": True,
        })
        c = MiniCluster(num_mons=1, num_osds=3, conf=conf).start()
        yield c
        c.stop()

    def test_scheduled_deep_scrub_catches_corruption(
            self, sched_cluster):
        cluster = sched_cluster
        rados = cluster.client()
        rados.create_pool("auto-scrub", pg_num=4)
        io = _settle(rados, cluster, "auto-scrub")
        io.write_full("victim", b"bitrot-target-content")
        pgid, pg = _primary_pg(cluster, io.pool_id, "victim")
        acting = _holders(cluster, pgid)
        # silent bitrot on a replica — NO scrub command follows
        replica = cluster.osds[acting[1]]
        replica.store.apply_transaction(
            Transaction().write(f"pg_{pgid}", "victim", 3,
                                b"\xde\xad"))
        # the scheduler must detect AND (auto_repair) heal it
        end = time.time() + 30
        while time.time() < end:
            res = pg.last_scrub_result
            if res and (res.get("inconsistent")
                        or res.get("repaired")):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"scheduled scrub never saw the corruption: "
                f"{pg.last_scrub_result}")
        # healed on disk without any command
        end = time.time() + 30
        while time.time() < end:
            if replica.store.read(f"pg_{pgid}", "victim") == \
                    b"bitrot-target-content":
                break
            time.sleep(0.2)
        else:
            raise AssertionError("auto repair never healed the copy")

    def test_stamps_advance_without_commands(self, sched_cluster):
        cluster = sched_cluster
        rados = cluster.client()
        rados.create_pool("auto-stamp", pg_num=4)
        io = _settle(rados, cluster, "auto-stamp")
        io.write_full("obj", b"x")
        pgid, pg = _primary_pg(cluster, io.pool_id, "obj")
        first = pg.last_scrub_stamp
        end = time.time() + 20
        while pg.last_scrub_stamp == first and time.time() < end:
            time.sleep(0.2)
        assert pg.last_scrub_stamp > first, \
            "scheduler never fired a scrub"
