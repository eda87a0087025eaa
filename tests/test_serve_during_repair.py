"""Serve-during-repair: client ops BLOCK on recovery pulls instead of
serving stale store bytes (ReplicatedPG wait_for_unreadable_object /
wait_for_degraded_object semantics), the blocked object's pull is
promoted to the front of the recovery queue, and the op resumes
bit-exact once the push applies.

Covered here:
  * missing-object read and write block-then-resume bit-exact
    (replicated + EC), with the recovery_blocked_ops /
    recovery_unblocked_ops / recovery_prio_promotions counters and
    the recovery_wait span;
  * blocked-op promotion ordering (AsyncReserver front lane);
  * a dup-op resend arriving while its first copy is recovery-blocked
    does not re-execute;
  * the stale-read oracle + storm-window slicing the recovery-storm
    drill (tools/loadgen.run_recovery_storm) is built from, and the
    drill itself: an OSD killed and reborn under open-loop load;
  * perf dump `qos.recovery` (the @recovery class's grants/stalls).
"""

import threading
import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils.config import Config
from ceph_tpu.utils.reserver import AsyncReserver
from ceph_tpu.vstart import MiniCluster

CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 5.0,
    "osd_qos_recovery": "0:2:0",
    # blocked ops resume well under 2s here; a tight op deadline only
    # bounds the damage when a drill wedges (30s default would stall
    # the whole tier-1 run, and the shared cluster poisons the file)
    "objecter_op_timeout": 10.0,
}


def _settle(io, timeout=60.0):
    end = time.time() + timeout
    while True:
        try:
            io.write_full("settle", b"s")
            return
        except RadosError:
            if time.time() > end:
                raise
            time.sleep(0.3)


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=3,
                    conf=Config(dict(CONF))).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def io(cluster):
    rados = cluster.client()
    rados.create_pool("sdr", pg_num=1, size=3, min_size=2)
    ctx = rados.open_ioctx("sdr")
    _settle(ctx)
    return ctx


def _primary_pg(cluster, io, oid):
    m = cluster.leader().osdmon.osdmap
    pgid = m.object_to_pg(io.pool_id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    primary = next(o for o in acting if o >= 0)
    osd = cluster.osds[primary]
    return osd, osd.get_pg(pgid)


def _counters(osd):
    d = osd._perf_dump()["osd"]
    return (d["recovery_blocked_ops"], d["recovery_unblocked_ops"],
            d["recovery_prio_promotions"])


def _make_missing(osd, pg, oid, stale=b"STALE-BYTES"):
    """Construct the exact hole the tentpole closes: the log claims
    the object's current version but the store holds other bytes —
    the state a GetLog merge / divergent rewind leaves behind until
    the recovery pull lands."""
    with pg.lock:
        cur = pg.pglog.objects[oid]
        osd.store.apply_transaction(
            Transaction().truncate(pg.cid, oid, 0)
            .write(pg.cid, oid, 0, stale))
        pg.pglog.missing[oid] = cur
    return cur


class TestReserverFrontLane:
    def test_front_request_jumps_fifo_waiters(self):
        """Blocked-op promotion ordering: a front grant runs before
        every queued FIFO waiter, FIFO order otherwise preserved."""
        order = []
        res = AsyncReserver(1)
        release_holder = []

        def holder(release):
            release_holder.append(release)

        res.request(holder)                       # occupies the slot
        for name in ("bg1", "bg2"):
            res.request(lambda rel, n=name: (order.append(n), rel()))
        res.request(lambda rel: (order.append("promoted"), rel()),
                    front=True)
        release_holder[0]()
        assert order == ["promoted", "bg1", "bg2"]

    def test_front_runs_immediately_when_slot_free(self):
        ran = []
        res = AsyncReserver(1)
        res.request(lambda rel: (ran.append(True), rel()), front=True)
        assert ran == [True]


class TestMissingBlockingReplicated:
    def test_read_blocks_then_resumes_bit_exact(self, cluster, io):
        body = b"PRISTINE-" * 200
        io.write_full("blk-r", body)
        osd, pg = _primary_pg(cluster, io, "blk-r")
        b0, u0, p0 = _counters(osd)
        _make_missing(osd, pg, "blk-r")
        got = io.read("blk-r")
        # bit-exact: the promoted pull restored the authoritative
        # copy BEFORE the read executed — never the stale store bytes
        assert bytes(got) == body
        b1, u1, p1 = _counters(osd)
        assert b1 > b0, "read never blocked"
        assert u1 - u0 == b1 - b0, "blocked op not resumed"
        assert p1 > p0, "pull never promoted"
        with pg.lock:
            assert "blk-r" not in pg.pglog.missing
            assert not pg._recovery_blocked

    def test_blocked_read_carries_recovery_wait_span(self, cluster,
                                                     io):
        body = b"SPAN-" * 100
        io.write_full("blk-span", body)
        osd, pg = _primary_pg(cluster, io, "blk-span")
        _make_missing(osd, pg, "blk-span")
        assert bytes(io.read("blk-span")) == body
        hist = osd.op_tracker.dump_historic_ops()["ops"]
        spans = [s for op in hist if "blk-span" in op["description"]
                 for s in op["spans"]]
        names = {s["name"] for s in spans}
        assert "recovery_wait" in names, sorted(names)
        wait = next(s for s in spans if s["name"] == "recovery_wait")
        assert wait["t1"] > wait["t0"]

    def test_write_blocks_then_resumes_bit_exact(self, cluster, io):
        """An append to a missing object must not build its txn over
        stale bytes: it parks, the pull restores the base, and the
        append lands on the restored content."""
        body = b"BASE-" * 150
        io.write_full("blk-w", body)
        osd, pg = _primary_pg(cluster, io, "blk-w")
        b0, u0, _ = _counters(osd)
        _make_missing(osd, pg, "blk-w")
        io.append("blk-w", b"+TAIL")
        assert bytes(io.read("blk-w")) == body + b"+TAIL"
        b1, u1, _ = _counters(osd)
        assert b1 > b0 and u1 - u0 == b1 - b0

    def test_dup_resend_while_blocked_not_reexecuted(self, cluster,
                                                     io):
        """A client resend arriving while its first copy is
        recovery-blocked parks too; on resume the first executes and
        the resend re-replies through the dedup table — the op runs
        ONCE."""
        from types import SimpleNamespace
        from ceph_tpu.osd.messages import MOSDOp
        body = b"ONCE-" * 120
        io.write_full("blk-dup", body)
        osd, pg = _primary_pg(cluster, io, "blk-dup")
        with pg.lock:
            cur = pg.pglog.objects["blk-dup"]
            # claim a FUTURE version missing: the promoted pull (a
            # peer's current copy) cannot retire it, so the ops stay
            # parked until the test releases them deliberately
            pg.pglog.missing["blk-dup"] = (cur[0], cur[1] + 1000)
        replies = []
        orig_reply = osd.reply_to_client
        osd.reply_to_client = \
            lambda conn, msg: replies.append((msg.tid, msg.result,
                                              msg.version))
        try:
            conn = SimpleNamespace(peer_name="client.dup",
                                   peer_addr=("127.0.0.1", 1))
            def mk():
                m = MOSDOp(tid=77001, pgid=str(pg.pgid),
                           oid="blk-dup",
                           ops=[("writefull", b"DUP-PAYLOAD" * 50)],
                           epoch=osd.osdmap.epoch)
                m.src = "client.dup"
                return m
            pg.do_op(conn, mk())          # first copy: parks
            pg.do_op(conn, mk())          # resend: parks too
            with pg.lock:
                assert len(pg._recovery_blocked["blk-dup"]["ops"]) == 2
                entries_before = sum(
                    1 for e in pg.pglog.entries
                    if e["oid"] == "blk-dup")
                # release: drop the artificial claim and wake
                del pg.pglog.missing["blk-dup"]
                pg._wake_recovery_blocked("blk-dup")
            # the resumes serialize on the pg's op shard: copy 1
            # executes, copy 2 lands in the dup table (in-flight or
            # completed) and is ANSWERED ONCE through the original
            # gather — exactly one reply, one log entry, one apply
            end = time.time() + 20
            while not replies and time.time() < end:
                time.sleep(0.05)
            time.sleep(1.0)               # a re-execution would have
            assert len(replies) == 1, replies    # produced a 2nd reply
            assert replies[0][1] == 0, replies
            with pg.lock:
                entries_after = sum(1 for e in pg.pglog.entries
                                    if e["oid"] == "blk-dup")
            assert entries_after == entries_before + 1
        finally:
            osd.reply_to_client = orig_reply
        assert bytes(io.read("blk-dup")) == b"DUP-PAYLOAD" * 50

    def test_interval_change_drops_blocked_ops_with_eagain(
            self, cluster, io):
        """A new interval EAGAINs parked ops back (the client
        resends against the re-peered pg) — nothing stays stranded."""
        from types import SimpleNamespace
        from ceph_tpu.osd.messages import MOSDOp
        io.write_full("blk-iv", b"IV" * 64)
        osd, pg = _primary_pg(cluster, io, "blk-iv")
        with pg.lock:
            cur = pg.pglog.objects["blk-iv"]
            pg.pglog.missing["blk-iv"] = (cur[0], cur[1] + 1000)
        replies = []
        orig_reply = osd.reply_to_client
        osd.reply_to_client = \
            lambda conn, msg: replies.append(msg.result)
        try:
            conn = SimpleNamespace(peer_name="client.iv",
                                   peer_addr=("127.0.0.1", 1))
            m = MOSDOp(tid=77002, pgid=str(pg.pgid), oid="blk-iv",
                       ops=[("read", 0, 0)], epoch=osd.osdmap.epoch)
            m.src = "client.iv"
            pg.do_op(conn, m)
            with pg.lock:
                assert pg._recovery_blocked
                pg.update_acting(list(pg.up), list(pg.acting[::-1]))
            assert replies == [-11]
            with pg.lock:
                assert not pg._recovery_blocked
                pg.pglog.missing.pop("blk-iv", None)
        finally:
            osd.reply_to_client = orig_reply
        # restore the pg for later tests (the reversed acting set is
        # fiction; the real map re-peers it)
        m2 = cluster.leader().osdmon.osdmap
        pgid = m2.object_to_pg(io.pool_id, "blk-iv")
        up, acting = m2.pg_to_up_acting_osds(pgid)
        with pg.lock:
            pg.update_acting(up, acting)
        end = time.time() + 30
        while time.time() < end:
            try:
                io.write_full("blk-iv", b"post")
                break
            except RadosError:
                time.sleep(0.3)


class TestMissingBlockingEC:
    def test_ec_read_blocks_then_resumes_bit_exact(self, cluster):
        rados = cluster.client()
        rados.create_ec_pool("sdrec", "sdrk2m1",
                             {"plugin": "tpu", "k": 2, "m": 1,
                              "technique": "reed_sol_van"}, pg_num=1)
        ioe = rados.open_ioctx("sdrec")
        _settle(ioe)
        body = b"ECBODY-" * 400
        ioe.write_full("eblk", body)
        osd, pg = _primary_pg(cluster, ioe, "eblk")
        b0, u0, p0 = _counters(osd)
        with pg.lock:
            cur = pg.pglog.objects["eblk"]
            pg.pglog.missing["eblk"] = cur
        assert bytes(ioe.read("eblk")) == body
        b1, u1, p1 = _counters(osd)
        assert b1 > b0, "EC read never blocked"
        assert u1 - u0 == b1 - b0
        assert p1 > p0, "EC rebuild never promoted"
        with pg.lock:
            assert "eblk" not in pg.pglog.missing

    def test_ec_write_blocks_then_resumes(self, cluster):
        ioe = cluster.client().open_ioctx("sdrec")
        body = b"ECW-" * 300
        ioe.write_full("eblk2", body)
        osd, pg = _primary_pg(cluster, ioe, "eblk2")
        b0, u0, _ = _counters(osd)
        with pg.lock:
            pg.pglog.missing["eblk2"] = pg.pglog.objects["eblk2"]
        ioe.append("eblk2", b"+ETAIL")
        assert bytes(ioe.read("eblk2")) == body + b"+ETAIL"
        b1, u1, _ = _counters(osd)
        assert b1 > b0 and u1 - u0 == b1 - b0


class TestBackfillTargetDiscipline:
    def test_parked_subop_on_backfill_target_promotes_base_pull(
            self, cluster, io):
        """A live sub-op landing on a backfill TARGET ahead of its
        base object's push (the primary's routing frontier runs ahead
        of landed pushes) parks on the prior gap, counts as
        recovery-blocked, and promotes the base pull from the primary
        — then applies in order when the push lands."""
        from types import SimpleNamespace
        from ceph_tpu.osd.messages import MOSDRepOp
        io.write_full("bft", b"BASE" * 64)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "bft")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary, replica = acting[0], acting[1]
        rosd = cluster.osds[replica]
        rpg = rosd.get_pg(pgid)
        b0, u0, _ = _counters(rosd)
        pulls = []
        orig_pull = rosd.pg_request_push
        rosd.pg_request_push = \
            lambda pgid_, holder, oid, front=False: pulls.append(
                (holder, oid, front))
        sent = []
        orig_send = rosd.send_osd_reply
        rosd.send_osd_reply = lambda conn, msg, req=None: sent.append(msg)
        try:
            with rpg.lock:
                cur = rpg.pglog.objects["bft"]
                # construct the race: the target is mid-backfill and
                # a sub-op arrives whose prior (a version the scan
                # has not pushed here yet) is absent locally
                rpg.set_backfill_state(False, "zzz")
                rpg.pglog.objects.pop("bft")
            entry = {"ev": (cur[0], cur[1] + 2), "oid": "bft",
                     "op": "modify", "prior": (cur[0], cur[1] + 1),
                     "rollback": None, "shard": None}
            sub = MOSDRepOp(reqid=("client.bft", 1),
                            pgid=str(pgid),
                            ops=Transaction().write(
                                rpg.cid, "bft", 0, b"RACED").ops,
                            log=entry, epoch=rosd.osdmap.epoch)
            sub.src = f"osd.{primary}"
            conn = SimpleNamespace(peer_name=f"osd.{primary}",
                                   peer_addr=("127.0.0.1", 1))
            rpg.handle_rep_op(conn, sub)
            with rpg.lock:
                assert rpg._parked, "sub-op did not park"
            b1, u1, _ = _counters(rosd)
            assert b1 > b0, "parked sub-op not counted as blocked"
            assert pulls == [(primary, "bft", True)], pulls
            # the base push lands: the parked sub-op applies in order
            with rpg.lock:
                rpg.pglog.record_recovered(
                    (cur[0], cur[1] + 1), "bft")
                rpg._flush_parked("bft")
                assert not rpg._parked
            b2, u2, _ = _counters(rosd)
            assert u2 - u0 == b2 - b0, "park release not balanced"
            assert sent and sent[-1].result == 0
        finally:
            rosd.pg_request_push = orig_pull
            rosd.send_osd_reply = orig_send
            with rpg.lock:
                rpg.set_backfill_state(True)
                # rewind the artificially minted entries (cur+1,
                # cur+2): they sit AHEAD of the primary's version
                # counter, so the next two real writes to this pool
                # would dedup as already-applied on this replica and
                # silently skip — polluting every later test in the
                # shared module cluster
                rpg.pglog.rewind(cur, lambda e: True)
                rpg.version = cur[1]
        # heal the replica for later tests
        io.write_full("bft", b"HEAL" * 64)


class TestStrandedMissingLiveness:
    def test_replica_missing_claim_is_healed_by_nudge(self, cluster,
                                                      io):
        """The run-12 wedge class: a REPLICA holds a missing claim
        whose heal push was lost (rewind-exposed prior, lost wire
        push).  Nothing used to retry — the copy sat data-incomplete
        behind a clean-looking head forever (and wait_for_clean now
        refuses to call that clean).  The heartbeat treats a
        non-empty missing set as incomplete: the replica nudges its
        primary, the peering round reads the peer's missing set off
        get_info (pg_missing_t rides the exchange) and re-pushes
        exactly those objects."""
        cluster.wait_for_clean(60)    # settle prior tests' backfill churn
        body = b"NUDGE-" * 120
        io.write_full("strand", body)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "strand")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        replica = acting[1]
        rosd = cluster.osds[replica]
        rpg = rosd.get_pg(pgid)
        # wait until the replica both holds the bytes AND indexes the
        # write in its live pglog, then strand it: stale bytes + a
        # missing claim at the current version
        end = time.time() + 30
        while time.time() < end:
            rpg = rosd.get_pg(pgid)
            try:
                with rpg.lock:
                    landed = "strand" in rpg.pglog.objects
                if landed and rosd.store.read(rpg.cid, "strand"):
                    break
            except Exception:
                pass
            time.sleep(0.2)
        with rpg.lock:
            cur = rpg.pglog.objects["strand"]
            rosd.store.apply_transaction(
                Transaction().truncate(rpg.cid, "strand", 0)
                .write(rpg.cid, "strand", 0, b"STALE"))
            rpg.pglog.missing["strand"] = cur
        assert rpg.get_info().get("missing"), "claim not advertised"
        # no client op touches it: only the liveness nudge can heal
        end = time.time() + 45
        while time.time() < end:
            with rpg.lock:
                if "strand" not in rpg.pglog.missing:
                    break
            cluster.tick(0.3)
        with rpg.lock:
            assert "strand" not in rpg.pglog.missing, \
                "missing claim stranded: nudge/re-push never healed it"
        assert bytes(rosd.store.read(rpg.cid, "strand")) == body
        cluster.wait_for_clean(30)


class TestQosRecoveryDump:
    def test_perf_dump_exposes_recovery_class(self, cluster, io):
        osd = next(iter(cluster.osds.values()))
        qos = osd._perf_dump()["qos"]
        assert "recovery" in qos
        rec = qos["recovery"]
        for key in ("configured", "res_grants", "prop_grants",
                    "deadline_misses", "throttle_stalls"):
            assert key in rec, key
        assert rec["configured"] == CONF["osd_qos_recovery"]

    def test_per_client_throttle_stalls_counted(self):
        from ceph_tpu.utils.dmclock import DmClockState, QosSpec
        t = [100.0]
        st = DmClockState(clock=lambda: t[0])
        st.configure({"capped": QosSpec(res=0.0, weight=1.0, lim=1.0)})
        # first grant advances l_tag a full second; the next pick has
        # nothing servable -> a stall attributed to the capped class
        got, _, _ = st.pick({"capped": 99.0}, now=t[0])
        assert got == "capped"
        got, _, _ = st.pick({"capped": 100.0}, now=t[0])
        assert got is None
        ent = st.stats()["clients"]["capped"]
        assert ent["throttle_stalls"] == 1


class TestStaleReadOracle:
    """The verify-mode oracle the storm drill's zero-stale-bytes gate
    rides (tools/loadgen._Verifier)."""

    def _pay(self, seed):
        from ceph_tpu.tools.loadgen import _payload_bytes
        return _payload_bytes(seed, 64)

    def test_current_write_is_not_stale(self):
        from ceph_tpu.tools.loadgen import _Verifier
        v = _Verifier()
        v.note_submit("p", "o", 1, 1.0)
        v.note_ack("p", "o", 1, 2.0)
        assert not v.judge_read("p", "o", self._pay(1), 5.0)

    def test_superseded_before_read_began_is_stale(self):
        from ceph_tpu.tools.loadgen import _Verifier
        v = _Verifier()
        v.note_submit("p", "o", 1, 1.0)
        v.note_ack("p", "o", 1, 2.0)
        v.note_submit("p", "o", 2, 3.0)       # after w1 fully acked
        v.note_ack("p", "o", 2, 4.0)
        # read began at 5.0, after w2 acked: observing w1 is stale
        assert v.judge_read("p", "o", self._pay(1), 5.0)
        assert not v.judge_read("p", "o", self._pay(2), 5.0)

    def test_concurrent_write_never_false_positives(self):
        from ceph_tpu.tools.loadgen import _Verifier
        v = _Verifier()
        v.note_submit("p", "o", 1, 1.0)
        v.note_ack("p", "o", 1, 4.0)          # overlaps w2's submit
        v.note_submit("p", "o", 2, 3.0)
        v.note_ack("p", "o", 2, 5.0)
        # w1 was still in flight when w2 was submitted: either answer
        # is linearizable for a read starting at 6.0
        assert not v.judge_read("p", "o", self._pay(1), 6.0)
        assert not v.judge_read("p", "o", self._pay(2), 6.0)

    def test_unknown_bytes_are_stale(self):
        from ceph_tpu.tools.loadgen import _Verifier
        v = _Verifier()
        v.note_warm("p", "o", 7)
        assert v.judge_read("p", "o", self._pay(99), 1.0)
        assert v.judge_read("p", "o", b"short", 1.0)
        assert not v.judge_read("p", "o", self._pay(7), 1.0)

    def test_in_flight_write_is_valid(self):
        from ceph_tpu.tools.loadgen import _Verifier
        v = _Verifier()
        v.note_warm("p", "o", 7)
        v.note_submit("p", "o", 8, 1.0)       # never acked
        assert not v.judge_read("p", "o", self._pay(8), 9.0)


class TestWindowReport:
    def test_storm_window_slices_by_scheduled_arrival(self):
        from ceph_tpu.tools.loadgen import LoadGen, TenantSpec, _Rec
        gen = LoadGen([TenantSpec("p", rate=1, duration=0.01)])
        gen.last_records = [
            _Rec("p", "read", 0.010, 10, True, False, 0.5, False),
            _Rec("p", "read", 0.500, 10, True, False, 1.5, False),
            _Rec("p", "read", 0.020, 10, True, False, 2.5, True),
            _Rec("p", "write_full", 0.1, 10, False, True, 1.7, False),
        ]
        win = gen.window_report(1.0, 2.0)
        assert win["p"]["ops"] == 2
        assert win["p"]["errors"] == 1
        assert win["p"]["stale_reads"] == 0
        assert win["p"]["p99_ms"] == 500.0
        full = gen.window_report(0.0, 10.0)
        assert full["p"]["ops"] == 4
        assert full["p"]["stale_reads"] == 1


class TestRecoveryStorm:
    def test_osd_kill_and_rebirth_under_open_loop_load(self):
        """The end-to-end durability drill: two tenants offer seeded
        open-loop load to size-3 / min_size-2 pools while one OSD is
        killed abruptly and reborn.  No client op fails, no read
        returns superseded bytes, every op that blocked on a recovery
        pull resumed, the ledger's acked writes (one of them made
        while degraded) read back bit-exact, and the cluster comes
        back clean.  Counts and completion only: no latency is held."""
        from ceph_tpu.tools.loadgen import TenantSpec, run_recovery_storm
        conf = dict(CONF, osd_pool_qos_gold="40:4:0",
                    objecter_op_timeout=60.0)
        c = MiniCluster(num_mons=1, num_osds=3,
                        conf=Config(conf)).start()
        try:
            rados = c.client()
            ios = {}
            for name in ("gold", "bulk"):
                rados.create_pool(name, pg_num=8, size=3, min_size=2)
                ios[name] = rados.open_ioctx(name)
                _settle(ios[name])
            tenants = [
                TenantSpec("gold", rate=30, duration=6.0, obj_count=16,
                           zipf_s=1.1, read_frac=0.6, payload=8192),
                TenantSpec("bulk", rate=15, duration=6.0, obj_count=16,
                           zipf_s=0.9, read_frac=0.3, payload=16384),
            ]
            res = run_recovery_storm(c, ios, tenants, seed=0x570A,
                                     kill_at=1.5, revive_after=1.2,
                                     clean_timeout=120.0)
        finally:
            c.stop()
        report = res["report"]
        assert report["completed"] == sum(report["offered"].values())
        assert res["errors"] == 0
        assert res["stale_reads"] == 0
        assert res["recovery_blocked_ops"] == \
            res["recovery_unblocked_ops"]
        assert res["ledger_ok"], res["ledger_detail"]
        # the storm really happened: ops were offered between the kill
        # and the clean cluster, and wait_for_clean returned
        assert sum(w["ops"] for w in res["storm"].values()) > 0
        assert res["recovery_wall_s"] > 0
