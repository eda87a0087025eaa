"""Divergent-log peering: kill the primary mid-EC-write and prove the
survivors converge without losing acked data.

The scenario the reference exercises via
test/osd/osd-scrub-repair.sh:243 (TEST_unfound_erasure_coded) and the
PGLog rewind machinery (osd/PGLog.h, osd/ECTransaction.h rollback):

  * a write acked to the client exists on ALL live shards (the EC
    gather requires every shard), so survivors can always decode it;
  * a write the primary died in the middle of exists on a SUBSET of
    shards.  If >= k shards carry it, the new primary may roll forward
    (decodable, no client was told either way); with < k shards it MUST
    roll back via the stashed rollback state — those shards alone can
    never decode stripe v2.
"""

import time

import pytest

from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pg import ZERO_EV, shard_oid, stash_oid
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils import denc
from ceph_tpu.vstart import MiniCluster


@pytest.fixture()
def cluster():
    c = MiniCluster(num_mons=3, num_osds=3).start()
    yield c
    c.stop()


def _ec_setup(cluster):
    rados = cluster.client()
    rados.create_ec_pool("ecdiv", "k2m1",
                         {"plugin": "tpu", "k": 2, "m": 1,
                          "technique": "reed_sol_van"})
    return rados, rados.open_ioctx("ecdiv")


def _partial_ec_write(cluster, io, oid: str, payload: bytes,
                      to_shards: list[int]):
    """Apply a v-next EC write to only SOME shards — exactly what the
    acting set looks like when the primary dies mid-fan-out."""
    m = cluster.leader().osdmon.osdmap
    pgid = m.object_to_pg(io.pool_id, oid)
    up, acting = m.pg_to_up_acting_osds(pgid)
    primary = next(o for o in acting if o >= 0)
    ppg = cluster.osds[primary].get_pg(pgid)
    codec = ppg._ec_codec()
    sinfo = ppg._ec_sinfo(codec)
    shards, crcs = ecutil.encode_object(codec, sinfo, payload)
    # strictly newer than EVERY replica's applied state: the mon-map
    # "primary" may not be the replica that executed the client write
    # (map propagation race), and a colliding eversion would make the
    # partial write an idempotent no-op instead of a divergent v-next
    replicas = [cluster.osds[o].get_pg(pgid) for o in acting if o >= 0]
    ev = (max(p.interval_epoch for p in replicas),
          max(p.version for p in replicas) + 1)
    # prior likewise from the most-advanced replica: a lagging copy
    # would yield prior=None, mislabeling the divergent write a CREATE
    # (rewind would then delete the object instead of restoring it)
    prior = max((p.pglog.objects.get(oid) for p in replicas
                 if p.pglog.objects.get(oid) is not None),
                default=None)
    entry = {"ev": ev, "oid": oid, "op": "modify", "prior": prior,
             "rollback": {"type": "stash"}, "shard": None}
    for shard in to_shards:
        osd_id = acting[shard]
        pg = cluster.osds[osd_id].get_pg(pgid)
        soid = shard_oid(oid, shard)
        txn = Transaction()
        if prior is not None:
            txn.try_clone(pg.cid, soid, stash_oid(soid, prior))
        hinfo = denc.dumps({"size": len(payload), "crc": crcs[shard],
                            "shard": shard,
                            "stripe_unit": sinfo.chunk_size})
        txn.truncate(pg.cid, soid, 0)
        txn.write(pg.cid, soid, 0, shards[shard])
        txn.setattr(pg.cid, soid, "_hinfo", hinfo)
        with pg.lock:
            pg._apply_ec_sub_write(txn, entry, shard)
    return pgid, acting, primary


def _settle(io) -> None:
    """Write until the new pool's PGs are active."""
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            return
        except Exception:
            if time.time() > end:
                raise
            time.sleep(0.3)


def _wait_read(io, oid: str, timeout: float = 30.0) -> bytes:
    from ceph_tpu.client import RadosError
    end = time.time() + timeout
    last = None
    while time.time() < end:
        try:
            return io.read(oid)
        except RadosError as e:
            last = e
            time.sleep(0.3)
    raise AssertionError(f"read never succeeded: {last}")


class TestDivergentRewind:
    def test_rollback_when_under_k_shards(self, cluster):
        """v2 reached only 1 of 3 shards (k=2): after the primary dies
        the divergent shard must REWIND and reads must return v1."""
        rados, io = _ec_setup(cluster)
        v1 = b"acked-and-safe" * 300
        v2 = b"torn-unacked!!" * 300
        io.write_full("obj", v1)
        assert io.read("obj") == v1
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj")
        up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        # partial v2: only the first non-primary shard gets it
        victim = [s for s, o in enumerate(acting) if o != primary][:1]
        _partial_ec_write(cluster, io, "obj", v2, to_shards=victim)
        cluster.kill_osd(primary)
        cluster.wait_for_osd_down(primary)
        assert _wait_read(io, "obj") == v1

    def test_rollforward_when_k_shards_have_it(self, cluster):
        """v2 reached 2 of 3 shards (k=2, both survivors): the new
        primary may keep it — v2 is decodable and was never nacked."""
        rados, io = _ec_setup(cluster)
        v1 = b"first-version!" * 300
        v2 = b"newer-version!" * 300
        io.write_full("obj2", v1)
        assert io.read("obj2") == v1
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj2")
        up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        others = [s for s, o in enumerate(acting) if o != primary]
        _partial_ec_write(cluster, io, "obj2", v2, to_shards=others)
        cluster.kill_osd(primary)
        cluster.wait_for_osd_down(primary)
        assert _wait_read(io, "obj2") == v2

    def test_unanswered_peer_blocks_the_rewind(self, cluster):
        """A peer that did not answer the info round may hold the
        newest head: the vote must NOT settle on an older head that a
        catching-up shard happens to share — that rewound (destroyed)
        fully acked writes on a busy host.  It stays inactive, and
        picks the real head once the peer answers."""
        rados, io = _ec_setup(cluster)
        io.write_full("obj", b"v1-acked-by-all" * 300)
        v2 = b"v2-acked-by-all" * 300
        io.write_full("obj", v2)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        ppg = cluster.osds[primary].get_pg(pgid)
        lagging, silent = [o for o in acting if o != primary]
        head = ppg.pglog.head
        older = (head[0], head[1] - 1)
        infos = {lagging: {"last_update": older, "log_tail": ZERO_EV},
                 silent: {"unknown": True, "unreachable": True}}
        with ppg.lock:
            assert ppg._ec_choose_and_rewind(infos) is None
        assert ppg.pglog.head == head, "the primary was rewound"
        infos[silent] = {"last_update": head, "log_tail": ZERO_EV}
        with ppg.lock:
            assert ppg._ec_choose_and_rewind(infos) == head
        assert _wait_read(io, "obj") == v2

    def test_open_gather_is_pending_not_divergent(self, cluster):
        """A round that runs while a write's gather is open (the
        re-peer of an active pg) finds its entry on the shards the
        sub-op has reached so far, here the primary's alone: that is
        a PENDING write, and rewinding it under the gather lost the
        acked write and minted the next one under the same version.
        With the gather gone the same heads are divergent."""
        rados, io = _ec_setup(cluster)
        v1 = b"v1-acked-by-all" * 300
        io.write_full("obj", v1)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        ppg = cluster.osds[primary].get_pg(pgid)
        settled = ppg.pglog.head
        _partial_ec_write(cluster, io, "obj", b"v2-in-flight!!!" * 300,
                          to_shards=[acting.index(primary)])
        pending = ppg.pglog.head
        assert pending > settled
        infos = {o: {"last_update": settled, "log_tail": ZERO_EV}
                 for o in acting if o != primary}
        reqid = ("client.test", 1)
        with ppg.lock:
            ppg._inflight[reqid] = {"waiting": {1, 2}, "version": pending}
            try:
                assert ppg._ec_choose_and_rewind(infos) == settled
                assert ppg.pglog.head == pending, "rewound under its gather"
                # a peer the sub-op has reached votes the same way
                ahead = dict(infos)
                ahead[next(iter(ahead))] = {"last_update": pending,
                                            "log_tail": ZERO_EV}
                assert ppg._ec_choose_and_rewind(ahead) == settled
            finally:
                del ppg._inflight[reqid]
            assert ppg._ec_choose_and_rewind(infos) == settled
            assert ppg.pglog.head == settled
        assert _wait_read(io, "obj") == v1

    def test_rewind_restores_stash_content(self, cluster):
        """Unit-ish: rewind_to restores the pre-write shard bytes and
        version index from the stash."""
        rados, io = _ec_setup(cluster)
        v1 = b"A" * 5000
        v2 = b"B" * 5000
        io.write_full("obj3", v1)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj3")
        up, acting = m.pg_to_up_acting_osds(pgid)
        shard = 1
        osd_id = acting[shard]
        pg = cluster.osds[osd_id].get_pg(pgid)
        before_ev = pg.pglog.objects["obj3"]
        before_bytes = cluster.osds[osd_id].store.read(
            pg.cid, shard_oid("obj3", shard))
        _partial_ec_write(cluster, io, "obj3", v2, to_shards=[shard])
        assert pg.pglog.objects["obj3"] > before_ev
        pg.rewind_to(before_ev)
        assert pg.pglog.objects["obj3"] == before_ev
        assert cluster.osds[osd_id].store.read(
            pg.cid, shard_oid("obj3", shard)) == before_bytes

    def test_duplicate_client_op_not_reexecuted(self, cluster):
        """A client retry (same src+tid) must re-reply, not re-execute
        — double execution mints a second version and races rewinds."""
        rados, io = _ec_setup(cluster)
        io.write_full("dup", b"once")
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "dup")
        up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        pg = cluster.osds[primary].get_pg(pgid)
        v_before = pg.pglog.objects["dup"]

        from ceph_tpu.osd.messages import MOSDOp
        replies = []

        class FakeConn:
            peer_name = "client.dup"
            peer_addr = None

        # reply_to_client goes through the messenger; intercept instead
        orig = pg.osd.reply_to_client
        pg.osd.reply_to_client = lambda conn, msg: replies.append(msg)
        try:
            op = MOSDOp(tid=9999, pgid=str(pgid), oid="dup",
                        ops=[("writefull", b"twice")], epoch=m.epoch,
                        snapc=None, snapid=None)
            op.src = "client.dup"
            pg.do_op(FakeConn(), op)
            dup = MOSDOp(tid=9999, pgid=str(pgid), oid="dup",
                         ops=[("writefull", b"twice")], epoch=m.epoch,
                         snapc=None, snapid=None)
            dup.src = "client.dup"
            deadline = time.time() + 10
            while len(replies) < 1 and time.time() < deadline:
                time.sleep(0.05)
            pg.do_op(FakeConn(), dup)       # retry after completion
            deadline = time.time() + 10
            while len(replies) < 2 and time.time() < deadline:
                time.sleep(0.05)
        finally:
            pg.osd.reply_to_client = orig
        assert len(replies) == 2
        assert replies[0].version == replies[1].version
        # exactly ONE new version was minted
        assert pg.pglog.objects["dup"][1] == v_before[1] + 1

    def test_stashes_trimmed_after_full_ack(self, cluster):
        """Rollback stashes are GC'd once later fully-acked writes
        carry roll_forward_to past them (ECSubWrite trim semantics)."""
        rados, io = _ec_setup(cluster)
        for i in range(4):
            io.write_full("obj4", bytes([i]) * 3000)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "obj4")
        up, acting = m.pg_to_up_acting_osds(pgid)
        deadline = time.time() + 20
        while time.time() < deadline:
            stashes = [n for o in acting if o >= 0
                       for n in cluster.osds[o].store.collection_list(
                           f"pg_{pgid}") if "obj4" in n and "@" in n]
            # the newest write may still be untrimmed; all older
            # generations must be gone (<= 1 stash per shard)
            if len(stashes) <= len([o for o in acting if o >= 0]):
                break
            time.sleep(0.2)
        assert len(stashes) <= len([o for o in acting if o >= 0]), stashes




class TestAuthorityProof:
    """The pg_temp race class, CONSTRUCTED (not lucked into): a
    pg_temp cut elects a primary whose log lags an acked write.  The
    GetLog authority proof must block serving until the auth log is
    merged, and a client retry of the acked write must RE-REPLY (from
    the reqid-carrying merged log entry), never re-execute — the
    deterministic re-arming of test_duplicate_client_op_not_reexecuted.
    """

    @pytest.fixture()
    def quiet_cluster(self):
        # long heartbeat: the heartbeat-driven pg_temp reconcile must
        # not release our injected pin mid-assertion
        from ceph_tpu.utils.config import Config
        c = MiniCluster(num_mons=1, num_osds=3,
                        conf=Config({"osd_heartbeat_interval": 30.0,
                                     "osd_heartbeat_grace": 120.0})
                        ).start()
        yield c
        c.stop()

    def test_pg_temp_cut_lagging_primary_blocked_until_merge(
            self, quiet_cluster):
        from ceph_tpu.osd.messages import MOSDOp
        from ceph_tpu.store.objectstore import Transaction as Txn
        cluster = quiet_cluster
        rados = cluster.client()
        rados.create_pool("authp", pg_num=4, size=3, min_size=2)
        io = rados.open_ioctx("authp")
        _settle(io)
        io.write_full("dup", b"v1")
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "dup")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary = acting[0]
        ppg = cluster.osds[primary].get_pg(pgid)
        replies = []

        class FakeConn:
            peer_name = "client.race"
            peer_addr = None

        def crafted_op(epoch):
            op = MOSDOp(tid=4242, pgid=str(pgid), oid="dup",
                        ops=[("writefull", b"acked-v2")], epoch=epoch,
                        snapc=None, snapid=None)
            op.src = "client.race"
            return op

        orig = cluster.osds[primary].reply_to_client
        cluster.osds[primary].reply_to_client = \
            lambda conn, msg: replies.append(msg)
        try:
            ppg.do_op(FakeConn(), crafted_op(m.epoch))
            end = time.time() + 15
            while not replies and time.time() < end:
                time.sleep(0.05)
        finally:
            cluster.osds[primary].reply_to_client = orig
        assert replies and replies[0].result == 0
        acked_ver = tuple(replies[0].version)
        # construct the LAGGING copy: one replica loses the acked
        # write (log entry + bytes back to v1) — exactly the copy the
        # old max(last_update) election could have let serve
        lag = acting[1]
        lpg = cluster.osds[lag].get_pg(pgid)
        with lpg.lock:
            prior = None
            for e in lpg.pglog.entries:
                if e["oid"] == "dup" and tuple(e["ev"]) == acked_ver:
                    prior = e.get("prior")
            assert prior is not None, "acked entry never reached lag"
            prior = tuple(prior)
            lpg.pglog.entries = [
                e for e in lpg.pglog.entries
                if not (e["oid"] == "dup"
                        and tuple(e["ev"]) == acked_ver)]
            lpg.pglog.objects["dup"] = prior
            from ceph_tpu.osd.pg import VER_KEY
            cluster.osds[lag].store.apply_transaction(
                Txn().truncate(lpg.cid, "dup", 0)
                .write(lpg.cid, "dup", 0, b"v1")
                .setattr(lpg.cid, "dup", VER_KEY,
                         repr(prior).encode()))
        assert not lpg.pglog.contains(acked_ver)
        # THE pg_temp cut: pin the lagging copy as primary
        cluster.osds[primary].monc.send_pg_temp(
            primary, {str(pgid): [lag, acting[2], primary]})
        end = time.time() + 30
        while time.time() < end:
            lm = cluster.osds[lag].osdmap
            _u, a = lm.pg_to_up_acting_osds(pgid)
            if a and a[0] == lag:
                break
            cluster.tick(0.2)
            time.sleep(0.05)
        assert cluster.osds[lag].get_pg(pgid).is_primary
        # retry the acked write against the new (lagging) primary: it
        # answers EAGAIN while the authority proof runs (inactive
        # until the auth log is merged), then RE-REPLIES the original
        # version — never a re-execution
        lreplies = []
        lorig = cluster.osds[lag].reply_to_client
        cluster.osds[lag].reply_to_client = \
            lambda conn, msg: lreplies.append(msg)
        try:
            end = time.time() + 45
            final = None
            while time.time() < end:
                n0 = len(lreplies)
                lpg.do_op(FakeConn(),
                          crafted_op(cluster.osds[lag].osdmap.epoch))
                while len(lreplies) == n0 and time.time() < end:
                    time.sleep(0.02)
                if lreplies[n0:] and lreplies[n0].result == 0:
                    final = lreplies[n0]
                    break
                time.sleep(0.2)
        finally:
            cluster.osds[lag].reply_to_client = lorig
        assert final is not None, "lagging primary never served"
        # the authority proof ran: the lag merged the auth log
        perf = cluster.osds[lag]._perf_dump()["osd"]
        assert perf["peering_auth_catchups"] >= 1
        assert perf["peering_getlog_merges"] >= 1
        # dedup across the primary change: same version, no re-mint
        assert tuple(final.version) == acked_ver
        with lpg.lock:
            assert tuple(lpg.pglog.objects["dup"]) == acked_ver
        # and the acked payload survived the cut
        assert bytes(io.read("dup")) == b"acked-v2"


class TestReplicatedDivergentRewind:
    """The replicated stale-primary drill (deterministic): a primary
    holds a divergent never-acked suffix (the state a partition
    leaves), the surviving majority serves a newer interval, and the
    stale copy reconciles through rewind_divergent_log — counter-
    asserted, recovery proportional to the divergence, every acked
    write ledger-verified bit-exact."""

    def test_stale_primary_rewinds_and_ledger_stays_clean(
            self, cluster):
        from ceph_tpu.client.ledger import DurabilityLedger
        from ceph_tpu.store.objectstore import Transaction as Txn
        rados = cluster.client()
        rados.create_pool("rewindp", pg_num=4, size=3, min_size=2)
        io = rados.open_ioctx("rewindp")
        _settle(io)
        ledger = DurabilityLedger()
        filler = {f"fill{i:02d}": bytes([i]) * 32768 for i in range(12)}
        for oid, body in filler.items():
            ledger.write(io, oid, body)
        v1 = b"acked-and-safe" * 100
        ledger.write(io, "vic", v1)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "vic")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        stale = acting[0]
        apg = cluster.osds[stale].get_pg(pgid)
        # divergent, never-acked suffix on the primary only — the
        # exact state a partition mid-fan-out leaves behind
        v2div = b"divergent-lost!" * 100
        with apg.lock:
            apg.version += 1
            dev = (apg.interval_epoch, apg.version)
            prior = tuple(apg.pglog.objects["vic"])
            txn, kind, _out = apg._build_txn("vic",
                                             [("writefull", v2div)],
                                             dev)
            apg._log_and_apply(txn, {
                "ev": dev, "oid": "vic", "op": kind, "prior": prior,
                "rollback": None, "shard": None})
        assert bytes(cluster.osds[stale].store.read(
            apg.cid, "vic")) == v2div
        # the majority serves a NEWER interval while the stale copy
        # is out (les advances past the divergent branch)
        cluster.mark_osd_out(stale)
        end = time.time() + 60
        while time.time() < end:
            m2 = cluster.leader().osdmon.osdmap
            _u2, a2 = m2.pg_to_up_acting_osds(pgid)
            if a2 and stale not in a2:
                npg = cluster.osds[a2[0]].get_pg(pgid)
                if npg is not None and npg.active:
                    break
            cluster.tick(0.3)
            time.sleep(0.05)
        v3 = b"served-after-partition" * 50
        ledger.write(io, "vic2", v3)
        b_rw0 = cluster.osds[stale]._perf_dump()["osd"][
            "peering_divergent_rewinds"]
        rec0 = sum(o._perf_dump()["osd"]["recovery_bytes"]
                   for o in cluster.osds.values())
        # partition heals: the stale copy re-enters and re-claims
        # primacy — it must rewind through the shared core, NOT
        # out-version the acked history
        rados.mon_command({"prefix": "osd in", "id": stale})
        cluster.wait_for_clean(timeout=90)
        end = time.time() + 60
        while time.time() < end:
            perf = cluster.osds[stale]._perf_dump()["osd"]
            if perf["peering_divergent_rewinds"] > b_rw0:
                break
            cluster.tick(0.3)
            time.sleep(0.05)
        perf = cluster.osds[stale]._perf_dump()["osd"]
        assert perf["peering_divergent_rewinds"] > b_rw0, \
            "reconciliation never went through rewind_divergent_log"
        assert perf["peering_divergent_entries"] >= 1
        # acked state bit-exact, divergent write gone
        assert bytes(io.read("vic")) == v1
        assert bytes(io.read("vic2")) == v3
        ledger.verify(io)
        # recovery proportional to DIVERGENCE, not pg size: the
        # filler corpus (12 x 32 KiB x 3 replicas ≈ 1.2 MiB) must not
        # have been re-pushed object-map style
        rec1 = sum(o._perf_dump()["osd"]["recovery_bytes"]
                   for o in cluster.osds.values())
        divergence_bytes = len(v1) + len(v3)
        assert rec1 - rec0 <= 6 * divergence_bytes + 65536, \
            f"object-map-shaped recovery: {rec1 - rec0} bytes"


class TestReplicatedTriangle:
    def test_third_replica_auth_converges_in_one_round(self, cluster):
        """The auth copy lives on a NON-primary replica while BOTH the
        primary and the other replica are stale: one peering round
        must heal everyone (the primary pulls, and delegates a push to
        the other stale peer — no waiting for a later re-peer)."""
        from ceph_tpu.client import RadosError
        rados = cluster.client()
        rados.create_pool("tri", pg_num=4, size=3, min_size=2)
        io = rados.open_ioctx("tri")
        end = time.time() + 60
        while True:
            try:
                io.write_full("settle", b"s")
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)
        io.write_full("tri-obj", b"authoritative-content")
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "tri-obj")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary, rep1, rep2 = acting
        # regress the object on the PRIMARY and one replica: holder of
        # the auth copy becomes the OTHER replica (rep1)
        for osd_id in (primary, rep2):
            osd = cluster.osds[osd_id]
            pg = osd.pgs[pgid]
            with pg.lock:
                osd.store.apply_transaction(
                    Transaction().remove(f"pg_{pgid}", "tri-obj"))
                pg.pglog.objects.pop("tri-obj", None)
                pg.pglog.entries = [
                    e for e in pg.pglog.entries
                    if e["oid"] != "tri-obj"]
        # force a peering round on the primary
        ppg = cluster.osds[primary].pgs[pgid]
        ppg.start_peering()
        end = time.time() + 30
        while True:
            healed = all(
                cluster.osds[o].store.exists(f"pg_{pgid}", "tri-obj")
                and cluster.osds[o].store.read(
                    f"pg_{pgid}", "tri-obj") == b"authoritative-content"
                for o in acting)
            if healed:
                break
            if time.time() > end:
                stat = {o: cluster.osds[o].store.exists(
                    f"pg_{pgid}", "tri-obj") for o in acting}
                raise AssertionError(
                    f"triangle did not converge in one round: {stat}")
            cluster.tick(0.3)
            time.sleep(0.05)
        assert io.read("tri-obj") == b"authoritative-content"


class TestLogBoundsPeering:
    """Peering exchanges LOG BOUNDS, and recovery follows the log's
    divergence: neither grows with the number of objects in the PG.
    Held by counting messages, bytes and pushes, never by timing."""

    @staticmethod
    def _one_pg_pool(cluster, name):
        rados = cluster.client()
        rados.create_pool(name, pg_num=1, size=3, min_size=2)
        io = rados.open_ioctx(name)
        _settle(io)
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, "settle")
        _up, acting = m.pg_to_up_acting_osds(pgid)
        return io, pgid, [o for o in acting if o >= 0]

    @staticmethod
    def _pushes(cluster):
        return sum(o._perf_dump()["osd"]["recovery_pushes"]
                   for o in cluster.osds.values())

    def test_clean_repeer_costs_the_same_at_ten_times_the_objects(
            self, cluster, monkeypatch):
        """A clean re-peer of one PG at 8 and at 80 objects: the same
        messages (a query and an info per peer, an activate per peer),
        the same bytes within the width of a version number, and not
        one recovery push.  An O(objects) term in the info exchange,
        the election or the delta would show in all three."""
        io, pgid, acting = self._one_pg_pool(cluster, "peerflat")
        osd = cluster.osds[acting[0]]
        pg = osd.get_pg(pgid)
        sent: list[tuple] = []
        for o in cluster.osds.values():
            def spy(msg, peer_name, peer_addr,
                    _send=o.msgr.send_message):
                if getattr(msg, "pgid", None) == str(pgid):
                    sent.append((type(msg).__name__,
                                 getattr(msg, "op", ""),
                                 len(msg.encode())))
                _send(msg, peer_name, peer_addr)
            monkeypatch.setattr(o.msgr, "send_message", spy)

        def repeer():
            """(messages by kind, bytes, pushes) of one clean round."""
            del sent[:]
            pushes0 = self._pushes(cluster)
            with pg.lock:
                pg.active = False
            osd.queue_peering(pgid)
            end = time.time() + 30
            while not pg.active and time.time() < end:
                time.sleep(0.01)
            assert pg.active
            with pg.lock:       # the round's last sends are made under it
                round_ = list(sent)
            return (sorted(kind for *kind, _n in round_),
                    sum(n for *_kind, n in round_),
                    self._pushes(cluster) - pushes0)

        written = 0
        rounds = []
        for count in (8, 80):
            while written < count:
                io.write_full(f"o{written:04d}", b"x" * 64)
                written += 1
            rounds.append(repeer())
        (kinds, nbytes, pushes), (kinds10x, nbytes10x, pushes10x) = rounds
        peers = len(acting) - 1
        assert kinds == kinds10x == sorted(
            [["MPGInfo", op] for op in ("query", "info", "activate")]
            * peers)
        assert pushes == pushes10x == 0
        # a version number ten times as large may take a byte more in
        # each of the bounds a message carries
        assert abs(nbytes10x - nbytes) <= 8 * len(kinds)

    def test_recovery_bytes_follow_the_divergence(self, cluster):
        """K objects vanish from one replica of a PG that holds many
        more: re-peering heals exactly those, and the bytes recovery
        pushes stay within 3 x K payloads, whatever the PG holds."""
        io, pgid, acting = self._one_pg_pool(cluster, "peerdiv")
        for i in range(40):
            io.write_full(f"fill{i:03d}", bytes([i]) * 4096)
        K, payload = 6, 1 << 15
        bodies = {f"div{i:03d}": bytes([0x40 + i]) * payload
                  for i in range(K)}
        for oid, body in bodies.items():
            io.write_full(oid, body)
        posd, vosd = (cluster.osds[o] for o in acting[:2])
        vpg = vosd.get_pg(pgid)
        with vpg.lock:
            for oid in bodies:
                vosd.store.apply_transaction(
                    Transaction().remove(vpg.cid, oid))
                vpg.pglog.objects.pop(oid, None)
                vpg.pglog.entries = [e for e in vpg.pglog.entries
                                     if e["oid"] != oid]
        before = posd._perf_dump()["osd"]["recovery_bytes"]
        posd.get_pg(pgid).start_peering()
        end = time.time() + 60
        healed = False
        while not healed and time.time() < end:
            healed = all(
                vosd.store.exists(vpg.cid, oid)
                and bytes(vosd.store.read(vpg.cid, oid)) == body
                for oid, body in bodies.items())
            time.sleep(0.1)
        assert healed
        pushed = posd._perf_dump()["osd"]["recovery_bytes"] - before
        assert K * payload <= pushed <= 3 * K * payload


class TestCatchUpMarker:
    def test_an_interval_change_voids_a_catch_up_under_way(self, cluster):
        """A primary that was polling for its catch-up pulls when the
        map took it out of the acting set and gave it back (a reborn
        OSD marked down once more by late failure reports) stops
        polling: the round's marker goes with its interval, or
        `wait_for_clean` waits for it for ever."""
        io, pgid, acting = TestLogBoundsPeering._one_pg_pool(cluster,
                                                             "flap")
        pg = cluster.osds[acting[0]].get_pg(pgid)
        with pg.lock:
            pg._catchup_pending = {"settle": (99, 99)}
        assert any("catch-up pending" in line
                   for line in cluster.unclean_pgs())
        cluster.mark_osd_down(acting[0])
        cluster.wait_for_clean(60)
        assert io.read("settle")
