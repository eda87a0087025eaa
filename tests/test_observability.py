"""Observability: perf counters move during I/O, op tracking, admin
socket (in-process + unix domain), slow-op surfacing.

The VERDICT item: PerfCounters existed but nothing instantiated them —
these tests pin that the messenger/OSD/mon sets are WIRED.
"""

import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.utils.admin_socket import admin_command
from ceph_tpu.utils.clock import ManualClock
from ceph_tpu.utils.optracker import OpTracker
from ceph_tpu.vstart import MiniCluster


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    sock_dir = str(tmp_path_factory.mktemp("asok"))
    from ceph_tpu.utils.config import Config
    conf = Config({
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
        "mon_osd_down_out_interval": 5.0,
        "admin_socket_dir": sock_dir,
    })
    c = MiniCluster(num_mons=1, num_osds=3, conf=conf).start()
    c.sock_dir = sock_dir
    yield c
    c.stop()


def _settle(ctx, oid="warm", body=b"x"):
    """Write until the new pool's PGs are active."""
    end = time.time() + 20
    while True:
        try:
            ctx.write_full(oid, body)
            return
        except RadosError:
            if time.time() > end:
                raise
            time.sleep(0.3)


@pytest.fixture(scope="module")
def io(cluster):
    rados = cluster.client()
    rados.create_pool("obs", pg_num=4)
    ctx = rados.open_ioctx("obs")
    _settle(ctx)
    return ctx


class TestCounterSchema:
    """The COMPLETE perf-counter schema per subsystem, asserted
    name-by-name: tools/counter_audit.py (tier-1) requires every
    counter declared or incremented anywhere in ceph_tpu/ to appear
    here — a counter cannot ship undocumented/untested."""

    OSD = {"op", "op_r", "op_w", "op_in_bytes", "op_out_bytes",
           "subop_w", "op_latency",
           "peering_auth_catchups", "peering_getlog_merges",
           "peering_divergent_rewinds", "peering_divergent_entries",
           "recovery_pushes", "recovery_bytes", "backfill_resumes",
           # what a repair did: backfill rounds and their objects, EC
           # rebuilds by where the lost shard came from
           # (tests/test_backfill_under_reads.py holds their sums)
           "backfill_rounds", "backfill_objects", "rebuild_cache_served",
           "rebuild_local", "rebuild_full",
           # rebuilds whose first plan's gather did not serve, and the
           # chunks the first plans named (the same file holds them)
           "rebuild_widened", "rebuild_planned_chunks",
           # the PG log as keys: keys and bytes handed to
           # transactions, logs that had to be written whole
           "pglog_keys_written", "pglog_bytes_written",
           "pglog_full_rewrites",
           # serve-during-repair: ops parked on a missing object's
           # recovery pull, their resumes, and front-of-queue pull
           # promotions (blocked == unblocked at quiesce)
           "recovery_blocked_ops", "recovery_unblocked_ops",
           "recovery_prio_promotions",
           # EC reads that needed the widened step after the planned
           "ec_read_widened", "ec_appends", "ec_append_fallbacks",
           # cache tiering (the reference's names): promotes, flushes
           # and evicts started, dirty/clean transitions, failures,
           # agent passes and what they started, ops a full tier held
           # back; the last two have to stay 0
           "tier_promote", "tier_flush", "tier_evict", "tier_dirty",
           "tier_clean", "tier_try_flush_fail", "tier_flush_fail",
           "tier_promote_fail", "agent_wake", "agent_flush",
           "agent_evict", "tier_full_waits", "tier_evict_dirty",
           "tier_full_admit",
           # PG mappings answered by the map's placement table, and
           # those CRUSH worked out (tests/test_osdmap_placement_cache.py)
           "placement_hit", "placement_miss"}
    MSGR = {"msg_send", "msg_recv", "bytes_send", "bytes_recv",
            "reconnects", "auth_failures", "auth_ticket_accepts",
            "auth_secret_accepts",
            # event-loop plane (shared schema across both stacks):
            # worker-model gauge, live connection gauge, cross-thread
            # loop handoffs, gather-writes resumed by EPOLLOUT, and
            # accepted-socket handshakes
            "event_workers", "open_connections", "event_wakeups",
            "partial_write_resumes", "accepts"}
    # the client's objecter: sends, resends by cause (the timer that
    # follows the target's reply latency, a session reset, a map
    # change, an EAGAIN) and connections marked down as silent
    OBJECTER = {"op_send", "op_resend", "op_resend_timer",
                "op_resend_reset", "op_resend_map", "op_resend_eagain",
                "conn_kick", "placement_hit", "placement_miss"}
    MON = {"elections_won", "elections_lost", "commands"}
    PAXOS = {"collect", "begin", "commit", "lease"}
    # multisite replication agent: rounds attempted, per-bucket/round
    # failures, in-round retries after a backoff expired, buckets
    # benched behind a per-bucket backoff, applied copies/deletes, and
    # total seconds of scheduled backoff (backoff-not-wedge evidence)
    RGW_SYNC = {"sync_rounds", "sync_errors", "sync_retries",
                "sync_quarantines", "sync_objects_copied",
                "sync_deletes_applied", "sync_backoff_secs"}

    def test_osd_schema_complete(self, cluster):
        osd = next(iter(cluster.osds.values()))
        assert set(osd.perf._schema) == self.OSD
        assert set(osd.msgr.perf._schema) == self.MSGR

    def test_pglog_counters_move_with_writes(self, cluster, io):
        """A write hands its log keys to its transaction on every
        copy: a few keys and a few hundred bytes each, and on a
        healthy cluster no log is ever written whole."""
        def total(key):
            return sum(o.perf.value(key) for o in cluster.osds.values())
        keys0, bytes0 = (total("pglog_keys_written"),
                         total("pglog_bytes_written"))
        for i in range(4):
            io.write_full(f"pglog-ctr-{i}", b"x" * 64)
        copies = 4 * len(cluster.osds)
        assert copies * 2 <= total("pglog_keys_written") - keys0 \
            <= copies * 4
        assert 0 < total("pglog_bytes_written") - bytes0 < copies * 512
        assert total("pglog_full_rewrites") == 0

    def test_client_schema_complete(self, cluster, io):
        """The client's `perf dump`: the objecter block's counters plus
        the ops in flight and each target's resend timeout, the
        messenger's set, and which codec walk serves the process;
        healthy I/O moves sends and nothing else."""
        rados = io.rados
        assert set(rados.objecter.perf._schema) == self.OBJECTER
        io.read("warm")
        dump = rados.perf_dump()
        assert set(dump) == {"objecter", "msgr", "denc"}
        assert set(dump["denc"]) == {"native_calls", "python_calls",
                                     "value_callbacks"}
        assert set(dump["msgr"]) == self.MSGR
        obj = dump["objecter"]
        assert set(obj) == self.OBJECTER | {"ops_in_flight",
                                            "resend_timeout"}
        assert obj["op_send"] >= 2 and obj["conn_kick"] == 0
        assert obj["ops_in_flight"] == 0
        assert obj["resend_timeout"]
        for target, secs in obj["resend_timeout"].items():
            kind, _, rw = target.partition("/")
            assert kind.startswith("osd.") and rw in ("read", "write")
            assert secs >= float(cluster.conf.objecter_backoff_base)

    def test_mon_schema_complete(self, cluster):
        mon = cluster.leader()
        assert set(mon.perf._schema) == self.MON
        assert set(mon.paxos.perf._schema) == self.PAXOS

    def test_rgw_sync_schema_complete(self, cluster):
        """The sync agent's `perf dump rgw_sync` block: schema pinned,
        and one healthy self-pointed round moves the round counter
        without manufacturing errors/backoff."""
        from ceph_tpu.rgw.sync import RGWSyncAgent
        gw = cluster.start_rgw()
        try:
            agent = RGWSyncAgent(gw, f"http://127.0.0.1:{gw.port}")
            assert set(agent.perf._schema) == self.RGW_SYNC
            agent.sync_once()       # self-sync: trivially healthy
            dump = agent.perf_dump()["rgw_sync"]
            assert set(dump) == self.RGW_SYNC | {"quarantined_buckets"}
            assert dump["sync_rounds"] == 1
            assert dump["sync_errors"] == 0
            assert dump["sync_backoff_secs"] == 0
            assert dump["quarantined_buckets"] == []
        finally:
            gw.shutdown()
            cluster.rgws.remove(gw)

    def test_counter_audit_clean(self):
        """Tier-1 gate: a counter incremented in ceph_tpu/ but absent
        from the sets above fails here until it is added."""
        from ceph_tpu.tools import counter_audit
        violations = counter_audit.audit()
        assert violations == [], "\n".join(violations)


class TestPerfCounters:
    def test_osd_counters_move_during_io(self, cluster, io):
        before = {o.whoami: o.perf.value("op") for o in
                  cluster.osds.values()}
        for i in range(5):
            io.write_full(f"c{i}", b"data" * 50)
            io.read(f"c{i}")
        after = {o.whoami: o.perf.value("op") for o in
                 cluster.osds.values()}
        assert sum(after.values()) >= sum(before.values()) + 10
        osd = max(cluster.osds.values(),
                  key=lambda o: o.perf.value("op_w"))
        assert osd.perf.value("op_w") >= 1
        assert osd.perf.value("op_in_bytes") >= 200
        assert osd.perf.avg("op_latency") >= 0.0

    def test_messenger_counters(self, cluster, io):
        osd = next(iter(cluster.osds.values()))
        dump = osd.msgr.perf.dump()
        assert dump["msg_send"] > 0
        assert dump["msg_recv"] > 0
        assert dump["bytes_send"] > 0

    def test_mon_paxos_counters(self, cluster, io):
        mon = cluster.leader()
        dump = mon.perf_collection.dump()
        assert dump["paxos"]["commit"] > 0
        assert dump["paxos"]["lease"] >= 0
        assert dump["mon"]["elections_won"] >= 1
        assert dump["mon"]["commands"] >= 1

    def test_perf_dump_includes_ec_codecs(self, cluster, io):
        cluster.client().create_ec_pool(
            "obsec", "k2m1", {"plugin": "tpu", "k": 2, "m": 1})
        ioe = cluster.client().open_ioctx("obsec")
        _settle(ioe, "e", b"ec" * 3000)
        dumps = [o.asok.execute("perf dump") for o in
                 cluster.osds.values()]
        assert any(d.get("ec_codecs") for d in dumps)

    def test_ec_pipeline_counters(self, cluster, io):
        """The shared EC dispatch pipeline surfaces its counters in
        perf dump: dispatch count, mean batch size, queue depth."""
        rados = cluster.client()
        rados.create_ec_pool(
            "obsecp", "k2m1p", {"plugin": "tpu", "k": 2, "m": 1})
        ioe = rados.open_ioctx("obsecp")
        _settle(ioe, "p0", b"pipe" * 2000)
        for i in range(1, 6):
            ioe.write_full(f"p{i}", bytes([i]) * 6000)
        dump = next(iter(cluster.osds.values())).asok.execute(
            "perf dump")
        stats = dump["ec_pipeline"]
        # the pipeline is process-wide, so every OSD reports the same
        # counters — the EC writes above must have moved them
        assert stats["dispatches"] >= 1
        assert stats["ops"] >= 6
        assert stats["stripes"] >= stats["dispatches"]
        assert stats["mean_batch_size"] >= 1.0
        assert stats["queue_depth"] >= 0
        assert stats["max_queue_depth"] >= 1
        for key in ("dev_dispatches", "host_dispatches",
                    "coalesce_waits", "device_errors",
                    "drained_to_host", "inflight", "depth",
                    # multichip surface: per-device lanes + placement
                    "active_devices", "devices", "quarantines",
                    "split_dispatches", "redrained",
                    "qos_scrub_yields", "scrub_weight",
                    "device_shards",
                    # the bytes-weighted QoS unit and its picks
                    "qos_cost_unit", "qos_cost_picks",
                    # always 0: benchmark/cluster.py reads the key
                    "mesh_degrades"):
            assert key in stats, key
        # transfer-plane bytes, measured placement, and the counters
        # that show when the device path was bypassed: a device set
        # that found no device, a route callback that raised, a
        # producer that self-served after RESULT_TIMEOUT, warm-ups
        # that failed or are still compiling
        for key in ("bytes_h2d", "bytes_d2h", "cost_placements",
                    "cost_diverged", "devset_errors", "route_errors",
                    "result_timeouts", "warm_failures",
                    "last_warm_error", "warmups_inflight"):
            assert key in stats, key
        # the HBM stripe cache's counters ride the same block
        for name in ("hit", "miss", "insert", "evict", "invalidate",
                     "lane_drops", "append_throughs",
                     "read_bytes_served", "bytes_d2h"):
            assert f"cache_{name}" in stats, name
        # per-device lane counters carry the full schema once the
        # device set is built (host-only runs may leave it lazy)
        for dev in stats["devices"].values():
            for key in ("device", "dispatches", "stripes", "bytes",
                        "errors", "inflight", "quarantined"):
                assert key in dev, key

    # what one op may materialize on the host (utils/copyaudit.py
    # sites).  A write: `ec.stage` (the payload rope into the encode
    # staging buffer), `ec.shard_layout` (stripe-major to shard-major),
    # and one spare for a journaled store's WAL flatten.  A read: the
    # one chunk a degraded read rebuilds (`ec.decode_rebuild`); an
    # intact read copies nothing.
    COPIES_PER_WRITE = 3.0
    COPIES_PER_READ = 1.0

    def test_data_path_copy_counters(self, cluster, io):
        """The zero-copy plane's audit block: perf dump reports where
        payload bytes still materialize, amortized per write AND per
        read op (the PR 9 read-side floor), and EC writes and reads,
        intact and degraded, stay inside their copy budgets."""
        from ceph_tpu.ops import hbm_cache
        from ceph_tpu.utils import copyaudit, faults
        io.write_full("dp0", b"copyaudit" * 400)
        io.read("dp0")
        dump = next(iter(cluster.osds.values())).asok.execute(
            "perf dump")
        dp = dump["data_path"]
        for key in ("host_copies", "ec_host_copy_bytes", "sites",
                    "host_copies_per_write",
                    "host_copy_bytes_per_write",
                    "reads", "read_copies", "read_copy_bytes",
                    "host_copies_per_read",
                    "host_copy_bytes_per_read"):
            assert key in dp, key
        assert dp["host_copies_per_write"] >= 0
        assert dp["reads"] >= 1
        # replicated/intact reads are view-served: no read-site copies
        assert dp["host_copies_per_read"] >= 0
        rados = cluster.client()
        rados.create_ec_pool("obsdp", "dpk2m1",
                             {"plugin": "tpu", "k": 2, "m": 1})
        ec = rados.open_ioctx("obsdp")
        _settle(ec)
        # past the out-of-band threshold, not a multiple of the stripe
        body = bytes(range(256)) * 49
        n = 8

        def moved(before, after, key):
            return after[key] - before[key]

        s0 = copyaudit.snapshot()
        for i in range(n):
            ec.write_full(f"dp{i}", body)
        s1 = copyaudit.snapshot()
        for i in range(n):
            assert bytes(ec.read(f"dp{i}")) == body
        s2 = copyaudit.snapshot()
        faults.get().store_eio("osd.*", "dp0.s0")
        try:
            hbm_cache.get().clear()    # or the cache serves, no shard asked
            for _ in range(n):
                assert bytes(ec.read("dp0")) == body
        finally:
            faults.get().reset()
        s3 = copyaudit.snapshot()
        writes = moved(s0, s1, "writes")
        assert writes >= n
        staged = s1["sites"]["ec.stage"]["copies"] - \
            s0["sites"].get("ec.stage", {"copies": 0})["copies"]
        assert staged >= n                  # the counters really count
        assert moved(s0, s1, "host_copies") / writes <= \
            self.COPIES_PER_WRITE
        assert moved(s1, s2, "reads") >= n
        assert moved(s1, s2, "read_copies") == 0
        assert moved(s2, s3, "reads") >= n
        assert 1 <= moved(s2, s3, "read_copies") <= \
            self.COPIES_PER_READ * moved(s2, s3, "reads")

    def test_qos_block_schema(self, cluster, io):
        """Per-pool QoS surfaces in perf dump: the op-queue dmClock
        state (grants/misses/stalls per client) plus the EC pipeline's
        dispatch-lane half — and installing a pool class at runtime
        (injectargs, dynamic option) makes it appear."""
        osd = next(iter(cluster.osds.values()))
        dump = osd.asok.execute("perf dump")
        qos = dump["qos"]
        for key in ("enabled", "throttle_stalls", "clients",
                    "pipeline", "recovery"):
            assert key in qos, key
        # the @recovery class surfaces its own grants/stalls even when
        # unconfigured (operators tune osd_qos_recovery against it)
        for key in ("configured", "res_grants", "prop_grants",
                    "deadline_misses", "throttle_stalls"):
            assert key in qos["recovery"], key
        assert qos["recovery"]["configured"] == ""
        assert qos["enabled"] is False        # nothing configured yet
        for key in ("enabled", "throttle_stalls", "clients"):
            assert key in qos["pipeline"], key
        # dynamic per-pool conf: a runtime injectargs registers the
        # class and the next I/O is scheduled (and counted) under it
        osd.conf.injectargs("--osd-pool-qos-obs 100:2:0")
        try:
            io.write_full("qos0", b"q" * 512)
            io.read("qos0")
            dump = osd.asok.execute("perf dump")
            qos = dump["qos"]
            assert qos["enabled"] is True
            # every osd sharing the conf reconfigures on its next map/
            # observer tick; the one serving qos0's pg granted it
            grants = 0
            for o in cluster.osds.values():
                ent = o._qos.stats()["clients"].get("obs")
                if ent:
                    assert ent["spec"] == "100:2:0"
                    grants += ent["res_grants"] + ent["prop_grants"]
            assert grants >= 1
        finally:
            osd.conf.injectargs("--osd-pool-qos-obs ''")

    def test_peering_and_recovery_counters(self, cluster, io):
        """The log-authoritative peering plane surfaces in perf dump:
        authority catch-ups, GetLog merges, divergent rewinds (and
        their entry counts), recovery push/byte accounting, and
        backfill watermark resumes."""
        dump = next(iter(cluster.osds.values())).asok.execute(
            "perf dump")
        for key in ("peering_auth_catchups", "peering_getlog_merges",
                    "peering_divergent_rewinds",
                    "peering_divergent_entries", "recovery_pushes",
                    "recovery_bytes", "backfill_resumes"):
            assert key in dump["osd"], key
            assert dump["osd"][key] >= 0

    def test_journal_and_crash_counters(self, cluster, io, tmp_path):
        """The crash-consistency plane surfaces in perf dump: every
        daemon reports a `crash` block (state + installed rules) and a
        `journal` block (recovery counters; empty for non-journaled
        backends like this cluster's memstore)."""
        from ceph_tpu.utils import faults
        osd = next(iter(cluster.osds.values()))
        dump = osd.asok.execute("perf dump")
        assert dump["journal"] == {}        # memstore: no journal
        assert dump["crash"] == {
            "crashed": 0, "site": "", "crash_rules": 0,
            "sites": ["store.pre_apply", "store.post_apply",
                      "pglog.append"],
            "wal_torn_extent_repairs": 0,
            "fsync_reorder_windows": 0}
        # an installed (unfired) crash rule is visible cluster-wide
        rid = faults.get().crash("journal.*", 0.0, "osd.none")
        try:
            dump = osd.asok.execute("perf dump")
            assert dump["crash"]["crash_rules"] == 1
        finally:
            faults.get().clear(rid)
        # the MON tier reports its own crash block: the paxos crash
        # sites plus the torn-commit repair counters
        mdump = cluster.mons[0].asok.execute("perf dump")
        assert mdump["crash"]["crashed"] == 0
        assert mdump["crash"]["sites"] == [
            "paxos.pre_commit", "paxos.mid_commit",
            "paxos.post_accept_pre_ack"]
        assert mdump["crash"]["paxos_torn_commit_repairs"] == 0
        assert mdump["crash"]["fsync_reorder_windows"] == 0
        # the journal block's schema on a journaled backend — the
        # same dict JournalFileStore feeds perf dump (the chaos
        # kill-restart drill asserts it end-to-end via asok)
        from ceph_tpu.store import JournalFileStore, Transaction
        s = JournalFileStore(str(tmp_path / "fs"), commit_interval=3600)
        s.mkfs()
        s.mount()
        s.apply_transaction(
            Transaction().create_collection("c").write("c", "o", 0,
                                                       b"x"))
        s._checkpoint()
        stats = s.journal_stats()
        for key in ("journal_records_replayed",
                    "journal_torn_tail_discards",
                    "journal_bad_record_halts",
                    "journal_tail_bytes_discarded",
                    "snapshot_corrupt_fallbacks",
                    "journal_checkpoint_errors",
                    "journal_checkpoints",
                    "fsync_reorder_windows"):
            assert key in stats, key
        assert stats["journal_checkpoints"] == 1
        assert set(s.crash_sites()) >= {
            "journal.pre_fsync", "journal.post_fsync",
            "journal.mid_apply", "snapshot.mid_write",
            "snapshot.pre_rename"}
        s.umount()
        # the blockstore's WAL/extent counters + site names
        from ceph_tpu.store.blockstore import BlockStore
        bs = BlockStore(str(tmp_path / "bs"))
        bs.mkfs()
        bstats = bs.journal_stats()
        for key in ("wal_records_replayed", "wal_torn_extent_repairs",
                    "freelist_repairs", "fsync_reorder_windows",
                    "kv_calls", "commits", "onode_lookups", "onode_hits",
                    "onodes_committed", "runs_committed", "runs_per_onode",
                    "reads", "reads_whole_run", "read_whole_run_share"):
            assert key in bstats, key
        assert set(bs.crash_sites()) >= {
            "wal.pre_kv_commit", "wal.post_kv_commit",
            "wal.mid_apply", "wal.pre_trim", "alloc.mid_cow"}
        bs.umount()
        assert s.health_warning() is None
        s.umount()


class TestAdminSocket:
    def test_in_process_hooks(self, cluster, io):
        osd = next(iter(cluster.osds.values()))
        assert "perf dump" in osd.asok.execute("help")
        st = osd.asok.execute("status")
        assert st["whoami"] == osd.whoami
        hist = osd.asok.execute("dump_historic_ops")
        assert isinstance(hist["num_ops"], int)
        assert osd.asok.execute({"prefix": "nope"})["error"]

    def test_unix_socket_roundtrip(self, cluster, io):
        osd = next(iter(cluster.osds.values()))
        path = f"{cluster.sock_dir}/{osd.entity}.asok"
        out = admin_command(path, "perf dump")
        assert "osd" in out and out["osd"]["op"] >= 0
        out = admin_command(path, {"prefix": "config show"})
        assert out["osd_op_num_shards"] == 5

    def test_config_set_via_asok(self, cluster, io):
        osd = next(iter(cluster.osds.values()))
        osd.asok.execute({"prefix": "config set",
                          "key": "osd_scrub_sleep", "value": "0.5"})
        assert osd.conf.osd_scrub_sleep == 0.5
        osd.asok.execute({"prefix": "config set",
                          "key": "osd_scrub_sleep", "value": "0.0"})

    def test_mon_quorum_status(self, cluster, io):
        mon = cluster.leader()
        qs = mon.asok.execute("quorum_status")
        assert qs["leader"] == mon.entity


class TestOpTracking:
    def test_historic_ops_recorded(self, cluster, io):
        io.write_full("tracked", b"watch me")
        osd_dumps = [o.asok.execute("dump_historic_ops")
                     for o in cluster.osds.values()]
        all_ops = [op for d in osd_dumps for op in d["ops"]]
        assert any("tracked" in op["description"] for op in all_ops)
        done = [op for op in all_ops if "tracked" in op["description"]]
        events = [e["event"] for e in done[0]["events"]]
        assert events[0] == "initiated"
        assert "reached_pg" in events
        assert events[-1] == "done"

    def test_slow_op_detection(self):
        clock = ManualClock()
        warned = []

        class Log:
            def warn(self, fmt, *a):
                warned.append(fmt % a)

        trk = OpTracker(clock, complaint_age=5.0, logger=Log())
        op = trk.create("osd_op(test slow)")
        clock.advance(10.0)
        slow = trk.check_slow_ops()
        assert len(slow) == 1
        assert slow[0]["age"] >= 10.0
        assert warned and "test slow" in warned[0]
        # complained once only
        assert trk.check_slow_ops() == []
        op.finish()
        assert trk.dump_ops_in_flight()["num_ops"] == 0
        assert trk.dump_historic_ops()["num_ops"] == 1
