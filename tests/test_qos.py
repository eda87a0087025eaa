"""Per-pool QoS: dmClock tag math, the QoS op queue, the EC pipeline's
tenant picks, and the cluster-level noisy-neighbor drill (a reserved
pool's tail latency bounded while another tenant saturates the
cluster — and the SAME seed starving without QoS, so the mechanism is
provably load-bearing, not vacuous)."""

import threading
import time

import pytest

from ceph_tpu.utils.dmclock import (DmClockState, QosSpec, parse_spec,
                                    RES, PROP)


class TestSpecGrammar:
    def test_parse_full(self):
        s = parse_spec("100:2:500")
        assert (s.res, s.weight, s.lim) == (100.0, 2.0, 500.0)

    def test_parse_partial(self):
        assert parse_spec("50") == QosSpec(res=50.0)
        assert parse_spec("0:3") == QosSpec(res=0.0, weight=3.0)
        assert parse_spec("10::") == QosSpec(res=10.0)

    def test_parse_rejects_garbage(self):
        for bad in ("a:b:c", "1:2:3:4", "1:-2:0", "5:1:2"):
            with pytest.raises(ValueError):
                parse_spec(bad)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class TestDmClockState:
    def test_unconstrained_is_fifo(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        # no specs anywhere: oldest arrival wins, exactly FIFO
        got, phase, _ = st.pick({"a": 99.0, "b": 98.0}, now=clk.t)
        assert (got, phase) == ("b", RES)

    def test_reservation_beats_weight(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"gold": QosSpec(res=10.0, weight=1.0),
                      "noise": QosSpec(res=0.0, weight=100.0)})
        # both queued since t-1: gold's reservation tag is due, noise
        # has only a proportional claim — gold wins the slot
        got, phase, _ = st.pick({"gold": clk.t - 1.0,
                                 "noise": clk.t - 1.0}, now=clk.t)
        assert (got, phase) == ("gold", RES)

    def test_reservation_rate_is_bounded(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"gold": QosSpec(res=10.0, weight=1.0),
                      "noise": QosSpec(res=0.0, weight=1.0)})
        # serve 20 slots in zero elapsed time: gold's r_tag runs ahead
        # of now after its first grant, so the surplus splits by
        # weight instead of gold eating every slot
        grants = {"gold": 0, "noise": 0}
        for _ in range(20):
            got, _phase, _ = st.pick({"gold": clk.t - 5.0,
                                      "noise": clk.t - 5.0},
                                     now=clk.t)
            grants[got] += 1
        assert grants["noise"] >= 8   # ~weight-fair after the 1st res

    def test_weight_shares_track_ratio(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"a": QosSpec(weight=3.0),
                      "b": QosSpec(weight=1.0)})
        grants = {"a": 0, "b": 0}
        for _ in range(40):
            got, phase, _ = st.pick({"a": clk.t - 1.0,
                                     "b": clk.t - 1.0}, now=clk.t)
            assert phase == PROP
            grants[got] += 1
        assert 25 <= grants["a"] <= 35          # ~3:1

    def test_limit_throttles(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"capped": QosSpec(res=0.0, weight=1.0,
                                        lim=10.0)})
        served = 0
        for _ in range(5):
            got, _phase, wake = st.pick({"capped": clk.t - 1.0},
                                        now=clk.t)
            if got is not None:
                served += 1
        # 1 grant consumes 1/10s of limit credit; with the clock
        # frozen only the first pick serves, the rest are throttled
        assert served == 1
        assert wake > clk.t
        # time passes -> credit returns
        clk.t += 0.2
        got, _phase, _ = st.pick({"capped": clk.t - 1.0}, now=clk.t)
        assert got == "capped"

    def test_deadline_miss_counted(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"gold": QosSpec(res=10.0)})
        st.pick({"gold": clk.t}, now=clk.t)
        # next due tag ~t+0.1; serve it 5s late -> a recorded miss
        clk.t += 5.0
        st.pick({"gold": clk.t - 5.0}, now=clk.t)
        stats = st.stats()
        assert stats["clients"]["gold"]["deadline_misses"] >= 1
        assert stats["enabled"] is True

    def test_bytes_weighted_cost_scales_limit(self):
        """The cost model beyond cost=1: a big op advances its
        client's tags by cost/rate, so a limit meters BYTES — one
        cost-10 grant exhausts as much credit as ten cost-1 grants."""
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"p": QosSpec(lim=10.0)})
        got, phase, _ = st.pick({"p": clk.t}, now=clk.t,
                                costs={"p": 10.0})
        assert got == "p" and phase == PROP
        # next opportunity immediately after: over limit (throttled)
        got, _phase, _wake = st.pick({"p": clk.t}, now=clk.t + 0.05)
        assert got is None
        # still throttled where a cost-1 grant would have recharged
        got, _p, _w = st.pick({"p": clk.t}, now=clk.t + 0.15)
        assert got is None
        # credit returns only after cost/lim = 1s
        got, _p, _w = st.pick({"p": clk.t}, now=clk.t + 1.01)
        assert got == "p"

    def test_bytes_weighted_cost_scales_reservation(self):
        clk = FakeClock()
        st = DmClockState(clock=clk)
        st.configure({"r": QosSpec(res=100.0)})
        got, phase, _ = st.pick({"r": clk.t}, now=clk.t,
                                costs={"r": 50.0})
        assert got == "r" and phase == RES
        # a 50-cost grant consumed 0.5s of a 100/s reservation
        got2, phase2, _ = st.pick({"r": clk.t}, now=clk.t + 0.1)
        assert (got2, phase2) == ("r", PROP)   # res tag not due yet

    def test_stats_schema(self):
        st = DmClockState()
        st.configure({"p": QosSpec(res=5.0, weight=2.0, lim=50.0)})
        st.pick({"p": 0.0}, now=1.0)
        s = st.stats()
        assert s["clients"]["p"]["spec"] == "5:2:50"
        for key in ("res_grants", "prop_grants", "deadline_misses"):
            assert key in s["clients"]["p"]
        assert "throttle_stalls" in s


class TestQosQueue:
    def test_untagged_fifo_and_join(self):
        from ceph_tpu.utils.workqueue import QosQueue
        q = QosQueue(DmClockState())
        got = []
        for i in range(5):
            q.put(i)
        while True:
            try:
                got.append(q.get(timeout=0.05))
            except Exception:
                break
        assert got == [0, 1, 2, 3, 4]

    def test_limit_blocks_then_serves(self):
        from ceph_tpu.utils.workqueue import QosQueue
        st = DmClockState()
        st.configure({"capped": QosSpec(lim=20.0)})
        q = QosQueue(st)
        for i in range(4):
            q.put(i, client="capped")
        t0 = time.monotonic()
        got = [q.get(timeout=2.0) for _ in range(4)]
        took = time.monotonic() - t0
        assert got == [0, 1, 2, 3]
        # 4 grants at 20/s: the last waits ~3/20s for credit
        assert took >= 0.1
        assert st.throttle_stalls >= 1

    def test_sharded_pool_runs_tagged_work(self):
        from ceph_tpu.utils.workqueue import ShardedThreadPool
        st = DmClockState()
        st.configure({"gold": QosSpec(res=100.0, weight=4.0)})
        pool = ShardedThreadPool("qos-t", 2, qos_state=st)
        pool.start()
        done = []
        lock = threading.Lock()

        def work(tag, i):
            with lock:
                done.append((tag, i))

        for i in range(10):
            pool.queue(("pg", i % 2), work, "gold", i, qos="gold")
            pool.queue(("pg", i % 2), work, None, i)
        pool.drain()
        pool.stop()
        assert len(done) == 20
        assert st.stats()["clients"]["gold"]["res_grants"] + \
            st.stats()["clients"]["gold"]["prop_grants"] >= 1


class TestPipelineTenantQos:
    def test_dispatches_never_mix_tenants(self):
        """Items of different service classes must coalesce into
        SEPARATE dispatches — a reserved pool's stripes can never ride
        (and wait) inside a noisy pool's mega-batch."""
        import numpy as np
        from ceph_tpu.ops import pipeline as ec_pipeline
        pipe = ec_pipeline.EcDevicePipeline(depth=1,
                                            coalesce_wait=0.001)
        with pipe._lock:
            pipe._qos.configure(
                {"gold": QosSpec(res=100.0, weight=4.0),
                 "noise": QosSpec(weight=1.0)})
            pipe._qos_enabled = True
        batches = []
        block = threading.Event()

        def host_fn(batch):
            block.wait(2.0)
            batches.append(batch.shape[0])
            return (batch,)

        chan = ec_pipeline.PipelineChannel(key=("t", "mix"),
                                           host_fn=host_fn)
        futs = []
        # first submission occupies the dispatcher inside host_fn;
        # the rest queue behind it per tenant
        futs.append(pipe.submit(chan, np.zeros((1, 4),
                                               dtype=np.uint8),
                                qos="noise"))
        time.sleep(0.1)
        for _ in range(3):
            futs.append(pipe.submit(chan, np.zeros((1, 4),
                                                   dtype=np.uint8),
                                    qos="noise"))
        for _ in range(2):
            futs.append(pipe.submit(chan, np.zeros((1, 4),
                                                   dtype=np.uint8),
                                    qos="gold"))
        block.set()
        for f in futs:
            f.result(timeout=10)
        pipe.stop()
        # 1 (first) + one noise batch (3) + one gold batch (2): the
        # queued noise and gold items must NOT have merged into one
        # 5-stripe dispatch
        assert sorted(batches) == [1, 2, 3], batches

    def test_configure_qos_module_surface(self):
        from ceph_tpu.ops import pipeline as ec_pipeline
        ec_pipeline.configure_qos({"p": QosSpec(res=10.0)},
                                  cost_unit=8192)
        try:
            s = ec_pipeline.qos_stats()
            assert s["enabled"] is True
            assert "p" in s["clients"]
            assert ec_pipeline.get().qos_cost_unit == 8192
        finally:
            ec_pipeline.configure_qos({})

    def test_picks_charge_per_candidate_head_bytes(self):
        """The dispatch-lane tenant picker charges each pick by its
        head batch's staged bytes (1 + bytes/unit), not cost=1: the
        dmClock state must receive a per-candidate costs map whose
        values scale with the head item sizes, and the pipeline's
        qos_cost_picks counter must move."""
        import numpy as np
        from ceph_tpu.ops import pipeline as ec_pipeline
        pipe = ec_pipeline.EcDevicePipeline(depth=1,
                                            coalesce_wait=0.001,
                                            qos_cost_unit=1024)
        seen_costs = []
        real_pick = pipe._qos.pick

        def spy_pick(cands, now=None, cost=1.0, costs=None):
            if costs is not None:
                seen_costs.append(dict(costs))
            return real_pick(cands, now=now, cost=cost, costs=costs)

        pipe._qos.pick = spy_pick
        with pipe._lock:
            pipe._qos.configure({"big": QosSpec(weight=1.0),
                                 "small": QosSpec(weight=1.0)})
            pipe._qos_enabled = True
        block = threading.Event()

        def host_fn(batch):
            block.wait(2.0)
            return (batch,)

        chan = ec_pipeline.PipelineChannel(key=("t", "cost"),
                                           host_fn=host_fn)
        futs = [pipe.submit(chan, np.zeros((1, 16), dtype=np.uint8),
                            qos="small")]
        time.sleep(0.1)          # occupy the dispatcher inside host_fn
        futs.append(pipe.submit(chan, np.zeros((1, 4096),
                                               dtype=np.uint8),
                                qos="big"))
        futs.append(pipe.submit(chan, np.zeros((1, 16),
                                               dtype=np.uint8),
                                qos="small"))
        block.set()
        for f in futs:
            f.result(timeout=10)
        stats = pipe.stats()
        pipe.stop()
        assert stats["qos_cost_picks"] >= 1
        assert stats["qos_cost_unit"] == 1024
        # at least one pick saw both tenants queued with costs that
        # scale with their head bytes (1 + nbytes/unit)
        both = [c for c in seen_costs if "big" in c and "small" in c]
        assert both, seen_costs
        assert both[0]["big"] == 1.0 + 4096 / 1024
        assert both[0]["small"] == 1.0 + 16 / 1024


# ---------------------------------------------------------------------------
# The noisy-neighbor drill: load-bearing proof on a real cluster.
# ---------------------------------------------------------------------------

DRILL_SEED = 0x90D1


def _drill(qos: bool) -> dict:
    """One seeded open-loop round: a noisy tenant saturates a
    deterministically-throttled cluster (every client op costs 20 ms
    on its op shard) while the gold tenant offers light traffic.
    Returns the gold pool's report."""
    from ceph_tpu.tools.loadgen import LoadGen, TenantSpec
    from ceph_tpu.utils.config import Config
    from ceph_tpu.vstart import MiniCluster
    conf = {
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
        "mon_osd_down_out_interval": 5.0,
        # known capacity: 2 shards/osd x 50 ops/s = overloadable
        "osd_op_num_shards": 2,
        "osd_debug_inject_dispatch_delay_probability": 1.0,
        "osd_debug_inject_dispatch_delay_duration": 0.02,
        "objecter_op_timeout": 60.0,
    }
    if qos:
        # gold: 80 IOPS reserved, 4x surplus weight, no cap
        conf["osd_pool_qos_gold"] = "80:4:0"
    cluster = MiniCluster(num_mons=1, num_osds=3,
                          conf=Config(conf)).start()
    try:
        rados = cluster.client()
        rados.create_pool("gold", pg_num=4)
        rados.create_pool("noise", pg_num=4)
        io_gold = rados.open_ioctx("gold")
        io_noise = rados.open_ioctx("noise")
        end = time.time() + 60
        while True:
            try:
                io_gold.write_full("settle", b"s")
                io_noise.write_full("settle", b"s")
                break
            except Exception:
                if time.time() > end:
                    raise
                time.sleep(0.3)
        tenants = [
            TenantSpec("gold", rate=15, duration=3.0, obj_count=8,
                       read_frac=0.5, payload=4096, max_workers=16),
            # offered ~3x the delay-throttled service capacity: the
            # op shards RUN A QUEUE for the whole window
            TenantSpec("noise", rate=220, duration=3.0, obj_count=16,
                       read_frac=0.0, payload=8192, max_workers=64),
        ]
        gen = LoadGen(tenants, seed=DRILL_SEED)
        report = gen.run({"gold": io_gold, "noise": io_noise})
        out = dict(report["pools"]["gold"])
        out["noise_ops"] = report["pools"]["noise"]["ops"]
        if qos:
            # the mechanism must actually have granted reservations
            qd = [o for o in cluster.osds.values()]
            grants = 0
            for osd in qd:
                st = osd._qos.stats()
                ent = st["clients"].get("gold")
                if ent:
                    grants += ent["res_grants"] + ent["prop_grants"]
            out["gold_grants"] = grants
        return out
    finally:
        cluster.stop()


class TestNoisyNeighborDrill:
    def test_reserved_pool_p99_bounded_and_mechanism_load_bearing(
            self):
        """With QoS: the reserved pool's p99 stays bounded while the
        noisy tenant saturates every op shard.  WITHOUT QoS, the same
        seed shows the starvation — FIFO queues the gold ops behind
        hundreds of noise ops.  Both halves run the identical offered
        schedule (seed-deterministic), so the only variable is the
        scheduler."""
        with_qos = _drill(qos=True)
        without = _drill(qos=False)
        assert with_qos["errors"] == 0, with_qos
        assert with_qos["gold_grants"] >= 1, with_qos
        # bounded: a reserved op waits at most ~the op in service +
        # scheduling slack, not the noise backlog
        assert with_qos["p99_ms"] < 1000.0, (with_qos, without)
        # load-bearing: the same seed WITHOUT QoS starves gold — its
        # tail rides the noise queue, several times the bounded p99
        assert without["p99_ms"] > 2.0 * with_qos["p99_ms"], \
            (with_qos, without)
        assert without["p99_ms"] > 1000.0, (with_qos, without)


class TestRecoveryQosClass:
    """QoS-aware recovery: with osd_qos_recovery set, MPGPush
    payloads are scheduled under the "@recovery" dmClock class
    (bytes-weighted) instead of the unconstrained control plane."""

    def test_backfill_pushes_ride_recovery_class(self):
        from ceph_tpu.utils.config import Config
        from ceph_tpu.vstart import MiniCluster
        conf = {
            "osd_heartbeat_interval": 0.5,
            "osd_heartbeat_grace": 8.0,
            "mon_osd_min_down_reporters": 2,
            "mon_osd_down_out_interval": 5.0,
            "osd_pg_log_max_entries": 16,
            # generous limit: throttleable, not test-slowing
            "osd_qos_recovery": "0:1:5000",
        }
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf=Config(conf)).start()
        try:
            rados = cluster.client()
            rados.create_pool("recq", pg_num=1)
            io = rados.open_ioctx("recq")
            end = time.time() + 60
            while True:
                try:
                    io.write_full("settle", b"s")
                    break
                except Exception:
                    if time.time() > end:
                        raise
                    time.sleep(0.3)
            for i in range(40):      # > log bound: forces backfill
                io.write_full(f"r{i:03d}", b"x" * 8192)
            m = cluster.leader().osdmon.osdmap
            pgid = m.object_to_pg(io.pool_id, "r000")
            _up, acting = m.pg_to_up_acting_osds(pgid)
            victim = acting[-1]
            cluster.kill_osd(victim)
            cluster.wait_for_osd_down(victim, timeout=40)
            cluster.start_osd(victim)     # memstore: reborn EMPTY
            cluster.wait_for_osds(3, timeout=40)
            vic = cluster.osds[victim]
            end = time.time() + 90
            while time.time() < end:
                have = sum(1 for i in range(40)
                           if vic.store.exists(f"pg_{pgid}",
                                               f"r{i:03d}"))
                if have == 40:
                    break
                time.sleep(0.5)
            assert have == 40, f"backfill incomplete: {have}/40"
            # the reborn peer's qos block shows the recovery class
            # actually granted work (the pushes it received)
            qos = vic._perf_dump()["qos"]["clients"]
            assert "@recovery" in qos, qos
            ent = qos["@recovery"]
            assert ent["res_grants"] + ent["prop_grants"] >= 10
        finally:
            cluster.stop()


class TestRecoveryDecodeLane:
    """The rebuild's decode, since PR 48 its one pass through the
    pipeline, must sit under the repair cap: reconstructing a dead
    shard from survivors tags its pipeline dispatch with the
    "@recovery" class — otherwise repair reads escape
    osd_qos_recovery."""

    def test_rebuild_decode_rides_recovery_class(self):
        from ceph_tpu.ops import pipeline as ec_pipeline
        from ceph_tpu.utils.config import Config
        from ceph_tpu.vstart import MiniCluster
        conf = {
            "osd_heartbeat_interval": 0.5,
            "osd_heartbeat_grace": 8.0,
            "mon_osd_min_down_reporters": 2,
            "mon_osd_down_out_interval": 5.0,
            "osd_qos_recovery": "0:1:5000",
            # force the rebuild to actually DECODE: no HBM stripe
            # cache shortcut serving the payload without a gather
            "osd_ec_hbm_cache_bytes": 0,
        }
        cluster = MiniCluster(num_mons=1, num_osds=3,
                              conf=Config(conf)).start()
        pipe = ec_pipeline.get()
        picks: list[tuple] = []
        orig = pipe.submit

        def spy(chan, arr, cache=None, qos=None):
            picks.append((chan.key[0], qos))
            return orig(chan, arr, cache=cache, qos=qos)

        pipe.submit = spy
        try:
            rados = cluster.client()
            # host_cutover=1 forces pipeline routing on the host-only
            # test rig, so decode lane picks actually reach submit()
            rados.create_ec_pool("decq", "dq_k2m1",
                                 {"plugin": "tpu", "k": 2, "m": 1,
                                  "host_cutover": "1"}, pg_num=1)
            io = rados.open_ioctx("decq")
            end = time.time() + 60
            while True:
                try:
                    io.write_full("settle", b"s" * 1024)
                    break
                except Exception:
                    if time.time() > end:
                        raise
                    time.sleep(0.3)
            for i in range(12):
                io.write_full(f"d{i:02d}", b"x" * 8192)
            m = cluster.leader().osdmon.osdmap
            pgid = m.object_to_pg(io.pool_id, "d00")
            _up, acting = m.pg_to_up_acting_osds(pgid)
            victim = acting[1]   # a DATA shard: its rebuild decodes
            cluster.kill_osd(victim)
            cluster.wait_for_osd_down(victim, timeout=40)
            cluster.start_osd(victim)     # memstore: reborn EMPTY
            cluster.wait_for_osds(3, timeout=40)
            end = time.time() + 90
            while time.time() < end:
                if any(k == "dec" and q == "@recovery"
                       for k, q in picks):
                    break
                time.sleep(0.3)
            dec_classes = {q for k, q in picks if k == "dec"}
            assert "@recovery" in dec_classes, \
                (dec_classes, picks[-20:])
        finally:
            # not `pipe.submit = orig`: that leaves the bound method ON
            # the process-wide instance, where it shadows the class's
            # for every later test of this worker (test_copy_audit
            # patches the class)
            del pipe.submit
            cluster.stop()
