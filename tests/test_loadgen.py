"""Load harness: seeded determinism, open-loop semantics, report
schema — plus the cache-served read coherence drill (bit-exactness vs
the store oracle under overwrites, appends and quarantine drops)."""

import time

import pytest

from ceph_tpu.tools.loadgen import (LoadGen, TenantSpec, _payload_bytes,
                                    _zipf_cdf)


class TestSchedule:
    def test_seed_deterministic(self):
        spec = TenantSpec("p", rate=200, duration=2.0, obj_count=32)
        a = LoadGen([spec], seed=7).schedule
        b = LoadGen([spec], seed=7).schedule
        assert [(o.t, o.pool, o.kind, o.oid, o.body_seed)
                for o in a] == \
            [(o.t, o.pool, o.kind, o.oid, o.body_seed) for o in b]
        c = LoadGen([spec], seed=8).schedule
        assert [(o.t, o.oid) for o in a] != [(o.t, o.oid) for o in c]

    def test_rate_and_duration_respected(self):
        spec = TenantSpec("p", rate=500, duration=4.0)
        sched = LoadGen([spec], seed=3).schedule
        assert all(0 <= o.t < 4.0 for o in sched)
        # Poisson(500/s * 4s): well within 5 sigma
        assert 1700 <= len(sched) <= 2300

    def test_zipf_head_is_hot(self):
        spec = TenantSpec("p", rate=2000, duration=2.0,
                          obj_count=64, zipf_s=1.2, read_frac=1.0)
        sched = LoadGen([spec], seed=5).schedule
        counts: dict[str, int] = {}
        for op in sched:
            counts[op.oid] = counts.get(op.oid, 0) + 1
        ranked = sorted(counts.values(), reverse=True)
        # the hot head dominates the tail
        assert ranked[0] > 5 * (ranked[-1] if ranked[-1] else 1)

    def test_op_mix(self):
        spec = TenantSpec("p", rate=1000, duration=2.0,
                          read_frac=0.5, append_frac=0.5)
        sched = LoadGen([spec], seed=9).schedule
        kinds = {k: sum(1 for o in sched if o.kind == k)
                 for k in ("read", "write_full", "append")}
        total = len(sched)
        assert 0.4 < kinds["read"] / total < 0.6
        assert kinds["append"] > 0 and kinds["write_full"] > 0

    def test_zipf_cdf_monotone(self):
        cdf = _zipf_cdf(16, 1.1)
        assert cdf == sorted(cdf) and abs(cdf[-1] - 1.0) < 1e-9
        flat = _zipf_cdf(4, 0.0)
        assert flat == [0.25, 0.5, 0.75, 1.0]

    def test_payloads_distinct_and_deterministic(self):
        assert _payload_bytes(1, 100) == _payload_bytes(1, 100)
        assert _payload_bytes(1, 100) != _payload_bytes(2, 100)
        assert len(_payload_bytes(3, 12345)) == 12345
        assert _payload_bytes(1, 0) == b""


class _StubIoCtx:
    """In-memory IoCtx stub with a configurable service delay."""

    def __init__(self, delay: float = 0.0):
        self.objs: dict[str, bytes] = {}
        self.delay = delay

    def _d(self):
        if self.delay:
            time.sleep(self.delay)

    def write_full(self, oid, data):
        self._d()
        self.objs[oid] = bytes(data)

    def append(self, oid, data):
        self._d()
        self.objs[oid] = self.objs.get(oid, b"") + bytes(data)

    def read(self, oid):
        self._d()
        return self.objs[oid]


class TestRun:
    def test_report_schema_and_goodput(self):
        spec = TenantSpec("p", rate=300, duration=1.0, obj_count=8,
                          read_frac=0.5, payload=1024)
        rep = LoadGen([spec], seed=11).run({"p": _StubIoCtx()})
        assert rep["completed"] == sum(rep["offered"].values())
        st = rep["pools"]["p"]
        for key in ("ops", "errors", "timeouts", "reads", "writes",
                    "p50_ms", "p99_ms", "p999_ms", "mean_ms",
                    "goodput_gbs", "queue_depth_max",
                    "queue_depth_mean"):
            assert key in st, key
        assert st["errors"] == 0
        assert st["p50_ms"] <= st["p99_ms"] <= st["p999_ms"]
        assert rep["goodput_gbs"] > 0

    def test_open_loop_latency_includes_queueing(self):
        """A slow backend must SHOW its backlog: arrivals outpace a
        25 ms service time, so the open-loop p99 (measured from the
        scheduled arrival) grows far beyond one service time."""
        spec = TenantSpec("p", rate=150, duration=1.0, obj_count=4,
                          read_frac=0.0, payload=64, max_workers=1)
        rep = LoadGen([spec], seed=13).run(
            {"p": _StubIoCtx(delay=0.025)}, warm=False)
        st = rep["pools"]["p"]
        assert st["p99_ms"] > 300.0            # backlog, not service
        assert st["queue_depth_max"] > 5


# ---------------------------------------------------------------------------
# Cache-served read coherence: bit-exact vs the store oracle through
# overwrites, appends (write-through) and quarantine drops.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.utils.config import Config
    from ceph_tpu.vstart import MiniCluster
    c = MiniCluster(num_mons=1, num_osds=3, conf=Config({
        "mon_tick_interval": 0.5,
        "osd_heartbeat_interval": 0.5,
        "osd_heartbeat_grace": 8.0,
        "mon_osd_min_down_reporters": 2,
        "mon_osd_down_out_interval": 5.0,
    })).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def ec_io(cluster):
    rados = cluster.client()
    # host_cutover=1: encodes ride the (CPU-mesh) device lanes so the
    # HBM stripe cache populates exactly as on a real chip
    rados.create_ec_pool("cread", "creadp",
                         {"plugin": "tpu", "k": 2, "m": 1,
                          "host_cutover": 1}, pg_num=4)
    io = rados.open_ioctx("cread")
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            return io
        except Exception:
            if time.time() > end:
                raise
            time.sleep(0.3)


def _write_until_cached(io, cache, oid: str, body: bytes,
                        window: float = 60.0) -> None:
    """Overwrite until a probe read serves from the cache (lanes warm
    their fused fns in the background; cold-lane writes host-serve)."""
    end = time.time() + window
    while time.time() < end:
        io.write_full(oid, body)
        s0 = cache.stats()["read_bytes_served"]
        got = io.read(oid)
        assert bytes(got) == body          # correct either way
        if cache.stats()["read_bytes_served"] > s0:
            return
        time.sleep(0.2)
    raise AssertionError(f"{oid} never became cache-served")


class TestCacheServedReads:
    def test_bit_exact_through_overwrites_appends_and_drops(
            self, cluster, ec_io):
        from ceph_tpu.ops import hbm_cache
        from ceph_tpu.ops import pipeline as ec_pipeline
        from ceph_tpu.utils import faults
        cache = hbm_cache.get()
        payload = 3 * 8192 + 517           # unaligned: padding paths
        v1 = _payload_bytes(0xA1, payload)
        _write_until_cached(ec_io, cache, "cobj", v1)
        # 1) cache-served == store oracle for the SAME read: disable
        # the cache (clears it), read again, compare byte-for-byte
        cached_read = bytes(ec_io.read("cobj"))
        hbm_cache.configure(0)
        try:
            oracle = bytes(ec_io.read("cobj"))
        finally:
            hbm_cache.configure(64 << 20)
        assert cached_read == oracle == v1
        # 2) overwrite coherence: the stale entry must never serve
        v2 = _payload_bytes(0xA2, payload - 2048)
        _write_until_cached(ec_io, cache, "cobj", v2)
        assert bytes(ec_io.read("cobj")) == v2
        # 3) append write-through: the appended object stays
        # cache-served (no re-upload of the prefix) and bit-exact
        delta = _payload_bytes(0xA3, 4321)
        s = cache.stats()
        ec_io.append("cobj", delta)
        got = bytes(ec_io.read("cobj"))
        assert got == v2 + delta
        s2 = cache.stats()
        if s2["append_throughs"] > s["append_throughs"]:
            # the write-through engaged: that read came off the chip
            assert s2["read_bytes_served"] > s["read_bytes_served"]
        # 4) quarantine drop: kill the lane(s), entries must drop and
        # the store path keeps serving the same bytes
        faults.get().tpu_error(1.0)        # every lane
        try:
            assert bytes(ec_io.read("cobj")) == v2 + delta
        finally:
            faults.get().reset()
        ec_pipeline.get().reset_devices()

    def test_concurrent_overwrites_never_serve_stale(
            self, cluster, ec_io):
        """Interleave overwrites and reads: every read must return
        the value of SOME completed write (monotone versions — a
        cache serving a stale entry would resurrect an old payload
        after a newer read observed the overwrite)."""
        import threading
        payload = 16384
        versions = [_payload_bytes(0xB0 + i, payload)
                    for i in range(6)]
        ec_io.write_full("race", versions[0])
        errors = []

        def reader():
            # sequential reads from one client: versions are monotone
            # at the primary, so observing v_i and THEN v_j (j < i)
            # means a stale cache entry served after its overwrite
            high = 0
            for _ in range(40):
                try:
                    got = bytes(ec_io.read("race"))
                except Exception:
                    continue
                try:
                    idx = versions.index(got)
                except ValueError:
                    errors.append("read returned bytes matching NO "
                                  "written version")
                    return
                if idx < high:
                    errors.append(
                        f"stale read: v{idx} after v{high}")
                    return
                high = idx

        th = threading.Thread(target=reader)
        th.start()
        for body in versions[1:]:
            ec_io.write_full("race", body)
            time.sleep(0.02)
        th.join(timeout=60)
        assert not th.is_alive()
        assert not errors, errors

    def test_seeded_round_on_the_ec_pool_completes_without_error(
            self, cluster, ec_io):
        """An open-loop round of reads, overwrites and appends against
        the plugin=tpu pool of a live cluster: every offered op
        completes, none fails, and the reads stay inside the read-side
        copy floor (a cache-served or intact read copies nothing)."""
        from ceph_tpu.utils import copyaudit
        gen = LoadGen([TenantSpec(
            ec_io.pool_name, rate=80, duration=2.0, obj_count=16,
            zipf_s=1.1, read_frac=0.6, payload=8192,
            append_frac=0.1)], seed=0x510AD)
        c0 = copyaudit.snapshot()
        rep = gen.run({ec_io.pool_name: ec_io})
        c1 = copyaudit.snapshot()
        assert rep["completed"] == sum(rep["offered"].values()) > 0
        assert sum(p["errors"] for p in rep["pools"].values()) == 0
        reads = c1["reads"] - c0["reads"]
        assert reads > 0
        assert c1["read_copies"] - c0["read_copies"] <= reads
