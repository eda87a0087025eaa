"""Packet-layout codes on `plugin=tpu` (ISSUE 32): jerasure's
`cauchy_good` (and the liberation family beside it) through the shared
device pipeline, encode and CRC32C in one program.  The plain reference
is the benchmark's (`benchmark/references/cauchy_good.py`, numpy alone);
GF(2) arithmetic, so every comparison is exact."""

import itertools
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import oracle
from benchmark.references import cauchy_good as ref
from ceph_tpu.client import RadosError
from ceph_tpu.erasure.registry import registry
from ceph_tpu.ops import hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pglog import HINFO_KEY
from ceph_tpu.utils import denc, faults
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

P = 32
L = 4096
SHAPES = [(6, 3), (3, 2)]


def cauchy(k, m, packetsize=P):
    return registry.factory("tpu", {
        "technique": "cauchy_good", "k": str(k), "m": str(m),
        "packetsize": str(packetsize), "host_cutover": "1"})


def random_stripes(seed, n, k, length=L):
    return np.random.default_rng(seed).integers(
        0, 256, (n, k, length), dtype=np.uint8)


def reference(stripes, m, packetsize=P):
    """((S, k+m, L) chunks, (S, k+m) crcs) by the plain reference."""
    allc = np.stack([np.concatenate([d, ref.encode(d, m, packetsize)])
                     for d in stripes])
    S, km, length = allc.shape
    return allc, oracle.crc32c(allc.reshape(S * km, length)).reshape(S, km)


def wait_for(probe, what, seconds=120.0):
    end = time.monotonic() + seconds
    while True:
        got = probe()
        if got:
            return got
        assert time.monotonic() < end, f"timed out waiting for {what}"
        time.sleep(0.02)


def encode_on_device(codec, stripes):
    """The result of a submission the DEVICE path served (the first
    ones are the host's while the program compiles)."""
    def once():
        before = codec.stat_counters()["device_stripe_passes"]
        handle = codec.encode_stripes_with_crcs_async(stripes)
        out = handle.result(60)
        if codec.stat_counters()["device_stripe_passes"] > before:
            return out, handle.trace_phases
    return wait_for(once, "a device-served encode")


@pytest.fixture(autouse=True)
def _clean():
    faults.get().reset(seed=0)
    yield
    faults.get().reset(seed=0)
    pipe = ec_pipeline.get()
    st = pipe.stats()
    if st["devices"] and any(d["quarantined"]
                             for d in st["devices"].values()):
        pipe.reset_devices()


# ---------------------------------------------------------------------------
# the codec on the pipeline
# ---------------------------------------------------------------------------


class TestEncode:
    @pytest.mark.parametrize("k,m,n", [(6, 3, 3), (6, 3, 11), (3, 2, 5)])
    def test_parity_and_crcs_equal_the_reference(self, k, m, n):
        codec = cauchy(k, m)
        assert np.array_equal(codec.coding_matrix, ref.coding_matrix(k, m))
        stripes = random_stripes(32 + n, n, k)
        want_chunks, want_crcs = reference(stripes, m)
        # whichever path serves the first submission (the host's, while
        # the program compiles), the bytes are the reference's
        chunks, crcs = codec.encode_stripes_with_crcs_async(
            stripes).result(60)
        assert np.array_equal(chunks, want_chunks)
        assert np.array_equal(crcs, want_crcs)
        (chunks, crcs), ph = encode_on_device(codec, stripes)
        assert np.array_equal(chunks, want_chunks)
        assert np.array_equal(crcs, want_crcs)
        # the dispatch says what it computed
        assert ph["rep"] == "packets" and ph["stripes"] == n
        assert ph["padded"] == ec_pipeline.next_bucket(n)
        assert "issue" in ph and "collect0" in ph and "host0" not in ph

    @pytest.mark.parametrize("k,m", SHAPES)
    def test_encode_with_crcs_serves_the_packet_code(self, k, m):
        codec = cauchy(k, m)
        stripes = random_stripes(7, 4, k, 512)
        want_chunks, want_crcs = reference(stripes, m)
        parity, crcs = codec.encode_with_crcs(stripes)
        assert np.array_equal(parity, want_chunks[:, k:])
        assert np.array_equal(crcs, want_crcs)
        assert not codec.degraded

    def test_object_with_a_padded_tail_equals_the_reference(self):
        codec = cauchy(6, 3)
        payload = np.random.default_rng(5).integers(
            0, 256, 100_000, dtype=np.uint8).tobytes()
        shards, crcs = ecutil.encode_object(
            codec, ecutil.StripeInfo(6, L), payload)
        want = ref.stored(payload, {
            "pool_profile": {"technique": "cauchy_good", "k": 6, "m": 3,
                             "packetsize": P}, "stripe_unit": L})
        assert len(shards[0]) == 5 * L      # 100,000 bytes: five stripes
        assert [(bytes(s), int(c)) for s, c in zip(shards, crcs)] == want

    def test_two_submissions_share_one_dispatch_and_split_back(
            self, monkeypatch):
        codec = cauchy(6, 3)
        a, b = random_stripes(1, 3, 6), random_stripes(2, 4, 6)
        device = jax.devices()[0]
        wait_for(lambda: codec.backend.fused_fn_if_ready(
            codec.coding_matrix, (8, 6, L), device), "the 8-bucket")
        pipe = ec_pipeline.EcDevicePipeline(depth=1, device_shards=1)
        monkeypatch.setattr(ec_pipeline, "get", lambda: pipe)
        try:
            # both are queued before the dispatcher starts: one pick
            # takes both
            pipe._running = True
            ha = codec.encode_stripes_with_crcs_async(a)
            hb = codec.encode_stripes_with_crcs_async(b)
            pipe._running = False
            pipe._ensure_threads()
            for stripes, handle in ((a, ha), (b, hb)):
                chunks, crcs = handle.result(60)
                want_chunks, want_crcs = reference(stripes, 3)
                assert np.array_equal(chunks, want_chunks)
                assert np.array_equal(crcs, want_crcs)
            st = pipe.stats()
            assert st["dispatches"] == st["dev_dispatches"] == 1
            assert st["stripes"] == 7 and st["bytes_h2d"] == 8 * 6 * L
            pa, pb = ha.trace_phases, hb.trace_phases
            assert (pa["stripes"], pb["stripes"]) == (3, 4)
            # the two shares add up to the bucket the dispatch ran at
            assert pa["padded"] + pb["padded"] == pytest.approx(8.0)
            assert pa["padded"] == pytest.approx(8 * 3 / 7)
        finally:
            pipe.stop()

    def test_a_degraded_lane_drains_on_the_host_to_the_same_bytes(self):
        codec = cauchy(6, 3)
        batches = [random_stripes(40 + i, n, 6, 1024)
                   for i, n in enumerate((1, 3, 2, 5, 1, 4))]
        handles = [codec.encode_stripes_with_crcs_async(x)
                   for x in batches[:3]]
        faults.get().tpu_device_error(1.0)      # mid-queue
        handles += [codec.encode_stripes_with_crcs_async(x)
                    for x in batches[3:]]
        for stripes, handle in zip(batches, handles):
            chunks, crcs = handle.result(60)
            want_chunks, want_crcs = reference(stripes, 3)
            assert np.array_equal(chunks, want_chunks)
            assert np.array_equal(crcs, want_crcs)
        assert codec.degraded and "device" in codec.degrade_reason
        assert codec.stat_counters()["host_stripe_passes"] >= 3

    def test_liberation_rides_the_same_channel(self):
        profile = {"technique": "liberation", "k": "5", "m": "2", "w": "7",
                   "packetsize": str(P)}
        codec = registry.factory("tpu", dict(profile, host_cutover="1"))
        host = registry.factory("jerasure", dict(profile, backend="host"))
        stripes = random_stripes(9, 6, 5, 7 * P * 8)
        want_chunks, want_crcs = host.encode_stripes_with_crcs(stripes)
        (chunks, crcs), ph = encode_on_device(codec, stripes)
        assert np.array_equal(chunks, want_chunks)
        assert np.array_equal(crcs, want_crcs)
        assert ph["rep"] == "bits" and ph["padded"] == 8
        # one program for both packet layouts, keyed by representation
        assert {key[0] for key in codec.backend._fns} == {"fused"}
        assert codec._encode_channel(7 * P * 8).key[0] == "enc"


class TestDecode:
    @pytest.mark.parametrize("k,m,lost", [(6, 3, 1), (6, 3, 2), (6, 3, 3),
                                          (3, 2, 1), (3, 2, 2)])
    def test_every_pattern_decodes_on_the_pipeline(self, k, m, lost):
        codec = cauchy(k, m)
        stripes = random_stripes(60 + lost, 3, k, 1024)
        allc, _crcs = reference(stripes, m)
        before = ec_pipeline.stats()["ops"]
        patterns = list(itertools.combinations(range(k + m), lost))
        for gone in patterns:
            avail = [i for i in range(k + m) if i not in gone]
            present = codec.minimum_to_decode(gone, avail)
            out = codec.decode_batch_async(
                list(gone), present, allc[:, present]).result(60)
            assert np.array_equal(out, allc[:, list(gone)]), gone
        # every one was a submission to the pipeline
        assert ec_pipeline.stats()["ops"] - before == len(patterns)

    def test_the_decode_warm_up_call_serves_the_packet_program(self):
        """`benchmark/warmers/decode.py` names the apply "bytes" with
        the codec's decode rows: on a packet codec's backend that is
        the packet program, ready by the rows' SHAPE."""
        codec = cauchy(6, 3)
        shape, device = (4, 6, 1024), jax.devices()[0]
        rows = codec._decode_rows([0, 1], list(range(2, 8)))
        fn = wait_for(lambda: codec.backend.device_fn_if_ready(
            "bytes", rows, (), shape, device), "the decode fn")
        stripes = random_stripes(3, 4, 6, 1024)
        allc, _crcs = reference(stripes, 3)
        assert np.array_equal(np.asarray(fn(allc[:, 2:8])), allc[:, :2])
        assert {key[0] for key in codec.backend._fns} == {"packets"}
        # another pattern of the shape is served at once
        other = codec._decode_rows([4, 7], [0, 1, 2, 3, 5, 6])
        assert codec.backend.device_fn_if_ready(
            "bytes", other, (), shape, device) is not None

    def test_decode_object_through_the_batched_path(self):
        codec = cauchy(6, 3)
        si = ecutil.StripeInfo(6, L)
        payload = np.random.default_rng(11).integers(
            0, 256, 100_000, dtype=np.uint8).tobytes()
        shards, _crcs = ecutil.encode_object(codec, si, payload)
        have = {i: s for i, s in enumerate(shards) if i not in (0, 4, 7)}
        assert ecutil.decode_object(codec, si, have,
                                    len(payload)) == payload


def test_the_reference_reproduces_the_archived_jerasure_corpus():
    """The reference shares no code with what wrote the corpus."""
    from tests.test_corpus import CORPUS_PATH
    with open(CORPUS_PATH) as f:
        archived = json.load(f)[
            "jerasure(k=6,m=3,packetsize=128,technique=cauchy_good)"]
    data = np.random.default_rng(0xCEF).integers(
        0, 256, 100_000, dtype=np.uint8)
    size = archived["chunk_size"]
    buf = np.zeros(6 * size, dtype=np.uint8)
    buf[:len(data)] = data
    chunks = buf.reshape(6, size)
    allc = np.concatenate([chunks, ref.encode(chunks, 3, 128)])
    assert [int(c) for c in oracle.crc32c(allc)] == archived["crcs"]


@pytest.mark.parametrize("profile,why", [
    ({"technique": "reed_sol_van", "k": 6, "m": 3, "packetsize": P},
     "technique"),
    ({"technique": "cauchy_good", "k": 6, "m": 3, "packetsize": P,
      "w": 16}, "w=8 only"),
    ({"technique": "cauchy_good", "k": 6, "m": 3, "packetsize": 48},
     "multiple"),
])
def test_the_reference_refuses_what_it_cannot_stand_for(profile, why):
    with pytest.raises(ValueError, match=why):
        ref.stored(b"x" * 100, {"pool_profile": profile,
                                "stripe_unit": 4096})


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(ref.__file__),
                           "cauchy_good.py")) as f:
        assert "ceph_tpu" not in f.read().split('"""', 2)[2]


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

OBJECT_BYTES = 100_000
CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 600.0,
    "osd_op_history_size": 4096,
}
PROFILE = {"plugin": "tpu", "technique": "cauchy_good", "k": 6, "m": 3,
           "packetsize": P, "host_cutover": 1, "stripe_unit": L}


def payload(i: int) -> bytes:
    return np.random.default_rng(3200 + i).integers(
        0, 256, OBJECT_BYTES, dtype=np.uint8).tobytes()


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=10, conf=Config(CONF)).start()
    yield c
    faults.get().reset()
    c.stop()


@pytest.fixture(scope="module")
def io(cluster):
    rados = cluster.client()
    rados.create_ec_pool("cauchy", "cauchy-prof", dict(PROFILE), pg_num=2)
    io = rados.open_ioctx("cauchy")
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            io.remove_object("settle")
            break
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)
    return io


def placement(cluster, io, oid):
    m = cluster.leader().osdmon.osdmap
    pgid = m.object_to_pg(io.pool_id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    return list(acting), cluster.osds[acting[0]].pgs[pgid]


def write_docs(cluster, oid):
    return [d for osd in cluster.osds.values()
            for d in osd.op_tracker.dump_historic_ops()["ops"]
            if d["kind"] == "client" and f" {oid} " in d["description"]
            and "'writefull'" in d["description"]]


class TestCluster:
    def test_write_rides_the_device_pipeline(self, cluster, io):
        """Spans, counters and the cache tag of a served write."""
        before = ec_pipeline.stats()

        def served():
            oid = f"w{served.n}"
            served.n += 1
            io.write_full(oid, payload(0))
            spans = [s for d in write_docs(cluster, oid)
                     for s in d["spans"] if s["name"] == "ec.device_compute"]
            return (oid, spans) if spans else None

        served.n = 0
        oid, (span,) = wait_for(served, "a device-served write")
        assert span["args"] == {"stripes": 5, "padded": 8.0,
                                "rep": "packets", "rows": 3}
        names = {s["name"] for d in write_docs(cluster, oid)
                 for s in d["spans"]}
        assert {"ec.stage_h2d", "ec.device_compute", "ec.d2h"} <= names
        after = ec_pipeline.stats()
        assert after["dev_dispatches"] > before["dev_dispatches"]
        assert after["bytes_h2d"] - before["bytes_h2d"] >= 8 * 6 * L
        acting, pg = placement(cluster, io, oid)
        codec = pg.osd.get_ec_codec(pg.pool)
        assert codec.rep == "packets"
        assert codec.stat_counters()["device_stripe_passes"] >= 1
        # the HBM cache was tagged with the object, and committed
        entry = hbm_cache.get().lookup(pg.cid, oid)
        assert entry is not None and entry.stripes == 5

    def test_stored_shards_and_crcs_equal_the_reference(self, cluster, io):
        io.write_full("obj", payload(1))
        acting, pg = placement(cluster, io, "obj")
        want = ref.stored(payload(1), {
            "pool_profile": PROFILE, "stripe_unit": L})
        assert len(want) == 9 and len(want[0][0]) == 5 * L
        for shard, (data, crc) in enumerate(want):
            store = cluster.osds[acting[shard]].store
            name = f"obj.s{shard}"
            assert bytes(store.read(pg.cid, name)) == data, shard
            hinfo = denc.loads(store.getattr(pg.cid, name, HINFO_KEY))
            assert int(hinfo["crc"]) == crc, shard

    @pytest.mark.parametrize("lost", [(0,), (1, 7), (2, 3, 8)])
    def test_degraded_read_returns_the_bytes(self, cluster, io, lost):
        oid = "deg" + "".join(map(str, lost))
        io.write_full(oid, payload(2))
        hbm_cache.get().clear()     # the read gathers shards
        for shard in lost:
            faults.get().store_eio("osd.*", f"{oid}.s{shard}")
        try:
            assert io.read(oid) == payload(2)
        finally:
            faults.get().reset()
