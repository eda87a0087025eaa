"""SHEC on `plugin=tpu` (ISSUE 28): the shingled code as a technique of
the matrix codec, its planned decode rows on the batched decode path,
and the read gather that completes on a decodable set and holds no op
worker.  The plain reference is the benchmark's
(`benchmark/references/shec.py`, numpy alone)."""

import itertools
import threading
import time

import numpy as np
import pytest

from benchmark.references import shec as ref
from ceph_tpu.client import RadosError
from ceph_tpu.erasure.interface import ErasureCodeError
from ceph_tpu.erasure.matrix_codec import MatrixErasureCode
from ceph_tpu.erasure.plugin_tpu import ErasureCodeTpu
from ceph_tpu.erasure.registry import registry
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pglog import VER_KEY, shard_oid
from ceph_tpu.store import Transaction
from ceph_tpu.utils import faults, optracker
from ceph_tpu.utils.clock import ManualClock
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

RNG = np.random.default_rng(28)
L = 256


def tpu_codec(k, m, c, technique="shec_multiple"):
    return registry.factory("tpu", {
        "technique": technique, "k": str(k), "m": str(m), "c": str(c),
        "host_cutover": "1"})


def stripes(codec, batch=3):
    """(B, k+m, L) random data with the codec's own parity."""
    data = RNG.integers(0, 256, (batch, codec.k, L), dtype=np.uint8)
    return np.concatenate([data, codec.encode_batch(data)], axis=1)


# ---------------------------------------------------------------------------
# the codec
# ---------------------------------------------------------------------------


class TestTechnique:
    @pytest.mark.parametrize("k,m,c", [(8, 4, 3), (4, 3, 2)])
    def test_parity_equals_the_reference(self, k, m, c):
        codec = tpu_codec(k, m, c)
        assert isinstance(codec, ErasureCodeTpu) and codec.planned
        assert np.array_equal(codec.coding_matrix,
                              ref.coding_matrix(k, m, c))
        payload = RNG.integers(0, 256, k * L * 2, dtype=np.uint8).tobytes()
        shards, crcs = ecutil.encode_object(
            codec, ecutil.StripeInfo(k, L), payload)
        want = ref.stored(payload, {
            "pool_profile": {"technique": "shec_multiple", "k": k, "m": m,
                             "c": c}, "stripe_unit": L})
        assert [(bytes(s), int(x)) for s, x in zip(shards, crcs)] == want

    def test_supports_of_8_4_3(self):
        codec = tpu_codec(8, 4, 3)
        assert ["".join("1" if x else "0" for x in row)
                for row in codec.coding_matrix] == [
            "11110000", "00001111", "11111111", "11111111"]

    @pytest.mark.parametrize("k,m,c", [(8, 4, 3), (4, 3, 2)])
    def test_every_pattern_up_to_c_decodes_on_an_r_by_k_operand(
            self, k, m, c, monkeypatch):
        codec = tpu_codec(k, m, c)
        allc = stripes(codec)
        shapes = set()
        real = codec._decode_channel

        def spy(want, present, rows, length):
            shapes.add((len(want), rows.shape))
            return real(want, present, rows, length)

        monkeypatch.setattr(codec, "_decode_channel", spy)
        n = k + m
        for r in range(1, c + 1):
            for lost in itertools.combinations(range(n), r):
                avail = [i for i in range(n) if i not in lost]
                present = codec.minimum_to_decode(lost, avail)
                out = codec.decode_batch(list(lost), present,
                                         allc[:, present])
                assert np.array_equal(out, allc[:, list(lost)]), lost
        # short plans too ride the k-wide operand: one executable a
        # row count, whatever the plan reads
        assert shapes == {(r, (r, k)) for r in range(1, c + 1)}

    def test_undecodable_sets_are_refused_as_the_reference_refuses(self):
        codec = tpu_codec(8, 4, 3)
        matrix = ref.coding_matrix(8, 4, 3)
        refused = 0
        for have in itertools.combinations(range(12), 8):
            accepts = ref.plan(range(8), have, matrix) is not None
            try:
                codec.minimum_to_decode(range(8), have)
                assert accepts, have
            except ErasureCodeError:
                assert not accepts, have
                refused += 1
        assert refused == 70        # of 495: the code is not MDS

    def test_a_plan_of_four_chunks_rebuilds_one_data_chunk(self):
        codec = tpu_codec(8, 4, 3)
        allc = stripes(codec)
        present = codec.minimum_to_decode([0], range(1, 12))
        assert present == [1, 2, 3, 8]      # neighbours + local parity
        out = codec.decode_batch([0], present, allc[:, present])
        assert np.array_equal(out[:, 0], allc[:, 0])
        # the reference rebuilds the same chunk from the same four
        shards = {i: allc[0, i] for i in present}
        matrix = ref.coding_matrix(8, 4, 3)
        assert ref.plan([0], shards, matrix) == ([0], [0])

    def test_plans_are_cached_by_pattern_counted_and_spanned(self):
        codec = tpu_codec(8, 4, 3)
        allc = stripes(codec, 1)
        lost, avail = [1, 9], [i for i in range(12) if i not in (1, 9)]
        op = optracker.OpTracker(ManualClock()).create("plan probe")

        def decode():
            with optracker.op_context(op):
                present = codec.minimum_to_decode(lost, avail)
                codec.decode_batch(lost, present, allc[:, present])
            return [s for s in op.dump()["spans"] if s["name"] == "ec.plan"]

        spans = decode()
        assert spans and {tuple(s["args"]["want"]) for s in spans} == {(1, 9)}
        counters = dict(codec.stat_counters())
        assert counters["decode_plan_misses"] >= 1
        assert counters["decode_plans"] >= 1
        # the pattern again: no search, no span, no miss
        assert len(decode()) == len(spans)
        assert codec.stat_counters()["decode_plan_misses"] == \
            counters["decode_plan_misses"]

    def test_plugin_shec_is_the_same_code(self):
        host = registry.factory("shec", {"k": "8", "m": "4", "c": "3"})
        assert isinstance(host, MatrixErasureCode) and host.planned
        assert np.array_equal(host.coding_matrix,
                              tpu_codec(8, 4, 3).coding_matrix)
        single = registry.factory("shec", {"k": "6", "m": "3", "c": "2",
                                           "technique": "single"})
        assert np.array_equal(
            single.coding_matrix,
            tpu_codec(6, 3, 2, "shec_single").coding_matrix)
        # one implementation: the plugin module holds no solver
        from ceph_tpu.erasure import plugin_shec
        assert not hasattr(plugin_shec, "_gf_solve")
        assert not hasattr(plugin_shec, "shec_matrix")

    @pytest.mark.parametrize("profile", [
        {"k": "2", "m": "4", "c": "1"},         # m > k
        {"k": "8", "m": "4", "c": "5"},         # c > m
        {"k": "8", "m": "4", "c": "0"},
    ])
    def test_invalid_profiles_are_refused(self, profile):
        with pytest.raises(ErasureCodeError):
            registry.factory("tpu", dict(profile,
                                         technique="shec_multiple"))

    def test_decode_object_takes_the_batched_path(self):
        """No per-stripe host loop for the tpu plugin's shec."""
        codec = tpu_codec(8, 4, 3)
        si = ecutil.StripeInfo(8, L)
        payload = RNG.integers(0, 256, 8 * L * 5, dtype=np.uint8).tobytes()
        shards, _crcs = ecutil.encode_object(codec, si, payload)
        calls = []
        real = codec.decode_batch_async

        def spy(want, present, stack, qos=None):
            calls.append((tuple(want), tuple(present), stack.shape))
            return real(want, present, stack, qos=qos)

        codec.decode_batch_async = spy
        have = {i: s for i, s in enumerate(shards) if i not in (2, 6, 9)}
        assert ecutil.decode_object(codec, si, have,
                                    len(payload)) == payload
        ((want, _present, shape),) = calls
        assert want == (2, 6) and shape[0] == 5


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

OBJECT_BYTES = 8 * 4096 * 2
CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    # a muted OSD stays "up" for as long as a test mutes it
    "osd_heartbeat_grace": 30.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 600.0,
    "osd_op_history_size": 4096,
    # ONE op worker a daemon: a gather that held it would starve the
    # sub-reads of every other read
    "osd_op_num_shards": 1,
}


def payload(i: int) -> bytes:
    return np.random.default_rng(1000 + i).integers(
        0, 256, OBJECT_BYTES, dtype=np.uint8).tobytes()


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=13, conf=Config(CONF)).start()
    yield c
    faults.get().reset()
    c.stop()


@pytest.fixture(scope="module")
def io(cluster):
    rados = cluster.client()
    rados.create_ec_pool("shec", "shec-prof", {
        "plugin": "tpu", "technique": "shec_multiple", "k": 8, "m": 4,
        "c": 3, "host_cutover": 1, "stripe_unit": 4096}, pg_num=2)
    io = rados.open_ioctx("shec")
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
    for i in range(8):
        io.write_full(f"obj{i}", payload(i))
    return io


def placement(cluster, io, oid):
    m = cluster.leader().osdmon.osdmap
    pgid = m.object_to_pg(io.pool_id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    return pgid, list(acting), cluster.osds[acting[0]].pgs[pgid]


def read_doc(cluster, oid):
    docs = [d for osd in cluster.osds.values()
            for d in osd.op_tracker.dump_historic_ops()["ops"]
            if d["kind"] == "client" and f" {oid} " in d["description"]
            and "'read'" in d["description"]]
    return max(docs, key=lambda d: d["mstart"])


def gather_spans(doc):
    return [s for s in doc["spans"] if s["name"] == "gather_wait"]


@pytest.fixture
def cold():
    """Reads gather: nothing served from the HBM cache."""
    from ceph_tpu.ops import hbm_cache
    hbm_cache.get().clear()
    yield
    faults.get().reset()


class TestGather:
    def test_read_carries_the_gather_span(self, cluster, io, cold):
        assert io.read("obj0") == payload(0)
        doc = read_doc(cluster, "obj0")
        (span,) = gather_spans(doc)
        args = span["args"]
        # the plan of a healthy read: the eight data chunks, one of
        # them the primary's own, and nothing to decode
        assert args["widened"] == 0 and args["replans"] == 0
        assert args["asked"] == args["used"] == 7
        assert args["chunks"] == list(range(8))
        names = [s["name"] for s in doc["spans"]]
        assert names.count("execute") == 2 and names.count("queue") == 2
        assert "ec.plan" not in names

    def test_waits_for_a_set_that_decodes(self, cluster, io, cold):
        """The primary's own planned shard is unreadable, so the read
        starts widened, over the eleven others.  The first eight
        arrivals do not decode: chunks 1-3 come late, and {4-11}
        loses a whole shingle group."""
        _pgid, acting, _pg = placement(cluster, io, "obj1")
        faults.get().store_eio("osd.*", "obj1.s0")
        for shard in (1, 2, 3):
            faults.get().delay(f"osd.{acting[0]}", 0.8,
                               src=f"osd.{acting[shard]}")
        assert io.read("obj1") == payload(1)    # never ENOENT
        (args,) = [s["args"] for s in
                   gather_spans(read_doc(cluster, "obj1"))]
        assert args["widened"] == 1 and args["asked"] == 11
        assert args["replans"] >= 1
        assert set(args["chunks"]) & {1, 2, 3} and 0 not in args["chunks"]
        assert ref.plan(range(8), args["chunks"],
                        ref.coding_matrix(8, 4, 3)) is not None

    def test_concurrent_degraded_reads_hold_no_worker(self, cluster, io,
                                                      cold):
        """Two OSDs dead and not yet marked down, one op worker a
        daemon, sixteen reads at once: all complete."""
        _pgid, acting, _pg = placement(cluster, io, "obj2")
        primaries = {placement(cluster, io, f"obj{i}")[1][0]
                     for i in range(8)}
        victims = [o for o in acting[1:] if o not in primaries][:2]
        for v in victims:
            faults.get().drop(f"osd.{v}", 1.0)      # mute, not down
        out: dict = {}

        def one(n: int) -> None:
            try:
                out[n] = io.read(f"obj{n % 8}")
            except RadosError as e:
                out[n] = e

        threads = [threading.Thread(target=one, args=(n,))
                   for n in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert [n for n in range(16)
                if out.get(n) != payload(n % 8)] == []

    def test_rebuild_read_honours_need_ver_and_mixed_versions(
            self, cluster, io, cold):
        _pgid, acting, pg = placement(cluster, io, "obj3")
        cur = tuple(pg.pglog.objects["obj3"])
        assert bytes(pg._ec_read_local("obj3", need_ver=cur)) == payload(3)
        newer = (cur[0], cur[1] + 1)
        assert pg._ec_read_local("obj3", need_ver=newer) is None
        # the primary's own shard claims a newer generation than the
        # peers': one decode must not mix them
        store = cluster.osds[acting[0]].store
        store.apply_transaction(Transaction().setattr(
            pg.cid, shard_oid("obj3", 0), VER_KEY, repr(newer).encode()))
        try:
            assert pg._ec_read_local("obj3", need_ver=cur) is None
        finally:
            store.apply_transaction(Transaction().setattr(
                pg.cid, shard_oid("obj3", 0), VER_KEY, repr(cur).encode()))
        assert bytes(pg._ec_read_local("obj3", need_ver=cur)) == payload(3)

    def test_undecodable_gather_answers_eio(self, cluster, io, cold):
        """Four chunks of one shingle group unreadable: no set of the
        rest decodes, and the object is in the log: EIO, not ENOENT."""
        for shard in (4, 5, 6, 7):
            faults.get().store_eio("osd.*", f"obj4.s{shard}")
        with pytest.raises(RadosError) as e:
            io.read("obj4")
        assert e.value.errno == 5
        faults.get().reset()
        assert io.read("obj4") == payload(4)
        with pytest.raises(RadosError) as e:
            io.read("no-such-object")
        assert e.value.errno == 2


# ---------------------------------------------------------------------------
# the store read under the gather
# ---------------------------------------------------------------------------


class TestBlockstoreRunReads:
    """A shard file is read in runs of blocks that lie one behind the
    other on the device, each block still held to its own checksum."""

    @pytest.fixture
    def store(self, tmp_path):
        from ceph_tpu.store.blockstore import BlockStore
        st = BlockStore(str(tmp_path / "bs"))
        st.mkfs()
        st.mount()
        st.apply_transaction(Transaction().create_collection("c"))
        preads = []
        real = st.dev.pread
        st.dev.pread = lambda off, n: (preads.append(n), real(off, n))[1]
        into = st.dev.pread_into      # whole blocks land in the answer
        st.dev.pread_into = lambda off, buf: (preads.append(len(buf)),
                                              into(off, buf))[1]
        yield st, preads
        st.umount()

    def test_a_shard_file_is_one_device_read(self, store):
        st, preads = store
        data = RNG.integers(0, 256, 512 * 1024, dtype=np.uint8).tobytes()
        st.apply_transaction(Transaction().write("c", "shard", 0, data))
        assert st.read("c", "shard") == data
        assert preads == [512 * 1024]
        del preads[:]
        assert st.read("c", "shard", 5000, 10000) == data[5000:15000]
        assert preads == [3 * 4096]

    def test_fragments_and_holes_read_right(self, store):
        st, preads = store
        a = RNG.integers(0, 256, 6 * 4096, dtype=np.uint8).tobytes()
        st.apply_transaction(Transaction().write("c", "a", 0, a))
        st.apply_transaction(Transaction().write("c", "b", 0, a))
        # rewriting two blocks in the middle moves them elsewhere
        # (copy-on-write); a write past a gap leaves a hole
        patch = bytes(range(256)) * 32
        st.apply_transaction(Transaction().write("c", "a", 2 * 4096, patch))
        st.apply_transaction(Transaction().write("c", "a", 9 * 4096, b"tail"))
        want = bytearray(a) + bytes(3 * 4096) + b"tail"
        want[2 * 4096: 4 * 4096] = patch
        del preads[:]
        assert st.read("c", "a") == bytes(want)
        assert len(preads) > 1 and sum(preads) == 7 * 4096

    def test_one_bad_block_in_a_run_is_eio(self, store):
        from ceph_tpu.store.objectstore import StoreError
        st, _preads = store
        data = RNG.integers(0, 256, 16 * 4096, dtype=np.uint8).tobytes()
        st.apply_transaction(Transaction().write("c", "o", 0, data))
        head = st._committed_onode("c", "o")
        (_blk, _n, first, _csums), = head["runs"]
        poff = first + 5 * 4096
        st.dev.pwrite(poff + 100, b"\xff\x00\xff")
        with pytest.raises(StoreError) as e:
            st.read("c", "o")
        assert e.value.errno == 5 and "block 5" in str(e.value)
        assert st.read("c", "o", 0, 5 * 4096) == data[:5 * 4096]
