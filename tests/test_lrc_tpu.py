"""LRC on `plugin=tpu` (ISSUE 34): the layered code as a technique of
the matrix codec (the layers composed to one generator, the plan layer
by layer, the chunk mapping), the OSD path that puts chunk
`inverse_mapping[p]` at acting position p, a rebuild that reads its
plan's l chunks, and the CRUSH rule a profile with `ruleset-locality`
gets.  The plain reference is the benchmark's
(`benchmark/references/lrc.py`: layer by layer, numpy alone)."""

import itertools
import threading
import time

import numpy as np
import pytest

from benchmark.references import lrc as ref
from ceph_tpu.client import RadosError
from ceph_tpu.erasure.interface import ErasureCodeError
from ceph_tpu.erasure.matrix_codec import MatrixErasureCode
from ceph_tpu.erasure.plugin_lrc import ErasureCodeLrc
from ceph_tpu.erasure.plugin_tpu import ErasureCodeTpu
from ceph_tpu.erasure.registry import registry
from ceph_tpu.ops import hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pglog import HINFO_KEY, shard_oid
from ceph_tpu.store import Transaction
from ceph_tpu.utils import denc, faults, optracker
from ceph_tpu.utils.clock import ManualClock
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

RNG = np.random.default_rng(34)
L = 256
K, M, LOC, N = 4, 2, 3, 8
MAPPING = "DD__DD__"
# chunk id -> position, and back (ErasureCode::to_mapping)
AT = [0, 1, 4, 5, 2, 3, 6, 7]
OF = [AT.index(p) for p in range(N)]
KML = {"k": str(K), "m": str(M), "l": str(LOC)}


def tpu_codec():
    return registry.factory("tpu", dict(KML, technique="lrc",
                                        host_cutover="1"))


def config(unit=L):
    return {"pool_profile": dict(KML, technique="lrc"),
            "stripe_unit": unit, "shards": N}


def layered_gives(lost) -> set:
    """The positions a layered decoder has after losing `lost`: a
    layer of the reference's layout rebuilds its chunks when it lacks
    no more of them than it has 'c's, the layers tried from the last,
    again and again (the test's own statement of the rule, over
    `ref.layout`'s strings)."""
    _mapping, whole, local = ref.layout(K, M, LOC)
    known = set(range(N)) - set(lost)
    grew = True
    while grew:
        grew = False
        for layer in reversed([whole] + local):
            member = {p for p, ch in enumerate(layer) if ch != "_"}
            if 0 < len(member - known) <= layer.count("c"):
                known |= member
                grew = True
    return known


def stripes(codec, batch=3):
    """(B, n, L) random data with the codec's own parity, by chunk id."""
    data = RNG.integers(0, 256, (batch, codec.k, L), dtype=np.uint8)
    return np.concatenate([data, codec.encode_batch(data)], axis=1)


# ---------------------------------------------------------------------------
# the codec
# ---------------------------------------------------------------------------


class TestTechnique:
    def test_shape_and_mapping(self):
        codec = tpu_codec()
        assert isinstance(codec, ErasureCodeTpu) and codec.planned
        assert (codec.k, codec.m, codec.get_chunk_count()) == (K, 4, N)
        assert codec.coding_matrix.shape == (4, 4)
        assert codec.get_chunk_mapping() == AT
        assert ecutil.shard_chunks(codec) == OF
        assert ref.layout(K, M, LOC) == (
            MAPPING, "DDc_DDc_", ["DDDc____", "____DDDc"])
        # another code has no mapping, and the OSD asks all the same
        rs = registry.factory("tpu", {"k": "4", "m": "2"})
        assert rs.get_chunk_mapping() == []
        assert ecutil.shard_chunks(rs) == ecutil.chunk_shards(rs) == \
            list(range(6))

    @pytest.mark.parametrize("stripes_n", [1, 3])
    def test_encode_and_crc_equal_the_layered_reference(self, stripes_n):
        codec = tpu_codec()
        payload = RNG.integers(0, 256, K * L * stripes_n - 17,
                               dtype=np.uint8).tobytes()
        before = dict(codec.stat_counters())
        shards, crcs = ecutil.encode_object(
            codec, ecutil.StripeInfo(K, L), payload)
        # position by position, CRC by CRC
        assert [(bytes(s), int(x)) for s, x in zip(shards, crcs)] == \
            ref.stored(payload, config())
        # through the pipeline's encode channel, on the device path
        after = codec.stat_counters()
        assert after["device_stripe_passes"] + after["host_stripe_passes"] \
            == before["device_stripe_passes"] \
            + before["host_stripe_passes"] + 1

    @pytest.mark.parametrize("lost_n", [1, 2, 3, 4])
    def test_every_pattern_the_layers_decode_decodes_bit_exact(
            self, lost_n, monkeypatch):
        codec = tpu_codec()
        allc = stripes(codec)
        shapes = set()
        real = codec._decode_channel

        def spy(want, present, rows, length):
            shapes.add(rows.shape)
            return real(want, present, rows, length)

        monkeypatch.setattr(codec, "_decode_channel", spy)
        decoded = refused = 0
        for lost_pos in itertools.combinations(range(N), lost_n):
            lost = [OF[p] for p in lost_pos]
            avail = [c for c in range(N) if c not in lost]
            if not set(lost_pos) <= layered_gives(lost_pos):
                with pytest.raises(ErasureCodeError):
                    codec.minimum_to_decode(lost, avail)
                refused += 1
                continue
            present = codec.minimum_to_decode(lost, avail)
            out = codec.decode_batch(lost, present, allc[:, present])
            assert np.array_equal(out, allc[:, lost]), lost_pos
            decoded += 1
        assert (decoded, refused) == {1: (8, 0), 2: (28, 0), 3: (54, 2),
                                      4: (33, 37)}[lost_n]
        # a plan of fewer than k chunks rides the (r x k) operand: no
        # executable of its own
        assert shapes == {(lost_n, K)}

    @pytest.mark.parametrize("lost_pos", range(N))
    def test_one_lost_chunk_is_read_from_its_local_group(self, lost_pos):
        codec = tpu_codec()
        lost = OF[lost_pos]
        plan = codec.minimum_to_decode(
            [lost], [c for c in range(N) if c != lost])
        group = range(0, 4) if lost_pos < 4 else range(4, 8)
        assert len(plan) == LOC
        assert sorted(AT[c] for c in plan) == \
            [p for p in group if p != lost_pos]

    def test_a_read_of_the_data_takes_the_fewest_chunks(self):
        codec = tpu_codec()
        # one data chunk lost: the global parity (4), not the local
        # group and the rest (5)
        assert codec.minimum_to_decode(range(K), [1, 2, 3, 4, 5, 6, 7]) \
            == [1, 2, 3, 4]
        assert ecutil.minimum_shards(codec, [1, 2, 3, 4, 5, 6, 7]) == \
            sorted(AT[c] for c in (1, 2, 3, 4))
        # all four in hand: themselves
        assert codec.minimum_to_decode(range(K), range(N)) == [0, 1, 2, 3]
        # three data chunks lost, one group whole: local, then global
        plan = codec.minimum_to_decode(range(K), [3, 4, 6, 7])
        assert plan == [3, 4, 6, 7]
        # what the layers cannot give is refused, whatever the algebra
        with pytest.raises(ErasureCodeError):
            codec.minimum_to_decode(range(K), [2, 3, 5, 6, 7])

    def test_plugin_lrc_is_the_same_code_at_the_same_positions(self):
        host = registry.factory("lrc", KML)
        dev = tpu_codec()
        assert isinstance(host, MatrixErasureCode) and host.planned
        assert np.array_equal(host.coding_matrix, dev.coding_matrix)
        assert host.get_chunk_mapping() == dev.get_chunk_mapping() == AT
        payload = RNG.integers(0, 256, K * L * 2, dtype=np.uint8).tobytes()
        si = ecutil.StripeInfo(K, L)
        a, ca = ecutil.encode_object(host, si, payload)
        b, cb = ecutil.encode_object(dev, si, payload)
        assert [bytes(x) for x in a] == [bytes(x) for x in b]
        assert list(ca) == list(cb)
        # one implementation: the plugin module composes nothing
        from ceph_tpu.erasure import plugin_lrc
        assert not hasattr(ErasureCodeLrc, "_compose_matrix")
        assert not hasattr(plugin_lrc, "_generate_kml")

    def test_explicit_layers_compose_too(self):
        codec = registry.factory("lrc", {
            "mapping": "DD_DD_",
            "layers": '[["DDc___", ""], ["___DDc", ""]]'})
        assert isinstance(codec, MatrixErasureCode)
        assert codec.get_chunk_mapping() == [0, 1, 3, 4, 2, 5]
        assert codec.minimum_to_decode([0], [1, 2, 3, 4, 5]) == [1, 4]
        with pytest.raises(ErasureCodeError):       # both of one layer
            codec.minimum_to_decode([0, 1], [2, 3, 4, 5])

    def test_a_layer_that_is_no_byte_matrix_keeps_the_layered_path(self):
        profile = {
            "mapping": "DD_DD_",
            "layers": '[["DDc___", "technique=cauchy_good packetsize=32"],'
                      ' ["___DDc", ""]]'}
        codec = registry.factory("lrc", profile)
        assert isinstance(codec, ErasureCodeLrc)
        data = RNG.integers(0, 256, 4 * 1024, dtype=np.uint8).tobytes()
        enc = codec.encode(range(6), data)
        for lost in range(6):
            have = {i: enc[i] for i in range(6) if i != lost}
            out = codec.decode([lost], have, len(enc[0]))
            assert np.array_equal(out[lost], enc[lost])
        with pytest.raises(ErasureCodeError):
            registry.factory("tpu", dict(profile, technique="lrc"))

    @pytest.mark.parametrize("profile", [
        {"k": "4", "m": "2", "l": "4"},             # k + m % l
        {"k": "4", "m": "2"},                       # l missing
        {"k": "4", "m": "2", "l": "3", "mapping": "DD__DD__"},
        {"mapping": "DD_", "layers": "[]"},
        {"mapping": "DD__", "layers": '[["DDc_", ""]]'},   # 3 unwritten
    ])
    def test_invalid_profiles_are_refused(self, profile):
        with pytest.raises(ErasureCodeError):
            registry.factory("tpu", dict(profile, technique="lrc"))
        with pytest.raises(ErasureCodeError):
            registry.factory("lrc", profile)

    def test_plans_are_spanned_and_counted_local(self):
        codec = tpu_codec()
        allc = stripes(codec, 1)
        op = optracker.OpTracker(ManualClock()).create("plan probe")

        def decode(lost):
            avail = [c for c in range(N) if c not in lost]
            with optracker.op_context(op):
                present = codec.minimum_to_decode(lost, avail)
                codec.decode_batch(lost, present, allc[:, present])
            return [s["args"] for s in op.dump()["spans"]
                    if s["name"] == "ec.plan"]

        spans = decode([5])                 # a local parity, lost
        assert spans and all(
            (a["local"], a["reads"]) == (1, LOC) for a in spans)
        assert codec.stat_counters()["decode_plans_local"] == 1
        spans = decode([0, 1])[len(spans):]     # the global layer's
        assert spans and all(
            (a["local"], a["reads"]) == (0, K) for a in spans)
        counters = dict(codec.stat_counters())
        assert counters["decode_plans_local"] == 1
        assert counters["decode_plan_misses"] >= 3
        # the patterns again: no search, no span, no miss
        before = len(op.dump()["spans"])
        decode([5]), decode([0, 1])
        assert len(op.dump()["spans"]) == before
        assert dict(codec.stat_counters()) == counters

    def test_decode_object_and_rebuild_shards_by_position(self):
        codec = tpu_codec()
        si = ecutil.StripeInfo(K, L)
        payload = RNG.integers(0, 256, K * L * 5 - 3,
                               dtype=np.uint8).tobytes()
        shards, _crcs = ecutil.encode_object(codec, si, payload)
        # positions 1 (data) and 6 (a global parity) gone
        have = {p: s for p, s in enumerate(shards) if p not in (1, 6)}
        assert ecutil.decode_object(codec, si, have,
                                    len(payload)) == payload
        calls = []
        real = codec.decode_batch_async

        def spy(want, present, stack, qos=None):
            calls.append((tuple(want), tuple(present), stack.shape))
            return real(want, present, stack, qos=qos)

        codec.decode_batch_async = spy
        # position 7, the second local parity, from its group alone
        group = {p: shards[p] for p in (4, 5, 6)}
        out = ecutil.rebuild_shards(codec, si, group, [7], len(payload))
        assert bytes(out[7]) == bytes(shards[7])
        ((want, present, shape),) = calls
        assert (want, sorted(present), shape) == ((7,), [2, 3, 6],
                                                  (5, 3, L))


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

UNIT = 4096
OBJECT_BYTES = K * UNIT * 3 - 100
CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    # a muted OSD stays "up" for as long as a test mutes it
    "osd_heartbeat_grace": 30.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 600.0,
    "osd_op_history_size": 4096,
}
PROFILE = dict(KML, plugin="tpu", technique="lrc", host_cutover="1",
               stripe_unit=str(UNIT))


def payload(i: int) -> bytes:
    return np.random.default_rng(3400 + i).integers(
        0, 256, OBJECT_BYTES, dtype=np.uint8).tobytes()


def settle(cluster, io):
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


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=10, conf=Config(CONF)).start()
    yield c
    faults.get().reset()
    c.stop()


@pytest.fixture(scope="module")
def io(cluster):
    rados = cluster.client()
    rados.create_ec_pool("lrc", "lrc-prof", PROFILE, pg_num=2)
    io = settle(cluster, rados.open_ioctx("lrc"))
    for i in range(6):
        io.write_full(f"obj{i}", payload(i))
    return io


def placement(cluster, io, oid):
    m = cluster.leader().osdmon.osdmap
    pgid = m.object_to_pg(io.pool_id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    return pgid, list(acting), cluster.osds[acting[0]].pgs[pgid]


def stored(cluster, io, oid):
    """[(bytes, stored crc)] of the eight positions."""
    _pgid, acting, pg = placement(cluster, io, oid)
    out = []
    for p in range(N):
        store = cluster.osds[acting[p]].store
        name = shard_oid(oid, p)
        hinfo = denc.loads(store.getattr(pg.cid, name, HINFO_KEY))
        assert hinfo["shard"] == p
        out.append((bytes(store.read(pg.cid, name)), int(hinfo["crc"])))
    return out


def docs_of(cluster, oid, what):
    return [d for osd in cluster.osds.values()
            for d in osd.op_tracker.dump_historic_ops()["ops"]
            if f" {oid} " in d["description"] + " "
            and what in d["description"]]


@pytest.fixture
def cold():
    """Reads gather: nothing served from the HBM cache."""
    hbm_cache.get().clear()
    yield
    faults.get().reset()


class TestServed:
    def test_pool_is_eight_wide(self, cluster, io):
        pool = cluster.leader().osdmon.osdmap.pools[io.pool_id]
        assert (pool.size, pool.min_size) == (N, K + 1)
        _pgid, acting, pg = placement(cluster, io, "obj0")
        assert len(acting) == N and len(set(acting)) == N
        codec = pg._ec_codec()
        assert isinstance(codec, ErasureCodeTpu)
        assert codec.get_chunk_mapping() == AT

    @pytest.mark.parametrize("i", [0, 1, 2])
    def test_stored_files_by_position_equal_the_reference(self, cluster,
                                                          io, i):
        assert stored(cluster, io, f"obj{i}") == \
            ref.stored(payload(i), config(UNIT))
        assert io.read(f"obj{i}") == payload(i)

    def test_write_takes_the_pipeline(self, cluster, io):
        """`encode_object_async` finds the async encode: the write
        shows the `ec.*` spans of a dispatch, with its four rows."""
        before = ec_pipeline.stats()
        end = time.time() + 60
        n = 0
        while True:
            oid = f"piped{n}"
            n += 1
            io.write_full(oid, payload(9))
            spans = [s for d in docs_of(cluster, oid, "writefull")
                     for s in d["spans"] if s["name"] == "ec.device_compute"]
            if spans:
                break
            assert time.time() < end, "no device-served write"
        assert spans[0]["args"] == {"stripes": 3, "padded": 4.0,
                                    "rep": "bytes", "rows": 4}
        after = ec_pipeline.stats()
        assert after["dev_dispatches"] > before["dev_dispatches"]
        assert stored(cluster, io, oid) == ref.stored(payload(9),
                                                      config(UNIT))
        _pgid, _acting, pg = placement(cluster, io, oid)
        dump = cluster.osds[pg.osd.whoami].asok.execute("perf dump")
        (block,) = [c for c in dump["ec_codecs"].values()
                    if "device_stripe_passes" in c
                    and c["device_stripe_passes"] > 0]
        assert block["device_stripe_passes"] >= 1

    @pytest.mark.parametrize("down", [
        (1,), (2,), (4,), (7,), (1, 4), (0, 1), (1, 2), (4, 5), (5, 6),
        (1, 3), (0, 5)])
    def test_degraded_read_with_one_and_two_positions_down(
            self, cluster, io, cold, down):
        """Any one position, or any two, unreadable (position 0 is the
        primary's own): the same bytes."""
        for p in down:
            faults.get().store_eio("osd.*", f"obj3.s{p}")
        assert io.read("obj3") == payload(3)
        (doc,) = [max(docs_of(cluster, "obj3", "'read'"),
                      key=lambda d: d["mstart"])]
        args = [s["args"] for s in doc["spans"]
                if s["name"] == "gather_wait"][-1]
        assert not set(args["chunks"]) & set(down)
        # the decode was handed a set the layers decode the data from
        data = {0, 1, 4, 5}
        assert data <= layered_gives(set(range(N)) - set(args["chunks"]))

    def test_undecodable_gather_answers_eio(self, cluster, io, cold):
        for p in (0, 1, 2):           # both data and the global parity
            faults.get().store_eio("osd.*", f"obj4.s{p}")
        with pytest.raises(RadosError) as e:
            io.read("obj4")
        assert e.value.errno == 5
        faults.get().reset()
        assert io.read("obj4") == payload(4)

    def test_osds_down_reads_degraded(self, cluster, io, cold):
        """Two OSDs muted, not yet marked down: every object reads.
        A read whose plan names a muted one waits that sub-read's
        window before it widens, so the six go at once."""
        primaries = {placement(cluster, io, f"obj{i}")[1][0]
                     for i in range(6)}
        _pgid, acting, _pg = placement(cluster, io, "obj5")
        victims = [o for o in acting[1:] if o not in primaries][:2]
        for v in victims:
            faults.get().drop(f"osd.{v}", 1.0)
        out: dict = {}

        def one(i: int) -> None:
            try:
                out[i] = io.read(f"obj{i}")
            except RadosError as e:
                out[i] = e

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert [i for i in range(6) if out.get(i) != payload(i)] == []

    @pytest.mark.parametrize("lost", [5, 2, 7])
    def test_recovery_rebuilds_one_shard_from_its_group(self, cluster, io,
                                                        cold, lost):
        """A lost position of the second group: three sub-reads, its
        group's, and the file and CRC the reference has."""
        oid = "obj2"
        pgid, acting, pg = placement(cluster, io, oid)
        if lost < 4:
            # the primary holds position 0 itself: two sub-reads
            want_asked = {p for p in range(4) if p not in (0, lost)}
        else:
            want_asked = {p for p in range(4, 8) if p != lost}
        holder = cluster.osds[acting[lost]]
        holder.store.apply_transaction(
            Transaction().remove(pg.cid, shard_oid(oid, lost)))
        primary = cluster.osds[acting[0]]
        asked = []
        real = primary.ec_fetch_shards

        def spy(pgid_, oid_, targets, **kw):
            asked.append(sorted(s for s, _o in targets))
            return real(pgid_, oid_, targets, **kw)

        primary.ec_fetch_shards = spy
        try:
            version = tuple(pg.pglog.objects[oid])
            assert primary._ec_rebuild(pgid, oid, version,
                                       [(lost, acting[lost])],
                                       retry=False)
        finally:
            del primary.ec_fetch_shards
        assert asked == [sorted(want_asked)]
        end = time.time() + 30
        while not holder.store.exists(pg.cid, shard_oid(oid, lost)):
            assert time.time() < end, "the push never landed"
            time.sleep(0.05)
        assert stored(cluster, io, oid) == ref.stored(payload(2),
                                                      config(UNIT))

    def test_deep_scrub_is_clean_from_the_cache_and_from_the_store(
            self, cluster, io):
        end = time.time() + 60
        while True:                 # until the device kept the stripes
            io.write_full("scrubbed", payload(7))
            _pgid, _acting, pg = placement(cluster, io, "scrubbed")
            cur = tuple(pg.pglog.objects["scrubbed"])
            if hbm_cache.get().lookup(pg.cid, "scrubbed",
                                      version=cur) is not None:
                break
            assert time.time() < end, "no cached write"
        hits = hbm_cache.stats()["hit"]
        warm = pg.scrub(deep=True)
        # the entry's chunk CRCs were held to the right positions
        assert warm["inconsistent"] == [], warm
        assert hbm_cache.stats()["hit"] > hits
        hbm_cache.get().clear()
        cold_result = pg.scrub(deep=True)
        assert cold_result["inconsistent"] == [], cold_result
        assert cold_result["checked"] == warm["checked"] > 0

    def test_recovery_from_the_cache_lands_the_mapped_shard(self, cluster,
                                                            io):
        """The HBM entry keeps chunks; the shard pushed for position 6
        is chunk 6's, not chunk 6 of the entry's order."""
        end = time.time() + 60
        while True:
            io.write_full("cached", payload(6))
            pgid, acting, pg = placement(cluster, io, "cached")
            cur = tuple(pg.pglog.objects["cached"])
            if hbm_cache.get().lookup(pg.cid, "cached",
                                      version=cur) is not None:
                break
            assert time.time() < end, "no cached write"
        primary = cluster.osds[acting[0]]
        for lost in (2, 6):
            assert primary._ec_push_shards(pg, "cached", cur,
                                           [(lost, acting[lost])], None)
        time.sleep(0.3)
        assert stored(cluster, io, "cached") == ref.stored(payload(6),
                                                           config(UNIT))

    @pytest.mark.parametrize("pos", [2, 4, 7])
    def test_a_corrupted_mapped_shard_is_flagged_by_its_position(
            self, cluster, io, pos):
        oid = f"rot{pos}"
        io.write_full(oid, payload(8))
        _pgid, acting, pg = placement(cluster, io, oid)
        cluster.osds[acting[pos]].store.apply_transaction(
            Transaction().write(pg.cid, shard_oid(oid, pos), 5,
                                b"\xff\x00\xff"))
        bad = [i for i in pg.scrub(deep=True)["inconsistent"]
               if i["object"].startswith(oid + ".")]
        assert bad == [{"object": shard_oid(oid, pos),
                        "osd": acting[pos]}]
        result = pg.scrub(deep=True, repair=True)
        assert result["repaired"] >= 1 and result["clean_after_repair"]
        assert stored(cluster, io, oid) == ref.stored(payload(8),
                                                      config(UNIT))

    def test_append_keeps_the_mapped_layout(self, cluster, io):
        head = payload(10)[:K * UNIT + 700]
        tail = payload(11)[:K * UNIT + 33]
        io.write_full("grown", head)
        io.append("grown", tail)
        assert io.read("grown") == head + tail
        assert stored(cluster, io, "grown") == \
            ref.stored(head + tail, config(UNIT))
        _pgid, _acting, pg = placement(cluster, io, "grown")
        assert pg.scrub(deep=True)["inconsistent"] == []


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


def two_racks(osds: int):
    from ceph_tpu.crush.map import BUCKET_STRAW2, CrushMap, Rule, Step, \
        STEP_CHOOSELEAF_FIRSTN, STEP_EMIT, STEP_TAKE
    m = CrushMap()
    w = 0x10000
    root = m.new_bucket(BUCKET_STRAW2, 4, name="default")
    per = osds // 2
    for r in range(2):
        rack = m.new_bucket(BUCKET_STRAW2, 2, name=f"rack{r}")
        for o in range(r * per, (r + 1) * per):
            host = m.new_bucket(BUCKET_STRAW2, 1, name=f"host{o}")
            m.add_device(o)
            host.add_item(o, w)
            rack.add_item(host.id, host.weight)
        root.add_item(rack.id, rack.weight)
    m.add_rule(Rule("replicated_rule", [
        Step(STEP_TAKE, root.id), Step(STEP_CHOOSELEAF_FIRSTN, 0, 1),
        Step(STEP_EMIT)]))
    return m


class TestRule:
    @pytest.mark.parametrize("domain", ["host", "osd"])
    def test_locality_rule_puts_each_group_in_a_rack(self, domain):
        from ceph_tpu.crush import do_rule
        m = two_racks(10)
        rid = m.make_locality_rule("lrc", K, 4, LOC + 1, "rack", domain)
        steps = [(s.op, s.arg1, s.arg2) for s in m.rules[rid].steps]
        assert steps[2:4] == [("choose_indep", 2, 2),
                              ("chooseleaf_indep", 4, 1 if domain == "host"
                               else 0)]
        for x in range(64):
            got = do_rule(m, rid, x, N)
            assert len(set(got)) == N, got
            racks = [o // 5 for o in got]
            assert len(set(racks[:4])) == 1 and len(set(racks[4:])) == 1
            assert racks[0] != racks[4]
        with pytest.raises(ValueError):
            m.make_locality_rule("bad", K, 4, 4, "shelf")
        with pytest.raises(ValueError):
            m.make_locality_rule("bad", K, 4, 3, "rack")

    def test_a_profile_with_ruleset_locality_gets_the_rule(self):
        """On a two-rack map built here: positions 0-3 of every PG in
        one rack, 4-7 in the other; without the key, the flat rule."""
        c = MiniCluster(num_mons=1, num_osds=10, conf=Config(CONF)).start()
        try:
            osdmon = c.leader().osdmon
            osdmon._pending().new_crush = denc.dumps(two_racks(10))
            osdmon.propose_pending()
            end = time.time() + 30
            while c.leader().osdmon.osdmap.crush.bucket_by_name(
                    "rack1") is None:
                assert time.time() < end
                c.tick(0.3)
            rados = c.client()
            rados.create_ec_pool("local", "local-prof", dict(PROFILE, **{
                "ruleset-locality": "rack",
                "ruleset-failure-domain": "host"}), pg_num=8)
            rados.create_ec_pool("flat", "flat-prof", PROFILE, pg_num=8)
            with pytest.raises(RadosError):
                rados.create_ec_pool("bad", "bad-prof", dict(
                    PROFILE, **{"ruleset-locality": "shelf"}), pg_num=8)
            m = c.leader().osdmon.osdmap
            local, flat = (m.pool_by_name(n) for n in ("local", "flat"))
            rules = m.crush.rules
            assert [s.op for s in rules[local.crush_ruleset].steps][2:4] \
                == ["choose_indep", "chooseleaf_indep"]
            assert [s.op for s in rules[flat.crush_ruleset].steps][2:3] \
                == ["choose_indep"]
            mixed = 0
            for pgid in m.all_pgs():
                _up, acting = m.pg_to_up_acting_osds(pgid)
                racks = [o // 5 for o in acting]
                if pgid.pool == local.id:
                    assert len(acting) == N and len(set(acting)) == N
                    assert len(set(racks[:4])) == 1, acting
                    assert len(set(racks[4:])) == 1, acting
                    assert racks[0] != racks[4], acting
                elif pgid.pool == flat.id:
                    mixed += len(set(racks[:4])) > 1
            assert mixed >= 1       # the flat rule knows no rack
            # and the pool serves
            io = settle(c, rados.open_ioctx("local"))
            io.write_full("o", payload(0))
            assert io.read("o") == payload(0)
        finally:
            c.stop()
