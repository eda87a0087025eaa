"""Stripe math + batched object encode/decode (osd/ecutil.py).

Mirrors the reference's ECUtil tests: stripe_info_t offset algebra,
encode/decode roundtrips across stripes, HashInfo-style cumulative CRC
equality, and the fused-device-pass counter the OSD path asserts.
"""

import functools

import numpy as np
import pytest

from ceph_tpu.erasure.registry import registry
from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.osd import ecutil


def tpu_codec(k=4, m=2, su=None):
    codec = registry.factory("tpu", {"k": str(k), "m": str(m),
                                     "technique": "reed_sol_van"})
    return codec


class TestStripeInfo:
    def test_offsets(self):
        si = ecutil.StripeInfo(4, 4096)
        assert si.stripe_width == 16384
        assert si.logical_to_prev_stripe_offset(20000) == 16384
        assert si.logical_to_next_stripe_offset(20000) == 32768
        assert si.aligned_logical_offset_to_chunk_offset(32768) == 8192
        assert si.aligned_chunk_offset_to_logical_offset(8192) == 32768
        assert si.offset_len_to_stripe_bounds(5000, 20000) == (0, 32768)

    def test_sizes(self):
        si = ecutil.StripeInfo(2, 4096)
        assert si.stripe_count(0) == 1
        assert si.stripe_count(1) == 1
        assert si.stripe_count(8192) == 1
        assert si.stripe_count(8193) == 2
        assert si.logical_size_to_shard_size(8193) == 8192

    def test_alignment_rounds_up(self):
        si = ecutil.StripeInfo(3, 100)     # not a multiple of 128
        assert si.chunk_size == 128


class TestEncodeDecodeObject:
    @pytest.mark.parametrize("size", [0, 1, 4095, 4096, 10000, 40000])
    def test_roundtrip_all_shards(self, size):
        codec = tpu_codec()
        si = ecutil.StripeInfo(codec.get_data_chunk_count(), 4096)
        payload = bytes(np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8))
        shards, crcs = ecutil.encode_object(codec, si, payload)
        assert len(shards) == 6
        assert all(len(s) == si.logical_size_to_shard_size(size)
                   for s in shards)
        have = {i: shards[i] for i in range(6)}
        assert ecutil.decode_object(codec, si, have, size) == payload

    def test_roundtrip_with_erasures(self):
        codec = tpu_codec()
        si = ecutil.StripeInfo(4, 4096)
        payload = bytes(range(256)) * 150          # 38400 B, 3 stripes
        shards, _ = ecutil.encode_object(codec, si, payload)
        # lose two data shards: parity must rebuild them, batched
        have = {i: shards[i] for i in (0, 3, 4, 5)}
        assert ecutil.decode_object(codec, si, have, len(payload)) == payload
        # lose one data + one parity
        have = {i: shards[i] for i in (0, 1, 3, 4)}
        assert ecutil.decode_object(codec, si, have, len(payload)) == payload

    def test_too_few_shards_raises(self):
        codec = tpu_codec()
        si = ecutil.StripeInfo(4, 4096)
        shards, _ = ecutil.encode_object(codec, si, b"x" * 9999)
        from ceph_tpu.erasure.interface import ErasureCodeError
        with pytest.raises(ErasureCodeError):
            ecutil.decode_object(codec, si,
                                 {i: shards[i] for i in (0, 1, 2)}, 9999)

    def test_shard_crcs_match_direct_crc(self):
        """Cumulative combine == crc32c of the whole shard file —
        HashInfo::append equivalence across stripes."""
        codec = tpu_codec()
        si = ecutil.StripeInfo(4, 4096)
        payload = bytes(np.random.default_rng(7).integers(
            0, 256, 50000, dtype=np.uint8))
        shards, crcs = ecutil.encode_object(codec, si, payload)
        for s, crc in zip(shards, crcs):
            assert crc_mod.crc32c(0, s) == crc

    def test_packets_technique_roundtrip(self):
        """Bit-matrix (packets) techniques must batch across stripes
        too — regression: 3-D batches crashed the host packet kernel."""
        codec = registry.factory("tpu", {"k": "4", "m": "2",
                                         "technique": "cauchy_good",
                                         "packetsize": "128"})
        si = ecutil.StripeInfo(4, codec.get_alignment() // 4)
        payload = bytes(np.random.default_rng(11).integers(
            0, 256, 3 * si.stripe_width + 17, dtype=np.uint8))
        shards, crcs = ecutil.encode_object(codec, si, payload)
        for s, crc in zip(shards, crcs):
            assert crc_mod.crc32c(0, s) == crc
        have = {i: shards[i] for i in (1, 2, 3, 5)}
        assert ecutil.decode_object(codec, si, have,
                                    len(payload)) == payload

    def test_host_plugin_fallback(self):
        """Non-matrix codecs use the base per-stripe host path."""
        codec = registry.factory("shec", {"k": "4", "m": "3", "c": "2"})
        si = ecutil.StripeInfo(4, 512)
        payload = b"shingled" * 700
        shards, crcs = ecutil.encode_object(codec, si, payload)
        assert codec.stat_counters()["host_stripe_passes"] >= 1
        have = {i: s for i, s in enumerate(shards) if i not in (1, 5)}
        assert ecutil.decode_object(codec, si, have,
                                    len(payload)) == payload
        for s, crc in zip(shards, crcs):
            assert crc_mod.crc32c(0, s) == crc


class TestDevicePassCounter:
    def test_fused_device_pass_counts(self):
        """With routing pinned to the device, the fused pass must
        engage (after background warm) and be bit-identical to host."""
        codec = tpu_codec()
        codec.backend.HOST_CUTOVER_BYTES = 1   # pin: CPU CI would
        si = ecutil.StripeInfo(4, 4096)        # rightly prefer host
        payload = bytes(np.random.default_rng(3).integers(
            0, 256, 256 * 1024, dtype=np.uint8))
        ref_shards, ref_crcs = None, None
        # kernels warm on a background thread (an OSD op never blocks
        # on a jit compile), so poll until the device path engages
        import time
        deadline = time.time() + 60
        while time.time() < deadline:
            shards, crcs = ecutil.encode_object(codec, si, payload)
            if ref_shards is None:
                ref_shards, ref_crcs = shards, crcs
            assert shards == ref_shards
            assert list(crcs) == list(ref_crcs)
            if codec.stat_counters()["device_stripe_passes"] >= 1:
                break
            time.sleep(0.05)
        stats = codec.stat_counters()
        assert stats["device_stripe_passes"] >= 1, stats
        assert stats["host_stripe_passes"] >= 1, stats

    def test_adaptive_router_prefers_faster_path(self):
        """Unpinned, both paths get sampled and the steady-state choice
        is whichever measured faster (on CPU CI that is host)."""
        codec = tpu_codec()
        si = ecutil.StripeInfo(4, 4096)
        payload = b"r" * (128 * 1024)
        import time
        deadline = time.time() + 60
        b = codec.backend
        # the routed decision compares EMAs within ONE size bucket —
        # read the payload's own bucket (multichip splits record
        # per-chip part samples into smaller buckets too)
        bkt = b._bucket(128 * 1024)
        while time.time() < deadline:
            ecutil.encode_object(codec, si, payload)
            dev = b._perf.get(("dev", bkt))
            host = b._perf.get(("host", bkt))
            if dev and host and dev["n"] >= 2 and host["n"] >= 2:
                break
            time.sleep(0.02)
        dev = b._perf.get(("dev", bkt))
        host = b._perf.get(("host", bkt))
        assert dev and host and dev["n"] >= 2 and host["n"] >= 2
        faster = "dev" if dev["spb"] <= host["spb"] else "host"
        # routed calls must follow the winner (majority: one in
        # PROBE_EVERY calls deliberately re-probes the loser)
        choices = [b.use_device(128 * 1024) for _ in range(5)]
        assert (sum(choices) >= 3) == (faster == "dev")


class TestStoredHinfo:
    """What a shard file's `hinfo` holds is the scalar chain's value,
    byte for byte: the CRC of the file as it lies in the store and of
    its full-stripe prefix, after a full write and after an append."""

    @pytest.fixture(scope="class")
    def cluster(self):
        from ceph_tpu.vstart import MiniCluster
        c = MiniCluster(num_mons=1, num_osds=3).start()
        yield c
        c.stop()

    @staticmethod
    def _hinfo_by_bytes(cluster, io, oid, size):
        from ceph_tpu.osd.pg import HINFO_KEY, shard_oid
        from ceph_tpu.utils import denc
        m = cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(io.pool_id, oid)
        _up, acting = m.pg_to_up_acting_osds(pgid)
        pg = cluster.osds[acting[0]].pgs[pgid]
        sinfo = pg._ec_sinfo(pg._ec_codec())
        prefix = size // sinfo.stripe_width * sinfo.chunk_size
        for shard, osd_id in enumerate(acting):
            store = cluster.osds[osd_id].store
            name = shard_oid(oid, shard)
            data = bytes(store.read(pg.cid, name))
            assert len(data) == sinfo.logical_size_to_shard_size(size)
            yield store.getattr(pg.cid, name, HINFO_KEY), denc.dumps(
                {"size": size, "crc": crc_mod.crc32c(0, data),
                 "crc_prefix": crc_mod.crc32c(0, data[:prefix]),
                 "shard": shard, "stripe_unit": sinfo.chunk_size})

    def test_hinfo_bytes_after_write_and_append(self, cluster):
        import time
        from ceph_tpu.client import RadosError
        rados = cluster.client()
        rados.create_ec_pool("hinfo-ec", "k2m1hi",
                             {"plugin": "tpu", "k": 2, "m": 1,
                              "technique": "reed_sol_van"}, pg_num=1)
        io = rados.open_ioctx("hinfo-ec")
        rng = np.random.default_rng(36)
        # 37 full stripes and a partial one; the append crosses two more
        body = rng.integers(0, 256, 37 * 8192 + 1234, dtype=np.uint8)
        end = time.time() + 30
        while True:
            try:
                io.write_full("obj", body.tobytes())
                break
            except RadosError:
                if time.time() > end:
                    raise
                time.sleep(0.3)
        for got, want in self._hinfo_by_bytes(cluster, io, "obj", len(body)):
            assert bytes(got) == want
        tail = rng.integers(0, 256, 2 * 8192 + 99, dtype=np.uint8)
        io.append("obj", tail.tobytes())
        for got, want in self._hinfo_by_bytes(cluster, io, "obj",
                                              len(body) + len(tail)):
            assert bytes(got) == want


# ---------------------------------------------------------------------------
# a rebuild decodes the lost positions and nothing else (PR 48)
# ---------------------------------------------------------------------------

REBUILD_UNIT = 4096
REBUILD_CODES = {
    "rs-k8m3": ({"technique": "reed_sol_van", "k": "8", "m": "3"}, 11),
    "cauchy-k6m3": ({"technique": "cauchy_good", "k": "6", "m": "3",
                     "packetsize": "32"}, 9),
    "lrc-k4m2l3": ({"technique": "lrc", "k": "4", "m": "2", "l": "3"}, 8),
    "shec-k8m4c3": ({"technique": "shec_multiple", "k": "8", "m": "4",
                     "c": "3"}, 12),
    "rs-k2m1": ({"technique": "reed_sol_van", "k": "2", "m": "1"}, 3),
}
ONE_LOST = [pytest.param(code, (pos,), id=f"{code}-p{pos}")
            for code, (_profile, width) in REBUILD_CODES.items()
            for pos in range(width)]
# several at once (a log-driven rebuild after several losses): an
# (r, k) decode, data and parity together
SEVERAL_LOST = [pytest.param("rs-k8m3", (0, 9), id="rs-k8m3-p0.9"),
                pytest.param("rs-k8m3", (3, 5, 10), id="rs-k8m3-p3.5.10"),
                pytest.param("cauchy-k6m3", (1, 8), id="cauchy-k6m3-p1.8"),
                pytest.param("lrc-k4m2l3", (0, 4), id="lrc-k4m2l3-p0.4"),
                pytest.param("shec-k8m4c3", (2, 11), id="shec-k8m4c3-p2.11")]


class TestRebuildShards:
    """`ecutil.rebuild_shards` from the codec's plan for the lost
    positions gives the shard files an encode of the object lays out,
    byte for byte, and `crc32c_batch` of their rows folds to the
    encode's CRCs: what `_ec_push_shards` lands for a rebuild."""

    @pytest.fixture(scope="class")
    def encoded(self):
        @functools.lru_cache(maxsize=None)
        def get(code: str):
            profile, width = REBUILD_CODES[code]
            codec = registry.factory("tpu", dict(profile))
            assert codec.get_chunk_count() == width
            si = ecutil.StripeInfo(codec.get_data_chunk_count(),
                                   REBUILD_UNIT)
            # a tail stripe that is padded
            size = si.stripe_width * 5 - 1000
            payload = np.random.default_rng(48).integers(
                0, 256, size, dtype=np.uint8).tobytes()
            shards, stripe_crcs = ecutil.encode_object_ex(
                codec, si, payload)
            return (codec, si, size, [bytes(s) for s in shards],
                    np.asarray(stripe_crcs))
        return get

    @pytest.mark.parametrize("code, lost", ONE_LOST + SEVERAL_LOST)
    def test_rebuilt_file_and_crc_equal_the_encodes(self, encoded, code,
                                                    lost):
        codec, si, size, shards, stripe_crcs = encoded(code)
        lost = list(lost)
        live = [p for p in range(len(shards)) if p not in lost]
        plan = ecutil.minimum_shards(codec, live, lost)
        assert not set(plan) & set(lost)
        calls = []
        real = codec.decode_batch_async

        def spy(want, present, stack, qos=None):
            calls.append((len(want), stack.shape[0]))
            return real(want, present, stack, qos=qos)

        codec.decode_batch_async = spy
        try:
            out = ecutil.rebuild_shards(
                codec, si, {p: shards[p] for p in plan}, lost, size)
        finally:
            del codec.decode_batch_async
        # one decode of len(lost) rows over the object's stripes
        assert calls == [(len(lost), si.stripe_count(size))]
        assert sorted(out) == sorted(lost)
        full = size // si.stripe_width
        for p in lost:
            assert bytes(out[p]) == shards[p]
            col = crc_mod.crc32c_batch(np.frombuffer(
                out[p], dtype=np.uint8).reshape(-1, REBUILD_UNIT))[:, None]
            assert ecutil.fold_shard_crcs(col, REBUILD_UNIT) == \
                ecutil.fold_shard_crcs(stripe_crcs[:, [p]], REBUILD_UNIT) \
                == [crc_mod.crc32c(0, shards[p])]
            assert ecutil.fold_shard_crcs(col, REBUILD_UNIT, upto=full) == \
                ecutil.fold_shard_crcs(stripe_crcs[:, [p]], REBUILD_UNIT,
                                       upto=full)

    @pytest.mark.parametrize("code", sorted(REBUILD_CODES))
    def test_the_plan_reads_no_more_than_an_objects_read(self, encoded,
                                                         code):
        """Reed-Solomon and cauchy read k for any position (7 data +
        the first parity for a lost data chunk, the k data chunks for
        a lost parity); lrc and shec read fewer where a group or a
        shingle covers the position."""
        codec, _si, _size, shards, _crcs = encoded(code)
        k = codec.get_data_chunk_count()
        reads = []
        for lost in range(len(shards)):
            live = [p for p in range(len(shards)) if p != lost]
            reads.append(len(ecutil.minimum_shards(codec, live, [lost])))
        assert max(reads) <= k
        if code in ("lrc-k4m2l3", "shec-k8m4c3"):
            assert min(reads) < k
        else:
            assert reads == [k] * len(shards)
