"""Copy-audit plane: the static hot-path scan stays clean, the scanner
itself catches regressions, and the runtime counters flow into perf
dump semantics."""

import time

import numpy as np
import pytest

from ceph_tpu.tools import copy_audit
from ceph_tpu.utils import copyaudit


class TestStaticPass:
    def test_hot_path_within_budget(self):
        """Tier-1 gate: a new bytes()/tobytes()/join in the zero-copy
        path fails here until its budget is consciously raised."""
        violations = copy_audit.audit()
        assert violations == [], "\n".join(violations)

    def test_scanner_catches_regressions(self):
        src = (
            "def send(payload):\n"
            "    flat = bytes(payload)      # the regression\n"
            "    arr = payload.tobytes()\n"
            "    joined = b''.join([flat, arr])\n"
            "    return joined\n")
        hits = copy_audit.scan_source(src)
        assert hits["bytes()"] == [2]
        assert hits[".tobytes()"] == [3]
        assert hits["b''.join()"] == [4]

    def test_scanner_ignores_prose_and_types(self):
        src = (
            '"""docstring mentioning bytes( and .tobytes( freely"""\n'
            "# comment: bytes( .tobytes( b''.join(\n"
            "def f(data: bytes) -> bytes:\n"
            "    s = 'literal with bytes( inside'\n"
            "    return data\n")
        assert copy_audit.scan_source(src) == {}

    def test_allowlist_files_exist(self):
        assert copy_audit.audit() == []      # includes missing-file check


class TestRuntimeCounters:
    def test_note_and_snapshot(self):
        copyaudit.note("test.site", 100)
        copyaudit.note("test.site", 50)
        snap = copyaudit.snapshot()
        assert snap["host_copies"] >= 2
        assert snap["ec_host_copy_bytes"] >= 150
        assert snap["sites"]["test.site"]["copies"] >= 2
        assert snap["sites"]["test.site"]["bytes"] >= 150

    def test_flatten_sites_fire(self):
        from ceph_tpu.utils.bufferlist import BufferList
        before = copyaudit.snapshot()
        bl = BufferList(b"a" * 64)
        bl.append(b"b" * 64)
        bl.to_bytes()
        after = copyaudit.snapshot()
        site = after["sites"]["bufferlist.flatten"]
        assert site["bytes"] >= \
            before["sites"].get("bufferlist.flatten",
                                {"bytes": 0})["bytes"] + 128

    # what serves the encode -> (plugin, extra profile keys, lanes,
    # the ec_pipeline counters that all rise when that rung served it)
    RUNGS = {
        "host-codec": ("jerasure", {}, 1, ()),
        "lane": ("tpu", {"host_cutover": "1"}, 1, ("dev_dispatches",)),
        "row-splits": ("tpu", {"host_cutover": "1"}, 2,
                       ("split_dispatches", "dev_dispatches")),
        "host-drain": ("tpu", {"host_cutover": str(1 << 40)}, 2,
                       ("host_dispatches",)),
    }

    @pytest.mark.parametrize("rung", list(RUNGS))
    def test_encode_staging_is_the_only_write_copy(self, rung,
                                                   monkeypatch):
        """A whole-object EC encode through ecutil costs exactly one
        payload staging copy + one shard-major relayout — shard files
        come back as views, never per-shard bytes — whatever serves
        it: a codec without the pipeline, one lane, row splits over
        two lanes, or the pipeline's host drain."""
        from ceph_tpu.erasure.registry import registry
        from ceph_tpu.ops import pipeline as ec_pipeline
        from ceph_tpu.osd import ecutil
        from ceph_tpu.utils.bufferlist import BufferList
        plugin, extra, lanes, counters = self.RUNGS[rung]
        pipe = ec_pipeline.EcDevicePipeline(device_shards=lanes,
                                            split_min=1)
        monkeypatch.setattr(ec_pipeline, "_global", pipe)
        profile = {"k": "2", "m": "1", "technique": "reed_sol_van"}
        codec = registry.factory(plugin, dict(profile, **extra))
        oracle = registry.factory("jerasure", profile)
        sinfo = ecutil.StripeInfo(2, 256)
        payload = BufferList(b"x" * 1000)
        payload.append(b"y" * 500)
        want, _ = ecutil.encode_object_ex(oracle, sinfo,
                                          payload.to_bytes())

        def site(name):
            return dict(copyaudit.snapshot()["sites"].get(
                name, {"copies": 0, "bytes": 0}))

        try:
            # the device fns warm on background threads and the host
            # serves meanwhile: the copy account holds on every call,
            # and the rung asked for has to serve one within the bound
            end = time.monotonic() + 120
            while True:
                st0, stage0 = pipe.stats(), site("ec.stage")
                layout0 = site("ec.shard_layout")["copies"]
                flat0 = site("bufferlist.flatten")["copies"]
                shards, crcs = ecutil.encode_object_ex(codec, sinfo,
                                                       payload)
                stage1 = site("ec.stage")
                assert stage1["copies"] == stage0["copies"] + 1
                assert stage1["bytes"] == stage0["bytes"] + 1500
                assert site("ec.shard_layout")["copies"] == layout0 + 1
                assert site("bufferlist.flatten")["copies"] == flat0
                assert all(isinstance(s, memoryview) for s in shards)
                for a, b in zip(shards, want):
                    assert bytes(a) == bytes(b)
                st1 = pipe.stats()
                if all(st1[c] > st0[c] for c in counters):
                    break
                assert time.monotonic() < end, (rung, st1)
                time.sleep(0.05)
            if rung == "lane":
                assert st1["split_dispatches"] == 0
            assert st1["device_errors"] == 0
        finally:
            pipe.stop()


class TestDecodeNoCopy:
    def test_decode_channel_key_is_cheap(self):
        """plugin_tpu regression: the decode-channel memo key must not
        serialize the decode matrix (rows.tobytes() copied it on every
        decode) — the key is the semantic (want, present, L) pattern
        and contains no bytes blob."""
        from ceph_tpu.erasure.registry import registry
        codec = registry.factory("tpu", {"k": "4", "m": "2",
                                         "technique": "reed_sol_van"})
        rows = codec._decode_rows([0], [1, 2, 3, 4])
        chan = codec._decode_channel([0], [1, 2, 3, 4], rows, 128)
        again = codec._decode_channel([0], [1, 2, 3, 4], rows, 128)
        assert chan is again                      # memoized
        flat = []

        def walk(x):
            if isinstance(x, tuple):
                for v in x:
                    walk(v)
            else:
                flat.append(x)

        walk(chan.key)
        assert not any(isinstance(v, (bytes, bytearray)) for v in flat)

    def test_decode_does_not_copy_input(self, monkeypatch):
        """The chunks array handed to decode_batch_async reaches the
        pipeline as the same memory (ascontiguousarray of a contiguous
        uint8 array is a no-op)."""
        from ceph_tpu.erasure.registry import registry
        from ceph_tpu.ops import pipeline as ec_pipeline
        codec = registry.factory("tpu", {"k": "4", "m": "2",
                                         "technique": "reed_sol_van"})
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, size=(2, 4, 128), dtype=np.uint8)
        parity = np.asarray(codec.encode_batch(data))
        present = [1, 2, 3, 4]
        stack = np.ascontiguousarray(
            np.stack([data[:, 1], data[:, 2], data[:, 3],
                      parity[:, 0]], axis=1))
        seen = {}
        real_submit = ec_pipeline.EcDevicePipeline.submit

        def spy(self, chan, arr, cache=None, qos=None):
            seen["arr"] = arr
            return real_submit(self, chan, arr, cache=cache, qos=qos)

        monkeypatch.setattr(ec_pipeline.EcDevicePipeline, "submit", spy)
        out = np.asarray(
            codec.decode_batch_async([0], present, stack).result())
        assert np.array_equal(out[:, 0], data[:, 0])
        assert "arr" in seen
        assert np.shares_memory(seen["arr"], stack), \
            "decode copied its input before submit"
