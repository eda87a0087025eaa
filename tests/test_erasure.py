"""Erasure plugin framework tests.

Mirrors the reference's unit-test tiers (SURVEY.md §4):
TestErasureCode (base chunk math), TestErasureCodeJerasure/Isa/Shec/Lrc
(per-technique roundtrips incl. every erasure pattern), and
TestErasureCodePlugin* (registry failure fixtures).
"""

import itertools

import numpy as np
import pytest

from ceph_tpu.erasure import ErasureCodeError  # noqa: F401  (re-export check)
from ceph_tpu.erasure.interface import ErasureCodeError
from ceph_tpu.erasure.registry import (ErasureCodePlugin,
                                       ErasureCodePluginRegistry, registry)
from ceph_tpu.ops import crc32c as crc_mod

RNG = np.random.default_rng(1234)


def roundtrip(codec, data: bytes, erasure_patterns=None):
    """Encode, then decode every erasure pattern and check bit-equality."""
    n = codec.get_chunk_count()
    k = codec.get_data_chunk_count()
    encoded = codec.encode(range(n), data)
    chunk_size = len(encoded[0])
    # decode_concat recovers the original (plus padding)
    if erasure_patterns is None:
        m = n - k
        erasure_patterns = [c for r in range(1, min(m, 2) + 1)
                            for c in itertools.combinations(range(n), r)]
    for pattern in erasure_patterns:
        avail = {i: encoded[i] for i in range(n) if i not in pattern}
        try:
            minimum = codec.minimum_to_decode(list(pattern), avail.keys())
        except ErasureCodeError:
            continue  # pattern not recoverable (e.g. shec beyond c)
        picked = {i: avail[i] for i in minimum if i in avail}
        out = codec.decode(list(pattern), picked, chunk_size)
        for c in pattern:
            assert np.array_equal(out[c], encoded[c]), (
                f"chunk {c} mismatch for erasures {pattern}")
    # full data roundtrip through decode_concat
    got = codec.decode_concat({i: encoded[i] for i in range(k)})
    assert got[: len(data)] == data


class TestBaseChunkMath:
    def test_chunk_size_padding(self):
        codec = registry.factory("jerasure", {"k": "3", "m": "2"})
        cs = codec.get_chunk_size(1000)
        assert cs * 3 >= 1000
        assert cs % 128 == 0

    def test_encode_pads_with_zeros(self):
        codec = registry.factory("jerasure", {"k": "2", "m": "1"})
        data = b"xy" * 100
        out = codec.encode(range(3), data)
        joined = b"".join(out[i].tobytes() for i in range(2))
        assert joined[: len(data)] == data
        assert set(joined[len(data):]) <= {0}

    def test_minimum_to_decode_prefers_data(self):
        codec = registry.factory("jerasure", {"k": "2", "m": "2"})
        assert codec.minimum_to_decode([0, 1], [0, 1, 2, 3]) == [0, 1]
        assert codec.minimum_to_decode([0, 1], [1, 2, 3]) == [1, 2]
        with pytest.raises(ErasureCodeError):
            codec.minimum_to_decode([0], [3])


class TestJerasure:
    @pytest.mark.parametrize("technique,k,m", [
        ("reed_sol_van", 2, 1),
        ("reed_sol_van", 4, 2),
        ("reed_sol_van", 8, 3),
        ("reed_sol_r6_op", 4, 2),
        ("cauchy_orig", 3, 2),
        ("cauchy_good", 6, 3),
    ])
    def test_roundtrip(self, technique, k, m):
        profile = {"k": str(k), "m": str(m), "technique": technique,
                   "packetsize": "128"}
        codec = registry.factory("jerasure", profile)
        data = RNG.integers(0, 256, size=k * 512, dtype=np.uint8).tobytes()
        roundtrip(codec, data)

    def test_first_parity_is_xor(self):
        # reed_sol_van row 0 is all ones -> parity 0 == XOR of data chunks
        codec = registry.factory("jerasure", {"k": "4", "m": "2"})
        data = RNG.integers(0, 256, size=4 * 256, dtype=np.uint8)
        chunks = data.reshape(4, 256)
        parity = codec.encode_chunks(chunks)
        assert np.array_equal(parity[0],
                              np.bitwise_xor.reduce(chunks, axis=0))

    @pytest.mark.parametrize("technique,k,w", [
        ("liberation", 5, 7),       # w prime, k <= w
        ("liberation", 7, 7),
        ("liberation", 4, 11),
        ("blaum_roth", 5, 6),       # w+1 prime
        ("blaum_roth", 6, 10),
        ("liber8tion", 6, 8),       # w = 8 fixed
        ("liber8tion", 8, 8),
    ])
    def test_bitmatrix_raid6_roundtrip(self, technique, k, w):
        """Minimal-density m=2 techniques: every 2-erasure combination
        must decode (ErasureCodeJerasure.h:176-259 family)."""
        import itertools
        codec = registry.factory("jerasure", {
            "technique": technique, "k": str(k), "m": "2", "w": str(w),
            "packetsize": "128"})
        data = bytes(np.random.default_rng(k * w).integers(
            0, 256, 20000, dtype=np.uint8))
        out = codec.encode(range(k + 2), data)
        for lost in itertools.combinations(range(k + 2), 2):
            have = {i: out[i] for i in range(k + 2) if i not in lost}
            assert codec.decode_concat(have)[:len(data)] == data, lost

    def test_bitmatrix_invalid_params_raise(self):
        with pytest.raises(ErasureCodeError):        # w not prime
            registry.factory("jerasure", {"technique": "liberation",
                                          "k": "4", "m": "2", "w": "6"})
        with pytest.raises(ErasureCodeError):        # m != 2
            registry.factory("jerasure", {"technique": "liberation",
                                          "k": "4", "m": "3", "w": "7"})
        with pytest.raises(ErasureCodeError):        # w+1 not prime
            registry.factory("jerasure", {"technique": "blaum_roth",
                                          "k": "4", "m": "2", "w": "7"})
        with pytest.raises(ErasureCodeError):        # k > 8
            registry.factory("jerasure", {"technique": "liber8tion",
                                          "k": "9", "m": "2"})


class TestIsa:
    @pytest.mark.parametrize("technique,k,m", [
        ("reed_sol_van", 7, 3),
        ("reed_sol_van", 8, 3),
        ("cauchy", 4, 3),
    ])
    def test_roundtrip(self, technique, k, m):
        codec = registry.factory("isa", {"k": str(k), "m": str(m),
                                         "technique": technique})
        data = RNG.integers(0, 256, size=k * 300, dtype=np.uint8).tobytes()
        roundtrip(codec, data)


class TestTpu:
    @pytest.mark.parametrize("technique,k,m", [
        ("reed_sol_van", 2, 1),
        ("reed_sol_van", 8, 3),
        ("isa_reed_sol_van", 8, 3),
        ("isa_cauchy", 4, 3),
        ("cauchy_good", 4, 2),
    ])
    def test_roundtrip(self, technique, k, m):
        profile = {"k": str(k), "m": str(m), "technique": technique,
                   "packetsize": "128", "host_cutover": "0"}
        codec = registry.factory("tpu", profile)
        data = RNG.integers(0, 256, size=k * 1024, dtype=np.uint8).tobytes()
        roundtrip(codec, data)

    def test_bit_identical_to_jerasure(self):
        """Device chunks must equal the host oracle byte-for-byte."""
        for technique in ("reed_sol_van", "cauchy_good"):
            profile = {"k": "4", "m": "2", "technique": technique,
                       "packetsize": "128", "host_cutover": "0"}
            host = registry.factory("jerasure", profile)
            dev = registry.factory("tpu", profile)
            data = RNG.integers(0, 256, size=4096 * 4, dtype=np.uint8)
            chunks = data.reshape(4, 4096)
            assert np.array_equal(host.encode_chunks(chunks),
                                  dev.encode_chunks(chunks)), technique

    def test_stored_profile_with_retired_compute_key_still_loads(self):
        """A pool created when the profile still took compute= must
        load: the key is ignored like any unknown key, whatever it
        says, and the codec encodes as every other."""
        profile = {"k": "4", "m": "2", "technique": "reed_sol_van",
                   "host_cutover": "0"}
        host = registry.factory("jerasure", profile)
        chunks = RNG.integers(0, 256, size=(4, 4096), dtype=np.uint8)
        for compute in ("int8", "no-such-path"):
            dev = registry.factory("tpu", dict(profile, compute=compute))
            assert np.array_equal(host.encode_chunks(chunks),
                                  dev.encode_chunks(chunks)), compute

    def test_bit_identical_to_isa(self):
        host = registry.factory("isa", {"k": "8", "m": "3"})
        dev = registry.factory("tpu", {"k": "8", "m": "3",
                                       "technique": "isa_reed_sol_van",
                                       "host_cutover": "0"})
        data = RNG.integers(0, 256, size=8 * 2048, dtype=np.uint8)
        chunks = data.reshape(8, 2048)
        assert np.array_equal(host.encode_chunks(chunks),
                              dev.encode_chunks(chunks))

    def test_encode_batch_and_decode_batch(self):
        codec = registry.factory("tpu", {"k": "4", "m": "2",
                                         "host_cutover": "0"})
        batch = RNG.integers(0, 256, size=(8, 4, 512), dtype=np.uint8)
        parity = codec.encode_batch(batch)
        assert parity.shape == (8, 2, 512)
        # knock out chunks 0 and 5 (parity 1), rebuild from survivors
        present = [1, 2, 3, 4]
        chunks = np.concatenate([batch, parity], axis=1)
        rebuilt = codec.decode_batch([0, 5], present,
                                     chunks[:, present, :])
        assert np.array_equal(rebuilt[:, 0, :], batch[:, 0, :])
        assert np.array_equal(rebuilt[:, 1, :], parity[:, 1, :])

    def test_encode_with_crcs(self):
        codec = registry.factory("tpu", {"k": "2", "m": "1",
                                         "host_cutover": "0"})
        batch = RNG.integers(0, 256, size=(4, 2, 256), dtype=np.uint8)
        parity, crcs = codec.encode_with_crcs(batch)
        assert crcs.shape == (4, 3)
        for b in range(4):
            for c in range(2):
                assert crcs[b, c] == crc_mod.crc32c_sw(0, batch[b, c])
            assert crcs[b, 2] == crc_mod.crc32c_sw(0, parity[b, 0])


class TestShec:
    def test_local_repair_uses_fewer_than_k(self):
        codec = registry.factory("shec", {"k": "8", "m": "4", "c": "3"})
        n = codec.get_chunk_count()
        minimum = codec.minimum_to_decode([0], set(range(n)) - {0})
        assert len(minimum) < 8, minimum

    @pytest.mark.parametrize("k,m,c", [(4, 3, 2), (8, 4, 3), (6, 3, 2)])
    def test_roundtrip_all_c_erasures(self, k, m, c):
        codec = registry.factory("shec",
                                 {"k": str(k), "m": str(m), "c": str(c)})
        n = k + m
        data = RNG.integers(0, 256, size=k * 256, dtype=np.uint8).tobytes()
        patterns = [p for r in range(1, c + 1)
                    for p in itertools.combinations(range(n), r)]
        roundtrip(codec, data, patterns)

    def test_all_c_failures_recoverable(self):
        """Any c erasures must be decodable (the SHEC guarantee)."""
        k, m, c = 4, 3, 2
        codec = registry.factory("shec",
                                 {"k": str(k), "m": str(m), "c": str(c)})
        n = k + m
        for pattern in itertools.combinations(range(n), c):
            avail = set(range(n)) - set(pattern)
            codec.minimum_to_decode(list(pattern), avail)  # must not raise

    def test_invalid_profile(self):
        with pytest.raises(ErasureCodeError):
            registry.factory("shec", {"k": "2", "m": "4", "c": "1"})


class TestLrc:
    def test_kml_generation(self):
        codec = registry.factory("lrc", {"k": "4", "m": "2", "l": "3"})
        assert codec.get_chunk_count() == 8  # 4 data + 2 global + 2 local
        assert codec.get_data_chunk_count() == 4

    def test_local_repair_is_cheap(self):
        codec = registry.factory("lrc", {"k": "4", "m": "2", "l": "3"})
        n = codec.get_chunk_count()
        minimum = codec.minimum_to_decode([0], set(range(n)) - {0})
        assert len(minimum) == 3, minimum  # l chunks, not k=4

    def test_roundtrip(self):
        codec = registry.factory("lrc", {"k": "4", "m": "2", "l": "3"})
        n = codec.get_chunk_count()
        data = RNG.integers(0, 256, size=4 * 400, dtype=np.uint8).tobytes()
        patterns = [(i,) for i in range(n)] + [(0, 4), (1, 5), (0, 1)]
        roundtrip(codec, data, patterns)

    def test_explicit_layers(self):
        profile = {
            "mapping": "DD_DD_",
            "layers": '[["DDc___", ""], ["___DDc", ""]]',
        }
        codec = registry.factory("lrc", profile)
        assert codec.get_data_chunk_count() == 4
        data = RNG.integers(0, 256, size=4 * 300, dtype=np.uint8).tobytes()
        roundtrip(codec, data, [(i,) for i in range(6)])


class TestPluginRegistry:
    def test_unknown_plugin(self):
        with pytest.raises(ErasureCodeError, match="unknown"):
            registry.factory("no-such-plugin", {})

    def test_preload(self):
        r = ErasureCodePluginRegistry()
        r.preload(("jerasure", "isa"))
        assert r.loaded_plugins() == ["isa", "jerasure"]

    def test_missing_entry_point(self, tmp_path, monkeypatch):
        r = ErasureCodePluginRegistry()
        with pytest.raises(ErasureCodeError, match="entry point"):
            r.load("bad", module="json")  # real module, no entry point

    def test_entry_point_raises(self):
        r = ErasureCodePluginRegistry()
        import sys
        import types
        mod = types.ModuleType("_ec_fail_init")
        def boom(reg, name):
            raise RuntimeError("fixture failure")
        mod.__erasure_code_init__ = boom
        sys.modules["_ec_fail_init"] = mod
        try:
            with pytest.raises(ErasureCodeError, match="entry point failed"):
                r.load("failinit", module="_ec_fail_init")
        finally:
            del sys.modules["_ec_fail_init"]

    def test_entry_point_registers_nothing(self):
        r = ErasureCodePluginRegistry()
        import sys
        import types
        mod = types.ModuleType("_ec_noreg")
        mod.__erasure_code_init__ = lambda reg, name: None
        sys.modules["_ec_noreg"] = mod
        try:
            with pytest.raises(ErasureCodeError, match="did not register"):
                r.load("noreg", module="_ec_noreg")
        finally:
            del sys.modules["_ec_noreg"]

    def test_version_mismatch(self):
        r = ErasureCodePluginRegistry()
        import sys
        import types

        class OldPlugin(ErasureCodePlugin):
            version = 0

        mod = types.ModuleType("_ec_oldver")
        mod.__erasure_code_init__ = (
            lambda reg, name: reg.add(name, OldPlugin()))
        sys.modules["_ec_oldver"] = mod
        try:
            with pytest.raises(ErasureCodeError, match="version"):
                r.load("oldver", module="_ec_oldver")
        finally:
            del sys.modules["_ec_oldver"]

    def test_profile_validation_errors(self):
        with pytest.raises(ErasureCodeError):
            registry.factory("jerasure", {"k": "abc"})
        with pytest.raises(ErasureCodeError):
            registry.factory("jerasure", {"technique": "nope"})
        with pytest.raises(ErasureCodeError):
            registry.factory("jerasure", {"k": "300", "m": "10"})
