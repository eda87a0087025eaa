"""Device kernels vs numpy ground truth (runs on the 8-device CPU backend)."""

import numpy as np
import pytest

from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.ops import ec_kernels, gf


def test_encode_matches_numpy():
    rng = np.random.default_rng(0)
    k, m, L = 8, 3, 1024
    coding = gf.reed_sol_van_matrix(k, m)
    data = rng.integers(0, 256, size=(k, L), dtype=np.uint8)
    fn = ec_kernels.make_codec_fn(coding)
    parity = np.asarray(fn(data))
    assert np.array_equal(parity, gf.encode_np(coding, data))


def test_encode_batched():
    rng = np.random.default_rng(1)
    k, m, L, B = 4, 2, 256, 5
    coding = gf.isa_rs_matrix(k, m)
    data = rng.integers(0, 256, size=(B, k, L), dtype=np.uint8)
    fn = ec_kernels.make_codec_fn(coding)
    parity = np.asarray(fn(data))
    assert parity.shape == (B, m, L)
    for b in range(B):
        assert np.array_equal(parity[b], gf.encode_np(coding, data[b]))


def test_decode_roundtrip_on_device():
    rng = np.random.default_rng(2)
    k, m, L = 6, 3, 512
    coding = gf.cauchy_good_matrix(k, m)
    gen = gf.systematic_generator(coding, k)
    data = rng.integers(0, 256, size=(k, L), dtype=np.uint8)
    parity = np.asarray(ec_kernels.make_codec_fn(coding)(data))
    chunks = np.concatenate([data, parity], axis=0)
    lost = {1, 4, 7}
    present = [i for i in range(k + m) if i not in lost][:k]
    dec = gf.decode_matrix(gen, k, present)
    rebuilt = np.asarray(ec_kernels.make_codec_fn(dec)(chunks[present]))
    assert np.array_equal(rebuilt, data)


def test_gf2_bitmatrix_direct():
    """w=1 path: a raw GF(2) matrix (e.g. cauchy bitmatrix) applied directly."""
    rng = np.random.default_rng(3)
    k, m = 3, 2
    bm = gf.expand_bitmatrix(gf.cauchy_orig_matrix(k, m), 8)
    data = rng.integers(0, 256, size=(k, 64), dtype=np.uint8)
    out_dev = np.asarray(ec_kernels.make_codec_fn(bm, w=1)(data))
    # bit-domain ground truth
    bits = np.unpackbits(data, axis=0, bitorder="little")
    bits = bits.reshape(k, 8, 64).reshape(k * 8, 64)
    expect_bits = (bm @ bits) % 2
    expect = np.zeros((m, 64), dtype=np.uint8)
    for i in range(m):
        for b in range(8):
            expect[i] |= (expect_bits[i * 8 + b] << b).astype(np.uint8)
    assert np.array_equal(out_dev, expect)


@pytest.mark.parametrize("L,block", [(256, 32), (1000, 0)])
def test_device_crc(L, block):
    rng = np.random.default_rng(4)
    chunks = rng.integers(0, 256, size=(3, L), dtype=np.uint8)
    fn = ec_kernels.make_crc_fn(L, block=block or ec_kernels.DEFAULT_CRC_BLOCK)
    got = np.asarray(fn(chunks))
    for i in range(3):
        assert int(got[i]) == crc_mod.crc32c_sw(0, chunks[i].tobytes())


def test_fused_encode_crc():
    rng = np.random.default_rng(5)
    k, m, L, B = 8, 3, 512, 2
    coding = gf.reed_sol_van_matrix(k, m)
    data = rng.integers(0, 256, size=(B, k, L), dtype=np.uint8)
    fn = ec_kernels.make_encode_crc_fn(coding, L)
    parity, crcs = fn(data)
    parity, crcs = np.asarray(parity), np.asarray(crcs)
    assert crcs.shape == (B, k + m)
    for b in range(B):
        expect_parity = gf.encode_np(coding, data[b])
        assert np.array_equal(parity[b], expect_parity)
        allc = np.concatenate([data[b], expect_parity], axis=0)
        for i in range(k + m):
            assert int(crcs[b, i]) == crc_mod.crc32c_sw(0, allc[i].tobytes())


def test_seed_chaining_via_combine():
    """Device CRCs (seed 0) chain into ceph-style seeded CRCs on host."""
    rng = np.random.default_rng(6)
    L = 128
    chunk = rng.integers(0, 256, size=L, dtype=np.uint8)
    dev = int(np.asarray(ec_kernels.make_crc_fn(L)(chunk[None]))[0])
    seed = 0xCAFEBABE
    assert crc_mod.crc32c_combine(seed, dev, L) == crc_mod.crc32c_sw(seed, chunk.tobytes())
