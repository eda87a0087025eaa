"""CRC32C: known vectors, seed chaining, GF(2) matrix formulation."""

import functools

import numpy as np
import pytest

from ceph_tpu.ops import crc32c as c
from ceph_tpu.osd import ecutil


def test_standard_vector():
    # canonical Castagnoli check value
    assert c.crc32c_std(b"123456789") == 0xE3069283


def test_raw_seed_semantics():
    # ceph-style chaining: crc(seed, a+b) == crc(crc(seed, a), b)
    seed = 0xDEADBEEF
    a, b = b"foo bar baz", b"the quick brown fox"
    assert c.crc32c_sw(c.crc32c_sw(seed, a), b) == c.crc32c_sw(seed, a + b)


def test_linear_formulation_matches():
    rng = np.random.default_rng(0)
    for n in (1, 7, 64, 100):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        for seed in (0, 1, 0xFFFFFFFF, 0x12345678):
            assert c.crc32c_linear(seed, data) == c.crc32c_sw(seed, data)


def test_combine():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, size=37, dtype=np.uint8).tobytes()
    b = rng.integers(0, 256, size=101, dtype=np.uint8).tobytes()
    ca = c.crc32c_sw(0, a)
    cb = c.crc32c_sw(0, b)
    assert c.crc32c_combine(ca, cb, len(b)) == c.crc32c_sw(0, a + b)


def test_block_factorization():
    rng = np.random.default_rng(2)
    n, blk = 256, 32
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    fold, combine = c.block_crc_matrices(n, blk)
    bits = np.unpackbits(data, bitorder="little").reshape(n // blk, 8 * blk)
    r = (bits @ fold.T) % 2                       # (nblocks, 32)
    acc = np.zeros(32, dtype=np.uint8)
    for j in range(n // blk):
        acc ^= ((combine[j] @ r[j]) % 2).astype(np.uint8)
    assert c._bits_to_u32(acc) == c.crc32c_sw(0, data.tobytes())


# -- the vectorised chain fold (crc32c_fold, ecutil.fold_shard_crcs) ---------

FOLD_SHAPES = [(128, 11), (171, 9), (256, 8), (8, 3), (1, 3), (2, 2),
               (2048, 3)]
FOLD_UPTO = ["none", "0", "1", "S-1", "S"]


@functools.lru_cache(maxsize=None)
def _fold_case(S, km, chunk):
    """Random stripes of one shape: the per-stripe chunk CRCs, and for
    every prefix length the two references a column: the scalar chain of
    crc32c_combine, and crc32c over the shard file's bytes."""
    rng = np.random.default_rng([S, km, chunk])
    chunks = rng.integers(0, 256, (S, km, chunk), dtype=np.uint8)
    crcs = c.crc32c_batch(chunks.reshape(S * km, chunk)).reshape(S, km)
    chain = np.zeros((S + 1, km), dtype=np.uint64)
    direct = np.zeros((S + 1, km), dtype=np.uint64)
    for col in range(km):
        for s in range(S):
            chain[s + 1, col] = (
                c.crc32c_combine(int(chain[s, col]), int(crcs[s, col]), chunk)
                if s else int(crcs[0, col]))
            direct[s + 1, col] = c.crc32c(int(direct[s, col]),
                                          chunks[s, col])
    return crcs, chain, direct


@pytest.mark.parametrize("chunk", [4096, 512, 700])
@pytest.mark.parametrize("upto", FOLD_UPTO)
@pytest.mark.parametrize("S,km", FOLD_SHAPES)
def test_fold_shard_crcs_equals_chain_and_file_crc(S, km, upto, chunk):
    """The cells' shapes and the edges: the one-pass fold gives, value
    for value, the scalar chain and the CRC of the shard file's bytes.
    (Integers throughout: XOR of uint32 table entries has no rounding
    and no carry, so no stripe count is past an exactness limit.)"""
    crcs, chain, direct = _fold_case(S, km, chunk)
    n = {"none": None, "0": 0, "1": 1, "S-1": S - 1, "S": S}[upto]
    got = ecutil.fold_shard_crcs(crcs, chunk, upto=n)
    assert isinstance(got, list) and all(type(v) is int for v in got)
    want = S if n is None else n
    assert got == chain[want].tolist()
    assert got == direct[want].tolist()


def test_fold_takes_any_integer_dtype_and_rejects_upto_past_the_end():
    crcs, chain, _ = _fold_case(8, 3, 512)
    for dtype in (np.int64, np.uint64, ">u4"):
        assert ecutil.fold_shard_crcs(crcs.astype(dtype), 512) \
            == chain[8].tolist()
    with pytest.raises(IndexError):
        ecutil.fold_shard_crcs(crcs, 512, upto=9)


def test_fold_calls_no_scalar_combine(monkeypatch):
    """The guard that cannot flake: W's fold makes no crc32c_combine
    call, where the loop it replaced made 1,397."""
    crcs, chain, _ = _fold_case(128, 11, 4096)
    calls = []
    real = c.crc32c_combine
    monkeypatch.setattr(
        c, "crc32c_combine",
        lambda *a: calls.append(a) or real(*a))
    assert ecutil.fold_shard_crcs(crcs, 4096) == chain[128].tolist()
    assert calls == []


def test_advance_tables_cache_is_capped():
    """Forty stripe counts at forty chunk sizes ask for far more tables
    than the cap; the cache holds no more than it, and an evicted
    table, rebuilt, gives the same fold."""
    for i in range(40):
        S, chunk = 3 + 7 * i, 512 + 64 * i
        crcs, chain, _ = _fold_case(S, 2, chunk)
        assert c.crc32c_fold(crcs, chunk).tolist() == chain[S].tolist()
    info = c.advance_tables.cache_info()
    assert info.maxsize == c.ADVANCE_TABLES_CAP == 64
    assert info.misses > info.maxsize >= info.currsize
    crcs, chain, _ = _fold_case(128, 11, 4096)
    assert ecutil.fold_shard_crcs(crcs, 4096) == chain[128].tolist()
