"""The plain reference against known answers and, as a cross-check made
only here, against the program's host codec."""

import numpy as np

from benchmark import oracle


def test_crc32c_known_answers():
    msg = np.frombuffer(b"123456789", dtype=np.uint8)[None, :]
    # the standard CRC-32C check value is taken with the register
    # inverted before and after; seed 0 without inversion gives this
    assert int(oracle.crc32c(msg)[0]) == 0x58E3FA20
    assert int(oracle.crc32c(np.zeros((1, 10000), np.uint8))[0]) == 0


def test_crc32c_blocks_equal_serial():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, (3, 3 * oracle.CRC_BLOCK + 77),
                        dtype=np.uint8)
    assert np.array_equal(oracle.crc32c(rows), oracle._crc_rows_serial(rows))


def test_gf_field_and_matrix_shape():
    for a in (1, 2, 87, 255):
        assert oracle.gf_mul(a, oracle.gf_inv(a)) == 1
    m = oracle.reed_sol_van_matrix(8, 3)
    assert m.shape == (3, 8) and np.all(m[0] == 1)
    assert oracle.reed_sol_van_matrix(2, 1).tolist() == [[1, 1]]


def test_any_k_shards_rebuild_the_object():
    """MDS: every k x k submatrix of [I; C] inverts (rank check by
    Gaussian elimination over GF(2^8))."""
    from itertools import combinations
    k, m = 4, 2
    gen = np.concatenate([np.eye(k, dtype=np.uint8),
                          oracle.reed_sol_van_matrix(k, m)])
    for rows in combinations(range(k + m), k):
        a = [[int(x) for x in gen[r]] for r in rows]
        for i in range(k):
            p = next(r for r in range(i, k) if a[r][i])
            a[i], a[p] = a[p], a[i]
            inv = oracle.gf_inv(a[i][i])
            a[i] = [oracle.gf_mul(x, inv) for x in a[i]]
            for r in range(k):
                if r != i and a[r][i]:
                    f = a[r][i]
                    a[r] = [x ^ oracle.gf_mul(f, y)
                            for x, y in zip(a[r], a[i])]


def test_matches_the_programs_host_codec():
    from ceph_tpu.erasure.registry import registry
    from ceph_tpu.ops import crc32c as crc_mod
    rng = np.random.default_rng(9)
    payload = rng.integers(0, 256, 70000, dtype=np.uint8).tobytes()
    for k, m in ((8, 3), (2, 1)):
        got = oracle.shard_files(payload, k, m, 4096)
        host = registry.factory("jerasure", {
            "k": str(k), "m": str(m), "technique": "reed_sol_van",
            "backend": "host"})
        S = got.shape[1] // 4096
        buf = np.zeros(S * k * 4096, np.uint8)
        buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        chunks, _crcs = host.encode_stripes_with_crcs(
            buf.reshape(S, k, 4096))
        assert np.array_equal(chunks.transpose(1, 0, 2).reshape(k + m, -1),
                              got)
        crcs = oracle.crc32c(got)
        assert [int(c) for c in crcs] == [
            crc_mod.crc32c(0, row.tobytes()) for row in got]
