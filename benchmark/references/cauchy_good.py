"""jerasure `cauchy_good` (w=8): shard files in the packet layout of
its bit-matrix encode, and their CRC32C, from `benchmark/oracle.py`
(numpy GF(2^8)), importing nothing of the program.  It refuses a
configuration of another technique, another word size, or a chunk
that does not hold whole super-blocks, rather than compare it with
the wrong code.

The equations (jerasure `cauchy.c` and `jerasure.c`, Ceph
`src/erasure-code/jerasure/ErasureCodeJerasure.cc`
`ErasureCodeJerasureCauchyGood`; that checkout is not on this machine,
so they are written from the library's published description, and
each departure is noted):

  * `cauchy_original_coding_matrix`: element (i, j) of the (m, k)
    matrix is 1 / (i ^ (m + j)) over GF(2^8).
  * `cauchy_improve_coding_matrix`: every column is divided by its
    row-0 element (row 0 becomes all ones); then each later row is
    divided by the one of its elements that leaves the fewest ones in
    the row's bit matrices (the first such, the row as it is if none
    is better).  `n_ones(e)` is the number of ones in the 8 x 8 bit
    matrix of multiply-by-e.
  * `jerasure_matrix_to_bitmatrix`: the (8m x 8k) GF(2) matrix whose
    block (i, j) has, in column x, the bits of e * 2^x (row y = bit y).
  * the packet-wise encode (`jerasure_bitmatrix_encode`): a chunk is
    super-blocks of 8 packets of `packetsize` bytes; packet y of
    coding chunk i, in each super-block, is the XOR of the data
    packets (j, x) of that super-block whose bit (8i + y, 8j + x) is
    set.
  * shard layout (ECUtil stripe_info_t, as `oracle.shard_files`): an
    object is stripes of k * stripe_unit bytes, zero-padded to a whole
    stripe; shard i's file is chunk i of every stripe, concatenated.
    A stripe unit is whole super-blocks, so a shard file is a run of
    super-blocks and is encoded as one.

Departures: for m = 2 and small k jerasure takes the matrix from its
tables of best known values (`cbest_*`) and not from the improvement;
this reference always improves (at m = 3, the configuration's, the
tables do not apply).  None else.
"""

from __future__ import annotations

import numpy as np

from benchmark import oracle

W = 8


def n_ones(e: int) -> int:
    """Ones in the bit matrix of multiply-by-e: the bits of e * 2^x
    for x = 0..7."""
    total = 0
    for _x in range(W):
        total += bin(e).count("1")
        e = oracle.gf_mul(e, 2)
    return total


def coding_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) uint8: the improved Cauchy matrix."""
    if k + m > 256:
        raise ValueError("k+m must be <= 256 for w=8")
    mat = [[oracle.gf_inv(i ^ (m + j)) for j in range(k)]
           for i in range(m)]
    for j in range(k):
        inv = oracle.gf_inv(mat[0][j])
        for row in mat:
            row[j] = oracle.gf_mul(row[j], inv)
    for row in mat[1:]:
        best, best_ones = None, sum(n_ones(e) for e in row)
        for d in row:
            if d == 1:
                continue
            inv = oracle.gf_inv(d)
            ones = sum(n_ones(oracle.gf_mul(e, inv)) for e in row)
            if ones < best_ones:
                best, best_ones = inv, ones
        if best is not None:
            row[:] = [oracle.gf_mul(e, best) for e in row]
    return np.array(mat, dtype=np.uint8)


def bitmatrix(matrix: np.ndarray) -> np.ndarray:
    """(8r x 8c) 0/1 uint8 of an (r, c) GF(2^8) matrix."""
    r, c = matrix.shape
    bits = np.zeros((r * W, c * W), dtype=np.uint8)
    for i in range(r):
        for j in range(c):
            e = int(matrix[i, j])
            for x in range(W):
                for y in range(W):
                    bits[i * W + y, j * W + x] = e >> y & 1
                e = oracle.gf_mul(e, 2)
    return bits


def encode(data: np.ndarray, m: int, packetsize: int) -> np.ndarray:
    """(k, L) uint8 data chunks -> (m, L) coding chunks; L is whole
    super-blocks of 8 * packetsize bytes."""
    k, length = data.shape
    if length % (W * packetsize):
        raise ValueError(f"a chunk of {length} bytes does not hold whole "
                         f"super-blocks of {W} x {packetsize}")
    bits = bitmatrix(coding_matrix(k, m))
    packets = data.reshape(k, -1, W, packetsize)
    out = np.zeros((m, packets.shape[1], W, packetsize), dtype=np.uint8)
    for row in range(m * W):
        acc = out[row // W, :, row % W, :]
        for col in np.flatnonzero(bits[row]):
            acc ^= packets[col // W, :, col % W, :]
    return out.reshape(m, length)


def shard_files(payload: bytes, k: int, m: int, packetsize: int,
                stripe_unit: int) -> np.ndarray:
    """(k+m, shard_size) uint8: every shard file of one object."""
    if stripe_unit % (W * packetsize):
        raise ValueError(f"stripe unit {stripe_unit} is not a multiple of "
                         f"{W * packetsize} bytes (w x packetsize)")
    width = k * stripe_unit
    stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = np.ascontiguousarray(
        buf.reshape(stripes, k, stripe_unit).transpose(1, 0, 2)
        .reshape(k, stripes * stripe_unit))
    return np.concatenate([data, encode(data, m, packetsize)], axis=0)


def stored(payload: bytes, config: dict) -> list:
    prof = config["pool_profile"]
    if prof["technique"] != "cauchy_good":
        raise ValueError(f"the cauchy_good reference cannot stand for "
                         f"technique {prof['technique']!r}")
    if int(prof.get("w", W)) != W:
        raise ValueError(f"the cauchy_good reference is w={W} only, not "
                         f"w={prof['w']}")
    files = shard_files(payload, int(prof["k"]), int(prof["m"]),
                        int(prof["packetsize"]), int(config["stripe_unit"]))
    crcs = oracle.crc32c(files)
    return [(f.tobytes(), int(c)) for f, c in zip(files, crcs)]
