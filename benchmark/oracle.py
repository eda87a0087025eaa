"""The plain reference: Reed-Solomon (jerasure `reed_sol_van`, w=8)
encode and CRC32C, in numpy, importing nothing of the program.

`correct` compares what the served path stored (shard files and their
CRCs on the OSDs' stores) with what these functions compute from the
seed-derived payload, bit for bit.

  * GF(2^8) over x^8+x^4+x^3+x^2+1 (0x11d), generator 2 - the field
    jerasure and ISA-L use.
  * `reed_sol_van_matrix(k, m)`: the (k+m) x k extended Vandermonde
    matrix, column-reduced until its top square is the identity, then
    each column scaled so the first coding row is all ones (Plank's
    published algorithm as the repo's `reed_sol_van` profile states
    it).  The bottom m rows are the coding matrix.
  * shard layout (ECUtil stripe_info_t): an object is stripes of
    k * stripe_unit bytes, zero-padded to a whole stripe; shard i's
    file is chunk i of every stripe, concatenated.
  * `crc32c(rows)`: CRC-32C (Castagnoli, reflected 0x82F63B78), seed 0,
    no final inversion - the chained-seed convention Ceph stores in
    HashInfo.  That register is linear over GF(2), so rows are cut
    into blocks, the blocks' CRCs computed side by side, and combined
    by Horner's rule with the "advance over n zero bytes" operator.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D
CRC_POLY_REFLECTED = 0x82F63B78
CRC_BLOCK = 4096


def _gf_tables():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def _mul_table() -> np.ndarray:
    """(256, 256) uint8 product table."""
    t = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    t[1:, 1:] = _EXP[_LOG[nz][:, None] + _LOG[nz][None, :]]
    return t


_MUL = _mul_table()


def reed_sol_van_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) coding matrix of the `reed_sol_van` technique, w=8."""
    rows = k + m
    if rows > 256:
        raise ValueError("k+m must be <= 256 for w=8")
    # extended Vandermonde: row 0 = e_0, row rows-1 = e_{k-1}, row i =
    # (i^0, i^1, ..., i^(k-1)) between
    v = [[0] * k for _ in range(rows)]
    v[0][0] = 1
    v[rows - 1][k - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(k):
            v[i][j] = acc
            acc = gf_mul(acc, i)
    for i in range(k):
        if v[i][i] == 0:
            j = next(j for j in range(i + 1, k) if v[i][j])
            for r in v:
                r[i], r[j] = r[j], r[i]
        if v[i][i] != 1:
            inv = gf_inv(v[i][i])
            for r in v:
                r[i] = gf_mul(r[i], inv)
        for j in range(k):
            f = v[i][j]
            if j != i and f:
                for r in v:
                    r[j] ^= gf_mul(f, r[i])
    if m:
        for j in range(k):
            d = v[k][j]
            if d == 0:
                raise ArithmeticError("vandermonde reduction is not MDS")
            if d != 1:
                inv = gf_inv(d)
                for r in v:
                    r[j] = gf_mul(r[j], inv)
                v[j][j] = 1
    return np.array(v[k:], dtype=np.uint8)


def shard_files(payload: bytes, k: int, m: int,
                stripe_unit: int) -> np.ndarray:
    """(k+m, shard_size) uint8: every shard file of one object."""
    width = k * stripe_unit
    stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    # (S, k, L) -> shard i = chunk i of every stripe
    data = buf.reshape(stripes, k, stripe_unit).transpose(1, 0, 2) \
        .reshape(k, stripes * stripe_unit)
    matrix = reed_sol_van_matrix(k, m)
    parity = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for r in range(m):
        for c in range(k):
            parity[r] ^= _MUL[matrix[r, c]][data[c]]
    return np.concatenate([data, parity], axis=0)


def _crc_byte_table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (CRC_POLY_REFLECTED if c & 1 else 0)
        t[i] = c
    return t


_CRC_T = _crc_byte_table()


def _zero_advance_tables(nbytes: int) -> np.ndarray:
    """(4, 256) uint32: the register after `nbytes` zero bytes, as a
    GF(2)-linear map split by input byte."""
    basis = (np.uint32(1) << np.arange(32, dtype=np.uint32))
    r = basis.copy()
    for _ in range(nbytes):
        r = (r >> np.uint32(8)) ^ _CRC_T[r & np.uint32(0xFF)]
    tables = np.zeros((4, 256), dtype=np.uint32)
    for b in range(4):
        for v in range(256):
            acc = np.uint32(0)
            for bit in range(8):
                if v >> bit & 1:
                    acc ^= r[8 * b + bit]
            tables[b, v] = acc
    return tables


_ADV_CACHE: dict[int, np.ndarray] = {}


def _advance(reg: np.ndarray, nbytes: int) -> np.ndarray:
    if nbytes == 0:
        return reg
    t = _ADV_CACHE.get(nbytes)
    if t is None:
        t = _ADV_CACHE[nbytes] = _zero_advance_tables(nbytes)
    return (t[0][reg & np.uint32(0xFF)]
            ^ t[1][(reg >> np.uint32(8)) & np.uint32(0xFF)]
            ^ t[2][(reg >> np.uint32(16)) & np.uint32(0xFF)]
            ^ t[3][reg >> np.uint32(24)])


def _crc_rows_serial(rows: np.ndarray) -> np.ndarray:
    """(N, L) uint8 -> (N,) uint32, byte by byte, all rows abreast."""
    reg = np.zeros(rows.shape[0], dtype=np.uint32)
    for j in range(rows.shape[1]):
        reg = (reg >> np.uint32(8)) ^ _CRC_T[(reg ^ rows[:, j])
                                             & np.uint32(0xFF)]
    return reg


def crc32c(rows: np.ndarray) -> np.ndarray:
    """(N, L) uint8 -> (N,) uint32: CRC-32C, seed 0, no inversion."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, length = rows.shape
    whole = length // CRC_BLOCK
    reg = np.zeros(n, dtype=np.uint32)
    if whole:
        blocks = rows[:, :whole * CRC_BLOCK].reshape(n * whole, CRC_BLOCK)
        part = _crc_rows_serial(blocks).reshape(n, whole)
        for b in range(whole):
            reg = _advance(reg, CRC_BLOCK) ^ part[:, b]
    tail = length - whole * CRC_BLOCK
    if tail:
        reg = _advance(reg, tail) ^ _crc_rows_serial(
            rows[:, whole * CRC_BLOCK:])
    return reg
