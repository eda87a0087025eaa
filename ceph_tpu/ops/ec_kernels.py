"""JAX/XLA device kernels: GF(2^8) erasure coding + CRC32C as matmuls.

The TPU-first formulation (this is the north-star kernel of the whole
framework, replacing the reference's ISA-L x86 assembly and gf-complete
SIMD paths, /root/reference/src/erasure-code/isa/isa-l/erasure_code/):

  * GF(2^8) multiply-by-constant is GF(2)-linear on a byte's 8 bits, so an
    (m x k) generator of bytes becomes an (8m x 8k) 0/1 matrix and encode
    is    parity_bits = (G_bits @ data_bits) mod 2
    — an int8 matmul on the MXU followed by a parity extraction.  Decode
    is the same matmul with an inverted matrix.  Bit-matrix techniques
    (cauchy, liberation) are *already* GF(2) matrices and map natively.

  * CRC32C is GF(2)-linear in the message, factored in two levels
    (ceph_tpu.ops.crc32c.block_crc_matrices): a shared 32x(8W) fold matmul
    per W-byte block plus per-position 32x32 combines.  Scrub checksums of
    every chunk ride the same device pass as the encode — "fused" in the
    sense that chunks are DMA'd once and XLA fuses unpack/fold.

Everything is traced once per (shape, matrix) and cached; shapes are
static, control flow is compile-time, no host sync inside the step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache
from . import crc32c as crc_mod
from . import gf

compile_cache.place()

# int8 inputs with int32 accumulation: the MXU's integer path on TPU
_IN_DTYPE, _ACC_DTYPE = jnp.int8, jnp.int32

_BIT_SHIFTS = tuple(1 << b for b in range(8))


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(..., n, L) uint8 -> (..., n*8, L) bits, row index = n*8 + bit."""
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape((1,) * (x.ndim - 1) + (8, 1))
    bits = (x[..., :, None, :] >> shifts) & jnp.uint8(1)
    shape = x.shape[:-2] + (x.shape[-2] * 8, x.shape[-1])
    return bits.reshape(shape).astype(_IN_DTYPE)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., n*8, L) {0,1} int32 -> (..., n, L) uint8."""
    shape = bits.shape[:-2] + (bits.shape[-2] // 8, 8, bits.shape[-1])
    b = bits.reshape(shape)
    weights = jnp.array(_BIT_SHIFTS, dtype=jnp.int32).reshape((1,) * (b.ndim - 3) + (1, 8, 1))
    return jnp.sum(b * weights, axis=-2).astype(jnp.uint8)


def _mod2(x: jnp.ndarray) -> jnp.ndarray:
    return (x & 1).astype(jnp.int32)


def gf2_matmul_bytes(g_bits: jnp.ndarray,
                     data: jnp.ndarray) -> jnp.ndarray:
    """Apply a GF(2) bit-matrix to byte chunks.

    g_bits: (R, C) 0/1 (R, C multiples of 8), data: (..., C/8, L) uint8
    -> (..., R/8, L) uint8.  The contraction runs on the MXU.
    """
    bits = _unpack_bits(data)
    g = g_bits.astype(_IN_DTYPE)
    acc = jax.lax.dot_general(
        g, bits,
        dimension_numbers=(((1,), (bits.ndim - 2,)), ((), ())),
        preferred_element_type=_ACC_DTYPE,
    )
    # dot_general output: (R, ..., L) — move R after batch dims
    if bits.ndim > 2:
        perm = tuple(range(1, bits.ndim - 1)) + (0, bits.ndim - 1)
        acc = jnp.transpose(acc, perm)
    return _pack_bits(_mod2(acc))


def _k_packing(rows: int, cols: int, L: int) -> int:
    """Segments to pack per MXU column so the contraction fills K=128.

    The systolic array streams one K<=128 column per cycle; a GF(2^8)
    encode has K = 8k bits, so for small k most of each column is padding.
    Packing d independent L/d-byte segments block-diagonally multiplies
    per-cycle useful work by d (e.g. k=2 -> d=8, k=8 -> d=2).
    """
    d = max(1, 128 // cols)
    while d > 1 and (L % d or (rows * d) > 128):
        d -= 1
    return d


def gf2_matmul_bytes_packed(g_bits: jnp.ndarray,
                            data: jnp.ndarray) -> jnp.ndarray:
    """Like gf2_matmul_bytes but block-diagonally packed to fill the MXU.

    data: (B, k, L) uint8 -> (B, m, L) uint8.
    """
    B, k, L = data.shape
    rows, cols = g_bits.shape
    m = rows // 8
    d = _k_packing(rows, cols, L)
    if d == 1:
        return gf2_matmul_bytes(g_bits, data)
    Ld = L // d
    # block-diagonal packing = kron(I_d, g); jnp.kron keeps this
    # traceable (a sharded caller may feed a per-device generator
    # slice), and XLA constant-folds it for concrete matrices
    g = jnp.kron(jnp.eye(d, dtype=jnp.uint8),
                 jnp.asarray(g_bits, dtype=jnp.uint8)).astype(_IN_DTYPE)
    # segment b of the chunk axis -> block b of the packed contraction
    seg = data.reshape(B, k, d, Ld).transpose(0, 2, 1, 3)      # (B, d, k, Ld)
    bits = _unpack_bits(seg)                                    # (B, d, 8k, Ld)
    bits = bits.reshape(B, d * cols, Ld)
    acc = jax.lax.dot_general(
        g, bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=_ACC_DTYPE,
    )                                                           # (dR, B, Ld)
    acc = jnp.transpose(acc, (1, 0, 2)).reshape(B, d, rows, Ld)
    packed = _pack_bits(_mod2(acc))                             # (B, d, m, Ld)
    return packed.transpose(0, 2, 1, 3).reshape(B, m, L)


@functools.lru_cache(maxsize=None)
def _apply_fn():
    """Jitted (g_bits (R, C), data (B, k, L) uint8) -> (B, R/8, L).

    The bit-matrix is an OPERAND, not a baked constant: every matrix
    of one shape shares one executable per data shape.  A degraded
    read's decode matrix depends on which shards were lost AND on
    which k survivors answered first, so with per-matrix executables
    every new (want, present) pattern paid its own compile and its
    first batches were host-served while it warmed."""

    @jax.jit
    def run_decode(g_bits, data):
        return gf2_matmul_bytes_packed(g_bits, data)

    return run_decode


def make_codec_fn(matrix: np.ndarray, w: int = 8):
    """Build a jitted chunk transform from a GF(2^w) byte matrix.

    matrix: (m, k) uint8 over GF(2^8) (or an already-expanded GF(2)
    bit-matrix when w == 1).  Returns fn(data: (B, k, L) or (k, L) uint8)
    -> same-rank parity array.
    """
    if w == 8:
        bits = gf.expand_bitmatrix(np.asarray(matrix, dtype=np.uint8), 8)
    elif w == 1:
        bits = np.asarray(matrix, dtype=np.uint8)
        assert bits.shape[0] % 8 == 0 and bits.shape[1] % 8 == 0
    else:
        raise ValueError(f"unsupported w={w}")
    fn = _apply_fn()
    bits = np.ascontiguousarray(bits)

    def call(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        out = fn(bits, data)
        return out[0] if squeeze else out

    return call


# ---------------------------------------------------------------------------
# Packetized GF(2) transforms (jerasure bitmatrix techniques)
#
# Bit-matrix techniques (cauchy_*, liberation) lay a chunk out as
# super-blocks of w packets and XOR whole packets per the 0/1 schedule
# (reference semantics: jerasure_bitmatrix_encode packet loops).  A packet
# XOR is bitwise, so the whole schedule is ONE GF(2) matmul with the raw
# bitmatrix — no 8x expansion — batched over super-blocks on the MXU.
# ---------------------------------------------------------------------------


def gf2_packet_matmul(m_bits: jnp.ndarray,
                      packets: jnp.ndarray) -> jnp.ndarray:
    """m_bits: (R, C) 0/1; packets: (..., C, P) uint8 -> (..., R, P) uint8.

    out[r] = XOR over c with m_bits[r, c] of packets[c]; bytes are 8
    independent GF(2) lanes, so unpack along the byte axis only.
    """
    lead = packets.shape[:-2]
    C, P = packets.shape[-2:]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((packets[..., None] >> shifts) & jnp.uint8(1))
    bits = bits.reshape(lead + (C, P * 8)).astype(_IN_DTYPE)
    acc = jax.lax.dot_general(
        m_bits.astype(_IN_DTYPE), bits,
        dimension_numbers=(((1,), (bits.ndim - 2,)), ((), ())),
        preferred_element_type=_ACC_DTYPE,
    )
    if bits.ndim > 2:
        perm = tuple(range(1, bits.ndim - 1)) + (0, bits.ndim - 1)
        acc = jnp.transpose(acc, perm)
    out_bits = _mod2(acc).reshape(lead + (m_bits.shape[0], P, 8))
    weights = jnp.array(_BIT_SHIFTS, dtype=jnp.int32)
    return jnp.sum(out_bits * weights, axis=-1).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _packet_fn(w: int, packetsize: int):
    """Jitted (m_bits (R, C), data (B, n, L) uint8) -> (B, R/w, L).

    The bit-matrix is an OPERAND, as in :func:`_apply_fn`: the decode
    rows of every (want, present) pattern of one shape share one
    executable per data shape."""

    @jax.jit
    def run_packet_codec(m_bits, data):
        # data: (B, n, L) uint8, n*w == cols, L % (w*packetsize) == 0
        rows, _cols = m_bits.shape
        B, n, L = data.shape
        nblk = L // (w * packetsize)
        blocks = data.reshape(B, n, nblk, w, packetsize)
        packets = blocks.transpose(0, 2, 1, 3, 4).reshape(
            B, nblk, n * w, packetsize)
        out = gf2_packet_matmul(m_bits, packets)
        r = rows // w
        out = out.reshape(B, nblk, r, w, packetsize).transpose(0, 2, 1, 3, 4)
        return out.reshape(B, r, nblk * w * packetsize)

    return run_packet_codec


def make_packet_codec_fn(matrix: np.ndarray, w: int, packetsize: int):
    """Jitted packetized transform from a GF(2^w) byte matrix.

    matrix: (r, c) uint8 -> fn(data (B, c, L) or (c, L)) -> (B, r, L)
    parity in jerasure bitmatrix chunk layout (bit-identical to the
    reference's packetized encode).
    """
    bits = gf.expand_bitmatrix(np.asarray(matrix, dtype=np.uint8), w)
    return make_bits_codec_fn(bits, w, packetsize)


def make_bits_codec_fn(bits: np.ndarray, w: int, packetsize: int):
    """Jitted packetized transform from a raw GF(2) bit-matrix
    (liberation / blaum_roth minimal-density codes, which have no
    byte-matrix form)."""
    bits = np.ascontiguousarray(bits, dtype=np.uint8)
    fn = _packet_fn(w, packetsize)

    def call(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        out = fn(bits, data)
        return out[0] if squeeze else out

    return call


def gf2_packet_xor(m_bits: np.ndarray, data: jnp.ndarray, w: int,
                   packetsize: int) -> jnp.ndarray:
    """A CONSTANT (R, C) 0/1 matrix applied to packet chunks as XORs.

    data: (B, C/w, L) uint8 -> (B, R/w, L) uint8, bit-identical to
    :func:`gf2_packet_matmul` on the same layout.  An encode's matrix
    never changes, so the schedule is unrolled at trace time: coding
    packet r is the XOR of the data packets whose bit is set in row r,
    whole bytes at a time, with no 8x bit expansion and no MXU pass.
    The packets first move to the LEADING axis, (C, B, nblk * P), so
    that a packet is a dense (B, nblk * P) slab and not a 32-byte sliver
    of a 128-lane row; the two relayouts are the cost."""
    rows, cols = m_bits.shape
    B, n, L = data.shape
    nblk = L // (w * packetsize)
    packets = data.reshape(B, n, nblk, w, packetsize).transpose(
        1, 3, 0, 2, 4).reshape(cols, B, nblk * packetsize)
    zero = jnp.zeros_like(packets[0])
    out = jnp.stack([
        functools.reduce(jnp.bitwise_xor,
                         [packets[c] for c in np.flatnonzero(m_bits[r])],
                         zero)
        for r in range(rows)])
    out = out.reshape(rows // w, w, B, nblk, packetsize).transpose(
        2, 0, 3, 1, 4)
    return out.reshape(B, rows // w, L)


# ---------------------------------------------------------------------------
# Device CRC32C
# ---------------------------------------------------------------------------

DEFAULT_CRC_BLOCK = 16  # bytes; 8W = 128 bits fills one MXU column exactly


CRC_GROUP = 64


@functools.lru_cache(maxsize=64)
def _crc_fn(nbytes: int, block: int):
    nblk = nbytes // block
    hierarchical = nblk % CRC_GROUP == 0 and nblk >= CRC_GROUP
    if hierarchical:
        fold_np, gcomb_np, top_np = crc_mod.block_crc_matrices_2level(
            nbytes, block, CRC_GROUP)
        gcomb = jnp.asarray(gcomb_np)
        top = jnp.asarray(top_np)
    else:
        fold_np, comb_np = crc_mod.block_crc_matrices(nbytes, block)
        comb = jnp.asarray(comb_np)
    fold = jnp.asarray(fold_np)          # (32, 8*block)
    weights32 = jnp.asarray([1 << i for i in range(32)], dtype=jnp.uint32)

    @jax.jit
    def run_scrub_crc(chunks):
        # chunks: (..., L) uint8; bits byte-major LSB-first to match
        # crc32c.message_matrix's column convention.
        lead = chunks.shape[:-1]
        blocks = chunks.reshape(lead + (nblk, block))
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = (blocks[..., None] >> shifts) & jnp.uint8(1)   # (..., nblk, block, 8)
        bits = bits.reshape(lead + (nblk, block * 8)).astype(_IN_DTYPE)
        # fold every block with the shared matrix: (..., nblk, 32)
        r = jax.lax.dot_general(
            bits, fold.astype(_IN_DTYPE),
            dimension_numbers=(((bits.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_ACC_DTYPE,
        )
        r = _mod2(r).astype(_IN_DTYPE)
        if hierarchical:
            ngroups = nblk // CRC_GROUP
            rg = r.reshape(lead + (ngroups, CRC_GROUP, 32))
            s = jnp.einsum("tvu,...gtu->...gv", gcomb.astype(_IN_DTYPE), rg,
                           preferred_element_type=_ACC_DTYPE)
            s = _mod2(s).astype(_IN_DTYPE)
            acc = jnp.einsum("gvu,...gu->...v", top.astype(_IN_DTYPE), s,
                             preferred_element_type=_ACC_DTYPE)
        else:
            acc = jnp.einsum("nvu,...nu->...v", comb.astype(_IN_DTYPE), r,
                             preferred_element_type=_ACC_DTYPE)
        bits_out = _mod2(acc).astype(jnp.uint32)
        return jnp.sum(bits_out * weights32, axis=-1, dtype=jnp.uint32)

    return run_scrub_crc


def make_crc_fn(nbytes: int, block: int = DEFAULT_CRC_BLOCK):
    """Jitted CRC32C (seed 0) over the last axis: (..., L) uint8 -> (...) uint32.

    Seed chaining is applied on the host via crc32c.crc32c_combine (a 32x32
    matvec) — the heavy lifting (the message fold) stays on device.
    """
    if nbytes % block:
        block = _pick_block(nbytes)
    return _crc_fn(nbytes, block)


def _pick_block(nbytes: int) -> int:
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if nbytes % b == 0:
            return b
    return 1


# ---------------------------------------------------------------------------
# Fused encode + scrub CRC (the north-star pass)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _encode_crc_fn(g_bits_key: bytes, shape_key: tuple, nbytes: int,
                   block: int, witness_only: bool = False):
    rows, cols = shape_key
    g_bits = np.frombuffer(g_bits_key, dtype=np.uint8).reshape(rows, cols)
    g_const = jnp.asarray(g_bits)
    crc = _crc_fn(nbytes, block)

    @jax.jit
    def run_xla_encode_crc(data):
        parity = gf2_matmul_bytes_packed(g_const, data)
        chunks = jnp.concatenate([data, parity], axis=-2)
        return crc(chunks) if witness_only else (parity, crc(chunks))

    return run_xla_encode_crc


def encode_readback_bytes(B: int, k: int, m: int, L: int) -> int:
    """Exact D2H bytes one fused encode+CRC dispatch of a (B, k, L)
    batch fetches: the (B, m, L) parity block plus the 4-byte CRC per
    chunk — the data shards the host already holds are NEVER echoed
    back.  tests/test_hbm_cache.py
    (test_encode_stages_entry_and_counts_transfer) holds the transfer
    plane's bytes_d2h counter to this identity."""
    return B * m * L + 4 * B * (k + m)


def make_encode_crc_fn(matrix: np.ndarray, nbytes: int,
                       block: int = DEFAULT_CRC_BLOCK):
    """fn(data (B, k, L)) -> (parity (B, m, L), crcs (B, k+m) uint32).

    One device dispatch per batch: chunks cross PCIe once (parity-only
    readback: the return tuple is exactly what crosses D2H — see
    encode_readback_bytes), encode matmul and scrub CRC fold share the
    on-device bit expansion.
    """
    bits = gf.expand_bitmatrix(np.asarray(matrix, dtype=np.uint8), 8)
    if nbytes % block:
        block = _pick_block(nbytes)
    return _encode_crc_fn(bits.tobytes(), bits.shape, nbytes, block)


def make_encode_crc_witness_fn(matrix: np.ndarray, nbytes: int,
                               block: int = DEFAULT_CRC_BLOCK):
    """Benchmark/scrub variant: fn(data (B, k, L)) -> crcs (B, k+m) uint32.

    Parity never leaves the device — only the 32-bit-per-chunk scrub
    checksums come back, so the host<->device link carries k*L in and
    4*(k+m) out.  The CRCs depend on every parity byte, so the full encode
    provably executes.
    """
    bits = gf.expand_bitmatrix(np.asarray(matrix, dtype=np.uint8), 8)
    if nbytes % block:
        block = _pick_block(nbytes)
    return _encode_crc_fn(bits.tobytes(), bits.shape, nbytes, block,
                          witness_only=True)


@functools.lru_cache(maxsize=64)
def _packet_encode_crc_fn(bits_key: bytes, shape_key: tuple, w: int,
                          packetsize: int, nbytes: int, crc):
    rows, cols = shape_key
    m_bits = np.frombuffer(bits_key, dtype=np.uint8).reshape(rows, cols)
    k, m = cols // w, rows // w

    @jax.jit
    def run_packet_encode_crc(data):
        B = data.shape[0]
        parity = gf2_packet_xor(m_bits, data, w, packetsize)
        # the CRC of a chunk is over its bytes as stored: the fold the
        # byte program has, data and parity slabs apart (no concatenate)
        dcrc = crc(data.reshape(B * k, nbytes)).reshape(B, k)
        pcrc = crc(parity.reshape(B * m, nbytes)).reshape(B, m)
        return parity, jnp.concatenate([dcrc, pcrc], axis=1)

    return run_packet_encode_crc


def make_packet_encode_crc_fn(bits: np.ndarray, w: int, packetsize: int,
                              nbytes: int, crc=None):
    """fn(data (B, k, L)) -> (parity (B, m, L), crcs (B, k+m) uint32)
    for a packet-layout code, from its raw (w*m x w*k) GF(2) bit-matrix
    (the expansion of a cauchy matrix, or a liberation-family matrix
    as it is): parity bit-identical to jerasure's packet-wise
    bit-matrix encode, CRC32C (seed 0) of every chunk as stored, one
    dispatch.  `crc` is the rows fold, (N, L) uint8 -> (N,) uint32:
    the XLA one unless the caller brings another (on a TPU the Pallas
    kernel the byte program uses, `pallas_ec.make_crc_fn`)."""
    bits = np.ascontiguousarray(bits, dtype=np.uint8)
    if nbytes % (w * packetsize):
        raise ValueError(f"chunk of {nbytes} bytes is not whole "
                         f"super-blocks of {w} x {packetsize}")
    return _packet_encode_crc_fn(bits.tobytes(), bits.shape, w, packetsize,
                                 nbytes, crc or make_crc_fn(nbytes))
