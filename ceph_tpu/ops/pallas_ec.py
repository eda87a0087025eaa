"""Pallas TPU kernels: GF(2^8) erasure encode on packed bytes.

The XLA formulation in ec_kernels.py materializes an 8x int8 bit-plane
expansion of every chunk in HBM (unpack -> matmul -> pack are separate
fusions), so the pass is HBM-bound at ~1/6 of the packed-byte ceiling.
These kernels keep the expansion in VMEM: each grid cell DMAs a packed
uint8 tile, unpacks to bit-planes in registers/VMEM, runs the GF(2)
matmul on the MXU, folds mod 2, and repacks — HBM traffic is exactly
input + parity bytes.

Replaces the role of the reference's ISA-L assembly
(/root/reference/src/erasure-code/isa/isa-l/erasure_code/*.asm.s,
gf_{2..6}vect_dot_prod pshufb kernels) on TPU.

The generator matrix enters as an (8m, k, 8) int8 constant: entry
[r, j, b] is bit r of the GF(2^8) column multiplier for input byte j's
bit b (expand_bitmatrix column j*8+b).  The contraction folds (k, 8)
against the tile's (k, 8, TL) bit-planes in one dot_general, so no
bit-plane reshape/relayout ever happens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compile_cache, gf

compile_cache.place()

# lanes per grid cell; 8 bit-planes of a TL-byte tile = TL*8k int8 in
# VMEM (k=8, TL=16384 -> 8 MB peak intermediates), inside ~16 MB VMEM.
# Measured on v5e: 16384 beats 4096/8192 (fewer cells amortize per-cell
# DMA setup) while 32768 regresses (VMEM pressure kills double
# buffering).
DEFAULT_TILE = 16384


def _g3_from_matrix(matrix: np.ndarray) -> np.ndarray:
    """(m, k) GF(2^8) matrix -> (8m, 8k) 0/1 int8, rows bit-major.

    Row b*m + i carries output bit b of parity byte i, so the kernel
    repacks with 8 contiguous static slices instead of a reshape or a
    second (unsupported int-mixing) matmul.
    """
    m, k = matrix.shape
    bits = gf.expand_bitmatrix(np.asarray(matrix, dtype=np.uint8), 8)
    perm = [8 * i + b for b in range(8) for i in range(m)]
    return bits[perm].astype(np.int8)


def _encode_kernel(g_ref, mask_ref, x_ref, out_ref, *, m: int, k: int):
    x = x_ref[0]                                   # (k, TL) uint8
    # flat (8k, TL) bit-planes without reshapes: row r = byte r//8's
    # bit r%8 (expand_bitmatrix column order).  The test stays in the
    # uint8 domain (4x the VPU lane density of int32 shifts): row r's
    # mask is the constant 1 << (r % 8), broadcast from the mask input.
    xrep = jnp.repeat(x, 8, axis=0)                # (8k, TL)
    bits = ((xrep & mask_ref[:]) != 0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        g_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                              # (8m, TL) bit-major rows
    parity = acc[0:m] & 1
    for b in range(1, 8):
        parity |= (acc[b * m:(b + 1) * m] & 1) << b
    out_ref[0] = parity.astype(jnp.uint8)


@functools.lru_cache(maxsize=256)
def _encode_call(g_key: bytes, mk: tuple[int, int], L: int, tile: int,
                 interpret: bool):
    m, k = mk
    g3 = np.frombuffer(g_key, dtype=np.int8).reshape(8 * m, 8 * k)
    g_const = jnp.asarray(g3)
    ntiles = L // tile

    kernel = functools.partial(_encode_kernel, m=m, k=k)
    mask_np = np.tile((1 << (np.arange(8 * k) % 8)).astype(np.uint8)
                      [:, None], (1, tile))
    mask_const = jnp.asarray(mask_np)

    @jax.jit
    def run_encode(data):                           # (B, k, L) uint8
        B = data.shape[0]
        return pl.pallas_call(
            kernel,
            name="ec_encode",
            grid=(B, ntiles),
            in_specs=[
                pl.BlockSpec((8 * m, 8 * k), lambda b, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((8 * k, tile), lambda b, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, k, tile), lambda b, j: (b, 0, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, m, tile), lambda b, j: (b, 0, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, m, L), jnp.uint8),
            interpret=interpret,
        )(g_const, mask_const, data)

    return run_encode


def _pick_tile(L: int, tile: int = DEFAULT_TILE) -> int | None:
    """Largest lane tile (multiple of 128) dividing L, or None."""
    t = min(tile, L)
    while t >= 128:
        if L % t == 0 and t % 128 == 0:
            return t
        t -= 128
    return None


def supports(L: int) -> bool:
    return _pick_tile(L) is not None


def make_encode_fn(matrix: np.ndarray, L: int, tile: int = DEFAULT_TILE,
                   interpret: bool | None = None):
    """Jitted pallas encode: (B, k, L) uint8 -> (B, m, L) uint8 parity.

    L must be a multiple of 128 (use ec_kernels.make_codec_fn for odd
    sizes).  `interpret` defaults to True off-TPU so tests exercise the
    same kernel on the CPU devices.
    """
    m, k = np.asarray(matrix).shape
    t = _pick_tile(L, tile)
    if t is None:
        raise ValueError(f"L={L} not tileable (needs multiple of 128)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    g3 = _g3_from_matrix(np.asarray(matrix, dtype=np.uint8))
    fn = _encode_call(g3.tobytes(), (m, k), L, t, interpret)

    def call(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        out = fn(data)
        return out[0] if squeeze else out

    return call


# ---------------------------------------------------------------------------
# CRC32C (ceph raw-seed semantics, seed 0) over rows
#
# Whole-tile fold + cross-tile Horner recurrence: each grid step folds a
# (rows_block, tile) slab with the tile-length message matrix on the MXU
# (bits stay in VMEM), then advances the running 32-bit state:
#     acc <- A_tile @ acc  ^  fold(tile)            (all GF(2))
# The j grid axis is sequential ("arbitrary") so the recurrence is legal;
# rows are independent and parallel.
# ---------------------------------------------------------------------------

CRC_ROWS_BLOCK = 32       # rows per grid cell; bits slab = rows*8*tile int8
CRC_TILE = 8192           # bytes per fold step; foldT = (8*tile, 32) int8


def _crc_kernel(foldT_ref, adv_ref, lanemask_ref, x_ref, out_ref, acc_ref,
                *, ntiles: int):
    j = pl.program_id(1)
    x = x_ref[:]                                    # (NC, TILE) uint8
    # Lane-expand x 8-fold with whole-tile copies (jnp.repeat along the
    # minor axis is unsupported for 8-bit): copy c holds bit c of every
    # byte, i.e. bit (byte j, bit b) lands at lane b*TILE + j.  The fold
    # matrix columns are permuted to this copy-major order host-side.
    brep = jnp.concatenate([x] * 8, axis=1)         # (NC, 8*TILE)
    bits = ((brep & lanemask_ref[:]) != 0).astype(jnp.int8)
    r = jax.lax.dot_general(
        bits, foldT_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1                                           # (NC, 32)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = r

    @pl.when(j > 0)
    def _():
        adv = jax.lax.dot_general(
            acc_ref[:].astype(jnp.int8), adv_ref[:],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc_ref[:] = (adv + r) & 1

    @pl.when(j == ntiles - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.lru_cache(maxsize=64)
def _crc_call(L: int, tile: int, rows_block: int, interpret: bool):
    from . import crc32c as crc_mod

    ntiles = L // tile
    fold = crc_mod.message_matrix(tile)             # cols: byte j, bit b
    # permute columns to the kernel's copy-major lane order b*tile + j
    perm = np.empty(8 * tile, dtype=np.int64)
    lanes = np.arange(8 * tile)
    perm[(lanes % 8) * tile + lanes // 8] = lanes
    foldT = jnp.asarray(fold[:, perm].T.astype(np.int8))
    # advance the running state over one tile of message: the state from
    # earlier bytes sits `tile` zero-bytes further from the end
    advT = jnp.asarray(crc_mod.advance_matrix(tile).T.astype(np.int8))
    lanemask = jnp.asarray(np.tile(
        (1 << (np.arange(8 * tile) // tile)).astype(np.uint8)[None, :],
        (rows_block, 1)))
    kernel = functools.partial(_crc_kernel, ntiles=ntiles)
    weights32 = jnp.asarray([1 << i for i in range(32)], dtype=jnp.uint32)

    @jax.jit
    def run_crc(rows):                              # (N, L) uint8
        N = rows.shape[0]
        pad = (-N) % rows_block
        if pad:
            rows = jnp.concatenate(
                [rows, jnp.zeros((pad, L), jnp.uint8)], axis=0)
        NP = N + pad
        bits_out = pl.pallas_call(
            kernel,
            name="crc_fold",
            grid=(NP // rows_block, ntiles),
            in_specs=[
                pl.BlockSpec((8 * tile, 32), lambda n, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((32, 32), lambda n, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rows_block, 8 * tile), lambda n, j: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((rows_block, tile), lambda n, j: (n, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rows_block, 32), lambda n, j: (n, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((NP, 32), jnp.int32),
            scratch_shapes=[pltpu.VMEM((rows_block, 32), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(foldT, advT, lanemask, rows)
        crcs = jnp.sum(bits_out.astype(jnp.uint32) * weights32[None, :],
                       axis=-1, dtype=jnp.uint32)
        return crcs[:N]

    return run_crc


def make_crc_fn(L: int, tile: int = CRC_TILE,
                rows_block: int = CRC_ROWS_BLOCK,
                interpret: bool | None = None):
    """Jitted CRC32C (seed 0): rows (N, L) uint8 -> (N,) uint32."""
    t = _pick_tile(L, tile)
    if t is None:
        raise ValueError(f"L={L} not tileable (needs multiple of 128)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _crc_call(L, t, rows_block, interpret)


def make_encode_crc_fn(matrix: np.ndarray, L: int,
                       interpret: bool | None = None):
    """fn(data (B, k, L)) -> (parity (B, m, L), crcs (B, k+m) uint32).

    Pallas encode + pallas CRC composed under one jit: parity stays in
    HBM between the two kernels; the scrub CRCs cover data and parity
    chunks (HashInfo semantics, osd/ECUtil.cc:140).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    run = _encode_crc_call(matrix.tobytes(), matrix.shape, L, interpret)

    def call(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        parity, crcs = run(data)
        return (parity[0], crcs[0]) if squeeze else (parity, crcs)

    return call


@functools.lru_cache(maxsize=64)
def _encode_crc_call(m_key: bytes, mk: tuple[int, int], L: int,
                     interpret: bool):
    """One jitted composition per (matrix, L): every codec in the
    process (13 OSDs share one chip) reuses it instead of tracing and
    compiling its own copy of the same program."""
    m, k = mk
    matrix = np.frombuffer(m_key, dtype=np.uint8).reshape(m, k)
    enc = make_encode_fn(matrix, L, interpret=interpret)
    crc = make_crc_fn(L, interpret=interpret)

    @jax.jit
    def run_encode_crc(data):
        B = data.shape[0]
        parity = enc(data)
        # CRC data and parity slabs separately: a concatenate would
        # copy every byte through HBM again just to flatten the rows
        dcrc = crc(data.reshape(B * k, L)).reshape(B, k)
        pcrc = crc(parity.reshape(B * m, L)).reshape(B, m)
        crcs = jnp.concatenate([dcrc, pcrc], axis=1)
        return parity, crcs

    return run_encode_crc
