"""Erasure-code plugin framework.

TPU-first re-design of the reference's erasure-code tier
(/root/reference/src/erasure-code/): the same plugin/profile/chunk
semantics — init from a profile, systematic k+m chunking with padding,
minimum_to_decode, encode/decode over chunk maps — but the hot math runs
as batched GF(2) matmuls on the TPU MXU (ceph_tpu.ops.ec_kernels) instead
of per-arch SIMD assembly.

Plugins (mirroring ErasureCodePluginRegistry's dlopen set):
  tpu       — the north-star device backend (all matrix techniques)
  jerasure  — numpy-exact port of jerasure techniques (correctness oracle)
  isa       — ISA-L matrix semantics (reed_sol_van / cauchy), table cache
  shec      — shingled EC (not MDS: decode by plan); the tpu plugin's
              shec_multiple / shec_single techniques under its own name
  lrc       — locally repairable codes by layered composition
"""

from .interface import ErasureCode, ErasureCodeError, ErasureCodeInterface
from .registry import ErasureCodePlugin, ErasureCodePluginRegistry, registry

__all__ = [
    "ErasureCodeInterface",
    "ErasureCode",
    "ErasureCodeError",
    "ErasureCodePlugin",
    "ErasureCodePluginRegistry",
    "registry",
]
