"""SHEC plugin: Shingled Erasure Code (k data, m parity, c recoverable).

The reference's plugin API surface (/root/reference/src/erasure-code/
shec/ErasureCodeShec.cc — techniques `single` and `multiple`, defaults
k=4 m=3 c=2) over the matrix codec every plugin shares: the shingled
coding matrix is ops/gf.py `shec_matrix`, and because that matrix is
not MDS, `minimum_to_decode` and the decode rows come from
MatrixErasureCode's plan (recovery may need FEWER than k chunks, and
may fail with k or more).  `plugin=tpu technique=shec_multiple` is the
same code with the device pipeline on top.
"""

from __future__ import annotations

from .matrix_codec import TECHNIQUES, MatrixErasureCode, TpuBackend
from .plugin_jerasure import backend_from_profile
from .registry import ErasureCodePlugin

SHEC_TECHNIQUES = {
    "multiple": TECHNIQUES["shec_multiple"],
    "single": TECHNIQUES["shec_single"],
}


class ErasureCodeShec(MatrixErasureCode):
    DEFAULT_K = 4
    DEFAULT_M = 3
    DEFAULT_C = 2
    DEFAULT_TECHNIQUE = "multiple"

    def __init__(self, backend=None):
        super().__init__(backend=backend or TpuBackend(),
                         techniques=SHEC_TECHNIQUES)


class ErasureCodeShecPlugin(ErasureCodePlugin):
    def factory(self, profile):
        return ErasureCodeShec(backend=backend_from_profile(profile))


def __erasure_code_init__(registry, name):
    registry.add(name, ErasureCodeShecPlugin())
