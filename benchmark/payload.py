"""Client payloads, made from the seed and re-derivable for the check.

A cell's objects are `bases` distinct random buffers of the object
size (made once in set-up, so generating costs the window nothing),
each op stamping (seed, key, version) into the first 16 bytes: every
object version is distinct, and the check rebuilds any of them from
the three numbers alone.
"""

from __future__ import annotations

import struct

import numpy as np

STAMP = struct.Struct("<QII")       # seed, key, version: 16 bytes


class Payloads:
    def __init__(self, seed: int, object_bytes: int, bases: int = 16):
        if object_bytes < STAMP.size:
            raise ValueError("object smaller than its stamp")
        self.seed = int(seed)
        self.object_bytes = object_bytes
        rng = np.random.default_rng([self.seed, 0x9A71])
        self._bases = [rng.integers(0, 256, object_bytes, dtype=np.uint8)
                       .tobytes() for _ in range(bases)]
        self._tails = [memoryview(b)[STAMP.size:] for b in self._bases]

    def _base(self, key: int, version: int) -> int:
        return (key * 7 + version) % len(self._bases)

    def make(self, key: int, version: int) -> bytes:
        return (STAMP.pack(self.seed, key, version)
                + self._bases[self._base(key, version)][STAMP.size:])

    def version_of(self, key: int, data) -> int | None:
        """The version `data` is of `key`, or None when it is no
        version of it at all (wrong length, stamp or body)."""
        if len(data) != self.object_bytes:
            return None
        mv = memoryview(data)
        seed, k, version = STAMP.unpack_from(mv)
        if seed != self.seed or k != key:
            return None
        if mv[STAMP.size:] != self._tails[self._base(key, version)]:
            return None
        return version


def object_name(key: int) -> str:
    return f"obj{key:07d}"
