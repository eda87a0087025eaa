"""An RBD image on an erasure-coded pool behind a replicated writeback
tier, as plain data structures: what a client must read back, what the
base holds of a flushed object, what the tier holds of a resident one.
Imports nothing of the program.

  Image     the image as one `bytearray` per data object (order-22: 4
            MiB each), with `write(offset, data)` and `read(offset,
            length)`: whatever is in the tier, a read through the
            overlay returns these bytes.
  stored    the k+m shard files of a flushed object and their
            cumulative CRC32C, by `references/reed_sol_van.py` (the
            base pool is `ec-k8m3-rados-4m`'s).
  resident  the tier's copies of a resident object: `size` equal ones.
"""

from __future__ import annotations

from benchmark.references import reed_sol_van


class Image:
    def __init__(self, size: int, object_bytes: int):
        if size % object_bytes:
            raise ValueError("the image is not a whole number of objects")
        self.size, self.object_bytes = int(size), int(object_bytes)
        self.objects: dict[int, bytearray] = {}

    def _object(self, n: int) -> bytearray:
        # an object never written reads as zeros (a sparse image)
        return self.objects.setdefault(n, bytearray(self.object_bytes))

    def write(self, offset: int, data) -> None:
        if offset < 0 or offset + len(data) > self.size:
            raise ValueError("write outside the image")
        data = memoryview(bytes(data))
        while len(data):
            n, at = divmod(offset, self.object_bytes)
            take = min(len(data), self.object_bytes - at)
            self._object(n)[at:at + take] = data[:take]
            data, offset = data[take:], offset + take

    def read(self, offset: int, length: int) -> bytes:
        if offset < 0 or length < 0 or offset + length > self.size:
            raise ValueError("read outside the image")
        out = bytearray()
        while length:
            n, at = divmod(offset, self.object_bytes)
            take = min(length, self.object_bytes - at)
            out += self._object(n)[at:at + take]
            offset, length = offset + take, length - take
        return bytes(out)

    def object(self, n: int) -> bytes:
        """Data object `n`, whole."""
        return bytes(self._object(n))


def stored(object_bytes: bytes, config: dict) -> list:
    """(shard file, CRC32C) for each of the base's k+m positions."""
    return reed_sol_van.stored(bytes(object_bytes), config)


def resident(object_bytes: bytes, config: dict) -> list:
    """The tier's copies of a resident object: `tier.size` equal ones."""
    return [bytes(object_bytes)] * int(config["tier"]["size"])
