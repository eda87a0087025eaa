"""An S3 gateway over an erasure-coded data pool, as plain data
structures: what a client must read back, which RADOS objects a PUT
makes and in which writes, and what the data pool's stores hold of
each.  Numpy and the standard library over `benchmark/oracle.py`;
imports nothing of the program.

Written from the reference's description (Ceph 11.0.2
src/rgw/rgw_rados.cc):

  * `RGWRados::get_max_chunk_size`: the chunk is `rgw_max_chunk_size`
    (512 KiB) in whole multiples of the data pool's required alignment
    (an EC pool's stripe width, k x stripe_unit).
  * `RGWPutObjProcessor_Atomic` + `RGWObjManifest::generator`: the
    first chunk is held back and becomes the HEAD object (manifest
    stripe 0, `max_head_size` = one chunk); what follows goes to TAIL
    objects, manifest stripe n (1 up) holding the bytes [head +
    (n-1) x `rgw_obj_stripe_size`, head + n x `rgw_obj_stripe_size`)
    (4 MiB a stripe), each written chunk by chunk at the offset that is
    its size: a create, then appends.  (So an object of 4 MiB + 1 has a
    head and ONE tail object of 3.5 MiB + 1; the second tail object
    starts at 4.5 MiB.)
  * the tail objects are named under the write's tag
    (`<marker>__shadow_<key>.<prefix>_<n>` there; here
    `<head>.shadow.<tag>_<n>`, the head `obj.<bucket>/<key>`, both
    percent-quoted: the program's names, which a reference has to be
    told).
  * an EC pool stores a RADOS object as k+m shard files (ECUtil
    stripe_info_t), each with its cumulative CRC32C: `oracle.shard_files`
    and `oracle.crc32c` (an empty object is one stripe of zeros there,
    as a `write_full` of nothing lies on this system's EC pools).

GF(2^8) and CRC32C arithmetic, MD5: every tolerance 0.
"""

from __future__ import annotations

import hashlib
from urllib.parse import quote

from benchmark import oracle

MAX_CHUNK_SIZE = 512 << 10          # rgw_max_chunk_size
OBJ_STRIPE_SIZE = 4 << 20           # rgw_obj_stripe_size


def etag(data) -> str:
    return hashlib.md5(data).hexdigest()


def shape(config: dict) -> tuple[int, int, int]:
    prof = config["pool_profile"]
    if prof["technique"] != "reed_sol_van":
        raise ValueError(f"this reference encodes reed_sol_van, not "
                         f"{prof['technique']!r}")
    return int(prof["k"]), int(prof["m"]), int(config["stripe_unit"])


def chunk_size(config: dict) -> int:
    """`get_max_chunk_size` on the configuration's data pool."""
    k, _m, unit = shape(config)
    align = k * unit
    if MAX_CHUNK_SIZE <= align:
        return align
    return MAX_CHUNK_SIZE - MAX_CHUNK_SIZE % align


def layout(size: int, config: dict) -> dict:
    """The RADOS objects a PUT of `size` bytes makes: {"head": bytes
    in the head, "tails": [(manifest stripe n, first byte, bytes,
    [bytes of each write to it, the first a create, the others
    appends])]}."""
    chunk = chunk_size(config)
    head = min(size, chunk)
    tails, at, n = [], head, 1
    while at < size:
        length = min(OBJ_STRIPE_SIZE, size - at)
        writes = [min(chunk, length - off)
                  for off in range(0, length, chunk)]
        tails.append((n, at, length, writes))
        at += length
        n += 1
    return {"head": head, "tails": tails}


def head_name(bucket: str, key: str) -> str:
    return f"obj.{quote(bucket, safe='')}/{quote(key, safe='')}"


def tail_name(bucket: str, key: str, tag: str, n: int) -> str:
    return f"{head_name(bucket, key)}.shadow.{tag}_{n}"


def rados_objects(bucket: str, key: str, tag: str, data,
                  config: dict) -> dict[str, bytes]:
    """{RADOS object name: its bytes} of the version `data` of the key,
    written under `tag`."""
    lay = layout(len(data), config)
    out = {head_name(bucket, key): bytes(data[:lay["head"]])}
    for n, at, length, _writes in lay["tails"]:
        out[tail_name(bucket, key, tag, n)] = bytes(data[at: at + length])
    return out


def stored(data: bytes, config: dict) -> list:
    """(shard file, cumulative CRC32C) for each of the k+m positions of
    ONE RADOS object holding `data`."""
    k, m, unit = shape(config)
    files = oracle.shard_files(bytes(data), k, m, unit)
    crcs = oracle.crc32c(files)
    return [(f.tobytes(), int(c)) for f, c in zip(files, crcs)]


class Store:
    """bucket -> key -> history of versions, oldest first.  A version
    is kept as (version, size, etag): its bytes are a function of the
    seed and are rebuilt by whoever compares them (a window's PUTs
    would be 800 MB kept whole)."""

    def __init__(self):
        self.buckets: dict[str, dict[str, list]] = {}

    def create(self, bucket: str) -> None:
        self.buckets.setdefault(bucket, {})

    def put(self, bucket: str, key: str, version: int, data) -> tuple:
        rec = (version, len(data), etag(data))
        self.buckets[bucket].setdefault(key, []).append(rec)
        return rec

    def history(self, bucket: str, key: str) -> list:
        return list(self.buckets[bucket].get(key, []))

    def get(self, bucket: str, key: str) -> tuple | None:
        """The newest version's (version, size, etag)."""
        hist = self.buckets[bucket].get(key)
        return hist[-1] if hist else None

    def list(self, bucket: str) -> dict[str, tuple]:
        """key -> (size, etag) of its newest version, as a listing
        names it."""
        return {k: (h[-1][1], h[-1][2])
                for k, h in sorted(self.buckets[bucket].items())}
