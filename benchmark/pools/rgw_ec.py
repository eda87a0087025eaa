"""radosgw's placement (config-ref.rst, "Pools"): an erasure-coded DATA
pool made as `pools/ec.py` makes one from the configuration's
`pool_profile` (this is `dep.io`'s pool, so `pool_pgs()` and the
warmers see its codecs), a replicated INDEX pool and a replicated
EXTRA pool (`index_pool`, `data_extra_pool` of the configuration:
size, min_size, pg_num), which may not be erasure-coded: an EC pool
has no omap.

A RADOS object of the data pool lies as `pools/ec.py` says (k+m shard
files `<oid>.s<i>` with their CRC32C).  A bucket's index object lies
whole, omap included, on each of its index PG's `size` acting OSDs.
"""

from __future__ import annotations

import inspect
import time

from benchmark.pools import ec
from benchmark.pools.ec import (file_bytes, shape,  # noqa: F401
                                stripes_per_object)
from ceph_tpu.rgw import RGWDaemon

# a gateway that takes no placement cannot serve this configuration:
# say so before a cluster boots for it, not after its warm-up
if "index_pool" not in inspect.signature(RGWDaemon.__init__).parameters:
    raise TypeError("ceph_tpu.rgw.RGWDaemon takes no placement "
                    "(index_pool, data_pool, data_extra_pool): it cannot "
                    "serve S3 objects from an erasure-coded data pool")


def index_pool(name: str) -> str:
    return name + "-index"


def extra_pool(name: str) -> str:
    return name + "-extra"


def create(dep, name: str) -> None:
    from ceph_tpu.client import RadosError
    ec.create(dep, name)
    for pool, spec in ((index_pool(name), dep.config["index_pool"]),
                       (extra_pool(name), dep.config["data_extra_pool"])):
        dep.rados.create_pool(pool, pg_num=int(spec["pg_num"]),
                              size=int(spec["size"]))
        rv, out, _ = dep.rados.mon_command({
            "prefix": "osd pool set", "pool": pool, "var": "min_size",
            "val": str(spec["min_size"])})
        if rv != 0:
            raise RuntimeError(f"min_size of {pool}: {rv} {out}")
        # every PG of it serves before the gateway boots on it
        io = dep.rados.open_ioctx(pool)
        m = dep.rados.monc.osdmap
        names: dict = {}
        for i in range(4096):
            names.setdefault(m.object_to_pg(io.pool_id, f"settle{i}"),
                             f"settle{i}")
            if len(names) == int(spec["pg_num"]):
                break
        end = time.time() + 120.0
        for oid in names.values():
            while True:
                try:
                    io.write_full(oid, b"s")
                    io.remove_object(oid)
                    break
                except RadosError:
                    if time.time() > end:
                        raise
                    dep.cluster.tick(0.3)


def stored(dep, oid: str) -> list:
    """The data pool's k+m positions of one RADOS object, as
    `pools/ec.py` lists them."""
    return ec.stored(dep, oid)


def index_replicas(dep, oid: str) -> list:
    """For each acting OSD of the index object's PG: (label, its omap
    as that OSD's store holds it, or None where it holds no such
    object)."""
    from ceph_tpu.store.objectstore import StoreError
    m = dep.osdmap()
    pool = m.pool_by_name(index_pool(dep.io.pool_name))
    pgid = m.object_to_pg(pool.id, oid)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    out = []
    for o in acting:
        osd = dep.cluster.osds[o]
        try:
            omap = osd.store.omap_get(osd.pgs[pgid].cid, oid)
        except StoreError:
            omap = None
        out.append((f"{oid}@osd.{o}", omap))
    return out
