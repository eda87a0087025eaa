"""An erasure-coded base pool behind a replicated writeback cache tier
(erasure-code.rst, "Erasure coded pool and cache tiering"): `pools/ec.py`
makes the base from the configuration's `pool_profile`; the tier is the
configuration's `tier` (size, min_size, pg_num, the agent's targets and
ratios), linked with `osd tier add` and `osd tier cache-mode`.  The
OVERLAY is not set here: the traffic mix sets it once the base holds
its data, as an operator does who puts a tier in front of a pool.

The tier's target is stated for the source's 4 MiB objects
(`image.order`); where the configuration's `object_bytes` is another
(the self-check's tiny sizes), the target is that many OBJECTS of it.

An object lies in the base as `pools/ec.py` says (k+m shard files
`<oid>.s<i>` with their CRC32C), and in the tier, while resident, as
one whole copy on each of its PG's `size` acting OSDs, dirty until the
agent flushed it.
"""

from __future__ import annotations

import time

from benchmark.pools import ec
from benchmark.pools.ec import (file_bytes, shape,  # noqa: F401
                                stripes_per_object)

TIER_SUFFIX = "-cache"
TIER_SETTINGS = ("cache_target_dirty_ratio", "cache_target_dirty_high_ratio",
                 "cache_target_full_ratio", "cache_min_flush_age",
                 "cache_min_evict_age", "hit_set_count", "hit_set_period")


def tier_name(base: str) -> str:
    return base + TIER_SUFFIX


def tier_target_bytes(config: dict) -> int:
    """`tier.target_max_bytes`, in objects of the configuration's
    `object_bytes`."""
    objects = int(config["tier"]["target_max_bytes"]) >> \
        int(config["image"]["order"])
    return objects * int(config["object_bytes"])


def mon(dep, cmd: dict) -> str:
    rv, out, _ = dep.rados.mon_command(cmd)
    if rv != 0:
        raise RuntimeError(f"{cmd}: {rv} {out}")
    return out


def create(dep, name: str) -> None:
    from ceph_tpu.client import RadosError
    ec.create(dep, name)
    tier, cache = dep.config["tier"], tier_name(name)
    dep.rados.create_pool(cache, pg_num=int(tier["pg_num"]),
                          size=int(tier["size"]))
    for var, val in [("min_size", tier["min_size"]),
                     ("target_max_bytes", tier_target_bytes(dep.config))] \
            + [(v, tier[v]) for v in TIER_SETTINGS]:
        mon(dep, {"prefix": "osd pool set", "pool": cache, "var": var,
                  "val": str(val)})
    mon(dep, {"prefix": "osd tier add", "pool": name, "tierpool": cache})
    mon(dep, {"prefix": "osd tier cache-mode", "pool": cache,
              "mode": tier["cache_mode"]})
    # every tier PG serves: a read of a name nobody wrote promotes,
    # finds nothing at the base and answers ENOENT
    io = dep.rados.open_ioctx(cache)
    m = dep.rados.monc.osdmap
    names: dict = {}
    for i in range(4096):
        names.setdefault(m.object_to_pg(io.pool_id, f"settle{i}"),
                         f"settle{i}")
        if len(names) == int(tier["pg_num"]):
            break
    end = time.time() + 120.0
    for name in names.values():
        while True:
            try:
                io.read(name)
            except RadosError as e:
                if e.errno == 2:
                    break
                if time.time() > end:
                    raise
                dep.cluster.tick(0.3)


def set_overlay(dep, name: str) -> None:
    """`osd tier set-overlay`, and wait until the client's map has it."""
    mon(dep, {"prefix": "osd tier set-overlay", "pool": name,
              "overlaypool": tier_name(name)})
    end = time.time() + 60.0
    while dep.rados.monc.osdmap.pools[dep.io.pool_id].write_tier < 0:
        if time.time() > end:
            raise RuntimeError("the overlay never reached the client's map")
        dep.cluster.tick(0.2)


def stored(dep, oid: str) -> list:
    """The base's k+m positions, as `pools/ec.py` lists them."""
    return ec.stored(dep, oid)


def tier_pgs(dep) -> dict:
    """pgid -> (acting, the primary's PG object) of the tier pool."""
    m = dep.osdmap()
    pool = m.pool_by_name(tier_name(dep.io.pool_name))
    out = {}
    for pgid in m.all_pgs():
        if pgid.pool != pool.id:
            continue
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        out[pgid] = (list(acting), dep.cluster.osds[primary].pgs[pgid])
    return out


def tier_copies(dep, oid: str) -> list:
    """For each acting OSD of the object's tier PG: (label, bytes or
    None where that OSD holds no copy, dirty)."""
    from ceph_tpu.osd.pglog import DIRTY_KEY
    from ceph_tpu.store.objectstore import StoreError
    m = dep.osdmap()
    pool = m.pool_by_name(tier_name(dep.io.pool_name))
    pgid = m.object_to_pg(pool.id, oid)
    acting, pg = tier_pgs(dep)[pgid]
    out = []
    for o in acting:
        store = dep.cluster.osds[o].store
        label = f"{oid}@osd.{o}"
        try:
            data = bytes(store.read(pg.cid, oid))
            dirty = DIRTY_KEY in store.getattrs(pg.cid, oid)
        except StoreError:
            data, dirty = None, False
        out.append((label, data, dirty))
    return out


def tier_status(dep) -> dict:
    """pgid (as text) -> that tier PG's line of its primary's `tier
    status` (modes, running counts, promotes / flushes / evicts)."""
    out = {}
    for osd in dep.cluster.osds.values():
        out.update(osd.asok.execute("tier status")["pgs"])
    return out


def tier_counters(dep) -> dict:
    """The tier's counters of `perf dump`'s `osd` block, summed."""
    out: dict = {}
    for osd in dep.cluster.osds.values():
        for k, v in osd.asok.execute("perf dump")["osd"].items():
            if k.startswith(("tier_", "agent_")):
                out[k] = out.get(k, 0) + int(v)
    return out
