"""An erasure-coded pool: the configuration's `pool_profile` (plugin,
k, m, technique, routing keys) with its `stripe_unit`, `pg_num` PGs.

An object lies on its PG's acting OSDs as k+m shard files named
`<oid>.s<i>` (ECUtil stripe_info_t: shard i is chunk i of every
stripe), each with its cumulative CRC32C in the HashInfo attribute.
"""

from __future__ import annotations


def shape(config: dict) -> tuple[int, int, int]:
    """(k, m, stripe unit) of the configuration's pool."""
    prof = config["pool_profile"]
    return int(prof["k"]), int(prof["m"]), int(config["stripe_unit"])


def stripes_per_object(config: dict) -> int:
    k, _m, unit = shape(config)
    return max(1, -(-int(config["object_bytes"]) // (k * unit)))


def file_bytes(config: dict) -> int:
    return stripes_per_object(config) * int(config["stripe_unit"])


def create(dep, name: str) -> None:
    profile = dict(dep.config["pool_profile"],
                   stripe_unit=str(dep.config["stripe_unit"]))
    dep.rados.create_ec_pool(name, name + "-profile", profile,
                             pg_num=int(dep.config["pg_num"]))


def stored(dep, oid: str) -> list:
    """For each of the k+m positions: (label, bytes, stored crc), or
    None where that position's OSD is not running."""
    from ceph_tpu.osd.pglog import HINFO_KEY
    from ceph_tpu.utils import denc
    k, m, _unit = shape(dep.config)
    _pgid, acting, pg = dep.object_pg(oid)
    out = []
    for shard in range(k + m):
        osd = dep.cluster.osds.get(acting[shard])
        if acting[shard] < 0 or osd is None:
            out.append(None)
            continue
        name = f"{oid}.s{shard}"
        data = bytes(osd.store.read(pg.cid, name))
        hinfo = denc.loads(osd.store.getattr(pg.cid, name, HINFO_KEY))
        out.append((name, data, int(hinfo["crc"])))
    return out


def corrupt(dep, oid: str):
    """Overwrite 16 bytes of the first shard file under the store;
    returns (pgid, the name a scrub must flag)."""
    from ceph_tpu.store import Transaction
    pgid, acting, pg = dep.object_pg(oid)
    name = f"{oid}.s0"
    dep.cluster.osds[acting[0]].store.apply_transaction(
        Transaction().write(pg.cid, name, 0, b"\xff" * 16))
    return pgid, name
