"""An erasure-coded pool whose code lays more shard files than k + m
of its profile, at positions of its own (lrc k=4 m=2 l=3: eight, as
`DD__DD__`): `pools/ec.py` with the number of POSITIONS taken from the
configuration's `shards`.

An object lies on its PG's acting OSDs as `shards` files named
`<oid>.s<p>`, p the acting position, each with its cumulative CRC32C in
the HashInfo attribute; which chunk lies at which position is for the
configuration's reference to say.  `k`, `m` and the stripe unit (what
the warm-ups and `file_bytes` ask for) are the profile's, as in
`ec.py`.
"""

from __future__ import annotations

from benchmark.pools.ec import (corrupt, create, file_bytes,  # noqa: F401
                                shape, stripes_per_object)


def stored(dep, oid: str) -> list:
    """For each of the configuration's `shards` positions: (label,
    bytes, stored crc), or None where that position's OSD is not
    running."""
    from ceph_tpu.osd.pglog import HINFO_KEY
    from ceph_tpu.utils import denc
    _pgid, acting, pg = dep.object_pg(oid)
    out = []
    for shard in range(int(dep.config["shards"])):
        osd = dep.cluster.osds.get(acting[shard])
        if acting[shard] < 0 or osd is None:
            out.append(None)
            continue
        name = f"{oid}.s{shard}"
        data = bytes(osd.store.read(pg.cid, name))
        hinfo = denc.loads(osd.store.getattr(pg.cid, name, HINFO_KEY))
        out.append((name, data, int(hinfo["crc"])))
    return out
