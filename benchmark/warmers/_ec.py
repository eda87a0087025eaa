"""What the encode and decode warm-ups share.  The parts of the
program named here and in `encode.py` / `decode.py`:

  osd.get_ec_codec(pool)           the pool's codec on one daemon
  codec.backend.fused_fn_if_ready  compiled encode+CRC fn, or None and
                                   a warm-up started (chip_smoke.py's
                                   readiness predicate, PR 23)
  codec.backend.device_fn_if_ready the same for decode
  codec.coding_matrix, codec._decode_rows
  ops.pipeline.next_bucket         the padded batch of n stripes
  perf dump ec_pipeline            depth, active_devices, dispatches
  conf osd_ec_pipeline_max_batch   the most stripes one dispatch takes
  osd.ecutil.encode_object_async, ops.hbm_cache.CacheIntent
                                   the op path's own encode call

A PR that renames one of them breaks set-up with that name in the
error, before any window opens.
"""

from __future__ import annotations

from benchmark import cluster as cl
from benchmark.pools import ec


def codecs(dep) -> list:
    """The distinct codec objects of the pool's primaries (readiness
    is per codec)."""
    seen = {}
    for _acting, pg in dep.pool_pgs().values():
        c = pg.osd.get_ec_codec(pg.pool)
        seen[id(c)] = c
    return list(seen.values())


def most_objects(dep, inflight: int) -> int:
    """As many objects as one dispatch can coalesce: those in flight,
    or what `osd_ec_pipeline_max_batch` stripes hold, whichever is
    fewer."""
    max_batch = int(next(iter(dep.cluster.osds.values()))
                    .conf.osd_ec_pipeline_max_batch)
    return max(1, min(inflight, max_batch // ec.stripes_per_object(
        dep.config)))


def batch_buckets(dep, inflight: int) -> list[int]:
    """Every padded batch the window's ops can coalesce to."""
    from ceph_tpu.ops import pipeline as ec_pipeline
    S = ec.stripes_per_object(dep.config)
    return sorted({ec_pipeline.next_bucket(S * j)
                   for j in range(1, most_objects(dep, inflight) + 1)})


def wait_codecs(dep, probe, what: str) -> float:
    """Wait until `probe(codec)` holds for every primary's codec: for
    the first codec alone, then for all.  The jitted programs are
    shared process-wide but readiness is per codec; codecs that warm
    the same shape at once each trace and lower it again, one after
    the other behind the GIL, while a codec that comes second finds the
    program compiled."""
    cs = codecs(dep)
    waited = cl.wait_warm(lambda: probe(cs[0]), cl.WARM_BOUND,
                          f"the first codec's {what}")
    # no short-circuit: the first poll starts every codec's warm-up
    return waited + cl.wait_warm(lambda: all([probe(c) for c in cs]),
                                 cl.WARM_BOUND, f"every primary's {what}")
