"""Fused encode+CRC at EVERY power-of-two batch up to the pipeline's
cap, for every primary's codec, and what the HBM cache keeps of an item
of every such bucket up to the configuration's largest RADOS write: a
size mix coalesces to any bucket, where `encode.py` reckons the buckets
of ONE object size.  Then coalesced dispatches of mixed sizes, so that
what the first of them compiles is compiled in set-up.

Beside what `_ec.py` names, of the program: `ops.pipeline.stats()`
(`warmups_inflight`: the warm threads the first staging of an item of a
bucket starts have to be through before a window opens).
"""

from __future__ import annotations

import numpy as np

from benchmark import cluster as cl
from benchmark.pools import ec
from benchmark.warmers import _ec

NEEDS_DATA = False


def pow2_up_to(n: int) -> list[int]:
    return [1 << e for e in range(n.bit_length()) if (1 << e) <= n]


def warm(dep, inflight: int) -> dict:
    import jax
    k, _m, unit = ec.shape(dep.config)
    max_batch = int(next(iter(dep.cluster.osds.values()))
                    .conf.osd_ec_pipeline_max_batch)
    buckets, devices = pow2_up_to(max_batch), jax.devices()

    def probe(c) -> bool:
        return all([c.backend.fused_fn_if_ready(
            c.coding_matrix, (b, k, unit), d) is not None
            for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "encode fns")
    items = pow2_up_to(ec.stripes_per_object(dep.config))
    staged = drive_items(dep, items)
    waited_cache = cl.wait_warm(
        lambda: cl.pipeline_stats()["warmups_inflight"] == 0,
        cl.WARM_BOUND, "the cache's programs of every item bucket")
    return {"encode_buckets": buckets, "item_buckets": items,
            "codecs": len(_ec.codecs(dep)),
            "waited_encode_s": round(waited, 3),
            "waited_cache_programs_s": round(waited_cache, 3),
            "first_stagings": staged,
            "coalesced": drive_mixed(dep, inflight, max_batch)}


def submit(dep, codec, rows: int, tag: str):
    """One encode of `rows` stripes through the op path's own call,
    tagged for the HBM cache as the op path tags it."""
    from ceph_tpu.ops import hbm_cache
    from ceph_tpu.osd import ecutil
    k, _m, unit = ec.shape(dep.config)
    size = rows * k * unit
    return ecutil.encode_object_async(
        codec, ecutil.StripeInfo(k, unit), bytes(size),
        cache=hbm_cache.CacheIntent("bench_warm", tag, (0, rows), size,
                                    unit))


def drop(tags: list) -> None:
    from ceph_tpu.ops import hbm_cache
    for tag in tags:
        hbm_cache.get().invalidate("bench_warm", tag)


def drive_items(dep, items: list[int]) -> int:
    """Stage one item of every bucket, alone: its first staging starts,
    on a warm thread, the programs the cache needs for that bucket
    (`ops/pipeline.py`)."""
    codec = _ec.codecs(dep)[0]
    for rows in items:
        submit(dep, codec, rows, f"first{rows}").result()
    drop([f"first{rows}" for rows in items])
    return len(items)


def drive_mixed(dep, inflight: int, max_batch: int) -> list:
    """Coalesced dispatches of items of seeded mixed sizes: each round
    first fills the dispatcher's window with single items (it coalesces
    only what queues while every lane's window is full), then queues
    `inflight` more.  Best effort: returns, for each round, the
    dispatches the queued items rode."""
    codec = _ec.codecs(dep)[0]
    most = ec.stripes_per_object(dep.config)
    st = cl.pipeline_stats()
    window = int(st["depth"]) * max(1, st["active_devices"])
    rng = np.random.default_rng(0xC05)
    took = []
    for rnd in range(6):
        sizes = [int(rng.integers(1, most + 1)) for _ in range(inflight)]
        while sum(sizes) > max_batch:
            sizes.pop()
        before = cl.pipeline_stats()["dispatches"]
        tags = [f"mix{rnd}.{j}" for j in range(window + len(sizes))]
        handles = [submit(dep, codec, rows, tag) for rows, tag in
                   zip([most] * window + sizes, tags)]
        for h in handles:
            h.result()
        drop(tags)
        took.append(cl.pipeline_stats()["dispatches"] - before - window)
    cl.wait_warm(lambda: cl.pipeline_stats()["warmups_inflight"] == 0,
                 cl.WARM_BOUND, "what the coalesced dispatches started")
    return took
