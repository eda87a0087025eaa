"""Fused encode+CRC at every padded batch the cell's writes can
coalesce to, for every primary's codec; then coalesced dispatches, so
that what the first of them compiles is compiled in set-up."""

from __future__ import annotations

from benchmark import cluster as cl
from benchmark.pools import ec
from benchmark.warmers import _ec

NEEDS_DATA = False


def warm(dep, inflight: int) -> dict:
    import jax
    k, _m, unit = ec.shape(dep.config)
    buckets, devices = _ec.batch_buckets(dep, inflight), jax.devices()

    def probe(c) -> bool:
        return all([c.backend.fused_fn_if_ready(
            c.coding_matrix, (b, k, unit), d) is not None
            for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "encode fns")
    return {"encode_buckets": buckets, "codecs": len(_ec.codecs(dep)),
            "waited_encode_s": round(waited, 3),
            "coalesced": drive_coalesced(dep, inflight)}


def drive_coalesced(dep, inflight: int) -> list:
    """Push 2..n objects' encodes through the pipeline in ONE dispatch
    each, tagged for the HBM cache as the op path tags them.  The
    readiness predicates do not cover what the first coalesced dispatch
    of a process compiles (the per-item device slices the cache keeps,
    and on the chip the fused program again at the coalesced bucket:
    seen 2 s into a window, my chip run, PR 24), and whether two ops
    coalesce in set-up is otherwise left to timing.  The dispatcher
    coalesces only what queues while every lane's overlap window is
    full, so each round first fills the window with single objects.
    Best effort: returns, for each n, the fewest dispatches that
    window + n objects took (window + 1 means all n rode one)."""
    from ceph_tpu.ops import hbm_cache
    from ceph_tpu.osd import ecutil
    k, _m, unit = ec.shape(dep.config)
    codec = _ec.codecs(dep)[0]
    sinfo = ecutil.StripeInfo(k, unit)
    st = cl.pipeline_stats()
    window = int(st["depth"]) * max(1, st["active_devices"])
    payload = bytes(dep.object_bytes)
    cid, fewest = "bench_warm", {}
    for n in range(2, _ec.most_objects(dep, inflight) + 1):
        for attempt in range(3):
            before = cl.pipeline_stats()["dispatches"]
            handles = [ecutil.encode_object_async(
                codec, sinfo, payload, cache=hbm_cache.CacheIntent(
                    cid, f"warm{j}", (n, attempt), dep.object_bytes,
                    unit)) for j in range(window + n)]
            for h in handles:
                h.result()
            for j in range(window + n):
                hbm_cache.get().invalidate(cid, f"warm{j}")
            took = cl.pipeline_stats()["dispatches"] - before
            fewest[n] = min(took, fewest.get(n, took))
            if took == window + 1:
                break
    return [f"{n}:{took - window}" for n, took in fewest.items()]
