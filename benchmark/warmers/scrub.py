"""Deep-scrub the pool, through the operator's path, until one whole
pass is served by the device alone: the scrub CRC row buckets compile
in the background and the host serves meanwhile."""

from __future__ import annotations

from benchmark import cluster as cl

NEEDS_DATA = True


def warm(dep, inflight: int) -> dict:
    pgs = [pg for _a, pg in dep.pool_pgs().values()]
    last: dict = {}

    def one_pass():
        before = cl.pipeline_stats()
        for pg in pgs:
            cl.deep_scrub(pg)
        after = cl.pipeline_stats()
        last["dev"] = after["dev_dispatches"] - before["dev_dispatches"]
        last["host"] = after["host_dispatches"] - before["host_dispatches"]

    waited = cl.drive_until(one_pass,
                            lambda: last["dev"] > 0 and last["host"] == 0,
                            cl.WARM_BOUND, "deep scrub")
    return {"waited_scrub_s": round(waited, 3)}
