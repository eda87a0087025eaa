"""Boot one configuration's deployment in-process and look inside it.

The cluster is the system under test: `MiniCluster` (the config's mons
and OSD daemons on its store) with one pool.  What kind of pool that
is, and how its objects lie on the OSDs' stores, is the business of
the module the configuration names (`benchmark/pools/<pool_kind>.py`);
nothing here knows an erasure code.  The helpers are copied from
`chip_smoke.py` (sound on the chip, PR 23) so that a later change to
that script cannot move the yardstick: waits on what the device served
instead of sleeps, windowed counter deltas, the counters that must
stay zero.

Counters come from the operator's interface alone: `perf dump` on an
OSD's admin socket (`ec_pipeline`, the process-wide dispatcher and HBM
cache; `ec_codecs`, every codec that OSD holds, whatever its profile
is called).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

# what must stay 0 from boot to the result line
ZERO_COUNTERS = ("device_errors", "quarantines", "drained_to_host",
                 "mesh_degrades", "warm_failures", "result_timeouts",
                 "devset_errors", "route_errors")
PIPE_KEYS = ("dispatches", "dev_dispatches", "host_dispatches", "ops",
             "stripes", "bytes_h2d", "bytes_d2h", "cache_hit", "cache_miss",
             "cache_read_bytes_served") + ZERO_COUNTERS
CODEC_KEYS = ("device_stripe_passes", "host_stripe_passes",
              "device_degraded")
WARM_BOUND = 900.0          # a cold compile cache: tens of seconds a shape
POOL = "bench"


class CheckFailed(Exception):
    """Set-up could not reach the state the cell measures."""


def fs_type(path: str) -> str:
    """Filesystem type of `path` (tmpfs or a disk decides what an fsync
    costs), from /proc/mounts; '?' where that cannot be read."""
    best, kind = "", "?"
    try:
        real = os.path.realpath(path)
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, kind = mnt, typ
    except OSError:
        pass
    return kind


class Deployment:
    """One booted configuration: cluster, pool, client, and the views
    the checks and readers need (pgs, counters, op docs).  `pool` is
    the configuration's pool module."""

    def __init__(self, config: dict, pool, extra_conf: dict | None = None):
        from ceph_tpu.utils.config import Config
        from ceph_tpu.vstart import MiniCluster

        self.config = config
        self.pool = pool
        self.object_bytes = int(config["object_bytes"])
        self.store_dir = tempfile.mkdtemp(prefix="bench_store_")
        conf = dict(config["conf"], **(extra_conf or {}))
        self.cluster = MiniCluster(
            num_mons=int(config["mons"]), num_osds=int(config["osds"]),
            conf=Config(conf), store_kind=config["store"],
            store_dir=self.store_dir).start()
        self.rados = None
        self.io = None

    def open_pool(self) -> None:
        self.rados = self.cluster.client()
        self.pool.create(self, POOL)
        self.io = self.rados.open_ioctx(POOL)
        self.retry(lambda: self.io.write_full("settle", b"s"))
        self.io.remove_object("settle")

    def close(self) -> None:
        try:
            self.cluster.stop()
        finally:
            shutil.rmtree(self.store_dir, ignore_errors=True)

    # -- views ---------------------------------------------------------------

    def retry(self, fn, window: float = 120.0):
        from ceph_tpu.client import RadosError
        end = time.time() + window
        while True:
            try:
                return fn()
            except RadosError:
                if time.time() > end:
                    raise
                self.cluster.tick(0.3)

    def osdmap(self):
        return self.cluster.leader().osdmon.osdmap

    def pool_pgs(self) -> dict:
        """pgid -> (acting, the primary's PG object)."""
        m = self.osdmap()
        out = {}
        for pgid in m.all_pgs():
            if pgid.pool != self.io.pool_id:
                continue
            _up, acting = m.pg_to_up_acting_osds(pgid)
            primary = next(o for o in acting if o >= 0)
            out[pgid] = (list(acting), self.cluster.osds[primary].pgs[pgid])
        return out

    def object_pg(self, oid: str):
        """(pgid, acting, the primary's PG object) of one object."""
        pgid = self.osdmap().object_to_pg(self.io.pool_id, oid)
        acting, pg = self.pool_pgs()[pgid]
        return pgid, acting, pg

    # -- counters ------------------------------------------------------------

    def counters(self) -> dict:
        """One flat snapshot from `perf dump`: the device pipeline's
        counters (HBM cache included; they are process-wide, so one
        daemon's copy is all of them) and the pass counters of every
        codec of every daemon, summed."""
        out = {k: 0 for k in PIPE_KEYS + CODEC_KEYS}
        first = True
        for osd in self.cluster.osds.values():
            dump = osd.asok.execute("perf dump")
            if first:
                out.update({k: dump["ec_pipeline"][k] for k in PIPE_KEYS})
                first = False
            for codec in dump["ec_codecs"].values():
                for k in CODEC_KEYS:
                    out[k] += int(codec.get(k, 0))
        return out

    def historic_ops(self) -> list[dict]:
        """Every OSD's historic op docs (client ops and sub-ops)."""
        docs = []
        for osd in self.cluster.osds.values():
            docs.extend(osd.asok.execute("dump_historic_ops")["ops"])
        return docs


def pipeline_stats() -> dict:
    from ceph_tpu.ops import pipeline as ec_pipeline
    return ec_pipeline.stats()


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


# -- warm-up: wait on what the device served, never sleep ---------------------


def drive_until(op, served, bound: float, what: str) -> float:
    """Drive `op()` until `served()`; seconds waited.  A failed warm-up
    ends the wait with the warm-up's own error."""
    t0 = time.monotonic()
    w0 = pipeline_stats()["warm_failures"]
    tries = 0
    while True:
        op()
        tries += 1
        st = pipeline_stats()
        if st["warm_failures"] != w0:
            raise CheckFailed(f"warm-up failed while waiting for {what}: "
                              f"{st['last_warm_error']}")
        if served():
            return time.monotonic() - t0
        if time.monotonic() - t0 > bound:
            raise CheckFailed(f"{what}: the device never served within "
                              f"{bound:.0f}s ({tries} tries)")
        time.sleep(0.05)


def wait_warm(ready, bound: float, what: str) -> float:
    """Poll `ready()` (asking for compiled fns starts their warm-ups)
    until true; seconds waited."""
    return drive_until(lambda: None, ready, bound, what)


def deep_scrub(pg) -> dict:
    """The operator's path: a deep scrub of one PG on its primary."""
    return pg.scrub(deep=True)
