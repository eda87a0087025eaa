"""`closed_loop` over a pool that is being repaired: set-up pre-writes
as that module does, then fails one OSD for good and lets the cluster
rebuild what it held; the loop and the read-back are that module's own
functions, and the verdict holds the run to the configuration's fourth
guarantee.

The failure goes the operator's way and no other: the daemon is killed
(no goodbye), `osd down`, `osd out` (`MiniCluster.kill_osd`,
`mark_osd_down`, `mark_osd_out`).  The mon's map change, CRUSH, peering,
backfill and the shard rebuilds are the program's.  The victim is the
lowest-numbered acting OSD that is primary of no PG (`closed_loop`'s
rule for `fail_osds`).  Set-up ends when every PG that held the victim
has a new acting set without it and has landed its first rebuilt shard
file (at a position whose OSD changed); then the loop ramps and the
window opens, with the repair running.

Parameters (traffic file), beside `closed_loop`'s (`prewrite_objects`
is raised to `least_objects` where a rehearsal asks for fewer):
  osds_out                  OSDs failed and marked out in set-up (1)
  first_shard_bound_s       bound on the wait for the first rebuilt
                            shard of every remapped PG
  clean_bound_s             bound on the wait for active+clean after
                            the window
  stored_sample             objects whose stored state the harness
                            compares with the reference after clean
  stored_sample_backfilled  at least this many of them from PGs that
                            held the victim

After the window: the cluster is waited clean (`wait_for_clean`, which
names what is not on timeout); every PG's acting set is k+m distinct
OSDs that are up and in, the victim not among them; at every position
whose OSD changed the PG's collection lists one shard file an object
of the PG's log; every PG deep-scrubs with nothing inconsistent; and
of the sampled objects no position's file is missing (the harness
compares the files that are there with the reference, bit for bit and
CRC for CRC).  The window has to have rebuilt something.

The OSDs' recovery counters (`perf dump`, block `osd`) are not among
`benchmark/cluster.py`'s fixed keys: their deltas over the window are
logged here, with how long after the window opened the last backfill
session and the last rebuild ended.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.generators import closed_loop
from benchmark.payload import object_name

RECOVERY_COUNTERS = ("recovery_pushes", "recovery_bytes", "backfill_rounds",
                     "backfill_objects", "rebuild_cache_served",
                     "rebuild_local", "rebuild_full")


def recovery_counters(dep) -> dict:
    """The recovery counters of every running OSD, summed; a counter
    the program does not have reads 0."""
    out = dict.fromkeys(RECOVERY_COUNTERS, 0)
    for osd in dep.cluster.osds.values():
        block = osd.asok.execute("perf dump")["osd"]
        for name in RECOVERY_COUNTERS:
            out[name] += int(block.get(name, 0))
    return out


def acting_sets(dep) -> dict:
    return {pgid: acting for pgid, (acting, _pg) in dep.pool_pgs().items()}


def shard_files(dep, pgid, position: int, osd_id: int) -> int:
    """Shard files of `position` in the PG's collection on `osd_id`."""
    osd = dep.cluster.osds[osd_id]
    pg = osd.pgs.get(pgid)
    if pg is None:
        return 0
    suffix = f".s{position}"
    return sum(1 for name in osd.store.collection_list(pg.cid)
               if name.endswith(suffix))


def repairing(dep) -> tuple[bool, bool]:
    """(a backfill session is queued or running, any repair is)."""
    osds = list(dep.cluster.osds.values())
    backfilling = any(osd._backfills_active for osd in osds)
    return backfilling, backfilling or any(
        getattr(osd, "_rebuilds_pending", None) for osd in osds)


def least_objects(dep) -> int:
    """The fewest objects with which the cell is itself: a PG's history
    twice as long as its log, or a new member is recovered from the log
    and nothing is backfilled."""
    osd = next(iter(dep.cluster.osds.values()))
    return 2 * int(osd.conf.osd_pg_log_max_entries) * \
        int(dep.config["pg_num"])


def prepare(ctx) -> dict:
    p, dep = ctx.params, ctx.dep
    p["prewrite_objects"] = max(int(p["prewrite_objects"]),
                                least_objects(dep))
    info = closed_loop.prepare(ctx)
    dep.cluster.wait_for_clean(timeout=120.0)
    before = acting_sets(dep)
    primaries = {acting[0] for acting in before.values()}
    member = {o for acting in before.values() for o in acting if o >= 0}
    out = int(p.get("osds_out", 1))
    victims = sorted(member - primaries)[:out]
    if len(victims) < out:
        raise RuntimeError(f"no {out} non-primary acting OSDs to fail "
                           f"(primaries {sorted(primaries)})")
    t_out = time.monotonic()
    for v in victims:
        dep.cluster.kill_osd(v)
        dep.cluster.mark_osd_down(v)     # the operator's `osd down`
        dep.cluster.mark_osd_out(v)      # and `osd out`
    held = {pgid for pgid, acting in before.items()
            if set(acting) & set(victims)}

    def moved() -> dict:
        """pgid -> [(position, its new OSD)] once the PG's acting set
        is full without the victims; None until every one is."""
        now = acting_sets(dep)
        if any(o not in dep.cluster.osds for pgid in held
               for o in now[pgid]):
            return None             # a hole, or a victim still
        return {pgid: [(i, o) for i, (o, was) in
                       enumerate(zip(now[pgid], before[pgid])) if o != was]
                for pgid in before}

    def first_shards_landed() -> bool:
        changed = moved()
        return changed is not None and all(
            any(shard_files(dep, pgid, i, o) > 0 for i, o in changed[pgid])
            for pgid in held)

    dep.cluster._wait(first_shards_landed, float(p["first_shard_bound_s"]),
                      "no first rebuilt shard in every remapped PG")
    ctx.backfill = {"victims": victims, "held": held, "before": before,
                    "changed": moved()}
    info.update(osds_out=victims, pgs_remapped=len(held),
                positions_changed=sum(len(v) for v in
                                      ctx.backfill["changed"].values()),
                first_shards_s=round(time.monotonic() - t_out, 3))
    return info


class RepairWatch(threading.Thread):
    """Samples, four times a second, whether a backfill session and
    whether any repair is still running."""

    def __init__(self, dep):
        super().__init__(daemon=True, name="bench-repair-watch")
        self.dep = dep
        self.samples: list[tuple[float, bool, bool]] = []
        self.stop = threading.Event()

    def run(self) -> None:
        while not self.stop.wait(0.25):
            self.samples.append((time.monotonic(), *repairing(self.dep)))

    def last_seen(self, which: int, since: float) -> float | None:
        """Seconds after `since` of the last sample that still saw
        backfill (1) or any repair (2); None if none did."""
        seen = [s[0] for s in self.samples if s[which]]
        return round(max(seen) - since, 3) if seen else None


def run(ctx, seconds: float) -> dict:
    dep = ctx.dep
    marks: dict = {}
    open_window, close_window = ctx.open_window, ctx.close_window

    def opened() -> float:
        marks["open"] = recovery_counters(dep)
        return open_window()

    def closed() -> None:
        close_window()
        marks["close"] = recovery_counters(dep)

    ctx.open_window, ctx.close_window = opened, closed
    watch = RepairWatch(dep)
    watch.start()
    try:
        window = closed_loop.run(ctx, seconds)
    finally:
        ctx.open_window, ctx.close_window = open_window, close_window
    delta = {k: marks["close"][k] - marks["open"][k] for k in marks["open"]}
    ctx.log(f"recovery counters over the window {delta}")
    window["recovery_delta"] = delta
    window["watch"] = watch
    return window


def verify(ctx, window: dict) -> dict:
    p, dep = ctx.params, ctx.dep
    state, watch = ctx.backfill, window["watch"]
    t_open, t_close = window["t_open"], window["t_close"]
    t0 = time.monotonic()
    dep.cluster.wait_for_clean(timeout=float(p["clean_bound_s"]))
    watch.stop.set()
    watch.join(5.0)
    in_window = [s for s in watch.samples if t_open <= s[0] <= t_close]
    ctx.log(f"clean {time.monotonic() - t_open:.1f}s after the window "
            f"opened ({time.monotonic() - t0:.1f}s after the loop ended); "
            f"a backfill session was last seen running "
            f"{watch.last_seen(1, t_open)}s after it opened, any repair "
            f"{watch.last_seen(2, t_open)}s; of {len(in_window)} samples "
            f"in the window {sum(s[1] for s in in_window)} saw a session, "
            f"{sum(s[2] for s in in_window)} any repair")
    ctx.log(f"recovery counters at the end {recovery_counters(dep)}")
    # a session makes a round every `osd_backfill_scan_batch` objects:
    # too few fall into a window for a per-layer metric, so the rounds
    # the op rings still hold are logged here
    rounds = [s["t1"] - s["t0"] for d in dep.historic_ops()
              if d["description"].startswith("backfill_scan(")
              for s in d["spans"] if s["name"] == "backfill.scan"]
    if rounds:
        ctx.log(f"backfill rounds in the op rings: {len(rounds)}, mean "
                f"{1000.0 * sum(rounds) / len(rounds):.1f} ms, longest "
                f"{1000.0 * max(rounds):.1f} ms")

    verdict = closed_loop.verify(ctx, window)
    comparisons = verdict["comparisons"]
    comparisons.append(("rebuilds_in_window", sum(
        window["recovery_delta"][k] for k in (
            "rebuild_cache_served", "rebuild_local", "rebuild_full")),
        ">=", 1))

    # the acting sets: k+m distinct OSDs, up and in, no victim
    osdmap = dep.osdmap()
    width = len(next(iter(state["before"].values())))
    bad_sets = 0
    for pgid, (acting, _pg) in dep.pool_pgs().items():
        ok = (len(acting) == width and len(set(acting)) == width
              and not set(acting) & set(state["victims"])
              and all(o >= 0 and osdmap.is_up(o) and osdmap.is_in(o)
                      for o in acting))
        if not ok:
            bad_sets += 1
            ctx.log(f"acting set of {pgid} after clean: {acting}")
    comparisons.append(("acting_sets_not_whole", bad_sets, "<=", 0))

    # every changed position holds one shard file an object of the log
    short = positions = 0
    for pgid, (acting, pg) in dep.pool_pgs().items():
        objects = len(pg.pglog.objects)
        for i, (o, was) in enumerate(zip(acting, state["before"][pgid])):
            if o == was:
                continue
            positions += 1
            have = shard_files(dep, pgid, i, o)
            if have != objects:
                short += 1
                ctx.log(f"{pgid} position {i} on osd.{o}: {have} shard "
                        f"files, the log has {objects} objects")
    ctx.log(f"positions whose OSD changed: {positions}")
    comparisons += [("rebuilt_positions_short", short, "<=", 0),
                    ("rebuilt_positions_counted", positions, ">=", 1)]

    # a deep scrub of every PG
    inconsistent = 0
    for pgid, (_acting, pg) in dep.pool_pgs().items():
        found = pg.scrub(deep=True).get("inconsistent", [])
        inconsistent += len(found)
        for name in found[:3]:
            ctx.log(f"deep scrub of {pgid}: {name} inconsistent")
    comparisons.append(("scrub_inconsistent_after_clean", inconsistent,
                        "<=", 0))

    # the sample the harness compares with the reference
    n_pre = int(p["prewrite_objects"])
    rng = np.random.default_rng([ctx.seed, 0xBACF])
    order = rng.permutation(n_pre).tolist()
    of_held = [k for k in order if dep.osdmap().object_to_pg(
        dep.io.pool_id, object_name(k)) in state["held"]]
    want, want_held = int(p["stored_sample"]), \
        int(p["stored_sample_backfilled"])
    sample = of_held[:want_held]
    sample += [k for k in order if k not in set(sample)][:want - len(sample)]
    verdict["stored_objects"] = [(k, 0) for k in sample]

    def after_stored_check() -> list:
        # a position the pool module lists as None: its OSD not running
        missing = sum(1 for k in sample for o in
                      dep.object_pg(object_name(k))[1]
                      if o not in dep.cluster.osds)
        return [("stored_positions_missing", missing, "<=", 0),
                ("stored_sample_backfilled",
                 sum(1 for k in sample if k in set(of_held)), ">=",
                 min(want_held, len(of_held)))]

    verdict["after_stored_check"] = after_stored_check
    return verdict
