"""Deep scrub of the pool's PGs, round-robin, through the operator's
path: `pg.scrub(deep=True)` on each PG's primary, one at a time (the
reference's `osd_max_scrubs` default), for the whole window.

Parameters (traffic file):
  prewrite_objects  objects written in set-up, `clients` in flight
  clients           in-flight writes of that set-up (no client op runs
                    in the window)

One op is one PG scrub, counted with the stored-file bytes it verified
(files checked times the pool module's `file_bytes`).
A PG scrub verifies some 44 MiB in most of a second, so a window cut at
a fixed instant would count in steps of 2%: the window closes instead
when the scrub in progress at `seconds` completes, and the rate is
taken over that whole time.  A scrub that reports an
inconsistency on clean data is a failed comparison, and so is a stored file
corrupted under the store after the window that the next scrub of its
PG does not flag alone.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.cluster import deep_scrub
from benchmark.generators.closed_loop import write_all
from benchmark.payload import object_name


def prepare(ctx) -> dict:
    n = int(ctx.params["prewrite_objects"])
    t0 = time.monotonic()
    write_all(ctx, range(n), int(ctx.params["clients"]))
    return {"prewritten": n, "prewrite_s": round(time.monotonic() - t0, 3)}


def run(ctx, seconds: float) -> dict:
    dep = ctx.dep
    pgs = sorted(dep.pool_pgs().items(), key=lambda e: str(e[0]))
    file_bytes = dep.pool.file_bytes(dep.config)
    ops, bad = [], []
    t_open = ctx.open_window()
    t_close = t_open + seconds
    i = 0
    while time.monotonic() < t_close:
        pgid, (_acting, pg) = pgs[i % len(pgs)]
        i += 1
        t0 = time.monotonic()
        r = deep_scrub(pg)
        t1 = time.monotonic()
        ops.append(("scrub", t0, t1, True, r["checked"] * file_bytes))
        if r["inconsistent"]:
            bad.append(f"scrub {pgid}: {r['inconsistent'][:2]}")
    ctx.close_window()
    t_close = ops[-1][2]
    return {"t_open": t_open, "t_close": t_close, "ramp_s": 0.0, "ops": ops,
            "bad": bad, "errors": []}


def verify(ctx, window: dict) -> dict:
    dep = ctx.dep
    n = int(ctx.params["prewrite_objects"])
    rng = np.random.default_rng([ctx.seed, 0x5C2B])
    sample = rng.choice(n, min(n, 4), replace=False).tolist()
    victim = object_name(sample[0])
    # the stored files are compared with the reference BEFORE one is
    # corrupted; the harness does that from `stored_objects`
    def after_stored_check() -> list:
        pgid, name = dep.pool.corrupt(dep, victim)
        _acting, pg = dep.pool_pgs()[pgid]
        flagged = [b["object"] for b in deep_scrub(pg)["inconsistent"]]
        ctx.log(f"corrupted {name}: scrub flagged {flagged}")
        return [("corruption_not_flagged_alone",
                 0 if flagged == [name] else 1, "<=", 0)]

    return {"comparisons": [], "after_stored_check": after_stored_check,
            "stored_objects": [(k, 0) for k in sample]}
