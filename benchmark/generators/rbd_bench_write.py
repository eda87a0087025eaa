"""`rbd bench-write` (src/tools/rbd/action/BenchWrite.cc: --io-size
4096 --io-threads 16 --io-pattern rand) on an image whose pool stands
behind a writeback cache tier (`pools/wbtier_ec.py`).

Parameters (traffic file):
  clients           writer threads, each with one write in flight
  io_bytes          bytes of one write, and the alignment of its offset
  pattern           "rand": a seeded, uniformly random aligned offset
                    of the image
  ramp_min_seconds  the same traffic runs at least this long before the
                    window opens, and until every tier PG has evicted
                    at least once (the tier is in its steady state)
  ramp_max_seconds  a tier that has not got there by then fails set-up
  readback_sample   objects read back whole after the window, half of
                    them written in the window

Set-up writes the image's data objects whole, seeded, to the BASE pool
under the image's object names, `clients` in flight (object n holds the
payload of key n, version 0); THEN the overlay is set, as an operator
puts a tier in front of a pool that has data: the tier starts empty and
every object cold.  `rbd create` comes after the overlay, as in the
documents: the header and the directory are cls objects with omap,
which an EC pool refuses (EOPNOTSUPP, here as in Kraken) and the tier
takes; both are then flushed to the base once (`cache-flush-evict-all`
on the still empty tier) and the programs their one-stripe encode
brings are waited for.  The writes go through `ceph_tpu.rbd.Image.write` on one open
image (`rbd_cache` off: one OSD op a write).  Two writers never have
the same block in flight (the one that draws a block another holds
draws again), so the acknowledged writes of a block have one order,
and each is applied to the plain reference image
(`references/rbd_wbtier.py`) when it is acknowledged.

The check after the window: (i) the sample read whole through the
overlay against the reference image, and the copies the tier's acting
OSDs hold of it then against the reference's `resident()`: all
`tier.size` copies or none, and all of them where one is dirty;
(ii) `cache-flush-evict-all`, after which the tier lists no data object
and counts none dirty; (iii) the stored k+m shard files and CRCs of the
whole sample, written through the tier or only prewritten, against
the reference's `stored()` of the reference image's
bytes - the configuration names no reference for the harness's own
comparison, whose payloads are a function of (key, version) where an
image's bytes are a history, so the numbers are reported here under
the harness's names; (iv) the tier's two counters that have to stay 0.
A write that failed leaves its block in doubt; the block is left out
of (i) and its object out of (iii).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import cluster as cl
from benchmark.references import rbd_wbtier

IMAGE = "vol"
ZERO_TIER_COUNTERS = ("tier_evict_dirty", "tier_full_admit")


def geometry(dep) -> tuple[int, int]:
    """(data objects, bytes of one): the image's size over its object
    size, at the configuration's `object_bytes`."""
    image = dep.config["image"]
    return int(image["size"]) >> int(image["order"]), dep.object_bytes


def prepare(ctx) -> dict:
    from ceph_tpu import rbd
    dep, p = ctx.dep, ctx.params
    objects, ob = geometry(dep)
    if ob & (ob - 1) or ob % int(p["io_bytes"]):
        raise ValueError("object_bytes: a power of two, whole writes")
    ref = rbd_wbtier.Image(objects * ob, ob)
    t0 = time.monotonic()
    pending: list = []
    for n in range(objects):
        data = ctx.payloads.make(n, 0)
        ref.write(n * ob, data)
        pending.append(dep.io.aio_write_full(rbd.data_oid(IMAGE, n), data))
        if len(pending) >= int(p["clients"]):
            c = pending.pop(0)
            c.wait_for_complete(300.0)
            c.result()
    for c in pending:
        c.wait_for_complete(300.0)
        c.result()
    prewrite_s = time.monotonic() - t0
    dep.pool.set_overlay(dep, dep.io.pool_name)
    rbd.RBD(dep.io).create(IMAGE, objects * ob, order=ob.bit_length() - 1)
    # the header and the directory are the cell's only objects of
    # another shape than a data object's (one stripe): the operator's
    # drain sends them to the base here, so that what the first encode
    # of a one-row item compiles behind the served path (the pipeline's
    # item slices at every bucket, the cache's check of a one-row
    # entry: some 7 s on a warm thread, my chip run, PR 40) is compiled
    # in set-up and not in a ramp that may be as short as the window's
    # opening allows
    dep.rados.cache_flush_evict_all(dep.pool.tier_name(dep.io.pool_name))
    waited = cl.wait_warm(
        lambda: cl.pipeline_stats()["warmups_inflight"] == 0,
        cl.WARM_BOUND, "the slices of the header's one-row encode")
    image = rbd.Image(dep.io, IMAGE,
                      cache=bool(dep.config.get("rbd_cache", False)))
    ctx.rbd = {"image": image, "ref": ref, "objects": objects,
               "object_bytes": ob, "doubt": set(), "written": set()}
    return {"image_objects": objects, "object_bytes": ob,
            "prewrite_s": round(prewrite_s, 3),
            "waited_header_slices_s": round(waited, 3),
            "tier_target_bytes": dep.pool.tier_target_bytes(dep.config),
            "overlay": "set after the prewrite"}


def all_evicted(dep) -> bool:
    status = dep.pool.tier_status(dep)
    return len(status) == int(dep.config["tier"]["pg_num"]) and all(
        line["tier_evict"] >= 1 for line in status.values())


def run(ctx, seconds: float) -> dict:
    from ceph_tpu.client import RadosError

    p, st = ctx.params, ctx.rbd
    image, ref = st["image"], st["ref"]
    clients, io = int(p["clients"]), int(p["io_bytes"])
    if p["pattern"] != "rand":
        raise ValueError(f"unknown pattern {p['pattern']!r}")
    blocks = st["objects"] * st["object_bytes"] // io
    per_object = st["object_bytes"] // io
    lock = threading.Lock()
    held: set = set()                 # blocks with a write in flight
    window = [float("inf"), float("inf")]
    in_window: set = set()            # objects written in the window
    stop_at = [float("inf")]
    records: list[list] = [[] for _ in range(clients)]
    errors: list[str] = []

    def client(idx: int) -> None:
        rng = np.random.default_rng([ctx.seed, 0xBE7C, idx])
        rec = records[idx]
        while time.monotonic() < stop_at[0]:
            block = int(rng.integers(0, blocks))
            data = rng.bytes(io)
            with lock:
                if block in held:
                    continue
                held.add(block)
            t0 = time.monotonic()
            try:
                image.write(block * io, data)
                t1 = time.monotonic()
                with lock:
                    ref.write(block * io, data)
                    st["written"].add(block // per_object)
                    if window[0] <= t1 <= window[1]:
                        in_window.add(block // per_object)
                rec.append(("write", t0, t1, True, io))
            except RadosError as e:
                rec.append(("write", t0, time.monotonic(), False, 0))
                with lock:
                    st["doubt"].add(block)
                errors.append(f"write block {block}: {e}")
            finally:
                with lock:
                    held.discard(block)

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-writer-{i}")
               for i in range(clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    least, most = float(p["ramp_min_seconds"]), float(p["ramp_max_seconds"])
    while True:
        time.sleep(0.5)
        waited = time.monotonic() - t_start
        if waited >= least and all_evicted(ctx.dep):
            break
        if waited > most:
            stop_at[0] = 0.0
            raise cl.CheckFailed(
                f"after {waited:.0f}s of ramp not every tier PG has "
                f"evicted: {ctx.dep.pool.tier_status(ctx.dep)}")
    before = ctx.dep.pool.tier_counters(ctx.dep)
    t_open = ctx.open_window()
    t_close = t_open + seconds
    window[0], window[1] = t_open, t_close
    stop_at[0] = t_close
    time.sleep(max(0.0, t_close - time.monotonic()))
    ctx.close_window()
    after = ctx.dep.pool.tier_counters(ctx.dep)
    for t in threads:
        t.join(600.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a writer thread did not finish its last write")
    ctx.log(f"tier counters over the window "
            f"{ {k: after[k] - before.get(k, 0) for k in sorted(after)} }")
    ops = [r for rec in records for r in rec]
    return {"t_open": t_open, "t_close": t_close, "ramp_s": t_open - t_start,
            "ops": ops, "bad": [], "errors": errors,
            "in_window": in_window}


def differing_blocks(got: bytes, want: bytes, io: int, skip: set,
                     first_block: int) -> list[int]:
    if len(got) != len(want):
        return [-1]
    return [b for b in range(len(want) // io)
            if first_block + b not in skip
            and got[b * io:(b + 1) * io] != want[b * io:(b + 1) * io]]


def held_copies(dep, oid: str) -> tuple[list, bool]:
    """([(label, bytes)] of the copies the tier PG's acting OSDs hold of
    `oid`, whether any of them is dirty).  The stores are read one after
    another while the agent goes on working, and an evict is a
    replicated delete that takes the copies away one store after
    another: an object seen with some of its copies is looked at once
    more, and the second look counts."""
    for attempt in range(2):
        copies = dep.pool.tier_copies(dep, oid)
        held = [(label, data) for label, data, _d in copies
                if data is not None]
        if len(held) in (0, len(copies)):
            break
        if not attempt:
            time.sleep(1.0)
    return held, any(dirty for _l, _data, dirty in copies)


def verify(ctx, window: dict) -> dict:
    from ceph_tpu import rbd
    dep, p, st = ctx.dep, ctx.params, ctx.rbd
    image, ref, ob = st["image"], st["ref"], st["object_bytes"]
    io = int(p["io_bytes"])
    per_object = ob // io
    rng = np.random.default_rng([ctx.seed, 0x5A3F])
    half = int(p["readback_sample"]) // 2
    touched = sorted(window["in_window"])
    rest = sorted(set(range(st["objects"])) - window["in_window"])

    def pick(pool: list, n: int) -> list:
        return [pool[i] for i in rng.choice(
            len(pool), min(n, len(pool)), replace=False)] if pool else []

    sample = pick(touched, half) + pick(rest, half)

    # (i) through the overlay, resident or not; and what the tier
    # holds of it then: all `tier.size` copies or none (the agent goes
    # on evicting), every one the reference's
    size = int(dep.config["tier"]["size"])
    read_bad = copy_bad = copies = short = dirty_short = 0
    for n in sample:
        got = dep.retry(lambda n=n: image.read(n * ob, ob))
        diff = differing_blocks(got, ref.object(n), io, st["doubt"],
                                n * per_object)
        if diff:
            read_bad += 1
            ctx.log(f"readback object {n}: blocks {diff[:8]} differ")
        held, any_dirty = held_copies(dep, rbd.data_oid(IMAGE, n))
        if len(held) not in (0, size):
            short += 1
            dirty_short += any_dirty
            ctx.log(f"object {n}: {len(held)} of {size} tier copies "
                    f"({'dirty' if any_dirty else 'clean'})")
        want = rbd_wbtier.resident(ref.object(n), dep.config)
        for (label, data), expect in zip(held, want):
            copies += 1
            if differing_blocks(data, expect, io, st["doubt"],
                                n * per_object):
                copy_bad += 1
                ctx.log(f"tier copy {label} differs from the reference")
    ctx.log(f"read back {len(sample)} objects through the overlay, "
            f"{sum(1 for n in sample if n in window['in_window'])} of "
            f"them written in the window; blocks in doubt "
            f"{len(st['doubt'])}")
    ctx.log(f"tier copies of them compared with the reference: {copies}")
    comparisons = [("readback_mismatches", read_bad, "<=", 0),
                   ("readback_objects", len(sample), ">=", 1),
                   ("tier_copy_mismatches", copy_bad, "<=", 0),
                   ("tier_objects_short_of_copies", short, "<=", 0),
                   ("tier_dirty_objects_short_of_copies", dirty_short,
                    "<=", 0)]

    # (ii) the operator drains the tier
    image.close()
    t0 = time.monotonic()
    tier = dep.pool.tier_name(dep.io.pool_name)
    left = dep.rados.cache_flush_evict_all(tier)
    names = dep.rados.open_ioctx(tier).list_objects()
    status = dep.pool.tier_status(dep)
    data_left = sum(1 for n in names if n.startswith("rbd_data."))
    ctx.log(f"cache-flush-evict-all took {time.monotonic() - t0:.1f}s; the "
            f"tier lists {len(names)} objects ({left} after the last "
            f"round), {data_left} of them data objects")
    comparisons += [
        ("tier_data_objects_left", data_left, "<=", 0),
        ("tier_dirty_left", sum(s["dirty"] for s in status.values()),
         "<=", 0)]

    # (iii) the base alone holds every acknowledged write
    doubted = {b // per_object for b in st["doubt"]}
    data_bad = crc_bad = files = 0
    for n in sample:
        if n in doubted:
            continue
        oid = rbd.data_oid(IMAGE, n)
        want = rbd_wbtier.stored(ref.object(n), dep.config)
        got = dep.pool.stored(dep, oid)
        if len(got) != len(want):
            raise cl.CheckFailed(f"{oid}: the pool lists {len(got)} stored "
                                 f"files, the reference {len(want)}")
        for (label, data, crc), (want_data, want_crc) in zip(got, want):
            files += 1
            if data != want_data:
                data_bad += 1
                ctx.log(f"stored {label} differs from the reference")
            if crc != want_crc:
                crc_bad += 1
                ctx.log(f"crc of {label}: stored {crc:#x}, reference "
                        f"{want_crc:#x}")
    ctx.log(f"stored files of the sample compared with the reference: "
            f"{files} ({sum(1 for n in sample if n in st['written'])} of "
            f"its objects written through the tier)")
    comparisons += [("stored_mismatches", data_bad, "<=", 0),
                    ("stored_crc_mismatches", crc_bad, "<=", 0),
                    ("stored_files_compared", files, ">=", 1)]

    # (iv) what must never happen, from boot to here
    counters = dep.pool.tier_counters(dep)
    ctx.log(f"tier counters since boot {counters}")
    comparisons += [(k, counters[k], "<=", 0) for k in ZERO_TIER_COUNTERS]
    return {"comparisons": comparisons, "stored_objects": []}
