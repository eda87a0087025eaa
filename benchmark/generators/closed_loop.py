"""Closed loop, as `rados bench`: `clients` callers, each sending its
next op when the last one completed.

Parameters (traffic file):
  clients           callers, each with one op in flight
  keys              "new": every op writes a new object (rados bench
                    write); "prewritten": every op draws a uniform key
                    among the pre-written objects (rados bench rand)
  prewrite_objects  objects written in set-up, `clients` in flight
  read_fraction     "prewritten" only: each op is a read with this
                    probability, a coin from the seed, else a
                    `write_full`
  fail_osds         non-primary acting OSDs killed in set-up and waited
                    down (not out): reads are then degraded
  ramp_seconds      the loop runs this long before the window opens, so
                    the window starts with `clients` ops in flight
  readback_sample   acknowledged writes read back after the window,
                    besides the last `clients`

Latency is submit to completion.  An op that raises counts as failed
and carries no bytes.  Keys are not kept apart: a read may race a
write of its key and two writes may race each other, as they do under
`rados bench`.  Every read is held to the acknowledged history of its
key (`History`): it has to return a version that was sent, and not one
that a later write had replaced, acknowledged, before the read began.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from benchmark.payload import object_name


def write_all(ctx, keys, inflight: int) -> None:
    pending: list = []

    def reap():
        c = pending.pop(0)
        c.wait_for_complete(300.0)
        c.result()

    for key in keys:
        pending.append(ctx.dep.io.aio_write_full(
            object_name(key), ctx.payloads.make(key, 0)))
        if len(pending) >= inflight:
            reap()
    while pending:
        reap()


def prepare(ctx) -> dict:
    p = ctx.params
    dep = ctx.dep
    n = int(p.get("prewrite_objects", 0))
    info: dict = {"prewritten": n}
    t0 = time.monotonic()
    # the first writes ride the host while the encode fns compile in
    # the background; the warm-up step waits for them afterwards
    write_all(ctx, range(n), int(p["clients"]))
    info["prewrite_s"] = round(time.monotonic() - t0, 3)
    fail = int(p.get("fail_osds", 0))
    if fail:
        pgs = dep.pool_pgs()
        primaries = {acting[0] for acting, _pg in pgs.values()}
        member = {o for acting, _pg in pgs.values() for o in acting
                  if o >= 0}
        victims = sorted(member - primaries)[:fail]
        if len(victims) < fail:
            raise RuntimeError(f"no {fail} non-primary acting OSDs to "
                               f"fail (primaries {sorted(primaries)})")
        for v in victims:
            dep.cluster.kill_osd(v)
            dep.cluster.mark_osd_down(v)     # the operator's `osd down`
        for v in victims:
            dep.cluster.wait_for_osd_down(v, timeout=180)
        # one degraded read per client slot, retried over peering
        for key in range(min(n, int(p["clients"]))):
            dep.retry(lambda k=key: dep.io.read(object_name(k)))
        info["failed_osds"] = victims
    return info


class History:
    """Per key, every write sent: version -> [sent at, acknowledged at
    or None].  Version 0 of a pre-written key was acknowledged in
    set-up, before everything."""

    SETUP = float("-inf")

    def __init__(self):
        self.lock = threading.Lock()
        self.writes: dict[int, dict[int, list]] = {}
        self.acked_order: list[tuple[int, int]] = []

    def _of(self, key: int) -> dict:
        return self.writes.setdefault(key, {0: [self.SETUP, self.SETUP]})

    def send(self, key: int, new: bool) -> tuple[int, float]:
        """Note a write about to go out; (its version, the instant)."""
        with self.lock:
            t = time.monotonic()
            if new:
                self.writes[key] = {0: [t, None]}
                return 0, t
            w = self._of(key)
            version = max(w) + 1
            w[version] = [t, None]
            return version, t

    def ack(self, key: int, version: int) -> float:
        with self.lock:
            t = time.monotonic()
            self.writes[key][version][1] = t
            self.acked_order.append((key, version))
            return t

    def valid(self, key: int, got, read_began: float) -> bool:
        """May a read that began at `read_began` return version `got`?
        Not a version never sent, and not one that another write, sent
        after `got` was acknowledged, had replaced and been
        acknowledged for before the read began.  A write that failed
        or is still out may or may not have been applied."""
        with self.lock:
            w = dict(self._of(key))
        if got not in w:
            return False
        acked = w[got][1]
        if acked is None:
            return True
        return not any(v != got and sent > acked and done is not None
                       and done < read_began
                       for v, (sent, done) in w.items())

    def latest_acked(self) -> dict[int, int]:
        """key -> the version acknowledged last."""
        return dict(self.acked_order)


def run(ctx, seconds: float) -> dict:
    from ceph_tpu.client import RadosError

    p = ctx.params
    io, payloads = ctx.dep.io, ctx.payloads
    clients = int(p["clients"])
    new_keys = p["keys"] == "new"
    n_pre = int(p.get("prewrite_objects", 0))
    read_fraction = float(p.get("read_fraction", 0.0))
    ramp = float(p.get("ramp_seconds", 0.0))
    history = History()
    fresh = itertools.count(n_pre)
    obj_bytes = payloads.object_bytes
    stop_at = [float("inf")]
    records: list[list] = [[] for _ in range(clients)]
    bad: list[str] = []          # reads that returned a wrong answer
    bad_ops: list[str] = []      # ops that raised

    def client(idx: int) -> None:
        rng = np.random.default_rng([ctx.seed, 0xC11E, idx])
        rec = records[idx]
        while time.monotonic() < stop_at[0]:
            if new_keys:
                key, is_read = next(fresh), False
            else:
                is_read = bool(rng.random() < read_fraction)
                key = int(rng.integers(0, n_pre))
            name = object_name(key)
            try:
                if is_read:
                    t0 = time.monotonic()
                    data = io.read(name)
                    t1 = time.monotonic()
                    got = payloads.version_of(key, data)
                    if not history.valid(key, got, t0):
                        bad.append(f"read {name}: version {got} of "
                                   f"{sorted(history.writes.get(key, {}))}")
                    rec.append(("read", t0, t1, True, obj_bytes))
                else:
                    version, t0 = history.send(key, new_keys)
                    io.write_full(name, payloads.make(key, version))
                    t1 = history.ack(key, version)
                    rec.append(("write", t0, t1, True, obj_bytes))
            except RadosError as e:
                rec.append(("read" if is_read else "write", t0,
                            time.monotonic(), False, 0))
                bad_ops.append(f"{'read' if is_read else 'write'} "
                               f"{name}: {e}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(ramp)
    t_open = ctx.open_window()
    t_close = t_open + seconds
    stop_at[0] = t_close
    time.sleep(max(0.0, t_close - time.monotonic()))
    ctx.close_window()
    for t in threads:
        t.join(600.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client thread did not finish its last op")
    ops = [r for rec in records for r in rec]
    return {"t_open": t_open, "t_close": t_close, "ramp_s": t_open - t_start,
            "ops": ops, "bad": bad, "errors": bad_ops, "history": history}


def verify(ctx, window: dict) -> dict:
    """Read back a seeded sample of the acknowledged writes plus the
    last `clients` of them, each held to its key's history; returns
    the comparisons made and, as (key, version read back), the objects
    whose stored state the harness compares with the reference."""
    p = ctx.params
    payloads, io = ctx.payloads, ctx.dep.io
    history = window["history"]
    acked = history.acked_order
    rng = np.random.default_rng([ctx.seed, 0x5A3E])
    tail = [k for k, _v in acked[-int(p["clients"]):]]
    pool = sorted(history.latest_acked())
    n_sample = min(len(pool), int(p.get("readback_sample", 8)))
    sample = [pool[i] for i in rng.choice(len(pool), n_sample,
                                          replace=False)] if pool else []
    n_pre = int(p.get("prewrite_objects", 0))
    if not pool and n_pre:          # a read-only mix: the pre-written
        sample = rng.choice(n_pre, min(n_pre, 8), replace=False).tolist()
    mismatches = 0
    read_back: dict[int, int] = {}
    for key in dict.fromkeys(sample + tail):
        began = time.monotonic()
        data = ctx.dep.retry(lambda k=key: io.read(object_name(k)))
        got = payloads.version_of(key, data)
        if history.valid(key, got, began):
            read_back[key] = got
        else:
            mismatches += 1
            ctx.log(f"readback {object_name(key)}: version {got} of "
                    f"{sorted(history.writes.get(key, {}))}")
    ctx.log(f"read back {len(read_back) + mismatches} objects")
    return {"comparisons": [("readback_mismatches", mismatches, "<=", 0)],
            "stored_objects": [(k, read_back[k]) for k in sample
                               if k in read_back][:4]}
