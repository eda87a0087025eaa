"""Cache tiering, tier-PG side (ReplicatedPG cache machinery:
maybe_handle_cache / promote_object / agent_work / agent_choose_mode /
start_flush / hit_set_persist; OSDService::agent_entry).

`CacheTier` is mixed into PG (pg.py); `TierAgent` is the OSD's agent
worker (daemon.py starts one with the first tier PG that has work).

What a tier PG's primary keeps, and where it comes from:

  * an index of its objects (oid -> `TierObject`: size, dirty,
    whiteout, mtime), rebuilt from the collection once, on activation, and kept by the
    write path from then on (`_tier_account` after every applied
    write): the running counts the agent works by (objects, bytes,
    dirty) are sums over it, and a steady-state agent pass lists no
    collection and reads no attribute;
  * two modes, chosen from those counts against the PG's share of
    the pool's targets (`agent_choose_mode`): flush idle / low / high
    by the dirty share, evict idle / some / full by the object share;
  * what is in flight: promotes (`_promote_waiting`), flushes
    (`_flushing`), client ops held back by a full tier
    (`_full_waiting`).

Spans and counters are listed in utils/optracker.py and PARITY.md.
"""

from __future__ import annotations

import itertools
import threading
import time
import traceback
from dataclasses import dataclass

from ..store.objectstore import ENOENT, StoreError, Transaction
from ..utils import denc, optracker
from .messages import MOSDOp
from .pglog import DIRTY_KEY, WHITEOUT_KEY

EBUSY = 16
FLUSH_IDLE, FLUSH_LOW, FLUSH_HIGH = "idle", "low", "high"
EVICT_IDLE, EVICT_SOME, EVICT_FULL = "idle", "some", "full"
# the operator's ops (CEPH_OSD_OP_CACHE_FLUSH / TRY_FLUSH / EVICT):
# each comes alone in its op vector, addressed to the tier pool
CACHE_OPS = ("cache-flush", "cache-try-flush", "cache-evict")
MICRO = 1000000
# the reference's osd_agent_slop and osd_agent_delay_time, at their
# defaults: a mode that is on goes off this share below the ratio that
# turns it on; the agent looks again after this many seconds with work
# queued that it could not start, unless something wakes it
SLOP = 0.02
AGENT_DELAY_S = 5.0


@dataclass(slots=True)
class TierObject:
    """What a tier PG's primary knows of one resident object."""
    size: int
    dirty: bool
    whiteout: bool
    mtime: float          # the daemon clock at its last change


def agent_choose_mode(pool, objects: int, nbytes: int, dirty: int,
                      flush_mode: str = FLUSH_IDLE,
                      evict_mode: str = EVICT_IDLE) -> tuple[str, str]:
    """(flush mode, evict mode) of one tier PG holding `objects`
    objects of `nbytes` bytes, `dirty` of them dirty
    (ReplicatedPG::agent_choose_mode).  The PG works against its share
    of the pool's targets, target / pg_num; where both targets are set
    the fuller reading counts.  Dirty objects are reckoned at the PG's
    mean object size.  `SLOP` is the hysteresis: a mode that is on
    goes off a little below the ratio that turns it on.  With no
    target set both modes are idle."""
    target_bytes = int(pool.target_max_bytes or 0)
    target_objects = int(pool.target_max_objects or 0)
    if target_bytes <= 0 and target_objects <= 0:
        return FLUSH_IDLE, EVICT_IDLE
    divisor = max(1, int(pool.pg_num))
    dirty_micro = full_micro = 0
    if target_bytes > 0 and objects > 0:
        share = max(target_bytes // divisor, 1)
        avg = nbytes // objects
        dirty_micro = dirty * avg * MICRO // share
        full_micro = objects * avg * MICRO // share
    if target_objects > 0:
        share = max(target_objects // divisor, 1)
        dirty_micro = max(dirty_micro, dirty * MICRO // share)
        full_micro = max(full_micro, objects * MICRO // share)

    def lift(ratio: float, was_idle: bool) -> int:
        target = int(ratio * MICRO)
        step = int(target * SLOP)
        return target + step if was_idle else target - min(target, step)

    idle = flush_mode == FLUSH_IDLE
    if dirty_micro > lift(pool.cache_target_dirty_high_ratio, idle):
        flush = FLUSH_HIGH
    elif dirty_micro > lift(pool.cache_target_dirty_ratio, idle):
        flush = FLUSH_LOW
    else:
        flush = FLUSH_IDLE
    # at its target the tier is full: an op that would add an object
    # waits, so the target is never passed
    if full_micro >= MICRO:
        evict = EVICT_FULL
    elif full_micro > lift(pool.cache_target_full_ratio,
                           evict_mode == EVICT_IDLE):
        evict = EVICT_SOME
    else:
        evict = EVICT_IDLE
    return flush, evict


class TierAgent:
    """The OSD's tiering agent (OSDService::agent_entry): one worker
    thread serving the queue of tier PGs whose mode is not idle, with
    at most `osd_agent_max_ops` flushes and evicts in flight on this
    OSD (`osd_agent_max_low_ops` flushes while no PG flushes in high
    mode).  It sleeps until kicked: by a write that changed a PG's
    mode, by each completion, or after `AGENT_DELAY_S` with
    work queued that it could not start."""

    def __init__(self, osd):
        self.osd = osd
        self.cv = threading.Condition()
        self.queue: dict = {}            # pgid -> None, in queue order
        self.ops = 0                     # agent flushes + evicts in flight
        self._kicked = False
        self._stopping = False
        self._thread: threading.Thread | None = None

    def enqueue(self, pgid) -> None:
        with self.cv:
            if self._stopping:
                return
            self.queue.setdefault(pgid, None)
            self._kicked = True
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"osd{self.osd.whoami}-agent")
                self._thread.start()
            self.cv.notify()

    def dequeue(self, pgid) -> None:
        with self.cv:
            self.queue.pop(pgid, None)

    def op_started(self) -> None:
        with self.cv:
            self.ops += 1

    def op_finished(self) -> None:
        with self.cv:
            self.ops -= 1
            self._kicked = True
            self.cv.notify()

    def stop(self) -> None:
        with self.cv:
            self._stopping = True
            self.cv.notify()
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(2.0)

    def _run(self) -> None:
        conf = self.osd.conf
        turn = 0
        while True:
            with self.cv:
                while not self._stopping and not (
                        self.queue and self._kicked):
                    if not self.cv.wait(AGENT_DELAY_S if self.queue
                                        else None):
                        break        # work queued, nobody kicked: look
                if self._stopping:
                    return
                self._kicked = False
                pgids = list(self.queue)
            turn += 1
            k = turn % len(pgids) if pgids else 0
            for pgid in pgids[k:] + pgids[:k]:
                with self.cv:
                    room = int(conf.osd_agent_max_ops) - self.ops
                    low_room = int(conf.osd_agent_max_low_ops) - self.ops
                if room <= 0:
                    break
                with self.osd.pg_lock:
                    pg = self.osd.pgs.get(pgid)
                if pg is None:
                    self.dequeue(pgid)
                    continue
                try:
                    pg.agent_work(room, low_room)
                except Exception:           # the worker must not die
                    self.osd.log.error("agent pass on %s failed:\n%s",
                                       pgid, traceback.format_exc())


class CacheTier:
    # ---- cache tiering (tier-pg side) ------------------------------------
    #
    #   * an op on an object the tier does not hold PROMOTES it from
    #     the base pool (async; the client op parks until the copy is
    #     installed through the replicated write path) - reads too:
    #     there is no proxied read or write;
    #   * writes land in the tier marked DIRTY (a whole-object write
    #     skips the promote: it defines the object entirely);
    #   * deletes leave a dirty WHITEOUT, flushed as a base delete;
    #   * in evict mode full an op that would add an object waits
    #     until an evict made room;
    #   * the agent flushes dirty objects to the base pool, propagates
    #     whiteouts and evicts clean objects, cold ones first.

    def _tier_init(self) -> None:
        """The tier state of a PG object (every PG has it; only a tier
        PG's primary fills it)."""
        self.hit_sets: list[list] = []     # [[start_ts, set(oids)]...]
        # oid -> {"ops": [(conn, msg)]}: the ops parked on a promote
        self._promote_waiting: dict[str, dict] = {}
        # oid -> {"version": the one being flushed, "agent": the
        # agent's or the operator's, "waiters": cache-flush ops,
        # "try": a cache-try-flush op}
        self._flushing: dict[str, dict] = {}
        self._full_waiting: list[tuple] = []
        # oid -> ops that waited for its promote and have not run yet:
        # the copy they wait for is not evicted under them
        self._tier_pinned: dict[str, int] = {}
        self._tier_index: dict[str, TierObject] = {}
        self._tier_bytes = 0
        self._tier_dirty = 0
        self._tier_biggest = 0        # the largest object seen here
        self._tier_ready = False
        self.flush_mode = FLUSH_IDLE
        self.evict_mode = EVICT_IDLE
        # what this PG started, as the OSD's perf counters count it
        self._tier_done = {"tier_promote": 0, "tier_flush": 0,
                           "tier_evict": 0}
        self._int_tid = itertools.count(1)   # internal-op reqid tids
        self._tier_seq = itertools.count(1)  # flush / agent trace ids

    @property
    def is_tier(self) -> bool:
        pool = self.pool
        return bool(pool and pool.tier_of >= 0)

    # ---- the index and the modes ------------------------------------------

    def _tier_ensure(self) -> bool:
        """Is this the active primary of a tier PG, with its index
        built?  Builds it where it is not: on activation, or when a
        map made the pool a tier.  Caller holds self.lock."""
        if not (self.is_tier and self.is_primary and self.active):
            return False
        if not self._tier_ready:
            self._tier_activate()
        return True

    def _tier_activate(self) -> None:
        """Rebuild the index from the collection, once an interval,
        and choose the modes.  Caller holds self.lock."""
        self._tier_index.clear()
        self._tier_bytes = self._tier_dirty = 0
        self.flush_mode, self.evict_mode = FLUSH_IDLE, EVICT_IDLE
        store = self.osd.store
        try:
            names = [n for n in store.collection_list(self.cid)
                     if not n.startswith("_pgmeta") and "@" not in n]
        except StoreError:
            names = []
        self._tier_ready = True
        for name in names:
            self._tier_account(name, choose=False)
        self._tier_choose_mode()

    def _tier_account(self, oid: str, choose: bool = True,
                      admitted: bool = True) -> None:
        """The store's state of `oid` changed (a write applied, a
        recovery push landed): bring its index entry and the running
        counts up to it.  `admitted`: an object new to the tier came
        through `_cache_intercept`, which holds back what a full tier
        has no room for - if the PG is full without it all the same,
        that is counted (`tier_full_admit` stays 0).  Caller holds
        self.lock."""
        if not self._tier_ready:
            if choose:
                self._tier_ensure()      # the rebuild sees this write
            return
        store = self.osd.store
        try:
            size = int(store.stat(self.cid, oid)["size"])
            attrs = store.getattrs(self.cid, oid)
            new = TierObject(size, DIRTY_KEY in attrs,
                             WHITEOUT_KEY in attrs, self.osd.clock.now())
        except StoreError:
            new = None
        old = self._tier_index.pop(oid, None)
        if choose and admitted and old is None and new is not None \
                and self._tier_mode_now()[1] == EVICT_FULL:
            self.osd.perf.inc("tier_full_admit")
        if old is not None:
            self._tier_bytes -= old.size
            self._tier_dirty -= int(old.dirty)
        if new is not None:
            self._tier_index[oid] = new
            self._tier_biggest = max(self._tier_biggest, new.size)
            self._tier_bytes += new.size
            self._tier_dirty += int(new.dirty)
        was_dirty = old is not None and old.dirty
        if new is not None and new.dirty and not was_dirty:
            self.osd.perf.inc("tier_dirty")
        elif new is not None and was_dirty and not new.dirty:
            self.osd.perf.inc("tier_clean")
        if choose:
            self._tier_choose_mode()

    def _tier_counts(self) -> tuple[int, int, int]:
        """(objects, bytes, dirty) the modes are chosen from: what the
        index holds, and every promote in flight as one more object
        (its room is taken when it is admitted; its size is not known
        before the base answers, so it counts as the largest object
        this PG has seen)."""
        coming = len(self._promote_waiting)
        return (len(self._tier_index) + coming,
                self._tier_bytes + coming * self._tier_biggest,
                self._tier_dirty)

    def _tier_mode_now(self, less_dirty: int = 0) -> tuple[str, str]:
        """The modes the running counts ask for (with `less_dirty`
        of the dirty objects taken as flushed)."""
        pool = self.pool
        objects, nbytes, dirty = self._tier_counts()
        flush, evict = agent_choose_mode(
            pool, objects, nbytes, dirty - less_dirty, self.flush_mode,
            self.evict_mode)
        if pool.cache_mode != "writeback":
            evict = EVICT_IDLE       # eviction is writeback's alone
        elif self._promote_waiting and not self._tier_biggest \
                and int(pool.target_max_bytes or 0) > 0:
            # a bytes target, and no object seen yet to size what is
            # coming by: one promote at a time until one is in
            evict = EVICT_FULL
        return flush, evict

    def _tier_choose_mode(self, wake: bool = True) -> None:
        """Choose the modes from the running counts.  A PG whose mode
        is not idle stands in the agent's queue, and every change of
        its counts wakes the agent (`wake` false: the agent's own
        pass asks); ops a full tier held back try again as soon as it
        is full no longer.  Caller holds self.lock."""
        if not self._tier_ensure():
            return
        self.flush_mode, self.evict_mode = self._tier_mode_now()
        agent = self.osd.tier_agent
        if self.flush_mode != FLUSH_IDLE or self.evict_mode != EVICT_IDLE:
            if wake:
                agent.enqueue(self.pgid)
        else:
            agent.dequeue(self.pgid)
        if self._full_waiting and self.evict_mode != EVICT_FULL:
            self._wake_full_waiters()

    def _tier_count(self, what: str) -> None:
        self.osd.perf.inc(what)
        self._tier_done[what] += 1

    def tier_status(self) -> dict:
        """This PG's line of the OSD's `tier status`."""
        with self.lock:
            return {"flush_mode": self.flush_mode,
                    "evict_mode": self.evict_mode,
                    "objects": len(self._tier_index),
                    "bytes": self._tier_bytes, "dirty": self._tier_dirty,
                    "promoting": len(self._promote_waiting),
                    "flushing": len(self._flushing),
                    "full_waiting": len(self._full_waiting),
                    **self._tier_done}

    def tier_map_changed(self) -> None:
        """A new map may bring new targets or ratios."""
        with self.lock:
            self._tier_choose_mode()

    # ---- the op path -------------------------------------------------------

    def _cache_intercept(self, conn, msg) -> bool:
        """Returns True when the op was fully handled (or parked for a
        promote or behind a full tier) here; False lets do_op execute
        it on the tier pg.

        msg._promoted marks a post-promote re-dispatch: it suppresses
        only the promote decision — whiteout/existence semantics still
        apply (a read parked behind a parked delete must see the
        whiteout the delete just created, not the marker object)."""
        if msg.ops and msg.ops[0][0] in CACHE_OPS:
            self._do_cache_op(conn, msg)
            return True
        if msg.ops and all(op[0] == "list" for op in msg.ops):
            return False              # a PG listing names no object
        self._tier_ensure()
        self._tier_unpin(msg)     # it executes under this lock hold
        promoted = getattr(msg, "_promoted", False)
        resumed = getattr(msg, "_tier_seen", False)
        msg._tier_seen = True
        pool = self.pool
        store = self.osd.store
        oid = msg.oid
        if not promoted and not resumed:
            self._hit_set_record(oid)
        reads, writes = self._split_ops(msg.ops)
        exists = store.exists(self.cid, oid)
        whiteout = False
        if exists:
            try:
                store.getattr(self.cid, oid, WHITEOUT_KEY)
                whiteout = True
            except StoreError:
                pass
        if not resumed:
            trk = getattr(msg, "_trk", None)
            if trk is not None:
                # one per client op, of no length: was the object in
                # the tier when the op came, what mode was the PG in,
                # how many bytes does the op bring
                now = time.monotonic()
                trk.add_span("tier.lookup", now, now,
                             hit=int(bool(exists)), mode=self.evict_mode,
                             bytes=self.osd._qos_payload_bytes(msg))
        if pool.cache_mode == "readonly":
            if writes:
                # readonly tiers serve reads only; the objecter sends
                # writes to the base pool — one reaching us is an
                # addressing error, not redirectable state
                self._reply(conn, msg, -22, [])
                return True
            if whiteout:
                # a leftover writeback-era whiteout is NOT an object
                self._reply(conn, msg, -ENOENT, [])
                return True
            if exists or promoted:
                return False
            waiting = self._promote_waiting.get(oid)
            if waiting is not None:
                self._park(waiting["ops"], conn, msg, "tier.promote_wait")
            else:
                self._promote(conn, msg)
            return True
        # writeback
        if whiteout:
            if writes:
                return False      # revive semantics in _build_txn
            self._reply(conn, msg, -ENOENT, [])
            return True
        if exists:
            return False
        waiting = self._promote_waiting.get(oid)
        if waiting is not None:
            self._park(waiting["ops"], conn, msg, "tier.promote_wait")
            return True
        # a miss: the op adds an object, which a full tier holds back
        # (waiting_for_cache_not_full); it is never failed
        if self.evict_mode == EVICT_FULL:
            if not getattr(msg, "_tier_full_waited", False):
                msg._tier_full_waited = True
                self.osd.perf.inc("tier_full_waits")
            self._park_full(conn, msg)
            return True
        if promoted:
            return False          # the base has no such object
        # a whole-object write needs no base copy
        if writes and any(op[0] == "writefull" for op in msg.ops):
            return False
        self._promote(conn, msg)
        return True

    def _park(self, where: list, conn, msg, span: str) -> None:
        trk = getattr(msg, "_trk", None)
        if trk is not None:
            trk.span_begin(span, oid=msg.oid, mode=self.evict_mode)
        msg._tier_parked = span
        where.append((conn, msg))

    def _park_full(self, conn, msg) -> None:
        """Hold an op back until the tier has room."""
        self._park(self._full_waiting, conn, msg, "tier.full_wait")
        self.osd.tier_agent.enqueue(self.pgid)

    @staticmethod
    def _unpark(msg) -> None:
        """Close the wait `_park` opened on the op's doc."""
        trk = getattr(msg, "_trk", None)
        span = getattr(msg, "_tier_parked", None)
        if trk is not None and span:
            trk.span_end(span)
        msg._tier_parked = None

    def _resume_parked(self, conn, msg) -> None:
        """Op-queue re-entry of an op a promote or a full tier had
        parked: close its wait and run it from the top (do_op checks
        everything again; it may park again)."""
        self._unpark(msg)
        self.osd._handle_op(conn, msg)
        with self.lock:
            self._tier_unpin(msg)     # where do_op turned it away

    def _tier_unpin(self, msg) -> None:
        """The op the promoted copy was pinned for runs now (or was
        turned away).  Caller holds self.lock."""
        if getattr(msg, "_tier_pin", False):
            msg._tier_pin = False
            left = self._tier_pinned.get(msg.oid, 0) - 1
            if left > 0:
                self._tier_pinned[msg.oid] = left
            else:
                self._tier_pinned.pop(msg.oid, None)

    def _requeue(self, waiters: list, absent: bool = False) -> None:
        """Send parked ops back through the op queue.  `absent`: their
        promote found no such object at the base (`_promoted`: a write
        creates it, a read answers ENOENT)."""
        for conn, m in waiters:
            if absent:
                m._promoted = True
            self.osd.op_wq.queue(self.pgid, self._resume_parked, conn, m)

    def _wake_full_waiters(self) -> None:
        """The tier is full no longer: the held ops go round again, in
        the order they came; the first to run takes the room and the
        others are held again."""
        waiters, self._full_waiting = self._full_waiting, []
        self._requeue(waiters)

    def _fail_parked(self, waiters: list, result: int) -> None:
        for conn, m in waiters:
            self._unpark(m)
            self._reply(conn, m, result, [])

    def _drop_tier_waiters(self) -> None:
        """New interval: what the parked ops waited for belongs to a
        dead one - EAGAIN them back (clients resend against the
        re-peered pg).  Caller holds self.lock."""
        self._tier_ready = False
        self._tier_pinned.clear()
        waiters, self._full_waiting = self._full_waiting, []
        for ent in self._promote_waiting.values():
            waiters += ent["ops"]
            ent["ops"] = []
        for ent in self._flushing.values():
            waiters += ent["waiters"]
            ent["waiters"] = []
        self._fail_parked(waiters, -11)
        self.osd.tier_agent.dequeue(self.pgid)

    # ---- promote ----------------------------------------------------------

    def _promote(self, conn, msg) -> None:
        """Async copy-up from the base pool (promote_object +
        CopyFromCallback model), a tracked op of kind `tier_promote`
        under the client op's trace id: `base_read`, then `install`
        through the normal replicated write path; the op parks until
        the copy is installed."""
        oid = msg.oid
        base = self.base_pool
        if base is None:
            self._reply(conn, msg, -22, [])
            return
        ctrk = getattr(msg, "_trk", None)
        ent = self._promote_waiting[oid] = {"ops": []}
        self._park(ent["ops"], conn, msg, "tier.promote_wait")
        self._tier_count("tier_promote")
        trk = self.osd.op_tracker.create(
            f"tier_promote({self.pgid} {oid})",
            trace_id=getattr(ctrk, "trace_id", "") or "",
            kind="tier_promote")
        self.osd.base_pool_op(
            base.id, oid,
            [("read", 0, 0), ("getxattrs",), ("omap_get",)],
            lambda reply: self.osd.op_wq.queue(
                self.pgid, self._finish_promote, oid, reply, trk),
            trk=trk, span="base_read", fail="tier_promote_fail")
        self._tier_choose_mode()      # its room is taken from now on

    def _finish_promote(self, oid: str, reply, trk) -> None:
        with self.lock:
            ent = self._promote_waiting.pop(oid, None)
            waiters = ent["ops"] if ent else []
            if self._promote_ended(oid, reply, waiters):
                trk.finish()
                self._tier_choose_mode()   # its room is free again
                return
            if self._tier_mode_now()[1] == EVICT_FULL:
                # its room was taken by guess (the largest object this
                # PG had seen); the PG is full without it now that
                # sizes are known: the copy is dropped, its ops wait
                # for room and promote again
                trk.finish()
                for conn, m in waiters:
                    self._unpark(m)
                    self._park_full(conn, m)
                return
            data, xattrs, omap = (reply.outdata + [b"", {}, {}])[:3]
            ops: list = [("writefull", data or b"")]
            for k, v in (xattrs or {}).items():
                ops.append(("setxattr", k, v))
            if omap:
                ops.append(("omap_set", dict(omap)))

            def installed(result: int) -> None:
                with self.lock:
                    trk.span_end("replica_wait")
                    trk.span_end("install")
                    trk.finish()
                    if result == 0:
                        self._requeue(waiters)
                    else:
                        for _conn, m in waiters:
                            self._tier_unpin(m)
                        self._fail_parked(waiters, result or -11)

            # the copy is in the index, clean, from the moment the
            # primary applied it: pinned until its ops have run
            for _conn, m in waiters:
                m._tier_pin = True
            self._tier_pinned[oid] = \
                self._tier_pinned.get(oid, 0) + len(waiters)

            trk.span_begin("install", bytes=len(data or b""))
            with optracker.op_context(trk):
                self._internal_write(oid, ops, installed, trk=trk)

    def _promote_ended(self, oid: str, reply, waiters: list) -> bool:
        """Did the promote end without a copy to install?  Then its
        waiters are answered or sent round again here.  Caller holds
        self.lock."""
        if not waiters:
            return True
        if not (self.is_primary and self.active):
            self._fail_parked(waiters, -11)
            return True
        if self.osd.store.exists(self.cid, oid):
            # a whole-object client write raced the base fetch and
            # fully defined the object — installing the (older)
            # base copy over it would lose the acked write
            self._requeue(waiters)
            return True
        if reply is None:
            # counted by base_pool_op; the agent looks again at once
            self._fail_parked(waiters, -11)     # retryable
            self.osd.tier_agent.enqueue(self.pgid)
            return True
        if reply.result != 0:
            # base miss: reads answer ENOENT; writes proceed and
            # create the object fresh in the tier
            go, fail = [], []
            for conn, m in waiters:
                _r, writes = self._split_ops(m.ops)
                (go if writes else fail).append((conn, m))
            self._requeue(go, absent=True)
            self._fail_parked(fail, reply.result)
            return True
        return False

    def _internal_write(self, oid: str, ops: list, done=None,
                        trk=None) -> None:
        """Write with no external client, through the NORMAL
        replicated path (version, log entry, fan-out) so tier
        replicas converge — a bare store txn would leave them
        inconsistent.  `trk`: the tracked op the write's spans
        (`msgr.send`, `replica_wait`) and its sub-ops' trace id belong
        to.  Caller holds self.lock."""
        msg = MOSDOp(tid=next(self._int_tid), pgid=str(self.pgid),
                     oid=oid, ops=ops, epoch=self.osd.osdmap.epoch)
        msg.src = f"osd.{self.osd.whoami}.cache.{self.pgid}"
        msg._cache_internal = True
        msg._internal_done = done
        msg._trk = trk
        self._do_write(None, msg)

    # ---- hit sets ----------------------------------------------------------

    def _hit_set_record(self, oid: str) -> None:
        """Append the access to the current HitSet, rotating by
        hit_set_period and keeping hit_set_count sets (HitSet history;
        persisted in the pg meta omap on rotation, hit_set_persist)."""
        pool = self.pool
        period = float(pool.hit_set_period or 0)
        count = max(1, int(pool.hit_set_count or 1))
        now = self.osd.clock.now()
        rotate = (not self.hit_sets or
                  (period > 0 and now - self.hit_sets[-1][0] >= period)
                  # period<=0 misconfiguration: still bound the set
                  or len(self.hit_sets[-1][1]) >= 65536)
        if rotate:
            self.hit_sets.append([now, set()])
            del self.hit_sets[:-count]
            txn = Transaction().omap_setkeys(
                self.cid, "_pgmeta",
                {"hitsets": denc.dumps(
                    [[ts, sorted(s)] for ts, s in self.hit_sets])})
            try:
                self.osd.store.apply_transaction(txn)
            except StoreError:
                pass
        self.hit_sets[-1][1].add(oid)

    def _hot_oids(self) -> set:
        hot: set = set()
        for _ts, oids in self.hit_sets:
            hot |= oids
        return hot

    # ---- the agent ---------------------------------------------------------

    def agent_work(self, max_ops: int, max_low_ops: int) -> int:
        """One agent pass over this PG (ReplicatedPG::agent_work), on
        the OSD's agent thread: start at most `max_ops` flushes and
        evicts (`max_low_ops` flushes in flush mode low); returns how
        many it started.  Its candidates are the index's: no listing,
        no attribute read.

        Dirty and whiteout flushing runs in EVERY cache mode while
        the pool is linked as a tier — switching writeback ->
        readonly -> none must not strand un-flushed updates/deletes in
        the tier.  Eviction is writeback-only."""
        with self.lock:
            if not self._tier_ensure():
                self.osd.tier_agent.dequeue(self.pgid)
                return 0
            base = self.base_pool
            if base is None:
                return 0
            self.osd.perf.inc("agent_wake")
            self._tier_choose_mode(wake=False)
            started = 0
            now = self.osd.clock.now()
            pool = self.pool
            full = self.evict_mode == EVICT_FULL
            if self.flush_mode != FLUSH_IDLE:
                quota = max_ops if self.flush_mode == FLUSH_HIGH \
                    else min(max_ops, max_low_ops)
                # how many flushes bring the PG back under the ratio
                # that started them: each one in flight counts
                inflight = sum(1 for f in self._flushing.values()
                               if f["agent"])
                want = self._flush_wanted(quota + inflight) - inflight
                min_age = float(pool.cache_min_flush_age or 0)
                for oid, ent in list(self._tier_index.items()):
                    if quota <= 0 or want <= 0:
                        break
                    if not ent.dirty or oid in self._flushing:
                        continue
                    if not full and min_age > 0 \
                            and now - ent.mtime < min_age:
                        continue
                    self.osd.perf.inc("agent_flush")
                    self._start_flush(oid, agent=True)
                    quota -= 1
                    want -= 1
                    started += 1
            room = max_ops - started
            if self.evict_mode != EVICT_IDLE and room > 0:
                started += self._agent_evict(room, now, full)
            return started

    def _flush_wanted(self, cap: int) -> int:
        """How many flushes (`cap` at most) bring the PG back under
        the dirty ratio, with the hysteresis of a mode that is on."""
        n = 0
        while n < min(cap, self._tier_dirty) \
                and self._tier_mode_now(n)[0] != FLUSH_IDLE:
            n += 1
        return n

    def _evictable(self, oid: str, ent: TierObject) -> bool:
        """Clean, and nobody holds it: no flush in flight, no watcher,
        no op that waited for its promote still to run."""
        return not (ent.dirty or ent.whiteout or oid in self._flushing
                    or oid in self.watchers or oid in self._tier_pinned)

    def _agent_evict(self, room: int, now: float, full: bool) -> int:
        """Evict clean objects, cold ones (in no hit set) first, until
        the PG is back under its full ratio or `room` is used."""
        min_age = float(self.pool.cache_min_evict_age or 0)
        hot = self._hot_oids()
        clean = [oid for oid, ent in self._tier_index.items()
                 if self._evictable(oid, ent)
                 and (full or min_age <= 0 or now - ent.mtime >= min_age)]
        clean.sort(key=lambda o: o in hot)
        started = 0
        work = None
        for oid in clean:
            # an evict leaves the index (and moves the mode) as soon
            # as the primary applied it
            if started >= room or self.evict_mode == EVICT_IDLE:
                break
            size = self._tier_index[oid].size
            if work is None:
                # the pass's op: its evicts are spans of it, and it
                # ends with the last of them
                work = {"evicts": 0, "trk": self.osd.op_tracker.create(
                    f"tier_agent({self.pgid} flush={self.flush_mode} "
                    f"evict={self.evict_mode})",
                    trace_id=f"tier_agent:{self.osd.whoami}:{self.pgid}:"
                             f"{next(self._tier_seq)}",
                    kind="tier_agent")}
            if self._start_evict(oid, work, size):
                self.osd.perf.inc("agent_evict")
                started += 1
        if work is not None and not work["evicts"]:
            work["trk"].finish()
        return started

    # ---- flush -------------------------------------------------------------

    def _is_whiteout(self, oid: str) -> bool:
        ent = self._tier_index.get(oid)
        return ent is not None and ent.whiteout

    def _start_flush(self, oid: str, agent: bool = False) -> dict:
        """Push the tier's copy of a dirty object (or its whiteout) to
        the base pool, a tracked op of kind `tier_flush`: `tier_read`,
        then `base_write`; the object is marked clean afterwards,
        unless a newer write overtook the flush (start_flush /
        finish_flush).  Caller holds self.lock."""
        base = self.base_pool
        ent = self._flushing[oid] = {
            "version": self.pglog.objects.get(oid), "agent": agent,
            "waiters": [], "try": None}
        if agent:
            self.osd.tier_agent.op_started()
        self._tier_count("tier_flush")
        store = self.osd.store
        trk = self.osd.op_tracker.create(
            f"tier_flush({self.pgid} {oid})",
            trace_id=f"tier_flush:{self.osd.whoami}:{self.pgid}:"
                     f"{next(self._tier_seq)}", kind="tier_flush")
        trk.span_begin("tier_read")
        if self._is_whiteout(oid):
            ops: list = [("delete",)]
            size = 0
        else:
            try:
                data = store.read(self.cid, oid)
                attrs = store.getattrs(self.cid, oid)
            except StoreError:
                # gone under the flush: nothing to push
                self.osd.op_wq.queue(self.pgid, self._finish_flush, oid,
                                     None, trk)
                return ent
            try:
                omap = store.omap_get(self.cid, oid)
            except StoreError:
                omap = {}
            size = len(data)
            ops = [("writefull", data)]
            for k, v in attrs.items():
                if k.startswith("u."):
                    ops.append(("setxattr", k[2:], v))
            if omap:
                ops.append(("omap_set", dict(omap)))
        trk.span_end("tier_read", bytes=size)
        self.osd.base_pool_op(
            base.id, oid, ops,
            lambda reply: self.osd.op_wq.queue(
                self.pgid, self._finish_flush, oid, reply, trk),
            trk=trk, span="base_write", fail="tier_flush_fail",
            bytes=size, mode=self.flush_mode)
        return ent

    def _finish_flush(self, oid: str, reply, trk) -> None:
        with self.lock:
            ent = self._flushing.get(oid)
            if ent is None:
                trk.finish()
                return
            if not (self.is_primary and self.active):
                self._flush_done(oid, -11, trk)
                return
            whiteout = self._is_whiteout(oid)
            if reply is None:
                # counted by base_pool_op; the agent looks again at once
                self._tier_account(oid)     # it may be gone
                result = -110
            elif reply.result == -ENOENT and whiteout:
                result = 0
            else:
                result = reply.result
            if result == 0 \
                    and self.pglog.objects.get(oid) != ent["version"]:
                result = -EBUSY     # a newer write overtook the flush:
                                    # still dirty, flushed again
            if result != 0:
                self._flush_done(oid, result, trk)
                return
            if whiteout:
                # base is clean (deleted or never had it): retire the
                # whiteout on the whole acting set
                ops = [("evict",)]
            else:
                ops = [("rmattr_raw", DIRTY_KEY)]
            self._internal_write(
                oid, ops, lambda r: self._flush_done(oid, r, trk))

    def _flush_done(self, oid: str, result: int, trk) -> None:
        """A flush ended (marked clean, failed, or overtaken): answer
        the operator's ops that waited on it, free the agent's slot."""
        with self.lock:
            ent = self._flushing.pop(oid, None)
            trk.finish()
            if ent is None:
                return
            if ent["agent"]:
                self.osd.tier_agent.op_finished()
            if ent["try"] is not None:
                conn, m = ent["try"]
                if result != 0:
                    self.osd.perf.inc("tier_try_flush_fail")
                self._reply(conn, m, result, [])
            if result == -EBUSY:
                # the blocking flushes start over on the newer version
                self._requeue(ent["waiters"])
            else:
                for conn, m in ent["waiters"]:
                    self._reply(conn, m, result, [])
            self._tier_choose_mode()
            if result != 0:
                self.osd.tier_agent.enqueue(self.pgid)

    # ---- evict -------------------------------------------------------------

    def _start_evict(self, oid: str, work: dict | None = None,
                     size: int = 0, done=None) -> bool:
        """Drop a clean object from the tier (the base holds it).  The
        store is asked once more whether it is clean: the index says
        so, and an evicted dirty object is an acknowledged write
        lost.  Caller holds self.lock."""
        try:
            attrs = self.osd.store.getattrs(self.cid, oid)
        except StoreError:
            return False
        if DIRTY_KEY in attrs or WHITEOUT_KEY in attrs:
            self.osd.perf.inc("tier_evict_dirty")
            self._tier_account(oid)
            return False
        self._tier_count("tier_evict")
        agent = work is not None
        if agent:
            self.osd.tier_agent.op_started()
            work["evicts"] += 1
            work["trk"].span_begin("tier.evict", oid=oid, bytes=size,
                                   mode=self.evict_mode)

        def evicted(result: int) -> None:
            with self.lock:
                if agent:
                    work["trk"].span_end("tier.evict")
                    work["evicts"] -= 1
                    if not work["evicts"]:
                        work["trk"].finish()
                    self.osd.tier_agent.op_finished()
                if done is not None:
                    done(result)
                self._tier_choose_mode()

        self._internal_write(oid, [("evict",)], evicted)
        return True

    # ---- the operator's ops ------------------------------------------------

    def _do_cache_op(self, conn, msg) -> None:
        """cache-flush (waits for a flush in flight, and flushes again
        if a write overtook it), cache-try-flush (EBUSY instead),
        cache-evict (EBUSY on a dirty or watched object); ENOENT on an
        object the tier does not hold.  Caller holds self.lock."""
        name, oid = msg.ops[0][0], msg.oid
        if len(msg.ops) != 1 or not self.is_tier:
            self._reply(conn, msg, -22, [])
            return
        if not self._tier_ensure():
            self._reply(conn, msg, -11, [])
            return
        ent = self._tier_index.get(oid)
        if ent is None:
            self._reply(conn, msg, -ENOENT, [])
            return
        if name == "cache-evict":
            if not self._evictable(oid, ent) or not self._start_evict(
                    oid, done=lambda r: self._reply(conn, msg, r, [])):
                self._reply(conn, msg, -EBUSY, [])
            return
        if not ent.dirty:
            self._reply(conn, msg, 0, [])      # clean: nothing to do
            return
        flight = self._flushing.get(oid)
        if name == "cache-try-flush":
            if flight is not None:
                self.osd.perf.inc("tier_try_flush_fail")
                self._reply(conn, msg, -EBUSY, [])
                return
            self._start_flush(oid)["try"] = (conn, msg)
            return
        if flight is None:
            flight = self._start_flush(oid)
        flight["waiters"].append((conn, msg))
