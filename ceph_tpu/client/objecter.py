"""Objecter: client op targeting + resend state machine.

The osdc/Objecter.{h,cc} analog: each op computes its target pg/primary
from the current OSDMap client-side (CRUSH — no lookup service), sends
MOSDOp, and resends on map change or EAGAIN from a stale/degraded
primary (op_submit/_calc_target/_send_op semantics, Objecter.cc:2289,
2661, 3078).  Ops carry a budget throttle like the reference's.

A silent op is resent on evidence.  The timer follows the reply latency
this client has seen from the op's target (`_ReplyLatency`: Jacobson's
smoothed latency and deviation, sampled under Karn's rule), a reset of
an established session resends its peer's ops at once
(Objecter::ms_handle_reset -> _kick_requests), and a connection is
marked down only when the LINK has gone quiet, never because one op is
slow.
"""

from __future__ import annotations

import itertools
import threading
import time

from ..mon.client import MonClient
from ..msg import Dispatcher, Message, Messenger
from ..osd.messages import MOSDOp, MOSDOpReply
from ..osd.osdmap import OSDMap
from ..utils.bufferlist import BufferList, wrap_payload
from ..utils.dout import DoutLogger
from ..utils.perf_counters import PerfCountersBuilder
from ..utils.throttle import Throttle

# the defined errno an op fails with when its deadline exhausts
# (ETIMEDOUT — the rados_osd_op_timeout contract)
ETIMEDOUT = 110


# why an op that was sent before is sent again -> its counter
_RESEND_COUNTER = {cause: f"op_resend_{cause}"
                   for cause in ("timer", "reset", "map", "eagain")}


class ObjecterError(Exception):
    def __init__(self, errno_: int, msg: str = ""):
        super().__init__(msg or f"errno {errno_}")
        self.errno = errno_


class _ReplyLatency:
    """Reply latency of one target, as TCP keeps its round-trip time
    (RFC 6298): `srtt` and `dev` are moved only by ops answered on
    their FIRST send (a resent op's reply may answer any of its sends:
    Karn's rule).  What a resent op teaches is a bound: its reply came
    no later after its first send than the target takes, so that long
    is `held` as the timeout of the target's next ops until one of
    them is answered on its first send.  Without `held` a target
    slower than the floor would have every op resent, so none sampled,
    and nothing learnt; TCP holds its doubled timer for the same
    reason, and a duplicate here is a whole 4 MiB frame, so the bound
    is taken in one step and not by doubling up to it."""

    __slots__ = ("srtt", "dev", "held")

    def __init__(self):
        self.srtt: float | None = None
        self.dev = 0.0
        self.held = 0.0

    def sample(self, latency: float) -> None:
        if self.srtt is None:
            self.srtt, self.dev = latency, latency / 2
        else:
            self.dev += (abs(latency - self.srtt) - self.dev) / 4
            self.srtt += (latency - self.srtt) / 8
        self.held = 0.0

    def timeout(self, floor: float) -> float:
        seen = 0.0 if self.srtt is None else self.srtt + 4 * self.dev
        return max(floor, seen, self.held)


class _Op:
    __slots__ = ("tid", "pool", "oid", "ops", "event", "reply", "attempts",
                 "pgid", "snapc", "snapid", "primary", "sent_at",
                 "is_write")

    def __init__(self, tid, pool, oid, ops, pgid=None, snapc=None,
                 snapid=None):
        self.tid = tid
        self.pool = pool
        self.oid = oid
        self.ops = ops
        self.pgid = pgid            # explicit target (pg listing ops)
        self.snapc = snapc          # (seq, [snaps]) write snap context
        self.snapid = snapid        # read-at-snap
        self.event = threading.Event()
        self.reply = None
        self.attempts = 0
        self.primary = None         # osd the last send went to
        self.sent_at = 0.0          # when (time.monotonic())
        self.is_write = Objecter._is_write(ops)


class Objecter(Dispatcher):
    def __init__(self, msgr: Messenger, monc: MonClient):
        self.msgr = msgr
        self.monc = monc
        self.conf = msgr.conf
        self.log = DoutLogger("objecter", msgr.name)
        self._tid = itertools.count(1)
        self._ops: dict[int, _Op] = {}
        self._lock = threading.Lock()
        self.throttle = Throttle("objecter-ops", 1024)
        self.on_map_hooks: list = []     # linger-ish: rewatch etc.
        # (primary osd, is a write) -> what its replies have taken
        self._latency: dict[tuple, _ReplyLatency] = {}
        self._kicked_at: dict[int, float] = {}
        self.perf = (PerfCountersBuilder(f"objecter.{msgr.name}")
                     .add_u64_counter("op_send")
                     .add_u64_counter("op_resend")
                     .add_u64_counter("op_resend_timer")
                     .add_u64_counter("op_resend_reset")
                     .add_u64_counter("op_resend_map")
                     .add_u64_counter("op_resend_eagain")
                     .add_u64_counter("conn_kick")
                     # targets the map's placement table answered,
                     # and those CRUSH had to work out
                     .add_u64_counter("placement_hit")
                     .add_u64_counter("placement_miss")
                     .create_perf_counters())
        msgr.add_dispatcher_head(self)
        monc.on_osdmap = self._on_map
        monc.count_placement(self.perf)

    @property
    def osdmap(self) -> OSDMap:
        return self.monc.osdmap

    # -- submission --------------------------------------------------------

    def op_submit(self, pool_id: int, oid: str, ops: list,
                  timeout: float | None = None, pgid=None, snapc=None,
                  snapid=None) -> Message:
        """Submit and wait, bounded by a per-op deadline.

        The op resends for as long as it lives (Objecter::_op_submit +
        _maybe_request_map, osdc/Objecter.cc:2289, 2661).  How long it
        waits in silence before the first resend is what this client
        has seen of its target (`resend_timeout`): the smoothed reply
        latency plus four deviations, never under
        objecter_backoff_base, which is also what a target nothing is
        known of gets.  Each further silent try doubles the wait, up
        to objecter_backoff_max or the observed timeout, whichever is
        longer, and re-requests newer maps.  A resend by the timer is
        also a probe of the link: every frame is acked by the peer's
        messenger, so a live link answers it even while the op is
        still being served.  Only when nothing at all has come in on
        the link to the primary (no reply, no ack, of any op) for
        objecter_silent_kick seconds of this op's waiting, the last
        send included, is the connection marked down so the resend
        dials a fresh socket: an opaque wedge in a long-lived session
        costs one reconnect, and a link that carries other ops'
        replies is never torn down under them.  On deadline exhaustion
        the op fails with the DEFINED errno ETIMEDOUT (110); an op
        whose OSD dies mid-flight can never hang forever, even if no
        new osdmap arrives."""
        if timeout is None:
            timeout = float(self.conf.objecter_op_timeout)
        self.throttle.get(1, timeout=timeout)
        try:
            # zero-copy payload contract: ops may carry bytes,
            # memoryview or BufferList payloads that ride untouched to
            # the messenger's gather write.  An op outlives this call's
            # frame (map-change resends re-encode it), so mutable
            # bytearrays are snapshotted HERE — the single defense
            # point for every client surface.
            ops = [tuple(wrap_payload(f) if isinstance(
                f, (bytes, bytearray, memoryview, BufferList)) else f
                for f in op) for op in ops]
            op = _Op(next(self._tid), pool_id, oid, ops, pgid,
                     snapc=snapc, snapid=snapid)
            with self._lock:
                self._ops[op.tid] = op
            deadline = time.monotonic() + timeout
            base = self._floor()
            bmax = max(base, float(self.conf.objecter_backoff_max))
            kick_after = max(2 * base,
                             float(self.conf.objecter_silent_kick))
            tries = 0           # waits that ran out since `first_sent`:
            first_sent = 0.0    # the first send to this primary, or the
            last_primary = None             # first after its EAGAIN
            # why the next send is a resend, if it is one: only the map
            # handler can have sent an op before its submitter does
            cause = "map"
            while True:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                primary = self._send(op, cause)
                sent = primary is not None
                if primary != last_primary:
                    # retargeted (map change): the silence clock and
                    # the backoff curve belong to the OLD link — a
                    # fresh primary gets its own timeout's tries
                    # before its conn is suspected
                    tries = 0
                    first_sent = time.monotonic()
                    last_primary = primary
                if not sent:
                    # no primary yet (pool absent / not enough osds):
                    # ask for newer maps and wait for one to arrive
                    self.monc.sub_want_osdmap(self.osdmap.epoch + 1)
                rto = self.resend_timeout(primary, op.is_write)
                waited = min(rto * (1 << min(tries, 16)), max(bmax, rto),
                             remain)
                if op.event.wait(waited):
                    reply = op.reply
                    if reply.result == -11:     # EAGAIN: resend later
                        op.event.clear()
                        op.reply = None
                        tries = 0
                        cause = "eagain"
                        time.sleep(0.2)
                        first_sent = time.monotonic()
                        self.monc.sub_want_osdmap(self.osdmap.epoch + 1)
                        continue
                    with self._lock:
                        self._ops.pop(op.tid, None)
                        self._note_reply(op, primary, first_sent, tries)
                    return reply
                op.event.clear()
                tries += 1
                cause = "timer"
                if sent:
                    self.monc.sub_want_osdmap(self.osdmap.epoch + 1)
                    if self._link_silent(primary, op, first_sent,
                                         kick_after):
                        self._kick_target(primary, op.tid)
            with self._lock:
                self._ops.pop(op.tid, None)
            raise ObjecterError(
                ETIMEDOUT,
                f"op on {oid} timed out after {timeout:.1f}s "
                f"({op.attempts} attempts)")
        finally:
            self.throttle.put(1)

    # -- when to resend: what has been seen of the target -------------------

    def _floor(self) -> float:
        return max(0.05, float(self.conf.objecter_backoff_base))

    def resend_timeout(self, primary: int | None, is_write: bool) -> float:
        """Seconds an op to this target waits in silence before its
        first resend."""
        floor = self._floor()
        seen = self._latency.get((primary, is_write))
        return floor if seen is None else seen.timeout(floor)

    def _note_reply(self, op: _Op, primary: int, first_sent: float,
                    tries: int) -> None:
        """An op was answered (caller holds self._lock).  Sent once,
        its latency is a sample of its target's; resent by the timer,
        (`tries` waits ran out), the time since its first send to this
        primary bounds what the target took and is held for the
        target's next ops; resent for
        another cause alone (map, reset, EAGAIN), or by the map
        handler to another primary than its submitter knows of, it
        says nothing about how long the target takes."""
        if op.primary != primary or (op.attempts > 1 and not tries):
            return
        key = (op.primary, op.is_write)
        seen = self._latency.get(key)
        if seen is None:
            seen = self._latency[key] = _ReplyLatency()
        if op.attempts == 1:
            seen.sample(time.monotonic() - op.sent_at)
        else:
            seen.held = time.monotonic() - first_sent

    def _link_silent(self, primary: int, op: _Op, first_sent: float,
                     kick_after: float) -> bool:
        """Has the link to `primary` been quiet for the kick window
        while this op waited on it?  The messenger stamps `last_recv`
        on a connection for every frame it reads there, acks included,
        so an ack of this op's last send, or anything of any other op,
        says the link is alive."""
        conn = self.msgr.conns.get(f"osd.{primary}")
        heard = 0.0 if conn is None else conn.last_recv
        if heard > op.sent_at:
            return False
        quiet_since = max(heard, first_sent,
                          self._kicked_at.get(primary, 0.0))
        return time.monotonic() - quiet_since >= kick_after

    def _kick_target(self, primary: int, tid: int) -> None:
        """Mark down the connection to a primary whose link is silent.
        What other ops had queued on it is lost with it, so they are
        resent with this one."""
        self._kicked_at[primary] = time.monotonic()
        conn = self.msgr.conns.get(f"osd.{primary}")
        if conn is None:
            return
        self.log.warn("op %d: link to osd.%d silent: marking conn down",
                      tid, primary)
        self.perf.inc("conn_kick")
        conn.mark_down()
        self._resend_peer(primary, "reset", skip=tid)

    @staticmethod
    def _is_write(ops: list) -> bool:
        for op in ops:
            if op[0] in ("read", "stat", "getxattr", "getxattrs",
                         "omap_get", "list"):
                continue
            if op[0] == "call":
                from ..cls import registry as cls_registry
                if not cls_registry.is_write(op[1], op[2]):
                    continue
            return True
        return False

    def _target_pool(self, op: _Op) -> int:
        """Cache-tier overlay redirect (Objecter::_calc_target
        consulting pg_pool_t read_tier/write_tier, Objecter.cc:2661):
        ops aimed at a base pool with an overlay go to the tier pool;
        in readonly mode only reads are diverted."""
        pool = self.osdmap.pools.get(op.pool)
        if pool is None or (pool.read_tier < 0 and pool.write_tier < 0):
            return op.pool
        if op.is_write:
            tier = self.osdmap.pools.get(pool.write_tier)
            if tier is not None and tier.cache_mode == "writeback":
                return tier.id
            return op.pool
        tier = self.osdmap.pools.get(pool.read_tier)
        if tier is not None and tier.cache_mode in ("writeback",
                                                    "readonly"):
            return tier.id
        return op.pool

    def _send(self, op: _Op, cause: str) -> int | None:
        """Send to the current target; return the primary osd id, or
        None when the op cannot be targeted yet (pool absent, no
        primary, no address).  `cause` says why an op that was sent
        before is sent again: `timer`, `reset`, `map` or `eagain`."""
        m = self.osdmap
        if op.pool not in m.pools:
            return None
        pgid = op.pgid if op.pgid is not None else \
            m.object_to_pg(self._target_pool(op), op.oid)
        primary = m.pg_primary(pgid)
        if primary is None:
            return None
        addr = m.get_addr(primary)
        if addr is None:
            return None
        op.attempts += 1
        op.primary = primary
        op.sent_at = time.monotonic()
        self.perf.inc("op_send")
        if op.attempts > 1:
            self.perf.inc("op_resend")
            self.perf.inc(_RESEND_COUNTER[cause])
        self.msgr.send_message(
            # `attempt`: which send of this op this is; the OSD puts
            # it on the op's doc, so resends can be counted from dumps
            MOSDOp(tid=op.tid, pgid=str(pgid), oid=op.oid, ops=op.ops,
                   epoch=m.epoch, snapc=op.snapc, snapid=op.snapid,
                   attempt=op.attempts),
            f"osd.{primary}", tuple(addr))
        return primary

    # -- map change: resend everything pending (resend_mon_ops model) ------

    def _on_map(self, osdmap: OSDMap) -> None:
        with self._lock:
            pending = [op for op in self._ops.values() if op.reply is None]
        for op in pending:
            self._send(op, "map")
        for hook in list(self.on_map_hooks):
            try:
                hook(osdmap)
            except Exception:
                self.log.error("on-map hook failed")

    # -- dispatch ----------------------------------------------------------

    def ms_dispatch(self, conn, msg: Message) -> bool:
        if isinstance(msg, MOSDOpReply):
            with self._lock:
                op = self._ops.get(msg.tid)
            if op is not None:
                op.reply = msg
                op.event.set()
            return True
        return False

    def ms_handle_reset(self, conn) -> None:
        """A session to an OSD was lost: what was queued or in flight
        on it went with it, so this peer's pending ops are sent again
        now and not when their timers run out
        (Objecter::ms_handle_reset -> _kick_requests).  Only a session
        that had been up counts, one on which a frame of the peer was
        read: a peer that cannot be reached resets every dial at once,
        and its ops stay with the timer, which backs off."""
        kind, _, num = conn.peer_name.partition(".")
        if kind == "osd" and num.isdigit() and conn.last_recv > 0:
            self._resend_peer(int(num), "reset")

    def _resend_peer(self, primary: int, cause: str,
                     skip: int | None = None) -> None:
        with self._lock:
            pending = [op for op in self._ops.values()
                       if op.reply is None and op.primary == primary
                       and op.tid != skip]
        for op in pending:
            self._send(op, cause)

    def perf_dump(self) -> dict:
        """The client's `perf dump objecter` block: sends and resends
        by cause, connections kicked, and the resend timeout each
        target has now."""
        out = self.perf.dump()
        with self._lock:
            out["ops_in_flight"] = len(self._ops)
            out["resend_timeout"] = {
                f"osd.{primary}/{'write' if is_write else 'read'}":
                    self.resend_timeout(primary, is_write)
                for primary, is_write in sorted(self._latency)}
        return {"objecter": out}
