"""The OSD daemon (osd/OSD.cc analog).

Owns two messengers (public for clients, cluster for peers — the
reference's 4-messenger split reduced to 2), a MonClient session, the
ObjectStore, and the PG map.  Requests are executed on a sharded op
queue keyed by pgid (ShardedOpWQ, osd/OSD.cc:8802) so per-PG ordering
holds while PGs run concurrently; replies and heartbeats are handled
inline on the messenger thread.

Heartbeats: every osd pings its peers (OSD::handle_osd_ping model);
a peer silent past osd_heartbeat_grace is reported to the mon
(MOSDFailure -> OSDMonitor::prepare_failure).

Deep scrub rides the TPU: each OSD batch-verifies its EC shard CRCs
against the stored HashInfo with one fused device pass per size class
(the north star's "deep-scrub-sized batches").
"""

from __future__ import annotations

import itertools
from ..utils import denc
import threading
import time

from typing import Callable

import numpy as np

from ..crush.map import ITEM_NONE
from ..mon.client import MonClient
from ..mon.monmap import MonMap
from ..msg import Dispatcher, Message, Policy, create_messenger
from ..ops import crc32c as crc_mod
from ..store import create as store_create
from ..store.objectstore import CrashPoint, StoreError, Transaction
from ..utils.config import Config
from ..utils.dout import DoutLogger
from ..utils.workqueue import ShardedThreadPool
from .messages import (MOSDECSubOpRead, MOSDECSubOpReadReply,
                       MOSDECSubOpWrite, MOSDECSubOpWriteReply, MOSDOp,
                       MOSDOpReply, MOSDPing, MOSDRepOp, MOSDRepOpReply,
                       MPGInfo, MPGPush, MPGPushReply, MOSDScrub,
                       MWatchNotifyAck, sender_id)
from .osdmap import OSDMap, PgId
from .pg import HINFO_KEY, PG, VER_KEY, shard_oid


from .recovery_svc import RecoveryService  # noqa: E402
from .scrubber import ScrubService  # noqa: E402

# dmClock client name for the recovery/backfill push class
# (osd_qos_recovery); "@" keeps it out of the pool namespace — client
# object (and pool) names containing "@" are rejected at the front door
RECOVERY_QOS_CLASS = "@recovery"


class OSDDaemon(Dispatcher, RecoveryService, ScrubService):
    def __init__(self, whoami: int, monmap: MonMap,
                 conf: Config | None = None, store_kind: str = "memstore",
                 store_path: str = "", clock=None):
        from ..utils.clock import SystemClock
        self.whoami = whoami
        self.entity = f"osd.{whoami}"
        self.conf = conf or Config()
        self.clock = clock or SystemClock()
        self.log = DoutLogger("osd", self.entity)
        self.osdmap = OSDMap()
        self.store = store_create(store_kind, store_path)
        self.store.owner = self.entity   # targeted store_eio fault scope
        # crash plane: a fired crash point freezes the store and this
        # callback aborts the daemon (power-loss simulation)
        self.store.crash_callback = self._on_store_crash
        if store_kind != "memstore":
            try:
                self.store.mount()
            except FileNotFoundError:
                self.store.mkfs()
                self.store.mount()

        self.msgr = create_messenger(self.entity, conf=self.conf)
        self.msgr.bind(("127.0.0.1", 0))
        self.msgr.set_policy("osd", Policy.lossless_peer())
        self.msgr.set_policy("mon", Policy.lossless_peer())
        self.msgr.set_policy("client", Policy.stateless_server())
        self.msgr.add_dispatcher_tail(self)

        self.monc = MonClient(self.msgr, monmap)
        self.monc.on_osdmap = self._on_osdmap

        self.pgs: dict[PgId, PG] = {}
        self.pg_lock = threading.RLock()
        # guards the recovery dedup sets ONLY.  Peering queues
        # backfills while holding pg.lock, and the map thread takes
        # pg_lock -> pg.lock, so the dedup guard must be its own lock:
        # reusing pg_lock there closes an ABBA deadlock cycle.
        self.backfill_lock = threading.Lock()
        self._backfills_active: set = set()
        self._rmtemp_active: set = set()
        # pgid -> {oid: [positions]}: the shard rebuilds this primary
        # still owes the PG, one entry a rebuild queued, running or
        # waiting for a retry (queue_ec_rebuild) and one an object a
        # backfill round found to push (`_rebuild_owed`).  A PG with
        # any is recovering, not clean, and a rebuild's plan reads no
        # position its object is still owed at (`rebuilds_owed`)
        self._rebuilds_pending: dict = {}
        # numbers the backfill rounds' ops (their trace ids)
        self._backfill_round_seq = itertools.count(1)
        # pgid -> last REAL-time incomplete-copy nudge (see _heartbeat)
        self._nudge_last: dict = {}
        # per-pool QoS (dmClock reservation/weight/limit service
        # classes, conf osd_pool_qos_<pool>="res:weight:lim"): ONE tag
        # state shared by every op shard so the configured rates hold
        # daemon-wide; client ops are tagged by pool in ms_dispatch,
        # internal work stays unconstrained (exact FIFO, never starved)
        from ..utils.dmclock import DmClockState
        self._qos = DmClockState()
        self._qos_names: set[str] = set()
        self.op_wq = ShardedThreadPool(
            f"osd{whoami}-ops", int(self.conf.osd_op_num_shards),
            qos_state=self._qos)
        # backfill/self-backfill rounds make BLOCKING peer RPCs
        # (ranged scans, full-log fetches) — on their own shards so a
        # round stuck in a 10s call can never convoy the op shard
        # that serves OTHER daemons' scan requests for a colliding
        # pgid (three daemons backfilling each other could otherwise
        # starve one another into permanent stall)
        self.recovery_wq = ShardedThreadPool(f"osd{whoami}-rcv", 2)

        # recovery reservations (AsyncReserver model): pushes/rebuilds
        # are granted bounded slots so recovery cannot starve client
        # I/O; a slot frees on push ack or a safety timer
        from ..utils.reserver import AsyncReserver
        self._recovery = AsyncReserver(
            int(self.conf.osd_recovery_max_active))

        self._ec_codecs: dict[str, object] = {}
        # the shared cross-op EC device pipeline (process-wide: every
        # producer feeding it is what makes batches mega)
        from ..ops import pipeline as ec_pipeline
        shards_conf = str(self.conf.osd_ec_device_shards).strip()
        ec_pipeline.configure(
            depth=int(self.conf.osd_ec_pipeline_depth),
            coalesce_wait=float(
                self.conf.osd_ec_pipeline_coalesce_ms) / 1000.0,
            max_batch=int(self.conf.osd_ec_pipeline_max_batch),
            device_shards=None if shards_conf in ("all", "0", "")
            else max(1, int(shards_conf)),
            scrub_weight=float(
                self.conf.osd_ec_pipeline_scrub_weight),
            cost_aware=bool(self.conf.osd_ec_cost_aware_placement),
            hbm_cache_bytes=int(self.conf.osd_ec_hbm_cache_bytes),
            qos_cost_unit=int(self.conf.osd_qos_cost_bytes_unit))
        # the tiering agent (OSDService::agent_entry): its thread
        # starts with the first tier PG that has work
        from .cache_tier import TierAgent
        self.tier_agent = TierAgent(self)
        self._rpc_tid = itertools.count(1)
        self._rpc: dict = {}
        self._rpc_async: dict[int, Callable] = {}
        self._rpc_cv = threading.Condition()
        self._hb_last: dict[int, float] = {}
        self._hb_timer = None
        self._removed_snaps_seen: dict[int, set] = {}
        self._map_requested_for = 0
        self._scrub_slots = threading.BoundedSemaphore(
            max(1, int(self.conf.osd_max_scrubs)))
        self._stopped = False

        # observability: perf counters + op tracing + admin socket
        # (common/perf_counters.h, common/TrackedOp.h,
        #  common/admin_socket.h — VERDICT: wired, not just built)
        from ..utils.admin_socket import AdminSocket
        from ..utils.optracker import OpTracker
        from ..utils.perf_counters import (PerfCountersBuilder,
                                           PerfCountersCollection)
        self.perf_collection = PerfCountersCollection()
        self.perf = (PerfCountersBuilder("osd")
                     .add_u64_counter("op")
                     .add_u64_counter("op_r")
                     .add_u64_counter("op_w")
                     .add_u64_counter("op_in_bytes")
                     .add_u64_counter("op_out_bytes")
                     .add_u64_counter("subop_w")
                     # log-authoritative peering: authority-proof
                     # catch-ups, auth-log merges, divergent rewinds
                     # (counter-asserted by the rewind drills), and
                     # recovery push accounting (recovery_bytes must
                     # track divergence, not pg size)
                     .add_u64_counter("peering_auth_catchups")
                     .add_u64_counter("peering_getlog_merges")
                     .add_u64_counter("peering_divergent_rewinds")
                     .add_u64_counter("peering_divergent_entries")
                     .add_u64_counter("recovery_pushes")
                     .add_u64_counter("recovery_bytes")
                     .add_u64_counter("backfill_resumes")
                     # what a repair did: backfill rounds and the
                     # objects they pushed or rebuilt; EC rebuilds by
                     # where the lost shard came from (the HBM cache's
                     # rows; decoded from fewer than k shards, a local
                     # repair; decoded from as many as a read asks)
                     .add_u64_counter("backfill_rounds")
                     .add_u64_counter("backfill_objects")
                     .add_u64_counter("rebuild_cache_served")
                     .add_u64_counter("rebuild_local")
                     .add_u64_counter("rebuild_full")
                     # of the rebuilds that read: those whose first
                     # plan's gather did not give the shard, so that
                     # every other holder was asked too; and the
                     # chunks their first plans named, summed
                     .add_u64_counter("rebuild_widened")
                     .add_u64_counter("rebuild_planned_chunks")
                     # the PG log as keys (pglog.persist_log): keys
                     # and bytes handed to transactions, and how often
                     # a log had to be written whole (a converted
                     # blob, an adopted window, a failed transaction)
                     .add_u64_counter("pglog_keys_written")
                     .add_u64_counter("pglog_bytes_written")
                     .add_u64_counter("pglog_full_rewrites")
                     # serve-during-repair: client ops parked on a
                     # missing object's recovery pull (and resumed
                     # after it lands — blocked == unblocked at
                     # quiesce is the no-stranded-ops invariant the
                     # storm drill asserts), plus pulls promoted to
                     # the front of the recovery queue for them
                     .add_u64_counter("recovery_blocked_ops")
                     .add_u64_counter("recovery_unblocked_ops")
                     .add_u64_counter("recovery_prio_promotions")
                     # EC reads whose planned gather did not give the
                     # object and that went on to the widened step
                     .add_u64_counter("ec_read_widened")
                     # EC appends that took the O(tail) path, and
                     # `append` ops on an existing object that fell
                     # back to the whole-object re-encode
                     .add_u64_counter("ec_appends")
                     .add_u64_counter("ec_append_fallbacks")
                     # cache tiering, under the reference's names:
                     # promotes, flushes (the agent's and the
                     # operator's) and evicts started, objects marked
                     # dirty and clean, failed ones, agent passes and
                     # what they started, client ops a full tier held
                     # back; and two that have to stay 0: evicts of a
                     # dirty object (refused), objects that entered a
                     # full tier
                     .add_u64_counter("tier_promote")
                     .add_u64_counter("tier_flush")
                     .add_u64_counter("tier_evict")
                     .add_u64_counter("tier_dirty")
                     .add_u64_counter("tier_clean")
                     .add_u64_counter("tier_try_flush_fail")
                     .add_u64_counter("tier_flush_fail")
                     .add_u64_counter("tier_promote_fail")
                     .add_u64_counter("agent_wake")
                     .add_u64_counter("agent_flush")
                     .add_u64_counter("agent_evict")
                     .add_u64_counter("tier_full_waits")
                     .add_u64_counter("tier_evict_dirty")
                     .add_u64_counter("tier_full_admit")
                     # PG mappings the map's placement table answered,
                     # and those CRUSH had to work out
                     .add_u64_counter("placement_hit")
                     .add_u64_counter("placement_miss")
                     .add_time_avg("op_latency")
                     .create_perf_counters())
        self.monc.count_placement(self.perf)
        self.perf_collection.add(self.perf)
        self.perf_collection.add(self.msgr.perf)
        self.op_tracker = OpTracker(
            self.clock,
            history_size=int(self.conf.osd_op_history_size),
            complaint_age=float(self.conf.osd_op_complaint_time),
            logger=self.log,
            history_duration=float(self.conf.osd_op_history_duration),
            enabled=bool(self.conf.osd_enable_op_tracker),
            daemon=self.entity)
        # daemon info block bookkeeping (perf dump `daemon`): boot
        # stamp + tick count, like the reference's `status`/uptime
        self._boot_time = self.clock.now()
        self._ticks = 0
        self.store_kind = store_kind
        # flight recorder: this daemon's op + pglog snapshot joins
        # every armed incident capture (CrashPoint / ledger failure)
        from ..utils import optracker
        optracker.recorder().register(self.entity, self._flight_dump)
        frd = str(getattr(self.conf, "flight_recorder_dir", "") or "")
        if frd:
            optracker.recorder().arm(
                frd, int(self.conf.flight_recorder_max))
        sock_dir = str(self.conf.admin_socket_dir)
        self.asok = AdminSocket(
            self.entity,
            path=f"{sock_dir}/{self.entity}.asok" if sock_dir else "")
        self.asok.register("perf dump", lambda c: self._perf_dump())
        self.asok.register("dump_ops_in_flight",
                           lambda c: self.op_tracker.dump_ops_in_flight())
        self.asok.register("dump_historic_ops",
                           lambda c: self.op_tracker.dump_historic_ops())
        self.asok.register(
            "dump_historic_slow_ops",
            lambda c: self.op_tracker.dump_historic_slow_ops())
        self.asok.register("tier status", lambda c: self._tier_status())
        self.asok.register("config show", lambda c: self.conf.dump())
        self.asok.register(
            "config set",
            lambda c: (self.conf.injectargs(
                f"--{c['key']} {c['value']}"), "ok")[1])
        self.asok.register("status", lambda c: {
            "whoami": self.whoami, "epoch": self.osdmap.epoch,
            "num_pgs": len(self.pgs)})
        # fault-injection surface: install/clear/dump FaultSet rules at
        # runtime through the admin socket, and via
        # `injectargs --faultset-rules '...' --faultset-seed N`
        from ..utils import faults
        faults.get().register_asok(self.asok)
        self._faults_observer = faults.conf_observer()
        self.conf.add_observer(self._faults_observer,
                               ("faultset_rules", "faultset_seed"))
        self._qos_observer = lambda conf, keys: self._qos_reconfigure()
        self.conf.add_observer(self._qos_observer,
                               ("osd_pool_qos_*", "osd_qos_recovery",
                                "osd_qos_cost_bytes_unit"))
        self._qos_reconfigure()
        if int(getattr(self.conf, "faultset_seed", 0)):
            faults.get().reseed(int(self.conf.faultset_seed))
        if str(getattr(self.conf, "faultset_rules", "") or ""):
            faults.get().install_from_spec(
                str(self.conf.faultset_rules), source="conf")
        # device-degrade health: erasure codecs that fell back to the
        # host matrix-codec path are reported to the mon (cluster log
        # once + a health flag on every pg-stats report)
        self._ec_degraded_logged: set[str] = set()

    # -- per-pool QoS ------------------------------------------------------

    def _qos_reconfigure(self, osdmap: OSDMap | None = None) -> None:
        """(Re)build the pool -> service-class map from conf + the
        current pool set.  Runs at startup, on every osdmap (pools
        appear/vanish at runtime) and on any osd_pool_qos_* conf
        change.  A bad spec is logged and skipped, never fatal."""
        osdmap = osdmap or self.osdmap
        from ..utils import dmclock
        from ..utils.config import QOS_OPT_PREFIX
        conf_specs: dict[str, "dmclock.QosSpec"] = {}
        for key, val in self.conf.dump().items():
            if not key.startswith(QOS_OPT_PREFIX) or \
                    key == "osd_pool_qos_default" or not val:
                continue
            try:
                conf_specs[key[len(QOS_OPT_PREFIX):]] = \
                    dmclock.parse_spec(val)
            except ValueError as e:
                self.log.warn("ignoring %s: %s", key, e)
        default = None
        dtext = str(getattr(self.conf, "osd_pool_qos_default", "") or "")
        if dtext:
            try:
                default = dmclock.parse_spec(dtext)
            except ValueError as e:
                self.log.warn("ignoring osd_pool_qos_default: %s", e)
        specs: dict[str, "dmclock.QosSpec"] = {}
        # once ANY pool class is configured, every other pool gets a
        # spec too (the conf default, or an implicit weight-1 class):
        # an unspecced pool left in the unconstrained FIFO class would
        # compete at arrival order and starve a reserved pool anyway —
        # the exact noisy-neighbor hole QoS exists to close.  Only
        # control-plane work (peering, recovery, gather replies) stays
        # unconstrained.
        implicit = default
        if implicit is None and conf_specs:
            implicit = dmclock.QosSpec(res=0.0, weight=1.0, lim=0.0)
        matched: set[str] = set()
        for pool in osdmap.pools.values():
            # conf key grammar normalizes '-' to '_' (injectargs and
            # conf files both do), so a pool named "load-hot" is
            # targeted by osd_pool_qos_load_hot — match both spellings
            spec = conf_specs.get(pool.name)
            key = pool.name
            if spec is None:
                key = pool.name.replace("-", "_")
                spec = conf_specs.get(key)
            if spec is not None:
                matched.add(key)
            else:
                spec = implicit
            if spec is not None:
                specs[pool.name] = spec
        if osdmap.pools:
            # a spec naming no pool is an operator's reservation
            # silently not applying — say so (once per key)
            warned = getattr(self, "_qos_warned_keys", set())
            for key in set(conf_specs) - matched - warned:
                self.log.warn("osd_pool_qos_%s matches no pool "
                              "(typo, or pool not created yet?)", key)
                warned.add(key)
            self._qos_warned_keys = warned
        # recovery/backfill pushes get their own throttleable class
        # (QoS-aware recovery): with osd_qos_recovery set, MPGPush
        # payloads are tagged into it (bytes-weighted) instead of
        # riding the unconstrained control plane — a backfill storm
        # becomes limit-throttleable.
        self._qos_recovery = None
        rtext = str(getattr(self.conf, "osd_qos_recovery", "") or "")
        if rtext:
            try:
                self._qos_recovery = dmclock.parse_spec(rtext)
                specs[RECOVERY_QOS_CLASS] = self._qos_recovery
            except ValueError as e:
                self.log.warn("ignoring osd_qos_recovery: %s", e)
        # the EC dispatch lanes honor the same classes, bytes-weighted
        # (the picker charges each pick by its head batch's staged
        # bytes): a tenant saturating encodes must not monopolize
        # device lanes either.  The @recovery class rides along, so a
        # rebuild's decode (tagged by recovery_svc) is throttleable
        # on the device plane exactly like its pushes on the op shards.
        from ..ops import pipeline as ec_pipeline
        ec_pipeline.configure_qos(
            dict(specs),
            cost_unit=int(self.conf.osd_qos_cost_bytes_unit))
        self._qos.configure(specs)
        self._qos_names = set(specs) - {RECOVERY_QOS_CLASS}

    def qos_tag_of(self, pool_id: int) -> str | None:
        """The QoS client tag for ops of `pool_id` (None = the
        unconstrained FIFO class)."""
        if not self._qos_names:
            return None
        pool = self.osdmap.pools.get(pool_id)
        if pool is not None and pool.name in self._qos_names:
            return pool.name
        return None

    def _daemon_info(self) -> dict:
        """perf dump `daemon` block: the identity/uptime facts every
        reference daemon serves via `status` — who this is, how long
        it has been up (clock seconds + heartbeat ticks), what store
        backs it, and which conf generation it runs."""
        return {"entity": self.entity,
                "role": "osd",
                "uptime": round(self.clock.now() - self._boot_time, 3),
                "ticks": self._ticks,
                "store_backend": self.store_kind,
                "conf_epoch": self.conf.generation,
                "osdmap_epoch": self.osdmap.epoch,
                "num_pgs": len(self.pgs),
                "op_tracker_enabled": self.op_tracker.enabled}

    def _flight_dump(self) -> dict:
        """One incident snapshot of this daemon: every in-flight op's
        span timeline, the historic + slow rings, and each pg's log
        summary (the in-process pglog_dump — bounds, missing set,
        backfill watermark, tail entries) so a wedged write can be
        walked from client ack to store state without rerunning."""
        from ..tools import pglog_dump
        pgs: dict[str, dict] = {}
        with self.pg_lock:
            snapshot = list(self.pgs.items())
        for pgid, pg in snapshot:
            try:
                pgs[str(pgid)] = pglog_dump.summarize(
                    {"pgid": str(pgid), "log": pg.pglog,
                     "last_backfill": pg.last_backfill,
                     "last_epoch_started": pg.last_epoch_started},
                    entries=True)
                pgs[str(pgid)]["acting"] = list(pg.acting)
                pgs[str(pgid)]["active"] = pg.active
            except Exception as e:      # a wedged pg still dumps peers
                pgs[str(pgid)] = {"error": f"{type(e).__name__}: {e}"}
        return {"daemon": self._daemon_info(),
                "crashed": int(bool(self.store.frozen)),
                "crash_site": self.store.crash_site,
                "ops_in_flight": self.op_tracker.dump_ops_in_flight(),
                "historic_ops": self.op_tracker.dump_historic_ops(),
                "historic_slow_ops":
                    self.op_tracker.dump_historic_slow_ops(),
                "pgs": pgs}

    def _perf_dump(self) -> dict:
        from ..ops import pipeline as ec_pipeline
        from ..utils import faults
        out = self.perf_collection.dump()
        out["daemon"] = self._daemon_info()
        # op tracing plane: in-flight/slow summary counts ride perf
        # dump so dashboards need not pull the full op dumps
        slow_n, slow_oldest = self.op_tracker.slow_ops_summary()
        out["ops_in_flight"] = self.op_tracker.num_inflight()
        out["slow_ops"] = {"count": slow_n,
                           "oldest_age": round(slow_oldest, 3)}
        out["ec_codecs"] = {name: dict(codec.stat_counters())
                            for name, codec in self._ec_codecs.items()}
        # crash-consistency plane: journal recovery counters (empty
        # for non-journaled backends) + this daemon's crash state
        out["journal"] = self.store.journal_stats()
        js = out["journal"]
        out["crash"] = {
            "crashed": int(bool(self.store.frozen)),
            "site": self.store.crash_site,
            "crash_rules": sum(1 for r in faults.get().rules()
                               if r.kind == "crash"),
            "sites": self.store.crash_sites(),
            "wal_torn_extent_repairs":
                js.get("wal_torn_extent_repairs", 0),
            "fsync_reorder_windows":
                js.get("fsync_reorder_windows", 0)}
        # zero-copy data-path audit: where payload bytes still
        # materialize on the host (utils/copyaudit.py sites), amortized
        # over this daemon's write ops.  Counters are process-wide (the
        # path spans client/msg/osd/store layers in one process), so
        # per-daemon writes only scale the denominator.
        from ..utils import copyaudit
        dp = copyaudit.snapshot()
        # process-wide copies over the PROCESS-WIDE write count
        # (copyaudit.note_write) — a multi-OSD process dividing by one
        # daemon's own op_w would over-report by the daemon count
        writes = max(1, dp["writes"])
        dp["host_copies_per_write"] = round(
            dp["host_copies"] / writes, 2)
        dp["host_copy_bytes_per_write"] = round(
            dp["ec_host_copy_bytes"] / writes, 1)
        # read-side floor: copies at the READ-classified sites
        # (copyaudit.READ_SITES) over the process-wide read count —
        # 0.0 on the intact/cache-served hot path, nonzero only when
        # degraded reads rebuild chunks or a consumer flattens
        reads = max(1, dp["reads"])
        dp["host_copies_per_read"] = round(
            dp["read_copies"] / reads, 2)
        dp["host_copy_bytes_per_read"] = round(
            dp["read_copy_bytes"] / reads, 1)
        out["data_path"] = dp
        # which codec walk serves this PROCESS (utils/denc.py): whole
        # passes by the compiled one and by the Python fallback, and
        # the values the compiled one handed back inside its own
        out["denc"] = denc.counters()
        # per-pool QoS: dmClock grants/misses/stalls for the op queue
        # (this daemon's shards) + the shared EC dispatch lanes
        out["qos"] = self._qos.stats()
        out["qos"]["pipeline"] = ec_pipeline.qos_stats()
        # serve-during-repair: the @recovery class's own grants and
        # limit stalls, surfaced directly (operators tune
        # osd_qos_recovery against exactly these numbers — "is my
        # repair throttle actually engaging?")
        rec = dict(out["qos"]["clients"].get(RECOVERY_QOS_CLASS)
                   or {"res_grants": 0, "prop_grants": 0,
                       "deadline_misses": 0, "throttle_stalls": 0})
        rec["configured"] = str(
            getattr(self.conf, "osd_qos_recovery", "") or "")
        out["qos"]["recovery"] = rec
        # serving-plane worker model: which messenger stack this daemon
        # runs (blocking: one loop thread; async: the shared event-loop
        # pool) and its per-worker socket/wakeup spread
        out["msgr_event"] = self.msgr.event_stats()
        # shared dispatcher counters + each codec's measured-routing
        # EMAs (amortized sec/byte per bucket, crossover estimate)
        out["ec_pipeline"] = ec_pipeline.stats()
        for name, codec in self._ec_codecs.items():
            backend = getattr(codec, "backend", None)
            if hasattr(backend, "perf_snapshot"):
                out["ec_codecs"][name]["routing"] = \
                    backend.perf_snapshot()
                xo = backend.crossover_estimate()
                if xo is not None:
                    out["ec_codecs"][name]["crossover_bytes"] = xo
        return out

    def _tier_status(self) -> dict:
        """`tier status`: the agent's queue and ops in flight, and for
        every tier PG this OSD is primary of its modes, its running
        counts and what it started."""
        with self.pg_lock:
            pgs = [(pgid, pg) for pgid, pg in self.pgs.items()
                   if pg.is_tier and pg.is_primary]
        return {"agent_queue": [str(p) for p in self.tier_agent.queue],
                "agent_ops": self.tier_agent.ops,
                "pgs": {str(pgid): pg.tier_status() for pgid, pg in pgs}}

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self.msgr.start()
        self.op_wq.start()
        self.recovery_wq.start()
        self.asok.start()
        if self.msgr.auth_mode == "cephx":
            # serve clients' service tickets (rotating secrets from
            # the mon) and dial peer OSDs with our own osd tickets
            self.monc.enable_service_auth(
                [self.msgr], own_service="osd",
                ticket_services=["osd"], clock=self.clock)
        self.monc.send_boot(self.whoami, self.msgr.addr)
        self.monc.sub_want_osdmap(0)
        self.monc.subscribe({"monmap": 0})   # learn membership changes
        self._schedule_heartbeat()

    def shutdown(self) -> None:
        if self._stopped:
            return                 # abort() may race a graceful stop
        self._stopped = True
        from ..utils import optracker
        optracker.recorder().unregister(self.entity)
        self.conf.remove_observer(self._faults_observer)
        self.conf.remove_observer(self._qos_observer)
        self.monc.shutdown()
        if self._hb_timer:
            self._hb_timer.cancel()
        self.asok.shutdown()
        self.tier_agent.stop()
        self.op_wq.stop()
        self.recovery_wq.stop()
        self.msgr.shutdown()
        try:
            self.store.umount()
        except CrashPoint:
            pass                   # frozen store: nothing to flush

    # -- crash plane -------------------------------------------------------

    def abort(self) -> None:
        """kill -9 analog: freeze the store FIRST (no in-flight op
        lands another byte, and the umount checkpoint is skipped —
        the disk stays exactly as the crash left it), drop this
        daemon's pgs from the HBM stripe cache (a restarted daemon
        starts cold; entries from a chip state we no longer track
        must never serve), then tear the threads down."""
        self.store.freeze()
        from ..ops import hbm_cache
        with self.pg_lock:
            cids = [pg.cid for pg in self.pgs.values()]
        hbm_cache.get().drop_cids(cids)
        self.shutdown()

    def _on_store_crash(self, site: str) -> None:
        """A FaultSet crash rule fired inside our store (which is
        already frozen): simulated power loss.  Abort from a separate
        thread — the crashing op thread is deep in the write path
        holding store/pg locks and must simply unwind via CrashPoint,
        never ack, never run the teardown itself."""
        if self._stopped:
            return
        self.log.warn("CRASH POINT %s fired: simulated power loss, "
                      "aborting", site)

        def _crash_abort() -> None:
            # flight recorder FIRST (while every daemon's in-flight
            # table still shows the moment of death), then tear down.
            # Disarmed recorder: one flag check, no I/O.
            from ..utils import optracker
            optracker.flight_record(
                f"crash-{self.entity}-{site}",
                extra={"daemon": self.entity, "site": site})
            self.abort()

        threading.Thread(target=_crash_abort, daemon=True,
                         name=f"{self.entity}-crash").start()

    # -- map handling ------------------------------------------------------

    def _on_osdmap(self, osdmap: OSDMap) -> None:
        # wrongly marked down (e.g. we stalled past the heartbeat
        # grace): the HEARTBEAT tick re-asserts boot (start_boot on
        # "map says i am down").  Deliberately NOT instant here: an
        # immediate re-boot makes an admin 'osd down' (map-level
        # failure injection) unobservable — the down state would last
        # only one paxos round; deferring to the clock-driven tick
        # keeps the window deterministic for tests and throttles the
        # boot storm when maps churn.
        # pg split (osd/OSD.cc:7553 split_pgs): a pool whose pg_num
        # grew needs every LOCAL parent pg to re-bucket its objects
        # into the new children before the children serve I/O — the
        # children start pg_temp-pinned to the parent's acting set, so
        # the split is purely local (no data moves over the network
        # until the pg_temp release backfills the CRUSH targets)
        grew: dict[int, int] = {}          # pool -> old pg_num
        residual: list[int] = []           # pools first seen this boot
        if not hasattr(self, "_pool_pg_nums"):
            self._pool_pg_nums = {}
        # pools appear/vanish with the map: refresh the QoS classes
        # (from the INCOMING map — self.osdmap publishes below)
        self._qos_reconfigure(osdmap)
        for pool_id, pool in osdmap.pools.items():
            seen = self._pool_pg_nums.get(pool_id)
            if seen is not None and pool.pg_num > seen:
                grew[pool_id] = seen
            elif seen is None:
                # restart may have crossed a pg_num commit: any local
                # pg of a first-seen pool gets a residual re-bucket
                # pass (a no-op scan when nothing is misplaced)
                residual.append(pool_id)
            self._pool_pg_nums[pool_id] = pool.pg_num
        with self.pg_lock:
            # publish the map INSIDE the lock: get_pg (also under
            # pg_lock) must never see the new map before the loop
            # below has marked fresh split children split_pending
            self.osdmap = osdmap
            for pgid in osdmap.all_pgs():
                up, acting = osdmap.pg_to_up_acting_osds(pgid)
                members = {o for o in list(up) + list(acting)
                           if o != ITEM_NONE}
                mine = self.whoami in members
                pg = self.pgs.get(pgid)
                if mine and pg is None:
                    pg = self.pgs[pgid] = PG(self, pgid)
                    if pgid.pool in grew:
                        from .osdmap import parent_seed
                        parent = PgId(pgid.pool, parent_seed(
                            pgid.seed, grew[pgid.pool]))
                        if parent != pgid and parent in self.pgs:
                            # a fresh child whose parent WE hold:
                            # hold client I/O + peering answers until
                            # the local split lands its objects (an
                            # up-only member with no parent data has
                            # nothing to wait for — it backfills)
                            pg.split_pending = True
                if pg is not None:
                    pg.update_acting(up, acting)
                    if pg.is_tier and pg.is_primary:
                        # new targets or ratios move the agent's modes
                        self.op_wq.queue(pgid, pg.tier_map_changed)
            # collected AFTER the creation loop: a restarted daemon
            # only instantiates (reloads) its pgs in the loop above
            split_parents = [
                pgid for pgid in self.pgs
                if pgid.pool in grew or pgid.pool in residual]
            if not hasattr(self, "_residual_pending"):
                self._residual_pending = {}
            for pool_id in residual:
                pool_pgs = [p for p in split_parents
                            if p.pool == pool_id]
                if not pool_pgs:
                    continue
                # a restart may have crossed a pg_num commit: until
                # every local re-bucket pass has run, ANY pg of the
                # pool may be missing objects that sit in a sibling's
                # collection — hold them all (brief EAGAIN/unknown)
                self._residual_pending[pool_id] = len(pool_pgs)
                for p in pool_pgs:
                    self.pgs[p].split_pending = True
            for pgid in split_parents:
                self.op_wq.queue(
                    pgid, self._split_pg, pgid,
                    grew.get(pgid.pool,
                             osdmap.pools[pgid.pool].pg_num))
            # snap trim: clones of newly-removed snaps get dropped
            # (ReplicatedPG snap_trimmer model, map-change driven)
            for pool_id, pool in osdmap.pools.items():
                removed = set(pool.removed_snaps)
                fresh = removed - self._removed_snaps_seen.get(
                    pool_id, set())
                if not fresh:
                    continue
                self._removed_snaps_seen[pool_id] = removed
                for pgid, pg in self.pgs.items():
                    if pgid.pool == pool_id:
                        self.op_wq.queue(pgid, pg.snap_trim, fresh)

    def get_pg(self, pgid: PgId) -> PG | None:
        with self.pg_lock:
            pg = self.pgs.get(pgid)
            if pg is None and pgid.pool in self.osdmap.pools:
                up, acting = self.osdmap.pg_to_up_acting_osds(pgid)
                # up-but-not-acting members instantiate too: a CRUSH
                # target of a pg_temp-pinned pg must exist to receive
                # its backfill before the pin is released
                members = {o for o in list(up) + list(acting)
                           if o != ITEM_NONE}
                if self.whoami in members:
                    pg = self.pgs[pgid] = PG(self, pgid)
                    pg.update_acting(up, acting)
            return pg

    def witnessed_pool_birth(self, pool_id: int) -> bool:
        """True when this daemon watched `pool_id` come to life (its
        creating incremental chained onto a map we already held).  A
        fresh pg copy of such a pool is the complete initial state; a
        fresh copy of any OTHER pool (boot catch-up, reboot that lost
        the store) may be a husk of data that lives elsewhere and
        must not claim completeness until backfilled."""
        return pool_id in self.monc.pool_births_witnessed

    def get_ec_codec(self, pool):
        """Codec per pool's EC profile (cached)."""
        from ..erasure.registry import registry
        name = pool.erasure_code_profile or "default"
        codec = self._ec_codecs.get(name)
        if codec is None:
            profile = dict(self.osdmap.ec_profiles.get(
                name, {"plugin": "tpu", "k": "2", "m": "1"}))
            codec = registry.factory(profile.pop("plugin", "tpu"), profile)
            self._ec_codecs[name] = codec
        return codec

    # -- messaging helpers -------------------------------------------------

    def send_osd(self, osd_id: int, msg: Message) -> None:
        addr = self.osdmap.get_addr(osd_id)
        if addr is None:
            return
        self.msgr.send_message(msg, f"osd.{osd_id}", tuple(addr))

    def send_osd_reply(self, conn, msg: Message,
                       req: Message | None = None) -> None:
        """`req`: the traced request this answers (a sub-op write or
        read, a push, a scrub scan).  Its trace id rides the reply, so
        that the receiver's `reply` op (`_reply_op`) joins the
        timeline of the op it completes a part of."""
        trace = getattr(req, "trace", "")
        if trace:
            msg.trace = trace
        self.msgr.send_message(msg, conn.peer_name, conn.peer_addr)

    def reply_to_client(self, conn, msg: Message) -> None:
        self.msgr.send_message(msg, conn.peer_name, conn.peer_addr)

    # -- generic peer RPC (blocking, used on worker threads only) ----------

    def _call(self, osd_id: int, msg: Message, timeout: float = 10.0):
        tids = self._send_calls({osd_id: msg})
        return self._wait_calls(
            tids, time.monotonic() + timeout).get(osd_id)

    def _send_calls(self, msgs: dict[int, Message]) -> dict[int, int]:
        """Ask many peers at once: every {osd: message} gets its tid
        and is sent before any is waited for; returns {osd: tid} for
        `_wait_calls`."""
        tids = {osd_id: next(self._rpc_tid) for osd_id in msgs}
        with self._rpc_cv:
            for osd_id, tid in tids.items():
                msgs[osd_id].rpc_tid = tid
                self._rpc[tid] = None
        for osd_id, msg in msgs.items():
            self.send_osd(osd_id, msg)
        return tids

    def _wait_calls(self, tids: dict[int, int],
                    deadline: float) -> dict[int, Message]:
        """One wait until every tid of `_send_calls` has its answer or
        `deadline` (time.monotonic()) has passed; {osd: reply} of those
        that answered.  The others' tids are forgotten: a later answer
        is dropped."""
        with self._rpc_cv:
            self._rpc_cv.wait_for(
                lambda: all(self._rpc.get(tid) is not None
                            for tid in tids.values()),
                max(0.0, deadline - time.monotonic()))
            got = {osd_id: self._rpc.pop(tid, None)
                   for osd_id, tid in tids.items()}
        return {osd_id: r for osd_id, r in got.items() if r is not None}

    # -- async peer RPC (never blocks a worker; timeouts on the clock) -----

    def _call_async(self, osd_id: int, msg: Message, done: Callable,
                    timeout: float = 5.0) -> None:
        """Send msg; done(reply_or_None) fires on reply or timeout.

        done runs on the messenger thread (reply) or a timer thread
        (timeout) — it must not take pg.lock; aggregate and queue any
        real work through op_wq.
        """
        if self.osdmap.get_addr(osd_id) is None:
            done(None)
            return
        tid = next(self._rpc_tid)
        msg.rpc_tid = tid
        with self._rpc_cv:
            self._rpc_async[tid] = done
        self.send_osd(osd_id, msg)
        self.clock.timer(timeout, lambda: self._rpc_async_timeout(tid))

    def _rpc_async_timeout(self, tid: int) -> None:
        with self._rpc_cv:
            done = self._rpc_async.pop(tid, None)
        if done is not None:
            done(None)

    def _rpc_reply(self, msg: Message) -> None:
        tid = getattr(msg, "rpc_tid", None)
        if tid is None:
            return
        with self._rpc_cv:
            done = self._rpc_async.pop(tid, None)
            if tid in self._rpc:
                self._rpc[tid] = msg
                self._rpc_cv.notify_all()
        if done is not None:
            done(msg)

    # -- dispatch ----------------------------------------------------------

    def ms_dispatch(self, conn, msg: Message) -> bool:
        if self._stopped:
            # crashed/aborting: a dead daemon answers nothing — not
            # even NACKs (power loss doesn't say goodbye)
            return True
        # Pure-RPC replies are completed inline (they only touch the
        # _rpc condvar, never pg.lock) so a worker blocked in _call can
        # always be woken.  Write-gather replies take pg.lock, so they
        # go through the sharded op queue like any other pg work —
        # handling them on the messenger event loop would let a worker
        # holding pg.lock across a blocking _call stall the whole
        # daemon's message processing (including the reply that worker
        # is waiting for).
        if isinstance(msg, (MOSDRepOpReply, MOSDECSubOpWriteReply)):
            pgid = PgId.parse(msg.pgid)
            trk = self._reply_op(msg)
            if trk is not None:
                trk.span_begin("queue", _t0=getattr(trk, "mstart", None))
            self.op_wq.queue(pgid, self._run_reply, trk,
                             self._handle_gather_reply, msg)
            return True
        if isinstance(msg, (MOSDECSubOpReadReply, MPGPushReply)) or (
                isinstance(msg, MPGInfo) and msg.op in (
                    "info", "scanned", "log", "scanned_range")):
            self._run_reply(self._reply_op(msg), self._rpc_reply, msg)
            return True
        if isinstance(msg, MOSDOpReply):
            # we are the CLIENT here: a cache-tier promote/flush op we
            # issued against another pool's primary came back
            self._rpc_reply(msg)
            return True
        if isinstance(msg, MOSDPing):
            self._handle_ping(conn, msg)
            return True
        if isinstance(msg, MWatchNotifyAck):
            pgid = PgId.parse(msg.pgid)
            self.op_wq.queue(pgid, self._handle_notify_ack, msg)
            return True
        if isinstance(msg, (MOSDOp, MOSDRepOp, MOSDECSubOpWrite,
                            MOSDECSubOpRead, MPGInfo, MPGPush, MOSDScrub)):
            self._note_peer_epoch(getattr(msg, "epoch", 0) or 0)
            if isinstance(msg, MOSDOp):
                # the trace id is minted from the client reqid (stable
                # across resends); sub-ops and recovery pushes carry
                # it over the wire so per-daemon dumps correlate
                # `attempt` is the client's send count for this op
                # (objecter resends): a top-level field of the doc,
                # never part of the description
                msg._trk = self.op_tracker.create(
                    f"osd_op({msg.src}:{msg.tid} {msg.oid} "
                    f"{[op[0] for op in msg.ops]})",
                    trace_id=f"{msg.src}:{msg.tid}",
                    attempt=getattr(msg, "attempt", None))
                self.perf.inc("op")
                from ..utils.bufferlist import BufferList
                self.perf.inc("op_in_bytes", sum(
                    len(op[-1]) for op in msg.ops
                    if op and isinstance(op[-1], (bytes, bytearray,
                                                  memoryview,
                                                  BufferList))))
            elif isinstance(msg, (MOSDRepOp, MOSDECSubOpWrite)):
                self.perf.inc("subop_w")
                msg._trk = self.op_tracker.create(
                    f"sub_op({msg.src} {msg.pgid} "
                    f"{msg.log.get('oid', '?')} "
                    f"ev={msg.log.get('ev')})",
                    trace_id=str(getattr(msg, "trace", "") or ""),
                    kind="subop")
            elif isinstance(msg, MOSDECSubOpRead):
                msg._trk = self.op_tracker.create(
                    f"sub_read({msg.src} {msg.pgid} {msg.oid} "
                    f"s{msg.shard})",
                    trace_id=str(getattr(msg, "trace", "") or ""),
                    kind="subop")
            elif isinstance(msg, MPGPush):
                msg._trk = self.op_tracker.create(
                    f"push({msg.src} {msg.pgid} {msg.oid} "
                    f"v={getattr(msg, 'version', None)})",
                    trace_id=str(getattr(msg, "trace", "") or ""),
                    kind="recovery")
            elif isinstance(msg, MPGInfo) and msg.op == "scan":
                # a peer's half of a PG scrub, under the primary's
                # scrub trace id (pg.scrub)
                msg._trk = self.op_tracker.create(
                    f"pg_scan({msg.src} {msg.pgid} "
                    f"deep={int(bool(msg.deep))})",
                    trace_id=str(getattr(msg, "trace", "") or ""),
                    kind="scrub_scan")
            pgid = PgId.parse(msg.pgid)
            # tenant traffic (client ops + the replica halves of its
            # writes) is scheduled under the pool's service class;
            # recovery pushes ride their own throttleable class when
            # osd_qos_recovery is set; everything else (peering, scrub
            # control) rides the unconstrained FIFO class.  Same-pg
            # ops of one class stay FIFO within their per-client
            # deque, so per-PG ordering is preserved.  Cost is
            # bytes-weighted (1 + payload/unit): a 4 MiB write
            # advances its pool's tags ~1000x further than a 4 KiB
            # stat, so configured rates meter bytes, not op counts.
            qos = None
            cost = 1.0
            unit = int(self.conf.osd_qos_cost_bytes_unit)
            if isinstance(msg, (MOSDOp, MOSDRepOp, MOSDECSubOpWrite)):
                qos = self.qos_tag_of(pgid.pool)
                if qos is not None and unit > 0:
                    cost = 1.0 + self._qos_payload_bytes(msg) / unit
            elif self._qos_recovery is not None and (
                    isinstance(msg, MPGPush)
                    or (isinstance(msg, MPGInfo) and msg.op in (
                        "push_delete", "backfill_progress",
                        "backfill_done", "rewind"))):
                # the recovery DATA PLANE and its ordering-sensitive
                # control markers ride ONE class: a backfill_progress
                # or backfill_done served from the unconstrained deque
                # while earlier pushes sit limit-throttled would
                # advance the peer's watermark (or completeness) ahead
                # of the objects it covers — per-class per-shard FIFO
                # keeps push -> marker order intact under throttling
                qos = RECOVERY_QOS_CLASS
                if unit > 0 and isinstance(msg, MPGPush):
                    data = getattr(msg, "data", b"") or b""
                    cost = 1.0 + len(data) / unit
            trk = getattr(msg, "_trk", None)
            if trk is not None:
                self._note_recv(trk, msg)
                # queue wait is anchored to the op's INITIATION (the
                # dispatch bookkeeping above is queue time too): the
                # span covers the op-shard deque AND any dmClock
                # throttle stall, tagged with the scheduling class
                trk.span_begin("queue", _t0=getattr(trk, "mstart",
                                                    None),
                               qos=qos, cost=round(cost, 2))
            self.op_wq.queue(pgid, self._handle_op, conn, msg,
                             qos=qos, qos_cost=cost)
            return True
        return False

    def _reply_op(self, msg):
        """The tracked op of a reply that completes part of a tracked
        op: one that carries the request's `trace` (`send_osd_reply`).
        Kind `reply`, a doc of its own under that trace id and NOT
        spans on the op that waits for it, whose `replica_wait`,
        `gather_wait` or `scrub.peer_wait` would lose to them the self
        time their metrics read.  An op like any other, as every
        queued message is an OpRequest in the reference: in flight,
        historic, slow if it waits past the complaint time.  None for a
        reply nobody traces."""
        trace = getattr(msg, "trace", "")
        if not trace:
            return None
        what = type(msg).__name__
        if isinstance(msg, MPGInfo):
            what = f"{what}.{msg.op}"
        shard = getattr(msg, "shard", None)
        trk = self.op_tracker.create(
            f"reply({what} {msg.src if shard is None else f's{shard}'}"
            f" <- {msg.src})", trace_id=str(trace), kind="reply")
        self._note_recv(trk, msg)
        return trk

    @staticmethod
    def _run_reply(trk, handler: Callable, msg) -> None:
        """A reply's handler under its op (`_reply_op`): `execute`
        from where its `queue` ended (a write's replies wait on the op
        shard) or from the op's start (the ones completed inline on
        the messenger thread).  The op is NOT published as the
        thread's current one: what the handler goes on to do (the
        answer to the client, a gather's continuation) belongs to the
        op that waited.  The doc finishes when the handler returns."""
        if trk is None:
            handler(msg)
            return
        t_dq = trk.span_end("queue")
        trk.span_begin("execute", _t0=t_dq if t_dq is not None
                       else getattr(trk, "mstart", None))
        try:
            handler(msg)
        finally:
            trk.finish()         # closes `execute`, with its cpu

    @staticmethod
    def _note_recv(trk, msg) -> None:
        """The messenger's part of a tracked op, from the stamps it
        left on the message (msg/messenger.py `stamp_received`): the
        way there from the sender's two stamps, `msgr.handoff` from
        the calling thread's hand-off to the sender's loop thread
        taking the message (the wake-up, the loop's backlog, the wait
        for the interpreter) and `msgr.wire` from there to the header
        read here (encode, the wait behind the `queued` frames ahead
        of it on the connection, sign, the socket write, the kernel,
        this loop getting to the read; `skew` where the two processes'
        clocks put a leg below 0 and it was clamped); then `msgr.recv`
        from header read to the last segment read and the signature
        checked (args: the frame's bytes and the socket reads that fed
        it), `msgr.dispatch` from there to the op's creation (decode,
        dispatcher walk).  Each begins where the one before ended and
        all end at or before `mstart`, so they lie inside no other
        span.  A loopback message was never on a wire and has no
        stamps."""
        r0 = getattr(msg, "_recv_stamp", None)
        mstart = getattr(trk, "mstart", None)
        if r0 is None or mstart is None:
            return
        sent = getattr(msg, "_sent_stamp", None)
        if sent is not None:
            handoff, taken, queued, skew = sent
            trk.add_span("msgr.handoff", handoff, taken)
            trk.add_span("msgr.wire", taken, r0, queued=queued,
                         **({"skew": 1} if skew else {}))
        r1 = msg._recv_complete_stamp
        trk.add_span("msgr.recv", r0, r1,
                     _cpu=max(0.0, msg._recv_complete_cpu - msg._recv_cpu),
                     bytes=msg._recv_bytes, reads=msg._recv_reads)
        trk.add_span("msgr.dispatch", r1, max(r1, mstart),
                     _cpu=max(0.0, time.thread_time()
                              - msg._recv_complete_cpu))

    @staticmethod
    def _qos_payload_bytes(msg) -> int:
        """Payload bytes of an op/sub-op vector for bytes-weighted
        QoS cost (the wire op tuples carry bytes-likes in any slot)."""
        from ..utils.bufferlist import BufferList
        total = 0
        for op in getattr(msg, "ops", ()) or ():
            for field in op:
                if isinstance(field, (bytes, bytearray, memoryview,
                                      BufferList)):
                    total += len(field)
        return total

    def _note_peer_epoch(self, epoch: int) -> None:
        """A peer/client spoke from a newer map than ours: request the
        missing range from the mon instead of waiting for a push that
        may have been stranded on the mon's lossy link
        (OSD::require_same_or_newer_map -> osdmap_subscribe,
        osd/OSD.cc).  One request per novel epoch."""
        if epoch > self.osdmap.epoch and epoch > self._map_requested_for:
            self._map_requested_for = epoch
            self.monc.sub_want_osdmap(self.osdmap.epoch + 1)

    def _handle_notify_ack(self, msg) -> None:
        pg = self.get_pg(PgId.parse(msg.pgid))
        if pg is not None:
            pg.handle_notify_ack(msg)

    def ms_handle_reset(self, conn) -> None:
        """A client link died: its watches die with it."""
        with self.pg_lock:
            pgs = list(self.pgs.values())
        for pg in pgs:
            pg.remove_watchers_of(conn.peer_name)   # cheap no-op when
                                                    # nothing registered

    def _handle_gather_reply(self, msg) -> None:
        pg = self.get_pg(PgId.parse(msg.pgid))
        if pg is None:
            return
        if isinstance(msg, MOSDRepOpReply):
            pg.handle_rep_reply(msg)
        else:
            pg.handle_ec_sub_write_reply(msg)

    def _handle_op(self, conn, msg, resume: Callable | None = None) -> None:
        """Op-shard entry: close the queue-wait span, publish the op
        as the thread's current trace target (deep layers — journal,
        EC staging — attach their spans through it), and run it under
        an `execute` span.  Sub-op / recovery-push trackers finish
        here (their reply is sent inline); client-op trackers finish
        at reply time in pg._reply, which may be a later gather.
        `resume` is the re-entry of an op that parked without a
        worker (an EC read waiting for its sub-reads): it runs in
        place of the dispatch, under an `execute` span of its own."""
        from ..utils import optracker
        run = resume or (lambda: self._execute_op(conn, msg))
        trk = getattr(msg, "_trk", None)
        if trk is None:
            run()
            return
        t_dq = trk.span_end("queue")
        trk.mark_event("dequeued")
        trk.span_begin("execute", _t0=t_dq)   # contiguous: no hole
        # a read that parks closes this span itself, before it hands
        # its gather over (pg._ec_read_park), and takes the token away:
        # the resumed op's `execute` may be open by the time this
        # thread is back here, and is not this thread's to close
        msg._exec_token = token = object()
        try:
            with optracker.op_context(trk):
                run()
        finally:
            if getattr(msg, "_exec_token", None) is token:
                trk.span_end("execute")     # no-op if already finished
            if not isinstance(msg, MOSDOp):
                trk.finish()            # sub-op/push: fully served

    def _execute_op(self, conn, msg) -> None:
        pgid = PgId.parse(msg.pgid)
        pg = self.get_pg(pgid)
        if pg is None:
            # NACK instead of dropping: a silent drop costs the caller
            # its full RPC timeout (peering serializes 5s stalls per PG
            # when a peer has not caught up to the pool-creating epoch)
            if isinstance(msg, MOSDOp):
                trk = getattr(msg, "_trk", None)
                if trk is not None:
                    trk.mark_event("no_pg")
                    trk.finish()
                self.reply_to_client(conn, MOSDOpReply(
                    tid=msg.tid, result=-11, outdata=[],
                    version=0, epoch=self.osdmap.epoch))
            elif isinstance(msg, MPGInfo) and msg.op == "query":
                # "unknown" (no pg instance yet — e.g. map lag) is NOT
                # the same as "empty pg": an empty info would count as
                # an authoritative (0,0) shard and could vote acked
                # writes into a rewind
                reply = MPGInfo(op="info", pgid=msg.pgid,
                                epoch=self.osdmap.epoch,
                                info={"last_update": (0, 0),
                                      "log_tail": (0, 0),
                                      "unknown": True})
                reply.rpc_tid = getattr(msg, "rpc_tid", None)
                self.send_osd_reply(conn, reply)
            elif isinstance(msg, MPGInfo) and msg.op in (
                    "scan_range", "get_log", "get_full_log"):
                # recovery RPCs to an OSD without the pg instance must
                # NACK with the unknown marker, not vanish: a silent
                # drop stalls the caller's backfill/catch-up for its
                # full RPC timeout with nothing scheduled to retry
                reply = MPGInfo(
                    op=("scanned_range" if msg.op == "scan_range"
                        else "log"),
                    pgid=msg.pgid, epoch=self.osdmap.epoch,
                    info={"unknown": True})
                reply.rpc_tid = getattr(msg, "rpc_tid", None)
                self.send_osd_reply(conn, reply)
            elif isinstance(msg, MPGInfo) and msg.op == "ec_omap":
                # no pg instance (map lag/restart): flag it — a bare
                # empty omap would read as authoritative absence
                reply = MPGInfo(op="info", pgid=msg.pgid,
                                epoch=self.osdmap.epoch,
                                info={"omap": {}, "unknown": True})
                reply.rpc_tid = getattr(msg, "rpc_tid", None)
                self.send_osd_reply(conn, reply)
            elif isinstance(msg, MPGInfo) and msg.op == "shard_scan":
                reply = MPGInfo(op="info", pgid=msg.pgid,
                                epoch=self.osdmap.epoch,
                                info={"objects": {}, "unknown": True})
                reply.rpc_tid = getattr(msg, "rpc_tid", None)
                self.send_osd_reply(conn, reply)
            elif isinstance(msg, MOSDECSubOpRead):
                reply = MOSDECSubOpReadReply(
                    reqid=msg.reqid, pgid=msg.pgid, shard=msg.shard,
                    result=-2, data=b"", hinfo=None)
                reply.rpc_tid = getattr(msg, "rpc_tid", None)
                self.send_osd_reply(conn, reply, msg)
            return
        if isinstance(msg, MOSDOp):
            if getattr(msg, "_trk", None) is not None:
                msg._trk.mark_event("reached_pg")
            pg.do_op(conn, msg)
        elif isinstance(msg, MOSDRepOp):
            pg.handle_rep_op(conn, msg)
        elif isinstance(msg, MOSDECSubOpWrite):
            pg.handle_ec_sub_write(conn, msg)
        elif isinstance(msg, MOSDECSubOpRead):
            pg.handle_ec_sub_read(conn, msg)
        elif isinstance(msg, MPGInfo):
            self._handle_pg_info(conn, msg, pg)
        elif isinstance(msg, MPGPush):
            self._handle_push(conn, msg, pg)
        elif isinstance(msg, MOSDScrub):
            result = pg.scrub(deep=msg.deep,
                              repair=getattr(msg, "repair", False))
            self.log.info("scrub %s: %s", pgid, result)

    # -- heartbeats + failure detection ------------------------------------

    def _schedule_heartbeat(self) -> None:
        if self._stopped:
            return
        self._hb_timer = self.clock.timer(
            float(self.conf.osd_heartbeat_interval), self._heartbeat)

    def _heartbeat(self) -> None:
        now = self.clock.now()
        grace = float(self.conf.osd_heartbeat_grace)
        self._ticks += 1
        self.op_tracker.check_slow_ops()
        self._report_to_mgr()
        self._report_pg_stats()
        self._sched_scrub(now)
        if not self.osdmap.is_up(self.whoami):
            # boot can be dropped during a mon no-leader window
            # (peons only relay when they know the leader); keep
            # re-asserting until the map shows us up, like the
            # reference's start_boot retry loop
            self.monc.send_boot(self.whoami, self.msgr.addr)
        # re-arm stalled write gathers (lost sub-op / lost reply /
        # shard holder gone): the resend is idempotent replica-side
        with self.pg_lock:
            stalled = [(pgid, pg) for pgid, pg in self.pgs.items()
                       if pg._inflight]
        for pgid, pg in stalled:
            self.op_wq.queue(pgid, pg.check_inflight)
        # an incomplete copy must ASK to be made whole: after a fast
        # bounce the mon may never have seen us down, so no acting
        # set changes and nothing else ever re-peers.  A replica
        # nudges its primary; a primary whose own copy is incomplete
        # (and whose self-backfill isn't in flight — it may have died
        # on a transient RPC timeout during the post-boot churn)
        # re-queues its own round, which re-queues the self-backfill.
        # A non-empty `missing` set counts as incomplete the same way:
        # the activation round queued its pulls ONCE, and a lost push
        # (or a holder that could not serve the version yet) would
        # otherwise strand the claim forever — a data-incomplete copy
        # sitting quiet, which is exactly the durable form of the
        # historical "deg: ACKED write lost" flake.  Re-peering
        # re-runs _queue_missing_pulls (primary) / the delta push
        # (replica), both version-gated and idempotent.
        with self.pg_lock:
            incomplete = [(pgid, pg) for pgid, pg in self.pgs.items()
                          if (not pg.backfill_complete
                              or pg.pglog.missing)
                          and not getattr(pg, "split_pending", False)]
        # throttled in REAL time, not the (possibly fast-forwarded)
        # virtual clock: a nudge per virtual heartbeat under a 10x
        # time-compressed test floods peering rounds faster than
        # their own info RPCs can answer — a self-inflicted storm
        # that keeps the pg from ever converging
        now_mono = time.monotonic()
        for pgid, pg in incomplete:
            if now_mono - self._nudge_last.get(pgid, 0.0) < 2.0:
                continue
            live = pg.acting_live()
            if not live:
                continue
            self._nudge_last[pgid] = now_mono
            if live[0] == self.whoami:
                with self.backfill_lock:
                    busy = (pgid, "self") in self._backfills_active
                if not busy:
                    self.queue_peering(pgid)
            elif not pg.is_primary:
                self.send_osd(live[0], MPGInfo(
                    op="request_peering", pgid=str(pgid),
                    epoch=self.osdmap.epoch))
        # pg_temp reconcile: a temp-pinned pg (post-split child) whose
        # primary we are gets its CRUSH targets backfilled, then the
        # pin is released so placement converges to CRUSH
        with self.pg_lock:
            pinned = [(pgid, pg) for pgid, pg in self.pgs.items()
                      if pgid in self.osdmap.pg_temp and pg.is_primary
                      and pg.active
                      and not getattr(pg, "split_pending", False)]
        for pgid, pg in pinned:
            self.op_wq.queue(pgid, self._pg_temp_reconcile, pgid)
        for osd_id, info in list(self.osdmap.osds.items()):
            if osd_id == self.whoami:
                continue
            if not info.up:
                # stop tracking while down: a stale timestamp would
                # trigger an instant false failure report on re-boot
                self._hb_last.pop(osd_id, None)
                continue
            self.send_osd(osd_id, MOSDPing(op="ping", stamp=now,
                                           epoch=self.osdmap.epoch,
                                           pgid="0.0"))
            # seed on first ping so a peer that NEVER answers still
            # exceeds grace eventually (map says up, socket says no)
            last = self._hb_last.setdefault(osd_id, now)
            if now - last > grace:
                self.log.warn("osd.%d silent for %.0fs, reporting",
                              osd_id, now - last)
                self.monc.report_failure(osd_id, now - last)
        self._schedule_heartbeat()

    def _ec_degraded_profiles(self) -> list[str]:
        return sorted(name for name, codec in self._ec_codecs.items()
                      if getattr(codec, "degraded", False))

    def _report_ec_degrade(self) -> None:
        """Cluster-log newly device-degraded EC codecs (once each)."""
        for name in self._ec_degraded_profiles():
            if name in self._ec_degraded_logged:
                continue
            self._ec_degraded_logged.add(name)
            codec = self._ec_codecs.get(name)
            reason = getattr(codec, "degrade_reason", "")
            self.log.warn("EC profile %s degraded to matrix-codec "
                          "fallback (%s)", name, reason)
            self.monc.cluster_log(
                "WRN", f"osd.{self.whoami} EC device error "
                       f"({reason}); profile {name} degraded to "
                       f"matrix-codec fallback")

    def _report_pg_stats(self) -> None:
        """Primary PGs report state to the mon's PGMap aggregation
        (MPGStats; the feed behind `ceph -s` health)."""
        self._report_ec_degrade()
        stats: dict[str, dict] = {}
        with self.pg_lock:
            pgs = list(self.pgs.items())
        for pgid, pg in pgs:
            # NON-blocking: this runs in the shared timer thread — a
            # scrub holding pg.lock across replica RPCs must not
            # freeze the virtual clock (and with it every grace
            # window); a busy PG just reports on the next tick
            if not pg.lock.acquire(blocking=False):
                continue
            try:
                if not pg.is_primary:
                    continue
                pool = pg.pool
                if pool is None:
                    continue
                live = len(pg.acting_live())
                want = max(pool.size, len(pg.acting))
                states = ["active"] if pg.active else ["peering"]
                if live < want:
                    states += ["undersized", "degraded"]
                elif pg.active:
                    # a full acting set is not clean while a member is
                    # being backfilled or shards are being rebuilt
                    states.append(self.pg_repairing(pgid) or "clean")
                stats[str(pgid)] = {
                    "state": "+".join(states),
                    "objects": len(pg.pglog.objects),
                    "live": live,
                    "acting": list(pg.acting)}
            finally:
                pg.lock.release()
        degraded = self._ec_degraded_profiles()
        flags = {}
        if degraded:
            flags["ec_device_degraded"] = degraded
        # slow-op health (osd_op_complaint_time): level-triggered —
        # the flag rides every report while ops sit blocked past the
        # threshold and clears by itself once they complete (leased
        # flag semantics, so a dead daemon's warning also ages out)
        slow_n, slow_oldest = self.op_tracker.slow_ops_summary()
        if slow_n:
            flags["slow_ops"] = {"count": slow_n,
                                 "oldest": round(slow_oldest, 1)}
        # store-level trouble (e.g. repeated journal checkpoint
        # failures): surfaced the same leased-flag way
        store_warn = self.store.health_warning()
        if store_warn:
            flags["store_health"] = store_warn
        # partial-fleet degrade: quarantined pipeline lanes redrain to
        # the surviving chips — worth a HEALTH_WARN (reduced EC
        # bandwidth + a chip to replace), distinct from the full
        # matrix-codec fallback above
        from ..ops import pipeline as ec_pipeline
        pstats = ec_pipeline.stats()
        quarantined = sum(1 for d in pstats.get("devices", {}).values()
                          if d["quarantined"])
        if quarantined:
            flags["ec_device_quarantined"] = \
                f"{quarantined}/{len(pstats['devices'])}"
        flags = flags or None
        if stats or flags:
            self.monc.send_pg_stats(self.whoami, stats,
                                    self.osdmap.epoch, flags=flags)

    def _report_to_mgr(self) -> None:
        """Push perf counters to the active mgr (MgrClient model;
        the heartbeat tick doubles as the report timer)."""
        addr = getattr(self.osdmap, "mgr_addr", None)
        if addr is None:
            return
        from ..mon.messages import MMgrReport
        self.msgr.send_message(
            MMgrReport(entity=self.entity, counters=self._perf_dump(),
                       epoch=self.osdmap.epoch),
            f"mgr.{self.osdmap.mgr_name}", tuple(addr))

    def _handle_ping(self, conn, msg) -> None:
        if msg.op == "ping":
            self.send_osd_reply(conn, MOSDPing(
                op="reply", stamp=msg.stamp, epoch=self.osdmap.epoch,
                pgid="0.0"))
        else:
            peer = int(msg.src.split(".")[1])
            self._hb_last[peer] = self.clock.now()

    # -- peering / recovery service ----------------------------------------

    def queue_peering(self, pgid: PgId) -> None:
        self.op_wq.queue(pgid, self._run_peering, pgid)

    def _run_peering(self, pgid: PgId) -> None:
        pg = self.get_pg(pgid)
        if pg:
            pg.start_peering()

    def pg_collect_info(self, pgid: PgId, peers: list[int],
                        done: Callable) -> None:
        """Query all peers CONCURRENTLY; done(infos) is queued through
        op_wq once every peer replied or timed out.  Blocking a worker
        per-peer here deadlocks: two OSDs peering different PGs that
        hash to each other's busy shard each wait out the full RPC
        timeout (the reference's peering is fully event-driven for the
        same reason, osd/PG.h RecoveryMachine)."""
        if not peers:
            self.op_wq.queue(pgid, done, {})
            return
        infos: dict[int, dict] = {}
        remaining = set(peers)
        lock = threading.Lock()

        def make_cb(osd_id: int) -> Callable:
            def cb(reply) -> None:
                with lock:
                    if reply is not None:
                        infos[osd_id] = reply.info
                    else:
                        # an unreachable LIVE peer (RPC timeout, or a
                        # rebooted daemon whose connection bounced)
                        # must not silently vanish from the round: the
                        # pg would activate without recovering it, and
                        # with the acting set unchanged nothing would
                        # ever re-peer.  Report it "unknown" so
                        # _peering_done's bounded re-peer/backfill
                        # machinery owns the retry.
                        infos[osd_id] = {"unknown": True,
                                         "unreachable": True}
                    remaining.discard(osd_id)
                    fire = not remaining
                if fire:
                    self.op_wq.queue(pgid, done, dict(infos))
            return cb

        for osd_id in peers:
            self._call_async(
                osd_id, MPGInfo(op="query", pgid=str(pgid),
                                epoch=self.osdmap.epoch),
                make_cb(osd_id), timeout=5.0)

    def _handle_pg_info(self, conn, msg, pg: PG) -> None:
        if msg.op == "query":
            reply = MPGInfo(op="info", pgid=msg.pgid, epoch=self.osdmap.epoch,
                            info=pg.get_info())
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "scan":
            # under this message's `scrub_scan` op (ms_dispatch): the
            # scan's spans land on it through optracker.current()
            reply = MPGInfo(op="scanned", pgid=msg.pgid,
                            epoch=self.osdmap.epoch,
                            info=self._scan_pg(pg, msg.deep))
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply, msg)
        elif msg.op == "ec_omap":
            try:
                omap = self.store.omap_get(pg.cid, shard_oid(msg.oid, 0))
            except StoreError:
                omap = {}
            reply = MPGInfo(op="info", pgid=msg.pgid,
                            epoch=self.osdmap.epoch,
                            info={"omap": omap})
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "shard_scan":
            # role audit: which objects do WE hold for shard `shard`,
            # and at what version — name-suffix scan, O(collection)
            shard = int(msg.shard)
            try:
                names = self.store.collection_list(pg.cid)
            except StoreError:
                names = []
            held: dict[str, tuple | None] = {}
            from .pglog import VER_KEY as _VK, _parse_ev as _pev
            for n in names:
                if "@" in n or n.startswith("_pgmeta") or ".s" not in n:
                    continue
                base, _, num = n.rpartition(".s")
                if num != str(shard):
                    continue
                try:
                    held[base] = _pev(self.store.getattr(pg.cid, n,
                                                         _VK))
                except StoreError:
                    continue
            reply = MPGInfo(op="info", pgid=msg.pgid,
                            epoch=self.osdmap.epoch,
                            info={"objects": held,
                                  "backfilling":
                                      not pg.backfill_complete})
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "fetch_obj":
            # synchronous whole-object fetch (scrub repair pulls the
            # authoritative copy through this)
            try:
                info = {"data": self.store.read(pg.cid, msg.oid),
                        "xattrs": self.store.getattrs(pg.cid, msg.oid),
                        "omap": self.store.omap_get(pg.cid, msg.oid),
                        "version": pg.pglog.objects.get(msg.oid,
                                                        (0, 0))}
            except StoreError:
                info = {"missing": True}
            reply = MPGInfo(op="info", pgid=msg.pgid,
                            epoch=self.osdmap.epoch, info=info)
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "pull":
            requester = sender_id(msg)
            if requester is None:
                return
            version = pg.pglog.objects.get(msg.oid, (0, 0))
            # front=1: a client op is recovery-blocked on this object
            # at the requester — the push jumps our recovery queue
            self.pg_push_object(pg.pgid, requester, msg.oid, version,
                                shard=None,
                                front=bool(getattr(msg, "front", 0)))
        elif msg.op == "get_log":
            # peering GetLog: entries since the caller's head, or
            # too_old when its head predates our tail (-> backfill).
            # contains_since tells the caller whether its head names
            # a point in OUR history at all — False means the caller
            # sits on a divergent branch and must rewind, not merely
            # merge (the authority proof's divergence detector).
            with pg.lock:
                since = tuple(msg.since)
                delta = pg.pglog.entries_since(since)
                info = ({"too_old": True} if delta is None
                        else {"entries": delta,
                              "last_update": pg.pglog.head,
                              "contains_since":
                                  pg.pglog.contains(since)})
            reply = MPGInfo(op="log", pgid=msg.pgid,
                            epoch=self.osdmap.epoch, info=info)
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "get_full_log":
            # self-backfill completion: the restored primary adopts
            # our entire retained log window
            with pg.lock:
                info = {"entries": list(pg.pglog.entries),
                        "tail": pg.pglog.tail}
            reply = MPGInfo(op="log", pgid=msg.pgid,
                            epoch=self.osdmap.epoch, info=info)
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "scan_range":
            # backfill scan: our object->version view of a name range
            # (BackfillInterval analog) — O(range), never the whole pg
            info = pg.scan_range(
                after=getattr(msg, "after", "") or "",
                upto=getattr(msg, "upto", "") or "",
                limit=int(getattr(msg, "limit", 0) or 0))
            reply = MPGInfo(op="scanned_range", pgid=msg.pgid,
                            epoch=self.osdmap.epoch, info=info)
            reply.rpc_tid = getattr(msg, "rpc_tid", None)
            self.send_osd_reply(conn, reply)
        elif msg.op == "push_delete":
            pg.handle_push_delete(msg.oid, tuple(msg.version))
        elif msg.op == "backfill_start":
            pg.handle_backfill_start()
        elif msg.op == "backfill_progress":
            pg.handle_backfill_progress(str(msg.watermark))
        elif msg.op == "activate":
            pg.handle_activate(int(msg.les))
        elif msg.op == "backfill_done":
            pg.handle_backfill_done(msg.entries, tuple(msg.tail))
        elif msg.op == "rewind":
            pg.rewind_to(tuple(msg.rewind_to))
        elif msg.op == "request_peering":
            # an incomplete replica is asking to be made whole (fast
            # bounce: no interval change, so nothing else would ever
            # re-peer it).  queue_backfill dedups per (pg, target),
            # so repeated nudges while the backfill runs are cheap.
            if pg.is_primary:
                self.queue_peering(pg.pgid)
        elif msg.op == "rebuild_me":
            # an EC shard noticed it skipped a superseded sub-op and
            # may hold stale bytes: reconstruct its shard from the
            # surviving k and push it back (primary side)
            requester = sender_id(msg)
            if requester is None:
                return
            shard = int(msg.shard)
            with pg.lock:
                version = pg.pglog.objects.get(msg.oid)
            if version is not None and pg.is_primary:
                self.queue_ec_rebuild(pg.pgid, msg.oid, version,
                                      [(shard, requester)])
