"""Cross-op EC device pipeline: coalesce stripe work, amortize dispatch,
spread mega-batches across every visible chip.

A serial op path pays one host->device->host round trip per EC write,
scrub batch and rebuild, for a stripe batch worth far less device
time than the trip.  A storage daemon has exactly the concurrency that
amortizes a fixed dispatch cost — many in-flight writes, scrub chunks
and recovery rebuilds are embarrassingly parallel stripes (SURVEY
§5.7) — and the serial path threw it away.

This module is the shared dispatcher all producers feed:

  * **channels** — a :class:`PipelineChannel` is one coalescable work
    class (same jitted kernel set): whole-object/append encodes of one
    (matrix, L), deep-scrub CRC folds of one shard size, rebuild
    decodes of one rows-matrix.  Items on one channel concatenate
    along the batch axis into a mega-batch.
  * **shape buckets** — mega-batches pad to a power-of-two stripe
    count (:func:`pad_batch`), so the device sees a small repeating
    shape set and jit recompiles stop after warm-up.
  * **device lanes** — a :class:`DeviceSet` enumerates every visible
    jax device at first use (``osd_ec_device_shards`` caps it); each
    device gets a dispatch lane with its OWN overlap window of
    ``depth`` in-flight dispatches and its own collector thread.
    Placement is least-loaded with a round-robin tie-break, so the
    aggregate window is ``depth * n_devices`` and one hot channel
    cannot serialize every producer behind one chip.
  * **mega-batch splitting** — a large coalesced batch additionally
    splits across idle lanes (``split_min`` stripes per shard, ceil
    partition): each shard pads to its own bucket, pins to its lane
    with ``jax.device_put``, and the parts re-assemble in submit
    order — bit-identical to the unsplit dispatch.
  * **futures** — :meth:`EcDevicePipeline.submit` returns a
    ``concurrent.futures.Future`` resolving to ``(path, outputs)``,
    so an OSD op submits its encode, keeps journaling metadata, and
    collects parity+CRCs at commit time.
  * **quarantine + redrain** — a device error on ONE chip (a real
    dispatch/fetch failure, or an injected ``tpu_error`` targeted at
    that device index) quarantines that lane only: the failed batch
    and everything queued redrains onto the surviving chips,
    bit-identically.  Only when EVERY lane is quarantined does the
    channel owner hear ``on_error`` (the tpu plugin degrades to the
    host matrix codec) and the queue drain to the host fn: no queued
    op is ever lost or corrupted, and one dead chip costs 1/n of the
    fleet, not all of it.
  * **scrub QoS** — under contention the deep-scrub CRC channels
    yield to client-write encode/decode channels:
    ``osd_ec_pipeline_scrub_weight`` bounds scrub's share of
    contended dispatch slots (weight w -> one pick in round(1/w)).

  * **zero-copy transfer plane** — each lane owns a STAGER thread and
    a double-buffered staging queue: the dispatcher hands a planned
    part to the lane and moves on immediately; the stager performs the
    H2D upload and issues the async compute, so batch N+1 uploads
    while batch N computes and uploads to different chips run in
    parallel instead of serializing on the dispatcher thread (the old
    per-dispatch synchronous ``device_put``).  Readback is
    parity-only: the fused kernel never echoes data shards, so per
    dispatch exactly ``S_pad * k * L`` bytes go up and
    ``S_pad * (m * L + 4 * (k + m))`` bytes come down — the
    ``bytes_h2d`` / ``bytes_d2h`` counters prove it
    (tests/test_hbm_cache.py holds them to the exact identity).
  * **HBM stripe cache** — an encode submission tagged with a
    :class:`~ceph_tpu.ops.hbm_cache.CacheIntent` leaves its uploaded
    data and computed parity ON the chip (device slices, no extra
    transfer): deep-scrub CRC folds and recovery decodes of that
    object then hit HBM with zero H2D (ceph_tpu.ops.hbm_cache).  A
    quarantined lane's entries drop with it.
  * **cost-aware placement** — each lane keeps per-shape-bucket EMAs
    of its marginal service time (the same samples
    ``TpuBackend.record`` scores, fed at fetch completion); when a
    measured slow chip would win the least-loaded tie, placement
    routes around it and ``cost_diverged`` counts how often the
    measured choice disagreed with least-loaded.

Host batches run inline on the dispatcher thread — single-threaded
host execution is itself the coalescing backpressure: while one host
batch runs, new submissions queue and the next dispatch swallows them
all in one call.

Timing recorded per dispatch is the *marginal* service time per LANE
(now minus the later of dispatch-issue and that lane's previous
fetch-completion), so an overlapped device dispatch records its
amortized per-chip cost, not the full round-trip latency — that is what
makes the TpuBackend's measured host/device routing produce a finite
crossover, and it stays meaningful when n chips serve in parallel.
"""

from __future__ import annotations

import atexit
import inspect
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from ..utils import faults
from . import hbm_cache

# defaults; daemons override via configure() from their conf
# (osd_ec_pipeline_depth / _coalesce_ms / _max_batch /
#  osd_ec_device_shards / osd_ec_pipeline_scrub_weight /
#  osd_ec_cost_aware_placement / osd_ec_hbm_cache_bytes /
#  osd_qos_cost_bytes_unit)
DEFAULT_DEPTH = 2
DEFAULT_COALESCE_WAIT = 0.002
DEFAULT_MAX_BATCH = 256
DEFAULT_SPLIT_MIN = 4       # min stripes per per-chip shard of a split
DEFAULT_SCRUB_WEIGHT = 0.25
DEFAULT_COST_AWARE = True
# the most bytes of one batch whose item slices _warm_item_buckets
# compiles ahead of serving
LANE_STAGE_BYTES = 256 << 20
# dmClock cost normalization for the dispatch-lane tenant picker
# (mirrors the op queue's osd_qos_cost_bytes_unit; 0 = cost 1/pick)
DEFAULT_QOS_COST_UNIT = 4096
# a measured-cost pick must beat the least-loaded pick by this factor
# to override it: EMA noise alone must not starve a healthy lane of
# the rotation (unprobed lanes have no EMA and always keep their turn)
COST_MARGIN = 1.25

_UNSET = object()


def _log():
    from ..utils.dout import DoutLogger
    return DoutLogger("ops", "ec-pipeline")


# -- background warm-ups ------------------------------------------------------
#
# Every device fn (the codecs' jitted kernels, the scrub CRC folds)
# compiles on a background thread while the host serves.  The threads
# and their failures are tracked HERE, process-wide, so stats() can
# say how many compiles are in flight / failed and a stopping process
# can wait them out.

_warm_lock = threading.Lock()
_warm_threads: list = []
_warm = {"warm_failures": 0, "last_warm_error": ""}


def start_warm_thread(target, name: str) -> None:
    t = threading.Thread(target=target, daemon=True, name=name)
    with _warm_lock:
        _warm_threads[:] = [w for w in _warm_threads if w.is_alive()]
        _warm_threads.append(t)
    t.start()


def note_warm_failure(what: str, e: Exception) -> None:
    """A warm-up failed: the shape is negative-cached by its owner and
    stays on the host path — say so, once, and count it."""
    msg = f"{what}: {type(e).__name__}: {e}"
    with _warm_lock:
        _warm["warm_failures"] += 1
        _warm["last_warm_error"] = msg
    _log().warn("device warm-up failed for %s (staying on host path)",
                msg)


def warm_stats() -> dict:
    with _warm_lock:
        out = dict(_warm)
        out["warmups_inflight"] = sum(
            1 for t in _warm_threads if t.is_alive())
    return out


def wait_warmups(timeout: float) -> bool:
    """Join every warm thread still compiling; False on timeout."""
    end = time.monotonic() + timeout
    with _warm_lock:
        threads = list(_warm_threads)
    for t in threads:
        t.join(max(0.0, end - time.monotonic()))
    return not any(t.is_alive() for t in threads)


# a warm thread still inside a device compile at interpreter teardown
# aborts the process (EcDevicePipeline.stop waits for the same reason);
# a process that never stops its pipeline waits here
atexit.register(wait_warmups, 60.0)

# liveness bounds: a device fetch that HANGS (no exception) must not
# become a process-wide EC outage.  A lane whose collector sits inside
# one fetch longer than STALL_TIMEOUT is skipped by placement; when
# every usable lane's window has been full for STALL_TIMEOUT the
# dispatcher latches host-only dispatch; producers self-serve on host
# after RESULT_TIMEOUT blocked in result() (encode/CRC are pure
# functions of inputs they still hold, and the future's done() guard
# makes a late device resolution harmless).
STALL_TIMEOUT = 60.0
RESULT_TIMEOUT = 120.0


def next_bucket(n: int) -> int:
    """Power-of-two shape bucket for a batch of n stripes (what the
    cache keeps of an item is cut at the item's own bucket)."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


def pad_batch(batch: np.ndarray) -> np.ndarray:
    """Zero-pad axis 0 to the next power of two so device shapes
    repeat (jit is shape-specialized; a stable bucket set compiles
    once per size).  Callers slice the result back to the true count;
    host paths never pay the padding."""
    S = batch.shape[0]
    S_pad = next_bucket(S)
    if S_pad == S:
        return batch
    return np.concatenate(
        [batch, np.zeros((S_pad - S,) + batch.shape[1:], dtype=np.uint8)])


def _wrap_device_fn(device_fn):
    """Channels predate device placement; accept both fn(padded) and
    fn(padded, device).  Wrapping once at construction keeps the
    dispatch path free of per-call signature probing."""
    if device_fn is None:
        return None
    try:
        params = list(inspect.signature(device_fn).parameters.values())
    except (TypeError, ValueError):
        return device_fn
    if len(params) >= 2 or any(
            p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD) for p in params):
        return device_fn

    def wrapped(padded, device=None, _fn=device_fn):
        return _fn(padded)

    return wrapped


def _wrap_record(record):
    """Like :func:`_wrap_device_fn` for the record callback: newer
    owners take a ``device=`` kwarg (per-(shape, chip) routing EMAs in
    TpuBackend.record); legacy four-argument callbacks are wrapped so
    the dispatch path stays free of per-call signature probing."""
    if record is None:
        return lambda path, nbytes, secs, depth=1, device=None: None
    try:
        params = inspect.signature(record).parameters
    except (TypeError, ValueError):
        return record
    if "device" in params or any(
            p.kind == p.VAR_KEYWORD for p in params.values()):
        return record

    def wrapped(path, nbytes, secs, depth=1, device=None, _fn=record):
        return _fn(path, nbytes, secs, depth)

    return wrapped


class PipelineChannel:
    """One coalescable work class.

    host_fn(batch) -> tuple of np arrays, each with leading dim ==
    batch.shape[0].  device_fn(padded_batch, device) -> same tuple of
    (lazy) device arrays, or None when the jitted fn is not warm yet
    on that device (the batch then runs on host while a background
    compile proceeds); legacy single-argument device_fns are wrapped.
    route(nbytes) -> True to try the device for a coalesced batch of
    that size.  on_error(exc) fires when the device path is exhausted
    (every lane quarantined — the tpu plugin degrades there);
    record(path, nbytes, secs, depth) feeds the owner's
    measured-routing EMA.  qos_class "scrub" marks channels that
    yield to "write" channels under contention.
    """

    __slots__ = ("key", "host_fn", "device_fn", "route", "on_error",
                 "record", "max_coalesce", "qos_class")

    def __init__(self, key, host_fn, device_fn=None, route=None,
                 on_error=None, record=None, max_coalesce=None,
                 qos_class="write"):
        self.key = key
        self.host_fn = host_fn
        self.device_fn = _wrap_device_fn(device_fn)
        self.route = route if route is not None else \
            (lambda nbytes: device_fn is not None)
        self.on_error = on_error or (lambda e: None)
        self.record = _wrap_record(record)
        self.max_coalesce = max_coalesce
        self.qos_class = qos_class


class _Item:
    __slots__ = ("arr", "n", "fut", "t", "cache", "tag", "ph")

    def __init__(self, arr: np.ndarray, cache=None, tag=None):
        self.arr = arr
        self.n = arr.shape[0]
        self.fut: Future = Future()
        self.t = time.monotonic()
        self.cache = cache          # hbm_cache.CacheIntent | None
        self.tag = tag              # QoS service class (pool name)
        # op-tracing phase stamps (time.monotonic — the span
        # timebase): submit -> picked (coalesce wait) -> stage0/1
        # (H2D) -> issue -> collect0 (compute done) -> done (D2H), or
        # host0/host1 for the host drain; requeues counts degrades.
        # `stripes` is this submission's own rows, `padded` its
        # pro-rata share of the bucket its dispatch ran at (the
        # submissions that shared a dispatch add up to the bucket).
        # Attached to the future as `trace_phases` at resolve so the
        # producer's op thread can span its TrackedOp.
        self.ph: dict = {"submit": self.t, "stripes": self.n}


class _Lane:
    """One device's dispatch lane: its own overlap window (a deque of
    in-flight dispatches bounded by the pipeline depth), a stager
    thread + staging queue (double-buffered H2D: upload of
    batch N+1 proceeds while batch N computes, and uploads to
    different chips run in parallel), its own collector thread,
    transfer accounting, and per-shape-bucket marginal service-time
    EMAs for cost-aware placement."""

    __slots__ = ("device", "index", "inflight", "stage_q", "staging",
                 "quarantined", "quarantine_reason", "alive",
                 "collect_started", "stage_started", "last_fetch_done",
                 "dispatches", "stripes", "nbytes", "errors",
                 "bytes_h2d", "bytes_d2h", "spb")

    def __init__(self, device, index: int):
        self.device = device
        self.index = index
        self.inflight: deque = deque()
        self.stage_q: deque = deque()
        self.staging = 0             # parts popped, not yet in flight
        self.quarantined = False
        self.quarantine_reason = ""
        self.alive = True            # False once the devset is rebuilt
        self.collect_started: float | None = None
        self.stage_started: float | None = None
        self.last_fetch_done = 0.0
        self.dispatches = 0
        self.stripes = 0
        self.nbytes = 0
        self.errors = 0
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        # shape-bucket (power of two of part bytes) -> marginal
        # sec/byte EMA — the same samples TpuBackend.record scores,
        # kept per chip so placement can prefer a measured-faster lane
        self.spb: dict[int, dict] = {}

    def load(self) -> int:
        """Occupancy the overlap window bounds: dispatched + staged +
        mid-staging parts (a part being uploaded is claimed work)."""
        return len(self.inflight) + len(self.stage_q) + self.staging

    def note_service(self, nbytes: int, secs: float) -> None:
        b = (max(nbytes, 1) - 1).bit_length()
        ent = self.spb.setdefault(b, {"spb": None, "n": 0})
        ent["n"] += 1
        spb = secs / max(nbytes, 1)
        ent["spb"] = spb if ent["spb"] is None else (
            0.7 * ent["spb"] + 0.3 * spb)

    def predict(self, nbytes: int) -> float | None:
        """Predicted marginal seconds to serve nbytes more on this
        lane (None until the shape bucket has enough samples)."""
        ent = self.spb.get((max(nbytes, 1) - 1).bit_length())
        if ent is None or ent["n"] < 3 or ent["spb"] is None:
            return None
        return ent["spb"] * nbytes * (self.load() + 1)

    def stuck(self, now: float) -> bool:
        for started in (self.collect_started, self.stage_started):
            if started is not None and now - started > STALL_TIMEOUT:
                return True
        return False

    def dump(self) -> dict:
        return {"device": str(self.device) if self.device is not None
                else "default",
                "dispatches": self.dispatches, "stripes": self.stripes,
                "bytes": self.nbytes, "errors": self.errors,
                "inflight": len(self.inflight),
                "staged": len(self.stage_q) + self.staging,
                "bytes_h2d": self.bytes_h2d,
                "bytes_d2h": self.bytes_d2h,
                "quarantined": self.quarantined,
                "quarantine_reason": self.quarantine_reason}


class DeviceSet:
    """The visible device topology, enumerated once at first device
    dispatch (importing jax is not free; host-only processes never
    pay it).  `shards` caps how many devices the pipeline spreads
    over (conf osd_ec_device_shards; None = all)."""

    def __init__(self, shards: int | None = None):
        devices: list = []
        # why enumeration failed, for the pipeline to log and count
        # (devset_errors) before the pseudo-lane takes over
        self.error: Exception | None = None
        try:
            import jax
            devices = list(jax.devices())
        except Exception as e:
            self.error = e
        if shards is not None:
            devices = devices[: max(1, int(shards))]
        if not devices:
            # no jax / no devices: one pseudo-lane keeps the dispatch
            # machinery uniform (device_fns get device=None, arrays
            # stay host-side)
            devices = [None]
        self.lanes = [_Lane(d, i) for i, d in enumerate(devices)]

    def active(self) -> list:
        return [l for l in self.lanes if not l.quarantined]


class _Group:
    """One mega-batch split across lanes: parts collect independently
    (possibly on different collector threads) and the futures resolve
    once every part landed, in original row order.  A failed part
    marks the whole group failed; its items requeue exactly once and
    surviving parts' outputs are discarded."""

    __slots__ = ("chan", "items", "nparts", "pending", "outs",
                 "failed", "nbytes", "t0")

    def __init__(self, chan, items, nparts, nbytes, t0):
        self.chan = chan
        self.items = items
        self.nparts = nparts
        self.pending = nparts
        self.outs: dict[int, tuple] = {}
        self.failed = False
        self.nbytes = nbytes
        self.t0 = t0


class _Staged:
    """One planned part waiting on (or inside) its lane's stager: the
    H2D upload + async compute issue happen on the lane's stager
    thread, off the dispatcher."""

    __slots__ = ("chan", "items", "part", "S", "group", "gidx")

    def __init__(self, chan, items, part, S, group=None, gidx=0):
        self.chan = chan
        self.items = items          # [] for split-group parts
        self.part = part
        self.S = S
        self.group = group
        self.gidx = gidx


class _Dispatch:
    __slots__ = ("chan", "items", "S", "out", "t0", "nbytes", "lane",
                 "group", "gidx", "dev_in")

    def __init__(self, chan, items, S, out, t0, nbytes, lane,
                 group=None, gidx=0, dev_in=None):
        self.chan = chan
        self.items = items
        self.S = S
        self.out = out
        self.t0 = t0
        self.nbytes = nbytes
        self.lane = lane
        self.group = group
        self.gidx = gidx
        self.dev_in = dev_in        # device-resident input (HBM cache)


def _cat_items(items: list) -> np.ndarray:
    """Reassemble one contiguous batch from items' stripe arrays."""
    arrs = [it.arr for it in items]
    return arrs[0] if len(arrs) == 1 else np.concatenate(arrs)


class EcDevicePipeline:
    def __init__(self, depth: int = DEFAULT_DEPTH,
                 coalesce_wait: float = DEFAULT_COALESCE_WAIT,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 device_shards: int | None = None,
                 split_min: int = DEFAULT_SPLIT_MIN,
                 scrub_weight: float = DEFAULT_SCRUB_WEIGHT,
                 cost_aware: bool = DEFAULT_COST_AWARE,
                 qos_cost_unit: int = DEFAULT_QOS_COST_UNIT):
        self.depth = max(1, int(depth))
        self.coalesce_wait = float(coalesce_wait)
        self.max_batch = max(1, int(max_batch))
        self.device_shards = device_shards
        self.split_min = max(1, int(split_min))
        self.scrub_weight = float(scrub_weight)
        self.cost_aware = bool(cost_aware)
        self.qos_cost_unit = max(0, int(qos_cost_unit))
        self._lock = threading.Lock()
        # three predicates, one lock: queued work (dispatcher waits),
        # in-flight dispatches (lane collectors wait), freed overlap
        # slots (dispatcher waits).  Separate conditions so a notify
        # can never wake the wrong thread and strand the right one.
        self._work_cv = threading.Condition(self._lock)
        self._inflight_cv = threading.Condition(self._lock)
        self._fetch_cv = threading.Condition(self._lock)
        # queues are keyed (chan.key, qos_tag): one coalescing stream
        # per (work class, tenant) — a mega-batch never mixes tenants,
        # so a reserved pool's encode can never wait INSIDE a noisy
        # pool's dispatch, and the picker below can order across
        # tenants (dmClock tags shared with the OSD op queue's conf)
        self._queues: dict = {}        # (chan.key, tag) -> deque[_Item]
        self._chans: dict = {}             # chan.key -> PipelineChannel
        from ..utils.dmclock import DmClockState
        self._qos = DmClockState()
        self._qos_enabled = False
        self._qos_wake = 0.0
        self._devset: DeviceSet | None = None
        self._rr = 0                       # placement tie-break rotor
        self._qos_contended = 0            # contended-pick counters
        self._qos_scrub = 0
        self._busy = 0                     # dispatches being processed
        self._stalled = False              # collectors wedged: host-only
        self._slices_warm: set = set()     # see _warm_item_buckets
        self._running = False
        self._threads: list = []
        self._c = {
            "dispatches": 0, "dev_dispatches": 0, "host_dispatches": 0,
            "ops": 0, "stripes": 0, "coalesce_waits": 0,
            "device_errors": 0, "drained_to_host": 0,
            "max_queue_depth": 0, "quarantines": 0,
            "split_dispatches": 0, "redrained": 0,
            "qos_scrub_yields": 0, "qos_cost_picks": 0,
            "bytes_h2d": 0, "bytes_d2h": 0,
            "cost_placements": 0, "cost_diverged": 0,
            # always 0: benchmark/cluster.py ZERO_COUNTERS reads this
            # key from perf dump's ec_pipeline block
            "mesh_degrades": 0,
            "devset_errors": 0, "route_errors": 0,
            "result_timeouts": 0,
        }

    # -- lifecycle ---------------------------------------------------------

    def _ensure_threads(self) -> None:
        if self._running:
            return
        self._running = True
        t = threading.Thread(target=self._dispatch_loop, daemon=True,
                             name="ec-pipeline-dispatch")
        t.start()
        self._threads.append(t)

    def _ensure_devset(self) -> DeviceSet:
        """Build the device set lazily (dispatcher thread only —
        imports jax, which must not run under the pipeline lock)."""
        ds = self._devset
        if ds is not None:
            return ds
        ds = DeviceSet(self.device_shards)
        if ds.error is not None:
            _log().warn(
                "EC pipeline found no device (%s: %s): one host "
                "pseudo-lane serves", type(ds.error).__name__, ds.error)
        with self._lock:
            if self._devset is None:
                self._devset = ds
                if ds.error is not None:
                    self._c["devset_errors"] += 1
                # collectors of retired device sets have exited by
                # now; drop them so repeated reset_devices sweeps
                # (a sweep over chip counts) cannot grow this unbounded
                self._threads = [t for t in self._threads
                                 if t.is_alive()]
                for lane in ds.lanes:
                    for target, tag in ((self._collect_loop, "collect"),
                                        (self._stage_loop, "stage")):
                        t = threading.Thread(
                            target=target, args=(lane,), daemon=True,
                            name=f"ec-pipeline-{tag}-{lane.index}")
                        t.start()
                        self._threads.append(t)
            return self._devset

    def reset_devices(self, device_shards=_UNSET) -> None:
        """Rebuild the device set on next dispatch: clears quarantine
        latches and (optionally) re-caps the shard count — tests that
        quarantined lanes or sweep chip counts use this."""
        self.flush(timeout=10.0)
        with self._lock:
            if device_shards is not _UNSET:
                self.device_shards = device_shards
            ds, self._devset = self._devset, None
            if ds is not None:
                for lane in ds.lanes:
                    lane.alive = False
            self._stalled = False
            self._inflight_cv.notify_all()
        # lane indices renumber with the topology: entries pinned to
        # the old lanes are no longer attributable — drop them (the
        # next writes repopulate from fresh uploads)
        hbm_cache.get().clear()

    def stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            self._running = False
            # drop the device set: a restarted pipeline (submit after
            # stop) must rebuild it so fresh collector threads spawn —
            # reusing the old lanes would enqueue work nothing collects
            ds, self._devset = self._devset, None
            if ds is not None:
                for lane in ds.lanes:
                    lane.alive = False
            self._work_cv.notify_all()
            self._inflight_cv.notify_all()
            self._fetch_cv.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._threads.clear()
        # a warm thread still inside a device compile at interpreter
        # teardown aborts the process: stopped means they finished
        wait_warmups(timeout)
        hbm_cache.get().clear()

    def note_result_timeout(self) -> None:
        """A producer gave up on a future after RESULT_TIMEOUT and
        served itself on the host (plugin encode/decode handles, the
        scrubber's fold)."""
        with self._lock:
            self._c["result_timeouts"] += 1

    def flush(self, timeout: float = 60.0) -> bool:
        """Block until every queued + staged + in-flight item resolved."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with self._lock:
                ds = self._devset
                inflight = sum(l.load() for l in ds.lanes) \
                    if ds else 0
                if not inflight and not self._busy and \
                        not any(self._queues.values()):
                    return True
            time.sleep(0.005)
        return False

    # -- producer side -----------------------------------------------------

    def submit(self, chan: PipelineChannel, arr: np.ndarray,
               cache=None, qos: str | None = None) -> Future:
        """Queue a (B, ...) uint8 batch on `chan`.  The future resolves
        to (path, outputs) with path in {"dev", "host"} and outputs the
        channel fn's tuple, sliced to this submission's B rows.

        `cache` (an hbm_cache.CacheIntent) asks the plane to keep this
        submission's device-resident inputs/outputs in the HBM stripe
        cache when the dispatch runs on a device (encode channels
        only — the fn's outputs must be (parity, crcs)).

        `qos` names the submission's service class (the pool, for
        client-write encodes): work of one class coalesces together
        and the dispatcher's picks honor the class's dmClock tags
        (configure_qos) — dispatch-level reservation/weight/limit, so
        a tenant saturating encodes cannot monopolize the lanes."""
        arr = np.ascontiguousarray(arr, dtype=np.uint8)
        if arr.ndim < 1 or arr.shape[0] == 0:
            raise ValueError(f"empty pipeline submission {arr.shape}")
        item = _Item(arr, cache=cache, tag=qos)
        with self._lock:
            self._ensure_threads()
            self._chans[chan.key] = chan
            self._queues.setdefault((chan.key, qos),
                                    deque()).append(item)
            self._c["ops"] += 1
            self._c["stripes"] += item.n
            qd = sum(len(q) for q in self._queues.values())
            if qd > self._c["max_queue_depth"]:
                self._c["max_queue_depth"] = qd
            self._work_cv.notify()
        return item.fut

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._c)
            out["queue_depth"] = sum(len(q) for q in
                                     self._queues.values())
            ds = self._devset
            out["inflight"] = sum(len(l.inflight) for l in ds.lanes) \
                if ds else 0
            out["staged"] = sum(len(l.stage_q) + l.staging
                                for l in ds.lanes) if ds else 0
            out["stalled"] = self._stalled
            out["devices"] = {str(l.index): l.dump()
                              for l in ds.lanes} if ds else {}
            out["active_devices"] = len(ds.active()) if ds else 0
        out["depth"] = self.depth
        out["device_shards"] = self.device_shards or "all"
        out["scrub_weight"] = self.scrub_weight
        out["cost_aware"] = self.cost_aware
        out["qos_cost_unit"] = self.qos_cost_unit
        d = out["dispatches"]
        out["mean_batch_size"] = (out["stripes"] / d) if d else 0.0
        out.update(warm_stats())
        # HBM stripe cache counters ride the same perf-dump section
        # (the cache is part of the transfer plane)
        for k, v in hbm_cache.stats().items():
            out[f"cache_{k}"] = v
        return out

    # -- dispatcher --------------------------------------------------------

    def _pick_key(self):
        """The (channel, tenant) queue to dispatch next.

        Two levels.  CLASS arbitration (unchanged from PR 3): the
        oldest queued item per class wins FIFO, except scrub yields to
        client-write work under contention (scrub_weight bounds its
        share of contended picks).  TENANT arbitration (per-pool QoS):
        among the write-class queue heads, a dmClock pick over the
        tenants' reservation/weight/limit tags (configure_qos) chooses
        WHICH tenant's stream dispatches — oldest-first within the
        tenant, exact cross-queue FIFO when no pool class is
        configured.  A write class fully limit-throttled serves scrub;
        with nothing else eligible the dispatcher sleeps till the
        earliest tag (self._qos_wake), never spinning and never
        serving a limited tenant above its cap."""
        best_w = best_s = None
        t_w = t_s = None
        write_heads: dict = {}
        for key, q in self._queues.items():
            if not q:
                continue
            chan = self._chans.get(key[0])
            if chan is not None and chan.qos_class == "scrub":
                if t_s is None or q[0].t < t_s:
                    best_s, t_s = key, q[0].t
            else:
                write_heads[key] = q[0].t
                if t_w is None or q[0].t < t_w:
                    best_w, t_w = key, q[0].t
        want = None
        if best_s is None:
            want = "write"
        elif best_w is None:
            return best_s
        else:
            w = self.scrub_weight
            if w >= 1.0:
                want = "scrub" if t_s < t_w else "write"
            else:
                # ratio-faithful: scrub's served fraction of contended
                # picks tracks the configured weight exactly
                self._qos_contended += 1
                if self._qos_scrub + 1 <= w * self._qos_contended:
                    self._qos_scrub += 1
                    want = "scrub"
                else:
                    if t_s < t_w:
                        self._c["qos_scrub_yields"] += 1
                    want = "write"
        if want == "scrub":
            return best_s
        if best_w is None:
            return None
        if not self._qos_enabled:
            return best_w
        return self._qos_pick_write(write_heads, best_s)

    def _qos_pick_write(self, write_heads: dict, best_s):
        """dmClock tenant pick among the write-class heads; falls back
        to scrub when every tenant is limit-throttled.

        Picks are BYTES-WEIGHTED: each candidate tenant's grant is
        charged 1 + head_batch_bytes/qos_cost_unit (the same
        normalization as the op queue's osd_qos_cost_bytes_unit), so
        a tenant streaming mega-batch encodes advances its tags
        proportionally further than one trickling 4 KiB stripes —
        configured rates meter bytes through the lanes, not dispatch
        counts (cost=1 was the PR 10 follow-up this closes)."""
        cands: dict = {}
        by_tag: dict = {}
        for key, t in write_heads.items():
            tag = key[1] if key[1] is not None else "_system"
            if t < cands.get(tag, float("inf")):
                cands[tag] = t
            by_tag.setdefault(tag, []).append((t, key))
        costs = None
        if self.qos_cost_unit > 0:
            costs = {}
            for tag, lst in by_tag.items():
                _t, hkey = min(lst, key=lambda e: e[0])
                head = self._queues[hkey][0]
                costs[tag] = 1.0 + head.arr.nbytes / self.qos_cost_unit
        client, _phase, wake = self._qos.pick(cands, costs=costs)
        if client is None:
            # every queued tenant over its limit: scrub may run; else
            # the dispatch loop sleeps until the earliest tag
            self._qos.note_stall()
            self._qos_wake = wake
            if best_s is not None and self.scrub_weight < 1.0:
                # scrub actually takes this contended pick: credit
                # the ratio ledger, or throttle windows would bank
                # scrub a burst of extra picks against resumed
                # client writes (the PR 3 share must stay honest)
                self._qos_scrub += 1
            return best_s
        if costs is not None:
            self._c["qos_cost_picks"] += 1
        return min(by_tag[client], key=lambda e: e[0])[1]

    def _window_full_locked(self, now: float) -> bool:
        """True while every usable lane's overlap window is full —
        the dispatcher holds off so arrivals coalesce into the next
        mega-batch (the whole point).  Quarantined and stuck lanes
        don't count: work must not wait behind a dead chip."""
        ds = self._devset
        if ds is None:
            return False
        lanes = [l for l in ds.lanes
                 if not l.quarantined and not l.stuck(now)]
        if not lanes:
            return False
        return all(l.load() >= self.depth for l in lanes)

    def _dispatch_loop(self) -> None:
        while True:
            with self._lock:
                while self._running and \
                        not any(self._queues.values()):
                    self._work_cv.wait()
                if not self._running:
                    return
                # overlap cap: while every lane's window is full, hold
                # off — arrivals during the wait coalesce into the
                # next mega-batch
                waited = False
                wait_start = None
                while self._running and not self._stalled and \
                        self._window_full_locked(time.monotonic()):
                    waited = True
                    now = time.monotonic()
                    if wait_start is None:
                        wait_start = now
                    elif now - wait_start > STALL_TIMEOUT:
                        # every usable lane's collector is wedged
                        # inside a hung device fetch (no exception to
                        # quarantine on): latch host-only dispatch so
                        # EC I/O keeps flowing; producers stuck on the
                        # wedged dispatches self-serve via their
                        # RESULT_TIMEOUT
                        self._stalled = True
                        _log().warn(
                            "device fetches stalled > %.0fs on every "
                            "usable lane: latching pipeline to "
                            "host-only dispatch", STALL_TIMEOUT)
                        break
                    self._fetch_cv.wait(self.coalesce_wait or 0.01)
                if waited:
                    self._c["coalesce_waits"] += 1
                if not self._running:
                    return
                key = self._pick_key()
                if key is None:
                    if any(self._queues.values()):
                        # work queued but every tenant limit-throttled:
                        # sleep until the earliest tag comes due (new
                        # submissions still notify immediately)
                        self._work_cv.wait(max(
                            0.001,
                            min(self._qos_wake - time.monotonic(),
                                0.1)))
                    continue
                chan = self._chans[key[0]]
                q = self._queues[key]
                cap = chan.max_coalesce or self.max_batch
                items, n = [], 0
                pick_t = time.monotonic()
                while q and (not items or n + q[0].n <= cap):
                    it = q.popleft()
                    it.ph["picked"] = pick_t    # coalesce wait ends
                    items.append(it)
                    n += it.n
                if not q:
                    # self-cleaning registry: a drained key drops its
                    # queue — and the channel ref once no other
                    # tenant's queue still needs it (submit
                    # re-registers), so retired codecs / one-off
                    # decode patterns cannot accumulate in the
                    # process-wide singleton
                    del self._queues[key]
                    if not any(k[0] == key[0] for k in self._queues):
                        self._chans.pop(key[0], None)
                self._busy += 1
            try:
                self._dispatch(chan, items)
            except Exception as e:      # never kill the loop
                for it in items:
                    if not it.fut.done():
                        it.fut.set_exception(e)
            finally:
                with self._lock:
                    self._busy -= 1

    # -- placement ---------------------------------------------------------

    def _quarantine_locked(self, lane: _Lane, reason: str) -> None:
        if lane.quarantined:
            return
        lane.quarantined = True
        lane.quarantine_reason = reason
        self._c["quarantines"] += 1
        # the chip is in an unknown state: its HBM cache entries must
        # never serve again (redrain re-uploads from host)
        hbm_cache.get().drop_lane(lane.index)

    def _log_quarantine(self, lane: _Lane, active_left: int) -> None:
        _log().warn(
            "EC device lane %d (%s) quarantined (%s): redraining its "
            "work onto %d surviving chip(s)%s", lane.index,
            lane.device, lane.quarantine_reason, active_left,
            "" if active_left else " — none left, host fallback")

    def _plan_locked(self, S: int, nbytes: int = 0,
                     bounds: list | None = None) -> tuple[list, bool]:
        """Place a coalesced S-stripe batch: (plan, exhausted).

        plan is [(lane, row_start, row_count), ...] — one entry for a
        whole-batch dispatch, several when the batch splits across
        idle lanes; empty when no lane can take it right now.
        exhausted=True means every lane is quarantined (host fallback,
        channel owner gets on_error).  Injected per-device faults
        (``tpu_error <prob> <device>``) are rolled here, at placement,
        so a targeted fault quarantines its lane even before the
        jitted fn warmed on it.

        `bounds` (interior item-boundary row offsets, ascending) marks
        a CACHE-TAGGED batch: splits may only cut at item boundaries,
        so every tagged item's rows land whole on ONE chip and its
        stripes can stay in that chip's HBM cache (a row-split part
        can't stage — an item's rows would straddle lanes).  A
        single-item tagged batch therefore rides whole on one lane:
        HBM residency saves the scrub/recovery re-upload AND the
        recompute, which beats one parallel upload.

        Whole-batch picks are COST-AWARE: per-(shape-bucket, chip)
        marginal service-time EMAs (fed from the same samples
        TpuBackend.record scores) override the least-loaded choice
        when a measured-faster lane would beat it by COST_MARGIN —
        `cost_diverged` counts the overrides.  Lanes without samples
        keep their least-loaded/round-robin turn, so every chip stays
        probed.
        """
        ds = self._devset
        if ds is None:
            # rebuilding (reset_devices raced this dispatch): host
            # serves this batch; the fresh device set takes the next
            return [], False
        now = time.monotonic()
        fs = faults.get()
        cands = []
        for lane in ds.lanes:
            if lane.quarantined or lane.stuck(now):
                continue
            if fs.tpu_error(device=lane.index):
                self._quarantine_locked(lane, "injected device error")
                self._c["device_errors"] += 1
                lane.errors += 1
                continue
        # re-scan after the fault roll (it may have quarantined lanes)
        active = ds.active()
        if not active:
            return [], True
        for lane in active:
            if not lane.stuck(now) and lane.load() < self.depth:
                cands.append(lane)
        if not cands:
            if all(lane.stuck(now) for lane in active):
                # every surviving chip's collector is wedged inside a
                # hung fetch: latch host-only dispatch (same terminal
                # state the window-full wait reaches) so placement
                # stops probing dead lanes per batch
                self._stalled = True
                _log().warn(
                    "all %d active EC device lanes stuck > %.0fs: "
                    "latching pipeline to host-only dispatch",
                    len(active), STALL_TIMEOUT)
            return [], False
        n = len(cands)
        rot = self._rr
        self._rr += 1
        cands.sort(key=lambda l: (l.load(), (l.index - rot) % n))
        idle = [l for l in cands if not l.load()]
        nparts = min(len(idle), S // self.split_min)
        if nparts >= 2:
            if bounds is not None:
                cuts = self._aligned_cuts(bounds, S, nparts)
                if cuts:
                    edges = [0] + cuts + [S]
                    return [(idle[i], edges[i], edges[i + 1] - edges[i])
                            for i in range(len(edges) - 1)], False
                # single tagged item: fall through to whole-batch
            else:
                base, rem = divmod(S, nparts)
                plan, r0 = [], 0
                for i in range(nparts):
                    rn = base + (1 if i < rem else 0)
                    plan.append((idle[i], r0, rn))
                    r0 += rn
                return plan, False
        pick = cands[0]
        if self.cost_aware and nbytes and len(cands) > 1:
            p_least = pick.predict(nbytes)
            if p_least is not None:
                self._c["cost_placements"] += 1
                best, p_best = pick, p_least
                for lane in cands[1:]:
                    p = lane.predict(nbytes)
                    if p is not None and p < p_best:
                        best, p_best = lane, p
                if best is not pick and p_best * COST_MARGIN < p_least:
                    pick = best
                    self._c["cost_diverged"] += 1
        return [(pick, 0, S)], False

    @staticmethod
    def _aligned_cuts(bounds: list, S: int, nparts: int) -> list:
        """Up to nparts-1 strictly-increasing cut points drawn from
        the item boundaries, each nearest the even-split ideal for the
        rows left — parts stay balanced to the extent item sizes
        allow."""
        cuts: list = []
        last = 0
        remaining = nparts
        avail = [b for b in bounds if 0 < b < S]
        while remaining > 1 and avail:
            want = last + max(1, round((S - last) / remaining))
            best = min(avail, key=lambda b: abs(b - want))
            cuts.append(best)
            last = best
            avail = [b for b in avail if b > best]
            remaining -= 1
        return cuts

    def _to_device(self, padded: np.ndarray, lane: _Lane):
        """Stage one part's H2D upload onto `lane`'s chip (runs on the
        lane's stager thread — uploads to different chips proceed in
        parallel and overlap the previous batch's compute).  Every
        byte that actually crosses the boundary is accounted."""
        if lane.device is None:
            return padded
        import jax
        dev = jax.device_put(padded, lane.device)
        with self._lock:
            lane.bytes_h2d += padded.nbytes
            self._c["bytes_h2d"] += padded.nbytes
        return dev

    def _requeue_locked(self, chan: PipelineChannel, items: list) -> None:
        """Push redrained items back to the FRONT of their channel
        queue (they were submitted first; FIFO fairness holds).  A
        dispatch never mixes tenants, so one requeue batch shares one
        (channel, tag) queue."""
        self._chans[chan.key] = chan
        tag = items[0].tag if items else None
        q = self._queues.setdefault((chan.key, tag), deque())
        for it in items:
            # quarantine/failure degrade marker for the op trace
            it.ph["requeues"] = it.ph.get("requeues", 0) + 1
            it.ph.pop("padded", None)   # the next dispatch pads anew
        q.extendleft(reversed(items))
        self._c["redrained"] += len(items)
        self._work_cv.notify()

    # -- dispatch ----------------------------------------------------------

    def _dispatch(self, chan: PipelineChannel, items: list) -> None:
        batch = _cat_items(items)
        nbytes = batch.nbytes
        use_dev = False
        if chan.device_fn is not None and not self._stalled:
            try:
                use_dev = bool(chan.route(nbytes))
            except Exception as e:
                with self._lock:
                    self._c["route_errors"] += 1
                _log().warn(
                    "EC channel %s route callback failed (%s: %s): "
                    "host serves the batch", chan.key,
                    type(e).__name__, e)
        if use_dev:
            self._ensure_devset()
            bounds = None
            if hbm_cache.get().capacity > 0 and \
                    any(it.cache is not None for it in items):
                bounds, r = [], 0
                for it in items[:-1]:
                    r += it.n
                    bounds.append(r)
            with self._lock:
                plan, exhausted = self._plan_locked(batch.shape[0],
                                                    nbytes, bounds)
            if exhausted:
                # every chip quarantined: the channel owner degrades
                # (tpu plugin -> host matrix codec) and this batch —
                # plus everything behind it — drains to the host fn
                with self._lock:
                    self._c["drained_to_host"] += len(items)
                chan.on_error(RuntimeError(
                    "all EC device lanes quarantined"))
            elif plan:
                parts_items = None
                if len(plan) > 1 and bounds is not None:
                    # item-aligned split: each part is an INDEPENDENT
                    # dispatch carrying its own items (no group), so
                    # every part resolves — and stages its tagged
                    # items into the HBM cache — on its own lane
                    parts_items, it_iter = [], iter(items)
                    for _lane, _r0, rn in plan:
                        sub, acc = [], 0
                        while acc < rn:
                            nxt = next(it_iter)
                            sub.append(nxt)
                            acc += nxt.n
                        parts_items.append(sub)
                self._issue(chan, items, batch, plan, parts_items)
                return          # staged onto its lanes (the stagers
                                # upload + dispatch, or host-serve a
                                # cold fn / redrain a dead lane)
            # no lane free right now: host serves
        self._run_host(chan, items, batch)

    def _issue(self, chan: PipelineChannel, items: list,
               batch: np.ndarray, plan: list,
               parts_items: list | None = None) -> bool:
        """Hand the placed (possibly split) batch to its lanes'
        stagers.  The dispatcher never touches the device: uploads and
        async compute issue on the per-lane stager threads, so it is
        free to keep coalescing while parts stream H2D in parallel.
        Returns True when the batch is claimed (staged, or redrained
        after hitting a dead lane); False never — host fallback for a
        cold (not-warm) device fn happens on the stager.

        `parts_items` (item-aligned splits) makes each part its own
        groupless dispatch over exactly its items."""
        group = None
        if len(plan) > 1:
            if parts_items is None:
                group = _Group(chan, items, len(plan), batch.nbytes,
                               time.perf_counter())
            with self._lock:
                self._c["split_dispatches"] += 1
        for gidx, (lane, r0, rn) in enumerate(plan):
            part = batch[r0: r0 + rn] if len(plan) > 1 else batch
            p_items = (items if group is None else []) \
                if parts_items is None else parts_items[gidx]
            staged = _Staged(chan, p_items, part, rn, group, gidx)
            with self._lock:
                if not lane.alive or lane.quarantined:
                    # placement raced a devset rebuild or quarantine:
                    # requeue for a healthy lane (or the host path).
                    # Row-split: the whole batch, parts already staged
                    # discard via the failed group.  Item-aligned:
                    # earlier parts are independent dispatches that
                    # resolve on their lanes — requeue only the parts
                    # not yet staged.
                    if parts_items is not None:
                        self._requeue_locked(
                            chan, [it for sub in parts_items[gidx:]
                                   for it in sub])
                        return True
                    already = group is not None and group.failed
                    if group is not None:
                        group.failed = True
                    if not already:
                        self._requeue_locked(chan, items)
                    return True
                lane.stage_q.append(staged)
                self._inflight_cv.notify_all()
        return True

    # -- stagers (one thread per lane: the H2D half of the plane) ----------

    def _stage_loop(self, lane: _Lane) -> None:
        while True:
            with self._lock:
                while self._running and lane.alive and \
                        not lane.stage_q:
                    self._inflight_cv.wait()
                if not self._running or not lane.alive:
                    # a retired lane (reset_devices) must not strand
                    # queued parts — their futures would never
                    # resolve and the op threads waiting on them
                    # would wedge: requeue for the fresh device set
                    while lane.stage_q:
                        staged = lane.stage_q.popleft()
                        already = staged.group is not None and \
                            staged.group.failed
                        if staged.group is not None:
                            staged.group.failed = True
                        if not already:
                            self._requeue_locked(
                                staged.chan,
                                staged.items if staged.group is None
                                else staged.group.items)
                    return
                staged = lane.stage_q.popleft()
                if lane.quarantined:
                    # quarantined after staging: redrain to survivors
                    already = staged.group is not None and \
                        staged.group.failed
                    if staged.group is not None:
                        staged.group.failed = True
                    if not already:
                        self._requeue_locked(
                            staged.chan,
                            staged.items if staged.group is None
                            else staged.group.items)
                    continue
                lane.staging += 1
                lane.stage_started = time.monotonic()
                self._busy += 1
            try:
                self._stage_one(staged, lane)
            except Exception as e:
                for it in (staged.items if staged.group is None
                           else staged.group.items):
                    if not it.fut.done():
                        it.fut.set_exception(e)
            finally:
                with self._lock:
                    lane.staging -= 1
                    lane.stage_started = None
                    self._busy -= 1
                    self._fetch_cv.notify_all()

    def _stage_one(self, staged: _Staged, lane: _Lane) -> None:
        """Upload one part and issue its async device dispatch."""
        chan = staged.chan
        its = staged.items if staged.group is None \
            else staged.group.items
        t_s0 = time.monotonic()
        padded = pad_batch(staged.part)
        try:
            dev_arr = self._to_device(padded, lane)
        except Exception as e:
            # a failed upload is a device error on this lane
            self._device_failed_dispatch(chan, lane, staged.group,
                                         staged, e)
            return
        t_s1 = time.monotonic()
        share = padded.shape[0] / sum(it.n for it in its)
        for it in its:
            # split-group parts stage concurrently; the per-item
            # stamps keep the widest window (min start, max end) and
            # add up the parts' buckets
            it.ph["padded"] = it.ph.get("padded", 0.0) + share * it.n
            it.ph["stage0"] = min(it.ph.get("stage0", t_s0), t_s0)
            it.ph["stage1"] = max(it.ph.get("stage1", t_s1), t_s1)
            it.ph["issue"] = it.ph["stage1"]
        t0 = time.perf_counter()
        try:
            out = chan.device_fn(dev_arr, lane.device)
        except Exception as e:
            self._device_failed_dispatch(chan, lane, staged.group,
                                         staged, e)
            return
        if out is None:
            # not warm on this device yet (background compile kicked
            # off): host serves the whole batch.  For a split group
            # only the FIRST cold part host-serves (every item lives
            # at group level); other parts' outputs discard.
            if staged.group is not None:
                with self._lock:
                    serve = not staged.group.failed
                    staged.group.failed = True
                if serve:
                    items = staged.group.items
                    self._run_host(chan, items, _cat_items(items))
            else:
                self._run_host(chan, staged.items, staged.part)
            return
        disp = _Dispatch(chan, staged.items, staged.S, out, t0,
                         staged.part.nbytes, lane, staged.group,
                         staged.gidx, dev_in=dev_arr)
        with self._lock:
            if not lane.alive:
                # reset_devices retired this lane mid-upload — its
                # collector may already be gone, so an append here
                # would never be collected: requeue for the fresh
                # device set instead
                already = staged.group is not None and \
                    staged.group.failed
                if staged.group is not None:
                    staged.group.failed = True
                if not already:
                    self._requeue_locked(
                        chan, staged.items if staged.group is None
                        else staged.group.items)
                return
            lane.inflight.append(disp)
            self._inflight_cv.notify_all()

    def _device_failed_dispatch(self, chan, lane, group, staged,
                                e: Exception) -> None:
        """A device_fn blew up at issue time: quarantine the lane and
        redrain onto survivors (host only when none remain).  Split
        parts fail concurrently on different stagers — the group's
        failed latch guarantees the items requeue exactly once."""
        items = staged.items if group is None else group.items
        with self._lock:
            self._c["device_errors"] += 1
            lane.errors += 1
            self._quarantine_locked(lane, f"{type(e).__name__}: {e}")
            already_requeued = False
            if group is not None:
                already_requeued = group.failed
                group.failed = True
            ds = self._devset
            # devset mid-rebuild counts as having survivors: requeue
            # and let the fresh lanes (or the host path) serve it
            active_left = len(ds.active()) if ds is not None else 1
        self._log_quarantine(lane, active_left)
        if already_requeued:
            return
        if active_left:
            with self._lock:
                self._requeue_locked(chan, items)
            return
        with self._lock:
            self._c["drained_to_host"] += len(items)
        chan.on_error(e)
        self._run_host(chan, items, _cat_items(items))

    # -- collectors (one thread per lane) ----------------------------------

    def _collect_loop(self, lane: _Lane) -> None:
        while True:
            with self._lock:
                while self._running and lane.alive and \
                        not lane.inflight:
                    self._inflight_cv.wait()
                if not self._running:
                    return
                if not lane.inflight:
                    return              # devset rebuilt, lane drained
                disp = lane.inflight.popleft()
                lane.collect_started = time.monotonic()
                self._busy += 1
            try:
                self._collect_one(disp)
            except Exception as e:
                # never kill the loop: a dead collector would leak
                # _busy and wedge every producer blocked in result()
                for it in (disp.items if disp.group is None
                           else disp.group.items):
                    if not it.fut.done():
                        it.fut.set_exception(e)
            finally:
                with self._lock:
                    lane.collect_started = None
                    self._busy -= 1
                    self._fetch_cv.notify_all()

    def _collect_one(self, disp: _Dispatch) -> None:
        lane = disp.lane
        try:
            # parity-only readback: exactly the channel fn's outputs
            # cross D2H (an encode fetches (S_pad, m, L) parity + the
            # 4*(k+m)-byte CRC vector per stripe — never the data
            # shards the host already holds)
            t_c0 = time.monotonic()
            outs = tuple(np.asarray(o) for o in disp.out)
            t_c1 = time.monotonic()
            for it in (disp.items if disp.group is None
                       else disp.group.items):
                it.ph["collect0"] = min(it.ph.get("collect0", t_c0),
                                        t_c0)
                it.ph["done"] = max(it.ph.get("done", t_c1), t_c1)
            d2h = sum(int(o.nbytes) for o in outs)
            now = time.perf_counter()
            # marginal service time PER LANE: overlap with this chip's
            # previous fetch does not double-bill — this is the
            # amortized per-chip sec/byte the measured router scores
            start = max(disp.t0, lane.last_fetch_done)
            lane.last_fetch_done = now
            secs = max(now - start, 1e-9)
            with self._lock:
                depth = len(lane.inflight) + 1
                self._c["dispatches"] += 1
                self._c["dev_dispatches"] += 1
                self._c["bytes_d2h"] += d2h
                lane.dispatches += 1
                lane.stripes += disp.S
                lane.nbytes += disp.nbytes
                lane.bytes_d2h += d2h
                lane.note_service(disp.nbytes, secs)
            try:
                disp.chan.record("dev", disp.nbytes, secs, depth,
                                 device=lane.index)
            except Exception:
                pass
            if disp.group is None:
                self._stage_cache(disp, outs)
            outs = tuple(o[: disp.S] for o in outs)
            if disp.group is None:
                self._resolve(disp.items, "dev", outs)
            else:
                self._group_part_done(disp, outs)
        except Exception as e:
            self._device_failed_fetch(disp, e)

    def _stage_cache(self, disp: _Dispatch, outs: tuple) -> None:
        """Keep cache-tagged items' stripes in HBM: the item's rows of
        the already-uploaded input and the already-computed parity —
        zero extra transfer (`hbm_cache.item_arrays`: the dispatch's
        own arrays, or a cut at the item's bucket with the offset an
        operand).  Only row-split group parts skip (an item's rows
        straddle part boundaries there) — placement cuts cache-tagged
        batches at item boundaries precisely so their parts arrive
        here as independent dispatches."""
        if disp.dev_in is None or len(disp.out) < 2 or \
                hbm_cache.get().capacity <= 0:
            return
        self._warm_item_buckets(disp)
        if not any(it.cache is not None for it in disp.items):
            return
        off = 0
        for it in disp.items:
            if it.cache is not None:
                try:
                    data, parity, row0 = hbm_cache.item_arrays(
                        disp.dev_in, disp.out[0], off, it.n,
                        next_bucket(it.n))
                    hbm_cache.get().stage(
                        it.cache, disp.lane.index, data, parity,
                        outs[1][off: off + it.n], row0)
                except Exception:
                    pass        # cache is an optimization, never a fault
            off += it.n

    def _warm_item_buckets(self, disp: _Dispatch) -> None:
        """What the cache keeps of an item is a device program a
        (batch bucket, item bucket) pair, whatever the item's rows and
        offset, and the CRC fold that checks a read served from it one
        a bucket.  When an item of a bucket is first staged, compile
        both on a warm thread (`hbm_cache.warm_item`): otherwise the
        first dispatch in which such an item shares a batch compiles
        on the collector, seconds or minutes into serving.  Untagged
        items count too: an append's tail encode is one, and
        `append_through` keeps its rows at that bucket."""
        lane = disp.lane
        if lane.device is None:
            return              # host arrays: numpy views, no program
        like = tuple((tuple(a.shape[1:]), np.dtype(a.dtype))
                     for a in (disp.dev_in, disp.out[0]))
        keys = {(lane.index, next_bucket(it.n), like)
                for it in disp.items}
        with self._lock:
            keys -= self._slices_warm
            self._slices_warm |= keys
        if not keys:
            return
        # batches worth warming: up to the channel's cap in rows and
        # LANE_STAGE_BYTES in bytes
        cap = disp.chan.max_coalesce or self.max_batch
        row_bytes = disp.dev_in.nbytes // disp.dev_in.shape[0]
        batches = [1 << e for e in range(next_bucket(cap).bit_length())
                   if (1 << e) <= cap
                   and (1 << e) * row_bytes <= LANE_STAGE_BYTES]
        buckets = sorted(b for _idx, b, _like in keys)

        def warm():
            try:
                for b in buckets:
                    hbm_cache.warm_item(like, lane.device, b, batches)
            except Exception as e:
                note_warm_failure(f"cache programs of buckets {buckets}",
                                  e)

        start_warm_thread(warm, "ec-slice-warm")

    def _group_part_done(self, disp: _Dispatch, outs: tuple) -> None:
        g = disp.group
        with self._lock:
            if g.failed:
                return                 # another part quarantined; the
            g.outs[disp.gidx] = outs   # items were already requeued
            g.pending -= 1
            done = g.pending == 0
        if done:
            # group-level routing sample at the FULL mega-batch size:
            # the per-part records capture per-chip marginal cost in
            # their (smaller) buckets; this one keeps the bucket the
            # host path records at comparable, scoring the fleet's
            # issue-to-complete cost for a batch this big
            try:
                g.chan.record(
                    "dev", g.nbytes,
                    max(time.perf_counter() - g.t0, 1e-9), g.nparts)
            except Exception:
                pass
            width = len(g.outs[0])
            cat = tuple(
                np.concatenate([g.outs[i][j] for i in range(g.nparts)])
                for j in range(width))
            self._resolve(g.items, "dev", cat)

    def _device_failed_fetch(self, disp: _Dispatch, e: Exception) -> None:
        """Async-dispatch errors surface at fetch: quarantine the lane
        and redrain the WHOLE batch onto surviving chips (or, with no
        chips left, degrade the channel owner and re-run on host) —
        nothing queued is lost, results stay bit-identical."""
        lane = disp.lane
        chan = disp.chan
        items = disp.items if disp.group is None else disp.group.items
        with self._lock:
            self._c["device_errors"] += 1
            lane.errors += 1
            self._quarantine_locked(lane, f"{type(e).__name__}: {e}")
            already_requeued = False
            if disp.group is not None:
                already_requeued = disp.group.failed
                disp.group.failed = True
            ds = self._devset
            active_left = len(ds.active()) if ds is not None else 1
        self._log_quarantine(lane, active_left)
        if already_requeued:
            return
        if active_left:
            with self._lock:
                self._requeue_locked(chan, items)
            return
        with self._lock:
            self._c["drained_to_host"] += len(items)
        chan.on_error(e)
        self._run_host(chan, items, _cat_items(items))

    # -- shared ------------------------------------------------------------

    def _run_host(self, chan: PipelineChannel, items: list,
                  batch: np.ndarray) -> None:
        t0 = time.perf_counter()
        t_h0 = time.monotonic()
        try:
            outs = tuple(np.asarray(o) for o in chan.host_fn(batch))
        except Exception as e:
            for it in items:
                if not it.fut.done():
                    it.fut.set_exception(e)
            return
        t_h1 = time.monotonic()
        for it in items:
            it.ph["host0"] = t_h0
            it.ph["host1"] = t_h1
        with self._lock:
            self._c["dispatches"] += 1
            self._c["host_dispatches"] += 1
        try:
            chan.record("host", batch.nbytes,
                        max(time.perf_counter() - t0, 1e-9), 1)
        except Exception:
            pass
        self._resolve(items, "host", outs)

    @staticmethod
    def _resolve(items: list, path: str, outs: tuple) -> None:
        off = 0
        for it in items:
            sl = tuple(o[off: off + it.n] for o in outs)
            off += it.n
            if not it.fut.done():
                # phase stamps ride the future itself: the producer's
                # op thread turns them into TrackedOp spans without
                # any pipeline->tracker coupling
                it.fut.trace_phases = dict(it.ph)
                it.fut.set_result((path, sl))


# ---------------------------------------------------------------------------
# Process-wide singleton (all producers in a process share one queue —
# that IS the cross-op coalescing) + plugin-agnostic channels.
# ---------------------------------------------------------------------------

_global: EcDevicePipeline | None = None
_glock = threading.Lock()


def get() -> EcDevicePipeline:
    global _global
    if _global is None:
        with _glock:
            if _global is None:
                _global = EcDevicePipeline()
    return _global


def configure(depth: int | None = None,
              coalesce_wait: float | None = None,
              max_batch: int | None = None,
              device_shards=_UNSET,
              scrub_weight: float | None = None,
              split_min: int | None = None,
              cost_aware: bool | None = None,
              hbm_cache_bytes: int | None = None,
              qos_cost_unit: int | None = None) -> EcDevicePipeline:
    """Tune the shared pipeline (daemon startup applies its conf)."""
    p = get()
    if depth is not None:
        p.depth = max(1, int(depth))
    if coalesce_wait is not None:
        p.coalesce_wait = max(0.0, float(coalesce_wait))
    if max_batch is not None:
        p.max_batch = max(1, int(max_batch))
    if scrub_weight is not None:
        p.scrub_weight = max(0.01, float(scrub_weight))
    if split_min is not None:
        p.split_min = max(1, int(split_min))
    if cost_aware is not None:
        p.cost_aware = bool(cost_aware)
    if hbm_cache_bytes is not None:
        hbm_cache.configure(hbm_cache_bytes)
    if qos_cost_unit is not None:
        p.qos_cost_unit = max(0, int(qos_cost_unit))
    if device_shards is not _UNSET and \
            device_shards != p.device_shards:
        # shard-count change rebuilds the device set (and clears any
        # quarantine latches with it)
        if p._devset is not None:
            p.reset_devices(device_shards)
        else:
            p.device_shards = device_shards
    return p


def stats() -> dict:
    return get().stats()


def configure_qos(specs: dict, cost_unit: int | None = None) -> None:
    """Install per-pool dmClock service classes ({pool: QosSpec}) on
    the dispatch-lane picker.  Called by every daemon's
    _qos_reconfigure — the pipeline is process-wide, so in-process
    daemons (one shared conf) converge on the same class set.  Rates
    apply at DISPATCH-pick granularity, BYTES-WEIGHTED: each pick is
    charged 1 + head_batch_bytes/cost_unit (osd_qos_cost_bytes_unit),
    so reservation/weight/limit meter a tenant's bytes through the
    lanes, not its dispatch count; the op queue's per-op rates remain
    the precise enforcement point."""
    p = get()
    if cost_unit is not None:
        p.qos_cost_unit = max(0, int(cost_unit))
    with p._lock:
        p._qos.configure(dict(specs))
        p._qos_enabled = bool(specs)


def qos_stats() -> dict:
    """The dispatch-lane half of the perf-dump `qos` block."""
    return get()._qos.stats()


# -- deep-scrub CRC channels -------------------------------------------------
#
# Keyed per shard size; device fn is the jitted CRC fold, warmed on a
# background thread PER DEVICE exactly like TpuBackend's codec fns so
# the shared dispatcher never blocks tens of seconds inside a
# first-shape compile.

_crc_channels: dict[int, PipelineChannel] = {}
# warmed jitted fns are pinned HERE, not re-fetched through
# ec_kernels' lru_cache: an LRU eviction would otherwise recompile
# inline on the shared dispatcher thread while the readiness set
# still claims the shape is warm (TpuBackend couples _fns/_ready the
# same way)
_crc_fns: dict = {}
_crc_ready: set = set()
_crc_warming: set = set()
_crc_warm_failed: set = set()
_crc_lock = threading.Lock()
# sticky device-dead latch (the tpu plugin's degrade equivalent): a
# REAL post-warm device failure that exhausts every lane must not
# cost a failing dispatch + host re-run on every later scrub batch
# until daemon restart
_crc_device_dead = False


def _crc_on_error(e: Exception) -> None:
    global _crc_device_dead
    if not _crc_device_dead:
        _crc_device_dead = True
        _log().warn(
            "scrub CRC device path failed (%s: %s): latching to host "
            "fold", type(e).__name__, e)


def _device_warm_key(device):
    if device is None:
        return None
    return (getattr(device, "platform", "?"), getattr(device, "id", 0))


def _crc_row_buckets(rows: int, cap: int | None) -> list[int]:
    """The row buckets to compile when a padded batch of `rows`
    misses: its own first (it is being served), then every power of
    two up to the bucket of the channel's cap, where it has one."""
    if not cap:
        return [rows]
    return [rows] + [1 << e for e in range(next_bucket(cap).bit_length())
                     if 1 << e != rows]


def _crc_device_fn(size: int):
    def device_fn(padded, device=None):
        shape = tuple(padded.shape)
        dev = _device_warm_key(device)
        with _crc_lock:
            fn = _crc_fns.get((size, shape, dev))
            if fn is None:
                # The first miss of a size compiles EVERY row bucket
                # its batches can come to, not this batch's alone:
                # the acting OSDs' scans of one PG scrub run side by
                # side and share a dispatch when timing has it, so
                # which bucket a dispatch pads to (one scan's rows, or
                # several scans' up to the channel's cap) is not known
                # from the first.  A bucket first met after the
                # warm-ups were waited out would compile while serving.
                chan = _crc_channels.get(size)
                todo = []
                for n in _crc_row_buckets(
                        shape[0], chan.max_coalesce if chan else None):
                    key = (size, (n,) + shape[1:], dev)
                    # negative-cache warm failures (TpuBackend does
                    # the same): re-warming every dispatch would churn
                    # a thread + a failing compile per batch
                    if key not in _crc_fns and key not in _crc_warming \
                            and key not in _crc_warm_failed:
                        _crc_warming.add(key)
                        todo.append(key[1])
                if todo:
                    # ONE thread, a program after another: they are
                    # one jitted function, and every lane's device
                    # misses for itself
                    start_warm_thread(
                        lambda: [_warm_crc(size, s, device)
                                 for s in todo],
                        "ec-crc-warm")
                return None
        return (fn(padded),)

    return device_fn


def _warm_crc(size: int, shape: tuple, device=None) -> None:
    from . import ec_kernels
    key = (size, shape, _device_warm_key(device))
    fn = None
    try:
        fn = ec_kernels.make_crc_fn(size)
        probe = np.zeros(shape, dtype=np.uint8)
        if device is not None:
            import jax
            probe = jax.device_put(probe, device)
        np.asarray(fn(probe))
    except Exception as e:
        fn = None   # negative-cached below; host path keeps serving
        note_warm_failure(f"crc {shape}", e)
    finally:
        with _crc_lock:
            _crc_warming.discard(key)
            if fn is not None:
                if len(_crc_fns) > 256:
                    _crc_fns.clear()
                    _crc_ready.clear()
                _crc_fns[key] = fn
                _crc_ready.add(key)
            else:
                _crc_warm_failed.add(key)


def crc_channel(size: int,
                max_coalesce: int | None = None) -> PipelineChannel:
    """Shared channel computing CRC32C(seed 0) per row of (B, size)
    batches; future outputs are ((B,) uint32,).  `max_coalesce`
    bounds stripes per dispatch (the scrubber passes its
    osd_deep_scrub_stripe_batch so coalescing cannot exceed the
    operator's per-dispatch device-memory cap).  Scrub-class QoS:
    these channels yield dispatch slots to client-write encodes under
    contention (osd_ec_pipeline_scrub_weight)."""
    with _crc_lock:
        chan = _crc_channels.get(size)
        if chan is None:
            from . import crc32c as crc_mod
            from ..utils import faults as faults_mod

            def host_fn(batch):
                return (crc_mod.crc32c_batch(batch),)

            def route(nbytes):
                return not _crc_device_dead and \
                    not faults_mod.get().tpu_error()

            chan = PipelineChannel(
                key=("crc", size), host_fn=host_fn,
                device_fn=_crc_device_fn(size), route=route,
                on_error=_crc_on_error, max_coalesce=max_coalesce,
                qos_class="scrub")
            _crc_channels[size] = chan
        elif max_coalesce is not None:
            # several daemons share this in-process registry: honor
            # the STRICTEST per-dispatch cap any of them configured
            chan.max_coalesce = max_coalesce if chan.max_coalesce \
                is None else min(chan.max_coalesce, max_coalesce)
        return chan
