"""The `tpu` erasure-code plugin — the framework's north-star backend.

Replaces the reference's SIMD plugin pile (isa x86 asm, jerasure
per-arch flavors, /root/reference/src/erasure-code/isa/,
jerasure/CMakeLists.txt:94-97) with ONE backend: every matrix technique
becomes a batched GF(2) matmul on the TPU MXU (ceph_tpu.ops.ec_kernels).

Profile keys beyond the standard k/m/w/technique/packetsize:
  c=N                   technique=shec_multiple|shec_single only: the
                        lost chunks the shingled code survives
                        (0 < c <= m <= k; ErasureCodeShec's `c`)
  l=N                   technique=lrc only: the reference's k/m/l form
                        (ErasureCodeLrc::parse_kml): (k+m)/l local
                        groups of l chunks and a local parity each, so
                        k + m + (k+m)/l chunks an object, at the shard
                        positions of its mapping (k=4 m=2 l=3:
                        `DD__DD__`); `mapping` + `layers` in its place
                        name the layers outright
  batch_stripes=N       coalesce-size hint for the shared device
                        pipeline: at most N stripes fuse into one
                        dispatch for this codec's channels (validated
                        in init(); default: the pipeline's global cap)

Extras over the host plugins:
  * encode_batch / decode_batch: (B, k, L) stripe batches in one
    dispatch — what ECBackend/deep-scrub feed (SURVEY §5.7: stripes are
    embarrassingly parallel, the TPU analog of "sequence parallelism");
  * encode_with_crcs: fused encode + per-chunk CRC32C scrub checksums,
    chunks cross host<->device once (the BASELINE.json north star);
  * encode_stripes_with_crcs(_async) / decode_batch_async: routed
    through the shared cross-op pipeline (ceph_tpu.ops.pipeline) —
    concurrent producers coalesce into shape-bucketed mega-batches
    and overlapped dispatches amortize the device round-trip.
"""

from __future__ import annotations

import threading
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np

from ..ops import crc32c as crc_mod
from ..ops import ec_kernels
from ..ops import pipeline as ec_pipeline
from ..utils import faults
from ..utils.dout import DoutLogger
from .interface import ErasureCodeError
from .matrix_codec import (REP_BYTES, TECHNIQUES, MatrixErasureCode,
                           NumpyBackend, TpuBackend, raw_bitmatrix)
from .registry import ErasureCodePlugin


class _Done:
    """Already-computed result behind the async-handle interface."""

    __slots__ = ("_v",)

    def __init__(self, value):
        self._v = value

    def result(self, timeout=None):
        return self._v


def _with_rep(fut, rep: str, rows: int) -> dict | None:
    """The pipeline's phase stamps with what the codec knows of the
    dispatch: the chunk representation it computed in and the rows of
    its matrix (parity rows of an encode, rebuilt rows of a decode)."""
    ph = getattr(fut, "trace_phases", None)
    return None if ph is None else dict(ph, rep=rep, rows=rows)


class _PipelinedEncode:
    """Future for one encode_stripes_with_crcs submission: resolves to
    ((S, k+m, L) chunks, (S, k+m) crcs) and bumps the codec's
    host/device pass counters by the path the batch actually took.

    Liveness: if the pipeline does not resolve within RESULT_TIMEOUT
    (a wedged device fetch hangs without raising), the caller
    self-serves on the host path — encode is a pure function of the
    stripes still held here, and a late pipeline resolution is
    discarded by the future's done() guard."""

    __slots__ = ("_codec", "_stripes", "_fut")

    def __init__(self, codec, stripes, fut):
        self._codec = codec
        self._stripes = stripes
        self._fut = fut

    @property
    def trace_phases(self) -> dict | None:
        """Pipeline phase stamps for the op tracer (attached to the
        raw future at resolve; None while unresolved / on the
        self-serve host fallback), with the chunk representation the
        dispatch computed in."""
        return _with_rep(self._fut, self._codec.rep,
                         len(self._codec.coding_matrix))

    def result_parts(self, timeout=None):
        """(stripes, parity, crcs) WITHOUT materializing the joined
        (S, k+m, L) array — the shard fan-out (ecutil.EncodeHandle)
        lays shards out straight from the parts, so the concat copy
        result() pays for API compatibility never happens on the
        write path."""
        if timeout is None:
            timeout = ec_pipeline.RESULT_TIMEOUT
        try:
            path, (parity, crcs) = self._fut.result(timeout)
        except FuturesTimeout:
            ec_pipeline.get().note_result_timeout()
            chan = self._codec._encode_channel(self._stripes.shape[2])
            parity, crcs = chan.host_fn(self._stripes)
            path = "host"
        key = ("device_stripe_passes" if path == "dev"
               else "host_stripe_passes")
        self._codec.stat_counters()[key] += 1
        return (self._stripes, np.asarray(parity),
                np.asarray(crcs, dtype=np.uint32))

    def result(self, timeout=None):
        stripes, parity, crcs = self.result_parts(timeout)
        return np.concatenate([stripes, parity], axis=1), crcs


class _PipelinedDecode:
    __slots__ = ("_fut", "_host", "_rep", "_rows")

    def __init__(self, fut, host, rep, rows):
        self._fut = fut
        self._host = host
        self._rep = rep
        self._rows = rows

    @property
    def trace_phases(self) -> dict | None:
        """The pipeline's per-item phase stamps (set at resolve) —
        decode-path op spans (recovery rebuild device time)."""
        return _with_rep(self._fut, self._rep, self._rows)

    def result(self, timeout=None):
        if timeout is None:
            timeout = ec_pipeline.RESULT_TIMEOUT
        try:
            _path, (out,) = self._fut.result(timeout)
        except FuturesTimeout:
            # wedged pipeline: host self-serve
            ec_pipeline.get().note_result_timeout()
            out = self._host()
        return np.asarray(out)


class ErasureCodeTpu(MatrixErasureCode):
    DEFAULT_K = 8
    DEFAULT_M = 3

    def __init__(self):
        super().__init__(backend=TpuBackend(), techniques=dict(TECHNIQUES))
        # device-failure degrade: a dead/erroring TPU swaps the backend
        # for the pure host matrix-codec path (same matrices, same
        # bytes) and raises a health warning — NEVER an op error.
        # Sticky until the daemon restarts, like a failed NIC offload.
        self.degraded = False
        self.degrade_reason = ""
        self.batch_stripes: int | None = None
        # op workers, scrub and recovery threads all share one cached
        # codec: channel-cache access must be locked (the eviction
        # sweep iterates while others insert)
        self._channels: dict[tuple, ec_pipeline.PipelineChannel] = {}
        self._chan_lock = threading.Lock()

    def init(self, profile):
        self.backend = TpuBackend()
        if "host_cutover" in profile:
            self.backend.HOST_CUTOVER_BYTES = int(profile["host_cutover"])
        if "batch_stripes" in profile:
            n = self.profile_int(profile, "batch_stripes", 0)
            if n < 1:
                raise ErasureCodeError(
                    f"batch_stripes={profile['batch_stripes']!r} "
                    "must be an integer >= 1")
            self.batch_stripes = n
        else:
            self.batch_stripes = None
        self.degraded = False
        self.degrade_reason = ""
        self._channels = {}     # matrices/geometry change under us
        super().init(profile)

    # -- device-failure degrade --------------------------------------------

    def _degrade(self, reason: str) -> None:
        if self.degraded:
            return
        self.degraded = True
        self.degrade_reason = reason
        self.backend = NumpyBackend()   # the pure matrix_codec path
        self._fast1 = self._build_fast1()   # size cap was device-tied
        self.stat_counters()["device_degraded"] = 1
        DoutLogger("erasure", "tpu").warn(
            "TPU device error (%s): degrading to matrix-codec host "
            "path", reason)
        from .registry import registry as _registry
        _registry.note_degraded("tpu", reason)

    def _apply(self, matrix: np.ndarray, chunks: np.ndarray,
               backend=None) -> np.ndarray:
        if backend is not None:
            return super()._apply(matrix, chunks, backend)
        if not self.degraded:
            if faults.get().tpu_error():
                self._degrade("injected device error")
            else:
                try:
                    return super()._apply(matrix, chunks)
                except ErasureCodeError:
                    raise       # geometry/validation — not the device
                except Exception as e:
                    self._degrade(f"{type(e).__name__}: {e}")
        return super()._apply(matrix, chunks)

    # -- shared-pipeline channels ------------------------------------------
    #
    # One channel per (kind, chunk length), whatever the technique's
    # chunk representation (byte symbols, cauchy's packets, a
    # liberation-family bit-matrix): items from every producer
    # concatenate into mega-batches; the channel's callbacks carry the
    # degrade guard (route), the warm-gated per-device jitted fn
    # (device_fn — the pipeline passes the lane's device and readiness
    # is per chip), the bit-identical host fallback (host_fn), the
    # measured-routing EMA feed (record), and on_error — which the
    # multichip pipeline fires only once EVERY device lane is
    # quarantined (single-chip failures quarantine one lane and
    # redrain to the survivors without degrading this codec).

    def _route(self, nbytes: int) -> bool:
        if self.degraded:
            return False
        if faults.get().tpu_error():
            self._degrade("injected device error")
            return False
        b = self.backend
        return isinstance(b, TpuBackend) and b.use_device(nbytes)

    def _on_device_error(self, e: Exception) -> None:
        self._degrade(f"{type(e).__name__}: {e}")

    def _record(self, path: str, nbytes: int, secs: float,
                depth: int = 1, device=None) -> None:
        b = self.backend
        if isinstance(b, TpuBackend):
            b.record(path, nbytes, secs, depth, device=device)

    def _host_backend(self):
        return getattr(self.backend, "_host", self.backend)

    def _encode_channel(self, L: int) -> ec_pipeline.PipelineChannel:
        with self._chan_lock:
            chan = self._channels.get(("enc", L))
        if chan is not None:
            return chan
        matrix = self.coding_matrix

        def host_fn(batch):
            # CRCs fold over the data and parity shards AS VIEWS — the
            # old concat materialized a full (B, k+m, L) copy just to
            # hand crc32c_batch one contiguous array, which on a slow-
            # memory rig cost more than the encode itself
            parity = np.asarray(
                self._apply(matrix, batch, self._host_backend()))
            B, k, CL = batch.shape
            pm = parity.shape[1]
            crcs = np.empty((B, k + pm), dtype=np.uint32)
            crcs[:, :k] = crc_mod.crc32c_batch(
                batch.reshape(B * k, CL)).reshape(B, k)
            crcs[:, k:] = crc_mod.crc32c_batch(
                parity.reshape(B * pm, CL)).reshape(B, pm)
            return parity, crcs

        def device_fn(padded, device=None):
            b = self.backend
            if self.degraded or not isinstance(b, TpuBackend):
                return None
            fn = b.fused_fn_if_ready(matrix, tuple(padded.shape),
                                     device)
            if fn is None:
                return None     # background warm-up; host serves
            return fn(padded)

        chan = ec_pipeline.PipelineChannel(
            key=("enc", id(self), L),
            host_fn=host_fn, device_fn=device_fn, route=self._route,
            on_error=self._on_device_error, record=self._record,
            max_coalesce=self.batch_stripes)
        with self._chan_lock:
            return self._channels.setdefault(("enc", L), chan)

    def _decode_channel(self, want: list[int], present: list[int],
                        rows: np.ndarray,
                        L: int) -> ec_pipeline.PipelineChannel:
        # id(self) in the key: the pipeline keys queues on chan.key,
        # and two codecs with identical decode geometry must NOT share
        # one — on_error/record callbacks are per-codec (a shared
        # queue would degrade/credit the last submitter's codec only).
        # The key is the SEMANTIC decode pattern (want, present): rows
        # is a pure function of it for a given codec, so hashing the
        # matrix bytes (the old rows.tobytes() key) bought nothing and
        # copied the whole matrix on every decode call.
        key = ("dec", id(self), tuple(want), tuple(present), L)
        with self._chan_lock:
            chan = self._channels.get(key)
        if chan is not None:
            return chan

        def host_fn(batch):
            return (np.asarray(
                self._apply(rows, batch, self._host_backend())),)

        def device_fn(padded, device=None):
            b = self.backend
            if self.degraded or not isinstance(b, TpuBackend):
                return None
            # "bytes": the apply of the representation `b` serves
            fn = b.device_fn_if_ready(REP_BYTES, rows, (),
                                      tuple(padded.shape), device)
            if fn is None:
                return None
            return (fn(padded),)

        chan = ec_pipeline.PipelineChannel(
            key=key, host_fn=host_fn, device_fn=device_fn,
            route=self._route, on_error=self._on_device_error,
            record=self._record, max_coalesce=self.batch_stripes)
        with self._chan_lock:
            if len(self._channels) > 128:
                # bound the decode-pattern set only — the hot encode
                # channels must survive an eviction sweep
                for k in [k for k in self._channels
                          if k[0] == "dec"]:
                    del self._channels[k]
            return self._channels.setdefault(key, chan)

    # -- batched stripe API (device-native entry points) -------------------

    def encode_stripes_with_crcs_async(self, stripes, cache=None,
                                       qos=None):
        """Submit an (S, k, L) stripe batch to the shared pipeline.

        Returns a handle whose .result() yields ((S, k+m, L) chunks,
        (S, k+m) uint32 crcs) — identical to encode_stripes_with_crcs.
        The op thread is free to journal metadata while the batch
        coalesces with other producers' stripes and rides an
        overlapped device dispatch (or the host drain when degraded).

        `cache` (an ops.hbm_cache.CacheIntent) asks the transfer
        plane to keep this batch's device-resident stripes in the HBM
        cache when the dispatch lands on a chip; the producer commits
        the entry once the shard bytes are on disk.

        `qos` names the service class (pool) the dispatch-lane picker
        schedules this batch under (ops.pipeline.configure_qos).
        """
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        if stripes.ndim != 3 or stripes.shape[1] != self.k:
            raise ErasureCodeError(f"want (S, {self.k}, L), "
                                   f"got {stripes.shape}")
        chan = self._encode_channel(stripes.shape[2])
        fut = ec_pipeline.get().submit(chan, stripes, cache=cache,
                                       qos=qos)
        return _PipelinedEncode(self, stripes, fut)

    def encode_stripes_with_crcs(self, stripes) -> tuple:
        return self.encode_stripes_with_crcs_async(stripes).result()

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """(B, k, L) uint8 -> (B, m, L) parity in one device dispatch."""
        data = np.asarray(data, dtype=np.uint8)
        if data.ndim != 3 or data.shape[1] != self.k:
            raise ErasureCodeError(f"want (B, {self.k}, L), got {data.shape}")
        return self._apply(self.coding_matrix, data)

    def decode_batch(self, want: list[int], present: list[int],
                     chunks: np.ndarray) -> np.ndarray:
        """chunks: (B, len(present), L) surviving chunks -> (B, len(want), L)."""
        return self.decode_batch_async(want, present, chunks).result()

    def decode_batch_async(self, want: list[int], present: list[int],
                           chunks: np.ndarray, qos: str | None = None):
        """Pipeline-coalesced shard rebuild: concurrent recovery ops
        reconstructing with the same decode pattern share a dispatch.
        `qos` names the dmClock class the decode lane bills against
        (rebuild decodes ride @recovery)."""
        want, present = list(want), list(present)
        rows = self._decode_rows(want, present)
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        if chunks.ndim != 3 or rows.shape[0] == 0:
            return _Done(self._apply(rows, chunks))
        short = self.k - len(present)
        if short > 0 and self.rep == REP_BYTES:
            # a plan that reads fewer than k chunks (shec's local
            # repair) rides the (r x k) operand and the (B, k, L)
            # stack every other decode uses: zero columns, zero-filled
            # chunks.  No executable of its own to compile, every
            # decode of one row count shares a dispatch shape, and the
            # kernel's work stays 2*8k*8r*L a stripe.  (Planned
            # techniques are byte codes; a packet code's plan is any k.)
            rows = np.pad(rows, ((0, 0), (0, short)))
            chunks = np.pad(chunks, ((0, 0), (0, short), (0, 0)))
        chan = self._decode_channel(want, present, rows,
                                    chunks.shape[2])
        return _PipelinedDecode(
            ec_pipeline.get().submit(chan, chunks, qos=qos),
            lambda: chan.host_fn(chunks)[0], self.rep, len(rows))

    def encode_with_crcs(self, data: np.ndarray):
        """(B, k, L) -> (parity (B, m, L), crcs (B, k+m) uint32), fused.

        CRCs are CRC32C(seed 0) of each chunk; combine with a running
        object CRC via ceph_tpu.ops.crc32c.crc32c_combine on the host.
        """
        data = np.asarray(data, dtype=np.uint8)
        B, k, L = data.shape
        if not self.degraded and faults.get().tpu_error():
            self._degrade("injected device error")
        if not self.degraded:
            try:
                if self.rep == REP_BYTES:
                    fn = ec_kernels.make_encode_crc_fn(
                        self.coding_matrix, L)
                else:
                    fn = ec_kernels.make_packet_encode_crc_fn(
                        raw_bitmatrix(self.rep, self.coding_matrix,
                                      self.w),
                        self.w, self.packetsize, L)
                parity, crcs = fn(data)
                return np.asarray(parity), np.asarray(crcs)
            except Exception as e:
                self._degrade(f"{type(e).__name__}: {e}")
        # host fallback: plain matmul + batched table CRCs, same bytes
        parity = np.asarray(self._apply(self.coding_matrix, data))
        allc = np.ascontiguousarray(
            np.concatenate([data, parity], axis=1))
        km = allc.shape[1]
        crcs = crc_mod.crc32c_batch(
            allc.reshape(B * km, L)).reshape(B, km)
        return parity, crcs


class ErasureCodeTpuPlugin(ErasureCodePlugin):
    def factory(self, profile):
        return ErasureCodeTpu()


def __erasure_code_init__(registry, name):
    registry.add(name, ErasureCodeTpuPlugin())
