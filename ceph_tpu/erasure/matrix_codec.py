"""Shared machinery for matrix-based erasure codes (RS / Cauchy families).

The jerasure, isa and tpu plugins all reduce to: build an (m x k) coding
matrix over GF(2^8) for a named technique, encode as matrix x data, decode
by inverting the surviving generator rows.  The shec techniques are the
same with a coding matrix that is not MDS: which chunks decode, and by
which rows, comes from a plan (`MatrixErasureCode._plan`) and not from
"any k".  The lrc technique is a layered code composed to one such
matrix, planned layer by layer, whose chunks lie at the shard positions
its mapping string gives.  This module holds the technique table, the
decode-matrix planner + cache, and two compute backends over the same
representation:

  * NumpyBackend — exact host reference (the correctness oracle, analog
    of the reference's gf-complete scalar path);
  * TpuBackend — batched GF(2) matmuls on the MXU via
    ceph_tpu.ops.ec_kernels (the north-star device path).

Two chunk representations, matching the reference's two code families
(/root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.h:91-259):

  * "bytes"   — chunk byte i is a GF(2^8) symbol (reed_sol_van,
                reed_sol_r6_op, isa techniques);
  * "packets" — jerasure bitmatrix layout: chunk = super-blocks of w
                packets of `packetsize` bytes, XOR schedule over packets
                (cauchy_orig, cauchy_good).  Chunk bytes are bit-identical
                to the reference technique's packetized output.
"""

from __future__ import annotations

import itertools
import json
import math
from typing import Mapping, NamedTuple, Sequence

import numpy as np

from ..ops import gf
from .interface import CHUNK_ALIGN, ErasureCode, ErasureCodeError

REP_BYTES = "bytes"
REP_PACKETS = "packets"
REP_BITS = "bits"        # native GF(2) bit-matrix (liberation family)


# ---------------------------------------------------------------------------
# Technique table: name -> (matrix builder, representation)
# ---------------------------------------------------------------------------

def _rs_van(k, m, w, packetsize):
    return gf.reed_sol_van_matrix(k, m)


def _rs_r6(k, m, w, packetsize):
    if m != 2:
        raise ErasureCodeError("reed_sol_r6_op requires m=2")
    return gf.reed_sol_r6_matrix(k)


def _cauchy_orig(k, m, w, packetsize):
    return gf.cauchy_orig_matrix(k, m)


def _cauchy_good(k, m, w, packetsize):
    return gf.cauchy_good_matrix(k, m)


def _isa_rs(k, m, w, packetsize):
    return gf.isa_rs_matrix(k, m)


def _isa_cauchy(k, m, w, packetsize):
    return gf.isa_cauchy_matrix(k, m)


def _liberation(k, m, w, packetsize):
    if m != 2:
        raise ErasureCodeError("liberation requires m=2")
    try:
        return gf.liberation_bitmatrix(k, w)
    except ValueError as e:
        raise ErasureCodeError(str(e))


def _blaum_roth(k, m, w, packetsize):
    if m != 2:
        raise ErasureCodeError("blaum_roth requires m=2")
    try:
        return gf.blaum_roth_bitmatrix(k, w)
    except ValueError as e:
        raise ErasureCodeError(str(e))


def _liber8tion(k, m, w, packetsize):
    if m != 2:
        raise ErasureCodeError("liber8tion requires m=2")
    if w != 8:
        raise ErasureCodeError("liber8tion requires w=8")
    try:
        return gf.liber8tion_bitmatrix(k)
    except ValueError as e:
        raise ErasureCodeError(str(e))


def _shec(single: bool):
    def build(k, m, w, packetsize, c):
        if not 0 < c <= m <= k:
            raise ErasureCodeError(
                f"require 0 < c <= m <= k, got k={k} m={m} c={c}")
        try:
            return gf.shec_matrix(k, m, c, single)
        except ValueError as e:
            raise ErasureCodeError(str(e))
    return build


class Layered(NamedTuple):
    """A layered code as one generator: the (n-k x k) coding matrix the
    layers compose to, the shard position of every chunk id, and each
    layer in chunk ids (its chunks, inputs first; how many are inputs;
    its own systematic generator)."""
    matrix: np.ndarray
    mapping: list[int]
    layers: list[tuple[tuple[int, ...], int, np.ndarray]]


def _layer_profile(text: str) -> dict[str, str]:
    out: dict[str, str] = {}
    for tok in text.split():
        if "=" not in tok:
            raise ErasureCodeError(f"bad layer profile token {tok!r}")
        key, val = tok.split("=", 1)
        out[key] = val
    return out


def lrc_layout(profile: Mapping[str, str]) -> tuple[str, list]:
    """(mapping string, [(layer mapping, layer profile)]) of an lrc
    profile: its own `mapping` + `layers`, or the k/m/l form, one
    global layer and (k+m)/l local ones, as the reference generates
    them (ErasureCodeLrc::parse_kml)."""
    kml = [ErasureCode.profile_int(profile, x, -1) for x in "kml"]
    if any(v != -1 for v in kml):
        if "layers" in profile or "mapping" in profile:
            raise ErasureCodeError(
                "layers/mapping cannot be combined with k/m/l")
        k, m, l = kml
        if -1 in kml:
            raise ErasureCodeError("all of k, m, l must be set")
        if l < 1 or (k + m) % l:
            raise ErasureCodeError("k + m must be a multiple of l")
        groups = (k + m) // l
        if k % groups or m % groups:
            raise ErasureCodeError("k and m must be multiples of (k+m)/l")
        kg, mg = k // groups, m // groups
        mapping = ("D" * kg + "_" * mg + "_") * groups
        desc = [[("D" * kg + "c" * mg + "_") * groups, ""]]
        desc += [["_" * (l + 1) * i + "D" * l + "c"
                  + "_" * (l + 1) * (groups - 1 - i), ""]
                 for i in range(groups)]
    else:
        if "mapping" not in profile or "layers" not in profile:
            raise ErasureCodeError(
                "lrc requires mapping + layers (or k/m/l)")
        mapping = profile["mapping"]
        try:
            desc = json.loads(profile["layers"])
        except json.JSONDecodeError as e:
            raise ErasureCodeError(f"layers is not valid JSON: {e}") from e
        if not isinstance(desc, list) or not desc:
            raise ErasureCodeError("layers must be a non-empty JSON list")
    layers = []
    for entry in desc:
        if not isinstance(entry, list) or len(entry) < 1:
            raise ErasureCodeError(f"bad layer entry {entry!r}")
        if len(entry[0]) != len(mapping):
            raise ErasureCodeError(
                f"layer mapping {entry[0]!r} length != {len(mapping)}")
        layers.append((entry[0], _layer_profile(
            entry[1] if len(entry) > 1 else "")))
    produced = {i for lmap, _p in layers
                for i, ch in enumerate(lmap) if ch == "c"}
    missing = [i for i, ch in enumerate(mapping)
               if ch != "D" and i not in produced]
    if missing:
        raise ErasureCodeError(
            f"mapping positions {missing} produced by no layer")
    return mapping, layers


def chunk_mapping(mapping: str) -> list[int]:
    """Shard position of every chunk id (ErasureCode::to_mapping): data
    chunk i lies at the i-th 'D', the coding chunks at the other
    positions in order."""
    return ([i for i, ch in enumerate(mapping) if ch == "D"]
            + [i for i, ch in enumerate(mapping) if ch != "D"])


def _lrc(profile: Mapping[str, str]) -> Layered:
    """The layers flattened into ONE (n-k x k) coding matrix over
    GF(2^8): the layered code is linear, so every coding position is a
    fixed combination of the k data chunks.  Walking the layers in
    order, each position has its row over the data chunks (a 'D' of
    the mapping a unit vector); a layer's coding rows are its own
    matrix times the rows of its inputs, so a local layer over a
    global parity composes too.  Only byte-matrix layers compose: a
    packet technique's matrix means an XOR schedule, and a plugin
    with a decoder of its own keeps the layered host path
    (erasure/plugin_lrc.py)."""
    mapping, desc = lrc_layout(profile)
    pos_of = chunk_mapping(mapping)
    chunk_at = {p: c for c, p in enumerate(pos_of)}
    k = mapping.count("D")
    if not 0 < k < len(mapping):
        raise ErasureCodeError(
            f"mapping {mapping!r} needs data and coding positions")
    rows = {p: np.eye(k, dtype=np.uint8)[c]
            for c, p in enumerate(pos_of[:k])}
    layers = []
    for lmap, lprofile in desc:
        ins = [i for i, ch in enumerate(lmap) if ch == "D"]
        outs = [i for i, ch in enumerate(lmap) if ch == "c"]
        tech = TECHNIQUES.get(lprofile.get("technique", "reed_sol_van"))
        if lprofile.get("plugin", "jerasure") not in ("jerasure", "tpu") \
                or tech is None or tech[1:] != (REP_BYTES,) \
                or set(lprofile) - {"plugin", "technique", "backend"}:
            raise ErasureCodeError(
                f"layer {lmap!r} {lprofile} is not a byte matrix")
        if not outs:
            continue
        if any(p not in rows for p in ins):
            raise ErasureCodeError(
                f"layer {lmap!r} reads a position no earlier layer wrote")
        cm = np.asarray(tech[0](len(ins), len(outs), 8, 0), dtype=np.uint8)
        made = gf.gf_matmul(cm, np.stack([rows[p] for p in ins]))
        rows.update(zip(outs, made))
        layers.append((tuple(chunk_at[p] for p in ins + outs), len(ins),
                       gf.systematic_generator(cm, len(ins))))
    return Layered(np.stack([rows[p] for p in pos_of[k:]]), pos_of, layers)


TECHNIQUES: dict[str, tuple] = {
    "reed_sol_van": (_rs_van, REP_BYTES),
    "reed_sol_r6_op": (_rs_r6, REP_BYTES),
    "cauchy_orig": (_cauchy_orig, REP_PACKETS),
    "cauchy_good": (_cauchy_good, REP_PACKETS),
    # minimal-density RAID-6 bit-matrix family
    # (ErasureCodeJerasure.h:176-259)
    "liberation": (_liberation, REP_BITS),
    "blaum_roth": (_blaum_roth, REP_BITS),
    "liber8tion": (_liber8tion, REP_BITS),
    # ISA-L matrix semantics exposed as techniques of the tpu plugin
    "isa_reed_sol_van": (_isa_rs, REP_BYTES),
    "isa_cauchy": (_isa_cauchy, REP_BYTES),
    # SHEC (ErasureCodeShec.cc) exposed the same way: reed_sol_van rows
    # zeroed by shingle windows, durability `c`; a third entry marks a
    # matrix that is not MDS, whose builder takes `c` and whose decode
    # goes by plan
    "shec_multiple": (_shec(False), REP_BYTES, "planned"),
    "shec_single": (_shec(True), REP_BYTES, "planned"),
    # LRC (ErasureCodeLrc.cc): layers composed to one matrix; the
    # builder takes the profile (k/m/l, or mapping + layers), the plan
    # goes layer by layer, and the chunks lie where the mapping says
    "lrc": (_lrc, REP_BYTES, "planned", "layered"),
}

# techniques whose natural word size is not 8
TECH_DEFAULT_W = {"liberation": 7, "blaum_roth": 6, "liber8tion": 8}


def raw_bitmatrix(rep: str, matrix: np.ndarray, w: int) -> np.ndarray:
    """The GF(2) matrix a packet-layout code XORs packets by: a
    liberation-family matrix as it is, a cauchy matrix expanded."""
    return matrix if rep == REP_BITS else gf.expand_bitmatrix(matrix, w)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


class NumpyBackend:
    """Exact host math (native C++ region kernels when built, numpy
    otherwise); used by the jerasure/isa oracle plugins."""

    def apply_bytes(self, matrix: np.ndarray, chunks: np.ndarray) -> np.ndarray:
        from .. import native
        if chunks.ndim == 2:
            out = native.gf_encode(matrix, chunks)
            if out is not None:
                return out
            return gf.encode_np(matrix, chunks)
        out = native.gf_encode_batch(matrix, chunks)
        if out is not None:
            return out
        return np.stack([gf.encode_np(matrix, c) for c in chunks])

    def apply_packets(self, matrix: np.ndarray, chunks: np.ndarray,
                      w: int, packetsize: int) -> np.ndarray:
        return self.apply_bits(gf.expand_bitmatrix(matrix, w), chunks,
                               w, packetsize)

    def apply_bits(self, bits: np.ndarray, chunks: np.ndarray,
                   w: int, packetsize: int) -> np.ndarray:
        from .. import native

        def one(c):
            out = native.bitmatrix_encode(bits, c, w, packetsize)
            if out is None:
                out = gf.bitmatrix_encode_np(bits, c, w, packetsize)
            return out

        if chunks.ndim == 3:
            return np.stack([one(c) for c in chunks])
        return one(chunks)


class TpuBackend:
    """Batched device matmuls; one jitted fn per (matrix, shape) cached.

    The callable cache avoids re-expanding the GF(2^8) matrix to bits on
    every call — that host-side work would dominate small-chunk ops.

    A backend serves ONE codec, whose chunk representation (`rep`,
    with `w` and `packetsize` for the packet layouts) it is told at the
    codec's init (:meth:`serve`): the readiness predicates answer for
    that representation, so a caller names a matrix, a shape and a
    device and gets the program the pool's technique needs.

    Host/device routing is MEASURED, not hardcoded: per size bucket
    (power of two of payload bytes) the backend keeps an EMA of observed
    seconds-per-byte for each path, routes to the faster one, and
    occasionally re-probes the loser so the decision tracks reality
    (different chip, CPU-only CI).  A profile can still pin a fixed
    threshold via host_cutover (HOST_CUTOVER_BYTES).
    """

    # fixed-threshold fallback when measurement is disabled by profile
    HOST_CUTOVER_BYTES: int | None = None
    # never dispatch tiny payloads: a device round-trip is >= tens of
    # microseconds while the native host kernel finishes a 4KiB-class
    # stripe in ~1.5us — and even the periodic re-probe of the losing
    # path would dominate at these sizes
    MIN_DEVICE_BYTES = 1 << 16
    PROBE_EVERY = 64

    def __init__(self):
        import threading
        from ..ops import ec_kernels
        self._ek = ec_kernels
        self.rep, self.w, self.packetsize = REP_BYTES, 8, 0
        self._fns: dict[tuple, object] = {}
        self._host = NumpyBackend()
        # (path, bucket) -> {"spb": ema sec/byte, "n": samples}
        self._perf: dict[tuple[str, int], dict] = {}
        # (bucket, lane index) -> per-chip service-time EMA (fed by
        # the pipeline's collect path; cost-aware placement signal)
        self._dev_perf: dict[tuple[int, int], dict] = {}
        self._calls = 0
        # jit is shape-specialized: a (fn, shape) pair is servable only
        # after its compile finished.  Compiles run on a background
        # thread so an OSD op never blocks on a first-shape compile —
        # until ready the call is served by the host kernels.
        self._ready: set = set()
        self._warming: set = set()
        self._warm_failed: set = set()
        self._warm_lock = threading.Lock()
        self._fn_lock = threading.Lock()

    def serve(self, rep: str, w: int, packetsize: int) -> None:
        """The chunk representation of the codec this backend serves."""
        self.rep, self.w, self.packetsize = rep, w, packetsize

    def _fn(self, kind: str, matrix: np.ndarray, *extra):
        key = (kind, matrix.tobytes(), matrix.shape, *extra)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        # one fn object a key: the warm threads of two shapes start
        # together, and were each to build its own, the last one kept
        # would be compiled for its own shape alone while `_ready`
        # vouches for both (the other then compiles in the thread that
        # serves it: a coalesced batch's first dispatch, seconds into
        # a window)
        with self._fn_lock:
            return self._fn_locked(key, kind, matrix, *extra)

    def _fn_locked(self, key: tuple, kind: str, matrix: np.ndarray,
                   *extra):
        fn = self._fns.get(key)
        if fn is None:
            if kind == "bytes":
                fn = self._ek.make_codec_fn(matrix, 8)
            elif kind == "fused":
                fn = self._make_fused(matrix, *extra)
            elif kind == "bits":
                w, packetsize = extra
                fn = self._ek.make_bits_codec_fn(matrix, w, packetsize)
            else:
                w, packetsize = extra
                fn = self._ek.make_packet_codec_fn(matrix, w, packetsize)
            if len(self._fns) > 256:
                # decode patterns first: an apply closure is cheap to
                # build again and its readiness hangs on the matrix
                # SHAPE, not on this entry, so dropping them strands
                # nothing (a degraded pool sees hundreds of patterns)
                for old in list(self._fns):
                    if old[0] != "fused":
                        self._fns.pop(old, None)
            if len(self._fns) > 256:
                # readiness is keyed on the fn cache: evicting one
                # without the other would strand "ready" shapes whose
                # fn is gone (device path permanently dead)
                self._fns.clear()
                self._ready.clear()
                with self._warm_lock:
                    self._warming.clear()
                    self._warm_failed.clear()
            self._fns[key] = fn
        return fn

    def _make_fused(self, matrix: np.ndarray, length: int,
                    rep: str = REP_BYTES, w: int = 8, packetsize: int = 0):
        """Fused encode+CRC kernel.  On a TPU the hand-tiled pallas
        kernel IS the kernel: a failure to build or compile it reaches
        the warm thread, which logs and counts it (warm_failures) and
        leaves the shape on the host path.  Pallas TPU kernels don't
        run on the CPU backend, so the tests' platform serves the XLA
        formulation.  A packet-layout code (cauchy's expanded matrix, a
        liberation-family bit-matrix as it is) has one program for
        both: XORs of whole packets, and the byte program's CRC fold."""
        import jax
        from ..ops import pallas_ec

        pallas = jax.devices()[0].platform == "tpu" and \
            pallas_ec.supports(length)
        if rep != REP_BYTES:
            return self._ek.make_packet_encode_crc_fn(
                raw_bitmatrix(rep, matrix, w), w, packetsize, length,
                crc=pallas_ec.make_crc_fn(length) if pallas else None)
        if pallas:
            return pallas_ec.make_encode_crc_fn(matrix, length)
        return self._ek.make_encode_crc_fn(matrix, length)

    # -- measured routing --------------------------------------------------

    @staticmethod
    def _bucket(nbytes: int) -> int:
        return max(12, (max(nbytes, 1) - 1).bit_length())

    def use_device(self, nbytes: int) -> bool:
        if self.HOST_CUTOVER_BYTES is not None:
            return nbytes >= self.HOST_CUTOVER_BYTES
        if nbytes < self.MIN_DEVICE_BYTES:
            return False
        self._calls += 1
        b = self._bucket(nbytes)
        host = self._perf.get(("host", b))
        dev = self._perf.get(("dev", b))
        if host is None:
            return False                  # host sample first (cheap)
        if dev is None or dev["n"] < 2:
            return True                   # warm + sample the device path
        if self._calls % self.PROBE_EVERY == 0:
            # re-probe the currently-losing path
            return host["spb"] < dev["spb"]
        return dev["spb"] <= host["spb"]

    def record(self, path: str, nbytes: int, seconds: float,
               depth: int = 1, device=None) -> None:
        """Feed one measured sample into the per-bucket EMA.

        `seconds` is the AMORTIZED cost the caller observed: the
        pipeline reports marginal service time for overlapped device
        dispatches (issue-to-fetch minus overlap with the previous
        fetch) over the coalesced batch's bytes, so a queue-depth-d
        stream scores ~1/d of the serial round-trip latency — the
        number that decides routing for batched producers.  `depth`
        (dispatches in flight when the sample landed) is tracked so
        the crossover report can say at what concurrency the device
        path won.

        `device` (the pipeline lane index the sample came from, when
        known) additionally maintains per-(shape bucket, chip) EMAs —
        the signal the pipeline's cost-aware placement consumes and
        perf dump exposes, so a chip running hot/slow is visible per
        shape instead of averaged into the fleet.
        """
        key = (path, self._bucket(nbytes))
        ent = self._perf.setdefault(key, {"spb": None, "n": 0,
                                          "depth": 1.0})
        ent["n"] += 1
        spb = seconds / max(nbytes, 1)
        ent["spb"] = spb if ent["spb"] is None else (
            0.7 * ent["spb"] + 0.3 * spb)
        ent["depth"] = 0.7 * ent.get("depth", 1.0) + 0.3 * float(depth)
        if device is not None and path == "dev":
            dkey = (self._bucket(nbytes), device)
            dent = self._dev_perf.setdefault(dkey, {"spb": None,
                                                    "n": 0})
            dent["n"] += 1
            dent["spb"] = spb if dent["spb"] is None else (
                0.7 * dent["spb"] + 0.3 * spb)

    def crossover_estimate(self) -> int | None:
        """Smallest measured payload bucket where the amortized device
        sec/byte beats the host EMA; None while the host wins every
        bucket both paths have samples for."""
        # snapshot first: pipeline threads record() concurrently with
        # admin-socket readers, and a python-level iteration over the
        # live dict would raise on a mid-loop insert
        perf = dict(self._perf)
        buckets = sorted({b for (_p, b) in perf})
        for b in buckets:
            h = perf.get(("host", b))
            d = perf.get(("dev", b))
            if h and d and h["spb"] is not None and \
                    d["spb"] is not None and d["spb"] <= h["spb"]:
                return 1 << b
        return None

    def perf_snapshot(self) -> dict:
        """Measured-routing EMAs keyed 'path:2^bucket', plus the
        per-chip view keyed 'dev@<lane>:2^bucket' (perf dump)."""
        out = {}
        for (path, b), ent in sorted(dict(self._perf).items()):
            spb = ent["spb"]
            if spb is not None:
                out[f"{path}:{1 << b}"] = {
                    "sec_per_byte": spb, "n": ent["n"],
                    "mean_depth": round(ent.get("depth", 1.0), 2)}
        for (b, dev), ent in sorted(dict(self._dev_perf).items()):
            if ent["spb"] is not None:
                out[f"dev@{dev}:{1 << b}"] = {
                    "sec_per_byte": ent["spb"], "n": ent["n"]}
        return out

    def device_fn_if_ready(self, kind: str, matrix: np.ndarray,
                           extra: tuple, shape: tuple, device=None):
        """The jitted fn for (kind, matrix, shape) if it is compiled,
        else None after kicking off a background warm-up.

        Building the fn ALSO stays off the caller's thread: closure
        construction materializes jnp constants, which triggers backend
        init — an OSD op must never pay that, so both construction and
        compile happen on the warm thread and the caller serves from
        host meanwhile.

        Readiness is tracked PER DEVICE: jit executables are
        device-specialized, so a shape warm on chip 0 still needs a
        (fast, lowering-shared) compile before chip 3 can serve it —
        the multichip pipeline probes each lane's readiness and the
        warm probe runs pinned to that device.

        For every kind but "fused" the matrix is an operand of the
        executable (ec_kernels._apply_fn, _packet_fn), so readiness is
        keyed on the matrix SHAPE: a decode pattern never seen before
        is served by the device at once when another pattern of its
        shape already warmed — only the cheap per-matrix closure is
        built here.

        `kind` "bytes" asks for the apply of the codec's OWN
        representation: on a backend that serves a packet-layout codec
        it is that codec's packet program (a caller that holds decode
        rows need not know which family made them).
        """
        from ..ops import pipeline as ec_pipeline
        if kind == REP_BYTES and self.rep != REP_BYTES:
            kind, extra = self.rep, (self.w, self.packetsize)
        fkey = (kind, matrix.tobytes(), matrix.shape, *extra)
        rkey = (fkey if kind == "fused" else (kind, matrix.shape, *extra),
                shape, ec_pipeline._device_warm_key(device))
        if rkey in self._ready:
            fn = self._fns.get(fkey)
            if fn is None and kind != "fused":
                fn = self._fn(kind, matrix, *extra)
            return fn
        with self._warm_lock:
            if rkey in self._warming or rkey in self._warm_failed:
                return None
            self._warming.add(rkey)

        def warm():
            ok = False
            try:
                fn = self._fn(kind, matrix, *extra)
                probe = np.zeros(shape, dtype=np.uint8)
                if device is not None:
                    import jax
                    probe = jax.device_put(probe, device)
                fn(probe)
                self._ready.add(rkey)
                ok = True
            except Exception as e:
                # negative-cache the failure: re-warming on every op
                # would churn a thread + a failing compile per EC
                # write, invisibly
                ec_pipeline.note_warm_failure(
                    f"{kind} {shape}", e)
            finally:
                with self._warm_lock:
                    self._warming.discard(rkey)
                    if not ok:
                        self._warm_failed.add(rkey)

        ec_pipeline.start_warm_thread(warm, "ec-jit-warm")
        return None

    def _timed(self, path: str, nbytes: int, fn) -> np.ndarray:
        import time as _time
        t0 = _time.perf_counter()
        out = fn()
        self.record(path, nbytes, _time.perf_counter() - t0)
        return out

    # -- transforms --------------------------------------------------------

    @staticmethod
    def pad_batch(chunks: np.ndarray) -> np.ndarray:
        """Pad a (S, ...) batch to a power-of-two S so device shapes
        repeat (jit is shape-specialized; a stable shape set compiles
        once per size bucket).  Host paths never pay this — callers pad
        only when dispatching to the device and slice the result."""
        from ..ops import pipeline as ec_pipeline
        return ec_pipeline.pad_batch(chunks)

    def apply_bytes(self, matrix: np.ndarray, chunks) -> np.ndarray:
        chunks = np.asarray(chunks, dtype=np.uint8)
        if chunks.nbytes < self.MIN_DEVICE_BYTES:
            # small-op fast path: no routing/timing bookkeeping — the
            # measurement overhead itself would rival the encode
            return self._host.apply_bytes(matrix, chunks)
        if self.use_device(chunks.nbytes):
            dev_in = self.pad_batch(chunks) if chunks.ndim == 3 else chunks
            fn = self.device_fn_if_ready("bytes", matrix, (), dev_in.shape)
            if fn is not None:
                return self._timed(
                    "dev", chunks.nbytes,
                    lambda: np.asarray(fn(dev_in))[: chunks.shape[0]]
                    if chunks.ndim == 3 else np.asarray(fn(dev_in)))
        return self._timed(
            "host", chunks.nbytes,
            lambda: self._host.apply_bytes(matrix, chunks))

    def apply_packets(self, matrix: np.ndarray, chunks, w: int,
                      packetsize: int) -> np.ndarray:
        chunks = np.asarray(chunks, dtype=np.uint8)
        if chunks.nbytes < self.MIN_DEVICE_BYTES:
            return self._host.apply_packets(matrix, chunks, w,
                                            packetsize)
        if self.use_device(chunks.nbytes):
            dev_in = self.pad_batch(chunks) if chunks.ndim == 3 else chunks
            fn = self.device_fn_if_ready("packets", matrix, (w, packetsize),
                                         dev_in.shape)
            if fn is not None:
                return self._timed(
                    "dev", chunks.nbytes,
                    lambda: np.asarray(fn(dev_in))[: chunks.shape[0]]
                    if chunks.ndim == 3 else np.asarray(fn(dev_in)))
        return self._timed(
            "host", chunks.nbytes,
            lambda: self._host.apply_packets(matrix, chunks, w, packetsize))

    def apply_bits(self, bits: np.ndarray, chunks, w: int,
                   packetsize: int) -> np.ndarray:
        chunks = np.asarray(chunks, dtype=np.uint8)
        if chunks.nbytes < self.MIN_DEVICE_BYTES:
            return self._host.apply_bits(bits, chunks, w, packetsize)
        if self.use_device(chunks.nbytes):
            dev_in = self.pad_batch(chunks) if chunks.ndim == 3 else chunks
            fn = self.device_fn_if_ready("bits", bits, (w, packetsize),
                                         dev_in.shape)
            if fn is not None:
                return self._timed(
                    "dev", chunks.nbytes,
                    lambda: np.asarray(fn(dev_in))[: chunks.shape[0]]
                    if chunks.ndim == 3 else np.asarray(fn(dev_in)))
        return self._timed(
            "host", chunks.nbytes,
            lambda: self._host.apply_bits(bits, chunks, w, packetsize))

    def fused_fn_if_ready(self, matrix: np.ndarray, shape: tuple,
                          device=None):
        """The fused encode+CRC fn of the served representation."""
        return self.device_fn_if_ready(
            "fused", matrix,
            (shape[-1], self.rep, self.w, self.packetsize), shape, device)


# ---------------------------------------------------------------------------
# The codec
# ---------------------------------------------------------------------------


class MatrixErasureCode(ErasureCode):
    """k+m systematic code from a technique's GF(2^8) coding matrix."""

    DEFAULT_K = 2
    DEFAULT_M = 1
    DEFAULT_W = 8
    DEFAULT_PACKETSIZE = 2048
    DEFAULT_TECHNIQUE = "reed_sol_van"
    DEFAULT_C = 2               # planned (shec) techniques only

    def __init__(self, backend=None, techniques: Mapping[str, tuple] | None = None):
        self.backend = backend or NumpyBackend()
        self.techniques = dict(techniques or TECHNIQUES)
        self.technique = self.DEFAULT_TECHNIQUE
        self.w = self.DEFAULT_W
        self.packetsize = self.DEFAULT_PACKETSIZE
        self.coding_matrix: np.ndarray | None = None
        self.generator: np.ndarray | None = None
        self._decode_cache: dict[tuple[int, ...], np.ndarray] = {}
        # planned techniques: (want, available) -> plan, see _plan
        self.planned = False
        self._plan_cache: dict[tuple[frozenset, frozenset], tuple] = {}
        # layered techniques: chunk id -> shard position, and the
        # layers in chunk ids (see Layered)
        self._mapping: list[int] = []
        self._layers: list[tuple] = []
        self._fast1 = None

    # -- init -------------------------------------------------------------

    def init(self, profile: Mapping[str, str]) -> None:
        self.technique = profile.get("technique", self.DEFAULT_TECHNIQUE)
        if self.technique not in self.techniques:
            raise ErasureCodeError(
                f"unknown technique {self.technique!r}; "
                f"have {sorted(self.techniques)}")
        builder, self.rep, *traits = self.techniques[self.technique]
        self.planned = "planned" in traits
        # a layered technique's builder takes the profile, and its
        # composed generator says what k and m are
        layered = builder(profile) if "layered" in traits else None
        self._mapping, self._layers = [], []
        if layered is not None:
            self._mapping, self._layers = layered.mapping, layered.layers
            self.m, self.k = layered.matrix.shape
        else:
            self.k = self.profile_int(profile, "k", self.DEFAULT_K)
            self.m = self.profile_int(profile, "m", self.DEFAULT_M)
        self.w = self.profile_int(
            profile, "w", TECH_DEFAULT_W.get(self.technique,
                                             self.DEFAULT_W))
        self.packetsize = self.profile_int(
            profile, "packetsize", self.DEFAULT_PACKETSIZE)
        if self.k < 1 or self.m < 0:
            raise ErasureCodeError(f"invalid k={self.k} m={self.m}")
        if self.k + self.m > 256:
            raise ErasureCodeError("k+m must be <= 256 for w=8")
        if self.rep != REP_BITS and self.w != 8:
            raise ErasureCodeError(
                f"technique {self.technique} supports w=8 only")
        if layered is not None:
            self.coding_matrix = layered.matrix
        else:
            extra = ((self.profile_int(profile, "c", self.DEFAULT_C),)
                     if self.planned else ())
            self.coding_matrix = np.asarray(
                builder(self.k, self.m, self.w, self.packetsize, *extra),
                dtype=np.uint8)
        if self.rep == REP_BITS:
            # native GF(2): generator = [identity; coding bits]
            self.generator = None
            self.gen_bits = np.vstack(
                [np.eye(self.k * self.w, dtype=np.uint8),
                 self.coding_matrix])
        else:
            self.generator = gf.systematic_generator(
                self.coding_matrix, self.k)
        self._decode_cache.clear()
        self._plan_cache.clear()
        # the data chunks each parity covers (what a shingled plan reads)
        self._support = [frozenset(np.flatnonzero(row).tolist())
                         for row in self.coding_matrix] \
            if self.planned and layered is None else []
        self._fast1 = self._build_fast1()
        if isinstance(self.backend, TpuBackend):
            self.backend.serve(self.rep, self.w, self.packetsize)

    def _build_fast1(self):
        """Pre-bound single-stripe encoder for the vstart-default
        small-write path (k=2,m=1 4KiB): one closure frame straight
        into the native extension, no routing/timing bookkeeping —
        the generic path's per-call overhead (~1.7us of asarray/
        branching) rivals the 1.2us the AVX2 kernel needs for the
        whole stripe.  Returns None (fall through to the routed path)
        for batches, big stripes, or non-canonical arrays."""
        if self.rep != REP_BYTES or self.coding_matrix.shape[0] == 0:
            return None
        from .. import native
        ext = native.get_ext()
        if ext is None:
            return None
        mat = np.ascontiguousarray(self.coding_matrix, dtype=np.uint8)
        rows, k = mat.shape
        enc = ext.gf_encode
        empty = np.empty
        u8 = np.dtype(np.uint8)
        size_cap = (TpuBackend.MIN_DEVICE_BYTES
                    if isinstance(self.backend, TpuBackend)
                    else 1 << 62)

        def fast(d: np.ndarray):
            if (d.ndim != 2 or d.dtype is not u8
                    or d.shape[0] != k or d.nbytes >= size_cap
                    or not d.flags.c_contiguous):
                return None
            L = d.shape[1]
            parity = empty((rows, L), u8)
            enc(mat, rows, k, d, parity, L)
            return parity

        return fast

    # -- geometry ---------------------------------------------------------

    def get_chunk_mapping(self) -> list[int]:
        return list(self._mapping)

    def get_alignment(self) -> int:
        if self.rep in (REP_PACKETS, REP_BITS):
            # a chunk must hold whole super-blocks of w packets AND be
            # device-lane aligned; the lcm is the minimal such unit
            return self.k * math.lcm(CHUNK_ALIGN,
                                     self.w * self.packetsize)
        return self.k * CHUNK_ALIGN

    # -- encode -----------------------------------------------------------

    def _apply(self, matrix: np.ndarray, chunks: np.ndarray,
               backend=None) -> np.ndarray:
        """`matrix` applied to `chunks` in the codec's representation,
        by `backend` (the codec's own unless given)."""
        backend = backend or self.backend
        if matrix.shape[0] == 0:
            return np.zeros((0, chunks.shape[-1]), dtype=np.uint8)
        if self.rep == REP_PACKETS:
            return backend.apply_packets(
                matrix, chunks, self.w, self.packetsize)
        if self.rep == REP_BITS:
            return backend.apply_bits(
                matrix, chunks, self.w, self.packetsize)
        return backend.apply_bytes(matrix, chunks)

    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        f = self._fast1
        if f is not None and type(data_chunks) is np.ndarray:
            out = f(data_chunks)
            if out is not None:
                return out
        data_chunks = np.asarray(data_chunks, dtype=np.uint8)
        if data_chunks.shape[-2] != self.k:
            raise ErasureCodeError(
                f"expected {self.k} data chunks, got {data_chunks.shape[-2]}")
        return self._apply(self.coding_matrix, data_chunks)

    # -- decode -----------------------------------------------------------

    def _note_plan_miss(self, local: bool = False) -> None:
        """`perf dump`: decode patterns computed (a plan search or a
        decode matrix), beside `decode_plans`, the patterns cached, and
        `decode_plans_local`, the plans computed that read fewer than k
        chunks (a local group, a shingle)."""
        c = self.stat_counters()
        c["decode_plan_misses"] = c.get("decode_plan_misses", 0) + 1
        c["decode_plans_local"] = c.get("decode_plans_local", 0) \
            + int(local)
        c["decode_plans"] = len(self._decode_cache) + len(self._plan_cache)

    def _plan(self, want: frozenset, avail: frozenset) -> tuple:
        """A planned (non-MDS) technique's way to `want` from `avail`,
        cached by pattern: the chunks to read first, then what its
        search (`_search_shingled`, `_search_layered`) solves them
        by.  Raises ErasureCodeError when the code cannot."""
        key = (want, avail)
        plan = self._plan_cache.get(key)
        if plan is not None:
            return plan
        plan = (self._search_layered if self._layers
                else self._search_shingled)(want, avail)
        if plan is None:
            raise ErasureCodeError(
                f"cannot decode {sorted(want)} from {sorted(avail)}")
        if len(self._plan_cache) > 1024:
            self._plan_cache.clear()
        # the plan's own chunks decode by the same plan
        self._plan_cache[key] = self._plan_cache[(want, plan[0])] = plan
        return plan

    def _plan_span(self, want: frozenset, avail: frozenset):
        from ..utils import optracker
        return optracker.span("ec.plan", want=sorted(want),
                              present=sorted(avail))

    def _note_plan(self, note, fetch: frozenset) -> None:
        """A search's result on its `ec.plan` span and in the counters:
        `reads`, the chunks the plan fetches, and `local`, 1 where they
        are fewer than the k a whole decode reads."""
        local = len(fetch) < self.k
        note.update(reads=len(fetch), local=int(local))
        self._note_plan_miss(local)

    def _search_shingled(self, want: frozenset, avail: frozenset):
        """(chunks to read, parities used, unknown data chunks, the
        inverse of the parities' rows restricted to the unknowns).

        Of the subsets of the available parities, the one that reads
        the fewest chunks among those whose rows, restricted to the
        data chunks they touch that are not available, are square and
        invertible over GF(2^8) (the reference's search for a decoding
        matrix, ErasureCodeShec.cc shec_make_decoding_matrix).  None
        when no subset is: more than c chunks lost, in a pattern the
        shingles do not cover."""
        k, cm, support = self.k, self.coding_matrix, self._support
        # data chunks to produce: wanted ones, and what a wanted
        # parity that is not available is computed from
        need0 = {i for i in want if i < k}
        for p in want:
            if p >= k and p not in avail:
                need0 |= support[p - k]
        parities = sorted(i - k for i in avail if i >= k)
        best, best_read = None, None
        # one equation a parity: fewer parities than unknowns cannot
        # decode, whatever they cover (the cheap refusal a gather asks
        # for after every arrival)
        if len(parities) < len(need0 - avail):
            return None
        with self._plan_span(want, avail) as note:
            for mask in range(1 << len(parities)):
                ps = [p for i, p in enumerate(parities) if mask >> i & 1]
                need = need0.union(*(support[p] for p in ps))
                unknowns = sorted(need - avail)
                if len(unknowns) != len(ps):
                    continue
                read = len(need & avail) + len(ps)
                if best is not None and read >= best_read:
                    continue
                inv = np.zeros((0, 0), dtype=np.uint8)
                if ps:
                    try:
                        inv = gf.gf_mat_inv(cm[np.ix_(ps, unknowns)])
                    except np.linalg.LinAlgError:
                        continue
                fetch = (need & avail) | {p + k for p in ps} \
                    | {p for p in want if p >= k and p in avail}
                best, best_read = (frozenset(fetch), tuple(ps),
                                   tuple(unknowns), inv), read
            if best is not None:
                self._note_plan(note, best[0])
            else:
                self._note_plan_miss()
        return best

    def _layer_steps(self, known: set) -> list[tuple]:
        """What the layers give from the chunks `known`, which grows
        by it: [(layer, the inputs' worth of known chunks it reads, the
        chunks it rebuilds)], local layers first and again until none
        has anything to add (ErasureCodeLrc::decode_chunks walks its
        layers from the last; a layer decodes when it lacks no more
        chunks than it has coding chunks)."""
        steps, grew = [], True
        while grew:
            grew = False
            for li in reversed(range(len(self._layers))):
                chunks, lk, _gen = self._layers[li]
                have = [c for c in chunks if c in known]
                if lk <= len(have) < len(chunks):
                    lost = tuple(c for c in chunks if c not in known)
                    steps.append((li, tuple(have[:lk]), lost))
                    known.update(lost)
                    grew = True
        return steps

    def _search_layered(self, want: frozenset, avail: frozenset):
        """(chunks to read, the layer steps that give the rest): the
        fewest available chunks from which the layers, one after the
        other, rebuild what is wanted: for one lost chunk its local
        group's l, for a read of the data what the global layer needs.
        None where they cannot, whatever linear algebra could: the
        code's promise is its layers'."""
        if not want <= set(avail).union(
                *(lost for _l, _r, lost in self._layer_steps(set(avail)))):
            return None         # the cheap refusal of a gather's ask
        must = want & avail
        rest = sorted(avail - must)
        with self._plan_span(want, avail) as note:
            for n in range(len(rest) + 1):
                for more in itertools.combinations(rest, n):
                    known = set(must).union(more)
                    steps = self._layer_steps(known)
                    if want <= known:
                        fetch = must.union(more)
                        self._note_plan(note, fetch)
                        return fetch, tuple(steps)

    def minimum_to_decode(self, want_to_read, available) -> list[int]:
        if not self.planned:
            return super().minimum_to_decode(want_to_read, available)
        want = frozenset(int(i) for i in want_to_read)
        avail = frozenset(int(i) for i in available)
        if want <= avail:
            return sorted(want)
        return sorted(self._plan(want, avail)[0])

    def _planned_rows(self, want: Sequence[int],
                      present: Sequence[int]) -> np.ndarray:
        """The plan's solved system as a matrix over `present`: every
        chunk the plan touches is a combination of the chunks read
        (itself, if it was read).  Chunks of `present` the plan does
        not read get zero columns."""
        plan = self._plan(frozenset(want), frozenset(present))
        col = {c: i for i, c in enumerate(present)}
        unit = np.eye(len(present), dtype=np.uint8)
        made = (self._layered_rows if self._layers
                else self._shingled_rows)(plan, col, unit)
        return np.stack([unit[col[c]] if c in col else made(c)
                         for c in want]).astype(np.uint8)

    def _shingled_rows(self, plan: tuple, col: dict, unit: np.ndarray):
        """An unknown data chunk is the inverse's row applied to each
        parity minus its known terms; a wanted parity the product of
        its coding row with the data."""
        k, cm = self.k, self.coding_matrix
        _fetch, ps, unknowns, inv = plan
        # row d: data chunk d as a combination of `present` (zero
        # while unknown, and for the chunks the plan does not touch)
        data = np.zeros((k, len(col)), dtype=np.uint8)
        for d in col:
            if d < k:
                data[d] = unit[col[d]]
        if ps:
            rhs = unit[[col[p + k] for p in ps]] \
                ^ gf.gf_matmul(cm[list(ps)], data)
            data[list(unknowns)] = gf.gf_matmul(inv, rhs)
        return lambda c: data[c] if c < k \
            else gf.gf_matmul(cm[c - k][None, :], data)[0]

    def _layered_rows(self, plan: tuple, col: dict, unit: np.ndarray):
        """Each step's layer, inverted over the chunks it reads, gives
        its own inputs as combinations of `present`, and its
        generator's rows the chunks it rebuilds."""
        fetch, steps = plan
        rows = {c: unit[col[c]] for c in fetch}
        for li, read, lost in steps:
            chunks, lk, gen = self._layers[li]
            at = {c: i for i, c in enumerate(chunks)}
            inputs = gf.gf_matmul(
                gf.decode_matrix(gen, lk, [at[c] for c in read]),
                np.stack([rows[c] for c in read]))
            rows.update(zip(lost, gf.gf_matmul(
                gen[[at[c] for c in lost]], inputs)))
        return rows.__getitem__

    def _decode_rows(self, want: Sequence[int],
                     present: Sequence[int]) -> np.ndarray:
        """(len(want) x len(present)) matrix rebuilding `want` from
        `present`, cached by pattern."""
        key = (tuple(want), tuple(present))
        cached = self._decode_cache.get(key)
        if cached is not None:
            return cached
        from ..utils import optracker
        with optracker.span("ec.plan", want=list(want),
                            present=list(present), reads=len(present),
                            local=int(len(present) < self.k)):
            if self.rep == REP_BITS:
                out = gf.bitmatrix_decode_rows(
                    self.gen_bits, self.k, self.w, list(want),
                    list(present))
            elif self.planned:
                out = self._planned_rows(want, present)
            else:
                inv = gf.decode_matrix(self.generator, self.k,
                                       list(present))
                rows = []
                for c in want:
                    if c < self.k:
                        rows.append(inv[c])
                    else:
                        rows.append(gf.gf_matmul(
                            self.coding_matrix[c - self.k][None, :],
                            inv)[0])
                out = np.stack(rows).astype(np.uint8)
            if len(self._decode_cache) > 512:
                self._decode_cache.clear()
            self._decode_cache[key] = out
            self._note_plan_miss()
        return out

    def encode_stripes_with_crcs(self, stripes) -> tuple:
        """Batched stripes, fused CRCs on the device path.

        One dispatch encodes all S stripes AND computes the k+m scrub
        CRCs per stripe (the north-star fused pass); the host path still
        batches the matmul but folds CRCs with the table kernel.
        """
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        if stripes.ndim != 3 or stripes.shape[1] != self.k:
            raise ErasureCodeError(f"want (S, {self.k}, L), "
                                   f"got {stripes.shape}")
        if isinstance(self.backend, TpuBackend):
            fn = None
            if self.backend.use_device(stripes.nbytes):
                dev_in = self.backend.pad_batch(stripes)
                fn = self.backend.fused_fn_if_ready(self.coding_matrix,
                                                    dev_in.shape)
            if fn is not None:
                import time as _time
                S = stripes.shape[0]
                t0 = _time.perf_counter()
                parity, crcs = fn(dev_in)
                parity = np.asarray(parity)[:S]
                crcs = np.asarray(crcs, dtype=np.uint32)[:S]
                self.backend.record("dev", stripes.nbytes,
                                    _time.perf_counter() - t0)
                allc = np.concatenate([stripes, parity], axis=1)
                self.stat_counters()["device_stripe_passes"] += 1
                return allc, crcs
            # explicit host fallback — routing through _apply here would
            # re-decide per call and could run the encode on device
            # WITHOUT the fused CRC, muddying both metrics and semantics
            parity = self.backend._timed(
                "host", stripes.nbytes,
                lambda: np.asarray(self._apply(
                    self.coding_matrix, stripes, self.backend._host)))
        else:
            parity = np.asarray(self._apply(self.coding_matrix, stripes))
        allc = np.concatenate([stripes, parity], axis=1)
        return self._finish_host_stripes(allc)

    def decode_chunks(self, want_to_read, chunks) -> dict[int, np.ndarray]:
        have = {int(i): np.asarray(b, dtype=np.uint8)
                for i, b in chunks.items()}
        want = list(want_to_read)
        out = {i: have[i] for i in want if i in have}
        missing = [i for i in want if i not in have]
        if not missing:
            return out
        present = self.minimum_to_decode(missing, have.keys())
        # already-present wanted chunks came straight from `have`;
        # reconstruct only the missing ones in one matmul
        stack = np.stack([have[i] for i in present])
        rows = self._decode_rows(missing, present)
        rebuilt = self._apply(rows, stack)
        for idx, c in enumerate(missing):
            out[c] = rebuilt[idx]
        return out
