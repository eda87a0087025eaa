"""HBM-resident EC stripe cache: bytes cross the host<->device
boundary at most once per object lifetime.

PR 2/3 amortized dispatch COUNT; the remaining e2e gap is pure
transfer: every producer re-uploaded bytes the device had already
seen.  An OSD's EC working set is written once and then re-touched by
deep scrub (CRC folds over the same shard bytes) and recovery
(decodes of the same stripes) — so after the write's single H2D
upload the encoded stripes simply STAY in HBM:

  * the pipeline stages an entry at collect time (device slices of the
    uploaded data and the computed parity — no extra transfer, the
    arrays are already device-resident) keyed (pg collection, oid);
  * the producer COMMITS the entry once the shard bytes landed in the
    object store, so the cache can never be ahead of disk;
  * deep scrub serves shard CRCs from the entry's per-stripe chunk
    CRCs (a host-side carry-less fold of 4-byte values — ZERO bytes
    re-uploaded, zero device dispatches);
  * recovery/degraded reads fetch the wanted shard rows D2H straight
    from the cached device arrays — no shard gather, no decode matmul,
    no H2D;
  * a read served from an entry is CHECKED where the entry lies: the
    chip folds CRC32C over the resident data stripes while they are
    copied out, and the copy serves only if the CRCs are the ones the
    write's fused pass left (a disk read is held to its block and
    shard CRCs; this is the same for HBM, at 4 bytes a chunk D2H).

Coherence is enforced at the OBJECT STORE layer, not by trusting
producers: every applied transaction is scanned
(:func:`note_store_txn`) and any data mutation of a cached object's
shard files invalidates the entry — UNLESS the same transaction
attests the entry's exact version via the per-shard version xattr
(the EC write fan-out and recovery pushes of the same version are the
cached content landing on more shards, not new content).  A raw
store write with no version attestation — silent bitrot, a test
poking corruption in, a rollback stash restore — always invalidates,
so a cache hit is as trustworthy as the disk read it replaces and
deep scrub keeps catching real corruption.

Quarantine-aware eviction: entries are pinned to the pipeline lane
whose chip holds their HBM; when a lane quarantines (device error,
real or injected) its entries drop immediately — a redrain re-uploads
from host rather than ever serving shards from a chip in an unknown
state.

Capacity is bounded by ``osd_ec_hbm_cache_bytes`` (LRU on committed
entries); 0 disables the cache entirely.

A size mix runs on a CLOSED set of device programs.  An entry is a
list of SEGMENTS, each a pair of device arrays of a power-of-two row
bucket with the first resident row and the true row count kept beside
it (:class:`Segment`): the item's own dispatch arrays where it rode
alone, a ``dynamic_slice`` to the item's bucket with the start an
operand where it shared a coalesced dispatch (:func:`item_arrays`: one
program a (batch bucket, item bucket) pair), an uploaded tail where an
append extended the entry (:meth:`HbmStripeCache.append_through`: a
transfer, no program).  The CRC fold that checks a served read runs at
a segment's bucket and the pad rows are left out of the comparison
(one program a bucket).  ``stats()["programs"]`` counts the distinct
programs acquired since boot: flat after warm-up is the sign.
"""

from __future__ import annotations

import ast
import functools
import threading
import time
from collections import OrderedDict

import numpy as np

DEFAULT_CAPACITY = 64 << 20
MAX_PENDING = 64
# the most segments an entry grows to by appends: one more invalidates
# it (a read fetches segment by segment)
MAX_SEGMENTS = 8

# per-shard version xattr (osd/pglog.py VER_KEY): the store-txn
# coherence scan parses it to recognize same-version fan-out writes.
# Duplicated here because the ops layer must not import the osd layer.
_VER_ATTR = "_v"


def _base_name(name: str) -> str:
    """Base object of a shard/stash file name: 'oid.s3@1.7' -> 'oid'."""
    base = name.split("@", 1)[0]
    stem, _, sfx = base.rpartition(".s")
    if sfx.isdigit():
        return stem
    return base


def _parse_ver(blob: bytes) -> tuple | None:
    try:
        ev = ast.literal_eval(blob.decode())
    except (ValueError, SyntaxError, UnicodeDecodeError, AttributeError):
        return None
    return tuple(ev) if isinstance(ev, tuple) else None


# (stripes' shape, device) whose CRC program is compiled: a read never
# compiles, it serves unchecked until the staging's warm-up is through
_verify_ready: set[tuple] = set()
# every distinct device program this module acquired since boot: the
# CRC folds above, the item slices of `item_arrays`
_programs: set[tuple] = set()
_plock = threading.Lock()


def _note_program(key: tuple) -> None:
    with _plock:
        _programs.add(key)


def _device_of(arr):
    """The device a resident array lies on; None for a host array or
    a buffer that is gone."""
    try:
        return next(iter(arr.devices()))
    except Exception:
        return None


def _verify_fn(dev_data):
    """The compiled CRC fold for these resident stripes, or None
    (host arrays, a shape not warm yet, a layout other than (S, k, L)
    bytes)."""
    if (tuple(dev_data.shape), _device_of(dev_data)) not in _verify_ready:
        return None
    from . import ec_kernels
    return ec_kernels.make_crc_fn(int(dev_data.shape[-1]))


def warm_verify(shape: tuple, device) -> None:
    """Compile the CRC fold a cache-served read runs over a segment of
    (bucket, k, L) uint8 stripes on `device` (the pipeline calls this
    on a warm thread when an item of that bucket is first staged)."""
    key = (tuple(shape), device)
    if key in _verify_ready or len(shape) != 3 or device is None:
        return
    import jax.numpy as jnp
    from . import ec_kernels
    fn = ec_kernels.make_crc_fn(int(shape[-1]))
    np.asarray(fn(jnp.zeros(shape, dtype=jnp.uint8, device=device)))
    _verify_ready.add(key)
    _note_program(("verify",) + key)


@functools.lru_cache(maxsize=None)
def _slice_fn(bucket: int):
    """Jitted: `bucket` rows of each of two batch arrays from row
    `start` on, the start an operand (one program a pair of batch
    shapes and bucket, whatever the offset)."""
    import jax

    def cut(data, parity, start):
        return (jax.lax.dynamic_slice_in_dim(data, start, bucket, 0),
                jax.lax.dynamic_slice_in_dim(parity, start, bucket, 0))

    return jax.jit(cut)


def item_arrays(dev_in, dev_parity, off: int, n: int, bucket: int):
    """What the cache keeps of one item of a dispatch: (data, parity,
    row0) where the item's `n` rows lie at rows [row0, row0 + n) of
    both arrays.  `bucket` is the pipeline's row bucket of n.  An item
    whose bucket is the batch's keeps the dispatch's own arrays (no
    program); any other is cut out at its bucket, where a start the
    slice would clamp is clamped here and the difference kept as
    row0."""
    rows = int(dev_in.shape[0])
    if bucket >= rows:
        return dev_in, dev_parity, off
    if _device_of(dev_in) is None:      # host arrays: numpy views
        return dev_in[off: off + n], dev_parity[off: off + n], 0
    start = min(off, rows - bucket)
    _note_program(("slice", tuple(dev_in.shape), tuple(dev_parity.shape),
                   bucket, _device_of(dev_in)))
    data, parity = _slice_fn(bucket)(dev_in, dev_parity, np.int32(start))
    return data, parity, off - start


def warm_item(like: tuple, device, bucket: int, batches) -> None:
    """Compile what staging and serving an item of `bucket` rows needs
    on `device`: the CRC fold at the bucket, and the slice out of each
    of the batch buckets `batches` above it.  `like` is ((k, L), dtype),
    ((m, L), dtype) of the data and parity arrays."""
    import jax.numpy as jnp
    (tail_d, dtype_d), (tail_p, dtype_p) = like
    if np.dtype(dtype_d) == np.uint8:
        warm_verify((bucket,) + tuple(tail_d), device)
    for rows in batches:
        if rows <= bucket:
            continue
        d = jnp.zeros((rows,) + tuple(tail_d), dtype=dtype_d, device=device)
        p = jnp.zeros((rows,) + tuple(tail_p), dtype=dtype_p, device=device)
        for a in item_arrays(d, p, rows - bucket, bucket, bucket)[:2]:
            a.block_until_ready()


def programs() -> int:
    with _plock:
        return len(_programs)


class CacheIntent:
    """Producer-side tag riding a pipeline submission: 'if this encode
    runs on a device, keep its stripes in HBM under this key'."""

    __slots__ = ("cid", "oid", "version", "size", "chunk_size")

    def __init__(self, cid: str, oid: str, version: tuple,
                 size: int, chunk_size: int):
        self.cid = cid
        self.oid = oid
        self.version = tuple(version)
        self.size = int(size)
        self.chunk_size = int(chunk_size)


class Segment:
    """Part of an entry: `rows` stripes at rows [row0, row0 + rows) of
    a (bucket, k, L) data array and a (bucket, m, L) parity array,
    both on one chip (or both numpy, for a host-served stage)."""

    __slots__ = ("data", "parity", "row0", "rows")

    def __init__(self, data, parity, row0: int, rows: int):
        self.data, self.parity = data, parity
        self.row0, self.rows = int(row0), int(rows)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.data.shape)) + \
            int(np.prod(self.parity.shape))

    def cut(self, rows: int) -> "Segment":
        """The first `rows` stripes of this segment (the arrays are
        shared: nothing moves)."""
        return Segment(self.data, self.parity, self.row0, rows)


class CacheEntry:
    """One object's encoded stripes, device-resident.

    `segs` hold the uploaded data stripes and the on-device encode
    output, in order, still on the chip of pipeline lane `lane`; crcs
    (S, k+m) uint32 are the fused kernel's per-stripe chunk CRCs
    (host-side, 4 bytes per chunk), of the true rows alone."""

    __slots__ = ("cid", "oid", "version", "size", "chunk_size", "k",
                 "m", "segs", "crcs", "lane", "nbytes", "committed")

    def __init__(self, intent: CacheIntent, lane: int, dev_data,
                 dev_parity, crcs: np.ndarray, row0: int = 0,
                 segs: list | None = None):
        crcs = np.asarray(crcs, dtype=np.uint32)
        if segs is None:
            # what a write stages: one segment, the stripes from
            # `row0` on, as many as `crcs` has
            segs = [Segment(dev_data, dev_parity, row0, crcs.shape[0])]
        self.cid = intent.cid
        self.oid = intent.oid
        self.version = intent.version
        self.size = intent.size
        self.chunk_size = intent.chunk_size
        self.segs = list(segs)
        self.k = int(segs[0].data.shape[1])
        self.m = int(segs[0].parity.shape[1])
        self.crcs = crcs
        if sum(g.rows for g in self.segs) != self.crcs.shape[0]:
            raise ValueError("segments and crcs disagree on the rows")
        self.lane = lane
        self.nbytes = sum(g.nbytes for g in self.segs) + self.crcs.nbytes
        self.committed = False

    @property
    def stripes(self) -> int:
        return int(self.crcs.shape[0])

    def shard_size(self) -> int:
        return self.stripes * self.chunk_size

    def data_bytes(self):
        """The logical object payload, fetched D2H from the cached
        data stripes (None if the device buffers are gone, or if the
        stripes no longer have the CRCs they were written with: the
        entry is dropped and the caller reads the shards).  Returns a
        zero-copy BufferList VIEW over the fetched arrays — the D2H
        fetch is the only materialization a cache-served read pays."""
        from ..utils.bufferlist import BufferList
        rope, left, at = BufferList(), self.size, 0
        try:
            # the chip folds the stripes' CRCs while they are copied
            # out: at each segment's bucket, the pad rows left out of
            # the comparison
            fns = [_verify_fn(g.data) for g in self.segs]
            check = all(fn is not None for fn in fns)
            t0 = time.monotonic()
            folded = [fn(g.data) for fn, g in zip(fns, self.segs)] \
                if check else None
            arrs = [np.ascontiguousarray(np.asarray(g.data,
                                                    dtype=np.uint8))
                    for g in self.segs]
            get().count_d2h(sum(a.nbytes for a in arrs))
            if check:
                t1 = time.monotonic()
                same = True
                for f, g in zip(folded, self.segs):
                    same = same and np.array_equal(
                        np.asarray(f)[g.row0: g.row0 + g.rows],
                        self.crcs[at: at + g.rows, : self.k])
                    at += g.rows
                from ..utils import optracker
                optracker.add_span(
                    "ec.device_compute", t0, t1, stripes=self.stripes,
                    padded=sum(int(g.data.shape[0]) for g in self.segs))
                optracker.add_span("ec.d2h", t1, time.monotonic())
                if not get().count_verified(self, same):
                    return None
            for a, g in zip(arrs, self.segs):
                view = memoryview(
                    a[g.row0: g.row0 + g.rows].reshape(-1))[:left]
                if len(view):
                    rope.append(view)
                left -= len(view)
        except Exception:
            return None
        get().count_read_hit_bytes(self.size)
        return rope

    def shard_bytes(self, shard: int) -> bytes | None:
        """One shard file's bytes (chunk `shard` of every stripe),
        fetched D2H — only this shard's column crosses the boundary."""
        try:
            rows = []
            for g in self.segs:
                src, col = (g.data, shard) if shard < self.k \
                    else (g.parity, shard - self.k)
                arr = np.asarray(src[:, col], dtype=np.uint8)
                get().count_d2h(arr.nbytes)
                rows.append(arr[g.row0: g.row0 + g.rows])
            return (rows[0] if len(rows) == 1
                    else np.concatenate(rows)).tobytes()
        except Exception:
            return None


class HbmStripeCache:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: OrderedDict[tuple, CacheEntry] = OrderedDict()
        self._pending: OrderedDict[tuple, CacheEntry] = OrderedDict()
        self._bases: set[tuple] = set()     # committed + pending keys
        self._bytes = 0                     # committed entries
        self._pbytes = 0                    # pending (staged) entries
        self._c = {"hit": 0, "miss": 0, "evict": 0, "insert": 0,
                   "invalidate": 0, "lane_drops": 0, "bytes_d2h": 0,
                   "read_bytes_served": 0, "append_throughs": 0,
                   "verified": 0, "verify_fail": 0}

    # -- accounting (entry fetches call back in) ---------------------------

    def count_d2h(self, n: int) -> None:
        with self._lock:
            self._c["bytes_d2h"] += int(n)

    def count_read_hit_bytes(self, n: int) -> None:
        """Logical payload bytes a read served from the cache
        (``read_bytes_served`` in ``stats()``)."""
        with self._lock:
            self._c["read_bytes_served"] += int(n)

    def count_verified(self, ent: CacheEntry, same: bool) -> bool:
        """A read's device-side CRC check of `ent`: counted, and the
        entry dropped where the stripes did not match."""
        with self._lock:
            self._c["verified" if same else "verify_fail"] += 1
            if not same and self._entries.get((ent.cid, ent.oid)) is ent:
                self._drop_locked((ent.cid, ent.oid))
        return same

    # -- write path --------------------------------------------------------

    def stage(self, intent: CacheIntent, lane: int, dev_data,
              dev_parity, crcs: np.ndarray, row0: int = 0) -> None:
        """Pipeline collect-time staging: the entry exists but is NOT
        servable until the producer commits it (shard bytes on disk).
        `lane` is the index of the pipeline lane whose chip holds the
        arrays; the item's stripes are their rows from `row0` on, as
        many as `crcs` has."""
        if self.capacity <= 0:
            return
        try:
            self._stage_entry(CacheEntry(intent, lane, dev_data,
                                         dev_parity, crcs, row0))
        except Exception:
            return

    def _stage_entry(self, ent: CacheEntry) -> None:
        if ent.nbytes > self.capacity:
            return
        key = (ent.cid, ent.oid)
        with self._lock:
            old = self._pending.pop(key, None)
            if old is not None:
                self._pbytes -= old.nbytes
            self._pending[key] = ent
            self._pbytes += ent.nbytes
            self._bases.add(key)
            # pending entries pin device HBM just like committed ones:
            # bound the TOTAL resident bytes by the configured budget
            # (an orphaned stage — producer died before commit — must
            # not overcommit the chip).  Committed LRU victims go
            # first — commit() would evict exactly them on promotion
            # anyway; staler pendings go after
            while self._bytes + self._pbytes > self.capacity and \
                    self._entries:
                k2, old = self._entries.popitem(last=False)
                self._bytes -= old.nbytes
                self._c["evict"] += 1
                if k2 not in self._pending:
                    self._bases.discard(k2)
            while self._pending and (
                    len(self._pending) > MAX_PENDING or
                    self._bytes + self._pbytes > self.capacity):
                old_key, old = self._pending.popitem(last=False)
                self._pbytes -= old.nbytes
                if old_key not in self._entries:
                    self._bases.discard(old_key)

    def append_through(self, cid: str, oid: str, old_version: tuple,
                       new_version: tuple, new_size: int,
                       chunk_size: int, full_before: int,
                       tail_data, tail_parity,
                       tail_crcs: np.ndarray) -> bool:
        """APPEND write-through: derive the appended object's entry
        from the resident whole-object stripes plus the tail encode's
        (S_tail, k, L) data / (S_tail, m, L) parity stripes — the
        untouched full-stripe prefix never leaves the chip, only the
        tail crosses, as one more SEGMENT: a transfer and no program,
        whatever the sizes.  The caller pads both tail arrays to the
        pipeline's row bucket (`tail_crcs` has the true rows alone),
        whose check the pipeline warmed when the tail was encoded.  Stages a PENDING
        entry at `new_version` (the producer commits once the shard
        tail bytes are on disk, the same contract as a whole-object
        write); the store-txn scan then drops the old committed entry
        (its version is not attested) while the attested pending one
        survives.

        Returns False — after invalidating, so a stale whole-object
        entry can never outlive the append — when there is no
        resident entry at exactly `old_version` with this geometry,
        the entry would pass MAX_SEGMENTS, or the upload fails; the
        caller loses nothing but the write-through."""
        key = (cid, oid)
        with self._lock:
            ent = self._entries.get(key) or self._pending.get(key)
        if self.capacity <= 0:
            return False
        if ent is None or ent.version != tuple(old_version) or \
                ent.chunk_size != chunk_size or \
                ent.stripes < full_before:
            self.invalidate(cid, oid)
            return False
        try:
            segs, left = [], full_before
            for g in ent.segs:
                if left <= 0:
                    break
                segs.append(g if g.rows <= left else g.cut(left))
                left -= segs[-1].rows
            if len(segs) >= MAX_SEGMENTS:
                raise ValueError("too many segments")
            rows = int(np.asarray(tail_crcs).shape[0])
            td = np.ascontiguousarray(tail_data, dtype=np.uint8)
            tp = np.ascontiguousarray(tail_parity, dtype=np.uint8)
            dev = _device_of(ent.segs[0].data)
            if dev is not None:
                # device-resident entry: upload only the tail (the
                # prefix never moves)
                import jax
                td, tp = jax.device_put(td, dev), jax.device_put(tp, dev)
            segs.append(Segment(td, tp, 0, rows))
            new_crcs = np.concatenate(
                [np.asarray(ent.crcs)[:full_before],
                 np.asarray(tail_crcs, dtype=np.uint32)])
            self._stage_entry(CacheEntry(
                CacheIntent(cid, oid, tuple(new_version), int(new_size),
                            chunk_size), ent.lane, None, None, new_crcs,
                segs=segs))
        except Exception:
            self.invalidate(cid, oid)
            return False
        with self._lock:
            self._c["append_throughs"] += 1
        return True

    def commit(self, cid: str, oid: str, version: tuple) -> bool:
        """Promote the staged entry for (cid, oid) at `version`: the
        producer's store transaction applied, disk and HBM now agree."""
        key = (cid, oid)
        version = tuple(version)
        with self._lock:
            ent = self._pending.get(key)
            if ent is None or ent.version != version:
                return False
            del self._pending[key]
            self._pbytes -= ent.nbytes
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            ent.committed = True
            self._entries[key] = ent
            self._bases.add(key)
            self._bytes += ent.nbytes
            self._c["insert"] += 1
            while self._bytes > self.capacity and self._entries:
                k2, old = self._entries.popitem(last=False)
                self._bytes -= old.nbytes
                self._c["evict"] += 1
                if k2 not in self._pending:
                    self._bases.discard(k2)
            return True

    # -- read path ---------------------------------------------------------

    def lookup(self, cid: str, oid: str,
               version: tuple | None = None) -> CacheEntry | None:
        key = (cid, oid)
        with self._lock:
            ent = self._entries.get(key)
            if ent is None or (version is not None
                               and ent.version != tuple(version)):
                self._c["miss"] += 1
                return None
            self._entries.move_to_end(key)
            self._c["hit"] += 1
            return ent

    # -- invalidation ------------------------------------------------------

    def _drop_locked(self, key: tuple) -> None:
        ent = self._entries.pop(key, None)
        if ent is not None:
            self._bytes -= ent.nbytes
            self._c["invalidate"] += 1
        pend = self._pending.pop(key, None)
        if pend is not None:
            self._pbytes -= pend.nbytes
            if ent is None:
                self._c["invalidate"] += 1
        self._bases.discard(key)

    def invalidate(self, cid: str, oid: str) -> None:
        with self._lock:
            self._drop_locked((cid, oid))

    def invalidate_cid(self, cid: str) -> None:
        with self._lock:
            for key in [k for k in self._bases if k[0] == cid]:
                self._drop_locked(key)

    def note_mutation(self, cid: str, base: str,
                      attested: set[tuple]) -> None:
        """A store transaction mutated shard data of (cid, base).
        Keep the entry only when the txn attested the entry's exact
        version (same-version fan-out / recovery push of the cached
        content); anything else — corruption, rewind, a newer write —
        invalidates."""
        key = (cid, base)
        with self._lock:
            # committed and pending are judged INDEPENDENTLY: an
            # overwrite's txn attests the NEW version, which must keep
            # the fresh pending entry (its commit follows) while
            # dropping the stale committed one
            dropped = False
            ent = self._entries.get(key)
            if ent is not None and ent.version not in attested:
                del self._entries[key]
                self._bytes -= ent.nbytes
                dropped = True
            pend = self._pending.get(key)
            if pend is not None and pend.version not in attested:
                del self._pending[key]
                self._pbytes -= pend.nbytes
                dropped = True
            if dropped:
                self._c["invalidate"] += 1
            if key not in self._entries and key not in self._pending:
                self._bases.discard(key)

    def drop_lane(self, lane: int) -> None:
        """Quarantine-aware eviction: a quarantined chip's entries are
        gone — redrain re-uploads from host, never serves stale HBM.
        Only entries RESIDENT on that chip drop; the same object's
        committed/pending counterpart on a healthy lane survives."""
        with self._lock:
            dropped = 0
            for key in [k for k, e in self._entries.items()
                        if e.lane == lane]:
                ent = self._entries.pop(key)
                self._bytes -= ent.nbytes
                dropped += 1
                if key not in self._pending:
                    self._bases.discard(key)
            for key in [k for k, e in self._pending.items()
                        if e.lane == lane]:
                pend = self._pending.pop(key)
                self._pbytes -= pend.nbytes
                dropped += 1
                if key not in self._entries:
                    self._bases.discard(key)
            if dropped:
                self._c["lane_drops"] += dropped

    def drop_cids(self, cids) -> None:
        """Crash/abort of a daemon: every entry of its pg collections
        goes — a restarted daemon starts COLD, and in-process replicas
        of the same pg share the cid key, so the conservative drop is
        the only one that can never serve stripes whose backing store
        just lost its tail."""
        wanted = set(cids)
        if not wanted:
            return
        with self._lock:
            for key in [k for k in self._bases if k[0] in wanted]:
                self._drop_locked(key)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pending.clear()
            self._bases.clear()
            self._bytes = 0
            self._pbytes = 0

    # -- store-txn coherence scan ------------------------------------------

    _DATA_OPS = {"write": 2, "zero": 2, "truncate": 2, "remove": 2,
                 "try_remove": 2, "clone": 3, "try_clone": 3}

    def note_txn_ops(self, ops: list[tuple]) -> None:
        """Scan one applied transaction's ops for mutations of cached
        objects' shard files (see module docstring for the
        version-attestation rule).  Cheap when nothing relevant is
        cached: one set lookup per mutating op.

        Ops targeting rollback STASH objects ('@' in the name — the
        same rule the scrubber skips them by) are not shard-file
        mutations: stashing a copy aside or trimming an acked stash
        never changes the current shard bytes (every EC write would
        otherwise self-invalidate at stash-trim time).  A stash
        RESTORE writes to the shard file itself and is caught by its
        destination name."""
        touched: dict[tuple, set] = {}
        mutated: set[tuple] = set()
        for op in ops:
            kind = op[0]
            idx = self._DATA_OPS.get(kind)
            if idx is not None:
                if "@" in op[idx]:
                    continue
                key = (op[1], _base_name(op[idx]))
                if key in self._bases:
                    mutated.add(key)
                    touched.setdefault(key, set())
            elif kind == "move":
                for cid, name in ((op[1], op[2]), (op[3], op[4])):
                    if "@" in name:
                        continue
                    key = (cid, _base_name(name))
                    if key in self._bases:
                        mutated.add(key)
                        touched.setdefault(key, set())
            elif kind == "setattr" and op[3] == _VER_ATTR:
                key = (op[1], _base_name(op[2]))
                if key in self._bases:
                    ver = _parse_ver(op[4])
                    if ver is not None:
                        touched.setdefault(key, set()).add(ver)
            elif kind == "rmcoll":
                if any(k[0] == op[1] for k in self._bases):
                    self.invalidate_cid(op[1])
        for key in mutated:
            self.note_mutation(key[0], key[1], touched.get(key, set()))

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._c)
            out["entries"] = len(self._entries)
            out["pending"] = len(self._pending)
            out["bytes"] = self._bytes
            out["pending_bytes"] = self._pbytes
            out["capacity"] = self.capacity
        out["programs"] = programs()
        return out

    def shrink_to_capacity(self) -> None:
        """LRU-evict committed (then oldest pending) entries until the
        resident bytes fit the current capacity — a runtime capacity
        DECREASE takes effect immediately, not at the next commit."""
        with self._lock:
            while self._bytes + self._pbytes > self.capacity and \
                    self._entries:
                key, old = self._entries.popitem(last=False)
                self._bytes -= old.nbytes
                self._c["evict"] += 1
                if key not in self._pending:
                    self._bases.discard(key)
            while self._bytes + self._pbytes > self.capacity and \
                    self._pending:
                key, old = self._pending.popitem(last=False)
                self._pbytes -= old.nbytes
                if key not in self._entries:
                    self._bases.discard(key)


# ---------------------------------------------------------------------------
# Process-wide singleton (the pipeline, every OSD in the process and
# the object stores all see one cache — same sharing model as the
# dispatch pipeline itself).
# ---------------------------------------------------------------------------

_global: HbmStripeCache | None = None
_glock = threading.Lock()


def get() -> HbmStripeCache:
    global _global
    if _global is None:
        with _glock:
            if _global is None:
                _global = HbmStripeCache()
    return _global


def configure(capacity_bytes: int | None = None) -> HbmStripeCache:
    c = get()
    if capacity_bytes is not None:
        c.capacity = int(capacity_bytes)
        if c.capacity <= 0:
            c.clear()
        else:
            c.shrink_to_capacity()
    return c


def note_store_txn(ops: list[tuple]) -> None:
    """Object-store hook: called for every applied transaction.  No-op
    (one attribute read) until something is cached."""
    c = _global
    if c is None or not c._bases:
        return
    try:
        c.note_txn_ops(ops)
    except Exception:
        # coherence scan must never fail a store apply; drop the whole
        # cache instead of risking a stale entry
        c.clear()


def stats() -> dict:
    return get().stats()
