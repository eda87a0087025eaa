"""BlockStore: raw-block ObjectStore — the BlueStore analog.

Model follows os/bluestore/BlueStore.cc semantics re-designed small:
object data lives in a single raw block file at allocator-assigned
extents; ALL metadata (onodes with their block maps, a run of blocks an
entry with a checksum a block, omap, collections, the free list, the
deferred-write WAL) lives in the KV tier (os/bluestore/BlueStore.h:413
Onode/Blob/Extent collapsed to a map of min_alloc-granularity runs).  The KV commit is the transaction's
durability point, exactly like BlueStore's _kv_sync_thread:

  * big writes go copy-on-write to freshly allocated blocks, the device
    is flushed, THEN the KV commit swaps onode + freelist atomically —
    a crash in between leaves the old onode intact and the new blocks
    still free (no WAL needed, BlueStore's "new allocation" fast path);
  * small writes (<= deferred_max bytes) ride the KV commit itself as a
    deferred-WAL record (BlueStore.h:1169 TransContext STATE_WAL_QUEUED
    analog) and are applied to the block device after commit; mount
    replays any pending records (idempotent pwrites);
  * every min_alloc block carries a crc32c verified on read
    (BlueStore's per-blob csum); mismatch surfaces StoreError(EIO);
  * a write's run of whole blocks is one extent from end to end: one
    allocation, one native checksum call, ONE entry in the onode's
    block map (a run: first block, count, first device offset, the
    blocks' checksums packed), one staged buffer, one device write,
    one WAL target.  A read finds the run by bisection, reads it in
    one device call, compares its checksums in one compare, and where
    the request is the run's whole blocks hands back the buffer the
    device read produced.  An overwrite, a hole punch or a truncate
    splits the run it touches.  Checksums stay a 4 KiB block each and
    the crash sites below still tear by block;
  * decoded onodes stay in the store beside the KV, as BlueStore keeps
    its onode cache: a bounded LRU written through at the commit point
    and nowhere else, so a look-up of an object this store committed
    or read is no KV call and no decode.

Divergence from the reference: clone copies blocks instead of
refcounting shared blobs (correctness-equivalent; COW sharing is a
space optimization) - except where the transaction's next op empties
or removes the source (an EC shard's rollback stash before a whole
rewrite or a delete) and in a move: there the copy takes the source's
block map, the blocks where they lie, a rename of the data - and the
freelist is
persisted as one coalesced blob per commit rather than
BitmapFreelistManager key-ranges — at this store's scale the blob is
tiny and the swap is atomic by construction.

Crash points (FaultSet `crash <prob> <site>` rules, seed-
deterministic, the ALICE torn-write model applied to KV commits and
extent writes):

  alloc.mid_cow       power loss partway through the COW extent
                      writes: a seeded prefix of one freshly
                      allocated block lands torn.  The committed
                      onode still points at the OLD block, so a
                      remount reads old content whole — never an
                      interleave.
  wal.pre_kv_commit   the KV commit itself is torn: a seeded prefix
                      (or, with an fsync_reorder rule armed, a
                      seeded SUBSET) of the KV transaction's ops
                      land.  Mount verifies freelist-vs-onode
                      consistency and repairs overlaps.
  wal.post_kv_commit  KV commit durable, the deferred-write device
                      applies never ran; mount replays the WAL
                      records (this replaces the old
                      debug_skip_deferred_apply test hook).
  wal.mid_apply       power loss partway through applying deferred
                      WAL writes to the device (one extent torn
                      mid-block); replay rewrites them whole.
  wal.pre_trim        applied + fsync'd, crash before the WAL
                      records are removed from the KV; replay is
                      idempotent.

With an `fsync_reorder` FaultSet rule armed, a crash additionally
rolls back a seeded SUBSET of the device writes buffered since the
last fsync barrier (deferred WAL applies ride un-fsync'd for up to
WAL_FLUSH_EVERY commits) — durable B, lost earlier A — and mount
replay must still repair every acked write bit-exact.
"""

from __future__ import annotations

import bisect
import os
import threading
from collections import OrderedDict
from typing import Iterable

import numpy as np

from ..kv.keyvaluedb import KeyValueDB, KVTransaction, after_prefix
from ..kv.memdb import MemDB
from ..kv.sqlitedb import SqliteDB
from ..ops.crc32c import crc32c_batch
from ..utils import denc
from .objectstore import (EEXIST, EIO, ENOENT, ObjectStore, StoreError,
                          Transaction)

MIN_ALLOC = 4096               # bluestore_min_alloc_size
DEFERRED_MAX = 64 * 1024       # writes at or under this ride the KV WAL
GROW = 256 * MIN_ALLOC         # device growth increment (1 MiB)
WAL_FLUSH_EVERY = 16           # applied WAL records kept before trim
IOV_MAX = os.sysconf("SC_IOV_MAX")      # buffers one gather write takes
# decoded onodes a store keeps, counted in block-map entries (and one an
# onode): 256 shard files of 4 MiB objects
ONODE_CACHE_BLOCKS = 256 * 129

P_SUPER = "S"
P_COLL = "C"
P_ONODE = "O"
P_OMAP = "M"
P_WAL = "W"


def _okey(cid: str, oid: str) -> str:
    return f"{cid}/{oid}"


# An onode's block map is a sorted list of RUNS: logical blocks that
# lie one behind the other on the device,
#     (first block#, blocks, first poff, csums)
# with `csums` the blocks' crc32c values packed "<u4", four bytes a
# block.  Runs are tuples of ints and bytes (a split or a join makes
# new ones, nobody edits one) and the map is canonical: two runs that
# touch in the object and on the device are one run.
EVERYTHING = 1 << 62            # "to the end of the object", in blocks

# the KV forms before the runs: one packed entry a block, and before
# that a plain dict {block#: [poff, crc32c]}
_MAP_ENTRY = np.dtype([("blk", "<u4"), ("poff", "<u8"), ("csum", "<u4")])


def dump_onode(head: dict) -> bytes:
    """An onode as the KV holds it (form 2): the block map as two
    packed fields, `runs` twenty-four bytes a run ("<u8" first block,
    blocks, first poff) and `csums` the runs' checksums end to end, so
    a shard file's map is some 540 bytes and its encoding no loop a
    block (form 1 was sixteen bytes a block)."""
    runs = head["runs"]
    return denc.dumps({
        "size": head["size"], "xattrs": head["xattrs"], "v": 2,
        "runs": np.array([run[:3] for run in runs], dtype="<u8").tobytes(),
        "csums": b"".join([run[3] for run in runs])})


def load_onode(blob: bytes) -> dict:
    """The onode of a KV value: `dump_onode`'s, or either form stores
    written before it hold (a packed entry a block; a plain dict)."""
    head = denc.loads(blob)
    if head.pop("v", None) == 2:
        csums, at, runs = head.pop("csums"), 0, []
        for blk, n, poff in np.frombuffer(
                head["runs"], dtype="<u8").reshape(-1, 3).tolist():
            runs.append((blk, n, poff, csums[at: at + 4 * n]))
            at += 4 * n
        head["runs"] = runs
        return head
    packed = head.pop("map", None)
    if packed is not None:
        rows = np.frombuffer(packed, dtype=_MAP_ENTRY)
        blk, poff, csum = rows["blk"], rows["poff"], rows["csum"]
    else:
        blocks = head.pop("blocks")
        blk = np.fromiter(blocks, dtype="<u8", count=len(blocks))
        ents = np.array(list(blocks.values()), dtype="<u8").reshape(-1, 2)
        poff, csum = ents[:, 0], ents[:, 1]
    head["runs"] = _runs_of_blocks(blk, poff, csum)
    return head


def _runs_of_blocks(blk, poff, csum) -> list[tuple]:
    """The canonical runs of a map given a block at a time (three
    arrays, in any order): one vectorised look for where the next
    block is not the next on the device, then a step a run."""
    if not len(blk):
        return []
    order = np.argsort(blk, kind="stable")
    blk = blk[order].astype(np.int64)
    poff = poff[order].astype(np.int64)
    raw = csum[order].astype("<u4").tobytes()
    cuts = (np.flatnonzero((np.diff(blk) != 1)
                           | (np.diff(poff) != MIN_ALLOC)) + 1).tolist()
    starts, ends = [0] + cuts, cuts + [len(blk)]
    return [(b, e - s, p, raw[4 * s: 4 * e]) for s, e, b, p in
            zip(starts, ends, blk[starts].tolist(), poff[starts].tolist())]


def _run_index(runs: list[tuple], blk: int) -> int:
    """Where logical block `blk` is in the map: the index of the run
    that holds it, else of the first run behind it (`len(runs)` if
    none).  A one-element tuple sorts in front of every run that
    starts at its block."""
    i = bisect.bisect_left(runs, (blk + 1,)) - 1
    if i >= 0 and blk < runs[i][0] + runs[i][1]:
        return i
    return i + 1


def _insert_run(runs: list[tuple], run: tuple) -> None:
    """Put a run into a map that holds none of its blocks, joined to a
    neighbour it continues in the object and on the device."""
    blk, n, poff, csums = run
    i = _run_index(runs, blk)
    if i < len(runs):
        nblk, nn, npoff, ncsums = runs[i]
        if nblk == blk + n and npoff == poff + n * MIN_ALLOC:
            n, csums = n + nn, csums + ncsums
            del runs[i]
    if i:
        pblk, pn, ppoff, pcsums = runs[i - 1]
        if pblk + pn == blk and ppoff + pn * MIN_ALLOC == poff:
            runs[i - 1] = (pblk, pn + n, ppoff, pcsums + csums)
            return
    runs.insert(i, (blk, n, poff, csums))


def _map_blocks(head: dict) -> int:
    return sum(run[1] for run in head["runs"])


class ExtentAllocator:
    """Coalesced free-extent list with first-fit block allocation
    (StupidAllocator's role, os/bluestore/StupidAllocator.cc)."""

    def __init__(self, extents: list[list[int]] | None = None):
        # sorted, non-adjacent [offset, length] runs
        self.free: list[list[int]] = [list(e) for e in (extents or [])]

    def dump(self) -> list[list[int]]:
        return [list(e) for e in self.free]

    def total_free(self) -> int:
        return sum(l for _, l in self.free)

    def allocate(self, nbytes: int) -> list[tuple[int, int]]:
        """Take nbytes (MIN_ALLOC-aligned) of space, possibly split
        across runs; raises if the device must grow first."""
        assert nbytes % MIN_ALLOC == 0
        got: list[tuple[int, int]] = []
        need = nbytes
        i = 0
        while need and i < len(self.free):
            off, length = self.free[i]
            take = min(length, need)
            got.append((off, take))
            need -= take
            if take == length:
                self.free.pop(i)
            else:
                self.free[i][0] += take
                self.free[i][1] -= take
                i += 1
        if need:
            # put partial grabs back and fail up to the caller (grow)
            self.release(got)
            raise MemoryError(f"allocator short {need} bytes")
        return got

    def allocate_at(self, off: int, length: int) -> bool:
        """Carve a SPECIFIC range out of the free list (mount-time
        freelist repair); False if the range is not wholly free."""
        for i, (roff, rlen) in enumerate(self.free):
            if roff <= off and off + length <= roff + rlen:
                self.free.pop(i)
                if off > roff:
                    self._insert(roff, off - roff)
                if off + length < roff + rlen:
                    self._insert(off + length, roff + rlen - off - length)
                return True
        return False

    def release(self, extents: Iterable[tuple[int, int]]) -> None:
        for off, length in extents:
            if not length:
                continue
            self._insert(off, length)

    def _insert(self, off: int, length: int) -> None:
        lo, hi = 0, len(self.free)
        while lo < hi:
            mid = (lo + hi) // 2
            if self.free[mid][0] < off:
                lo = mid + 1
            else:
                hi = mid
        self.free.insert(lo, [off, length])
        # coalesce with neighbours
        if lo + 1 < len(self.free) and \
                self.free[lo][0] + self.free[lo][1] == self.free[lo + 1][0]:
            self.free[lo][1] += self.free[lo + 1][1]
            self.free.pop(lo + 1)
        if lo > 0 and \
                self.free[lo - 1][0] + self.free[lo - 1][1] == self.free[lo][0]:
            self.free[lo - 1][1] += self.free[lo][1]
            self.free.pop(lo)


class _Device:
    """The raw block "device": a file (or a bytearray for path-less
    test stores), pread/pwrite/flush — KernelDevice.cc's role."""

    def __init__(self, path: str):
        self.path = path
        self._f = None
        self._mem = bytearray() if not path else None
        self.size = 0

    def create(self) -> None:
        if self.path:
            with open(self.path, "wb"):
                pass
        self.open()

    def open(self) -> None:
        if self.path:
            if self._f is not None:
                self._f.close()    # mkfs-then-mount must not leak one
            # unbuffered: pwrite goes to the descriptor, and a buffer
            # would serve reads what it held from before
            self._f = open(self.path, "r+b", buffering=0)
            self._f.seek(0, os.SEEK_END)
            self.size = self._f.tell()
        else:
            self.size = len(self._mem)

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def grow(self, new_size: int) -> None:
        if new_size <= self.size:
            return
        if self._f is not None:
            self._f.truncate(new_size)
        else:
            self._mem.extend(b"\x00" * (new_size - len(self._mem)))
        self.size = new_size

    def pwrite(self, off: int, data) -> None:
        if self._f is not None:
            fd = self._f.fileno()
            done = os.pwrite(fd, data, off)
            while done < len(data):         # a short write: the rest
                done += os.pwrite(fd, memoryview(data)[done:], off + done)
        else:
            self._mem[off: off + len(data)] = data

    def pwritev(self, off: int, pieces: list) -> None:
        """Buffers that lie one behind the other on the device, in one
        gather write (no copy to join them)."""
        if self._f is None:
            for piece in pieces:
                self._mem[off: off + len(piece)] = piece
                off += len(piece)
            return
        fd = self._f.fileno()
        for i in range(0, len(pieces), IOV_MAX):
            batch = pieces[i: i + IOV_MAX]
            want = sum(len(piece) for piece in batch)
            if os.pwritev(fd, batch, off) != want:
                # a short gather write: the pieces again, one by one
                # (pwrite finishes a short write or raises what stops it)
                at = off
                for piece in batch:
                    self.pwrite(at, piece)
                    at += len(piece)
            off += want

    def pread(self, off: int, length: int) -> bytes:
        """One call where the file has the bytes (what comes back is
        the read's own buffer); short only at the end of the file."""
        if self._f is None:
            return bytes(memoryview(self._mem)[off: off + length])
        fd = self._f.fileno()
        data = os.pread(fd, length, off)
        while len(data) < length:
            more = os.pread(fd, length - len(data), off + len(data))
            if not more:
                break
            data += more
        return data

    def pread_into(self, off: int, buf: memoryview) -> int:
        """Fill `buf` from the device at `off`: the bytes land where
        the caller wants them.  Returns how many did."""
        if self._f is None:
            with memoryview(self._mem)[off: off + len(buf)] as have:
                buf[: len(have)] = have
                return len(have)
        fd = self._f.fileno()
        done = 0
        while done < len(buf):
            got = os.preadv(fd, [buf[done:]], off + done)
            if not got:
                break
            done += got
        return done

    def flush(self) -> None:
        if self._f is not None:
            os.fsync(self._f.fileno())


class _OnodeCache:
    """Decoded committed onodes by okey, least recently used first out,
    bounded by the blocks they map (a shard file of one run weighs its
    128 blocks, and is a dict, a list and a tuple to the collector).
    A head in here is shared: whoever gets one does not edit it."""

    def __init__(self, limit: int):
        self.limit = limit
        self._heads: OrderedDict[str, dict] = OrderedDict()
        self._weight = 0

    def __len__(self) -> int:
        return len(self._heads)

    def get(self, okey: str) -> dict | None:
        head = self._heads.get(okey)
        if head is not None:
            self._heads.move_to_end(okey)
        return head

    def put(self, okey: str, head: dict) -> None:
        self.drop(okey)
        self._heads[okey] = head
        self._weight += 1 + _map_blocks(head)
        while self._weight > self.limit:
            _okey, old = self._heads.popitem(last=False)
            self._weight -= 1 + _map_blocks(old)

    def drop(self, okey: str) -> None:
        old = self._heads.pop(okey, None)
        if old is not None:
            self._weight -= 1 + _map_blocks(old)


class _Staged:
    """The device writes a transaction has staged, an extent each:
    (first poff, buffer, deferred), in the order they were staged (a
    handful: a write is one, unless the free list is in pieces).  The
    buffers are views of the caller's data until the commit; a
    deferred one rides the KV commit as a WAL record, the others go to
    the device before it.  Reads inside the transaction see them (the
    overlay), and blocks freed in the transaction that wrote them are
    cut out again, so they never reach the device."""

    def __init__(self):
        self.extents: list[tuple[int, memoryview, bool]] = []

    def __bool__(self) -> bool:
        return bool(self.extents)

    def blocks(self) -> int:
        return sum(len(view) for _p, view, _d in self.extents) // MIN_ALLOC

    def of(self, deferred: bool) -> list[tuple[int, memoryview]]:
        return [(poff, view) for poff, view, d in self.extents
                if d == deferred]

    def add(self, poff: int, view: memoryview, deferred: bool) -> None:
        self.extents.append((poff, view, deferred))

    def pieces(self, poff: int, length: int):
        """[poff, poff + length) in order as (offset, staged view or
        None, bytes): None where the device holds the bytes."""
        end = poff + length
        for start, view, _d in sorted(
                (e for e in self.extents
                 if e[0] < end and poff < e[0] + len(e[1])),
                key=lambda e: e[0]):
            if start > poff:
                yield poff, None, start - poff
                poff = start
            upto = min(start + len(view), end)
            yield poff, view[poff - start: upto - start], upto - poff
            poff = upto
        if poff < end:
            yield poff, None, end - poff

    def cut(self, poff: int, length: int) -> None:
        """Take [poff, poff + length) out; what is left of a cut extent
        keeps its place in the order."""
        end = poff + length
        left = []
        for start, view, deferred in self.extents:
            stop = start + len(view)
            if stop <= poff or end <= start:
                left.append((start, view, deferred))
                continue
            if start < poff:
                left.append((start, view[: poff - start], deferred))
            if end < stop:
                left.append((end, view[end - start:], deferred))
        self.extents = left


class BlockStore(ObjectStore):
    """Onode (decoded): {"size", "xattrs", "runs": [(first block#,
    blocks, first poff, csums), ...]}, the runs sorted, canonical and
    never edited in place; a block no run holds is a hole.  In the KV
    (P_ONODE) the map is packed: `dump_onode` / `load_onode`."""

    def __init__(self, path: str = "", deferred_max: int = DEFERRED_MAX):
        super().__init__()
        self.path = path
        self.deferred_max = deferred_max
        self.db: KeyValueDB = SqliteDB(f"{path}/db") if path else MemDB()
        self.dev = _Device(f"{path}/block" if path else "")
        self.alloc = ExtentAllocator()
        self._lock = threading.RLock()
        self._wal_seq = 0
        self._wal_applied: list[str] = []   # applied, not yet trimmed
        # the extents those records target, (poff, length) each
        self._wal_extents: list[tuple[int, int]] = []
        # device writes since the last fsync barrier, with pre-images,
        # recorded only while crash rules are installed: the
        # fsync-reordering model rolls a seeded subset of them back at
        # crash time (durable B, lost earlier A)
        self._unflushed: list[tuple[int, bytes]] = []
        # what the KV holds, decoded: onodes this store committed or
        # read, and the collections (loaded at first use).  Both are
        # written at the commit point only and read under _lock.
        self._onodes = _OnodeCache(ONODE_CACHE_BLOCKS)
        self._colls: set[str] | None = None
        self.counters = {
            "wal_records_replayed": 0,
            "wal_torn_extent_repairs": 0,
            "freelist_repairs": 0,
            "fsync_reorder_windows": 0,
            "commits": 0,
            "onode_lookups": 0,     # of committed onodes, reads included
            "onode_hits": 0,
            "onodes_committed": 0,  # onodes a commit wrote, and the
            "runs_committed": 0,    # runs their block maps held
            "reads": 0,             # of object data (not of nothing)
            # ... that were the whole blocks of one run: one device
            # read, whose buffer is what the caller got (`read`) or
            # was the caller's own (`read_into`)
            "reads_whole_run": 0,
        }

    def journal_stats(self) -> dict:
        c = self.counters
        return dict(
            c, kv_calls=self.db.calls,
            runs_per_onode=c["runs_committed"] / max(1, c["onodes_committed"]),
            read_whole_run_share=c["reads_whole_run"] / max(1, c["reads"]))

    def crash_sites(self) -> list[str]:
        return ["wal.pre_kv_commit", "wal.post_kv_commit",
                "wal.mid_apply", "wal.pre_trim", "alloc.mid_cow",
                "store.pre_apply", "store.post_apply", "pglog.append"]

    # -- lifecycle ---------------------------------------------------------

    def mkfs(self) -> None:
        if self.path:
            os.makedirs(self.path, exist_ok=True)
            self.db = SqliteDB(f"{self.path}/db")
        self.db.open()
        self.dev.create()
        kvt = self.db.transaction()
        kvt.set(P_SUPER, "super", denc.dumps(
            {"min_alloc": MIN_ALLOC, "dev_size": 0}))
        kvt.set(P_SUPER, "freelist", denc.dumps([]))
        self.db.submit_transaction(kvt, sync=True)

    def mount(self) -> None:
        if self.path and not os.path.exists(f"{self.path}/db"):
            raise FileNotFoundError(f"{self.path}/db")
        self.db.open()
        # nothing decoded outlives a mount: a crashed store comes back
        # with whatever part of its last commit landed
        self._onodes = _OnodeCache(ONODE_CACHE_BLOCKS)
        self._colls = None
        blob = self.db.get(P_SUPER, "super")
        if blob is None:
            raise StoreError(EIO, "no blockstore superblock")
        super_ = denc.loads(blob)
        self.dev.open()
        # the file may be shorter than the committed dev_size if a grow
        # raced a crash; extend (zeros are fine, blocks are COW)
        self.dev.grow(super_["dev_size"])
        self.alloc = ExtentAllocator(
            denc.loads(self.db.get(P_SUPER, "freelist")))
        self._replay_wal()
        self._verify_freelist()

    def umount(self) -> None:
        if not self.frozen:
            self._flush_deferred()
        self.dev.close()
        self.db.close()

    # -- crash plane -------------------------------------------------------

    def _forget(self) -> None:
        """Drop everything decoded, and keep no onode from here on: a
        store that crashed or was frozen answers from the KV (which no
        longer changes) until it is mounted again."""
        self._onodes = _OnodeCache(0)
        self._colls = None

    def freeze(self) -> None:
        super().freeze()
        self._forget()

    def _crash_tracking(self) -> bool:
        from ..utils import faults
        return faults.get().crash_tracking_armed(self.owner)

    def _dev_write(self, poff: int, data, tracked: bool) -> None:
        """One block to the device; with crash tracking armed its
        pre-image is kept, so the reordering model can roll un-fsync'd
        writes back, a block each, at crash time."""
        if tracked:
            self._unflushed.append(
                (poff, self.dev.pread(poff, len(data))))
        self.dev.pwrite(poff, data)

    def _write_staged(self, extents: list, tracked: bool) -> int:
        """All device mutation of a commit funnels through here: the
        staged extents ((poff, buffer), in the order they were staged)
        go to the device a call each, and those that lie one behind
        the other in ONE gather write.  With crash tracking armed an
        extent is written a block at a time, each with its pre-image.
        Returns the device calls made."""
        if tracked:
            calls = 0
            for poff, view in extents:
                for at in range(0, len(view), MIN_ALLOC):
                    self._dev_write(poff + at, view[at: at + MIN_ALLOC],
                                    True)
                    calls += 1
            return calls
        calls = i = 0
        while i < len(extents):
            start, view = extents[i]
            pieces, end = [view], start + len(view)
            while i + 1 < len(extents) and extents[i + 1][0] == end:
                i += 1
                pieces.append(extents[i][1])
                end += len(extents[i][1])
            if len(pieces) == 1:
                self.dev.pwrite(start, view)
            else:
                self.dev.pwritev(start, pieces)
            calls += 1
            i += 1
        return calls

    def _dev_flush(self) -> None:
        """fsync barrier: everything buffered is durable now."""
        self.dev.flush()
        self._unflushed = []

    def _panic(self, site: str) -> None:
        """On simulated power loss, first settle which un-fsync'd
        device writes actually survived: with an fsync_reorder rule
        armed, a seeded SUBSET survives (out-of-order durability) —
        the rest are rolled back to their pre-images."""
        self._apply_crash_reorder()
        self._forget()
        super()._panic(site)

    def _apply_crash_reorder(self) -> None:
        from ..utils import faults
        fs = faults.get()
        if not self._unflushed or not fs.reorder_armed(self.owner):
            self._unflushed = []
            return
        mask = fs.torn_survivors(self.owner, len(self._unflushed))
        for (poff, pre), survives in zip(self._unflushed, mask):
            if not survives:
                self.dev.pwrite(poff, pre)
        self.dev.flush()
        self._unflushed = []
        self.counters["fsync_reorder_windows"] += 1

    def _torn_extent_crash(self, site: str, extents: list) -> None:
        """Power loss mid-way through a batch of extent writes, torn a
        block: of all their blocks a seeded number land whole, one
        more lands TORN (a prefix of the block), the rest never reach
        the device."""
        from ..utils import faults
        fs = faults.get()
        tracked = self._crash_tracking()
        blocks = [(poff + at, view[at: at + MIN_ALLOC])
                  for poff, view in extents
                  for at in range(0, len(view), MIN_ALLOC)]
        k = int(fs.torn_keep_fraction(self.owner) * len(blocks))
        for poff, data in blocks[:k]:
            self._dev_write(poff, data, tracked)
        if k < len(blocks):
            poff, data = blocks[k]
            keep = int(fs.torn_keep_fraction(self.owner) * len(data))
            self._dev_write(poff, data[:keep], tracked)
        self._panic(site)

    def _maybe_crash_torn_kv(self, site: str, kvt: KVTransaction) -> None:
        """The ALICE torn-write model applied to the KV commit: a
        seeded prefix (or, under the reordering model, a seeded
        subset) of the transaction's ops land as a committed torn
        transaction, then the store dies.  Mount-time freelist
        verification repairs the inconsistent window."""
        from ..utils import faults
        fs = faults.get()
        if not fs.should_crash(self.owner, site):
            return
        ops, reordered = fs.torn_ops(self.owner, kvt.ops)
        if reordered:
            self.counters["fsync_reorder_windows"] += 1
        part = self.db.transaction()
        part.ops = ops
        self.db.submit_transaction(part, sync=True)
        self._panic(site)

    # -- deferred WAL ------------------------------------------------------

    def _replay_wal(self) -> None:
        """Re-apply every pending deferred write (idempotent: targets
        are extents owned by the committed onodes).  A target whose
        on-disk bytes don't already match the record — torn mid-apply,
        lost to an fsync-reorder window, or never applied at all — is
        a repair and counted."""
        pending = list(self.db.iterate(P_WAL, ""))
        for _key, blob in pending:
            for poff, data in denc.loads(blob)["writes"]:
                if self.dev.pread(poff, len(data)) != data:
                    self.counters["wal_torn_extent_repairs"] += 1
                self.dev.pwrite(poff, data)
            self.counters["wal_records_replayed"] += 1
        if pending:
            self.dev.flush()
            kvt = self.db.transaction()
            for key, _ in pending:
                kvt.rmkey(P_WAL, key)
            self.db.submit_transaction(kvt, sync=True)
        self._wal_applied = []
        self._wal_extents = []
        self._unflushed = []

    def _verify_freelist(self) -> None:
        """Mount-time consistency pass: a torn KV commit can land an
        onode without its freelist swap (or vice versa), leaving a
        block both referenced and free — the next allocation would
        then overwrite live data.  Carve every referenced extent out
        of the free list (count repairs); leaked-but-unreferenced
        blocks are merely lost space, never corruption."""
        referenced = sorted({
            (poff, n * MIN_ALLOC)
            for _key, blob in self.db.iterate(P_ONODE, "")
            for _blk, n, poff, _csums in load_onode(blob)["runs"]})
        # both lists are sorted: one pass finds where they overlap
        overlaps, free, i = [], self.alloc.free, 0
        for poff, length in referenced:
            while i < len(free) and free[i][0] + free[i][1] <= poff:
                i += 1
            j = i
            while j < len(free) and free[j][0] < poff + length:
                lo = max(poff, free[j][0])
                hi = min(poff + length, free[j][0] + free[j][1])
                overlaps.append((lo, hi - lo))
                j += 1
        for poff, length in overlaps:
            if self.alloc.allocate_at(poff, length):
                self.counters["freelist_repairs"] += length // MIN_ALLOC
        if overlaps:
            kvt = self.db.transaction()
            kvt.set(P_SUPER, "freelist", denc.dumps(self.alloc.dump()))
            self.db.submit_transaction(kvt, sync=True)

    def _flush_deferred(self) -> None:
        """fsync the device, then drop applied WAL records — they are
        no longer needed for crash recovery."""
        if not self._wal_applied:
            return
        self._dev_flush()
        # crash site: device durable, WAL records not yet trimmed —
        # mount must replay them idempotently
        self._maybe_crash("wal.pre_trim")
        kvt = self.db.transaction()
        for key in self._wal_applied:
            kvt.rmkey(P_WAL, key)
        self.db.submit_transaction(kvt, sync=True)
        self._wal_applied = []
        self._wal_extents = []

    # -- transaction application ------------------------------------------

    def _do_transaction(self, txn: Transaction) -> None:
        with self._lock:
            st = {
                "onodes": {},       # okey -> head dict | None
                "omaps": {},        # "cid/oid/k" -> bytes | None
                "new_colls": set(),
                "rm_colls": set(),
                "kvt": self.db.transaction(),
                "staged": _Staged(),    # this txn's device writes
                "allocated": [],    # extents, rolled back on failure
                "freed": [],        # extents, released only at commit
                # where the counts stood: the wal span reports what
                # this txn added (no other thread moves them under _lock)
                "before": (self.db.calls, self.counters["onode_lookups"],
                           self.counters["onode_hits"]),
            }
            try:
                try:
                    ops = txn.ops
                    for i, op in enumerate(ops):
                        self._apply_op(op, st, ops[i + 1]
                                       if i + 1 < len(ops) else None)
                except BaseException:
                    self.alloc.release(st["allocated"])
                    raise
                self._commit(st)
            except BaseException:
                # any way out but a whole commit: whatever the KV now
                # holds of this txn's keys, it is the truth
                for okey in st["onodes"]:
                    self._onodes.drop(okey)
                self._colls = None
                raise

    def _commit(self, st: dict) -> None:
        self._check_frozen()     # crashed: no device or KV write lands
        # traced: the wal span covers COW extent writes + the KV
        # commit + deferred applies — the BlockStore durability cost
        # a write pays, the journal-span analog for this backend
        from ..utils import optracker
        with optracker.span("wal") as late:
            # how often a run was one device call: blocks written by
            # this commit (COW and deferred) over the calls made
            late["blocks"] = st["staged"].blocks()
            c = self.counters
            onodes, runs = c["onodes_committed"], c["runs_committed"]
            late["dev_writes"] = self._commit_traced(st)
            # how long the block maps are that the commit wrote: one
            # run a shard file that lies in one extent
            late["onodes"] = c["onodes_committed"] - onodes
            late["runs"] = c["runs_committed"] - runs
            # how often the txn crossed into the KV tier (its look-ups
            # before this span opened included), and how many of its
            # onode look-ups the store answered from what it keeps
            calls, lookups, hits = st["before"]
            late["kv_calls"] = self.db.calls - calls
            late["commits"] = 1
            late["onode_lookups"] = self.counters["onode_lookups"] - lookups
            late["onode_hits"] = self.counters["onode_hits"] - hits

    def _commit_traced(self, st: dict) -> int:
        kvt: KVTransaction = st["kvt"]
        tracked = self._crash_tracking()
        dev_writes = 0
        # If a freed extent is still the target of an untrimmed WAL
        # record, trim the WAL first — otherwise a crash after the
        # extent is reused would replay stale bytes over live data
        # (BlueStore sequences deferred txns against reuse the same way).
        if self._wal_extents and any(
                off < woff + wlen and woff < off + length
                for off, length in st["freed"]
                for woff, wlen in self._wal_extents):
            self._flush_deferred()
        # frees take effect with this commit; no further allocations
        # happen in this txn, so in-memory release is safe now
        self.alloc.release(st["freed"])
        direct, wal = st["staged"].of(False), st["staged"].of(True)
        if direct:
            # crash site: power loss mid-way through the COW extent
            # writes — one block lands torn, but the committed onode
            # still points at the old block (old-or-new, never a mix)
            from ..utils import faults
            if faults.get().should_crash(self.owner, "alloc.mid_cow"):
                self._torn_extent_crash("alloc.mid_cow", direct)
            dev_writes += self._write_staged(direct, tracked)
            self._dev_flush()
        wal_key = None
        if wal:
            self._wal_seq += 1
            wal_key = f"{self._wal_seq:016x}"
            kvt.set(P_WAL, wal_key,
                    denc.dumps({"writes": [[o, d] for o, d in wal]}))
        for okey, head in st["onodes"].items():
            if head is None:
                kvt.rmkey(P_ONODE, okey)
            else:
                kvt.set(P_ONODE, okey, dump_onode(head))
        for key, val in st["omaps"].items():
            if val is None:
                kvt.rmkey(P_OMAP, key)
            else:
                kvt.set(P_OMAP, key, val)
        kvt.set(P_SUPER, "freelist", denc.dumps(self.alloc.dump()))
        kvt.set(P_SUPER, "super", denc.dumps(
            {"min_alloc": MIN_ALLOC, "dev_size": self.dev.size}))
        # crash site: the KV commit itself tears — a seeded prefix (or
        # reordered subset) of its ops land; mount repairs
        self._maybe_crash_torn_kv("wal.pre_kv_commit", kvt)
        self.db.submit_transaction(kvt, sync=True)
        # ---- commit point ----
        # the one place what the store keeps decoded is written: the
        # KV holds exactly these heads now (a txn edits copies, so a
        # head becomes visible to readers here and not before)
        self.counters["commits"] += 1
        for okey, head in st["onodes"].items():
            if head is None:
                self._onodes.drop(okey)
            else:
                self._onodes.put(okey, head)
                self.counters["onodes_committed"] += 1
                self.counters["runs_committed"] += len(head["runs"])
        if self._colls is not None:
            self._colls -= st["rm_colls"]
            self._colls |= st["new_colls"]
        if wal:
            # crash site: KV durable (the txn is committed), deferred
            # device applies never run — mount replays the WAL record
            self._maybe_crash("wal.post_kv_commit")
            from ..utils import faults
            if faults.get().should_crash(self.owner, "wal.mid_apply"):
                # crash site: power loss partway through the deferred
                # applies, one extent torn mid-block; replay rewrites
                self._torn_extent_crash("wal.mid_apply", wal)
            dev_writes += self._write_staged(wal, tracked)
            self._wal_applied.append(wal_key)
            self._wal_extents.extend((o, len(d)) for o, d in wal)
            if len(self._wal_applied) >= WAL_FLUSH_EVERY:
                self._flush_deferred()
        return dev_writes

    # -- allocation helpers ------------------------------------------------

    def _allocate(self, st: dict, nbytes: int) -> list[tuple[int, int]]:
        """Space for a run of blocks in one request, the device grown
        until it fits; first fit, so the extents may be split."""
        while True:
            try:
                ext = self.alloc.allocate(nbytes)
                break
            except MemoryError:
                self.alloc.release([(self.dev.size, GROW)])
                self.dev.grow(self.dev.size + GROW)
        st["allocated"].extend(ext)
        return ext

    # -- onode helpers -----------------------------------------------------

    def _collections(self) -> set[str]:
        if self._colls is None:
            self._colls = {k for k, _v in self.db.iterate(P_COLL, "")}
        return self._colls

    def _committed(self, okey: str) -> dict | None:
        """The onode as the KV holds it, from what the store keeps
        decoded or, on a miss, from the KV (one SELECT, one decode; an
        absent object is a SELECT every time: names are unbounded).
        Shared with every other reader: not to be edited."""
        self.counters["onode_lookups"] += 1
        head = self._onodes.get(okey)
        if head is not None:
            self.counters["onode_hits"] += 1
            return head
        blob = self.db.get(P_ONODE, okey)
        if blob is None:
            return None
        head = load_onode(blob)
        self._onodes.put(okey, head)
        return head

    def _load_onode(self, st: dict, cid: str, oid: str):
        okey = _okey(cid, oid)
        if okey in st["onodes"]:
            return st["onodes"][okey]
        head = self._committed(okey)
        if head is not None:
            # the txn edits a copy; runs are replaced, never edited,
            # so the map's own copy is enough
            head = {"size": head["size"], "xattrs": dict(head["xattrs"]),
                    "runs": list(head["runs"])}
        st["onodes"][okey] = head
        return head

    def _onode(self, st: dict, cid: str, oid: str, create: bool) -> dict:
        head = self._load_onode(st, cid, oid)
        if head is None:
            if not create:
                raise StoreError(ENOENT, f"no object {cid}/{oid}")
            if cid not in st["new_colls"] and \
                    cid not in self._collections():
                raise StoreError(ENOENT, f"no collection {cid}")
            head = {"size": 0, "xattrs": {}, "runs": []}
            st["onodes"][_okey(cid, oid)] = head
        return head

    def _read_verified(self, poff: int, csums: bytes, blk: int, what: str,
                       into: memoryview | None = None):
        """The device blocks from `poff` on that `csums` are of, in
        one device read, every block held to its own checksum by one
        native call and ONE compare of the packed values; only a
        mismatch is looked into, for the block to name (`blk` is the
        first one's number in its object, `what` the rest of the
        EIO's text).  Into the caller's buffer if it gives one, else
        the read's own buffer is the result."""
        length = len(csums) // 4 * MIN_ALLOC
        if into is None:
            data = self.dev.pread(poff, length)
            got = len(data)
        else:
            data, got = into, self.dev.pread_into(poff, into)
        if got != length:
            raise StoreError(EIO, f"short read {what} block {blk}")
        sums = crc32c_batch(np.frombuffer(
            data, dtype=np.uint8).reshape(-1, MIN_ALLOC)).astype(
                "<u4", copy=False)
        if sums.tobytes() != csums:
            bad = np.flatnonzero(sums != np.frombuffer(csums, dtype="<u4"))
            raise StoreError(
                EIO, f"csum mismatch {what} block {blk + int(bad[0])}")
        return data

    def _read_raw(self, st: dict, run: tuple, blk: int, n: int):
        """Blocks `blk` .. `blk + n` of a run as the txn sees them:
        what it staged itself, the rest from the device.  Device reads
        ARE csum-verified: an RMW merge or a clone over silently
        corrupt bytes would otherwise re-seal them under a fresh valid
        crc and launder the corruption past every future read."""
        first, _n, poff, csums = run
        poff += (blk - first) * MIN_ALLOC
        got = []
        for at, view, length in st["staged"].pieces(poff, n * MIN_ALLOC):
            if view is None:
                i = blk - first + (at - poff) // MIN_ALLOC
                view = self._read_verified(
                    at, csums[4 * i: 4 * i + length // MIN_ALLOC * 4],
                    first + i, f"at {at:#x} for rmw,")
            got.append(view)
        return got[0] if len(got) == 1 else b"".join(got)

    def _read_block_raw(self, st: dict, head: dict, blk: int):
        """Current content of a logical block through the txn overlay
        (nothing for a hole)."""
        runs = head["runs"]
        i = _run_index(runs, blk)
        if i == len(runs) or runs[i][0] > blk:
            return b""
        return self._read_raw(st, runs[i], blk, 1)

    def _put_run(self, st: dict, head: dict, blk: int, data,
                 deferred: bool) -> None:
        """COW a run of whole logical blocks from `blk` on as one
        extent: free the old blocks, allocate once, checksum the run in
        one native call, then map it and stage its device write, a run
        and a buffer an extent the allocator gave (one, unless the
        free list is in pieces; slices of `data`, no copy).  A call a
        block gives the GIL up a block, and on a host whose OSDs share
        one interpreter every such call hands it round."""
        n, rest = divmod(len(data), MIN_ALLOC)
        assert n and not rest
        self._cut(st, head, blk, n)
        extents = self._allocate(st, n * MIN_ALLOC)
        csums = crc32c_batch(np.frombuffer(
            data, dtype=np.uint8).reshape(n, MIN_ALLOC)).astype(
                "<u4", copy=False).tobytes()
        view = memoryview(data)
        i = 0
        for poff, length in extents:
            took = length // MIN_ALLOC
            _insert_run(head["runs"], (blk + i, took, poff,
                                       csums[4 * i: 4 * (i + took)]))
            st["staged"].add(
                poff, view[i * MIN_ALLOC: (i + took) * MIN_ALLOC], deferred)
            i += took

    def _put_block(self, st: dict, head: dict, blk: int,
                   data, deferred: bool) -> None:
        """COW one logical block, zero-padded to its size: a run of
        one."""
        assert len(data) <= MIN_ALLOC
        if len(data) < MIN_ALLOC:
            block = bytearray(MIN_ALLOC)
            block[: len(data)] = data
            data = block
        self._put_run(st, head, blk, data, deferred)

    def _cut(self, st: dict, head: dict, blk: int,
             n: int = EVERYTHING) -> None:
        """Take logical blocks `blk` .. `blk + n` out of the map and
        free what they held: a run that reaches over either end is
        split there, what is left of it one or two new runs.  Freed
        space is released at the commit; a same-txn write to it must
        not hit the device."""
        runs, end = head["runs"], blk + n
        i = j = _run_index(runs, blk)
        left = []
        while j < len(runs) and runs[j][0] < end:
            first, count, poff, csums = runs[j]
            lo, hi = max(first, blk), min(first + count, end)
            at, length = poff + (lo - first) * MIN_ALLOC, \
                (hi - lo) * MIN_ALLOC
            st["freed"].append((at, length))
            if st["staged"]:
                st["staged"].cut(at, length)
            if first < lo:
                left.append((first, lo - first, poff,
                             csums[: 4 * (lo - first)]))
            if hi < first + count:
                left.append((hi, first + count - hi,
                             poff + (hi - first) * MIN_ALLOC,
                             csums[4 * (hi - first):]))
            j += 1
        runs[i:j] = left

    def _write_span(self, st: dict, head: dict, offset: int,
                    data: bytes, zero: bool = False) -> None:
        view = memoryview(data).cast("B")
        size = len(view)
        deferred = size <= self.deferred_max
        pos = 0
        while pos < size:
            blk, boff = divmod(offset + pos, MIN_ALLOC)
            whole = 0 if boff else (size - pos) // MIN_ALLOC
            if whole:
                # the aligned middle of the write: whole blocks, taken
                # together (a 512 KiB shard file is one run of 128)
                take = whole * MIN_ALLOC
                if zero:
                    self._cut(st, head, blk, whole)     # punch a hole
                else:
                    self._put_run(st, head, blk, view[pos: pos + take],
                                  deferred)
            else:
                # a head or tail fragment: read-modify-write
                take = min(size - pos, MIN_ALLOC - boff)
                cur = bytearray(self._read_block_raw(st, head, blk))
                if len(cur) < boff + take:
                    cur.extend(b"\x00" * (boff + take - len(cur)))
                cur[boff: boff + take] = view[pos: pos + take]
                if zero and not any(cur):
                    self._cut(st, head, blk, 1)
                else:
                    self._put_block(st, head, blk, cur, deferred)
            pos += take

    def _purge(self, st: dict, cid: str, oid: str) -> None:
        head = self._load_onode(st, cid, oid)
        if head is not None:
            self._cut(st, head, 0)
        st["onodes"][_okey(cid, oid)] = None
        for k in self._omap_items(st, cid, oid):
            st["omaps"][f"{cid}/{oid}/{k}"] = None

    def _copy_object(self, st: dict, src_head: dict, dcid: str,
                     doid: str, omap: dict[str, bytes],
                     take: bool = False) -> None:
        """`take`: the source is about to lose its data (a move; a
        clone whose next op empties or removes the source, as an EC
        shard's rollback stash before a whole rewrite or a delete): the
        copy takes the source's block map, the blocks where they lie,
        and leaves it empty; nothing is read, checked or written.
        Else a copy goes a run at a time, as it was written."""
        self._purge(st, dcid, doid)
        new = {"size": src_head["size"],
               "xattrs": dict(src_head["xattrs"]), "runs": []}
        st["onodes"][_okey(dcid, doid)] = new
        if take:
            new["runs"], src_head["runs"] = src_head["runs"], []
            src_head["size"] = 0
            for k, val in omap.items():
                st["omaps"][f"{dcid}/{doid}/{k}"] = val
            return
        # deferred-vs-direct follows the TOTAL copied size, or a large
        # clone would smuggle its whole body into one KV WAL record
        deferred = src_head["size"] <= self.deferred_max
        for run in list(src_head["runs"]):
            self._put_run(st, new, run[0],
                          self._read_raw(st, run, run[0], run[1]), deferred)
        for k, val in omap.items():
            st["omaps"][f"{dcid}/{doid}/{k}"] = val

    def _omap_items(self, st: dict, cid: str, oid: str) -> dict[str, bytes]:
        prefix = f"{cid}/{oid}/"
        out = {}
        for key, val in self.db.iterate(P_OMAP, prefix,
                                        after_prefix(prefix)):
            out[key[len(prefix):]] = val
        for key, val in st["omaps"].items():
            if key.startswith(prefix):
                k = key[len(prefix):]
                if val is None:
                    out.pop(k, None)
                else:
                    out[k] = val
        return out

    # -- op dispatch -------------------------------------------------------

    def _apply_op(self, op: tuple, st: dict, nxt=None) -> None:
        kind = op[0]
        if kind == "mkcoll":
            _, cid = op
            if cid in self._collections() or cid in st["new_colls"]:
                raise StoreError(EEXIST, f"collection {cid} exists")
            st["new_colls"].add(cid)
            st["kvt"].set(P_COLL, cid, b"1")
        elif kind == "rmcoll":
            _, cid = op
            st["kvt"].rmkey(P_COLL, cid)
            st["new_colls"].discard(cid)
            st["rm_colls"].add(cid)
            # committed objects
            for key, _v in list(self.db.iterate(P_ONODE, f"{cid}/")):
                if not key.startswith(f"{cid}/"):
                    break
                oid = key[len(cid) + 1:]
                self._purge(st, cid, oid)
            # objects staged earlier in this same txn
            for key in [k for k, h in st["onodes"].items()
                        if h is not None and k.startswith(f"{cid}/")]:
                self._purge(st, cid, key[len(cid) + 1:])
        elif kind == "touch":
            self._onode(st, op[1], op[2], create=True)
        elif kind == "write":
            _, cid, oid, offset, data = op
            head = self._onode(st, cid, oid, create=True)
            self._write_span(st, head, offset, data)
            head["size"] = max(head["size"], offset + len(data))
        elif kind == "zero":
            _, cid, oid, offset, length = op
            head = self._onode(st, cid, oid, create=True)
            self._write_span(st, head, offset, b"\x00" * length, zero=True)
            head["size"] = max(head["size"], offset + length)
        elif kind == "truncate":
            _, cid, oid, size = op
            head = self._onode(st, cid, oid, create=True)
            if size < head["size"]:
                self._cut(st, head, (size + MIN_ALLOC - 1) // MIN_ALLOC)
                if size % MIN_ALLOC:
                    blk = size // MIN_ALLOC
                    cur = self._read_block_raw(st, head, blk)
                    kept = cur[: size % MIN_ALLOC]
                    if any(kept):
                        self._put_block(
                            st, head, blk, kept,
                            deferred=len(kept) <= self.deferred_max)
                    elif cur:
                        self._cut(st, head, blk, 1)
            head["size"] = size
        elif kind in ("remove", "try_remove"):
            _, cid, oid = op
            if self._load_onode(st, cid, oid) is None:
                if kind == "remove":
                    raise StoreError(ENOENT, f"remove {cid}/{oid}")
                return
            self._purge(st, cid, oid)
        elif kind in ("clone", "try_clone"):
            _, cid, src, dst = op
            src_head = self._load_onode(st, cid, src)
            if src_head is None:
                if kind == "try_clone":
                    return
                raise StoreError(ENOENT, f"clone src {cid}/{src}")
            omap = self._omap_items(st, cid, src)
            # clone + truncate-to-nothing, clone + remove: a rename of
            # the data
            take = nxt is not None and src != dst and (
                (nxt[0] == "truncate" and tuple(nxt[1:]) == (cid, src, 0))
                or (nxt[0] in ("remove", "try_remove")
                    and tuple(nxt[1:]) == (cid, src)))
            self._copy_object(st, src_head, cid, dst, omap, take=take)
        elif kind == "move":
            _, scid, soid, dcid, doid = op
            src_head = self._load_onode(st, scid, soid)
            if src_head is None:
                raise StoreError(ENOENT, f"move src {scid}/{soid}")
            if dcid not in st["new_colls"] and \
                    dcid not in self._collections():
                raise StoreError(ENOENT, f"no collection {dcid}")
            omap = self._omap_items(st, scid, soid)
            self._copy_object(st, src_head, dcid, doid, omap,
                              take=(scid, soid) != (dcid, doid))
            self._purge(st, scid, soid)
        elif kind == "setattr":
            _, cid, oid, name, value = op
            self._onode(st, cid, oid, create=True)["xattrs"][name] = value
        elif kind == "rmattr":
            _, cid, oid, name = op
            self._onode(st, cid, oid, create=False)["xattrs"].pop(name, None)
        elif kind == "omap_set":
            _, cid, oid, kvs = op
            self._onode(st, cid, oid, create=True)
            for k, v in kvs.items():
                st["omaps"][f"{cid}/{oid}/{k}"] = v
        elif kind == "omap_rm":
            _, cid, oid, keys = op
            self._onode(st, cid, oid, create=False)
            for k in keys:
                st["omaps"][f"{cid}/{oid}/{k}"] = None
        elif kind == "omap_clear":
            _, cid, oid = op
            self._onode(st, cid, oid, create=False)
            for k in self._omap_items(st, cid, oid):
                st["omaps"][f"{cid}/{oid}/{k}"] = None
        else:
            raise StoreError(22, f"blockstore: unknown op {kind!r}")

    # -- reads -------------------------------------------------------------

    def _committed_onode(self, cid: str, oid: str) -> dict:
        head = self._committed(_okey(cid, oid))
        if head is None:
            raise StoreError(ENOENT, f"no object {cid}/{oid}")
        return head

    def _read_span(self, head: dict, offset: int, end: int,
                   out: memoryview, what: str) -> None:
        """Bytes [offset, end) of an object into `out`, a device read a
        run: whole blocks land where they belong in `out`, a first or
        last block the request takes part of goes through a buffer of
        its run's, and what no run holds reads as zeros."""
        runs = head["runs"]
        first, last = offset // MIN_ALLOC, (end - 1) // MIN_ALLOC
        pos = offset
        for i in range(_run_index(runs, first), len(runs)):
            blk, n, poff, csums = runs[i]
            if blk > last:
                break
            lo, hi = max(blk, first), min(blk + n, last + 1)
            want = max(offset, lo * MIN_ALLOC), min(end, hi * MIN_ALLOC)
            if want[0] > pos:
                out[pos - offset: want[0] - offset] = \
                    b"\x00" * (want[0] - pos)
            at, sums = poff + (lo - blk) * MIN_ALLOC, \
                csums[4 * (lo - blk): 4 * (hi - blk)]
            dest = out[want[0] - offset: want[1] - offset]
            if len(dest) == (hi - lo) * MIN_ALLOC:
                self._read_verified(at, sums, lo, what, into=dest)
            else:
                data = memoryview(self._read_verified(at, sums, lo, what))
                dest[:] = data[want[0] - lo * MIN_ALLOC:
                               want[1] - lo * MIN_ALLOC]
            pos = want[1]
        if pos < end:
            out[pos - offset:] = b"\x00" * (end - pos)

    def _read(self, cid: str, oid: str, offset: int, length: int,
              into: memoryview | None):
        """`read` (the bytes) and `read_into` (how many went into the
        caller's buffer)."""
        self._maybe_eio(oid)
        with self._lock:
            head = self._committed_onode(cid, oid)
            size = head["size"]
            if into is not None:
                length = len(into)
            elif length == 0:
                length = max(0, size - offset)
            end = min(offset + length, size)
            if end <= offset:
                return b"" if into is None else 0
            if into is not None:
                into = into[: end - offset]
            what = f"{cid}/{oid}"
            self.counters["reads"] += 1
            # Where ONE run holds the request (every read of a shard
            # file: 128 blocks as one COW write laid them) it is one
            # device read, one native call for the checksums and one
            # compare, and where whole blocks are asked for the read's
            # buffer is the answer: a read and a CRC a block are two
            # calls a block that each give the GIL up, and on a host
            # whose OSDs share one interpreter every such call hands
            # it round (a 4 ms shard read took 440 ms with sixteen
            # degraded reads in flight, chip run, PR 28), and a copy
            # of 512 KiB holds it.  Every block is still checked
            # against its own checksum.
            runs = head["runs"]
            first, last = offset // MIN_ALLOC, (end - 1) // MIN_ALLOC
            i = _run_index(runs, first)
            whole = offset % MIN_ALLOC == 0 and \
                end - offset == (last + 1 - first) * MIN_ALLOC
            if (whole or into is None) and i < len(runs) \
                    and runs[i][0] <= first \
                    and last < runs[i][0] + runs[i][1]:
                blk, _n, poff, csums = runs[i]
                data = self._read_verified(
                    poff + (first - blk) * MIN_ALLOC,
                    csums[4 * (first - blk): 4 * (last + 1 - blk)],
                    first, what, into=into)
                if whole:
                    self.counters["reads_whole_run"] += 1
                    return data if into is None else len(data)
                lo = offset - first * MIN_ALLOC
                return data[lo: lo + end - offset]
            if into is not None:
                self._read_span(head, offset, end, into, what)
                return len(into)
            out = bytearray(end - offset)
            self._read_span(head, offset, end, memoryview(out), what)
            return bytes(out)

    def read(self, cid: str, oid: str, offset: int = 0,
             length: int = 0) -> bytes:
        return self._read(cid, oid, offset, length, None)

    def read_into(self, cid: str, oid: str, buf, offset: int = 0) -> int:
        """Read from `offset` on into the caller's buffer, as much as
        it takes or the object has, and say how much that was: where
        whole blocks are asked for, the device read lands there and
        nowhere else."""
        return self._read(cid, oid, offset, 0, memoryview(buf).cast("B"))

    def stat(self, cid: str, oid: str) -> dict:
        with self._lock:
            return {"size": self._committed_onode(cid, oid)["size"]}

    def exists(self, cid: str, oid: str) -> bool:
        with self._lock:
            return self._committed(_okey(cid, oid)) is not None

    def getattr(self, cid: str, oid: str, name: str) -> bytes:
        with self._lock:
            xattrs = self._committed_onode(cid, oid)["xattrs"]
            if name not in xattrs:
                raise StoreError(ENOENT, f"no xattr {name}")
            return xattrs[name]

    def getattrs(self, cid: str, oid: str) -> dict[str, bytes]:
        with self._lock:
            return dict(self._committed_onode(cid, oid)["xattrs"])

    def omap_get(self, cid: str, oid: str) -> dict[str, bytes]:
        with self._lock:
            self._committed_onode(cid, oid)
            prefix = f"{cid}/{oid}/"
            out = {}
            for key, val in self.db.iterate(P_OMAP, prefix,
                                            after_prefix(prefix)):
                out[key[len(prefix):]] = val
            return out

    def omap_get_values(self, cid: str, oid: str,
                        keys: Iterable[str]) -> dict[str, bytes]:
        omap = self.omap_get(cid, oid)
        return {k: omap[k] for k in keys if k in omap}

    def list_collections(self) -> list[str]:
        with self._lock:
            return sorted(self._collections())

    def collection_exists(self, cid: str) -> bool:
        with self._lock:
            return cid in self._collections()

    def collection_list(self, cid: str, start: str = "",
                        max_count: int = 0) -> list[str]:
        with self._lock:
            if cid not in self._collections():
                raise StoreError(ENOENT, f"no collection {cid}")
            prefix = f"{cid}/"
            names = []
            # seed the iterator at the resume point, or paging a big
            # collection (backfill/scrub) rescans from the front each
            # page — O(N^2/k) over the whole scan
            for key, _v in self.db.iterate(P_ONODE, prefix + start,
                                           after_prefix(prefix)):
                name = key[len(prefix):]
                if name > start:
                    names.append(name)
                    if max_count and len(names) >= max_count:
                        break
            return names
