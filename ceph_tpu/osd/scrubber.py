"""OSD scrub service: scheduled + commanded scrubs and repair.

Mixin half of the OSD daemon: interval-driven scrub scheduling
(OSD::sched_scrub, osd/OSD.cc:1054), shallow/deep scans (EC deep
scans batch shard CRCs through the fused device pass — the north
star's scrub-sized batches), authoritative-copy repair
(PGBackend.cc:501 be_select_auth_object) and EC shard rebuild repair
(test/osd/osd-scrub-repair.sh scenarios).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np

from ..crush.map import ITEM_NONE
from ..ops import crc32c as crc_mod
from ..store.objectstore import StoreError, Transaction
from ..utils import denc, optracker
from .messages import MPGInfo
from .pg import HINFO_KEY, PG, VER_KEY, shard_oid


# The daemons of one process share one interpreter.  A scan is short
# stretches of Python between calls that give the interpreter up (a KV
# row, a device read, a checksum batch), and scans that run side by
# side hand it round at every one of them: the eleven scans of one PG
# scrub, started together, took 80-100 ms each where one alone takes
# 10, and the scrub was slower than with one scan after another
# (PERF.md §6, PR 43).  So the host part of a scan (list, fold, read,
# stack, submit) takes turns, process-wide; what a scan WAITS for, its
# dispatches on the chip and its answer on the wire, overlaps the
# others' turns.
_HOST_TURN = threading.Lock()


class ScrubService:
    def _sched_scrub(self, now: float) -> None:
        """Interval-driven scrubs (OSD::sched_scrub under
        sched_scrub_lock, osd/OSD.cc:1054): each heartbeat tick kicks
        up to osd_max_scrubs primary PGs whose stamps are past
        osd_scrub_min_interval (shallow) or osd_deep_scrub_interval
        (deep), gated on client load — a busy OSD defers."""
        if self._stopped:
            return
        load = self.op_tracker.dump_ops_in_flight()["num_ops"]
        if load >= int(self.conf.osd_scrub_load_threshold):
            return
        min_iv = float(self.conf.osd_scrub_min_interval)
        deep_iv = float(self.conf.osd_deep_scrub_interval)
        repair = bool(self.conf.osd_scrub_auto_repair)
        with self.pg_lock:
            pgs = list(self.pgs.values())
        for pg in pgs:
            if not pg.acting or pg.acting[0] != self.whoami \
                    or not getattr(pg, "active", False):
                continue
            deep = now - pg.last_deep_scrub_stamp >= deep_iv
            if not deep and now - pg.last_scrub_stamp < min_iv:
                continue
            # acquire the slot BEFORE stamping: a PG stamped by a
            # loser-of-the-race would silently skip its whole interval
            if not self._scrub_slots.acquire(blocking=False):
                break
            # stamp optimistically: a failing scrub must not re-fire
            # every tick (the next interval retries it)
            pg.last_scrub_stamp = now
            if deep:
                pg.last_deep_scrub_stamp = now

            def run(pg=pg, deep=deep):
                # dedicated thread: a scrub blocks on replica round-
                # trips, so it must neither occupy an op-queue shard
                # (cross-OSD shard deadlock when every OSD schedules
                # at once) nor run in the timer thread
                try:
                    result = pg.scrub(deep=deep, repair=repair)
                    self.log.info("scheduled %sscrub %s: %s",
                                  "deep-" if deep else "", pg.pgid,
                                  result)
                except Exception as e:
                    self.log.warn("scheduled scrub %s failed: %s",
                                  pg.pgid, e)
                finally:
                    self._scrub_slots.release()

            threading.Thread(target=run, daemon=True,
                             name=f"osd{self.whoami}-scrub").start()

    # -- scrub + repair ----------------------------------------------------

    def _scan_pg(self, pg: PG, deep: bool) -> dict:
        """Local scrub scan: {oid_or_shard: (size, crc|None)}."""
        if pg.is_ec and deep:
            return self._scan_ec_deep(pg)
        with _HOST_TURN:
            out = {}
            for name in self._scan_list(pg):
                if name.startswith("_pgmeta") or "@" in name:
                    continue          # pg meta + EC rollback stashes
                try:
                    data = self.store.read(pg.cid, name)
                except StoreError:
                    continue
                crc = crc_mod.crc32c(0, data) if deep else None
                out[name] = (len(data), crc)
            return out

    def _scan_list(self, pg: PG) -> list[str]:
        with optracker.span("scrub.list") as note:
            try:
                names = self.store.collection_list(pg.cid)
            except StoreError:
                names = []
            note["names"] = len(names)
        return names

    def _scan_ec_deep(self, pg: PG) -> dict:
        """TPU-batched shard verification through the shared EC device
        pipeline: shards group by size, every group's CRC batches are
        submitted up front (overlapped dispatches), results gather at
        the end (the north-star scrub path).  The concurrency there is:
        the acting OSDs' scans of ONE PG scrub run side by side
        (`_gather_scans`).  Where they share a process they take turns
        at the host part (`_HOST_TURN`), so one scan's dispatch and
        answer overlap the next one's reads, and two batches of a shard
        size share a dispatch only where they are submitted within the
        pipeline's coalesce window; PGs are still scrubbed
        `osd_max_scrubs` at a time.

        HBM-cache fast path first: an object whose encoded stripes
        still sit on a chip (committed at the object's current
        version, store-coherent — any non-attested shard mutation
        dropped the entry) has its shard CRC folded from the entry's
        per-stripe chunk CRCs: a host-side carry-less combine of
        4-byte values, ZERO bytes re-uploaded, zero device dispatches.
        Corrupted or out-of-band-mutated shards always miss and take
        the full read+fold path below."""
        from ..ops import hbm_cache
        from ..ops import pipeline as ec_pipeline
        from . import ecutil
        out = {}
        cached_folds: dict[str, list[int] | None] = {}

        def cache_folds(base: str):
            """Per-shard folded CRCs for `base` from the HBM cache
            (None = miss; memoized per scan so k+m shard files cost
            one lookup)."""
            if base in cached_folds:
                return cached_folds[base]
            folds = None
            with pg.lock:
                cur = pg.pglog.objects.get(base)
            if cur is not None:
                ent = hbm_cache.get().lookup(pg.cid, base,
                                             version=tuple(cur))
                if ent is not None:
                    # the entry keeps chunks; a shard file is held to
                    # the CRC of the chunk its position holds
                    by_chunk = ecutil.fold_shard_crcs(ent.crcs,
                                                      ent.chunk_size)
                    folds = [by_chunk[c] for c in
                             ecutil.shard_chunks(pg._ec_codec())]
            cached_folds[base] = folds
            return folds

        batch_max = int(self.conf.osd_deep_scrub_stripe_batch)
        pipe = ec_pipeline.get()
        pending: list = []

        def collect_one() -> None:
            size, rows, arr, fut = pending.pop(0)
            with optracker.span("scrub.collect") as note:
                try:
                    _path, (crcs,) = fut.result(
                        ec_pipeline.RESULT_TIMEOUT)
                except FuturesTimeout:
                    # wedged pipeline (hung device fetch): self-serve
                    # the fold on host — same bytes, same CRCs
                    pipe.note_result_timeout()
                    crcs = crc_mod.crc32c_batch(arr)
                    note["timeouts"] = 1
            # the dispatch's own phases (coalesce, H2D, compute, D2H),
            # as ecutil notes them for writes
            optracker.note_pipeline_phases(
                getattr(fut, "trace_phases", None))
            for (name, expected), got in zip(rows, crcs):
                out[name] = (size, bool(int(got) == expected))

        with _HOST_TURN:
            names = self._scan_list(pg)
            # two passes so that each is one span: first what the HBM
            # cache can answer (lookup, fold, stat, getattr), then the
            # store reads of everything else
            to_read: list[str] = []
            with optracker.span("scrub.cache_fold") as note:
                hits = 0
                for name in names:
                    if name.startswith("_pgmeta") or "@" in name:
                        continue          # pg meta + EC rollback stashes
                    base, _, sfx = name.rpartition(".s")
                    folds = cache_folds(base) if sfx.isdigit() else None
                    if folds is None or int(sfx) >= len(folds):
                        to_read.append(name)
                        continue
                    try:
                        size = self.store.stat(pg.cid, name)["size"]
                        hinfo = denc.loads(self.store.getattr(
                            pg.cid, name, HINFO_KEY))
                    except StoreError:
                        continue
                    out[name] = (size, bool(folds[int(sfx)]
                                            == hinfo["crc"]))
                    hits += 1
                note.update(shards=hits, objects=sum(
                    1 for f in cached_folds.values() if f is not None))
            with optracker.span("scrub.read") as note:
                # a batch is made where its files are read: each file
                # straight into its row, no copy of it after the
                # store's (a file another size than its `stat` said,
                # or one that does not read, is left out of the scan)
                before = self.store.journal_stats()
                by_size: dict[int, list[str]] = {}
                for name in to_read:
                    try:
                        size = self.store.stat(pg.cid, name)["size"]
                    except StoreError:
                        continue
                    by_size.setdefault(size, []).append(name)
                batches = []
                for size, group in by_size.items():
                    for i in range(0, len(group), batch_max):
                        part = group[i:i + batch_max]
                        arr = np.empty((len(part), size), dtype=np.uint8)
                        rows = []
                        for name in part:
                            try:
                                if self.store.read_into(
                                        pg.cid, name, arr[len(rows)]) != size:
                                    continue
                                hinfo = denc.loads(self.store.getattr(
                                    pg.cid, name, HINFO_KEY))
                            except StoreError:
                                continue
                            rows.append((name, hinfo["crc"]))
                        if rows:
                            batches.append((size, rows, arr[:len(rows)]))
                note.update(shards=sum(len(b[1]) for b in batches),
                            bytes=sum(b[2].size for b in batches))
                after = self.store.journal_stats()
                note.update({k: after[k] - before[k] for k in
                             ("reads", "reads_whole_run") if k in after})
            for size, rows, arr in batches:
                if size == 0:
                    for name, expected in rows:
                        out[name] = (0, 0 == expected)
                    continue
                chan = ec_pipeline.crc_channel(size,
                                               max_coalesce=batch_max)
                with optracker.span("scrub.stack", batches=1,
                                    bytes=arr.size):
                    pending.append((size, rows, arr,
                                    pipe.submit(chan, arr)))
                # sliding window: keep a handful of batches in flight
                # for dispatch overlap without queueing a second copy
                # of the whole PG's shard bytes at once (a wait:
                # the turn goes to another scan meanwhile)
                if len(pending) >= 8:
                    _HOST_TURN.release()
                    try:
                        collect_one()
                    finally:
                        _HOST_TURN.acquire()
        while pending:
            collect_one()
        return out

    # how long a PG scrub waits for its peers' scans, from the asks
    SCAN_TIMEOUT = 20.0

    def _gather_scans(self, pg: PG, deep: bool) -> dict:
        """{osd: scan} of the acting set, as PG::chunky_scrub goes
        about it: ask every live acting OSD but this one for its scan
        (NEW_CHUNK's _request_scrub_map to every replica), scan our
        own shards while they work (BUILD_MAP), then wait once for all
        the answers (WAIT_REPLICAS), so the peers' scans run side by
        side (in one process: `_HOST_TURN`).

        The scrub's trace id rides each request, so every peer's
        `scrub_scan` op carries it.  ONE `scrub.peer_wait` span a PG
        scrub, from the end of our own scan to the last answer (or to
        SCAN_TIMEOUT after the asks): `peers` asked, `answered`,
        `late` (no answer by then; such a peer is left out of the
        result, the others' scans are used)."""
        trk = optracker.current()
        asks = {osd_id: MPGInfo(
            op="scan", pgid=str(pg.pgid), deep=deep,
            trace=getattr(trk, "trace_id", "") or "",
            epoch=self.osdmap.epoch)
            for osd_id in pg.acting_live()
            if osd_id != self.whoami
            and self.osdmap.get_addr(osd_id) is not None}
        deadline = time.monotonic() + self.SCAN_TIMEOUT
        tids = self._send_calls(asks)
        try:
            scans = {self.whoami: self._scan_pg(pg, deep)}
        except BaseException:
            self._wait_calls(tids, 0.0)     # forget the asks
            raise
        with optracker.span("scrub.peer_wait", peers=len(tids)) as note:
            replies = self._wait_calls(tids, deadline)
            note.update(answered=len(replies),
                        late=len(tids) - len(replies))
        for osd_id, reply in replies.items():
            scans[osd_id] = reply.info
        return scans

    def scrub_replicated_pg(self, pg: PG, deep: bool) -> dict:
        scans = self._gather_scans(pg, deep)
        inconsistent = []
        all_names = set()
        with optracker.span("scrub.compare") as note:
            for scan in scans.values():
                all_names.update(scan)
            for name in sorted(all_names):
                variants = {osd: scan.get(name)
                            for osd, scan in scans.items()}
                vals = set(variants.values())
                if len(vals) > 1:
                    inconsistent.append({"object": name,
                                         "copies": variants})
            note.update(checked=len(all_names),
                        inconsistent=len(inconsistent))
        return {"checked": len(all_names), "inconsistent": inconsistent}

    def scrub_ec_pg(self, pg: PG) -> dict:
        """Each shard OSD verifies its shards against hinfo (deep);
        shards a holder should have but doesn't are flagged too."""
        scans = self._gather_scans(pg, deep=True)
        inconsistent = []
        checked = 0
        bases = set()
        with optracker.span("scrub.compare") as note:
            for osd_id, scan in scans.items():
                for name, (size, ok) in scan.items():
                    checked += 1
                    base, _, sfx = name.rpartition(".s")
                    if sfx.isdigit():
                        bases.add(base)
                    if ok is False:
                        inconsistent.append({"object": name,
                                             "osd": osd_id})
            # a shard FILE a live holder lacks entirely never shows up
            # in its scan: cross-check expected placement (only for
            # holders whose scan we actually have — a scan timeout is
            # not absence)
            for base in bases:
                if base not in pg.pglog.objects:
                    continue
                for shard, holder in enumerate(pg.acting):
                    if holder == ITEM_NONE or holder not in scans:
                        continue
                    name = shard_oid(base, shard)
                    if name not in scans[holder]:
                        inconsistent.append({"object": name,
                                             "osd": holder,
                                             "missing": True})
            note.update(checked=checked, inconsistent=len(inconsistent))
        return {"checked": checked, "inconsistent": inconsistent}

    def repair_replicated_pg(self, pg: PG, inconsistent: list) -> int:
        """Heal scrub findings: majority vote over the scan variants
        picks the authoritative copy (be_select_auth_object reduced —
        the reference prefers digest-clean copies; absent stored
        digests, agreement is the signal), the primary pulls it if a
        peer holds it, then pushes it to every divergent holder.

        Runs WITHOUT pg.lock held (push/fetch replies need it)."""
        my = self.whoami
        repaired = 0
        for item in inconsistent:
            name = item["object"]
            if "@" in name or name.startswith("_pgmeta"):
                continue
            variants = {o: (tuple(v) if v is not None else None)
                        for o, v in item["copies"].items()}
            counts: dict[tuple, list] = {}
            for osd_id, v in variants.items():
                if v is not None:
                    counts.setdefault(v, []).append(osd_id)
            if not counts:
                continue
            auth, holders = max(
                counts.items(), key=lambda kv: (len(kv[1]), my in kv[1]))
            bad = [o for o, v in variants.items() if v != auth]
            with pg.lock:
                version = pg.pglog.objects.get(name, (0, 0))
            if my not in holders:
                reply = self._call(holders[0], MPGInfo(
                    op="fetch_obj", pgid=str(pg.pgid), oid=name,
                    epoch=self.osdmap.epoch), timeout=10.0)
                if reply is None or reply.info.get("missing"):
                    continue
                with pg.lock:
                    txn = Transaction()
                    txn.try_remove(pg.cid, name)
                    txn.touch(pg.cid, name)
                    if reply.info["data"]:
                        txn.write(pg.cid, name, 0, reply.info["data"])
                    for k, v in reply.info["xattrs"].items():
                        txn.setattr(pg.cid, name, k, v)
                    if reply.info["omap"]:
                        txn.omap_setkeys(pg.cid, name,
                                         reply.info["omap"])
                    try:
                        self.store.apply_transaction(txn)
                    except StoreError:
                        continue
                bad = [o for o in bad if o != my]
                self.log.info("repair: pulled auth %s from osd.%d",
                              name, holders[0])
            healed = True
            for osd_id in bad:
                if osd_id != my:
                    # synchronous: the clean_after_repair re-scrub
                    # right after this must observe the healed copy
                    if not self.repair_push_object(pg, osd_id, name,
                                                   version,
                                                   shard=None):
                        healed = False
            if healed:
                repaired += 1
        return repaired

    def repair_ec_pg(self, pg: PG, inconsistent: list) -> int:
        """Shard-granular EC repair: the bad shards of each damaged
        object are the positions a rebuild has lost (the HBM cache's
        rows where it still holds the object, else decoded from what
        the codec's plan reads among the others, the known-bad ones
        never a source) and land in place (osd-scrub-repair.sh
        TEST_corrupt_and_repair_jerasure/lrc scenarios)."""
        by_oid: dict[str, set] = {}
        for item in inconsistent:
            base, _, sfx = item["object"].rpartition(".s")
            if sfx.isdigit():
                by_oid.setdefault(base, set()).add(int(sfx))
        repaired = 0
        for oid, bad_shards in sorted(by_oid.items()):
            targets = [(s, pg.acting[s]) for s in sorted(bad_shards)
                       if s < len(pg.acting)
                       and pg.acting[s] != ITEM_NONE]
            if not targets:
                continue
            with pg.lock:
                version = pg.pglog.objects.get(oid, (0, 0))
            if self._ec_push_shards(pg, oid, version, targets):
                repaired += 1
                continue
            with pg.lock:
                rebuilt = pg._ec_read_local(
                    oid, exclude=bad_shards,
                    want=[s for s, _o in targets])
            if rebuilt is None:
                self.log.warn("repair: %s unrecoverable without "
                              "shards %s", oid, sorted(bad_shards))
                continue
            self._ec_push_shards(pg, oid, version, targets, rebuilt)
            repaired += 1
        return repaired

