"""PG log + object naming helpers (osd/PGLog.{h,cc} and the
hobject_t naming conventions reduced).

Split out of pg.py along the reference's file boundary: the log is a
standalone value type the OSD, the backends and the tools all consume.
"""

from __future__ import annotations

from ..store.objectstore import StoreError
from ..utils import denc

HINFO_KEY = "_hinfo"        # per-shard cumulative crc xattr (EC)
VER_KEY = "_v"              # per-object version xattr
SNAPSET_KEY = "_snapset"    # head/snapdir snapshot metadata (SnapSet)
WHITEOUT_KEY = "_wo"        # cache tier: object logically deleted here
DIRTY_KEY = "_dirty"        # cache tier: differs from the base copy


def clone_oid(oid: str, snapid: int) -> str:
    """Clone object for state as of snap `snapid` (hobject_t snap)."""
    return f"{oid}@{snapid}"


def snapdir_oid(oid: str) -> str:
    """Holds the SnapSet once the head is deleted but clones remain."""
    return f"{oid}@dir"

ZERO_EV = (0, 0)


def shard_oid(oid: str, shard: int) -> str:
    return f"{oid}.s{shard}"


def _parse_ev(blob: bytes) -> tuple | None:
    """Parse a VER_KEY xattr (repr of an (epoch, v) tuple)."""
    import ast
    try:
        ev = ast.literal_eval(blob.decode())
    except (ValueError, SyntaxError, UnicodeDecodeError):
        return None
    return tuple(ev) if isinstance(ev, tuple) else None


# _pgmeta attrs shared by the OSD (pg.py persistence) and the offline
# tools (pglog_dump): ONE encoding, one decoder
BACKFILL_ATTR = "backfilling"   # "@<name>" watermark; legacy b"1" = ""
LES_ATTR = "les"                # last_epoch_started stamp


def encode_backfill_attr(watermark: str) -> bytes:
    return b"@" + watermark.encode()


def decode_backfill_attr(blob: bytes) -> str:
    """Watermark from the persisted attr (legacy b"1" flag reads as
    "nothing restored yet")."""
    return (blob[1:].decode("utf-8", "replace")
            if blob.startswith(b"@") else "")


def stash_oid(soid: str, ev: tuple) -> str:
    """Rollback stash name for a shard object at a given version.

    The '@' marker keeps stashes out of listings/scrubs — the analog of
    the reference's rollback generations (osd/ECTransaction.h:201:
    generate_transactions emits stash/rename ops whose objects carry a
    generation suffix)."""
    return f"{soid}@{ev[0]}.{ev[1]}"


# The stored form of a PGLog: keys under the omap of <cid>/_pgmeta, so
# that a write puts the keys that changed and nothing else (the
# reference's PGLog::_write_log_and_missing: one key an entry, named by
# eversion_t::get_key_name so that key order is version order; the
# dirty entries written, the trimmed ones removed).
PGMETA = "_pgmeta"
LOG_ATTR = "log"            # the old form: one blob, read and converted
LOG_META_KEY = "log_meta"   # denc {"v": LOG_FORMAT, "tail": ev}
LOG_FORMAT = 1
ENTRY_PREFIX = "log."       # log.<epoch:010d>.<v:020d> -> denc entry
INDEX_PREFIXES = (("obj.", "objects"), ("del.", "deleted"),
                  ("mis.", "missing"))   # <prefix><oid> -> b"<epoch>.<v>"


def entry_key(ev: tuple) -> str:
    return f"{ENTRY_PREFIX}{ev[0]:010d}.{ev[1]:020d}"


def _is_log_key(key: str) -> bool:
    return key == LOG_META_KEY or key.startswith(ENTRY_PREFIX) or \
        any(key.startswith(p) for p, _ in INDEX_PREFIXES)


class _Index(dict):
    """An oid -> ev map of the log that remembers which oids changed
    since the log was last persisted, whoever changed them."""

    __slots__ = ("touched",)

    def __init__(self, *args):
        super().__init__(*args)
        self.touched: set[str] = set()

    def __setitem__(self, oid, ev):
        self.touched.add(oid)
        dict.__setitem__(self, oid, ev)

    def __delitem__(self, oid):
        self.touched.add(oid)
        dict.__delitem__(self, oid)

    def pop(self, oid, *default):
        if oid in self:
            self.touched.add(oid)
        return dict.pop(self, oid, *default)

    def _whole(self, *args, **kw):
        raise TypeError("a log index changes one oid at a time")

    update = clear = popitem = setdefault = __ior__ = _whole


class PGLog:
    """Bounded per-PG op log + object version index (osd/PGLog.{h,cc}).

    Entries are dicts:
      {"ev": (epoch, v), "oid": str, "op": "modify"|"delete",
       "prior": (epoch, v) | None,      # object's previous version
       "rollback": {"type": "stash"} | None,   # EC: how to undo
       "shard": int | None}             # EC: local shard at apply time

    Versions are eversion_t analogs (osd/osd_types.h): (epoch of the
    primary's interval, per-pg counter), compared lexicographically —
    entries minted by primaries of different intervals order correctly
    and same-counter divergence is detectable.

    The log is BOUNDED: `entries` covers the ev range (tail, head].
    Trimming advances `tail`; peering exchanges only (head, tail) and
    on-demand entry deltas (entries_since), never whole object maps —
    the reference's core scaling property (osd/PGLog.h:1: delta
    recovery from a bounded log; peers behind `tail` must backfill).
    `objects`/`deleted` remain as the LOCAL have-index only.

    `missing` is the pg_missing_t analog: objects whose log entry is
    CLAIMED here (merged from an auth log, or re-exposed by a
    divergent rewind that could not restore bytes locally) but whose
    data has not landed yet — recovery pulls exactly this set and
    `record_recovered` retires it.
    """

    MAX_ENTRIES = 2000

    def __init__(self, max_entries: int | None = None):
        self._entries: list[dict] = []
        self.objects: dict[str, tuple] = _Index()       # oid -> ev
        self.deleted: dict[str, tuple] = _Index()       # oid -> ev
        self.missing: dict[str, tuple] = _Index()       # oid -> needed ev
        self.tail: tuple = ZERO_EV      # entries cover (tail, head]
        self.max_entries = int(max_entries or self.MAX_ENTRIES)
        # what changed since the log was last handed to a transaction
        # (persist_log drains it): evs whose key is to be put, evs
        # whose key is to be removed, the oids the three indexes
        # remember themselves, and the tail against the one stored
        self._put: set[tuple] = set()
        self._cut: set[tuple] = set()
        self._stored_tail: tuple | None = None   # None: no log_meta yet
        # every key is to be written anew: the entries were replaced
        # from outside, or the log was read from the old blob
        self._whole = False
        # handed to a transaction that has not been seen to apply
        self._unconfirmed = False
        # ev -> the entry's denc bytes: an entry is encoded once, when
        # it is first persisted, however often its key is written
        self._enc: dict[tuple, bytes] = {}

    @property
    def entries(self) -> list[dict]:
        return self._entries

    @entries.setter
    def entries(self, entries: list[dict]) -> None:
        """The window replaced from outside (a backfill adopting the
        primary's): nothing is known of what the store holds of it."""
        self._entries = entries
        self._enc.clear()
        self._whole = True

    def add(self, entry: dict) -> None:
        ev = tuple(entry["ev"])
        oid = entry["oid"]
        entry = dict(entry)
        entry["ev"] = ev
        if entry.get("prior") is not None:
            entry["prior"] = tuple(entry["prior"])
        entries = self._entries
        if entries and ev < entries[-1]["ev"]:
            # late delivery (sub-op resend raced a newer op): insert
            # in ev order — an appended stale entry would regress head
            # (the peering last_update vote) and break the monotonic
            # iteration _trim_rollback and _already_applied rely on
            idx = len(entries)
            while idx > 0 and entries[idx - 1]["ev"] > ev:
                idx -= 1
            entries.insert(idx, entry)
        else:
            entries.append(entry)
        # stored keys sort as evs do: a late entry is one more put
        self._put.add(ev)
        self._cut.discard(ev)
        self._enc.pop(ev, None)
        # the version index tracks the NEWEST op per object; a stale
        # entry must not clobber it
        if entry["op"] == "delete":
            if ev > self.deleted.get(oid, ZERO_EV):
                self.deleted[oid] = ev
            if ev >= self.objects.get(oid, ZERO_EV):
                self.objects.pop(oid, None)
            if ev >= self.missing.get(oid, ZERO_EV):
                self.missing.pop(oid, None)   # pull superseded by delete
        else:
            if ev >= self.objects.get(oid, ZERO_EV) and \
                    ev > self.deleted.get(oid, ZERO_EV):
                self.objects[oid] = ev
                self.deleted.pop(oid, None)
        if len(entries) > self.max_entries:
            cut = len(entries) - self.max_entries
            self.tail = max(self.tail, entries[cut - 1]["ev"])
            self._forget(entries[:cut])
            self._entries = entries[cut:]

    def _forget(self, gone: list[dict]) -> None:
        """Entries that left the window: their keys are to go too."""
        for e in gone:
            ev = e["ev"]
            self._put.discard(ev)
            self._cut.add(ev)
            self._enc.pop(ev, None)

    def entries_since(self, ev: tuple) -> list[dict] | None:
        """Entries strictly newer than `ev`, oldest first — the
        peering log delta.  None when `ev` predates the tail: the
        delta is unknowable and the peer must backfill."""
        ev = tuple(ev)
        if ev < self.tail:
            return None
        return [e for e in self.entries if e["ev"] > ev]

    def contains(self, ev: tuple) -> bool:
        """True when `ev` names a point in OUR history: an entry at
        exactly ev, the tail boundary itself, or anything below the
        tail (trimmed history is committed history).  A peer whose
        last_update fails this check sits on a DIVERGENT branch — its
        log suffix was minted by a primary whose interval this log
        never merged."""
        ev = tuple(ev)
        if ev <= self.tail:
            return True
        return any(e["ev"] == ev for e in self.entries)

    # -- authoritative-log election (PG::find_best_info) -------------------

    @staticmethod
    def find_best_info(cands: dict) -> object | None:
        """Elect the authoritative log holder over exchanged bounds.

        `cands`: id -> {"last_update": ev, "log_tail": ev,
        "last_epoch_started": int, "in_up": bool}.  The reference's
        ordering (osd/PG.cc find_best_info), reduced:

          1. max last_epoch_started — a peer that actually SERVED a
             later interval beats any stray higher version minted on a
             partitioned branch (the pg_temp race killer: max(lu)
             alone elects the stale branch);
          2. then max last_update;
          3. then the LONGER log tail (smaller tail ev) — more history
             means more peers delta-recover instead of backfilling;
          4. then prefer a member of `up` over an acting-only
             (pg_temp) member, so authority converges onto the copy
             that will survive the pin release;
          5. then the smallest id, for determinism.
        """
        best = None
        best_key = None
        for cid in sorted(cands, key=lambda c: str(c)):
            info = cands[cid]
            key = (int(info.get("last_epoch_started", 0) or 0),
                   tuple(info.get("last_update", ZERO_EV)),
                   # negate the tail ordering: longer log == smaller
                   # tail ev must score HIGHER
                   tuple(-x for x in tuple(
                       info.get("log_tail", ZERO_EV))),
                   bool(info.get("in_up", True)))
            if best_key is None or key > best_key:
                best, best_key = cid, key
        return best

    # -- divergence (PGLog::merge_log / rewind_divergent_log math) ---------

    @staticmethod
    def divergence_point(ref_entries: list[dict],
                         cand_entries: list[dict],
                         ref_tail: tuple) -> tuple[tuple, list[dict]]:
        """Compare a candidate log window against the authoritative
        reference: returns (rewind_to, divergent) where `divergent`
        are the candidate's entries on a branch the reference never
        merged (newest first) and `rewind_to` is the newest shared
        point — truncating the candidate to it drops exactly the
        divergent suffix.  Candidate entries at or below `ref_tail`
        are trusted as committed history (the reference trimmed
        them)."""
        ref_evs = {tuple(e["ev"]) for e in ref_entries}
        ref_tail = tuple(ref_tail)
        shared = ref_tail
        divergent: list[dict] = []
        for e in cand_entries:
            ev = tuple(e["ev"])
            if ev <= ref_tail or ev in ref_evs:
                if ev > shared:
                    shared = ev
            else:
                divergent.append(e)
        if divergent:
            # the rewind point must sit BELOW every divergent ev so
            # truncate_to drops them all; shared entries always do
            # (divergence is a suffix property: once a branch forks,
            # the forked copy can never have merged a later ref entry)
            first_div = min(tuple(e["ev"]) for e in divergent)
            if shared >= first_div:
                # defensive: an interleaved (corrupt) window — rewind
                # below the whole suspect range rather than keeping a
                # mixed history
                shared = max((ev for ev in ref_evs | {ref_tail}
                              if ev < first_div), default=ZERO_EV)
        return shared, list(reversed(sorted(
            divergent, key=lambda e: tuple(e["ev"]))))

    def find_divergence(self, peer_entries: list[dict]
                        ) -> tuple[tuple, list[dict]]:
        """A PEER's divergence vs our (authoritative) log: the rewind
        point we should send it and its divergent entries."""
        return self.divergence_point(self.entries, peer_entries,
                                     self.tail)

    # -- merge (PGLog::merge_log: adopt the auth log's claims) -------------

    def merge_log(self, entries: list[dict],
                  shard: int | None = None) -> dict[str, tuple]:
        """Merge authoritative log entries into this log (the GetLog
        authority proof's second half): every entry is CLAIMED — the
        index advances and modify targets enter `missing` until their
        data lands via recovery.  Returns {oid: ev} of the pulls
        (newest modify per object; deletes apply via the caller's
        store txn and never pull)."""
        pulls: dict[str, tuple] = {}
        # membership set built ONCE: a per-entry contains() scan would
        # make a full-window merge O(len(log) * len(auth)) inside
        # pg.lock — exactly the peering path the flatness gate times
        have = {e["ev"] for e in self.entries}
        for e in entries:
            e = dict(e)
            ev = tuple(e["ev"])
            e["ev"] = ev
            if e.get("prior") is not None:
                e["prior"] = tuple(e["prior"])
            e["shard"] = shard
            if ev <= self.tail or ev in have:
                continue          # already ours (idempotent re-merge)
            have.add(ev)
            self.add(e)
            oid = e["oid"]
            if e["op"] == "delete":
                pulls.pop(oid, None)
                self.missing.pop(oid, None)
            else:
                pulls[oid] = ev
                self.missing[oid] = ev
        return pulls

    # -- divergent rewind (PGLog::rewind_divergent_log) --------------------

    def rewind(self, ev: tuple, on_divergent=None) -> list[dict]:
        """Drop every entry newer than `ev` and repair the version
        index — THE shared divergence core (replicated and EC peering
        both reconcile through here; the reference's
        PGLog::rewind_divergent_log).

        For each divergent entry (newest first) `on_divergent(entry)`
        — the backend's store-level undo — is called and must return
        True when it restored the prior bytes locally (EC rollback
        stash).  When it cannot (replicated pools have no stash), an
        entry with a prior version re-enters `missing` at that prior:
        recovery pulls the authoritative copy.  Returns the divergent
        entries, newest first."""
        ev = tuple(ev)
        divergent = self.truncate_to(ev)
        for e in divergent:
            oid, prior = e["oid"], e.get("prior")
            restored = bool(on_divergent(e)) if on_divergent else False
            if prior is not None:
                self.objects[oid] = prior
                if e["op"] == "delete":
                    self.deleted.pop(oid, None)
                if not restored:
                    self.missing[oid] = prior
            else:
                # divergent create: the object never existed at the
                # rewind point — delete-or-rollback resolves to delete
                self.objects.pop(oid, None)
                self.missing.pop(oid, None)
        # invariant sweep: no index claim may outlive the new head
        for idx in (self.objects, self.deleted):
            for oid in [o for o, v in idx.items() if v > ev]:
                idx.pop(oid, None)
        for oid in [o for o, v in self.missing.items() if v > ev]:
            self.missing.pop(oid, None)
        return divergent

    def note(self, ev: tuple, oid: str, op: str,
             prior: tuple | None = None, rollback: dict | None = None,
             shard: int | None = None) -> dict:
        entry = {"ev": tuple(ev), "oid": oid, "op": op, "prior": prior,
                 "rollback": rollback, "shard": shard}
        self.add(entry)
        return entry

    @property
    def head(self) -> tuple:
        return self._entries[-1]["ev"] if self._entries else ZERO_EV

    def record_recovered(self, ev: tuple, oid: str,
                         shard: int | None = None) -> None:
        """Note an object landed by recovery (push/rebuild) WITHOUT
        regressing the log: recovered versions are usually older than
        head, and appending them would make entries non-monotonic and
        head (our peering last_update vote) lie backwards."""
        ev = tuple(ev)
        if self.deleted.get(oid, ZERO_EV) > ev:
            return    # a stale push must not resurrect a deleted object
        if ev >= self.missing.get(oid, ZERO_EV):
            self.missing.pop(oid, None)
        if ev > self.head:
            self.note(ev, oid, "modify", shard=shard)
            return
        if ev >= self.objects.get(oid, ZERO_EV):
            self.objects[oid] = ev
            self.deleted.pop(oid, None)

    def truncate_to(self, ev: tuple) -> list[dict]:
        """Drop (and return, newest first) entries newer than ev.
        Index fixups are the caller's job — it is applying rollbacks."""
        ev = tuple(ev)
        divergent = [e for e in self._entries if e["ev"] > ev]
        if divergent:
            self._entries = [e for e in self._entries if e["ev"] <= ev]
            self._forget(divergent)
        return list(reversed(divergent))

    def _applied(self) -> None:
        """The transaction the log was last handed to has applied."""
        self._unconfirmed = False

    def encode(self) -> bytes:
        """The log as one blob: what the tools and the tests pass
        around, and what a store written before the keyed form holds
        (`load_log` reads it; nothing writes it to a store)."""
        return denc.dumps((self._entries, dict(self.objects),
                           dict(self.deleted), self.tail,
                           dict(self.missing)))

    @staticmethod
    def decode(blob: bytes,
               max_entries: int | None = None) -> "PGLog":
        log = PGLog(max_entries=max_entries)
        fields = denc.loads(blob)
        entries, objects, deleted = fields[0], fields[1], fields[2]
        if len(fields) > 3:
            log.tail = tuple(fields[3])
        elif len(entries) >= PGLog.MAX_ENTRIES:
            # legacy 3-field blob at the old cap: the log WAS trimmed
            # but the boundary was not recorded — claim a conservative
            # tail so entries_since never reports a delta that spans
            # the lost range (forcing backfill is safe; a silent gap
            # is not)
            log.tail = tuple(entries[0]["ev"])
        else:
            log.tail = ZERO_EV
        for e in entries:
            e = dict(e)
            e["ev"] = tuple(e["ev"])
            if e.get("prior") is not None:
                e["prior"] = tuple(e["prior"])
            log._entries.append(e)
        log.objects = _Index((o, tuple(v)) for o, v in objects.items())
        log.deleted = _Index((o, tuple(v)) for o, v in deleted.items())
        if len(fields) > 4:
            log.missing = _Index((o, tuple(v))
                                 for o, v in fields[4].items())
        # whatever store this blob came from holds none of its keys
        log._whole = True
        return log


# -- the stored form: keys under _pgmeta's omap ----------------------------


def _ev_bytes(ev: tuple) -> bytes:
    return b"%d.%d" % (ev[0], ev[1])


def _ev_from(blob: bytes) -> tuple:
    epoch, _, v = blob.partition(b".")
    return (int(epoch), int(v))


def _entry_bytes(log: PGLog, e: dict) -> bytes:
    blob = log._enc.get(e["ev"])
    if blob is None:
        blob = log._enc[e["ev"]] = denc.dumps(e)
    return blob


def persist_log(log: PGLog, store, cid: str, txn) -> tuple[int, int, bool]:
    """Put what changed in `log` since it was last persisted into
    `txn`, as keys of <cid>/_pgmeta's omap: the log rides in the
    transaction of the data it describes, applied or not with it.
    Returns (keys written, their bytes, whether every key was).

    A write's share is its entry and its oid's index key, and at the
    bound the new tail and the cut entry's key besides, whatever the
    log holds.  Every key is written anew, over a clean slate, where
    the log does not know what the store holds: read from the old
    blob (which goes in the same transaction), its entries replaced
    from outside, or handed to a transaction that was never seen to
    apply.

    The order is what a commit torn at a row boundary leaves behind:
    index keys, then the tail, then entries oldest first, so that a
    log entry never lands without what it claims."""
    whole = log._whole or log._unconfirmed
    sets: dict[str, bytes] = {}
    rms: set[str] = {entry_key(ev) for ev in log._cut}
    for prefix, name in INDEX_PREFIXES:
        index = getattr(log, name)
        for oid in (index.touched.union(index) if whole
                    else index.touched):
            ev = index.get(oid)
            if ev is None:
                rms.add(prefix + oid)
            else:
                sets[prefix + oid] = _ev_bytes(ev)
        index.touched.clear()
    if whole or log.tail != log._stored_tail:
        sets[LOG_META_KEY] = denc.dumps({"v": LOG_FORMAT,
                                         "tail": log.tail})
        log._stored_tail = log.tail
    if whole:
        for e in log._entries:
            sets[entry_key(e["ev"])] = _entry_bytes(log, e)
        try:
            rms.update(k for k in store.omap_get(cid, PGMETA)
                       if _is_log_key(k))
        except StoreError:
            pass        # no _pgmeta yet: nothing to clear
    elif log._put:
        head = log._entries[-1] if log._entries else None
        if head is not None and log._put == {head["ev"]}:
            sets[entry_key(head["ev"])] = _entry_bytes(log, head)
        else:                       # a merge, a late entry
            put = log._put
            for e in log._entries:
                if e["ev"] in put:
                    sets[entry_key(e["ev"])] = _entry_bytes(log, e)
    rms.difference_update(sets)
    log._put.clear()
    log._cut.clear()
    log._whole = False
    log._unconfirmed = True
    # (even when empty: it makes the _pgmeta the lines below need)
    txn.omap_setkeys(cid, PGMETA, sets)
    if rms:
        txn.omap_rmkeys(cid, PGMETA, sorted(rms))
    if whole:
        txn.rmattr(cid, PGMETA, LOG_ATTR)   # the old blob, if it is there
    txn.register_on_applied(log._applied)
    return len(sets), sum(map(len, sets.values())), whole


def load_log(store, cid: str,
             max_entries: int | None = None) -> PGLog | None:
    """The log a store holds for a PG: its keys where they are, else
    the old blob (whose first persist lays the keys down and removes
    it), else None."""
    try:
        omap = store.omap_get(cid, PGMETA)
    except StoreError:
        return None
    if LOG_META_KEY not in omap:
        try:
            blob = store.getattr(cid, PGMETA, LOG_ATTR)
        except StoreError:
            return None
        return PGLog.decode(blob, max_entries=max_entries)
    meta = denc.loads(omap[LOG_META_KEY])
    if meta["v"] > LOG_FORMAT:
        raise denc.DencError(f"{cid}: pg log format {meta['v']} is "
                             f"newer than this code's {LOG_FORMAT}")
    log = PGLog(max_entries=max_entries)
    log.tail = log._stored_tail = tuple(meta["tail"])
    indexes = {p: getattr(log, name) for p, name in INDEX_PREFIXES}
    for key in sorted(omap):
        prefix = key[:4]            # the four prefixes are as long
        if prefix == ENTRY_PREFIX:
            e = denc.loads(omap[key])
            log._entries.append(e)
            log._enc[e["ev"]] = omap[key]
        elif prefix in indexes:
            dict.__setitem__(indexes[prefix], key[4:], _ev_from(omap[key]))
    return log
