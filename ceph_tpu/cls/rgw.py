"""cls_rgw: the bucket index, kept inside the OSD that holds the
bucket's index object (cls/rgw/cls_rgw.cc reduced to what the gateway
calls: `rgw_bucket_prepare_op`, `rgw_bucket_complete_op`,
`rgw_bucket_list`, and the index log the sync agent pages).

The index object's omap holds one entry a key, the entry as the
gateway lists it (size, etag, mtime, version id, delete marker) with
two fields of the index's own:

  pending   {tag: op} of the writes PREPARED on the key and not yet
            completed (`rgw_bucket_dir_entry::pending_map`); a gateway
            that dies between the two leaves its tag here
  exists    False for an entry that only holds pending tags: a key
            whose first PUT has not completed is not listed

and, under keys that start with NUL (no S3 key does), the index log:
`\\0bilog.<seq>` -> {op, key, vid, ts} and the counter `\\0bilog_seq`.
prepare, the data ops on the data pool, complete: the reference's
order; complete replaces the entry and appends the log entry in ONE
transaction of the index PG.  The index is one object a bucket
(`rgw_override_bucket_index_max_shards` 0, the default).
"""

from __future__ import annotations

from ..utils import denc
from . import RD, WR, MethodContext, cls_method

META = "\x00"
LOG_PREFIX = "\x00bilog."
LOG_SEQ = "\x00bilog_seq"


def _entry(ctx: MethodContext, key: str) -> dict | None:
    blob = ctx.omap_get([key]).get(key)
    return denc.loads(blob) if blob else None


@cls_method("rgw", "bucket_init_index", WR)
def bucket_init_index(ctx: MethodContext) -> None:
    if not ctx.exists():
        ctx.create()


@cls_method("rgw", "bucket_prepare_op", WR)
def bucket_prepare_op(ctx: MethodContext) -> None:
    req = denc.loads(ctx.input)             # {key, tag, op}
    ent = _entry(ctx, req["key"]) or {"exists": False}
    ent.setdefault("pending", {})[req["tag"]] = req["op"]
    if not ctx.exists():
        ctx.create()
    ctx.omap_set({req["key"]: denc.dumps(ent)})


@cls_method("rgw", "bucket_complete_op", WR)
def bucket_complete_op(ctx: MethodContext) -> None:
    """{key, tag (or None: nothing was prepared), op: "put" | "del" |
    "none" (the entry stays), meta: the entry to list (put), ver: the
    version the data op made the head (or None), log: {op, vid, ts} or
    None}.  Two writers of one key complete in any order: the entry
    follows the head, so an op whose `ver` is below the entry's leaves
    the entry as it is (`rgw_bucket_complete_op`'s epoch check)."""
    req = denc.loads(ctx.input)
    key = req["key"]
    cur = _entry(ctx, key) or {}
    pending = dict(cur.get("pending", {}))
    pending.pop(req.get("tag"), None)
    if not ctx.exists():
        ctx.create()
    sets: dict = {}
    ver = req.get("ver")
    if ver is not None and cur.get("ver") is not None \
            and tuple(cur["ver"]) > tuple(ver):
        cur["pending"] = pending
        sets[key] = denc.dumps(cur)
    elif req["op"] == "put":
        ent = dict(req["meta"])
        if pending:
            ent["pending"] = pending
        if ver is not None:
            ent["ver"] = list(ver)
        sets[key] = denc.dumps(ent)
    elif req["op"] == "del":
        if pending:
            sets[key] = denc.dumps({"exists": False, "pending": pending})
        elif cur:
            ctx.omap_rm([key])
    log = req.get("log")
    if log is not None:
        blob = ctx.omap_get([LOG_SEQ]).get(LOG_SEQ)
        seq = (int(blob) if blob else 0) + 1
        sets[LOG_SEQ] = str(seq).encode()
        sets[f"{LOG_PREFIX}{seq:020d}"] = denc.dumps(
            {"op": log["op"], "key": key, "vid": log.get("vid"),
             "ts": log.get("ts", "")})
    if sets:
        ctx.omap_set(sets)


@cls_method("rgw", "bucket_list", RD)
def bucket_list(ctx: MethodContext) -> bytes:
    """{marker, prefix, max} -> {"entries": {key: entry}, "truncated"}:
    the keys after `marker` that begin with `prefix`, in order, those
    that exist, `max` at most."""
    req = denc.loads(ctx.input)
    want = int(req.get("max", 1000))
    marker, prefix = req.get("marker", ""), req.get("prefix", "")
    out: dict = {}
    truncated = False
    while not truncated:
        asked = want - len(out) + 1
        page = ctx.omap_get_vals(marker, prefix, asked)
        for k in sorted(page):
            ent = None if k.startswith(META) else denc.loads(page[k])
            if ent is not None and ent.get("exists", True):
                if len(out) == want:
                    truncated = True
                    break
                out[k] = ent
            marker = k
        if len(page) < asked:
            break
    return denc.dumps({"entries": out, "truncated": truncated})


@cls_method("rgw", "bilog_list", RD)
def bilog_list(ctx: MethodContext) -> bytes:
    """{marker: seq, max} -> [{seq, op, key, vid, ts}] after `marker`."""
    req = denc.loads(ctx.input)
    page = ctx.omap_get_vals(f"{LOG_PREFIX}{int(req['marker']):020d}",
                             LOG_PREFIX, int(req.get("max", 1000)))
    return denc.dumps([dict(denc.loads(page[k]),
                            seq=int(k[len(LOG_PREFIX):]))
                       for k in sorted(page)])
