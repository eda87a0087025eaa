"""RGW: S3-dialect HTTP object gateway (rgw/rgw_main.cc, rgw_rest_s3.cc,
rgw_rados.cc reduced to the core object workflow), on radosgw's layout.

PLACEMENT (config-ref.rst "Pools").  A gateway runs on three pools:
`index_pool` (bucket roots and metas, one index object a bucket with
its index log, version records, the GC list: omap and cls calls),
`data_extra_pool` (multipart bookkeeping: omap) and `data_pool`, which
holds object DATA and nothing else, and may therefore be erasure-coded
(an EC pool has no omap, runs no class and takes no write at an
offset).  With all three naming one replicated pool the gateway is the
one-pool gateway it was; there is one code path for both.

AN OBJECT (RGWPutObjProcessor_Atomic, RGWObjManifest).  A PUT's bytes
are cut in chunks of `rgw_max_chunk_size` (512 KiB, in whole stripes
of a data pool that asks for alignment: `IoCtx.pool_required_alignment`).
The first chunk is the HEAD object `obj.<bucket>/<key>` (`.v.<vid>`
for a version of a versioned bucket); what follows goes to TAIL
objects `<head>.shadow.<tag>_<n>`, manifest stripe n (1 up) holding
`rgw_obj_stripe_size` (4 MiB) bytes, each written chunk by chunk at the
offset that is its size: a `write_full`, then `append`s (on an EC pool
the O(tail) append path of osd/backend_ec.py).  `<tag>` is fresh for
every write.  The tails are written FIRST; then ONE compound op on the
head (`write_full` of the first chunk + the xattrs `rgw.manifest`,
`rgw.etag`, `rgw.idtag`, `rgw.mtime`), guarded by the tag of the head
it replaces (`cmpxattr`; a writer that lost re-reads and retries),
switches the object: a reader sees the old version whole or the new one
whole.  A GET is one compound read of the head (xattrs + first chunk:
one version, osd/backend_ec.py `_ec_read_attrs`) and then the tail
objects its manifest names, `rgw_get_obj_max_req_size` a request at
most; it reads no index.

THE INDEX (cls/rgw.py, RGWRados::Bucket::UpdateIndex).  `prepare`
marks the key's entry with the write's tag before the first data op,
`complete` replaces the entry (size, etag, mtime, version id) and
appends the index-log entry after the head op, both cls calls on the
bucket's ONE index object (unsharded, the reference's default); it
carries the version the head op made the object, so of two writers of
one key the entry is the one's whose head stands, whichever completes
last (`rgw_bucket_complete_op`'s epoch check).  The
log is served at ``?bilog&marker=N`` and feeds the multisite sync agent
(rgw/sync.py).

GC (rgw_gc.cc reduced, :class:`RGWGC`).  The tail of an overwritten or
removed head is ENQUEUED, after the head switch, on a list on the index
pool and removed by `gc.process()` no sooner than `rgw_gc_obj_min_wait`
(2 h) later; nothing else removes a tail.

Signature auth in both AWS v2 and v4 dialects (auth_v4.py;
rgw/rgw_auth_s3.h:24-32).  Object versioning follows
rgw/rgw_op.h:484-493 (RGWGetBucketVersioning/RGWSetBucketVersioning)
and RGWDeleteObj's delete-marker path: versioned buckets stack
versions per key, a plain DELETE plants a marker, and deleting the
marker restores the previous version.  The Swift v1 dialect
(rgw/swift.py, TempAuth + container/object ops over the SAME namespace)
serves /auth/v1.0 and /v1/* requests that don't carry AWS signatures.

TRACING.  A request is a tracked op of kind `rgw_req` (`PUT
/bucket/key`) with spans `rgw.recv_body`, `rgw.etag`,
`rgw.idx_prepare`, `rgw.put_tail` (args appends, bytes),
`rgw.put_head`, `rgw.idx_complete`, `rgw.get_head`, `rgw.get_tail`
(arg reqs), `rgw.send_body`; the gateway's admin socket (`asok`)
serves `dump_historic_ops` and `perf dump` (block `rgw`: put, get,
put_bytes, get_bytes, tail_appends, overwrites, gc_enqueued,
gc_removed).

DEPARTURES from radosgw that are left: the index is never sharded; a
versioned bucket's current version is the index entry's word (no OLH
object), so a GET there reads the index; CompleteMultipartUpload reads
the parts back and writes the whole through the processor (a copy,
where the reference assembles by manifest); a pending tag left by a
gateway that died is not cleaned up (no dir_suggest); GC runs only when
`process()` is called; a PUT's tail chunks are written one at a time
(no `rgw_put_obj_min_window_size` of writes in flight: this client's
aio keeps no order of submission, and an append names no offset the
OSD could check); lifecycle is out of scope.

S3 surface:
    GET  /                          ListAllMyBuckets
    PUT  /bucket                    create bucket
    DELETE /bucket                  delete (must be empty)
    GET  /bucket?prefix=&max-keys=&marker=   ListBucket (paginated:
                                    NextMarker continuation, the cls
                                    `bucket_list` over a ranged omap
                                    read — O(page), not O(bucket))
    GET  /bucket?uploads            list in-progress multipart uploads
    PUT  /bucket/key                put object
    GET|HEAD /bucket/key            get/stat object
    DELETE /bucket/key              delete object
    POST /bucket/key?uploads        InitiateMultipartUpload
    PUT  /bucket/key?uploadId=&partNumber=   UploadPart
    POST /bucket/key?uploadId=      CompleteMultipartUpload
    DELETE /bucket/key?uploadId=    AbortMultipartUpload
(rgw/rgw_op.cc RGWInitMultipart/RGWPutObj 'multipart'/
 RGWCompleteMultipart/RGWAbortMultipart, rgw_rest_s3.cc)
"""

from __future__ import annotations

import base64
import hashlib
import hmac
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, quote, unquote, urlparse
from xml.sax.saxutils import escape

from ..client.rados import RadosError
from ..utils import denc, optracker
from ..utils.admin_socket import AdminSocket
from ..utils.bufferlist import iov_of
from ..utils.clock import SystemClock
from . import auth_v4

BUCKETS_ROOT = "rgw.buckets"        # omap: bucket name -> meta
DATA_POOL = "rgw_data"
GC_OID = "gc.0"                     # omap: <expiry us>.<tag> -> tail oids

# the reference's options (src/common/config_opts.h), as constants
RGW_MAX_CHUNK_SIZE = 512 << 10      # rgw_max_chunk_size
RGW_OBJ_STRIPE_SIZE = 4 << 20       # rgw_obj_stripe_size
RGW_GET_OBJ_MAX_REQ_SIZE = 4 << 20  # rgw_get_obj_max_req_size
RGW_GC_OBJ_MIN_WAIT = 2 * 60 * 60   # rgw_gc_obj_min_wait

# the head object's xattrs (RGW_ATTR_MANIFEST, _ETAG, _ID_TAG; the
# reference takes mtime from the object's own stat)
ATTR_MANIFEST = "rgw.manifest"
ATTR_ETAG = "rgw.etag"
ATTR_ID_TAG = "rgw.idtag"
ATTR_MTIME = "rgw.mtime"
ECANCELED = 125
ENOENT = 2
# how often a head write that lost its guard to a concurrent PUT reads
# the head's state again and retries before it gives up
HEAD_RACE_RETRIES = 64

PERF_COUNTERS = ("put", "get", "put_bytes", "get_bytes", "tail_appends",
                 "overwrites", "gc_enqueued", "gc_removed")


def index_oid(bucket: str) -> str:
    return f"bucket.index.{bucket}"


def uploads_oid(bucket: str) -> str:
    """omap: uploadId -> {key, started} (RGWMPObj meta analog)."""
    return f"bucket.uploads.{quote(bucket, safe='')}"


def parts_oid(bucket: str, upload_id: str) -> str:
    """omap: zero-padded part number -> {etag, size}."""
    return f"bucket.parts.{quote(bucket, safe='')}.{upload_id}"


def part_soid(bucket: str, key: str, upload_id: str, n: int) -> str:
    return obj_soid(bucket, key) + f".mp.{upload_id}.{n:05d}"


def obj_soid(bucket: str, key: str) -> str:
    """Collision-proof backing name: bucket and key are fully quoted
    (so 'a'/'b.c' and 'a.b'/'c' cannot alias, and '@' — reserved by
    the OSD namespace — never appears) and joined with '/', which the
    quoting removes from both halves."""
    return f"obj.{quote(bucket, safe='')}/{quote(key, safe='')}"


def versions_oid(bucket: str) -> str:
    """omap: quoted-key + NUL + version-id -> version meta.  The vid
    is a descending time stamp (see new_version_id), so a ranged read
    under one key's prefix walks versions newest-first."""
    return f"bucket.versions.{quote(bucket, safe='')}"


def version_key(key: str, vid: str) -> str:
    return f"{quote(key, safe='')}\x00{vid}"


def ver_soid(bucket: str, key: str, vid: str) -> str:
    """Backing object for one version.  The 'null' version (pre-
    versioning writes, and writes while suspended) lives at the base
    name so enabling versioning needs no data movement."""
    base = obj_soid(bucket, key)
    return base if vid == "null" else f"{base}.v.{vid}"


def new_version_id() -> str:
    """Lexically ASCENDING = newest first (complemented nanoseconds),
    plus randomness against same-tick collisions."""
    return (f"{0xFFFFFFFFFFFFFFFF - time.time_ns():016x}"
            f"{os.urandom(3).hex()}")


def new_tag() -> str:
    """A write's tag: the head's `rgw.idtag`, the prefix its tail
    objects are named under, the tag its index op is prepared under."""
    return os.urandom(8).hex()


def tail_oid(head_oid: str, tag: str, n: int) -> str:
    """Tail object of manifest stripe `n` (1 up: stripe 0 is the head)
    of the write `tag` of `head_oid` (`<marker>__shadow_<key>.<prefix>_
    <n>` in the reference)."""
    return f"{head_oid}.shadow.{tag}_{n}"


def manifest_tails(head_oid: str, manifest: dict) -> list[tuple[str, int]]:
    """[(tail oid, its bytes)] a manifest names: the bytes past the
    head in stripes of `stripe` bytes, the last as long as is left."""
    left = int(manifest["size"]) - int(manifest["head_size"])
    out, n = [], 1
    while left > 0:
        out.append((tail_oid(head_oid, manifest["tag"], n),
                    min(left, int(manifest["stripe"]))))
        left -= out[-1][1]
        n += 1
    return out


class RGWGC:
    """Deferred removal of tail objects (rgw_gc.cc `RGWGC` reduced): a
    chain of tail oids is enqueued on a list on the index pool when the
    head that named them was switched or removed, and `process()`
    removes the chains whose `rgw_gc_obj_min_wait` is over: a reader
    that took the old head a moment before still finds its tail.
    Nothing here runs by itself: an operator (`radosgw-admin gc
    process`) or a test calls `process`."""

    def __init__(self, gw: "RGWDaemon"):
        self.gw = gw

    def enqueue(self, tag: str, oids: list[str]) -> None:
        if not oids:
            return
        expiry = int((self.gw.clock() + RGW_GC_OBJ_MIN_WAIT) * 1e6)
        self.gw.index_io.set_omap(GC_OID, {
            f"{expiry:020d}.{tag}": denc.dumps(list(oids))})
        self.gw.count("gc_enqueued", len(oids))

    def list(self) -> dict[str, list[str]]:
        try:
            return {k: denc.loads(v) for k, v in
                    self.gw.index_io.get_omap(GC_OID).items()}
        except RadosError:
            return {}

    def process(self, now: float | None = None) -> int:
        """Remove the chains due at `now` (the gateway's clock where
        None); returns the objects removed."""
        now = self.gw.clock() if now is None else now
        removed = 0
        for key, oids in sorted(self.list().items()):
            if int(key[:20]) > now * 1e6:
                break
            for oid in oids:
                try:
                    self.gw.data_io.remove_object(oid)
                    removed += 1
                except RadosError as e:
                    if e.errno != ENOENT:
                        raise
            self.gw.index_io.rm_omap_keys(GC_OID, [key])
        self.gw.count("gc_removed", removed)
        return removed


class _Server(ThreadingHTTPServer):
    # a stage's workers connect at once: the default backlog is 5
    request_queue_size = 128
    daemon_threads = True


class RGWDaemon:
    """The radosgw process: HTTP frontend over a Rados handle, on a
    placement of three pools (see the module docstring); the index and
    the extra pool default to the data pool, which is then replicated."""

    def __init__(self, rados, port: int = 0, access_key: str = "",
                 secret_key: str = "", data_pool: str = DATA_POOL,
                 index_pool: str | None = None,
                 data_extra_pool: str | None = None, clock=time.time):
        self.rados = rados
        self.access_key = access_key
        self.secret_key = secret_key
        self.clock = clock
        index_pool = index_pool or data_pool
        data_extra_pool = data_extra_pool or index_pool
        for pool in {data_pool, index_pool, data_extra_pool}:
            try:
                rados.create_pool(pool)
            except RadosError:
                pass
        self.data_io = rados.open_ioctx(data_pool)
        self.index_io = rados.open_ioctx(index_pool)
        self.extra_io = rados.open_ioctx(data_extra_pool)
        if self.index_io.pool_requires_alignment() or \
                self.extra_io.pool_requires_alignment():
            raise ValueError("the index and the extra pool keep omaps: "
                             "an erasure-coded pool has none")
        # RGWRados::get_max_chunk_size: rgw_max_chunk_size in whole
        # stripes of a data pool that asks for alignment
        align = self.data_io.pool_required_alignment()
        self.chunk_size = RGW_MAX_CHUNK_SIZE if not align else max(
            align, RGW_MAX_CHUNK_SIZE - RGW_MAX_CHUNK_SIZE % align)
        self.gc = RGWGC(self)
        self._perf_mu = threading.Lock()
        self._perf = dict.fromkeys(PERF_COUNTERS, 0)
        conf = getattr(rados, "conf", None)
        self.op_tracker = optracker.OpTracker(
            SystemClock(), daemon="client.rgw", history_size=int(
                getattr(conf, "osd_op_history_size", 20)))
        self.asok = AdminSocket("client.rgw")
        self.asok.register("perf dump", lambda c: {
            "rgw": self.perf(), "denc": denc.counters()})
        self.asok.register("dump_historic_ops",
                           lambda c: self.op_tracker.dump_historic_ops())
        gw = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # an answer is two writes (headers, body): with Nagle's
            # algorithm on, the second waits for the client's delayed
            # ACK of the first
            disable_nagle_algorithm = True

            def log_message(self, *a):
                pass

            def do_GET(self):
                gw.handle(self, "GET")

            def do_PUT(self):
                gw.handle(self, "PUT")

            def do_DELETE(self):
                gw.handle(self, "DELETE")

            def do_HEAD(self):
                gw.handle(self, "HEAD")

            def do_POST(self):
                gw.handle(self, "POST")

        self.httpd = _Server(("127.0.0.1", port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "RGWDaemon":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="rgw-http")
        self._thread.start()
        return self

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()

    # -- counters (`perf dump`, block `rgw`) -------------------------------

    def count(self, name: str, n: int = 1) -> None:
        with self._perf_mu:
            self._perf[name] += n

    def perf(self) -> dict:
        with self._perf_mu:
            return dict(self._perf)

    # -- auth (AWS v2-style shared-key signatures) -------------------------

    def _check_auth(self, req, method: str, path: str,
                    raw_query: str = "", body: bytes = b"") -> bool:
        if not self.access_key:
            return True                      # auth disabled
        header = req.headers.get("Authorization", "")
        if header.startswith(auth_v4.ALGORITHM):
            headers = {k.lower(): v for k, v in req.headers.items()}
            return auth_v4.verify_v4(method, path, raw_query, headers,
                                     body, self.access_key,
                                     self.secret_key)
        want = sign_v2(method, path, req.headers.get("Date", ""),
                       self.access_key, self.secret_key)
        return hmac.compare_digest(want, header)

    # -- the bucket index (cls/rgw.py, on the index pool) ------------------

    def _index_call(self, bucket: str, method: str, req: dict):
        return self.index_io.execute(index_oid(bucket), "rgw", method,
                                     denc.dumps(req))

    def _index_prepare(self, bucket: str, key: str, tag: str,
                       op: str) -> None:
        with optracker.span("rgw.idx_prepare"):
            self._index_call(bucket, "bucket_prepare_op",
                             {"key": key, "tag": tag, "op": op})

    def _index_complete(self, bucket: str, key: str, tag: str | None,
                        op: str, meta: dict | None, log_op: str | None,
                        vid: str | None = None,
                        ver: tuple | None = None) -> None:
        """Replace (op "put"), drop ("del") or leave ("none") the key's
        entry, take `tag` off its pending ops and append `log_op`
        (where not None) to the bucket's index log (the sync agent's
        feed, `?bilog&marker=N`), in one transaction of the index PG.
        `ver` is the version the data op made the head: of two writers
        of one key the one whose head stands keeps the entry, whichever
        completes last."""
        with optracker.span("rgw.idx_complete"):
            self._index_call(bucket, "bucket_complete_op", {
                "key": key, "tag": tag, "op": op, "meta": meta,
                "ver": ver and list(ver),
                "log": log_op and {"op": log_op, "vid": vid,
                                   "ts": _http_date()}})

    def _bilog_page(self, bucket: str, marker: int,
                    count: int = 1000) -> list[dict]:
        try:
            return denc.loads(self._index_call(
                bucket, "bilog_list", {"marker": marker, "max": count}))
        except RadosError:
            return []

    def _create_bucket(self, bucket: str) -> None:
        self._set_bucket_meta(bucket, {"created": _http_date()})
        try:
            self._index_call(bucket, "bucket_init_index", {})
        except RadosError:
            pass

    def _remove_bucket(self, bucket: str) -> None:
        self.index_io.rm_omap_keys(BUCKETS_ROOT, [bucket])
        for io, oid in ((self.index_io, index_oid(bucket)),
                        (self.index_io, versions_oid(bucket)),
                        (self.extra_io, uploads_oid(bucket))):
            try:
                io.remove_object(oid)
            except RadosError:
                pass

    # -- bucket metadata ---------------------------------------------------

    def _buckets(self) -> dict:
        try:
            return {k: denc.loads(v) for k, v in
                    self.index_io.get_omap(BUCKETS_ROOT).items()}
        except RadosError:
            return {}

    def _bucket_exists(self, bucket: str) -> bool:
        return self._bucket_meta(bucket) is not None

    def _bucket_meta(self, bucket: str) -> dict | None:
        try:
            got = self.index_io.get_omap_keys(BUCKETS_ROOT, [bucket])
        except RadosError:
            return None
        blob = got.get(bucket)
        return denc.loads(blob) if blob else None

    def _set_bucket_meta(self, bucket: str, meta: dict) -> None:
        self.index_io.set_omap(BUCKETS_ROOT, {bucket: denc.dumps(meta)})

    def _index_entry(self, bucket: str, key: str) -> dict | None:
        """One key's index record — a single-key omap read, not the
        whole bucket index."""
        try:
            got = self.index_io.get_omap_keys(index_oid(bucket), [key])
        except RadosError:
            return None
        blob = got.get(key)
        ent = denc.loads(blob) if blob else None
        if ent is None or not ent.get("exists", True):
            return None
        ent.pop("pending", None)
        return ent

    def _index_page(self, bucket: str, marker: str, prefix: str,
                    count: int) -> dict:
        try:
            return denc.loads(self._index_call(
                bucket, "bucket_list", {"marker": marker,
                                        "prefix": prefix, "max": count})
            )["entries"]
        except RadosError:
            return {}

    def _index_empty(self, bucket: str) -> bool:
        return not self._index_page(bucket, "", "", 1)

    # -- request routing ---------------------------------------------------

    def handle(self, req, method: str) -> None:
        """One S3 (or Swift) request: a tracked op of kind `rgw_req`,
        whose spans say where its time went (`dump_historic_ops` on
        the gateway's admin socket)."""
        op = self.op_tracker.create(
            f"{method} {urlparse(req.path).path}", kind="rgw_req")
        try:
            with optracker.op_context(op):
                self._handle(req, method)
        finally:
            op.finish()

    def _handle(self, req, method: str) -> None:
        parsed = urlparse(req.path)
        path = unquote(parsed.path)
        query = parse_qs(parsed.query, keep_blank_values=True)
        # drain the request body FIRST: replying on an error path with
        # unread body bytes desyncs the keep-alive connection (the next
        # request line would be parsed out of the leftover payload)
        try:
            length = int(req.headers.get("Content-Length", 0) or 0)
        except ValueError:
            self._error(req, 400, "InvalidArgument")
            return
        with optracker.span("rgw.recv_body", bytes=length):
            body = req.rfile.read(length) if length > 0 else b""
        from . import swift
        authz = req.headers.get("Authorization", "")
        if swift.handles(path) and not authz.startswith("AWS"):
            # the Swift dialect authenticates with its own TempAuth
            # token (rgw_rest_swift.cc), not AWS signatures
            try:
                swift.dispatch(self, req, method, path, query, body)
            except RadosError as e:
                self._error(req, 500, f"InternalError: {e}")
            return
        if not self._check_auth(req, method, path, parsed.query, body):
            self._error(req, 403, "AccessDenied")
            return
        parts = [p for p in path.split("/") if p]
        try:
            if not parts:
                if method == "GET":
                    self._list_buckets(req)
                else:
                    self._error(req, 405, "MethodNotAllowed")
            elif len(parts) == 1:
                self._bucket_op(req, method, parts[0], query, body)
            else:
                self._object_op(req, method, parts[0],
                                "/".join(parts[1:]), body, query)
        except RadosError as e:
            self._error(req, 500, f"InternalError: {e}")

    # -- responses ---------------------------------------------------------

    def _reply(self, req, code: int, body: bytes = b"",
               headers: dict | None = None) -> None:
        req.send_response(code)
        have_len = False
        for k, v in (headers or {}).items():
            req.send_header(k, v)
            if k.lower() == "content-length":
                have_len = True      # HEAD advertises the entity size
        if not have_len:
            req.send_header("Content-Length", str(len(body)))
        req.end_headers()
        if req.command != "HEAD" and len(body):
            # gather-write: reads arrive as BufferList ropes — the
            # segments go straight to the socket, never joined
            for seg in iov_of(body):
                req.wfile.write(seg)

    def _xml(self, req, code: int, body: str,
             headers: dict | None = None) -> None:
        self._reply(req, code,
                    ('<?xml version="1.0" encoding="UTF-8"?>'
                     + body).encode(),
                    {"Content-Type": "application/xml",
                     **(headers or {})})

    def _error(self, req, code: int, s3code: str) -> None:
        self._xml(req, code, f"<Error><Code>{escape(s3code)}</Code>"
                             f"</Error>")

    # -- bucket ops --------------------------------------------------------

    def _list_buckets(self, req) -> None:
        entries = "".join(
            f"<Bucket><Name>{escape(name)}</Name>"
            f"<CreationDate>{meta['created']}</CreationDate></Bucket>"
            for name, meta in sorted(self._buckets().items()))
        self._xml(req, 200,
                  "<ListAllMyBucketsResult><Buckets>"
                  f"{entries}</Buckets></ListAllMyBucketsResult>")

    def _bucket_op(self, req, method: str, bucket: str,
                   query: dict, body: bytes = b"") -> None:
        if "versioning" in query:
            self._versioning_op(req, method, bucket, body)
            return
        if "versions" in query and method in ("GET", "HEAD"):
            self._list_versions(req, bucket, query)
            return
        if "bilog" in query and method == "GET":
            import json
            try:
                marker = int(query.get("marker", ["0"])[0])
            except ValueError:
                self._error(req, 400, "InvalidArgument")
                return
            entries = self._bilog_page(bucket, marker)
            self._reply(req, 200, json.dumps(entries).encode(),
                        {"Content-Type": "application/json"})
            return
        buckets = self._buckets()
        if method == "PUT":
            if bucket in buckets:
                self._error(req, 409, "BucketAlreadyExists")
                return
            self._create_bucket(bucket)
            self._reply(req, 200)
        elif method == "DELETE":
            if bucket not in buckets:
                self._error(req, 404, "NoSuchBucket")
                return
            if not self._index_empty(bucket):
                self._error(req, 409, "BucketNotEmpty")
                return
            self._remove_bucket(bucket)
            self._reply(req, 204)
        elif method in ("GET", "HEAD"):
            if bucket not in buckets:
                self._error(req, 404, "NoSuchBucket")
                return
            if "uploads" in query:
                self._list_uploads(req, bucket)
                return
            prefix = query.get("prefix", [""])[0]
            marker = query.get("marker", [""])[0]
            try:
                max_keys = int(query.get("max-keys", ["1000"])[0])
            except ValueError:
                self._error(req, 400, "InvalidArgument")
                return
            if max_keys < 0:
                self._error(req, 400, "InvalidArgument")
                return
            # ranged index read: one page + 1 sentinel for IsTruncated
            # (RGWRados::cls_bucket_list marker pagination)
            # delete-marker-latest keys are invisible to a plain list
            # (RGWListBucket skips entries whose current version is a
            # marker); page through the index until a full page of
            # visible keys (or exhaustion)
            page = {}
            cursor = marker
            exhausted = False
            while len(page) <= max_keys and not exhausted:
                chunk = self._index_page(bucket, cursor, prefix,
                                         max_keys + 1)
                if len(chunk) < max_keys + 1:
                    exhausted = True
                for k, v in chunk.items():
                    if not v.get("delete_marker"):
                        page[k] = v
                if chunk:
                    cursor = max(chunk)
            keys = sorted(page)
            truncated = len(keys) > max_keys
            keys = keys[:max_keys]
            entries = "".join(
                f"<Contents><Key>{escape(k)}</Key>"
                f"<Size>{page[k]['size']}</Size>"
                f"<ETag>&quot;{page[k]['etag']}&quot;</ETag>"
                "</Contents>"
                for k in keys)
            next_marker = (f"<NextMarker>{escape(keys[-1])}"
                           f"</NextMarker>") if truncated and keys \
                else ""
            self._xml(req, 200,
                      "<ListBucketResult>"
                      f"<Name>{escape(bucket)}</Name>"
                      f"<Prefix>{escape(prefix)}</Prefix>"
                      f"<Marker>{escape(marker)}</Marker>"
                      f"<KeyCount>{len(keys)}</KeyCount>"
                      f"<IsTruncated>{str(truncated).lower()}"
                      f"</IsTruncated>{next_marker}{entries}"
                      "</ListBucketResult>")
        else:
            self._error(req, 405, "MethodNotAllowed")

    # -- versioning (rgw/rgw_op.h:484-493 RGWGet/SetBucketVersioning) ------

    def _versioning_op(self, req, method: str, bucket: str,
                       body: bytes) -> None:
        meta = self._bucket_meta(bucket)
        if meta is None:
            self._error(req, 404, "NoSuchBucket")
            return
        if method in ("GET", "HEAD"):
            status = meta.get("versioning", "")
            inner = f"<Status>{status}</Status>" if status else ""
            self._xml(req, 200,
                      '<VersioningConfiguration xmlns="http://s3.'
                      f'amazonaws.com/doc/2006-03-01/">{inner}'
                      "</VersioningConfiguration>")
        elif method == "PUT":
            import re
            m = re.search(rb"<Status>\s*(Enabled|Suspended)\s*"
                          rb"</Status>", body)
            if m is None:
                self._error(req, 400, "IllegalVersioningConfiguration"
                                      "Exception")
                return
            meta["versioning"] = m.group(1).decode()
            self._set_bucket_meta(bucket, meta)
            self._reply(req, 200)
        else:
            self._error(req, 405, "MethodNotAllowed")

    def _version_record(self, bucket: str, key: str,
                        vid: str) -> dict | None:
        try:
            got = self.index_io.get_omap_keys(versions_oid(bucket),
                                        [version_key(key, vid)])
        except RadosError:
            got = {}          # no versions object yet: still fall
                              # through to the null-version fallback
        blob = got.get(version_key(key, vid))
        if blob:
            return denc.loads(blob)
        if vid == "null":
            # a pre-versioning object is addressable as version "null"
            # IMMEDIATELY (S3 null-version semantics); the omap record
            # only materializes on the next write (_migrate_null_
            # version), so fall back to the unmigrated index entry
            ent = self._index_entry(bucket, key)
            if ent is not None and \
                    ent.get("version_id", "null") == "null":
                return ent
        return None

    def _put_version_record(self, bucket: str, key: str, vid: str,
                            rec: dict) -> None:
        self.index_io.set_omap(versions_oid(bucket),
                         {version_key(key, vid): denc.dumps(rec)})

    def _key_versions(self, bucket: str, key: str) -> list[tuple]:
        """All (vid, record) for one key, newest first (vids are
        complemented timestamps, so lexical order IS newest-first)."""
        prefix = quote(key, safe="") + "\x00"
        try:
            vals = self.index_io.get_omap_vals(versions_oid(bucket),
                                         start_after="", prefix=prefix,
                                         max_return=100000)
        except RadosError:
            return []
        out = [(k[len(prefix):], denc.loads(v))
               for k, v in sorted(vals.items())]
        # a "null" vid sorts after hex stamps; order by recorded mtime
        out.sort(key=lambda t: -t[1].get("mtime_ns", 0))
        return out

    def _migrate_null_version(self, bucket: str, key: str) -> None:
        """First versioned write over a pre-versioning object: the
        existing base-name data becomes the 'null' version (S3's
        null-version semantics — no data movement, just a record)."""
        ent = self._index_entry(bucket, key)
        if ent is not None and "version_id" not in ent:
            ent["version_id"] = "null"
            ent["mtime_ns"] = ent.get("mtime_ns", 0)
            self._put_version_record(bucket, key, "null", ent)

    def _list_versions(self, req, bucket: str, query: dict) -> None:
        if not self._bucket_exists(bucket):
            self._error(req, 404, "NoSuchBucket")
            return
        prefix = query.get("prefix", [""])[0]
        try:
            vals = self.index_io.get_omap_vals(
                versions_oid(bucket), start_after="",
                prefix=quote(prefix, safe="") if prefix else "",
                max_return=100000)
        except RadosError:
            vals = {}
        per_key: dict[str, list] = {}
        for k, blob in vals.items():
            qkey, _, vid = k.partition("\x00")
            per_key.setdefault(unquote(qkey), []).append(
                (vid, denc.loads(blob)))
        entries = []
        for key in sorted(per_key):
            cur = self._index_entry(bucket, key) or {}
            latest_vid = cur.get("version_id")
            vers = sorted(per_key[key],
                          key=lambda t: -t[1].get("mtime_ns", 0))
            for vid, rec in vers:
                tag = ("DeleteMarker" if rec.get("delete_marker")
                       else "Version")
                extra = ("" if rec.get("delete_marker") else
                         f"<Size>{rec.get('size', 0)}</Size>"
                         f"<ETag>&quot;{rec.get('etag', '')}&quot;"
                         "</ETag>")
                entries.append(
                    f"<{tag}><Key>{escape(key)}</Key>"
                    f"<VersionId>{vid}</VersionId>"
                    f"<IsLatest>{str(vid == latest_vid).lower()}"
                    f"</IsLatest>"
                    f"<LastModified>{rec.get('mtime', '')}"
                    f"</LastModified>{extra}</{tag}>")
        self._xml(req, 200,
                  "<ListVersionsResult>"
                  f"<Name>{escape(bucket)}</Name>"
                  f"<Prefix>{escape(prefix)}</Prefix>"
                  f"{''.join(entries)}</ListVersionsResult>")

    # -- object ops --------------------------------------------------------

    def _object_op(self, req, method: str, bucket: str,
                   key: str, body: bytes = b"",
                   query: dict | None = None) -> None:
        query = query or {}
        bmeta = self._bucket_meta(bucket)
        if bmeta is None:
            self._error(req, 404, "NoSuchBucket")
            return
        vstate = bmeta.get("versioning", "")
        upload_id = query.get("uploadId", [None])[0]
        if method == "POST" and "uploads" in query:
            self._initiate_multipart(req, bucket, key)
            return
        if upload_id is not None:
            if method == "PUT":
                self._upload_part(req, bucket, key, upload_id,
                                  query, body)
            elif method == "POST":
                self._complete_multipart(req, bucket, key, upload_id,
                                         body)
            elif method == "DELETE":
                self._abort_multipart(req, bucket, key, upload_id)
            else:
                self._error(req, 405, "MethodNotAllowed")
            return
        req_vid = query.get("versionId", [None])[0]
        if method == "PUT":
            self._put_object(req, bucket, key, body, vstate)
        elif method in ("GET", "HEAD"):
            self._get_object(req, method, bucket, key, req_vid, vstate)
        elif method == "DELETE":
            self._delete_object(req, bucket, key, req_vid, vstate)
        else:
            self._error(req, 405, "MethodNotAllowed")

    # -- the PUT processor and the GET path (both kinds of data pool) -------

    def _head_state(self, head_oid: str) -> dict | None:
        """The head's xattrs (RGWRados::get_obj_state), None where
        there is no such object."""
        try:
            return self.data_io.operate(head_oid, [("getxattrs",)])[0]
        except RadosError as e:
            if e.errno != ENOENT:
                raise
            return None

    def _write_object(self, head_oid: str, body, etag: str,
                      mtime: str, fresh: bool = False) -> tuple:
        """RGWPutObjProcessor_Atomic: the first chunk is held back;
        every further chunk goes to the tail object of its manifest
        stripe, named under a fresh tag, at the offset that is the tail
        object's size (its first chunk creates it, the others append:
        aligned but for the last, on a pool that asks for it); then ONE
        compound op on the head writes the first chunk and the xattrs
        (manifest, etag, tag, mtime) and so switches the object, a
        reader seeing the old one whole or the new one whole.  The op
        is guarded by the tag of the head it replaces (`fresh`: by
        there being none), and a writer that lost to another reads the
        head again and retries.  Only then the replaced head's tail
        goes to the GC list.  Returns the version the head op made the
        object."""
        body = memoryview(body) if not isinstance(body, memoryview) \
            else body
        size, chunk, tag = len(body), self.chunk_size, new_tag()
        manifest = {"size": size, "head_size": min(size, chunk),
                    "stripe": RGW_OBJ_STRIPE_SIZE, "tag": tag}
        tails = manifest_tails(head_oid, manifest)
        try:
            if tails:
                with optracker.span("rgw.put_tail") as late:
                    at, writes = chunk, 0
                    for oid, length in tails:
                        for off in range(0, length, chunk):
                            piece = body[at + off:
                                         at + min(off + chunk, length)]
                            if off:
                                self.data_io.append(oid, piece)
                            else:
                                self.data_io.write_full(oid, piece)
                            writes += 1
                        at += length
                    late.update(appends=writes, bytes=size - chunk)
                self.count("tail_appends", writes)
            attrs = [("setxattr", ATTR_MANIFEST, denc.dumps(manifest)),
                     ("setxattr", ATTR_ETAG, etag.encode()),
                     ("setxattr", ATTR_ID_TAG, tag.encode()),
                     ("setxattr", ATTR_MTIME, mtime.encode())]
            with optracker.span("rgw.put_head"):
                for _ in range(HEAD_RACE_RETRIES):
                    old = None if fresh else self._head_state(head_oid)
                    try:
                        _out, ver = self.data_io.operate(head_oid, [
                            ("cmpxattr", ATTR_ID_TAG,
                             (old or {}).get(ATTR_ID_TAG)),
                            ("writefull", body[:chunk])] + attrs,
                            want_version=True)
                        break
                    except RadosError as e:
                        if e.errno != ECANCELED:
                            raise
                else:
                    raise RadosError(ECANCELED, f"{head_oid}: the head "
                                     "changed under every try")
        except Exception:
            # what was written under the tag names nothing
            self.gc.enqueue(tag, [oid for oid, _n in tails])
            raise
        if old is not None:
            self.count("overwrites")
            self._gc_tail_of(head_oid, old)
        return ver

    def _gc_tail_of(self, head_oid: str, attrs: dict) -> None:
        """Hand the tail a (replaced or removed) head named to GC."""
        if ATTR_MANIFEST in attrs:
            manifest = denc.loads(attrs[ATTR_MANIFEST])
            self.gc.enqueue(manifest["tag"], [
                oid for oid, _n in manifest_tails(head_oid, manifest)])

    def _remove_object(self, head_oid: str) -> bool:
        """Remove the head (guarded by its tag, as a write is) and
        hand its tail to GC; False where there was none."""
        for _ in range(HEAD_RACE_RETRIES):
            old = self._head_state(head_oid)
            if old is None:
                return False
            try:
                self.data_io.operate(head_oid, [
                    ("cmpxattr", ATTR_ID_TAG, old.get(ATTR_ID_TAG)),
                    ("delete",)])
            except RadosError as e:
                if e.errno == ECANCELED:
                    continue
                if e.errno == ENOENT:
                    return False
                raise
            self._gc_tail_of(head_oid, old)
            return True
        raise RadosError(ECANCELED, f"{head_oid}: the head changed "
                         "under every try")

    def _read_object(self, head_oid: str,
                     want_body: bool = True) -> tuple | None:
        """(manifest, xattrs, [body pieces]) of one version, whole: ONE
        compound read of the head (xattrs + first chunk), then the
        tail objects its manifest names, `rgw_get_obj_max_req_size` a
        request at most; None where there is no such object.  The
        index is not read."""
        try:
            with optracker.span("rgw.get_head"):
                out = self.data_io.operate(
                    head_oid, [("getxattrs",)]
                    + ([("read", 0, 0)] if want_body else []))
        except RadosError as e:
            if e.errno != ENOENT:
                raise
            return None
        attrs = out[0]
        if ATTR_MANIFEST not in attrs:
            return None
        manifest = denc.loads(attrs[ATTR_MANIFEST])
        if not want_body:
            return manifest, attrs, []
        pieces = [out[1]]
        tails = manifest_tails(head_oid, manifest)
        if tails:
            with optracker.span("rgw.get_tail") as late:
                reqs = 0
                for oid, length in tails:
                    for off in range(0, length,
                                     RGW_GET_OBJ_MAX_REQ_SIZE):
                        pieces.append(self.data_io.read(
                            oid, min(RGW_GET_OBJ_MAX_REQ_SIZE,
                                     length - off), off))
                        reqs += 1
                late.update(reqs=reqs)
        return manifest, attrs, pieces

    def _put_object(self, req, bucket: str, key: str, body: bytes,
                    vstate: str, swift_status: int | None = None,
                    etag: str | None = None, reply: bool = True) -> dict:
        """PUT: index prepare, the processor, index complete.  `etag`
        where it is not the body's MD5 (a completed multipart)."""
        if etag is None:
            with optracker.span("rgw.etag"):
                etag = hashlib.md5(body).hexdigest()
        ent = {"size": len(body), "etag": etag, "mtime": _http_date(),
               "mtime_ns": time.time_ns()}
        headers = {"ETag": f'"{etag}"'}
        tag, ver = new_tag(), None
        self._index_prepare(bucket, key, tag, "put")
        if vstate == "Enabled":
            self._migrate_null_version(bucket, key)
            vid = new_version_id()
            ent["version_id"] = vid
            self._write_object(ver_soid(bucket, key, vid), body, etag,
                               ent["mtime"], fresh=True)
            self._put_version_record(bucket, key, vid, ent)
            headers["x-amz-version-id"] = vid
        else:
            # unversioned OR suspended: (over)write the null version
            ver = self._write_object(obj_soid(bucket, key), body, etag,
                                     ent["mtime"])
            if vstate == "Suspended":
                ent["version_id"] = "null"
                self._put_version_record(bucket, key, "null", ent)
                headers["x-amz-version-id"] = "null"
        self._index_complete(bucket, key, tag, "put", ent, "put",
                             ent.get("version_id"), ver)
        self.count("put")
        self.count("put_bytes", len(body))
        if reply:
            self._reply(req, swift_status or 200, headers=headers)
        return ent

    def _get_object(self, req, method: str, bucket: str, key: str,
                    req_vid: str | None, vstate: str = "") -> None:
        vid = "null"
        if req_vid is not None or vstate:
            # a versioned bucket's current version is the index's word
            # (the reference keeps it on the key's OLH object)
            if req_vid is None:
                ent = self._index_entry(bucket, key)
                if ent is None:
                    self._error(req, 404, "NoSuchKey")
                    return
                if ent.get("delete_marker"):
                    req.send_response(404)
                    req.send_header("x-amz-delete-marker", "true")
                    req.send_header("x-amz-version-id",
                                    ent.get("version_id", "null"))
                    req.send_header("Content-Length", "0")
                    req.end_headers()
                    return
                vid = ent.get("version_id", "null")
            else:
                vid = req_vid
                ent = self._version_record(bucket, key, vid)
                if ent is None:
                    self._error(req, 404, "NoSuchVersion")
                    return
                if ent.get("delete_marker"):
                    # GET on a delete-marker version is 405 per S3
                    self._error(req, 405, "MethodNotAllowed")
                    return
        got = self._read_object(ver_soid(bucket, key, vid),
                                want_body=method == "GET")
        if got is None:
            self._error(req, 404, "NoSuchKey")
            return
        manifest, attrs, pieces = got
        req.send_response(200)
        req.send_header("Content-Length", str(manifest["size"]))
        req.send_header("ETag", f'"{bytes(attrs[ATTR_ETAG]).decode()}"')
        req.send_header("Last-Modified",
                        bytes(attrs[ATTR_MTIME]).decode())
        if vid != "null" or req_vid is not None:
            req.send_header("x-amz-version-id", vid)
        req.send_header("Content-Type", "application/octet-stream")
        req.end_headers()
        if method == "GET":
            with optracker.span("rgw.send_body", bytes=manifest["size"]):
                for piece in pieces:
                    for seg in iov_of(piece):
                        req.wfile.write(seg)
            self.count("get")
            self.count("get_bytes", manifest["size"])

    def _delete_object(self, req, bucket: str, key: str,
                       req_vid: str | None, vstate: str) -> None:
        if req_vid is not None:
            self._delete_version(req, bucket, key, req_vid)
            return
        if vstate in ("Enabled", "Suspended"):
            # plant a delete marker (RGWDeleteObj's versioned path);
            # suspended buckets use the null id, replacing any null
            # version outright
            self._migrate_null_version(bucket, key)
            vid = (new_version_id() if vstate == "Enabled" else "null")
            if vid == "null":
                old = self._version_record(bucket, key, "null")
                if old is not None and not old.get("delete_marker"):
                    self._remove_object(ver_soid(bucket, key, "null"))
            marker = {"delete_marker": True, "version_id": vid,
                      "mtime": _http_date(), "mtime_ns": time.time_ns()}
            self._put_version_record(bucket, key, vid, marker)
            self._index_complete(bucket, key, None, "put", marker,
                                 "delete-marker", vid)
            self._reply(req, 204, headers={
                "x-amz-delete-marker": "true",
                "x-amz-version-id": vid})
            return
        tag = new_tag()
        self._index_prepare(bucket, key, tag, "del")
        removed = self._remove_object(obj_soid(bucket, key))
        self._index_complete(bucket, key, tag, "del", None,
                             "delete" if removed else None)
        self._reply(req, 204)

    def _delete_version(self, req, bucket: str, key: str,
                        vid: str) -> None:
        """Permanent removal of one version; deleting the current
        delete marker restores the previous version as latest."""
        rec = self._version_record(bucket, key, vid)
        if rec is None:
            self._error(req, 404, "NoSuchVersion")
            return
        if not rec.get("delete_marker"):
            self._remove_object(ver_soid(bucket, key, vid))
        self.index_io.rm_omap_keys(versions_oid(bucket),
                                   [version_key(key, vid)])
        cur = self._index_entry(bucket, key)
        op, newest = "none", None
        if cur is not None and cur.get("version_id", "null") == vid:
            remaining = self._key_versions(bucket, key)
            op, newest = ("put", remaining[0][1]) if remaining \
                else ("del", None)
        self._index_complete(bucket, key, None, op, newest,
                             "delete-version", vid)
        headers = {"x-amz-version-id": vid}
        if rec.get("delete_marker"):
            headers["x-amz-delete-marker"] = "true"
        self._reply(req, 204, headers=headers)

    # -- multipart upload (RGWInitMultipart/RGWCompleteMultipart) ----------

    def _initiate_multipart(self, req, bucket: str, key: str) -> None:
        import uuid
        upload_id = uuid.uuid4().hex[:16]
        self.extra_io.set_omap(uploads_oid(bucket), {
            upload_id: denc.dumps({"key": key, "started": _http_date()})})
        self._xml(req, 200,
                  "<InitiateMultipartUploadResult>"
                  f"<Bucket>{escape(bucket)}</Bucket>"
                  f"<Key>{escape(key)}</Key>"
                  f"<UploadId>{upload_id}</UploadId>"
                  "</InitiateMultipartUploadResult>")

    def _upload_meta(self, bucket: str, upload_id: str) -> dict | None:
        try:
            got = self.extra_io.get_omap_keys(uploads_oid(bucket),
                                              [upload_id])
        except RadosError:
            return None
        blob = got.get(upload_id)
        return denc.loads(blob) if blob else None

    def _upload_parts(self, bucket: str, upload_id: str) -> dict:
        try:
            return {int(k): denc.loads(v) for k, v in
                    self.extra_io.get_omap(
                        parts_oid(bucket, upload_id)).items()}
        except RadosError:
            return {}

    def _upload_part(self, req, bucket: str, key: str, upload_id: str,
                     query: dict, body: bytes) -> None:
        meta = self._upload_meta(bucket, upload_id)
        if meta is None or meta["key"] != key:
            self._error(req, 404, "NoSuchUpload")
            return
        try:
            n = int(query.get("partNumber", ["0"])[0])
        except ValueError:
            n = 0
        if not 1 <= n <= 10000:
            self._error(req, 400, "InvalidPartNumber")
            return
        # a part is an object of the data pool, written by the same
        # processor (head, tail, manifest); a part sent again replaces
        # the one before it
        etag = hashlib.md5(body).hexdigest()
        self._write_object(part_soid(bucket, key, upload_id, n), body,
                           etag, _http_date())
        self.extra_io.set_omap(parts_oid(bucket, upload_id), {
            f"{n:05d}": denc.dumps({"etag": etag, "size": len(body)})})
        self._reply(req, 200, headers={"ETag": f'"{etag}"'})

    def _complete_multipart(self, req, bucket: str, key: str,
                            upload_id: str, body: bytes) -> None:
        import re
        meta = self._upload_meta(bucket, upload_id)
        if meta is None or meta["key"] != key:
            self._error(req, 404, "NoSuchUpload")
            return
        parts = self._upload_parts(bucket, upload_id)
        want = [int(m) for m in
                re.findall(r"<PartNumber>(\d+)</PartNumber>",
                           body.decode("utf-8", "replace"))] \
            if body else sorted(parts)
        if not want or any(n not in parts for n in want):
            self._error(req, 400, "InvalidPart")
            return
        if any(b <= a for a, b in zip(want, want[1:])):
            # S3 requires strictly ascending part numbers — which also
            # rejects duplicates (a part listed twice would be
            # concatenated twice into the final object)
            self._error(req, 400, "InvalidPartOrder")
            return
        # assemble: the parts are read back and the whole goes through
        # the processor as one PUT (RGWCompleteMultipart assembles by
        # manifest and moves no data; this one copies).  On a
        # versioning-enabled bucket the completed object is a NEW
        # version, like any other PUT.
        whole, md5s = bytearray(), []
        for n in want:
            got = self._read_object(part_soid(bucket, key, upload_id, n))
            if got is None:
                self._error(req, 400, "InvalidPart")
                return
            m = hashlib.md5()
            for piece in got[2]:
                for seg in iov_of(piece):
                    m.update(seg)
                    whole += seg
            md5s.append(m.digest())
        etag = hashlib.md5(b"".join(md5s)).hexdigest() + \
            f"-{len(want)}"
        vstate = (self._bucket_meta(bucket) or {}).get("versioning", "")
        ent = self._put_object(req, bucket, key, bytes(whole), vstate,
                               etag=etag, reply=False)
        vid = ent.get("version_id")
        self._cleanup_upload(bucket, key, upload_id, parts)
        self._xml(req, 200,
                  "<CompleteMultipartUploadResult>"
                  f"<Bucket>{escape(bucket)}</Bucket>"
                  f"<Key>{escape(key)}</Key>"
                  f"<ETag>&quot;{etag}&quot;</ETag>"
                  "</CompleteMultipartUploadResult>",
                  headers={"x-amz-version-id": vid} if vid else None)

    def _abort_multipart(self, req, bucket: str, key: str,
                         upload_id: str) -> None:
        meta = self._upload_meta(bucket, upload_id)
        if meta is None:
            self._error(req, 404, "NoSuchUpload")
            return
        self._cleanup_upload(bucket, meta["key"], upload_id,
                             self._upload_parts(bucket, upload_id))
        self._reply(req, 204)

    def _cleanup_upload(self, bucket: str, key: str, upload_id: str,
                        parts: dict) -> None:
        for n in parts:
            try:
                self._remove_object(part_soid(bucket, key, upload_id, n))
            except RadosError:
                pass
        try:
            self.extra_io.remove_object(parts_oid(bucket, upload_id))
        except RadosError:
            pass
        try:
            self.extra_io.rm_omap_keys(uploads_oid(bucket), [upload_id])
        except RadosError:
            pass

    def _list_uploads(self, req, bucket: str) -> None:
        try:
            ups = {k: denc.loads(v) for k, v in
                   self.extra_io.get_omap(uploads_oid(bucket)).items()}
        except RadosError:
            ups = {}
        entries = "".join(
            f"<Upload><Key>{escape(m['key'])}</Key>"
            f"<UploadId>{uid}</UploadId>"
            f"<Initiated>{m['started']}</Initiated></Upload>"
            for uid, m in sorted(ups.items()))
        self._xml(req, 200,
                  "<ListMultipartUploadsResult>"
                  f"<Bucket>{escape(bucket)}</Bucket>{entries}"
                  "</ListMultipartUploadsResult>")


def _http_date() -> str:
    return time.strftime("%a, %d %b %Y %H:%M:%S GMT", time.gmtime())


def sign_v2(method: str, path: str, date: str, access: str,
            secret: str) -> str:
    """Client-side helper producing the Authorization header."""
    to_sign = "\n".join([method, "", "", date, path])
    sig = base64.b64encode(hmac.new(
        secret.encode(), to_sign.encode(), hashlib.sha1).digest()
    ).decode()
    return f"AWS {access}:{sig}"
