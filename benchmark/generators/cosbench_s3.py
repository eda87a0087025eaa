"""COSBench's S3 sample workload (conf/s3-config-sample.xml) against the
gateway on radosgw's placement (`pools/rgw_ec.py`): stages init and
prepare in set-up, main in the window.

Parameters (traffic file):
  clients           COSBench's `workers`: threads, each with one request
                    in flight on its own keep-alive connection, no think
                    time, no rate (a closed loop)
  buckets           containers; an op's is u(1, buckets)
  objects           prepared objects a bucket: names 1..objects are
                    PUT in set-up and only read in the window; names
                    objects+1..2*objects are what the window's PUTs
                    write (a name drawn twice is overwritten)
  block_gets        GETs in a block of the schedule
  size_classes      [[least, most, PUTs a block]]: the User Guide's
                    h(1|64|10,64|512|20,512|2048|30)KB, KB = 1,000
                    bytes; a size is uniform inside its class
  ramp_seconds      the same traffic runs this long before the window
  readback_written, readback_prepared
                    keys read back and compared after the window

ONE seeded schedule is shared by the workers, made block by block:
`block_gets` GETs (bucket u(1,buckets), whole object) and the classes'
PUTs (bucket u(1,buckets), object u(objects+1, 2*objects)), shuffled
inside the block: a quota sample where COSBench draws each op
independently.  The prepared objects' sizes are a quota sample of the
same histogram (1/6, 1/3, 1/2), and so are a block's GETs: of 24, 4 /
8 / 12 read a prepared object of the three classes, uniform among the
bucket's objects of that class.  Every prepared object is as likely as
under u(1,objects); what goes is the swing of the bytes a GET between
seeds (3.7% of the mean over a window's 900 draws, which alone took
`read_mibps`'s spread over six seeds to 9.2% of a limit of 10%).

Payload bytes are a function of (seed, bucket, key, version): a stamp
and a slice of one seeded buffer.  Requests are path-style and signed
(AWS v2, `ceph_tpu.rgw.sign_v2`).

The gateway boots in `prepare` on the harness's own RADOS client and on
the configuration's three pools, and is stopped after the checks.  The
parts of the program named here: `ceph_tpu.rgw.RGWDaemon(rados, port,
access_key, secret_key, data_pool=, index_pool=, data_extra_pool=)`,
its `port`, `asok` (`perf dump` block `rgw`, `dump_historic_ops`),
`shutdown()`; `ceph_tpu.rgw.sign_v2`, `index_oid`.

`correct` (all exact; `references/rgw_s3_ec.py`): (i) every GET of the
window returns the prepared version of its key (the read and the write
ranges are disjoint, as the sample's are), whole, with its ETag and
Content-Length; (ii) the sample is read back: a prepared key its one
version, a key PUT in the window an acknowledged version that no PUT
begun after its acknowledgement replaced; (iii) for every sampled key
the data pool holds the RADOS objects the reference's layout names
under the head's tag and none else under that tag, and all their k+m
shard files and CRCs equal the reference's (under the harness's names:
the configuration names no reference, whose payloads are of one size);
(iv) the sampled keys' index entries are on all three replicas of their
bucket's index PG with the size and the etag; (v) both buckets, listed
to the end, name the prepared and the acknowledged keys with their
sizes and etags, a key with a failed PUT allowed either way; (vi) the
gateway's GC removed nothing in the window.  A PUT that failed leaves
its key in doubt; the key is left out of (ii)-(iv).
"""

from __future__ import annotations

import hashlib
import struct
import threading
import time
from email.utils import formatdate
from http.client import HTTPConnection
from urllib.parse import quote
from xml.sax.saxutils import unescape

import numpy as np

from benchmark import cluster as cl
from benchmark.references import rgw_s3_ec as ref

ACCESS, SECRET = "cosbench", "cosbench-secret"
STAMP = struct.Struct("<QIII")      # seed, bucket, key, version
# the gateway's spans whose window means the log gives (no reader is
# handed a client tracker's docs: PERF.md section 7)
SPANS = ("rgw.recv_body", "rgw.etag", "rgw.idx_prepare", "rgw.put_tail",
         "rgw.put_head", "rgw.idx_complete", "rgw.get_head", "rgw.get_tail",
         "rgw.send_body")


def bucket_name(b: int) -> str:
    return f"mybucket{b}"


def key_name(n: int) -> str:
    return f"myobjects{n}"


class Payloads:
    def __init__(self, seed: int, most: int):
        rng = np.random.default_rng([int(seed), 0x53AC])
        self.seed = int(seed)
        self.base = rng.integers(0, 256, most + 4096, dtype=np.uint8) \
            .tobytes()

    def make(self, bucket: int, key: int, version: int, size: int) -> bytes:
        at = (key * 131 + version * 31 + bucket * 7) % 4096
        body = STAMP.pack(self.seed, bucket, key, version) + \
            self.base[at: at + max(0, size - STAMP.size)]
        return body[:size]


class Client:
    """One keep-alive connection that signs what it sends."""

    def __init__(self, port: int):
        from ceph_tpu.rgw import sign_v2
        self.port, self.sign = port, sign_v2
        self.conn = HTTPConnection("127.0.0.1", port, timeout=300)

    def request(self, method: str, path: str, body: bytes | None = None,
                query: str = ""):
        date = formatdate(usegmt=True)
        headers = {"Date": date, "Authorization": self.sign(
            method, path, date, ACCESS, SECRET)}
        url = quote(path) + (f"?{query}" if query else "")
        try:
            self.conn.request(method, url, body=body, headers=headers)
            resp = self.conn.getresponse()
        except (OSError, ConnectionError):
            # the server closed an idle connection: once more, afresh
            self.conn.close()
            self.conn = HTTPConnection("127.0.0.1", self.port, timeout=300)
            self.conn.request(method, url, body=body, headers=headers)
            resp = self.conn.getresponse()
        return resp, resp.read()


def quota_sizes(rng, classes: list, count: int) -> list[tuple]:
    """`count` (size, index of its class), the classes' shares kept
    exactly (but for rounding), uniform inside a class, shuffled."""
    weight = sum(c[2] for c in classes)
    sizes: list[tuple] = []
    for idx, (least, most, w) in enumerate(classes):
        n = count * w // weight
        sizes += [(int(s), idx) for s in rng.integers(least, most + 1, n)]
    while len(sizes) < count:
        least, most, _w = classes[-1]
        sizes.append((int(rng.integers(least, most + 1)),
                      len(classes) - 1))
    order = rng.permutation(len(sizes))
    return [sizes[i] for i in order]


def prepare(ctx) -> dict:
    from ceph_tpu.rgw import RGWDaemon
    dep, p = ctx.dep, ctx.params
    name = dep.io.pool_name
    gw = RGWDaemon(dep.rados, access_key=ACCESS, secret_key=SECRET,
                   data_pool=name, index_pool=dep.pool.index_pool(name),
                   data_extra_pool=dep.pool.extra_pool(name)).start()
    buckets, objects = int(p["buckets"]), int(p["objects"])
    classes = [list(c) for c in p["size_classes"]]
    payloads = Payloads(ctx.seed, max(c[1] for c in classes))
    store = ref.Store()
    st = ctx.s3 = {"gw": gw, "payloads": payloads, "store": store,
                   "versions": {}, "sent": {}, "doubt": set(),
                   "lock": threading.Lock()}
    boot = Client(gw.port)
    for b in range(1, buckets + 1):
        resp, _ = boot.request("PUT", f"/{bucket_name(b)}")
        if resp.status != 200:
            raise cl.CheckFailed(f"create bucket: {resp.status}")
        store.create(bucket_name(b))
    # COSBench's prepare: every object of the read range, `clients`
    # workers, through the gateway
    rng = np.random.default_rng([ctx.seed, 0x9E7A])
    todo, by_class = [], {}
    for b in range(1, buckets + 1):
        for n, (size, idx) in enumerate(
                quota_sizes(rng, classes, objects), 1):
            todo.append((b, n, size))
            by_class.setdefault((b, idx), []).append(n)
    st["by_class"] = by_class
    errors: list[str] = []
    t0 = time.monotonic()

    def worker(idx: int) -> None:
        cli = Client(gw.port)
        for b, n, size in todo[idx::int(p["clients"])]:
            data = payloads.make(b, n, 0, size)
            resp, _ = cli.request("PUT",
                                  f"/{bucket_name(b)}/{key_name(n)}", data)
            if resp.status != 200:
                errors.append(f"prepare PUT {b}/{n}: {resp.status}")
                return
            with st["lock"]:
                store.put(bucket_name(b), key_name(n), 0, data)
                st["versions"][(b, n)] = [(0, size, 0.0, 0.0)]

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(int(p["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(cl.WARM_BOUND)
    if errors or any(t.is_alive() for t in threads):
        raise cl.CheckFailed(f"prepare failed: {errors[:3]}")
    took = time.monotonic() - t0
    total = sum(size for _b, _n, size in todo)
    return {"buckets": buckets, "prepared_objects": len(todo),
            "prepared_bytes": total, "prepare_s": round(took, 3),
            "prepare_puts_per_s": round(len(todo) / took, 2),
            "prepare_mibps": round(total / took / (1 << 20), 2),
            "chunk_bytes": gw.chunk_size,
            "warm_left": cl.wait_warm(
                lambda: cl.pipeline_stats()["warmups_inflight"] == 0,
                cl.WARM_BOUND, "what the prepare stage's writes started")}


class Schedule:
    """The one seeded op list the workers share, block by block."""

    def __init__(self, seed: int, p: dict, by_class: dict):
        """`by_class`: {(bucket, index of a size class): the prepared
        objects of that class}."""
        self.rng = np.random.default_rng([int(seed), 0xC05B])
        self.p, self.lock, self.block = p, threading.Lock(), []
        self.by_class = by_class

    def _make_block(self) -> list:
        p, rng = self.p, self.rng
        buckets, objects = int(p["buckets"]), int(p["objects"])
        weight = sum(c[2] for c in p["size_classes"])
        ops = []
        for idx, (_least, _most, w) in enumerate(p["size_classes"]):
            for _ in range(int(p["block_gets"]) * w // weight):
                b = int(rng.integers(1, buckets + 1))
                ops.append(("GET", b, int(rng.choice(
                    self.by_class[(b, idx)])), 0))
        for least, most, count in p["size_classes"]:
            ops += [("PUT", int(rng.integers(1, buckets + 1)),
                     int(rng.integers(objects + 1, 2 * objects + 1)),
                     int(rng.integers(least, most + 1)))
                    for _ in range(int(count))]
        order = rng.permutation(len(ops))
        return [ops[i] for i in order]

    def take(self) -> tuple:
        with self.lock:
            if not self.block:
                self.block = self._make_block()
            return self.block.pop()


def run(ctx, seconds: float) -> dict:
    p, st = ctx.params, ctx.s3
    gw, payloads, store = st["gw"], st["payloads"], st["store"]
    schedule = Schedule(ctx.seed, p, st["by_class"])
    clients = int(p["clients"])
    stop_at = [float("inf")]
    records: list[list] = [[] for _ in range(clients)]
    errors: list[str] = []
    bad: list[str] = []

    def do_get(cli, rec, b: int, n: int) -> None:
        want = store.get(bucket_name(b), key_name(n))
        t0 = time.monotonic()
        resp, body = cli.request("GET", f"/{bucket_name(b)}/{key_name(n)}")
        digest = hashlib.md5(body).hexdigest()
        t1 = time.monotonic()
        if resp.status != 200:
            rec.append(("read", t0, t1, False, 0))
            errors.append(f"GET {b}/{n}: {resp.status}")
            bad.append(f"GET {b}/{n} answered {resp.status} for a key "
                       "with an acknowledged version")
            return
        rec.append(("read", t0, t1, True, len(body)))
        if (len(body), digest) != want[1:] or \
                resp.headers["ETag"] != f'"{digest}"' or \
                int(resp.headers["Content-Length"]) != len(body):
            bad.append(f"GET {b}/{n}: {len(body)} bytes md5 {digest} etag "
                       f"{resp.headers['ETag']}, want {want[1:]}")

    def do_put(cli, rec, b: int, n: int, size: int) -> None:
        with st["lock"]:
            hist = st["versions"].setdefault((b, n), [])
            version = st["sent"][(b, n)] = st["sent"].get((b, n), 0) + 1
            st["doubt"].add((b, n, version))    # until acknowledged
        data = payloads.make(b, n, version, size)
        t0 = time.monotonic()
        resp, _ = cli.request("PUT", f"/{bucket_name(b)}/{key_name(n)}",
                              data)
        t1 = time.monotonic()
        if resp.status != 200:
            rec.append(("write", t0, t1, False, 0))
            errors.append(f"PUT {b}/{n}: {resp.status}")
            return
        if resp.headers["ETag"] != f'"{ref.etag(data)}"':
            bad.append(f"PUT {b}/{n}: etag {resp.headers['ETag']}")
        with st["lock"]:
            st["doubt"].discard((b, n, version))
            store.put(bucket_name(b), key_name(n), version, data)
            hist.append((version, size, t0, t1))
        rec.append(("write", t0, t1, True, size))

    def worker(idx: int) -> None:
        cli, rec = Client(gw.port), records[idx]
        while time.monotonic() < stop_at[0]:
            op, b, n, size = schedule.take()
            try:
                if op == "GET":
                    do_get(cli, rec, b, n)
                else:
                    do_put(cli, rec, b, n, size)
            except Exception as e:       # a connection lost mid-answer
                now = time.monotonic()
                rec.append(("read" if op == "GET" else "write", now, now,
                            False, 0))
                errors.append(f"{op} {b}/{n}: {type(e).__name__}: {e}")
                cli = Client(gw.port)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True,
                                name=f"cosbench-worker-{i}")
               for i in range(clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(float(p["ramp_seconds"]))
    before = gw.asok.execute("perf dump")["rgw"]
    t_open = ctx.open_window()
    t_close = t_open + seconds
    stop_at[0] = t_close
    time.sleep(max(0.0, t_close - time.monotonic()))
    ctx.close_window()
    after = gw.asok.execute("perf dump")["rgw"]
    for t in threads:
        t.join(600.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a worker did not finish its last request")
    moved = {k: after[k] - before[k] for k in sorted(after)}
    ctx.log(f"rgw counters over the window {moved}")
    log_span_means(ctx, gw, t_open, t_close)
    ops = [r for rec in records for r in rec]
    return {"t_open": t_open, "t_close": t_close,
            "ramp_s": t_open - t_start, "ops": ops, "bad": bad,
            "errors": errors, "rgw_delta": moved}


def log_span_means(ctx, gw, t_open: float, t_close: float) -> None:
    """Mean milliseconds of each gateway span over the requests of the
    window that the gateway's ring still holds (all of them in a traced
    run, whose history is raised)."""
    docs = [d for d in gw.asok.execute("dump_historic_ops")["ops"]
            if t_open <= d["mstart"] <= t_close]
    for verb in ("PUT", "GET"):
        mine = [d for d in docs if d["description"].startswith(verb + " ")]
        if not mine:
            continue
        sums: dict = {}
        for d in mine:
            for s in d["spans"]:
                if s["name"] in SPANS:
                    sums[s["name"]] = sums.get(s["name"], 0.0) + \
                        (s["t1"] - s["t0"])
        whole = sum(d["duration"] for d in mine) / len(mine)
        ctx.log(f"gateway {verb}: {len(mine)} requests in the ring, mean "
                f"{1000.0 * whole:.2f} ms; span means (ms a request) "
                + ", ".join(f"{n} {1000.0 * sums[n] / len(mine):.2f}"
                            for n in SPANS if n in sums))


def acceptable(hist: list) -> set:
    """Of a key's acknowledged versions (version, size, t0, t1), those
    the key may stand at once no request is in flight: the ones no
    other PUT that began after their acknowledgement replaced."""
    return {v for v, _s, _t0, t1 in hist
            if not any(w != v and w0 > t1 for w, _ws, w0, _w1 in hist)}


def listing(cli: Client, bucket: str) -> dict:
    """key -> (size, etag) of a bucket listed to the end."""
    out, marker = {}, ""
    while True:
        resp, body = cli.request("GET", f"/{bucket}",
                                 query=f"marker={quote(marker)}")
        if resp.status != 200:
            raise cl.CheckFailed(f"list {bucket}: {resp.status}")
        text = body.decode()
        for ent in text.split("<Contents>")[1:]:
            key = unescape(ent.split("<Key>")[1].split("</Key>")[0])
            size = int(ent.split("<Size>")[1].split("</Size>")[0])
            etag = ent.split("<ETag>")[1].split("</ETag>")[0] \
                .replace("&quot;", "")
            out[key] = (size, etag)
            marker = key
        if "<IsTruncated>true</IsTruncated>" not in text:
            return out


def verify(ctx, window: dict) -> dict:
    from ceph_tpu.rgw import index_oid
    from ceph_tpu.utils import denc
    dep, p, st = ctx.dep, ctx.params, ctx.s3
    gw, payloads = st["gw"], st["payloads"]
    objects = int(p["objects"])
    rng = np.random.default_rng([ctx.seed, 0x5A3F])
    doubted = {(b, n) for b, n, _v in st["doubt"]}
    written = sorted(k for k, h in st["versions"].items()
                     if k[1] > objects and h and k not in doubted)
    prepared = sorted(k for k in st["versions"] if k[1] <= objects)

    def pick(pool: list, n: int) -> list:
        return [pool[i] for i in rng.choice(
            len(pool), min(n, len(pool)), replace=False)] if pool else []

    sample = pick(written, int(p["readback_written"])) + \
        pick(prepared, int(p["readback_prepared"]))
    cli = Client(gw.port)
    names = dep.io.list_objects()
    replicas: dict = {}
    read_bad = files = data_bad = crc_bad = layout_bad = index_short = 0
    for b, n in sample:
        hist = st["versions"][(b, n)]
        bucket, key = bucket_name(b), key_name(n)
        resp, body = cli.request("GET", f"/{bucket}/{key}")
        by_etag = {ref.etag(payloads.make(b, n, v, size)): (v, size)
                   for v, size, _t0, _t1 in hist if v in acceptable(hist)}
        got = hashlib.md5(body).hexdigest()
        if resp.status != 200 or got not in by_etag or \
                resp.headers["ETag"] != f'"{got}"':
            read_bad += 1
            ctx.log(f"readback {bucket}/{key}: {resp.status}, {len(body)} "
                    f"bytes, md5 {got}: no acceptable version of "
                    f"{sorted(by_etag.values())}")
            continue
        version, size = by_etag[got]
        data = payloads.make(b, n, version, size)
        # (iii) the RADOS objects under the head's tag, and their files
        head = ref.head_name(bucket, key)
        tag = bytes(dep.io.get_xattr(head, "rgw.idtag")).decode()
        want = ref.rados_objects(bucket, key, tag, data, dep.config)
        have = {o for o in names
                if o == head or o.startswith(f"{head}.shadow.{tag}_")}
        if have != set(want):
            layout_bad += 1
            ctx.log(f"{bucket}/{key}: the data pool has {sorted(have)}, "
                    f"the reference's layout {sorted(want)}")
            continue
        for oid, part in want.items():
            for (label, fdata, fcrc), (wdata, wcrc) in zip(
                    dep.pool.stored(dep, oid), ref.stored(part, dep.config)):
                files += 1
                if fdata != wdata:
                    data_bad += 1
                    ctx.log(f"stored {label} differs from the reference")
                if fcrc != wcrc:
                    crc_bad += 1
                    ctx.log(f"crc of {label}: stored {fcrc:#x}, reference "
                            f"{wcrc:#x}")
        # (iv) the index entry, on every replica of the index PG
        for label, omap in replicas.setdefault(
                bucket, dep.pool.index_replicas(dep, index_oid(bucket))):
            ent = denc.loads(omap[key]) if omap and key in omap else {}
            if (ent.get("size"), ent.get("etag")) != (size, got):
                index_short += 1
                ctx.log(f"index entry of {key} on {label}: "
                        f"{ent.get('size')}, {ent.get('etag')}")
    ctx.log(f"read back {len(sample)} keys ({len(written)} written in the "
            f"run, {len(doubted)} in doubt); stored files compared "
            f"{files}; index replicas a bucket "
            f"{ {b: len(r) for b, r in replicas.items()} }")
    # (v) the listings
    missing = unknown = wrong = 0
    for b in range(1, int(p["buckets"]) + 1):
        listed = listing(cli, bucket_name(b))
        known = {key_name(n): h for (bb, n), h in st["versions"].items()
                 if bb == b}
        for key, hist in known.items():
            n = int(key[len("myobjects"):])
            if (b, n) in doubted:
                continue
            ok = {(size, ref.etag(payloads.make(b, n, v, size)))
                  for v, size, _t0, _t1 in hist if v in acceptable(hist)}
            if not ok:
                continue
            if key not in listed:
                missing += 1
            elif listed[key] not in ok:
                wrong += 1
        unknown += sum(1 for key in listed if key not in known and (
            b, int(key[len("myobjects"):])) not in doubted)
    comparisons = [
        ("readback_mismatches", read_bad, "<=", 0),
        ("readback_objects", len(sample), ">=", 1),
        ("layout_mismatches", layout_bad, "<=", 0),
        ("stored_mismatches", data_bad, "<=", 0),
        ("stored_crc_mismatches", crc_bad, "<=", 0),
        ("stored_files_compared", files, ">=", sum(
            dep.pool.shape(dep.config)[:2]) * (
                int(p["readback_written"]) + int(p["readback_prepared"]))),
        ("index_replicas_short", index_short, "<=", 0),
        ("listing_missing", missing, "<=", 0),
        ("listing_unknown", unknown, "<=", 0),
        ("listing_wrong", wrong, "<=", 0),
        ("rgw_gc_removed_in_window", window["rgw_delta"]["gc_removed"],
         "<=", 0)]

    def stop_gateway() -> list:
        gw.shutdown()
        return []

    return {"comparisons": comparisons, "stored_objects": [],
            "after_stored_check": stop_gateway}
