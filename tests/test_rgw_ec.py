"""The gateway on radosgw's layout: an erasure-coded `plugin=tpu` data
pool (k=4 m=2), a replicated index pool and a replicated extra pool;
head + appended tail with a manifest; the index prepared and completed
by cls calls; an atomic overwrite; tails removed by GC alone.

Every test runs on two placements of ONE gateway code path: `ec` (the
three pools above) and `rep` (all three names one replicated pool, the
layout the older tests run on).  What the data pool's stores hold is
held to the plain reference (`benchmark/references/rgw_s3_ec.py`), bit
for bit.
"""

import hashlib
import threading
import time
import urllib.error
import urllib.request
from http.client import HTTPConnection

import numpy as np
import pytest

from benchmark.references import rgw_s3_ec as ref
from ceph_tpu.client import RadosError
from ceph_tpu.osd.pglog import HINFO_KEY
from ceph_tpu.rgw import RGW_GC_OBJ_MIN_WAIT, RGWDaemon
from ceph_tpu.utils import denc
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

K, M, UNIT = 4, 2, 4096
CONFIG = {"pool_profile": {"k": str(K), "m": str(M),
                           "technique": "reed_sol_van"},
          "stripe_unit": UNIT}
KIB, MIB = 1 << 10, 1 << 20
SIZES = [0, 1, 4095, 16 * KIB, 512 * KIB - 1, 512 * KIB, 512 * KIB + 1,
         MIB + 7, 2_048_000, 4 * MIB + 1, 4 * MIB + 512 * KIB + 1]


class Placement:
    def __init__(self, cluster, kind: str):
        self.cluster, self.kind = cluster, kind
        self.rados = cluster.client(f"client.s3{kind}")
        if kind == "ec":
            self.rados.create_ec_pool(
                "s3data", "s3prof", {
                    "plugin": "tpu", "technique": "reed_sol_van",
                    "k": K, "m": M, "host_cutover": 1,
                    "stripe_unit": UNIT}, pg_num=4)
            self.rados.create_pool("s3index", pg_num=4)
            self.rados.create_pool("s3extra", pg_num=4)
            pools = ("s3data", "s3index", "s3extra")
        else:
            self.rados.create_pool("s3one", pg_num=4)
            pools = ("s3one",) * 3
        self.data_pool = pools[0]
        self.io = self.rados.open_ioctx(pools[0])
        end = time.time() + 60
        while True:
            try:
                self.io.write_full("settle", b"s")
                self.io.remove_object("settle")
                break
            except RadosError:
                assert time.time() < end
                time.sleep(0.3)
        self.now = [1_000_000.0]
        self.gw = RGWDaemon(self.rados, data_pool=pools[0],
                            index_pool=pools[1], data_extra_pool=pools[2],
                            clock=lambda: self.now[0]).start()
        self.base = f"http://127.0.0.1:{self.gw.port}"
        assert req("PUT", f"{self.base}/bkt").status == 200

    # -- what the data pool holds ------------------------------------------

    def data_objects(self, prefix: str = "obj.") -> set:
        return {n for n in self.io.list_objects() if n.startswith(prefix)}

    def stored(self, oid: str) -> list:
        """[(bytes, crc)] of the k+m shard files of one RADOS object
        (ec), or [(the object's bytes, None)] (rep)."""
        m = self.cluster.leader().osdmon.osdmap
        pgid = m.object_to_pg(self.io.pool_id, oid)
        _up, acting = m.pg_to_up_acting_osds(pgid)
        if self.kind != "ec":
            return [(bytes(self.io.read(oid)), None)]
        out = []
        for shard, o in enumerate(acting):
            osd = self.cluster.osds[o]
            cid, name = osd.pgs[pgid].cid, f"{oid}.s{shard}"
            hinfo = denc.loads(osd.store.getattr(cid, name, HINFO_KEY))
            out.append((bytes(osd.store.read(cid, name)),
                        int(hinfo["crc"])))
        return out

    def perf(self, name: str) -> int:
        return sum(int(o.asok.execute("perf dump")["osd"][name])
                   for o in self.cluster.osds.values())

    def data_pool_ops(self) -> list:
        """The op names of every client op on an object of the
        gateway's data (`obj.` names), from the OSDs' historic ops."""
        out = []
        for o in self.cluster.osds.values():
            for d in o.asok.execute("dump_historic_ops")["ops"]:
                desc = d["description"]
                if desc.startswith("osd_op(") and " obj." in desc:
                    out.append(desc)
        return out

    def check_stored(self, key: str, data: bytes, tag: str) -> None:
        want = ref.rados_objects("bkt", key, tag, data, CONFIG)
        head = ref.head_name("bkt", key)
        assert self.data_objects(head) == set(want)
        for oid, part in want.items():
            got = self.stored(oid)
            if self.kind == "ec":
                assert got == ref.stored(part, CONFIG), oid
            else:
                assert got == [(part, None)], oid

    def tag_of(self, key: str) -> str:
        return bytes(self.io.get_xattr(
            ref.head_name("bkt", key), "rgw.idtag")).decode()


def req(method: str, url: str, data: bytes | None = None):
    r = urllib.request.Request(url, data=data, method=method)
    return urllib.request.urlopen(r, timeout=120)


def payload(seed: int, size: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=6, conf=Config({
        "osd_op_history_size": 20000})).start()
    yield c
    c.stop()


@pytest.fixture(scope="module", params=["rep", "ec"])
def s3(request, cluster):
    p = Placement(cluster, request.param)
    yield p
    p.gw.shutdown()


@pytest.mark.parametrize("size", SIZES)
def test_put_get_layout_and_stored_state(s3, size):
    key = f"sized/{size}"
    data = payload(size, size)
    appends = s3.perf("ec_appends")
    resp = req("PUT", f"{s3.base}/bkt/{key}", data)
    assert resp.status == 200
    assert resp.headers["ETag"] == f'"{ref.etag(data)}"'
    got = req("GET", f"{s3.base}/bkt/{key}")
    assert got.headers["ETag"] == f'"{ref.etag(data)}"'
    assert int(got.headers["Content-Length"]) == size
    assert got.read() == data
    head = req("HEAD", f"{s3.base}/bkt/{key}")
    assert int(head.headers["Content-Length"]) == size
    # the RADOS objects the reference's layout names and no others,
    # every shard file and stored CRC
    s3.check_stored(key, data, s3.tag_of(key))
    if s3.kind == "ec":
        # every write to a tail object after its first is an append
        # that took the O(tail) path, not the whole-object re-encode
        lay = ref.layout(size, CONFIG)
        want = sum(len(writes) - 1 for _n, _at, _l, writes in lay["tails"])
        assert s3.perf("ec_appends") - appends == want
        assert s3.perf("ec_append_fallbacks") == 0


def test_list_after_write_names_size_and_etag(s3):
    data = payload(7, 600_000)
    assert req("PUT", f"{s3.base}/bkt/listed/one", data).status == 200
    body = req("GET", f"{s3.base}/bkt?prefix=listed/").read().decode()
    assert "<Key>listed/one</Key>" in body
    assert f"<Size>{len(data)}</Size>" in body
    assert ref.etag(data) in body


def test_delete_then_404_and_tail_left_to_gc(s3):
    data = payload(8, 700_000)
    assert req("PUT", f"{s3.base}/bkt/gone", data).status == 200
    tag = s3.tag_of("gone")
    assert req("DELETE", f"{s3.base}/bkt/gone").status == 204
    with pytest.raises(urllib.error.HTTPError) as ei:
        req("GET", f"{s3.base}/bkt/gone")
    assert ei.value.code == 404
    assert "<Key>gone</Key>" not in \
        req("GET", f"{s3.base}/bkt").read().decode()
    # the head is gone, the tail waits for GC
    tail = ref.tail_name("bkt", "gone", tag, 1)
    assert s3.data_objects(ref.head_name("bkt", "gone")) == {tail}
    assert tail in [o for oids in s3.gw.gc.list().values() for o in oids]


def test_delete_marker_and_versions(s3):
    assert req("PUT", f"{s3.base}/vbkt").status == 200
    cfg = (b"<VersioningConfiguration><Status>Enabled</Status>"
           b"</VersioningConfiguration>")
    assert req("PUT", f"{s3.base}/vbkt?versioning", cfg).status == 200
    v1, v2 = payload(9, 530_000), payload(10, 5_000)
    id1 = req("PUT", f"{s3.base}/vbkt/k", v1).headers["x-amz-version-id"]
    id2 = req("PUT", f"{s3.base}/vbkt/k", v2).headers["x-amz-version-id"]
    assert req("GET", f"{s3.base}/vbkt/k").read() == v2
    assert req("GET", f"{s3.base}/vbkt/k?versionId={id1}").read() == v1
    marker = req("DELETE", f"{s3.base}/vbkt/k")
    assert marker.headers["x-amz-delete-marker"] == "true"
    with pytest.raises(urllib.error.HTTPError) as ei:
        req("GET", f"{s3.base}/vbkt/k")
    assert ei.value.code == 404
    assert ei.value.headers["x-amz-delete-marker"] == "true"
    assert req("GET", f"{s3.base}/vbkt/k?versionId={id2}").read() == v2
    mid = marker.headers["x-amz-version-id"]
    assert req("DELETE", f"{s3.base}/vbkt/k?versionId={mid}").status == 204
    assert req("GET", f"{s3.base}/vbkt/k").read() == v2


def test_multipart_on_the_placement(s3):
    init = req("POST", f"{s3.base}/bkt/mp/big?uploads").read().decode()
    uid = init.split("<UploadId>")[1].split("<")[0]
    parts = [payload(20, 700_000), payload(21, 600_001), payload(22, 17)]
    for n, part in enumerate(parts, 1):
        r = req("PUT", f"{s3.base}/bkt/mp/big?uploadId={uid}"
                f"&partNumber={n}", part)
        assert r.headers["ETag"] == f'"{ref.etag(part)}"'
    done = req("POST", f"{s3.base}/bkt/mp/big?uploadId={uid}").read()
    want = hashlib.md5(b"".join(hashlib.md5(p).digest()
                                for p in parts)).hexdigest() + "-3"
    assert want.encode() in done
    whole = b"".join(parts)
    got = req("GET", f"{s3.base}/bkt/mp/big")
    assert got.read() == whole and got.headers["ETag"] == f'"{want}"'
    # the object lies as any PUT of its size does; the parts are gone
    # (their tails are GC's)
    s3.gw.gc.process(now=s3.now[0] + RGW_GC_OBJ_MIN_WAIT + 1)
    s3.check_stored("mp/big", whole, s3.tag_of("mp/big"))


def test_overwrite_is_atomic_and_old_tails_are_gc_s(s3):
    """Four writers overwrite one key with versions of different sizes
    either side of the head / tail boundary while four readers GET it:
    every body is one version whole, never a 404 or a 5xx."""
    sizes = [100, 512 * KIB, 512 * KIB + 1, 900_000, 1_300_000, 40_000]
    versions = {w: [payload(1000 + 10 * w + i, sizes[(w + i) % len(sizes)])
                    for i in range(4)] for w in range(4)}
    etags = {ref.etag(v) for vs in versions.values() for v in vs}
    first = payload(999, 800_000)
    etags.add(ref.etag(first))
    assert req("PUT", f"{s3.base}/bkt/hot", first).status == 200
    stop = threading.Event()
    bad: list = []
    reads = [0]

    def writer(w: int) -> None:
        conn = HTTPConnection("127.0.0.1", s3.gw.port, timeout=120)
        for v in versions[w]:
            conn.request("PUT", "/bkt/hot", body=v)
            r = conn.getresponse()
            r.read()
            if r.status != 200:
                bad.append(("put", r.status))

    def reader() -> None:
        conn = HTTPConnection("127.0.0.1", s3.gw.port, timeout=120)
        while not stop.is_set():
            conn.request("GET", "/bkt/hot")
            r = conn.getresponse()
            body = r.read()
            reads[0] += 1
            if r.status != 200:
                bad.append(("get", r.status))
            elif ref.etag(body) not in etags or \
                    r.headers["ETag"] != f'"{ref.etag(body)}"' or \
                    int(r.headers["Content-Length"]) != len(body):
                bad.append(("torn", len(body), r.headers["ETag"]))

    readers = [threading.Thread(target=reader) for _ in range(4)]
    writers = [threading.Thread(target=writer, args=(w,))
               for w in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(300)
    stop.set()
    for t in readers:
        t.join(60)
    assert not bad, bad[:5]
    assert reads[0] >= 4
    # one version stands; every other version's tail is on the GC
    # list, still there, and goes when its wait is over, not before
    final = req("GET", f"{s3.base}/bkt/hot").read()
    assert ref.etag(final) in etags
    # the index follows the head, whichever writer completed last
    listed = req("GET", f"{s3.base}/bkt?prefix=hot").read().decode()
    assert f"<Size>{len(final)}</Size>" in listed
    assert ref.etag(final) in listed
    head = ref.head_name("bkt", "hot")
    live = set(ref.rados_objects("bkt", "hot", s3.tag_of("hot"), final,
                                 CONFIG))
    queued = {o for oids in s3.gw.gc.list().values() for o in oids
              if o.startswith(head)}
    assert s3.data_objects(head) == live | queued
    assert queued and not queued & live
    for oid in sorted(queued)[:3]:
        assert len(s3.io.read(oid)) > 0
    assert s3.gw.gc.process(now=s3.now[0] + RGW_GC_OBJ_MIN_WAIT - 1) == 0
    assert s3.data_objects(head) == live | queued
    s3.gw.gc.process(now=s3.now[0] + RGW_GC_OBJ_MIN_WAIT + 1)
    assert s3.data_objects(head) == live
    assert req("GET", f"{s3.base}/bkt/hot").read() == final
    s3.check_stored("hot", final, s3.tag_of("hot"))


def test_data_pool_sees_no_omap_no_cls_no_offset_write(s3):
    """Object data and nothing else on the data pool: its objects were
    written by `writefull`, `append`, xattrs and guarded deletes."""
    descs = s3.data_pool_ops()
    assert descs
    allowed = {"writefull", "append", "setxattr", "cmpxattr", "delete",
               "getxattrs", "getxattr", "read"}
    for desc in descs:
        ops = set(desc.split("[", 1)[1].rstrip("])").replace("'", "")
                  .split(", "))
        assert ops <= allowed, desc
    if s3.kind == "ec":
        # nothing but the gateway's data objects lies on the data pool
        assert all(n.startswith("obj.") for n in s3.io.list_objects())
        m = s3.cluster.leader().osdmon.osdmap
        for osd in s3.cluster.osds.values():
            for pgid, pg in osd.pgs.items():
                if pgid.pool != s3.io.pool_id:
                    continue
                for name in osd.store.collection_list(pg.cid):
                    if not name.startswith("_pgmeta"):
                        assert not osd.store.omap_get(pg.cid, name), name
        assert m.pools[s3.io.pool_id].is_erasure


def test_get_with_a_data_holding_osd_down(s3):
    """(last: it takes an OSD away)  The head's and the tail's reads
    decode, and the body is exact."""
    if s3.kind != "ec":
        pytest.skip("a replicated pool decodes nothing")
    data = payload(77, 1_100_000)
    assert req("PUT", f"{s3.base}/bkt/degraded", data).status == 200
    m = s3.cluster.leader().osdmon.osdmap
    head = ref.head_name("bkt", "degraded")
    pgid = m.object_to_pg(s3.io.pool_id, head)
    _up, acting = m.pg_to_up_acting_osds(pgid)
    victim = acting[1]                  # holds data chunk 1 of the head
    s3.cluster.kill_osd(victim)
    s3.cluster.wait_for_osd_down(victim, timeout=60)
    end = time.time() + 120
    while True:
        try:
            got = req("GET", f"{s3.base}/bkt/degraded")
            break
        except urllib.error.HTTPError:
            assert time.time() < end
            time.sleep(0.5)
    assert got.read() == data
    assert got.headers["ETag"] == f'"{ref.etag(data)}"'
