"""The host path in the op tracker (ISSUE 25): PG scrubs and their
peers' scans as tracked ops with `scrub.*` spans, the messenger's
`msgr.recv` / `msgr.dispatch` / `msgr.send`, `sub_read` ops on the shard
OSDs, CPU time on spans, the client's send count on op docs, and the
jitted kernels by name.  ISSUE 38: the sender's stamps on every frame
(`msgr.handoff`, `msgr.wire`) and a tracked op of kind `reply` for
every answer to a traced request, on both messenger stacks."""

import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.utils.clock import ManualClock
from ceph_tpu.utils.config import Config
from ceph_tpu.utils.optracker import OpTracker
from ceph_tpu.vstart import MiniCluster

BASE_CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 5.0,
    "osd_op_history_size": 4096,
}
OBJECTS = 6
OBJECT_BYTES = 64 * 1024
# one clock read apart, two clocks: a span's cpu against its wall time
CLOCK_GRAIN = 2e-3


def _boot(tmp, store="filestore", **conf):
    return MiniCluster(num_mons=1, num_osds=3,
                       conf=Config(dict(BASE_CONF, **conf)),
                       store_kind=store, store_dir=str(tmp)).start()


def _ec_pool(cluster, name="hp-ec", pg_num=2):
    rados = cluster.client()
    rados.create_ec_pool(
        name, f"{name}-prof",
        {"plugin": "tpu", "k": 2, "m": 1, "host_cutover": 1},
        pg_num=pg_num)
    io = rados.open_ioctx(name)
    end = time.time() + 60
    while True:
        try:
            io.write_full("settle", b"s")
            io.remove_object("settle")
            return io
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)


def _docs(cluster, kind=None):
    out = []
    for osd in cluster.osds.values():
        for op in osd.op_tracker.dump_historic_ops()["ops"]:
            if kind is None or op["kind"] == kind:
                out.append(op)
    return out


def _wait_docs(find, want: int, window: float = 5.0):
    """A sub-op, sub-read or scan op finishes just AFTER its reply
    left: wait for the docs instead of racing their daemons."""
    end = time.time() + window
    while True:
        got = find()
        if len(got) >= want or time.time() > end:
            return got
        time.sleep(0.02)


def _spans(doc, name):
    return [s for s in doc["spans"] if s["name"] == name]


def _inside(inner, outer) -> bool:
    return inner["t0"] >= outer["t0"] and inner["t1"] <= outer["t1"]


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = _boot(tmp_path_factory.mktemp("hostpath"))
    yield c
    c.stop()


@pytest.fixture(scope="module")
def written(cluster):
    """An EC pool with a few objects, written once for the module."""
    io = _ec_pool(cluster)
    for i in range(OBJECTS):
        io.write_full(f"obj{i}", bytes([i + 1]) * OBJECT_BYTES)
    return io


def _primary_pgs(cluster, io):
    m = cluster.leader().osdmon.osdmap
    out = []
    for pgid in m.all_pgs():
        if pgid.pool != io.pool_id:
            continue
        _up, acting = m.pg_to_up_acting_osds(pgid)
        out.append((pgid, list(acting),
                    cluster.osds[acting[0]].pgs[pgid]))
    return out


# ---------------------------------------------------------------------------
# scrub
# ---------------------------------------------------------------------------


class TestScrubOps:
    def test_scrub_leaves_one_op_and_one_scan_per_peer(self, cluster,
                                                       written):
        for pgid, acting, pg in _primary_pgs(cluster, written):
            result = pg.scrub(deep=True)
            assert result["inconsistent"] == []
            scrubs = [d for d in _docs(cluster, "scrub")
                      if f"pg_scrub({pgid} " in d["description"]]
            doc = max(scrubs, key=lambda d: d["mstart"])
            assert doc["description"] == f"pg_scrub({pgid} deep=1)"
            assert doc["daemon"] == f"osd.{acting[0]}"
            trace = doc["trace_id"]
            assert trace.startswith(f"scrub:{acting[0]}:{pgid}:")
            scans = _wait_docs(
                lambda: [d for d in _docs(cluster, "scrub_scan")
                         if d["trace_id"] == trace], len(acting) - 1)
            assert sorted(d["daemon"] for d in scans) == \
                sorted(f"osd.{o}" for o in acting[1:])
            # the primary: its own scan, the wait for the peers, the compare
            names = {s["name"] for s in doc["spans"]}
            assert {"scrub.list", "scrub.cache_fold", "scrub.read",
                    "scrub.peer_wait", "scrub.compare"} <= names
            # ONE wait a PG scrub: every peer asked before our own
            # scan, all gathered after it
            (wait,) = _spans(doc, "scrub.peer_wait")
            assert wait["args"] == {"peers": len(acting) - 1,
                                    "answered": len(acting) - 1,
                                    "late": 0}
            (own,) = _spans(doc, "scrub.read")
            assert own["t1"] <= wait["t0"]
            (cmp_,) = _spans(doc, "scrub.compare")
            assert cmp_["args"]["checked"] == result["checked"]
            assert cmp_["args"]["inconsistent"] == 0
            # each peer: a queued op with the scan's spans inside execute
            for scan in scans:
                snames = {s["name"] for s in scan["spans"]}
                assert {"msgr.recv", "msgr.dispatch", "queue", "execute",
                        "scrub.list", "scrub.cache_fold",
                        "scrub.read"} <= snames
                (ex,) = _spans(scan, "execute")
                for s in scan["spans"]:
                    if s["name"].startswith("scrub."):
                        assert _inside(s, ex)
            # args add up to the PG's shard files and their bytes
            shards = nbytes = read_bytes = 0
            for d in [doc] + scans:
                osd = cluster.osds[int(d["daemon"].split(".")[1])]
                files = [n for n in osd.store.collection_list(pg.cid)
                         if not n.startswith("_pgmeta") and "@" not in n]
                (lst,) = _spans(d, "scrub.list")
                assert lst["args"]["names"] >= len(files)
                (fold,) = _spans(d, "scrub.cache_fold")
                (rd,) = _spans(d, "scrub.read")
                assert fold["args"]["shards"] + rd["args"]["shards"] \
                    == len(files)
                shards += len(files)
                sizes = {n: osd.store.stat(pg.cid, n)["size"]
                         for n in files}
                nbytes += sum(sizes.values())
                read_bytes += rd["args"]["bytes"]
                stacked = sum(s["args"]["bytes"]
                              for s in _spans(d, "scrub.stack"))
                assert stacked == rd["args"]["bytes"]
                if rd["args"]["shards"]:
                    assert _spans(d, "scrub.collect")
                    assert len(_spans(d, "scrub.collect")) == \
                        sum(s["args"]["batches"]
                            for s in _spans(d, "scrub.stack"))
            assert shards == result["checked"]
            # what was not folded from the HBM cache was read, whole
            folded = sum(_spans(d, "scrub.cache_fold")[0]["args"]["shards"]
                         for d in [doc] + scans)
            assert read_bytes <= nbytes
            if folded == 0:
                assert read_bytes == nbytes

    def test_scrub_trace_ids_count_up(self, cluster, written):
        _pgid, _acting, pg = _primary_pgs(cluster, written)[0]
        pg.scrub(deep=True)
        pg.scrub(deep=True)
        ids = [d["trace_id"] for d in _docs(cluster, "scrub")
               if d["description"].startswith(f"pg_scrub({pg.pgid} ")]
        assert len(set(ids)) == len(ids) >= 2
        ns = sorted(int(i.rsplit(":", 1)[1]) for i in ids)
        assert ns == list(range(ns[0], ns[0] + len(ns)))

    def test_replicated_scrub_is_tracked_too(self, cluster):
        rados = cluster.client()
        rados.create_pool("hp-rep", pg_num=1)
        io = rados.open_ioctx("hp-rep")
        end = time.time() + 60
        while True:
            try:
                io.write_full("r0", b"x" * 4096)
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)
        (_pgid, acting, pg), = _primary_pgs(cluster, io)
        pg.scrub(deep=False)
        doc = max((d for d in _docs(cluster, "scrub")
                   if d["description"] == f"pg_scrub({pg.pgid} deep=0)"),
                  key=lambda d: d["mstart"])
        scans = _wait_docs(
            lambda: [d for d in _docs(cluster, "scrub_scan")
                     if d["trace_id"] == doc["trace_id"]], len(acting) - 1)
        assert len(scans) == len(acting) - 1
        assert all("deep=0" in d["description"] for d in scans)
        (wait,) = _spans(doc, "scrub.peer_wait")
        assert wait["args"]["peers"] == wait["args"]["answered"] \
            == len(acting) - 1


# ---------------------------------------------------------------------------
# messenger spans, sub-reads
# ---------------------------------------------------------------------------


def _write_docs(cluster, io, oid, data):
    """(client doc, sub-op docs) of one fresh write."""
    io.write_full(oid, data)
    client = max((d for d in _docs(cluster, "client")
                  if f" {oid} " in d["description"]
                  and "writefull" in d["description"]),
                 key=lambda d: d["mstart"])
    subs = _wait_docs(
        lambda: [d for d in _docs(cluster, "subop")
                 if d["trace_id"] == client["trace_id"]
                 and d["description"].startswith("sub_op(")], 2)
    return client, subs


class TestMessengerSpans:
    @pytest.mark.parametrize("pool", ["ec", "rep"])
    def test_recv_and_dispatch_end_before_mstart(self, cluster, written,
                                                 pool):
        io = written if pool == "ec" else \
            cluster.client().open_ioctx("hp-rep")
        client, subs = _write_docs(cluster, io, f"m-{pool}",
                                   b"q" * 16384)
        assert len(subs) == 2
        for d in [client] + subs:
            (recv,) = _spans(d, "msgr.recv")
            (disp,) = _spans(d, "msgr.dispatch")
            assert recv["t0"] <= recv["t1"] == disp["t0"] <= disp["t1"]
            assert disp["t1"] <= d["mstart"]
            assert recv["args"]["bytes"] > 0
            for s in (recv, disp):
                assert 0.0 <= s["cpu"] <= s["t1"] - s["t0"] + CLOCK_GRAIN
        # the write's frame carries its payload; a sub-op its shard
        assert _spans(client, "msgr.recv")[0]["args"]["bytes"] >= 16384
        share = 16384 // 2 if pool == "ec" else 16384
        for d in subs:
            assert _spans(d, "msgr.recv")[0]["args"]["bytes"] >= share

    @pytest.mark.parametrize("pool", ["ec", "rep"])
    def test_send_before_replica_wait_and_outside_the_store(
            self, cluster, written, pool):
        io = written if pool == "ec" else \
            cluster.client().open_ioctx("hp-rep")
        client, subs = _write_docs(cluster, io, f"s-{pool}",
                                   b"w" * 16384)
        (send,) = _spans(client, "msgr.send")
        (wait,) = _spans(client, "replica_wait")
        assert send["t1"] <= wait["t0"]
        assert send["args"]["frames"] == 2
        assert send["args"]["bytes"] >= (16384 if pool == "ec"
                                         else 2 * 16384)
        for d in [client] + subs:
            sends = _spans(d, "msgr.send")
            assert sends
            store = [s for s in d["spans"]
                     if s["name"] in ("journal", "wal", "store_apply")]
            assert store
            for send in sends:
                for st in store:
                    assert send["t0"] >= st["t1"] or send["t1"] <= st["t0"]
        for d in subs:       # a shard's answer: after its commit
            (send,) = _spans(d, "msgr.send")
            assert send["args"]["frames"] == 1
            assert send["t0"] >= max(
                s["t1"] for s in d["spans"]
                if s["name"] in ("journal", "wal", "store_apply"))

    def test_recv_carries_the_socket_reads_that_fed_the_frame(
            self, cluster, written, tmp_path):
        """ISSUE 33: `reads` beside `bytes`, on the client doc and every
        sub-op doc; a 1 MiB frame takes fewer reads than the five that a
        256 KiB piece a read made of it; trace_dump shows both."""
        import json

        from ceph_tpu.tools import trace_dump
        client, subs = _write_docs(cluster, written, "m-reads",
                                   b"r" * (1 << 20))
        for d in [client] + subs:
            args = _spans(d, "msgr.recv")[0]["args"]
            assert args["reads"] >= 1 and args["bytes"] > 0
        big = _spans(client, "msgr.recv")[0]["args"]
        assert big["bytes"] > 1 << 20 and big["reads"] <= 4
        small, _ = _write_docs(cluster, written, "m-reads-small",
                               b"r" * 100)
        assert _spans(small, "msgr.recv")[0]["args"]["reads"] == 1
        daemon = client["daemon"]
        (tmp_path / f"{daemon}.json").write_text(json.dumps([client]))
        events = trace_dump.chrome_trace(
            trace_dump.load_dump_dir(str(tmp_path)))["traceEvents"]
        (recv,) = [e for e in events if e.get("name") == "msgr.recv"]
        assert recv["args"]["reads"] == big["reads"]
        assert recv["args"]["bytes"] == big["bytes"]

    def test_ec_read_leaves_sub_read_ops(self, cluster, written):
        from ceph_tpu.ops import hbm_cache
        # a read the HBM cache serves asks no shard: drop the entries
        hbm_cache.get().clear()
        assert written.read("obj1") == bytes([2]) * OBJECT_BYTES
        client = max((d for d in _docs(cluster, "client")
                      if " obj1 " in d["description"]
                      and "'read'" in d["description"]),
                     key=lambda d: d["mstart"])
        (wait,) = _spans(client, "gather_wait")
        subs = _wait_docs(
            lambda: [d for d in _docs(cluster, "subop")
                     if d["trace_id"] == client["trace_id"]],
            wait["args"]["asked"])
        assert subs, "no sub_read op under the client's trace id"
        for d in subs:
            assert d["description"].startswith("sub_read(")
            assert " obj1 " in d["description"]
            assert d["daemon"] != client["daemon"]
            names = [s["name"] for s in d["spans"]]
            for want in ("msgr.recv", "msgr.dispatch", "queue", "execute"):
                assert names.count(want) == 1
            (disp,) = _spans(d, "msgr.dispatch")
            assert disp["t1"] <= d["mstart"]
        # nothing on the primary's read doc but these (PR 28: the
        # parked gather's `gather_wait`); a healthy read asks its
        # plan's shards, the data chunks, and decodes nothing: no
        # `ec.*` span, no `ec.plan`
        assert {s["name"] for s in client["spans"]} <= \
            {"msgr.handoff", "msgr.wire", "msgr.recv", "msgr.dispatch",
             "queue", "execute", "recovery_wait", "gather_wait"}
        # the read parked for its gather: an `execute` either side
        # of `gather_wait`, neither around a messenger span
        executes = _spans(client, "execute")
        assert wait["args"]["widened"] == 0
        assert wait["args"]["asked"] == wait["args"]["used"] == len(subs)
        assert len(executes) == 2
        assert executes[0]["t1"] <= wait["t1"] <= executes[1]["t0"]
        for ex in executes:
            assert not [s for s in client["spans"]
                        if s["name"].startswith("msgr.") and _inside(s, ex)]


# ---------------------------------------------------------------------------
# cpu on spans
# ---------------------------------------------------------------------------


class TestSpanCpu:
    def test_same_thread_spans_carry_cpu(self):
        from ceph_tpu.utils import optracker
        assert {"execute", "scrub.read", "scrub.peer_wait"} <= \
            optracker.CPU_SPANS
        trk = OpTracker(ManualClock(), history_size=4)
        op = trk.create("cpu")
        op.span_begin("scrub.read")
        x = 0
        t_end = time.thread_time() + 0.02
        while time.thread_time() < t_end:
            x += 1
        op.span_end("scrub.read", loops=x)
        op.span_begin("scrub.peer_wait")
        time.sleep(0.03)
        op.span_end("scrub.peer_wait")
        op.span_begin("wal")         # not a span whose CPU is read
        op.span_end("wal")
        op.span_begin("replica_wait")
        op.add_span("ec.d2h", 1.0, 2.0)
        op.add_span("msgr.recv", 1.0, 2.0, _cpu=0.25, bytes=7)
        op.span_begin("execute")
        op.finish()                  # auto-close, by the opening thread
        doc = trk.dump_historic_ops()["ops"][0]
        by = {s["name"]: s for s in doc["spans"]}
        assert by["scrub.read"]["cpu"] >= 0.019
        assert by["scrub.read"]["args"] == {"loops": x}
        idle = by["scrub.peer_wait"]
        assert idle["cpu"] < 0.02 <= idle["t1"] - idle["t0"]
        assert "cpu" in by["execute"]
        for name in ("wal", "replica_wait", "ec.d2h"):
            assert "cpu" not in by[name]
        assert by["msgr.recv"]["cpu"] == 0.25
        for s in doc["spans"]:
            if "cpu" in s and s["name"] != "msgr.recv":
                assert s["cpu"] <= s["t1"] - s["t0"] + CLOCK_GRAIN
        assert doc["mstart_ns"] > 1_600_000_000 * 10**9

    def test_span_closed_on_another_thread_has_no_cpu(self):
        import threading
        trk = OpTracker(ManualClock(), history_size=4)
        op = trk.create("handoff")
        op.span_begin("execute")
        t = threading.Thread(target=op.span_end, args=("execute",))
        t.start()
        t.join()
        op.finish()
        (s,) = trk.dump_historic_ops()["ops"][0]["spans"]
        assert s["name"] == "execute" and "cpu" not in s

    def test_cluster_docs(self, cluster, written):
        _write_docs(cluster, written, "cpu-obj", b"c" * 16384)
        seen = set()
        for d in _docs(cluster):
            for s in d["spans"]:
                if s["name"] in ("queue", "replica_wait", "ec.coalesce",
                                 "ec.stage_h2d", "ec.device_compute",
                                 "ec.d2h", "ec.host_encode", "msgr.send",
                                 "journal", "wal", "store_apply"):
                    assert "cpu" not in s, (d["description"], s)
                elif "cpu" in s:
                    seen.add(s["name"])
                    assert -1e-9 <= s["cpu"] <= \
                        s["t1"] - s["t0"] + CLOCK_GRAIN, (d, s)
        assert {"execute", "msgr.recv", "msgr.dispatch", "scrub.read",
                "scrub.peer_wait"} <= seen


# ---------------------------------------------------------------------------
# resends by number
# ---------------------------------------------------------------------------


def test_resent_op_docs_carry_the_attempt(tmp_path):
    """A client that backs off from 50 ms against an op that takes
    300 ms sends it several times: one doc a send, numbered."""
    c = _boot(tmp_path, objecter_backoff_base=0.05,
              osd_debug_inject_dispatch_delay_probability=1.0,
              osd_debug_inject_dispatch_delay_duration=0.3)
    try:
        rados = c.client()
        rados.create_pool("hp-resend", pg_num=1)
        io = rados.open_ioctx("hp-resend")
        end = time.time() + 60
        while True:
            try:
                io.write_full("again", b"a" * 512)
                break
            except RadosError:
                if time.time() > end:
                    raise
                c.tick(0.3)
        time.sleep(1.5)          # the resends are served after the first
        by_trace: dict = {}
        for d in _docs(c, "client"):
            if " again " in d["description"]:
                by_trace.setdefault(d["trace_id"], []).append(d["attempt"])
        assert by_trace
        for attempts in by_trace.values():
            assert sorted(attempts) == list(range(1, len(attempts) + 1))
        assert max(len(a) for a in by_trace.values()) >= 2
        for d in _docs(c, "client"):
            assert "attempt" not in d["description"]
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# tracker off
# ---------------------------------------------------------------------------


def test_tracker_off_new_call_sites_are_inert(tmp_path):
    c = _boot(tmp_path, osd_enable_op_tracker=False)
    try:
        io = _ec_pool(c, "hp-off", pg_num=1)
        io.write_full("o", b"z" * 16384)
        from ceph_tpu.ops import hbm_cache
        hbm_cache.get().clear()
        assert io.read("o") == b"z" * 16384
        (_pgid, _acting, pg), = _primary_pgs(c, io)
        assert pg.scrub(deep=True)["inconsistent"] == []
        for osd in c.osds.values():
            assert osd.op_tracker.dump_historic_ops()["num_ops"] == 0
            assert osd.op_tracker.num_inflight() == 0
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# the way there and the way back (ISSUE 38)
# ---------------------------------------------------------------------------

STACKS = ["blocking", "async"]


@pytest.fixture(scope="module", params=STACKS)
def stack(request, tmp_path_factory):
    """(cluster, EC ioctx, replicated ioctx) on one messenger stack."""
    c = _boot(tmp_path_factory.mktemp(f"legs-{request.param}"),
              ms_type=request.param)
    try:
        io_ec = _ec_pool(c, "legs-ec", pg_num=1)
        rados = c.client()
        rados.create_pool("legs-rep", pg_num=1)
        io_rep = rados.open_ioctx("legs-rep")
        end = time.time() + 60
        while True:
            try:
                io_rep.write_full("settle", b"s")
                break
            except RadosError:
                if time.time() > end:
                    raise
                c.tick(0.3)
        yield c, io_ec, io_rep
    finally:
        c.stop()


def _replies(cluster, trace):
    return [d for d in _docs(cluster, "reply") if d["trace_id"] == trace]


def _check_way_in(doc):
    """The four messenger spans of a doc that came off a wire, each
    beginning where the one before it ended, all before `mstart`."""
    (hand,) = _spans(doc, "msgr.handoff")
    (wire,) = _spans(doc, "msgr.wire")
    (recv,) = _spans(doc, "msgr.recv")
    (disp,) = _spans(doc, "msgr.dispatch")
    assert hand["t0"] <= hand["t1"] == wire["t0"] <= wire["t1"] \
        == recv["t0"] <= recv["t1"] == disp["t0"] <= disp["t1"] \
        <= doc["mstart"]
    assert wire["args"]["queued"] >= 0 and "skew" not in wire["args"]
    assert "args" not in hand and "cpu" not in hand and "cpu" not in wire
    names = [s["name"] for s in doc["spans"]]
    assert names[:4] == ["msgr.handoff", "msgr.wire", "msgr.recv",
                         "msgr.dispatch"]


class TestWaitLegs:
    @pytest.mark.parametrize("pool", ["ec", "rep"])
    def test_a_write_leaves_one_reply_doc_a_remote_shard(self, stack, pool):
        cluster, io_ec, io_rep = stack
        io = io_ec if pool == "ec" else io_rep
        client, subs = _write_docs(cluster, io, f"legs-{pool}",
                                   b"l" * 16384)
        replies = _wait_docs(
            lambda: _replies(cluster, client["trace_id"]), 2)
        assert len(replies) == len(subs) == 2
        (wait,) = _spans(client, "replica_wait")
        kind = "MOSDECSubOpWriteReply" if pool == "ec" else "MOSDRepOpReply"
        assert sorted(d["description"].rsplit("<- ", 1)[1][:-1]
                      for d in replies) == sorted(d["daemon"] for d in subs)
        for d in replies:
            # on the daemon that received it, an op of its own
            assert d["daemon"] == client["daemon"]
            assert d["description"].startswith(f"reply({kind} ")
            assert [s["name"] for s in d["spans"]] == [
                "msgr.handoff", "msgr.wire", "msgr.recv", "msgr.dispatch",
                "queue", "execute"]
            _check_way_in(d)
            (q,) = _spans(d, "queue")
            (ex,) = _spans(d, "execute")
            assert q["t0"] == d["mstart"] and q["t1"] == ex["t0"]
            assert 0.0 <= ex["cpu"] <= ex["t1"] - ex["t0"] + CLOCK_GRAIN
            assert ex["t1"] == pytest.approx(d["mstart"] + d["duration"],
                                             abs=1e-3)
            # handed over inside the shard's sub-op, after its commit
            (sub,) = [x for x in subs
                      if d["description"].endswith(f"<- {x['daemon']})")]
            (send,) = _spans(sub, "msgr.send")
            hand = _spans(d, "msgr.handoff")[0]
            assert send["t0"] <= hand["t0"] <= send["t1"]
            assert wait["t0"] <= ex["t0"] <= wait["t1"]
        # the wait closes inside the last answer's handler, and the
        # waiting op's own spans are what they were
        last = max(replies, key=lambda d: _spans(d, "execute")[0]["t0"])
        assert _spans(last, "execute")[0]["t1"] >= wait["t1"]
        assert not [s for s in client["spans"]
                    if s["name"] != "replica_wait" and _inside(s, wait)
                    and s["name"].startswith("msgr.")]
        for d in [client] + subs:
            _check_way_in(d)

    def test_an_ec_read_leaves_one_reply_doc_a_sub_read(self, stack):
        from ceph_tpu.ops import hbm_cache
        cluster, io_ec, _ = stack
        io_ec.write_full("legs-read", b"g" * OBJECT_BYTES)
        hbm_cache.get().clear()
        assert io_ec.read("legs-read") == b"g" * OBJECT_BYTES
        client = max((d for d in _docs(cluster, "client")
                      if " legs-read " in d["description"]
                      and "'read'" in d["description"]),
                     key=lambda d: d["mstart"])
        subs = _wait_docs(
            lambda: [d for d in _docs(cluster, "subop")
                     if d["trace_id"] == client["trace_id"]], 1)
        replies = _wait_docs(
            lambda: _replies(cluster, client["trace_id"]), len(subs))
        assert subs and len(replies) == len(subs)
        (wait,) = _spans(client, "gather_wait")
        for d in replies:
            assert d["daemon"] == client["daemon"]
            assert d["description"].startswith(
                "reply(MOSDECSubOpReadReply s")
            # completed inline on the messenger thread: no queue
            assert [s["name"] for s in d["spans"]] == [
                "msgr.handoff", "msgr.wire", "msgr.recv", "msgr.dispatch",
                "execute"]
            _check_way_in(d)
            assert _spans(d, "execute")[0]["t0"] == d["mstart"]
            shard = d["description"].split()[1]
            (sub,) = [x for x in subs
                      if d["description"].endswith(f"<- {x['daemon']})")]
            assert sub["description"].endswith(f" {shard})")
            _check_way_in(sub)
        # the plan's shards are asked and no others (k=2 of m=1: one
        # where the primary holds a data chunk, two where the parity),
        # so every answer is in time and used
        on_time = [d for d in replies
                   if _spans(d, "execute")[0]["t0"] <= wait["t1"]]
        assert wait["args"]["widened"] == 0
        assert wait["args"]["used"] == wait["args"]["asked"] \
            == len(on_time) == len(replies)
        last = max(on_time, key=lambda d: _spans(d, "execute")[0]["t0"])
        ex = _spans(last, "execute")[0]
        assert ex["t0"] <= wait["t1"] <= ex["t1"]
        assert _spans(last, "msgr.recv")[0]["args"]["bytes"] \
            >= OBJECT_BYTES // 2

    def test_a_deep_scrub_leaves_one_reply_doc_a_scan(self, stack):
        cluster, io_ec, _ = stack
        (_pgid, acting, pg), = _primary_pgs(cluster, io_ec)
        assert pg.scrub(deep=True)["inconsistent"] == []
        doc = max((d for d in _docs(cluster, "scrub")
                   if d["description"] == f"pg_scrub({pg.pgid} deep=1)"),
                  key=lambda d: d["mstart"])
        scans = _wait_docs(
            lambda: [d for d in _docs(cluster, "scrub_scan")
                     if d["trace_id"] == doc["trace_id"]], len(acting) - 1)
        replies = _replies(cluster, doc["trace_id"])
        assert len(replies) == len(scans) == len(acting) - 1
        (wait,) = _spans(doc, "scrub.peer_wait")
        assert wait["args"]["peers"] == wait["args"]["answered"] \
            == len(acting) - 1
        for d in replies:
            assert d["daemon"] == doc["daemon"]
            sender = d["description"].rsplit("<- ", 1)[1][:-1]
            assert d["description"] == \
                f"reply(MPGInfo.scanned {sender} <- {sender})"
            _check_way_in(d)
            (ex,) = _spans(d, "execute")
            # asked before the scrub's own scan, answered by the end of
            # the one wait, and nothing of it ON the scrub's doc
            assert doc["mstart"] <= ex["t0"] <= wait["t1"]
            (scan,) = [x for x in scans if x["daemon"] == sender]
            _check_way_in(scan)
            assert doc["mstart"] <= _spans(scan, "msgr.handoff")[0]["t0"] \
                <= _spans(doc, "scrub.list")[0]["t0"]
        assert not [s for s in doc["spans"] if s["name"].startswith("msgr.")]

    def test_old_self_times_are_what_they_were(self, stack):
        """The new spans nest in nothing: a doc's self times by name,
        as the readers compute them, equal those of the same doc with
        the two new spans taken out."""
        from benchmark.readers.span_self_time import self_times
        cluster, io_ec, _ = stack
        client, subs = _write_docs(cluster, io_ec, "legs-self",
                                   b"s" * 16384)
        for d in [client] + subs:
            assert _spans(d, "msgr.handoff") and _spans(d, "msgr.wire")
            old = [s for s in d["spans"]
                   if s["name"] not in ("msgr.handoff", "msgr.wire")]
            assert len(old) == len(d["spans"]) - 2
            want = self_times(old)
            got = [(n, t) for n, t in self_times(d["spans"])
                   if n not in ("msgr.handoff", "msgr.wire")]
            assert got == want


@pytest.fixture(params=STACKS)
def pair(request):
    """Two messengers of one stack on real sockets, and what the second
    received."""
    import queue

    from ceph_tpu.msg import Dispatcher, create_messenger

    class Box(Dispatcher):
        def __init__(self):
            self.q = queue.Queue()

        def ms_dispatch(self, conn, msg):
            self.q.put(msg)
            return True

    made = []
    for name in ("legs-a", "legs-b"):
        m = create_messenger(name, Config({"ms_type": request.param}))
        m.bind(("127.0.0.1", 0))
        box = Box()
        m.add_dispatcher_tail(box)
        m.start()
        made.append((m, box))
    yield made
    for m, _box in made:
        m.shutdown()


class TestSenderStamps:
    def test_every_hand_off_is_stamped_and_the_object_is_not(self, pair):
        from ceph_tpu.msg.messenger import MONO_EPOCH_NS
        from ceph_tpu.osd.messages import MOSDPing
        (a, abox), (b, bbox) = pair
        ping = MOSDPing(op="ping", stamp=1.0, epoch=3)
        ping.src = "legs-a"            # as the send path will name it
        before = ping.encode(7)
        t0 = time.monotonic()
        a.send_message(ping, "legs-b", b.addr)
        first = bbox.q.get(timeout=5)
        time.sleep(0.05)
        t1 = time.monotonic()
        a.send_message(ping, "legs-b", b.addr)    # the same object again
        second = bbox.q.get(timeout=5)
        assert "sent_stamp" not in ping.__dict__
        assert ping.encode(7) == before
        for got, lo in ((first, t0), (second, t1)):
            assert "sent_stamp" not in got.__dict__
            handoff, taken, queued, skew = got._sent_stamp
            assert lo <= handoff <= taken <= got._recv_stamp \
                <= got._recv_complete_stamp
            assert queued == 0 and skew is False
            assert (got.op, got.stamp, got.epoch) == ("ping", 1.0, 3)
        assert second._sent_stamp[0] >= t1 > first._recv_stamp
        # loopback was never on a wire: no stamps of either side
        b.send_message(ping, "legs-b", b.addr)
        own = bbox.q.get(timeout=5)
        assert not hasattr(own, "_sent_stamp")
        assert not hasattr(own, "_recv_stamp")
        assert MONO_EPOCH_NS > 0

    def test_a_requeued_frame_keeps_its_first_stamps(self, pair):
        """A frame the link lost goes out again after the reconnect
        with the stamps of its first hand-off: the reconnect is part
        of its flight, and shows as `msgr.wire`."""
        from ceph_tpu.osd.messages import MOSDPing
        from ceph_tpu.utils import faults
        (a, _abox), (b, bbox) = pair
        a.send_message(MOSDPing(op="ping", stamp=0.0, epoch=1),
                       "legs-b", b.addr)
        bbox.q.get(timeout=5)                     # the session is up
        fs = faults.get()
        rules = [fs.drop("legs-b", 1.0, src="legs-a")]
        try:
            t0 = time.monotonic()
            a.send_message(MOSDPing(op="ping", stamp=1.0, epoch=1),
                           "legs-b", b.addr)
            end = time.time() + 5
            while not fs.rules()[-1].hits and time.time() < end:
                time.sleep(0.01)
            t_lost = time.monotonic()
            assert fs.rules()[-1].hits == 1       # written nowhere
            time.sleep(0.2)
            fs.clear(rules.pop())
            rules.append(fs.socket_kill("legs-b", 1, src="legs-a"))
            a.send_message(MOSDPing(op="ping", stamp=2.0, epoch=1),
                           "legs-b", b.addr)
            end = time.time() + 5
            while not fs.rules()[-1].hits and time.time() < end:
                time.sleep(0.01)
            fs.clear(rules.pop())
            got = [bbox.q.get(timeout=10), bbox.q.get(timeout=10)]
        finally:
            for r in rules:
                fs.clear(r)
        assert [m.stamp for m in got] == [1.0, 2.0]
        handoff, taken, queued, skew = got[0]._sent_stamp
        assert t0 <= handoff <= taken <= t_lost
        assert got[0]._recv_stamp - taken >= 0.2 and not skew
        assert a.perf.value("reconnects") >= 1

    def test_clocks_of_two_processes(self):
        """Another process's stamps come over through the two wall
        clocks; a leg they put below 0 is clamped and marked."""
        from ceph_tpu.msg import messenger
        from ceph_tpu.osd.messages import MOSDPing

        def received(sent_stamp, recv_at):
            msg = MOSDPing(op="ping")
            if isinstance(sent_stamp, tuple) and len(sent_stamp) == 4:
                sent_stamp = messenger._SENT_STAMP.pack(*sent_stamp)
            msg.sent_stamp = sent_stamp
            messenger.stamp_received(
                msg, (recv_at, 0.0, recv_at + 0.001, 0.0, 64, 1))
            return msg
        mine = messenger.MONO_EPOCH_NS
        # a process whose monotonic clock started 100 s after ours
        far = received((5.0, 5.5, 2, mine + 100 * 10**9), 106.0)
        assert far._sent_stamp == pytest.approx((105.0, 105.5, 2, False))
        late = received((5.0, 7.5, 0, mine + 100 * 10**9), 106.0)
        assert late._sent_stamp == pytest.approx((105.0, 106.0, 0, True))
        for junk in ("x", (1.0, 2.0), b"short", b"l" * 33, 7):
            msg = received(junk, 9.0)
            assert not hasattr(msg, "_sent_stamp")
            assert "sent_stamp" not in msg.__dict__
        trk = OpTracker(ManualClock(), history_size=4, daemon="osd.9")
        from ceph_tpu.osd.daemon import OSDDaemon
        op = trk.create("skewed")
        late._recv_stamp = op.mstart - 0.002
        late._recv_complete_stamp = op.mstart - 0.001
        late._sent_stamp = (op.mstart - 0.004, op.mstart - 0.002, 0, True)
        OSDDaemon._note_recv(op, late)
        op.finish()
        (doc,) = trk.dump_historic_ops()["ops"]
        (wire,) = _spans(doc, "msgr.wire")
        assert wire["args"] == {"queued": 0, "skew": 1}
        assert wire["t0"] == wire["t1"]


@pytest.mark.parametrize("ms_type", STACKS)
def test_tracker_off_leaves_no_reply_docs(tmp_path, ms_type):
    c = _boot(tmp_path, osd_enable_op_tracker=False, ms_type=ms_type)
    try:
        io = _ec_pool(c, "legs-off", pg_num=1)
        io.write_full("o", b"z" * 16384)
        from ceph_tpu.ops import hbm_cache
        hbm_cache.get().clear()
        assert io.read("o") == b"z" * 16384
        (_pgid, _acting, pg), = _primary_pgs(c, io)
        assert pg.scrub(deep=True)["inconsistent"] == []
        for osd in c.osds.values():
            assert osd.op_tracker.dump_historic_ops()["num_ops"] == 0
            assert osd.op_tracker.num_inflight() == 0
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# kernels by name
# ---------------------------------------------------------------------------


def test_jitted_programs_have_distinct_names():
    """Each kernel's program has a name of its own, and each still
    matches the roofline readers' pattern."""
    import re

    import numpy as np

    from ceph_tpu.ops import ec_kernels, gf, pallas_ec
    matrix = gf.reed_sol_van_matrix(2, 1)
    fns = {
        "encode": pallas_ec._encode_call(
            pallas_ec._g3_from_matrix(matrix).tobytes(), (1, 2), 4096,
            512, True),
        "crc": pallas_ec._crc_call(4096, 512, 8, True),
        "encode_crc": pallas_ec._encode_crc_call(
            np.ascontiguousarray(matrix).tobytes(), (1, 2), 4096, True),
        "decode": ec_kernels._apply_fn(),
        "scrub_crc": ec_kernels._crc_fn(
            4096, ec_kernels._pick_block(4096)),
        "xla_encode_crc": ec_kernels._encode_crc_fn(
            gf.expand_bitmatrix(matrix, 8).tobytes(), (8, 16), 4096,
            ec_kernels._pick_block(4096)),
        "packet_codec": ec_kernels._packet_fn(8, 8),
        "packet_encode_crc": ec_kernels.make_packet_encode_crc_fn(
            np.eye(8, dtype=np.uint8), 8, 8, 64),
    }
    names = {key: fn.__name__ for key, fn in fns.items()}
    assert names == {key: f"run_{key}" for key in fns}
    assert len(set(names.values())) == len(names)
    for name in names.values():
        assert re.search("jit_run", f"jit_{name}")
    # the name the device trace shows for a program is jit_<name>
    lowered = fns["scrub_crc"].lower(np.zeros((2, 4096), np.uint8))
    assert "jit_run_scrub_crc" in lowered.as_text()[:400]
