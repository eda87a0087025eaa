"""The readers of ISSUE 25 (`op_span_time`, `span_cpu_time`,
`sends_per_op`, `idle_gap_spans`) on hand-made cases and on fixtures
recorded from traced chip runs of this tree (TPU v5 lite): a scrub
window's docs, a mixed window's docs with sub-reads, and the docs and
`.xplane.pb` of a few dispatches of a write window together with the
clock offset the recording script noted."""

import copy
import json
import os

import pytest

from benchmark import harness, trace

FIX = os.path.join(harness.HERE, "fixtures")


def reader(name):
    return harness.load_module(harness.HERE, "readers", name)


op_span_time = reader("op_span_time")
span_cpu_time = reader("span_cpu_time")
sends_per_op = reader("sends_per_op")
idle_gap_spans = reader("idle_gap_spans")


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=(), lines=None):
        self.op_docs = list(docs)
        self.trace = None if lines is None else {"lines": lines}
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def doc(kind, trace_id, desc, spans, mstart=0.0, **extra):
    return dict({"kind": kind, "trace_id": trace_id, "description": desc,
                 "daemon": "osd.0", "mstart": mstart,
                 "spans": [dict(zip(("name", "t0", "t1"), s[:3]),
                                **(s[3] if len(s) > 3 else {}))
                           for s in spans]}, **extra)


# -- hand-made cases ----------------------------------------------------------


def scrub_docs():
    return [
        doc("scrub", "scrub:0:1.0:1", "pg_scrub(1.0 deep=1)", [
            ("scrub.list", 0.0, 0.01, {"cpu": 0.004}),
            ("scrub.read", 0.01, 0.11, {"cpu": 0.05}),
            ("scrub.peer_wait", 0.2, 0.5, {"cpu": 0.001}),
            ("scrub.peer_wait", 0.5, 0.9, {"cpu": 0.002})]),
        doc("scrub_scan", "scrub:0:1.0:1", "pg_scan(osd.0 1.0 deep=1)", [
            ("queue", 0.21, 0.22),
            ("execute", 0.22, 0.48, {"cpu": 0.1}),
            ("scrub.list", 0.22, 0.24, {"cpu": 0.01}),
            ("scrub.read", 0.24, 0.44, {"cpu": 0.07})]),
        doc("scrub", "scrub:0:1.1:1", "pg_scrub(1.1 deep=1)", [
            ("scrub.read", 1.0, 1.3, {"cpu": 0.2})]),
        doc("client", "c:9", "osd_op(c:9 o ['read'])", [
            ("execute", 0.0, 5.0, {"cpu": 4.0})])]


def test_op_span_time_means_per_root_op_over_its_docs():
    r = R(scrub_docs())
    p = {"root_kind": "scrub", "root_match": "pg_scrub(",
         "kinds": ["scrub", "scrub_scan"],
         "spans": ["scrub.list", "scrub.read"]}
    # (0.01 + 0.1 + 0.02 + 0.2 + 0.3) s over two scrubs
    assert op_span_time.read(r, p) == pytest.approx(315.0)
    assert op_span_time.read(r, dict(p, kinds=["scrub"])) == \
        pytest.approx(205.0)
    assert op_span_time.read(
        r, dict(p, kinds=["scrub"], spans=["scrub.peer_wait"])) == \
        pytest.approx(350.0)
    # self time: execute minus the scrub.* spans inside it
    assert op_span_time.read(r, dict(p, spans=["execute"])) == \
        pytest.approx(1000 * (0.26 - 0.22) / 2)
    assert op_span_time.read(r, dict(p, match="pg_scan(",
                                     spans=["scrub.read"])) == \
        pytest.approx(100.0)


def test_op_span_time_reads_nothing_without_roots_or_spans():
    r = R(scrub_docs())
    p = {"root_kind": "scrub", "root_match": "pg_scrub(",
         "kinds": ["scrub", "scrub_scan"], "spans": ["scrub.stack"]}
    assert op_span_time.read(r, p) is None and "none of" in r.said[-1]
    assert op_span_time.read(
        r, dict(p, root_match="no such", spans=["scrub.read"])) is None
    # the parent's docs: client writes with none of the new spans
    old = R([doc("client", "c:1", "osd_op(c:1 o ['writefull'])",
                 [("queue", 0.0, 1.0), ("execute", 1.0, 2.0)])])
    for metric in ("msgr.recv_ms.write", "msgr.send_ms.write",
                   "host.cpu_ms_per_op.write", "client.sends_per_op.write",
                   "scrub.store_read_ms", "scrub.cpu_ms_per_pg"):
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 metric + ".json")
        assert reader(spec["reader"]).read(old, spec["params"]) is None, \
            metric


def test_span_cpu_time_counts_outermost_named_spans_once():
    r = R(scrub_docs())
    p = {"root_kind": "scrub", "root_match": "pg_scrub(",
         "kinds": ["scrub", "scrub_scan"],
         "spans": ["execute", "scrub.list", "scrub.read",
                   "scrub.peer_wait"]}
    # primary 1: 0.004 + 0.05 + 0.001 + 0.002; its scan: execute alone
    # (0.1; list and read lie inside); primary 2: 0.2
    assert span_cpu_time.read(r, p) == pytest.approx(
        1000 * (0.057 + 0.1 + 0.2) / 2)
    assert span_cpu_time.outermost_cpu(r.op_docs[1]["spans"],
                                       {"scrub.list", "scrub.read"}) == \
        (pytest.approx(0.08), 2)
    assert span_cpu_time.read(r, dict(p, spans=["queue"])) is None
    assert "carries cpu" in r.said[-1]


def test_sends_per_op_takes_the_highest_attempt():
    docs = [doc("client", "c:1", "osd_op(c:1 a ['writefull'])", [],
                attempt=1),
            doc("client", "c:1", "osd_op(c:1 a ['writefull'])", [],
                attempt=3),
            doc("client", "c:2", "osd_op(c:2 b ['writefull'])", [],
                attempt=1),
            doc("client", "c:3", "osd_op(c:3 b ['read'])", [], attempt=7),
            doc("subop", "c:1", "sub_op(...)", [])]
    r = R(docs)
    assert sends_per_op.read(r, {"op": "'writefull'"}) == 2.0
    assert sends_per_op.read(r, {"op": "'read'"}) == 7.0
    assert sends_per_op.read(r, {"op": "'append'"}) is None
    for d in docs:
        d.pop("attempt", None)
    assert sends_per_op.read(R(docs), {"op": "'writefull'"}) is None


GAP = {"line": trace.MODULES_LINE, "pattern": "jit_run", "gaps": 10,
       "min_inside": 0.8}


def gap_case(offset=100.0):
    """Three dispatches; the trace clock starts `offset` s after the
    spans' clock's zero.  Programs of 1 ms at trace seconds 1, 2 and
    4.5; each dispatch interval is issue..fetched around its program."""
    docs, mods, ops = [], [], []
    for n, at in enumerate((1.0, 2.0, 4.5)):
        t = at + offset
        docs.append(doc(
            "client", f"c:{n}", f"osd_op(c:{n} o{n} ['writefull'])", [
                ("msgr.recv", t - 0.9, t - 0.85),
                ("queue", t - 0.8, t - 0.1),
                ("execute", t - 0.1, t + 0.3),
                ("ec.device_compute", t - 0.002 - 0.001 * n, t + 0.0005),
                ("ec.d2h", t + 0.0005, t + 0.004 + 0.001 * n)],
            mstart=t - 0.8))
        mods.append((f"jit_run_encode_crc({n})", at * 1e9, 1e6))
        ops.append((f"%ec_encode.{n}", at * 1e9, 4e5))
        ops.append((f"%crc_fold.{n}", at * 1e9 + 5e5, 5e5))
    mods.append(("jit__multi_slice(9)", 3.0e9, 1e5))   # not the pattern
    return docs, {0: {trace.MODULES_LINE: mods, trace.OPS_LINE: ops}}


def test_offset_is_recovered_from_dispatch_spans():
    docs, lines = gap_case(offset=100.0)
    r = R(docs, lines)
    share = idle_gap_spans.read(r, GAP)
    head = r.said[0]
    assert "3 of 3 programs (100.0%)" in head
    found = float(head.split("clock offset ")[1].split(" s")[0])
    assert found == pytest.approx(100.0, abs=2e-3)
    # every pair allows offsets from 2 ms under to 3 ms over the true
    # one: the reader takes 0.1 ms above the lower end, 99.9981.
    # gap 1: 2.499 s before the third dispatch (trace 2.001..4.5): its
    # op arrived 0.9 s before its program, so 1.601 s precede it and
    # the rest is under msgr.recv, queue, execute but for the 0.05 s
    # between msgr.recv and queue; gap 2: 0.999 s before the second,
    # which arrived 0.9 s earlier: 0.101 s precede it
    assert found == pytest.approx(99.9981, abs=1e-6)
    assert "idle gap 1: 2.499" in r.said[1] and "'c:2'" in r.said[1]
    assert "before it existed 1.601" in r.said[1]
    assert "before it existed 0.101" in r.said[2]
    assert "queue 0.700" in r.said[1]
    assert "idle gap 2: 0.999" in r.said[2] and "'c:1'" in r.said[2]
    named = (2.499 - 0.05) + (0.999 - 0.05)
    assert share == pytest.approx(100 * named / (2.499 + 0.999), rel=1e-3)


def test_no_offset_no_number():
    docs, lines = gap_case()
    # each dispatch on a clock of its own: no one offset fits two
    for n, d in enumerate(docs):
        for s in d["spans"]:
            s["t0"] += 7.0 * n
            s["t1"] += 7.0 * n
    r = R(docs, lines)
    assert idle_gap_spans.read(r, GAP) is None
    assert "1 of 3 programs" in r.said[0]
    assert "nothing to read" in r.said[-1]
    # no trace, no programs, no dispatch spans
    assert idle_gap_spans.read(R(docs), GAP) is None
    assert idle_gap_spans.read(R(docs, {}), GAP) is None
    docs, lines = gap_case()
    assert idle_gap_spans.read(
        R(docs, lines), dict(GAP, pattern="jit_nothing")) is None
    bare = [dict(d, spans=[s for s in d["spans"]
                           if not s["name"].startswith("ec.")])
            for d in docs]
    r = R(bare, lines)
    assert idle_gap_spans.read(r, GAP) is None
    assert "0 dispatch spans" in r.said[0]


def test_gap_ended_by_an_untracked_dispatch_is_not_named():
    """The third program belongs to no doc of the window (its op had
    not finished, say): the gap it ends counts as idle, not as named."""
    docs, lines = gap_case()
    r = R(docs[:2], lines)
    share = idle_gap_spans.read(r, dict(GAP, min_inside=0.6))
    assert "2 of 3 programs" in r.said[0]
    assert "idle gap 1: 2.499" in r.said[1]
    assert "ended by no tracked op's dispatch" in r.said[1]
    assert share == pytest.approx(100 * (0.999 - 0.05) / (2.499 + 0.999),
                                  rel=1e-3)
    assert idle_gap_spans.read(R(docs[:2], lines), GAP) is None


# -- recorded fixtures (traced chip runs of this tree, TPU v5 lite) -----------


def load(name):
    with open(os.path.join(FIX, name)) as f:
        return json.load(f)


def metric(name, r):
    spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
    return reader(spec["reader"]).read(r, spec["params"])


def plain_ms(docs, ids, names, desc=""):
    """Summed duration of the named spans over the docs of the given
    trace ids, per trace id: equal to the self time wherever the named
    spans hold no other span."""
    total = sum(s["t1"] - s["t0"] for d in docs
                if d["trace_id"] in ids and desc in d["description"]
                for s in d["spans"] if s["name"] in names)
    return 1000.0 * total / len(ids)


def test_recorded_scrub_window():
    """Two PG scrubs of `k8m3-4m-deep-scrub` with their ten scans each."""
    docs = load("scrub_ops.json")
    scrubs = [d for d in docs if d["kind"] == "scrub"]
    scans = [d for d in docs if d["kind"] == "scrub_scan"]
    assert len(scrubs) == 2 and len(scans) == 20
    ids = {d["trace_id"] for d in scrubs}
    assert {d["trace_id"] for d in scans} == ids
    r = R(docs)
    # 11 scans a scrub, each over one 512 KiB shard file an object of
    # the PG: answered by the HBM cache or read
    files = {}
    for d in docs:
        (fold,) = [s for s in d["spans"] if s["name"] == "scrub.cache_fold"]
        (rd,) = [s for s in d["spans"] if s["name"] == "scrub.read"]
        files.setdefault(d["trace_id"], set()).add(
            fold["args"]["shards"] + rd["args"]["shards"])
        assert rd["args"]["bytes"] == rd["args"]["shards"] * 524288
        assert sum(s["args"]["bytes"] for s in d["spans"]
                   if s["name"] == "scrub.stack") == rd["args"]["bytes"]
    assert all(len(counts) == 1 for counts in files.values())
    assert metric("scrub.store_read_ms", r) == pytest.approx(
        plain_ms(docs, ids, {"scrub.list", "scrub.read"}))
    assert metric("scrub.store_read_ms", r) == pytest.approx(270.943, abs=1e-3)
    assert metric("scrub.cache_fold_ms", r) == pytest.approx(
        plain_ms(docs, ids, {"scrub.cache_fold"}))
    assert metric("scrub.cache_fold_ms", r) == pytest.approx(141.555, abs=1e-3)
    assert metric("scrub.peer_wait_ms", r) == pytest.approx(
        plain_ms(scrubs, ids, {"scrub.peer_wait"}))
    assert metric("scrub.peer_wait_ms", r) == pytest.approx(433.107, abs=1e-3)
    # ec.d2h and part of ec.device_compute lie inside scrub.collect:
    # self time counts them once, the plain sum twice
    plain = plain_ms(docs, ids, {"scrub.stack", "scrub.collect",
                                 "ec.coalesce", "ec.stage_h2d",
                                 "ec.device_compute", "ec.d2h"})
    assert metric("scrub.device_path_ms", r) == pytest.approx(39.854, abs=1e-3)
    assert 39.854 < plain == pytest.approx(71.235, abs=1e-3)
    # cpu: execute on each scan, the scrub.* spans on the primaries
    want = sum(s["cpu"] for d in scans for s in d["spans"]
               if s["name"] == "execute")
    want += sum(s["cpu"] for d in scrubs for s in d["spans"]
                if s["name"].startswith("scrub."))
    assert metric("scrub.cpu_ms_per_pg", r) == pytest.approx(1000 * want / 2)
    assert metric("scrub.cpu_ms_per_pg", r) == pytest.approx(340.0)
    # a peer's scan lies inside the primary's wait for it
    for d in scans:
        primary = next(p for p in scrubs if p["trace_id"] == d["trace_id"])
        osd = int(d["daemon"].split(".")[1])
        (wait,) = [s for s in primary["spans"] if s["name"] ==
                   "scrub.peer_wait" and s["args"]["osd"] == osd]
        (ex,) = [s for s in d["spans"] if s["name"] == "execute"]
        assert wait["t0"] <= ex["t0"] and ex["t1"] <= wait["t1"]


def test_recorded_mixed_window():
    """Four reads with their sub-reads, three writes sent once and two
    sent twice, of `k2m1-64k-mixed`."""
    docs = load("mixed_ops.json")
    reads = {d["trace_id"] for d in docs if d["kind"] == "client"
             and "'read'" in d["description"]}
    writes = {d["trace_id"] for d in docs if d["kind"] == "client"
              and "'writefull'" in d["description"]}
    assert len(reads) == 4 and len(writes) == 5
    r = R(docs)
    both = {"msgr.recv", "msgr.dispatch"}
    assert metric("msgr.recv_ms.write", r) == pytest.approx(
        plain_ms(docs, writes, both)) == pytest.approx(6.4427, abs=1e-4)
    assert metric("msgr.send_ms.write", r) == pytest.approx(
        plain_ms(docs, writes, {"msgr.send"})) == \
        pytest.approx(3.4007, abs=1e-4)
    assert metric("msgr.recv_ms.read", r) == pytest.approx(
        plain_ms(docs, reads, both)) == pytest.approx(1.3411, abs=1e-4)
    # k=2: each of the four reads asked two shard OSDs
    subs = [d for d in docs if d["description"].startswith("sub_read(")]
    assert len(subs) == 8 and {d["trace_id"] for d in subs} == reads
    assert metric("osd.subop_read_ms.read", r) == pytest.approx(
        plain_ms(docs, reads, {"queue", "execute"}, "sub_read(")) == \
        pytest.approx(98.931, abs=1e-3)
    # two of five writes went out twice: (1 + 1 + 1 + 2 + 2) / 5
    assert sorted(d["attempt"] for d in docs if d["kind"] == "client"
                  and d["trace_id"] in writes) == [1, 1, 1, 1, 1, 2, 2]
    assert metric("client.sends_per_op.write", r) == 1.4
    named = ("msgr.recv", "msgr.dispatch", "execute")
    for ids, name, want in ((writes, "host.cpu_ms_per_op.write", 36.0),
                            (reads, "host.cpu_ms_per_op.read", 10.0)):
        cpu = sum(s.get("cpu", 0.0) for d in docs if d["trace_id"] in ids
                  for s in d["spans"] if s["name"] in named)
        assert metric(name, r) == pytest.approx(1000 * cpu / len(ids)) == \
            pytest.approx(want)
    # the metrics that were there read these docs as before
    assert metric("osd.execute_ms.read", r) == pytest.approx(78.674, abs=1e-3)
    # nothing new lies inside a span an older metric reads
    for d in docs:
        old = [s for s in d["spans"] if s["name"] in (
            "queue", "replica_wait", "journal", "wal", "store_apply",
            "ec.coalesce", "ec.stage_h2d", "ec.device_compute", "ec.d2h")]
        if d["kind"] == "client" and "'read'" in d["description"]:
            old += [s for s in d["spans"] if s["name"] == "execute"]
        for s in d["spans"]:
            if s["name"].startswith("msgr."):
                assert not any(o["t0"] <= s["t0"] and s["t1"] <= o["t1"]
                               and o["t1"] > o["t0"] for o in old), (d, s)


def test_recorded_gaps_of_a_write_window():
    """Five dispatches of `k8m3-4m-write`: their programs and device ops
    from the `.xplane.pb`, the docs of their ops, and the offset the
    recording script noted from a `TraceAnnotation` it stamped with
    `time.monotonic()` on the profiler's host plane."""
    fix = load("gaps_write.docs.json")
    red = trace.reduce(os.path.join(FIX, "gaps_write.xplane.pb"), 1.0, "tpu")
    mods = red["lines"][0][trace.MODULES_LINE]
    assert len(mods) == 5
    assert all(n.startswith("jit_run_encode_crc(") for n, _s, _d in mods)
    ops = {trace.short_name(n).split(".")[0]
           for n, _s, _d in red["lines"][0][trace.OPS_LINE]}
    assert {"%ec_encode", "%crc_fold"} <= ops
    r = R(fix["docs"], red["lines"])
    share = metric("host.idle_gap_named_share.write", r)
    assert "5 of 5 programs (100.0%)" in r.said[0]
    found = float(r.said[0].split("clock offset ")[1].split(" s")[0])
    assert abs(found - fix["offset_s"]) < 1e-3
    # the four gaps between five dispatches, longest first, each ended
    # by another op; the first two arrived inside their gap
    heads = [line.split(";")[0] for line in r.said[1:5]]
    assert [h.split(" s before")[0] for h in heads] == [
        "idle gap 1: 0.511197", "idle gap 2: 0.372514",
        "idle gap 3: 0.158771", "idle gap 4: 0.095992"]
    enders = [line.split("the dispatch of '")[1].split("'")[0]
              for line in r.said[1:5]]
    assert len(set(enders)) == 4
    assert "msgr.recv 0.085" in r.said[1] and "before it existed 0.41" \
        in r.said[1]
    assert "queue 0.150" in r.said[3] and "before it existed 0.000" \
        in r.said[3]
    assert share == pytest.approx(100.0, abs=0.01)
    # shifted so that no offset fits: every other dispatch a second off
    moved = copy.deepcopy(fix["docs"])
    order = sorted(idle_gap_spans.dispatch_intervals(moved))
    for n, key in enumerate(order):
        for d in idle_gap_spans.dispatch_intervals(moved)[key]:
            for s in d["spans"]:
                if s["name"].startswith("ec."):
                    s["t0"] += 0.4 * n
                    s["t1"] += 0.4 * n
    r = R(moved, red["lines"])
    assert metric("host.idle_gap_named_share.write", r) is None
    assert "1 of 5 programs" in r.said[0] or "2 of 5 programs" in r.said[0]
    assert "nothing to read" in r.said[-1]
