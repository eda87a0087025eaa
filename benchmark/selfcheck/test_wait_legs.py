"""The `wait_legs` reader and the eight metrics of ISSUE 38 (three
shares over it, five data files over `op_span_time`), read from a
fixture: the op docs of two 64 KiB writes, two reads the HBM cache did
not serve and one deep scrub on a k=2 m=1 `plugin=tpu` pool over three
filestore OSDs in one process, recorded from this tree on the CPU
(`fixtures/wait_legs_ops.json`: 4 client docs, 8 sub-op docs, 1 scrub,
2 scans, 10 `reply` docs).  The times in it are a CPU rig's and say
nothing of the chip host; what is checked is the arithmetic."""

import copy
import json
import os

import pytest

from benchmark import harness

wait_legs = harness.load_module(harness.HERE, "readers", "wait_legs")
op_span_time = harness.load_module(harness.HERE, "readers", "op_span_time")
span_self_time = harness.load_module(harness.HERE, "readers",
                                     "span_self_time")

W, M, S, N, R, C, L, H = (
    "k8m3-4m-write", "k2m1-64k-mixed", "k8m3-4m-deep-scrub",
    "shec-k8m4c3-4m-degraded-read", "k8m3-4m-degraded-read",
    "cauchy-k6m3-4m-write", "lrc-k4m2l3-4m-write", "k8m3-4m-rand-read")
CELLS = {
    "msgr.flight_ms.write": (W, M, C, L),
    "msgr.flight_ms.read": (M, N, R, H),
    "msgr.flight_ms.scrub": (S,),
    "osd.reply_path_ms.write": (W, M, C, L),
    "osd.reply_path_ms.read": (M, N, R, H),
    "osd.replica_wait_named_share.write": (W, M, C, L),
    "osd.gather_wait_named_share.read": (N, R, H),
    "scrub.peer_wait_named_share": (S,),
}
SHARES = {"osd.replica_wait_named_share.write": "replica_wait",
          "osd.gather_wait_named_share.read": "gather_wait",
          "scrub.peer_wait_named_share": "scrub.peer_wait"}
WAY_IN = ("msgr.handoff", "msgr.wire")


def spec(name):
    return harness.load_json(harness.HERE, "layer_metrics", name + ".json")


class Rd:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=()):
        self.op_docs = list(docs)
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def docs():
    with open(os.path.join(harness.HERE, "fixtures",
                           "wait_legs_ops.json")) as f:
        return json.load(f)


def spans(doc, name):
    return [s for s in doc["spans"] if s["name"] == name]


def share(name, ds):
    r = Rd(ds)
    return wait_legs.read(r, spec(name)["params"]), r


def test_the_recording_is_what_the_docstring_says():
    ds = docs()
    kinds = {}
    for d in ds:
        kinds[d["kind"]] = kinds.get(d["kind"], 0) + 1
    assert kinds == {"client": 4, "subop": 8, "scrub": 1, "scrub_scan": 2,
                     "reply": 10}
    for d in ds:
        if d["kind"] == "scrub":
            assert not spans(d, "msgr.wire")     # it came off no wire
            continue
        (hand,), (wire,) = spans(d, "msgr.handoff"), spans(d, "msgr.wire")
        (recv,) = spans(d, "msgr.recv")
        assert hand["t0"] <= hand["t1"] == wire["t0"] <= wire["t1"] \
            == recv["t0"] <= d["mstart"]
        assert wire["args"]["queued"] >= 0


@pytest.mark.parametrize("name", ["osd.replica_wait_named_share.write",
                                  "osd.gather_wait_named_share.read"])
def test_a_chain_that_covers_its_wait_reads_100(name):
    """A write's wait opens after its sub-ops were handed over and
    closes inside the last answer's handler, a gather's the same: the
    chain has no hole and reaches over both ends."""
    got, r = share(name, docs())
    assert got == pytest.approx(100.0, abs=1e-6)
    said = r.said[-1]
    assert said.startswith(f"wait legs of {SHARES[name]}: 2 waits of 2 "
                           "root ops")
    assert " 0.000 ms in no leg" in said
    # the table adds up to what was waited
    waited = float(said.split(" ms an op waited")[0].rsplit(" ", 1)[1])
    table = said.split("ms an op by leg: ")[1].split(";")[0]
    legs = {k: float(v) for k, v in
            (item.rsplit(" ", 1) for item in table.split(", "))}
    assert tuple(legs) == wait_legs.LEGS
    assert sum(legs.values()) == pytest.approx(waited, abs=0.01)
    assert legs["req.op"] > 0 and legs["reply.wire"] > 0
    if name.endswith(".read"):
        assert legs["reply.queue"] == 0.0    # completed inline
    else:
        assert legs["reply.queue"] > 0.0     # waited on the op shard
    assert "frames queued ahead: req.wire 0.00, reply.wire 0.00" in said


def test_a_scrubs_waits_are_each_laid_and_the_waking_is_in_no_leg():
    ds = docs()
    got, r = share("scrub.peer_wait_named_share", ds)
    (scrub,) = [d for d in ds if d["kind"] == "scrub"]
    waits = spans(scrub, "scrub.peer_wait")
    assert len(waits) == 2
    assert r.said[-1].startswith(
        "wait legs of scrub.peer_wait: 2 waits of 1 root ops")
    # by hand: from the request's hand-off to the end of the answer's
    # handler; in front of it the scrub's thread builds the request,
    # behind it it wakes
    covered = waited = 0.0
    for w in waits:
        osd = f"osd.{w['args']['osd']}"
        (scan,) = [d for d in ds if d["kind"] == "scrub_scan"
                   and d["daemon"] == osd]
        (reply,) = [d for d in ds if d["kind"] == "reply"
                    and d["description"].endswith(f"<- {osd})")
                    and d["trace_id"] == scrub["trace_id"]]
        t0 = spans(scan, "msgr.handoff")[0]["t0"]
        t1 = spans(reply, "execute")[0]["t1"]
        assert w["t0"] < t0 < t1 < w["t1"]
        covered += t1 - t0
        waited += w["t1"] - w["t0"]
    assert got == pytest.approx(100.0 * covered / waited)
    assert 50.0 < got < 100.0


def test_a_reply_doc_taken_out_reads_the_share_that_is_left():
    ds = docs()
    name = "osd.replica_wait_named_share.write"
    (write,) = [d for d in ds if d["kind"] == "client"
                and " a ['writefull']" in d["description"]]
    (wait,) = spans(write, "replica_wait")
    mine = sorted((d for d in ds if d["kind"] == "reply"
                   and d["trace_id"] == write["trace_id"]),
                  key=lambda d: spans(d, "execute")[0]["t0"])
    assert len(mine) == 2
    only = [d for d in ds if d["trace_id"] == write["trace_id"]]
    assert share(name, only)[0] == pytest.approx(100.0, abs=1e-6)
    # without the last answer the one before it is laid: its handler
    # ended before the wait did
    less, _ = share(name, [d for d in only if d is not mine[-1]])
    end = spans(mine[0], "execute")[0]["t1"]
    assert end < wait["t1"]
    assert less == pytest.approx(
        100.0 * (end - wait["t0"]) / (wait["t1"] - wait["t0"]))
    # without either the wait is waited and not covered; the other
    # write's answers keep the metric readable
    none, r = share(name, [d for d in ds if d not in mine])
    (other,) = [d for d in ds if d["kind"] == "client"
                and " b ['writefull']" in d["description"]]
    (wait_b,) = spans(other, "replica_wait")
    assert none == pytest.approx(
        100.0 * (wait_b["t1"] - wait_b["t0"])
        / (wait_b["t1"] - wait_b["t0"] + wait["t1"] - wait["t0"]))
    # and without the request doc the chain begins at the reply
    no_req, _ = share(name, [d for d in only if d["kind"] != "subop"])
    first = min(s["t0"] for s in mine[-1]["spans"])
    assert no_req == pytest.approx(
        100.0 * (wait["t1"] - first) / (wait["t1"] - wait["t0"]))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent: no `reply` docs, no `msgr.handoff` or `msgr.wire`
    on any doc.  Nothing, not 0."""
    old = [d for d in docs() if d["kind"] != "reply"]
    for d in old:
        d["spans"] = [s for s in d["spans"] if s["name"] not in WAY_IN]
    sp = spec(name)
    reader = harness.load_module(harness.HERE, "readers", sp["reader"])
    assert reader.read(Rd(old), sp["params"]) is None
    # and the docs of another traffic mix (no such root op) as well
    other = [d for d in docs() if d["kind"] in ("reply", "scrub_scan")]
    assert reader.read(Rd(other), sp["params"]) is None


def by_hand(ds, roots, kinds, names):
    total = 0.0
    for d in ds:
        if d["kind"] in kinds and d["trace_id"] in roots:
            total += sum(s["t1"] - s["t0"] for s in d["spans"]
                         if s["name"] in names)
    return 1000.0 * total / len(roots)


def test_flight_and_reply_path_add_up_by_hand():
    ds = docs()
    roots = {what: {d["trace_id"] for d in ds if d["kind"] == kind
                    and match in d["description"]}
             for what, kind, match in (("write", "client", "'writefull'"),
                                       ("read", "client", "'read'"),
                                       ("scrub", "scrub", "pg_scrub("))}
    assert [len(roots[k]) for k in ("write", "read", "scrub")] == [2, 2, 1]
    path = ("msgr.recv", "msgr.dispatch", "queue", "execute")
    # none of these spans nests in another on these docs, so self time
    # is duration
    for name, ids, kinds, names in (
            ("msgr.flight_ms.write", roots["write"],
             ("client", "subop", "reply"), WAY_IN),
            ("msgr.flight_ms.read", roots["read"],
             ("client", "subop", "reply"), WAY_IN),
            ("msgr.flight_ms.scrub", roots["scrub"],
             ("scrub_scan", "reply"), WAY_IN),
            ("osd.reply_path_ms.write", roots["write"], ("reply",), path),
            ("osd.reply_path_ms.read", roots["read"], ("reply",), path)):
        sp = spec(name)
        assert sp["reader"] == "op_span_time"
        got = op_span_time.read(Rd(ds), sp["params"])
        assert got == pytest.approx(by_hand(ds, ids, kinds, names)), name
        assert got > 0
    # a write's flight: its own frame, two sub-ops, two answers
    flown = [d for d in ds if d["trace_id"] in roots["write"]
             and spans(d, "msgr.wire")]
    assert len(flown) == 2 * 5


def test_the_old_metrics_read_what_they_read_without_the_additions():
    """No new span nests in an old one and no reader picks the new
    kind up: the metrics that were there give the same number on the
    recording and on the recording with the additions taken out."""
    ds = docs()
    old = [copy.deepcopy(d) for d in ds if d["kind"] != "reply"]
    for d in old:
        d["spans"] = [s for s in d["spans"] if s["name"] not in WAY_IN]
    for name in ("osd.replica_wait_ms.write", "osd.gather_wait_ms.read",
                 "scrub.peer_wait_ms", "msgr.recv_ms.write",
                 "msgr.recv_ms.read", "msgr.send_ms.write",
                 "osd.queue_ms.write", "osd.execute_ms.read",
                 "osd.subop_read_ms.read", "host.cpu_ms_per_op.write",
                 "host.cpu_ms_per_op.read", "scrub.cpu_ms_per_pg",
                 "osd.subreads_per_op.read", "msgr.bytes_per_read.write"):
        sp = spec(name)
        reader = harness.load_module(harness.HERE, "readers", sp["reader"])
        new, was = (reader.read(Rd(x), sp["params"]) for x in (ds, old))
        assert new is not None and new == was, name


def test_they_are_declared_for_their_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    for name, want in CELLS.items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        sp = spec(name)
        for cell in want:
            assert cell in cells and cell in entry["workloads"], (name, cell)
        assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                      "moves")} == \
            {k: sp[k] for k in ("layer", "unit", "better", "source",
                                "moves")}
        assert entry["source"] == "program_span"
        assert entry["moves"] == ("scrub_mibps" if S in want else
                                  "read_mibps" if "read" in name
                                  else "write_mibps")
        assert sp["limits"]
        if name in SHARES:
            assert (entry["unit"], entry["better"]) == ("%", "higher")
            assert sp["reader"] == "wait_legs"
            assert sp["params"]["wait"] == SHARES[name]
        else:
            assert (entry["unit"], entry["better"]) == ("ms", "lower")
