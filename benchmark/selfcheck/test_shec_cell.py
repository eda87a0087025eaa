"""What PR 28 adds for `shec-k8m4c3-4m-degraded-read`: the contract of
its configuration, traffic and metric files, the new reader and the
generator's decode-set check on recorded docs, the planned warm-up's
operands, and the cell at a tiny size on the CPU platform."""

import json
import os

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny

CELL = "shec-k8m4c3-4m-degraded-read"
NEW_METRICS = ["osd.gather_wait_ms.read", "osd.subreads_per_op.read",
               "ec.plan_ms.read", "ec.device_path_ms.read",
               "ec.batch_stripes.read", "kernel.decode_roofline",
               "client.sends_per_op.read", "cache.hit_share.read",
               "host.idle_gap_named_share.read"]
SHARED_METRICS = ["osd.execute_ms.read", "osd.subop_read_ms.read",
                  "msgr.recv_ms.read", "host.cpu_ms_per_op.read"]


@pytest.fixture(scope="module")
def docs():
    with open(os.path.join(harness.HERE, "fixtures",
                           "shec_read_ops.json")) as f:
        return json.load(f)


class Readings:
    def __init__(self, docs):
        self.op_docs = docs
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert cfg["pool_profile"] == {
        "plugin": "tpu", "technique": "shec_multiple", "k": "8", "m": "4",
        "c": "3", "host_cutover": "1"}
    assert cfg["reference"] == "shec" and cfg["pool_kind"] == "ec"
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "store", "stripe_unit", "pg_num",
                "object_bytes", "inflight", "conf"):
        assert cfg[key] == twin[key], key
    assert set(cfg["assumed"]) == {"host_cutover", "pg_num", "conf",
                                   "gather"}
    assert "c = 3" in cfg["guarantees"][2]
    # the traffic is degraded-read-qd16's, letter for letter, but for
    # the planned warm-up and the generator that adds the decode-set
    # check to closed_loop's verdict
    mine = cell.traffic
    theirs = harness.load_json(harness.HERE, "traffic",
                               "degraded-read-qd16.json")
    assert mine["params"] == theirs["params"]
    assert mine["window_counters"] == theirs["window_counters"]
    assert mine["warm"] == ["encode", "decode_planned"]
    gen = harness.load_module(harness.HERE, "generators", mine["generator"])
    base = harness.load_module(harness.HERE, "generators",
                               theirs["generator"])
    assert gen.prepare is base.prepare and gen.run is base.run


def test_metrics_of_the_cell():
    cell = harness.Cell(CELL)
    assert [m["name"] for m, _s in cell.end_to_end()] == ["read_mibps",
                                                          "setup_s"]
    listed = [m["name"] for m, _s in cell.per_layer()]
    assert sorted(listed) == sorted(NEW_METRICS + SHARED_METRICS)
    for m, spec in cell.per_layer():
        assert m["moves"] == "read_mibps"
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


def test_subreads_reader_on_recorded_docs(docs):
    reader = harness.load_module(harness.HERE, "readers", "subreads_per_op")
    rd = Readings(docs)
    # three reads of the recording, nine live peers asked each
    assert reader.read(rd, {"op": "'read'", "match": "sub_read("}) == 9.0
    assert "3 of 3 reads sent 27" in rd.lines[0]
    # a program whose sub-reads make no op: nothing to read
    assert reader.read(Readings([d for d in docs if d["kind"] == "client"]),
                       {"op": "'read'", "match": "sub_read("}) is None
    assert reader.read(Readings([]), {"op": "'read'",
                                      "match": "sub_read("}) is None


def test_span_metrics_on_recorded_docs(docs):
    for name in ("osd.gather_wait_ms.read", "ec.plan_ms.read"):
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        reader = harness.load_module(harness.HERE, "readers",
                                     spec["reader"])
        assert reader.read(Readings(docs), spec["params"]) > 0, name


def test_decode_sets_of_recorded_docs(docs):
    gen = harness.load_module(harness.HERE, "generators",
                              "closed_loop_planned")
    reference = harness.load_module(harness.HERE, "references", "shec")
    sets = gen.decode_sets(docs, 0.0)
    assert len(sets) == 3 and all(len(s) >= 8 for s in sets)
    cfg = harness.Cell(CELL).config
    assert all(reference.decodable(s, cfg) for s in sets)
    # every read of the recording first held eight chunks that do NOT
    # decode (`replans` 1): the set it was served from is another
    spans = [s for d in docs if d["kind"] == "client"
             for s in d["spans"]]
    refused = [s["args"]["present"] for s in spans
               if s["name"] == "ec.plan"]
    assert refused and not any(reference.decodable(s, cfg)
                               for s in refused)
    assert gen.decode_sets(docs, float("inf")) == []


def test_planned_warm_up_operands():
    from ceph_tpu.erasure.registry import registry
    warmer = harness.load_module(harness.HERE, "warmers", "decode_planned")
    codec = registry.factory("tpu", {
        "technique": "shec_multiple", "k": "8", "m": "4", "c": "3",
        "host_cutover": "1"})
    operands = warmer.plans(codec, 8, 4, 3)
    assert {r: rows.shape for r, rows in operands.items()} == {
        1: (1, 8), 2: (2, 8), 3: (3, 8), 4: (4, 8)}


def run(seed, traced, **kw):
    lines = []
    result = harness.run_cell(CELL, seed, 2.0, traced, "cpu",
                              overrides=tiny.overrides(CELL),
                              out=lines.append, **kw)
    return result, lines


def test_cell_ends_correct_with_its_decode_sets_checked():
    result, lines = run(2**31 + 28, False)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"read_mibps", "setup_s"}
    for needle in ("check decode_sets_refused = 0 limit <= 0 ok",
                   "check decode_sets_checked = ",
                   "check stored_crc_mismatches = 0",
                   "warm decode_planned", "failed_osds"):
        assert needle in text, needle


def test_a_refusing_reference_is_not_correct(monkeypatch):
    reference = harness.load_module(harness.HERE, "references", "shec")
    monkeypatch.setattr(reference, "decodable", lambda chunks, cfg: False)
    result, lines = run(29, False)
    assert result["correct"] is False
    assert any("decode_sets_refused" in l and "FAILED" in l for l in lines)
