"""What PR 32 adds: the cauchy pool's configuration and its cell
`cauchy-k6m3-4m-write` at a tiny size on the CPU platform, the
`ec.dispatch_fill.write` metric file (over the `span_arg_ratio` reader)
on recorded and on hand-made docs, and `k8m3-4m-degraded-read` declared
on the configuration and the traffic file the benchmark already had.

The recording (`fixtures/dispatch_fill_ops.json`): the op docs of two
device-served 64 KiB writes (three stripes of 6 x 4096 in a 4-bucket)
on a `cauchy_good` k=6 m=3 packetsize=32 `plugin=tpu` pool over ten
OSDs, from this tree on the CPU (counts, no times are read)."""

import json
import os

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny

CELL = "cauchy-k6m3-4m-write"
READ_CELL = "k8m3-4m-degraded-read"
FILL = "ec.dispatch_fill.write"
READ_METRICS = [
    "osd.execute_ms.read", "msgr.recv_ms.read", "osd.subop_read_ms.read",
    "host.cpu_ms_per_op.read", "osd.gather_wait_ms.read",
    "osd.subreads_per_op.read", "ec.plan_ms.read", "ec.device_path_ms.read",
    "ec.batch_stripes.read", "kernel.decode_roofline",
    "client.sends_per_op.read", "cache.hit_share.read",
    "host.idle_gap_named_share.read"]

span_arg_ratio = harness.load_module(harness.HERE, "readers",
                                     "span_arg_ratio")


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=()):
        self.op_docs = list(docs)
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def fill_spec():
    return harness.load_json(harness.HERE, "layer_metrics", FILL + ".json")


def recorded():
    with open(os.path.join(harness.HERE, "fixtures",
                           "dispatch_fill_ops.json")) as f:
        return json.load(f)


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert cfg["pool_profile"] == {
        "plugin": "tpu", "k": "6", "m": "3", "technique": "cauchy_good",
        "packetsize": "32", "host_cutover": "1"}
    assert cfg["reference"] == "cauchy_good" and cfg["pool_kind"] == "ec"
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "chips", "store", "stripe_unit", "pg_num",
                "object_bytes", "inflight", "conf", "store_flush_policy"):
        assert cfg[key] == twin[key], key
    assert set(cfg["reduced"]) == {"objects", "run_length", "hosts"}
    assert set(cfg["assumed"]) == {"stripe_unit", "host_cutover", "pg_num",
                                   "conf"}
    assert len(cfg["guarantees"]) == 3 and "9 shards" in cfg["guarantees"][0]
    # a chunk holds whole super-blocks; 4 MiB is no whole number of
    # stripes
    assert cfg["stripe_unit"] % (8 * int(cfg["pool_profile"]["packetsize"])) \
        == 0
    pool = harness.load_module(harness.HERE, "pools", "ec")
    assert pool.stripes_per_object(cfg) == 171
    assert pool.file_bytes(cfg) == 171 * 4096 == 700_416
    # W's own traffic file, and its one warm-up
    entry = cell.workload
    assert entry["traffic"] == "write-new-qd16" and entry["chips"] == 1
    assert cell.traffic["warm"] == ["encode"]
    bench = cell.bench
    (declared,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert declared["reduced"] == ["objects", "run_length", "hosts"]
    assert "cauchy_good k=6 m=3 packetsize=32" in declared["source"]
    assert len(declared["source"]) <= 200


def test_metrics_of_the_write_cell():
    cell = harness.Cell(CELL)
    assert [m["name"] for m, _s in cell.end_to_end()] == ["write_mibps",
                                                          "setup_s"]
    mine = {m["name"] for m, _s in cell.per_layer()}
    theirs = {m["name"] for m, _s in harness.Cell("k8m3-4m-write").per_layer()}
    assert mine == theirs and FILL in mine
    assert "kernel.encode_crc_roofline" in mine
    assert all(m["moves"] == "write_mibps" for m, _s in cell.per_layer())


def test_the_degraded_read_cell_is_declared_on_what_was_there():
    cell = harness.Cell(READ_CELL)
    assert cell.workload == dict(cell.workload, config="ec-k8m3-rados-4m",
                                 traffic="degraded-read-qd16", chips=1)
    assert cell.traffic["params"]["fail_osds"] == 2
    assert cell.traffic["warm"] == ["encode", "decode"]
    assert [m["name"] for m, _s in cell.end_to_end()] == ["read_mibps",
                                                          "setup_s"]
    listed = [m["name"] for m, _s in cell.per_layer()]
    assert sorted(listed) == sorted(READ_METRICS)
    twin = [m["name"] for m, _s in harness.Cell(
        "shec-k8m4c3-4m-degraded-read").per_layer()]
    assert sorted(listed) == sorted(twin)
    # every metric lists its cells; none is left to every later cell
    assert all("workloads" in m for m in cell.bench["per_layer"])


def test_dispatch_fill_is_declared_over_the_reader_that_was_there():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == FILL]
    spec = fill_spec()
    assert entry["workloads"] == ["k8m3-4m-write", "k2m1-64k-mixed", CELL]
    assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
        {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")}
    assert (entry["unit"], entry["better"]) == ("share", "higher")
    assert spec["reader"] == "span_arg_ratio"
    blocks = harness.load_json(harness.HERE, "layer_metrics",
                               "store.blocks_per_dev_write.write.json")
    same = ("root_kind", "root_match", "kinds")
    assert {k: spec["params"][k] for k in same} == \
        {k: blocks["params"][k] for k in same}
    assert (spec["params"]["span"], spec["params"]["numerator"],
            spec["params"]["denominator"]) == ("ec.device_compute",
                                               "stripes", "padded")


def test_dispatch_fill_from_recorded_writes():
    docs = recorded()
    assert sum(d["kind"] == "client" for d in docs) == 2
    args = [s["args"] for d in docs for s in d["spans"]
            if s["name"] == "ec.device_compute"]
    assert args == [{"stripes": 3, "padded": 4.0, "rep": "packets"}] * 2
    r = R(docs)
    assert span_arg_ratio.read(r, fill_spec()["params"]) == 0.75
    assert "6 stripes over 8.0 padded" in r.said[-1]


def test_dispatch_fill_of_ops_that_shared_a_dispatch_and_of_a_parent():
    def doc(tid, args):
        return {"kind": "client", "trace_id": tid, "daemon": "osd.0",
                "description": f"osd_op({tid} o ['writefull'])",
                "mstart": 0.0,
                "spans": [dict({"name": "ec.device_compute", "t0": 0.0,
                                "t1": 1.0}, **({"args": args} if args
                                               else {}))]}

    shared = [doc("c:1", {"stripes": 3, "padded": 8 * 3 / 7, "rep": "bytes"}),
              doc("c:2", {"stripes": 4, "padded": 8 * 4 / 7, "rep": "bytes"})]
    assert span_arg_ratio.read(R(shared), fill_spec()["params"]) == \
        pytest.approx(7 / 8)
    full = [doc("c:3", {"stripes": 128, "padded": 128.0, "rep": "bytes"})]
    assert span_arg_ratio.read(R(full), fill_spec()["params"]) == 1.0
    # the parent's spans carry no args: nothing to read, and no raise
    assert span_arg_ratio.read(R([doc("c:4", None)]),
                               fill_spec()["params"]) is None
    assert span_arg_ratio.read(R([]), fill_spec()["params"]) is None


def run(seed, traced, **kw):
    lines = []
    result = harness.run_cell(CELL, seed, 2.0, traced, "cpu",
                              overrides=tiny.overrides(CELL),
                              out=lines.append, **kw)
    return result, lines


def test_cell_ends_correct_against_the_cauchy_reference():
    result, lines = run(2**31 + 32, False)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"write_mibps", "setup_s"}
    for needle in ("check stored_mismatches = 0 limit <= 0 ok",
                   "check stored_crc_mismatches = 0 limit <= 0 ok",
                   "check dev_dispatches_in_window = ",
                   "check compiles_in_window = 0", "warm encode"):
        assert needle in text, needle
    assert "FAILED" not in text


def test_traced_run_reads_the_fill_and_the_device_share():
    result, lines = run(33, True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    metrics = result["metrics"]
    # 64 KiB at the tiny size: three stripes in a 4-bucket
    assert metrics[FILL]["value"] == 0.75, text
    # counters: a dispatch at the window's edge is in one and not the
    # other
    assert metrics["ec.batch_stripes.write"]["value"] == \
        pytest.approx(3.0, abs=0.5)
    assert metrics["codec.device_stripe_share.write"]["value"] == 100.0
    assert metrics["store.blocks_per_dev_write.write"]["value"] == 3.0


def test_a_refusing_reference_is_not_correct(monkeypatch):
    reference = harness.load_module(harness.HERE, "references",
                                    "cauchy_good")
    real = reference.encode

    def flipped(data, m, packetsize):
        out = real(data, m, packetsize)
        out[0, 0] ^= 1
        return out

    monkeypatch.setattr(reference, "encode", flipped)
    result, lines = run(34, False)
    assert result["correct"] is False
    assert any("stored_mismatches" in l and "FAILED" in l for l in lines)
    assert any("stored_crc_mismatches" in l and "FAILED" in l for l in lines)
