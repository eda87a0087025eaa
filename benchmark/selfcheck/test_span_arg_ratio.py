"""The `span_arg_ratio` reader (ISSUE 29) on hand-made docs, and the
metric that uses it, `store.blocks_per_dev_write.write`, on the docs of
a tiny write window of the program as it stands."""

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny

span_arg_ratio = harness.load_module(harness.HERE, "readers",
                                     "span_arg_ratio")
SPEC = harness.load_json(harness.HERE, "layer_metrics",
                         "store.blocks_per_dev_write.write.json")


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=()):
        self.op_docs = list(docs)
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def doc(kind, trace_id, desc, spans):
    return {"kind": kind, "trace_id": trace_id, "description": desc,
            "daemon": "osd.0", "mstart": 0.0,
            "spans": [dict({"name": n, "t0": 0.0, "t1": 1.0},
                           **({"args": a} if a else {}))
                      for n, a in spans]}


def docs():
    return [
        doc("client", "c:1", "osd_op(c:1 a ['writefull'])",
            [("queue", None), ("wal", {"blocks": 128, "dev_writes": 1})]),
        doc("subop", "c:1", "sub_op(c:1 a.s3)",
            [("wal", {"blocks": 128, "dev_writes": 3}),
             ("wal", {"blocks": 0, "dev_writes": 0}),
             ("store_apply", {"blocks": 999, "dev_writes": 1})]),
        # a resent op's second doc is the same op's
        doc("client", "c:1", "osd_op(c:1 a ['writefull'])",
            [("wal", {"blocks": 8, "dev_writes": 2})]),
        # not a write, and a sub-op of no selected op
        doc("client", "c:2", "osd_op(c:2 b ['read'])",
            [("wal", {"blocks": 500, "dev_writes": 1})]),
        doc("subop", "c:9", "sub_op(c:9 z.s0)",
            [("wal", {"blocks": 500, "dev_writes": 1})])]


def test_sums_both_args_over_the_ops_and_their_subops():
    r = R(docs())
    assert span_arg_ratio.read(r, SPEC["params"]) == \
        pytest.approx((128 + 128 + 0 + 8) / (1 + 3 + 0 + 2))
    assert "264 blocks over 6 dev_writes" in r.said[-1]
    alone = dict(SPEC["params"], kinds=["client"])
    assert span_arg_ratio.read(r, alone) == pytest.approx(136 / 3)


def test_reads_nothing_from_a_program_without_the_args():
    # the parent's docs: wal spans with no args, or with other args
    old = R([doc("client", "c:1", "osd_op(c:1 a ['writefull'])",
                 [("wal", None), ("journal", {"bytes": 4096})]),
             doc("subop", "c:1", "sub_op(c:1 a.s3)",
                 [("wal", {"blocks": 4})])])
    assert span_arg_ratio.read(old, SPEC["params"]) is None
    assert "0 wal spans" in old.said[-1]
    # commits that wrote no block: the denominator did not move
    idle = R([doc("client", "c:1", "osd_op(c:1 a ['writefull'])",
                  [("wal", {"blocks": 0, "dev_writes": 0})])])
    assert span_arg_ratio.read(idle, SPEC["params"]) is None
    assert span_arg_ratio.read(R(), SPEC["params"]) is None


def test_the_metric_is_declared_for_the_write_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = bench["per_layer"][-1]
    assert entry["name"] == "store.blocks_per_dev_write.write"
    assert entry["workloads"] == ["k8m3-4m-write", "k2m1-64k-mixed"]
    assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
        {k: SPEC[k] for k in ("layer", "unit", "better", "source", "moves")}


def test_a_tiny_write_window_reports_runs_longer_than_a_block():
    """64 KiB objects on k=8 m=3 are 8 KiB shard files: two blocks a
    commit, one device write where they land in one extent."""
    res = harness.run_cell("k8m3-4m-write", 7, 1.5, True, "cpu",
                           overrides=tiny.overrides("k8m3-4m-write"))
    assert res["correct"], res
    got = res["metrics"].get("store.blocks_per_dev_write.write")
    if got is None:
        pytest.skip("this program's wal spans carry no counts: the line "
                    "leaves the metric out")
    assert 1.0 < got["value"] <= 2.0, got
