"""`msgr.bytes_per_read.write` (ISSUE 33), a metric file over the
`span_arg_ratio` reader, read from a fixture: the op docs of three
writes (two 1 MiB objects, one of 16 KiB) on a k=2 m=1 `plugin=tpu`
pool over three memstore OSDs, recorded from this tree on the CPU
(counts, no times are read)."""

import json
import os

import pytest

from benchmark import harness

span_arg_ratio = harness.load_module(harness.HERE, "readers",
                                     "span_arg_ratio")
NAME = "msgr.bytes_per_read.write"
SPEC = harness.load_json(harness.HERE, "layer_metrics", NAME + ".json")


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=()):
        self.op_docs = list(docs)
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def docs():
    with open(os.path.join(harness.HERE, "fixtures",
                           "msgr_recv_ops.json")) as f:
        return json.load(f)


def recv_args(ds):
    return [s["args"] for d in ds for s in d["spans"]
            if s["name"] == "msgr.recv"]


def test_bytes_over_reads_from_recorded_writes():
    ds = docs()
    assert sum(d["kind"] == "client" for d in ds) == 3
    assert sum(d["kind"] == "subop" for d in ds) == 6
    args = recv_args(ds)
    assert len(args) == 9 and all(a["reads"] >= 1 for a in args)
    r = R(ds)
    got = span_arg_ratio.read(r, SPEC["params"])
    assert got == pytest.approx(sum(a["bytes"] for a in args) /
                                sum(a["reads"] for a in args))
    assert f"on {len(args)} msgr.recv spans of 3 root ops" in r.said[-1]
    # a small frame came in one read; a long one in as many as the
    # kernel of an idle rig cut it into, so the ratio is below the
    # frames' mean length and above a small frame's
    small = [a for a in args if a["bytes"] < 65536]
    assert len(small) == 3 and all(a["reads"] == 1 for a in small)
    assert max(a["bytes"] for a in small) < got < \
        sum(a["bytes"] for a in args) / len(args)
    # the client's docs alone: the frames the objects came in
    alone = dict(SPEC["params"], kinds=["client"])
    mine = [a for d in ds if d["kind"] == "client"
            for a in recv_args([d])]
    assert span_arg_ratio.read(R(ds), alone) == pytest.approx(
        sum(a["bytes"] for a in mine) / sum(a["reads"] for a in mine))


def test_a_program_without_the_count_reads_nothing():
    # the parent's msgr.recv spans carry `bytes` alone
    old = docs()
    for d in old:
        for s in d["spans"]:
            if s["name"] == "msgr.recv":
                s["args"] = {"bytes": s["args"]["bytes"]}
    r = R(old)
    assert span_arg_ratio.read(r, SPEC["params"]) is None
    assert "0 msgr.recv spans with bytes and reads" in r.said[-1]


def test_it_is_declared_for_the_write_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    for cell in ("k8m3-4m-write", "k2m1-64k-mixed", "cauchy-k6m3-4m-write"):
        assert cell in entry["workloads"]
    assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
        {k: SPEC[k] for k in ("layer", "unit", "better", "source", "moves")}
    assert (entry["unit"], entry["better"], entry["layer"]) == \
        ("bytes", "higher", "messenger")
    assert SPEC["reader"] == "span_arg_ratio" and \
        SPEC["params"]["span"] == "msgr.recv"
