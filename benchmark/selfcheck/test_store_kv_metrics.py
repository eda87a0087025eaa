"""`store.kv_calls_per_commit.write` and `store.onode_hit_share.write`
(ISSUE 31), two metric files over the `span_arg_ratio` reader, read
from a fixture: the op docs of three writes (two new 64 KiB objects, an
overwrite of the first) on a k=2 m=1 `plugin=tpu` pool over three
blockstore OSDs, recorded from this tree on the CPU (counts, no times
are read)."""

import json
import os

import pytest

from benchmark import harness

span_arg_ratio = harness.load_module(harness.HERE, "readers",
                                     "span_arg_ratio")
NAMES = ("store.kv_calls_per_commit.write", "store.onode_hit_share.write")


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, docs=()):
        self.op_docs = list(docs)
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def spec(name):
    return harness.load_json(harness.HERE, "layer_metrics", name + ".json")


def docs():
    with open(os.path.join(harness.HERE, "fixtures",
                           "store_kv_ops.json")) as f:
        return json.load(f)


def wal_args(ds):
    return [s["args"] for d in ds for s in d["spans"] if s["name"] == "wal"]


def test_both_ratios_from_recorded_writes():
    ds = docs()
    assert sum(d["kind"] == "client" for d in ds) == 3
    args = wal_args(ds)
    r = R(ds)
    calls = span_arg_ratio.read(r, spec(NAMES[0])["params"])
    assert calls == pytest.approx(sum(a["kv_calls"] for a in args) /
                                  sum(a["commits"] for a in args))
    assert f"over {len(args)} commits" in r.said[-1]
    hits = span_arg_ratio.read(r, spec(NAMES[1])["params"])
    assert hits == pytest.approx(sum(a["onode_hits"] for a in args) /
                                 sum(a["onode_lookups"] for a in args))
    # a new object's commit is one SELECT that finds nothing and one
    # statement, with the PG's meta object resident
    new = [a for a in args if a["blocks"] == 8]
    assert len(new) == 6 and all(
        (a["kv_calls"], a["onode_lookups"], a["onode_hits"]) == (2, 2, 1)
        for a in new)
    assert 2.0 <= calls <= 4.0 and 0.5 <= hits < 1.0


def test_a_program_without_the_counts_reads_nothing():
    # the parent's wal spans carry `blocks` and `dev_writes` alone
    old = docs()
    for d in old:
        for s in d["spans"]:
            if s["name"] == "wal":
                s["args"] = {k: s["args"][k] for k in ("blocks",
                                                       "dev_writes")}
    for name in NAMES:
        assert span_arg_ratio.read(R(old), spec(name)["params"]) is None


def test_both_are_declared_for_the_write_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    per = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better in zip(NAMES, ("calls", "ratio"),
                                  ("lower", "higher")):
        entry, sp = per[name], spec(name)
        assert entry["workloads"] == ["k8m3-4m-write", "k2m1-64k-mixed"]
        assert (entry["unit"], entry["better"]) == (unit, better)
        assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                      "moves")} == \
            {k: sp[k] for k in ("layer", "unit", "better", "source",
                                "moves")}
        assert sp["reader"] == "span_arg_ratio" and \
            sp["params"]["span"] == "wal"
