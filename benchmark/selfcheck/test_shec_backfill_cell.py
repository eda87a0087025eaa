"""What PR 50 adds: the configuration `ec-shec-k8m4c3-rados-4m-osd-out`
and its cell `shec-k8m4c3-4m-backfill-rand-read` at a tiny size on the
CPU platform through `run_cell` (B's failure and traffic on N's pool),
the warmer's plans and operands, and the reader of what a rebuild
planned and gathered.

The cell is also rehearsed, traced and untraced, by `test_cells.py` as
it stands (its cases are the entries of BENCHMARK.json)."""

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny
from benchmark.selfcheck.test_backfill_cell import READ, RECOVERY

CELL = "shec-k8m4c3-4m-backfill-rand-read"
TWIN = "k8m3-4m-backfill-rand-read"
PLAN = {"recovery.widened_share", "recovery.planned_chunks",
        "recovery.gather_chunks"}
PROFILE = {"plugin": "tpu", "technique": "shec_multiple", "k": "8",
           "m": "4", "c": "3", "host_cutover": "1"}

plan_reader = harness.load_module(harness.HERE, "readers", "rebuild_plan")
warmer = harness.load_module(harness.HERE, "warmers", "rebuild_shingle")


def run(seconds=3.0, traced=False, seed=2**31 + 50, **params):
    ov = tiny.overrides(CELL)
    ov["params"].update(params)
    lines = []
    result = harness.run_cell(CELL, seed, seconds, traced, "cpu",
                              overrides=ov, out=lines.append)
    return result, lines


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    pool = harness.load_json(harness.HERE, "configs",
                             "ec-shec-k8m4c3-rados-4m.json")
    failed = harness.load_json(harness.HERE, "configs",
                               "ec-k8m3-rados-4m-osd-out.json")
    for key in ("osds", "mons", "chips", "store", "store_flush_policy",
                "pool_kind", "pool_profile", "stripe_unit", "reference",
                "pg_num", "object_bytes", "inflight"):
        assert cfg[key] == pool[key], key
    assert cfg["pool_profile"] == PROFILE and cfg["reference"] == "shec"
    assert cfg["conf"] == failed["conf"]
    assert cfg["failure"] == failed["failure"]
    assert cfg["guarantees"][:3] == pool["guarantees"]
    assert len(cfg["guarantees"]) == 4
    assert "active+clean" in cfg["guarantees"][3]
    assert "k+m = 12" in cfg["guarantees"][3]
    assert set(cfg["reduced"]) == {"objects", "run_length", "hosts"}
    for key, why in failed["assumed"].items():
        assert cfg["assumed"][key] == why, key
    # the stale text of N's file is not carried over
    assert "fast_read" not in cfg["assumed"]["gather"]
    (declared,) = [c for c in cell.bench["configs"]
                   if c["name"] == cell.workload["config"]]
    assert declared["reduced"] == ["objects", "run_length", "hosts"]
    assert len(declared["source"]) <= 200
    assert "erasure-code-shec.rst" in declared["source"]
    assert "add-or-rm-osds.rst" in declared["source"]
    assert len(cell.workload["why"]) <= 200 and cell.workload["chips"] == 1
    # B's traffic but for its own size and warm-up
    mine = cell.traffic
    theirs = harness.load_json(harness.HERE, "traffic",
                               "rand-read-qd16-osd-out.json")
    assert mine["generator"] == theirs["generator"]
    assert mine["window_counters"] == theirs["window_counters"]
    assert mine["warm"] == ["rebuild_shingle", "encode", "decode_planned"]
    p = dict(mine["params"])
    assert p.pop("prewrite_objects") % 256 == 0 and \
        768 <= mine["params"]["prewrite_objects"] <= 2048
    assert p == {k: v for k, v in theirs["params"].items()
                 if k != "prewrite_objects"}
    assert {m["name"] for m, _s in cell.end_to_end()} == {"read_mibps",
                                                          "setup_s"}
    assert {m["name"] for m, _s in cell.per_layer()} == \
        RECOVERY | READ | PLAN
    # the twin reports the three new metrics too, and nothing the
    # benchmark had went: the new entries are last
    assert {m["name"] for m, _s in harness.Cell(TWIN).per_layer()} == \
        RECOVERY | READ | PLAN
    assert [w["name"] for w in cell.bench["workloads"]][-1] == CELL
    assert [c["name"] for c in cell.bench["configs"]][-1] == \
        "ec-shec-k8m4c3-rados-4m-osd-out"
    assert [m["name"] for m in cell.bench["per_layer"]][-3:] == [
        "recovery.widened_share", "recovery.planned_chunks",
        "recovery.gather_chunks"]
    for m in cell.bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL, TWIN]
        assert (m["layer"], m["moves"], m["source"]) == (
            "recovery", "read_mibps", "program_span")


def test_warmer_plans_and_operands():
    from ceph_tpu.erasure.registry import registry
    codec = registry.factory("tpu", dict(PROFILE))
    plans = warmer.plans(codec, 12)
    one_loss = {lost: reads for (lost, empty), reads in plans.items()
                if not empty}
    assert [len(one_loss[p]) for p in range(12)] == [4] * 10 + [8, 8]
    assert one_loss[0] == [1, 2, 3, 8] and one_loss[9] == [4, 5, 6, 7]
    # with a member of the shingle unavailable the plan is wider, and
    # never names the lost or an unavailable position
    assert len(plans[0, (1,)]) == 8
    for (lost, empty), reads in plans.items():
        assert lost not in reads and not set(empty) & set(reads)
        assert len(reads) <= 8
    # every plan rides the one (1 x k) operand
    assert {shape: rows.shape for shape, rows in
            warmer.operands(codec, 8, 12).items()} == {(1, 8): (1, 8)}


def test_cell_traced_reads_every_metric():
    result, lines = run(traced=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    missing = (RECOVERY | READ | PLAN) - set(result["metrics"])
    assert missing <= {"host.idle_gap_named_share.read"}, (missing, text)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # a shingle where it is whole, k where it is not: never RS's 8.0
    assert 4.0 <= m["recovery.read_bytes_per_rebuilt_byte"] < 8.0
    assert 4.0 <= m["recovery.planned_chunks"] <= 8.0
    assert 4.0 <= m["recovery.gather_chunks"] <= 11.0
    assert 0.0 <= m["recovery.widened_share"] <= 1.0
    for check in ("rebuilds_in_window", "acting_sets_not_whole",
                  "rebuilt_positions_short", "stored_positions_missing",
                  "scrub_inconsistent_after_clean", "stored_mismatches",
                  "stored_crc_mismatches", "readback_mismatches"):
        assert f"check {check} " in text, check
    assert "warm rebuild_shingle" in text and "FAILED" not in text


def test_rebuild_plan_reader_on_made_docs():
    def doc(desc, spans, kind="recovery"):
        return {"kind": kind, "description": desc, "trace_id": desc,
                "mstart": 0.0, "duration": 1.0, "spans": spans}

    def span(name, **args):
        return {"name": name, "t0": 0.0, "t1": 1.0,
                **({"args": args} if args else {})}

    def push(acked=True):
        return span("rebuild.push", shard=3, target=7, bytes=512,
                    acked=acked)

    class R:
        log = staticmethod(lambda msg: None)
        op_docs = [
            doc("osd_op(c:1 o ['read'])", [span("execute")], "client"),
            doc("rebuild(1.0 a v=(1, 1))", [
                span("rebuild.read", path="local", chunks=4, planned=4,
                     widened=0, bytes_read=2048), push()]),
            doc("rebuild(1.0 b v=(1, 2))", [
                span("rebuild.read", path="full", chunks=10, planned=4,
                     widened=1, bytes_read=5120), push()]),
            doc("rebuild(1.0 c v=(1, 3))", [
                span("rebuild.read", path="cache", chunks=0,
                     bytes_read=0), push()]),
            # read and stopped there: pushed nothing, counts nowhere
            doc("rebuild(1.0 d v=(1, 4))", [
                span("rebuild.read", path="full", chunks=2, planned=8,
                     widened=1, bytes_read=1024)]),
            doc("rebuild(1.0 e v=(1, 5))", [
                span("rebuild.read", path="full", chunks=8, planned=8,
                     widened=0, bytes_read=4096), push(acked=False)])]

    assert plan_reader.read(R, {"what": "widened_share"}) == \
        pytest.approx(0.5)
    assert plan_reader.read(R, {"what": "planned_chunks"}) == \
        pytest.approx(4.0)
    assert plan_reader.read(R, {"what": "gather_chunks"}) == \
        pytest.approx(7.0)
    # the parent's docs: `chunks` alone
    for d in R.op_docs[1:]:
        for s in d["spans"]:
            if s["name"] == "rebuild.read":
                s["args"].pop("planned", None)
                s["args"].pop("widened", None)
    assert plan_reader.read(R, {"what": "widened_share"}) is None
    assert plan_reader.read(R, {"what": "planned_chunks"}) is None
    assert plan_reader.read(R, {"what": "gather_chunks"}) == \
        pytest.approx(7.0)
    R.op_docs = R.op_docs[:1]
    for what in ("widened_share", "planned_chunks", "gather_chunks"):
        assert plan_reader.read(R, {"what": what}) is None
