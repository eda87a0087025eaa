"""What PR 47 adds: the configuration `ec-k8m3-rados-4m-osd-out` and its
cell `k8m3-4m-backfill-rand-read` at a tiny size on the CPU platform
through `run_cell`, its two controls (a rebuilt shard spoiled after
clean comes out not correct; an OSD down but not out never gets a
window), and the reader of the rebuilds' docs.

The cell is also rehearsed, traced and untraced, by `test_cells.py` as
it stands (its cases are the entries of BENCHMARK.json): the generator
raises that file's four objects to the fewest with which a new member
is backfilled (`least_objects`)."""

import pytest

from benchmark import harness
from benchmark.payload import object_name
from benchmark.selfcheck import tiny

CELL = "k8m3-4m-backfill-rand-read"
RECOVERY = {
    "recovery.rebuild_mibps", "recovery.objects_per_s",
    "recovery.rebuild_ms", "recovery.read_ms", "recovery.device_path_ms",
    "recovery.encode_ms", "recovery.push_wait_ms",
    "recovery.target_commit_ms", "recovery.read_bytes_per_rebuilt_byte", "recovery.cpu_ms_per_object",
    "recovery.active_share"}
# the span-read metrics of a client read that H and R list; the four
# that divide pipeline-wide counters are not this cell's (PERF.md §7)
READ = {
    "osd.execute_ms.read", "osd.gather_wait_ms.read",
    "osd.gather_wait_named_share.read", "osd.gather_used_share.read",
    "osd.subop_read_ms.read", "osd.subreads_per_op.read",
    "osd.reply_path_ms.read", "msgr.recv_ms.read", "msgr.flight_ms.read",
    "host.cpu_ms_per_op.read", "host.idle_gap_named_share.read",
    "client.sends_per_op.read", "ec.plan_ms.read", "ec.device_path_ms.read"}
NOT_THIS_CELLS = {"kernel.decode_roofline", "kernel.encode_crc_roofline",
                  "ec.batch_stripes.read", "cache.hit_share.read"}

ops = harness.load_module(harness.HERE, "readers", "recovery_ops")
gen = harness.load_module(harness.HERE, "generators",
                          "closed_loop_backfill")


def run(seconds=3.0, traced=False, seed=2**31 + 47, **params):
    ov = tiny.overrides(CELL)
    ov["params"].update(params)
    lines = []
    result = harness.run_cell(CELL, seed, seconds, traced, "cpu",
                              overrides=ov, out=lines.append)
    return result, lines


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "chips", "store", "store_flush_policy",
                "pool_kind", "pool_profile", "stripe_unit", "reference",
                "pg_num", "object_bytes", "inflight"):
        assert cfg[key] == twin[key], key
    assert cfg["conf"] == dict(twin["conf"], osd_recovery_max_active=3,
                               osd_backfill_scan_batch=64,
                               osd_pg_log_max_entries=8)
    assert cfg["guarantees"][:3] == twin["guarantees"]
    assert len(cfg["guarantees"]) == 4
    assert "active+clean" in cfg["guarantees"][3]
    assert cfg["failure"]["osds_out"] == 1
    assert set(cfg["reduced"]) == {"objects", "run_length", "hosts"}
    for lacking in ("osd_max_backfills", "osd_recovery_max_single_start",
                    "osd_recovery_op_priority", "osd_recovery_sleep",
                    "recovery_settings", "osd_pg_log_max_entries"):
        assert lacking in cfg["assumed"], lacking
    for key, why in twin["assumed"].items():
        assert cfg["assumed"][key] == why
    (declared,) = [c for c in cell.bench["configs"]
                   if c["name"] == cell.workload["config"]]
    assert declared["reduced"] == ["objects", "run_length", "hosts"]
    assert len(declared["source"]) <= 200
    assert "add-or-rm-osds.rst" in declared["source"]
    assert len(cell.workload["why"]) <= 200 and cell.workload["chips"] == 1
    assert cell.traffic["generator"] == "closed_loop_backfill"
    assert cell.traffic["warm"] == ["rebuild", "encode", "decode"]
    assert cell.traffic["window_counters"] == [["dev_dispatches", ">=", 1]]
    p = cell.traffic["params"]
    assert (p["clients"], p["read_fraction"], p["osds_out"],
            p["ramp_seconds"], p["readback_sample"]) == (16, 1.0, 1, 10.0, 8)
    assert p["prewrite_objects"] % 256 == 0 and \
        512 <= p["prewrite_objects"] <= 2048
    assert (p["stored_sample"], p["stored_sample_backfilled"]) == (64, 48)
    assert {m["name"] for m, _s in cell.end_to_end()} == {"read_mibps",
                                                          "setup_s"}
    listed = {m["name"] for m, _s in cell.per_layer()}
    assert listed == RECOVERY | READ
    assert not listed & NOT_THIS_CELLS
    # nothing the benchmark had went, and the new entries are last
    assert [w["name"] for w in cell.bench["workloads"]][-1] == CELL
    assert [c["name"] for c in cell.bench["configs"]][-1] == \
        "ec-k8m3-rados-4m-osd-out"
    assert [m["name"] for m in cell.bench["per_layer"]][-11:] == [
        "recovery.rebuild_mibps", "recovery.objects_per_s",
        "recovery.rebuild_ms", "recovery.read_ms",
        "recovery.device_path_ms", "recovery.encode_ms",
        "recovery.push_wait_ms", "recovery.target_commit_ms",
        "recovery.read_bytes_per_rebuilt_byte",
        "recovery.cpu_ms_per_object", "recovery.active_share"]


def test_cell_traced_reads_every_recovery_metric():
    result, lines = run(traced=True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    # spans and counters are there to read on any platform; the one
    # device-trace metric may find nothing on a CPU
    missing = (RECOVERY | READ) - set(result["metrics"])
    assert missing <= {"host.idle_gap_named_share.read"}, (missing, text)
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # Reed-Solomon reads k = 8 shard files for each one it lands
    assert m["recovery.read_bytes_per_rebuilt_byte"] == pytest.approx(8.0)
    assert 0.0 < m["recovery.active_share"] <= 1.0
    assert m["recovery.rebuild_ms"] >= m["recovery.read_ms"] > 0
    for check in ("rebuilds_in_window", "acting_sets_not_whole",
                  "rebuilt_positions_short", "stored_positions_missing",
                  "scrub_inconsistent_after_clean", "stored_mismatches",
                  "stored_crc_mismatches", "readback_mismatches"):
        assert f"check {check} " in text, check
    assert "FAILED" not in text


def test_a_rebuilt_shard_overwritten_after_clean_is_not_correct(monkeypatch):
    """Control: after the cluster came clean, sixteen bytes of one
    REBUILT shard file (a position whose OSD changed) are overwritten
    under the store.  Only the comparison with the plain reference and
    the stored CRC see it: clients read data chunks from the planned
    set, and the scrub ran before."""
    from ceph_tpu.store import Transaction
    orig = gen.verify

    def verify_then_corrupt(ctx, window):
        verdict = orig(ctx, window)
        dep = ctx.dep
        for key, _version in verdict["stored_objects"]:
            oid = object_name(key)
            pgid, acting, pg = dep.object_pg(oid)
            moved = [i for i, (o, was) in enumerate(
                zip(acting, ctx.backfill["before"][pgid])) if o != was]
            if moved:
                dep.cluster.osds[acting[moved[-1]]].store.apply_transaction(
                    Transaction().write(pg.cid, f"{oid}.s{moved[-1]}", 0,
                                        b"\\xff" * 16))
                return verdict
        raise AssertionError("no sampled object in a remapped PG")

    monkeypatch.setattr(gen, "verify", verify_then_corrupt)
    result, lines = run()
    assert result["correct"] is False
    failed = [l for l in lines if "FAILED" in l]
    assert any("stored_mismatches" in l for l in failed), failed


def test_down_but_not_out_never_opens_a_window(monkeypatch):
    """Control: the victim is killed and marked down but NOT out.  CRUSH
    keeps its positions as holes and nothing is backfilled: set-up waits
    its bound for new acting sets and a first rebuilt shard, and the run
    ends there with no result (the command exits 1), never with a
    window measured on a cluster that repairs nothing."""
    from ceph_tpu.vstart import MiniCluster
    monkeypatch.setattr(MiniCluster, "mark_osd_out",
                        lambda self, osd_id: None)
    with pytest.raises(TimeoutError, match="no first rebuilt shard"):
        run(first_shard_bound_s=6.0)


def test_recovery_ops_reader_on_made_docs():
    def doc(trace, desc, t0, spans, kind="recovery"):
        return {"kind": kind, "description": desc, "trace_id": trace,
                "mstart": t0, "duration": max(s["t1"] for s in spans) - t0,
                "spans": spans}

    def span(name, t0, t1, **args):
        return {"name": name, "t0": t0, "t1": t1,
                **({"args": args} if args else {})}

    class R:
        log = staticmethod(lambda msg: None)
        op_docs = [
            doc("c:1", "osd_op(c:1 o ['read'])", 0.0,
                [span("execute", 0.0, 0.1)], kind="client"),
            doc("backfill:1.0:obj1", "rebuild(1.0 obj1 v=(1, 1))", 1.0, [
                span("rebuild.read", 1.0, 1.4, path="full", chunks=8,
                     bytes_read=4096),
                span("rebuild", 1.0, 1.5),
                span("rebuild.push", 1.5, 2.0, shard=3, target=7,
                     bytes=512, acked=True)]),
            doc("rebuild:1.0:obj2:s4", "rebuild(1.0 obj2 v=(1, 2))", 4.0, [
                span("rebuild.read", 4.0, 4.1, path="cache", chunks=0,
                     bytes_read=0),
                span("rebuild", 4.0, 4.2),
                span("rebuild.push", 4.2, 4.3, shard=4, target=2,
                     bytes=512, acked=True)]),
            doc("c:2", "osd_op(c:2 o ['read'])", 10.0,
                [span("execute", 10.0, 10.1)], kind="client")]

    assert ops.read(R, {"what": "objects_per_s"}) == pytest.approx(0.2)
    assert ops.read(R, {"what": "push_mibps"}) == \
        pytest.approx(1024 / (1 << 20) / 10.0)
    assert ops.read(R, {"what": "rebuild_ms"}) == pytest.approx(650.0)
    assert ops.read(R, {"what": "read_per_rebuilt_byte"}) == \
        pytest.approx(4.0)
    # one backfill doc of 1 s in a 10 s window: alone with a bridge of
    # 0.5 s, to both edges and the other with a bridge of 9 s
    assert ops.read(R, {"what": "active_share", "prefixes": ["backfill:"],
                        "bridge_s": 0.5}) == pytest.approx(0.1)
    assert ops.read(R, {"what": "active_share", "prefixes": ["backfill:"],
                        "bridge_s": 9.0}) == pytest.approx(1.0)
    assert ops.covered([(1.0, 2.0), (2.3, 3.0), (6.0, 7.0)], 0.0, 10.0,
                       0.5) == pytest.approx(3.0)
    R.op_docs = R.op_docs[:1]
    assert ops.read(R, {"what": "objects_per_s"}) is None
