"""What PR 40 adds: the configuration `rbd-wbtier-rep3-ec-k8m3` and its
cell `wbtier-k8m3-rbd-4k-randwrite` at a tiny size on the CPU platform
through `run_cell`, the plain reference image, and the reader of sums
of span args.

The cell is also rehearsed, traced and untraced, by `test_cells.py` as
it stands (its cases are the entries of BENCHMARK.json), at 64 KiB
objects: the tier's target is stated in objects of the source's size
(`pools/wbtier_ec.py` `tier_target_bytes`), so the tiny image fills
its tiny tier as the real one does."""

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny

CELL = "wbtier-k8m3-rbd-4k-randwrite"
NEW = {"tier.promote_wait_ms.write", "tier.promote_base_read_ms",
       "tier.promote_install_ms", "tier.flush_base_write_ms",
       "tier.full_wait_ms.write", "tier.miss_share.write",
       "tier.moved_bytes_per_user_byte", "rep.replica_wait_ms.write",
       "rep.queue_ms.write", "rep.store_commit_ms.write"}
# of the metrics that were there, the ones whose readers do not pick
# their docs by `'writefull'` (in this cell those are the flushes'
# writes at the base, not the clients' ops)
SHARED = {"kernel.encode_crc_roofline", "host.idle_gap_named_share.write",
          "client.op_p95_ms"}

reference = harness.load_module(harness.HERE, "references", "rbd_wbtier")
terms = harness.load_module(harness.HERE, "readers", "span_arg_terms")


def overrides(**params):
    """A 32-object image of 64 KiB objects behind an 8-object tier of
    2 PGs (4 objects a PG: full at 4, evicting above 3.2)."""
    ov = tiny.overrides(CELL)
    ov["config"].update(
        image={"size": 32 << 22, "order": 22},
        tier=dict(harness.Cell(CELL).config["tier"], pg_num=2,
                  target_max_bytes=8 << 22))
    ov["params"].update(ramp_min_seconds=1.0, ramp_max_seconds=60.0,
                        readback_sample=8, **params)
    return ov


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "chips", "store", "stripe_unit", "pg_num",
                "object_bytes", "inflight", "store_flush_policy",
                "pool_profile"):
        assert cfg[key] == twin[key], key
    # the client waits as the reference's does (no limit there)
    assert cfg["conf"] == dict(twin["conf"], objecter_op_timeout=300.0)
    assert cfg["pool_kind"] == "wbtier_ec" and "reference" not in cfg
    assert cfg["tier"] == {
        "size": 3, "min_size": 2, "pg_num": 8, "cache_mode": "writeback",
        "target_max_bytes": 268435456, "cache_target_dirty_ratio": 0.4,
        "cache_target_dirty_high_ratio": 0.6,
        "cache_target_full_ratio": 0.8, "cache_min_flush_age": 0,
        "cache_min_evict_age": 0, "hit_set_count": 4, "hit_set_period": 10}
    assert cfg["image"] == {"size": 1 << 30, "order": 22}
    assert (cfg["io_bytes"], cfg["rbd_cache"]) == (4096, False)
    assert set(cfg["reduced"]) == {"image", "tier", "run_length", "hosts"}
    assert len(cfg["guarantees"]) == 4
    (declared,) = [c for c in cell.bench["configs"]
                   if c["name"] == cell.workload["config"]]
    assert declared["reduced"] == ["image", "tier", "run_length", "hosts"]
    assert len(declared["source"]) <= 200 and "BenchWrite.cc" in \
        declared["source"]
    assert len(cell.workload["why"]) <= 200 and cell.workload["chips"] == 1
    assert cell.traffic["generator"] == "rbd_bench_write"
    assert cell.traffic["warm"] == ["encode", "decode"]
    assert cell.traffic["params"] == {
        "clients": 16, "io_bytes": 4096, "pattern": "rand",
        "ramp_min_seconds": 10, "ramp_max_seconds": 90,
        "readback_sample": 32}
    assert {m["name"] for m, _s in cell.end_to_end()} == {"write_mibps",
                                                          "setup_s"}
    assert {m["name"] for m, _s in cell.per_layer()} == NEW | SHARED
    # the eight cells the benchmark had are there, in their order
    assert [w["name"] for w in cell.bench["workloads"]][:8] == [
        "k8m3-4m-write", "k2m1-64k-mixed", "k8m3-4m-deep-scrub",
        "shec-k8m4c3-4m-degraded-read", "k8m3-4m-degraded-read",
        "cauchy-k6m3-4m-write", "lrc-k4m2l3-4m-write", "k8m3-4m-rand-read"]


def test_reference_image_and_stored():
    img = reference.Image(4 * 8192, 8192)
    img.write(8192 - 2, b"abcd")             # across two objects
    assert img.read(8192 - 3, 6) == b"\0abcd\0"
    assert img.object(0)[-2:] == b"ab" and img.object(1)[:2] == b"cd"
    assert img.object(3) == bytes(8192)
    with pytest.raises(ValueError):
        img.write(4 * 8192 - 1, b"xy")
    cfg = harness.Cell(CELL).config
    rs = harness.load_module(harness.HERE, "references", "reed_sol_van")
    data = bytes(range(256)) * 256
    assert reference.stored(data, cfg) == rs.stored(data, cfg)
    assert len(reference.stored(data, cfg)) == 11
    assert reference.resident(data, cfg) == [data] * 3
    src = open(reference.__file__).read()
    assert "ceph_tpu" not in src.replace("Imports nothing", "")


def test_span_arg_terms_reader():
    docs = [
        {"kind": "client", "description": "osd_op(c:1 o ['write'])",
         "trace_id": "c:1",
         "spans": [{"name": "tier.lookup", "t0": 1.0,
                    "args": {"hit": 0, "bytes": 4096}}]},
        # the same op sent again: it finds the copy its first send
        # promoted, and is no second write and no hit
        {"kind": "client", "description": "osd_op(c:1 o ['write'])",
         "trace_id": "c:1",
         "spans": [{"name": "tier.lookup", "t0": 3.0,
                    "args": {"hit": 1, "bytes": 4096}}]},
        {"kind": "client", "description": "osd_op(c:2 o ['write'])",
         "trace_id": "c:2",
         "spans": [{"name": "tier.lookup", "t0": 2.0,
                    "args": {"hit": 1, "bytes": 4096}}]},
        {"kind": "client", "description": "osd_op(o:3 o ['writefull'])",
         "trace_id": "o:3", "spans": []},
        # two promotes under one trace id (a copy dropped at a full PG
        # and promoted again) both moved their bytes
        {"kind": "tier_promote", "description": "tier_promote(1.0 o)",
         "trace_id": "c:1",
         "spans": [{"name": "install", "t0": 1.5,
                    "args": {"bytes": 32768}}]},
        {"kind": "tier_promote", "description": "tier_promote(1.0 o)",
         "trace_id": "c:1",
         "spans": [{"name": "install", "t0": 2.5,
                    "args": {"bytes": 32768}}]},
        {"kind": "tier_flush", "description": "tier_flush(1.0 o)",
         "trace_id": "f:1",
         "spans": [{"name": "base_write", "t0": 4.0,
                    "args": {"bytes": 65536}}]}]

    class R:
        op_docs = docs
        log = staticmethod(lambda _m: None)

    spec = lambda n: harness.load_json(  # noqa: E731
        harness.HERE, "layer_metrics", n + ".json")["params"]
    assert terms.read(R, spec("tier.miss_share.write")) == 50.0
    assert terms.read(R, spec("tier.moved_bytes_per_user_byte")) == 16.0
    R.op_docs = docs[3:]               # a program without the spans
    assert terms.read(R, spec("tier.miss_share.write")) is None


@pytest.mark.parametrize("traced", [False, True])
def test_cell_at_a_tiny_size(traced):
    lines = []
    result = harness.run_cell(CELL, 2**31 + 40, 3.0, traced, "cpu",
                              overrides=overrides(), out=lines.append)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["attempted"] > 0 and result["failed"] == 0
    for check in ("readback_mismatches", "tier_copy_mismatches",
                  "tier_objects_short_of_copies",
                  "tier_dirty_objects_short_of_copies",
                  "tier_data_objects_left",
                  "tier_dirty_left", "stored_mismatches",
                  "stored_crc_mismatches", "stored_files_compared",
                  "tier_evict_dirty", "tier_full_admit",
                  "dev_dispatches_in_window", "compiles_in_window"):
        assert f"check {check} = " in text, check
    assert "FAILED" not in text
    if traced:
        missing = (NEW | SHARED) - set(result["metrics"])
        assert all("roofline" in m for m in missing), missing
        assert 0.0 < result["metrics"]["tier.miss_share.write"]["value"] \
            <= 100.0
        assert result["metrics"]["tier.moved_bytes_per_user_byte"][
            "value"] > 1.0
    else:
        assert set(result["metrics"]) == {"write_mibps", "setup_s"}


def test_control_comes_out_not_correct():
    """One guarantee broken under the served path: every encode (here
    every flush's) returns its last parity shard one bit wrong.  No
    client reads parity; the comparison of the base's shard files with
    the reference's `stored()` has to see it."""
    from benchmark import control
    lines = []
    result = control.run_control(CELL, 41, 3.0, "parity_bitflip", "cpu",
                                 overrides(), out=lines.append)
    assert result["correct"] is False
    failed = [l for l in lines if "FAILED" in l]
    assert any("stored_mismatches" in l for l in failed), failed
    assert not any("readback_mismatches" in l for l in failed), failed
