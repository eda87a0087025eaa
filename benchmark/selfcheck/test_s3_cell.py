"""What PR 42 adds: the configuration `rgw-ec-k4m2-idx-rep3` and its
cell `s3-k4m2-sizemix` at a tiny size on the CPU platform through
`run_cell`, and the three controls that have to end NOT correct: a
flipped byte in one tail shard file, a reference whose `layout()` cuts
the head at another size, a gateway answer that mixes two versions.

(`test_cells.py` rehearses the cell too, at the traffic file's own 768
objects: `tiny.py` knows no parameter of this mix.)"""

import pytest

from benchmark import harness
from benchmark.selfcheck import tiny

CELL = "s3-k4m2-sizemix"
NEW = {"rgw.rados_writes_per_put", "rgw.rados_reads_per_get",
       "osd.replica_wait_ms.append", "store.commit_ms.append",
       "ec.device_path_ms.append", "ec.dispatch_fill.append",
       "ec.append_through_share", "idx.replica_wait_ms.write",
       "idx.store_commit_ms.write"}

reference = harness.load_module(harness.HERE, "references", "rgw_s3_ec")
pool = harness.load_module(harness.HERE, "pools", "rgw_ec")


def overrides(**params):
    """12 prepared objects a bucket, sizes either side of the 512 KiB
    chunk (the largest has three appends), 4 workers."""
    ov = tiny.overrides(CELL)
    ov["config"] = {"pg_num": 2,
                    "index_pool": {"size": 3, "min_size": 2, "pg_num": 2},
                    "data_extra_pool": {"size": 3, "min_size": 2,
                                        "pg_num": 2}}
    ov["conf"]["osd_ec_hbm_cache_bytes"] = 8 << 20
    ov["params"] = dict({
        "clients": 4, "objects": 12, "ramp_seconds": 0.5,
        "size_classes": [[1000, 64000, 1], [64000, 512000, 2],
                         [512000, 1700000, 3]],
        "readback_written": 4, "readback_prepared": 4}, **params)
    return ov


def run(seed=5, seconds=3.0, traced=False, **params):
    lines = []
    result = harness.run_cell(CELL, seed, seconds, traced, "cpu",
                              overrides=overrides(**params),
                              out=lines.append)
    return result, lines


def checks(lines):
    return {ln.split()[2]: ln for ln in lines if ln.startswith("# check ")}


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "chips", "store", "stripe_unit",
                "inflight", "store_flush_policy", "conf"):
        assert cfg[key] == twin[key], key
    assert cfg["pool_profile"] == {
        "plugin": "tpu", "k": "4", "m": "2", "technique": "reed_sol_van",
        "host_cutover": "1"}
    assert cfg["pool_kind"] == "rgw_ec" and "reference" not in cfg
    assert cfg["pg_num"] == 16 and cfg["object_bytes"] == 524288
    assert cfg["index_pool"] == cfg["data_extra_pool"] == {
        "size": 3, "min_size": 2, "pg_num": 8}
    assert cfg["gateway"]["rgw_max_chunk_size"] == \
        reference.MAX_CHUNK_SIZE == reference.chunk_size(cfg)
    assert cfg["gateway"]["rgw_obj_stripe_size"] == \
        reference.OBJ_STRIPE_SIZE
    assert set(cfg["reduced"]) == {"objects", "run_length", "hosts"}
    assert len(cfg["guarantees"]) == 4
    (declared,) = [c for c in cell.bench["configs"]
                   if c["name"] == cell.workload["config"]]
    assert declared["reduced"] == ["objects", "run_length", "hosts"]
    assert len(declared["source"]) <= 200
    assert len(cell.workload["why"]) <= 200 and cell.workload["chips"] == 1
    assert cell.traffic["generator"] == "cosbench_s3"
    assert cell.traffic["warm"] == ["encode_pow2"]
    p = cell.traffic["params"]
    assert (p["clients"], p["buckets"], p["objects"]) == (16, 2, 384)
    # 80 / 20, and the histogram's 10 : 20 : 30
    puts = [c[2] for c in p["size_classes"]]
    assert p["block_gets"] == 4 * sum(puts) and puts == [1, 2, 3]
    assert [c[:2] for c in p["size_classes"]] == [
        [1000, 64000], [64000, 512000], [512000, 2048000]]
    assert {m["name"] for m, _s in cell.end_to_end()} == {
        "write_mibps", "read_mibps", "setup_s"}
    assert NEW <= {m["name"] for m, _s in cell.per_layer()}


def test_reference_layout():
    cfg = harness.Cell(CELL).config
    lay = reference.layout(2_048_000, cfg)
    assert lay["head"] == 524288
    assert lay["tails"] == [(1, 524288, 1523712,
                             [524288, 524288, 475136])]
    assert reference.layout(524288, cfg) == {"head": 524288, "tails": []}
    # tail stripe n holds [head + (n-1) x 4 MiB, head + n x 4 MiB)
    two = reference.layout((4 << 20) + (512 << 10) + 1, cfg)["tails"]
    assert [(n, at, length) for n, at, length, _w in two] == [
        (1, 524288, 4 << 20), (2, 524288 + (4 << 20), 1)]
    assert len(reference.stored(b"x" * 20000, cfg)) == 6


def test_cell_is_correct_and_reports():
    result, lines = run()
    assert result["correct"] is True, "\n".join(lines)
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"write_mibps", "read_mibps",
                                      "setup_s"}
    got = checks(lines)
    for name in ("window_mismatches", "readback_mismatches",
                 "layout_mismatches", "stored_mismatches",
                 "stored_crc_mismatches", "stored_files_compared",
                 "index_replicas_short", "listing_missing",
                 "listing_unknown", "rgw_gc_removed_in_window",
                 "compiles_in_window", "dev_dispatches_in_window"):
        assert name in got and got[name].endswith("ok"), name
    text = "\n".join(lines)
    assert "rgw counters over the window" in text
    assert "gateway PUT:" in text and "rgw.put_head" in text
    assert "gateway GET:" in text and "rgw.get_head" in text


def test_cell_traced_reports_the_new_metrics():
    result, lines = run(seed=9, seconds=4.0, traced=True)
    assert result["correct"] is True, "\n".join(lines)
    missing = NEW - set(result["metrics"])
    assert not missing, (missing, "\n".join(lines))
    assert 2.0 <= result["metrics"]["rgw.rados_writes_per_put"]["value"] \
        <= 8.0
    assert 1.0 <= result["metrics"]["rgw.rados_reads_per_get"]["value"] \
        <= 2.0


def test_flipped_tail_shard_byte_is_not_correct(monkeypatch):
    real = pool.stored

    def stored(dep, oid):
        files = real(dep, oid)
        if ".shadow." in oid:
            label, data, crc = files[-1]
            files[-1] = (label, bytes([data[0] ^ 1]) + data[1:], crc)
        return files
    monkeypatch.setattr(pool, "stored", stored)
    result, lines = run(seed=11)
    assert result["correct"] is False
    assert checks(lines)["stored_mismatches"].endswith("FAILED")


def test_reference_with_another_head_size_is_not_correct(monkeypatch):
    monkeypatch.setattr(reference, "MAX_CHUNK_SIZE", 256 << 10)
    result, lines = run(seed=12)
    assert result["correct"] is False
    assert checks(lines)["layout_mismatches"].endswith("FAILED") or \
        checks(lines)["stored_mismatches"].endswith("FAILED")


def test_answer_that_mixes_two_versions_is_not_correct(monkeypatch):
    from ceph_tpu import rgw
    real = rgw.RGWDaemon._read_object
    last_tail = []

    def mixed(self, head_oid, want_body=True):
        got = real(self, head_oid, want_body)
        if got is not None and len(got[2]) > 1:
            manifest, attrs, pieces = got
            mine = list(pieces[1:])
            if last_tail and sum(map(len, last_tail[0])) == \
                    sum(map(len, mine)):
                pieces = [pieces[0]] + last_tail[0]
            elif last_tail:
                # another version's tail, cut or padded to this length
                other = b"".join(bytes(p) for p in last_tail[0])
                need = sum(map(len, mine))
                pieces = [pieces[0], (other * (need // len(other) + 1))
                          [:need]]
            last_tail[:] = [mine]
            return manifest, attrs, pieces
        return got
    monkeypatch.setattr(rgw.RGWDaemon, "_read_object", mixed)
    result, lines = run(seed=13)
    assert result["correct"] is False
    assert checks(lines)["window_mismatches"].endswith("FAILED")
