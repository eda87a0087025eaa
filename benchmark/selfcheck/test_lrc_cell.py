"""What PR 34 adds: the lrc pool's configuration and its cell
`lrc-k4m2l3-4m-write` at a tiny size on the CPU platform, its layered
reference against a stripe computed by hand, the pool module that
lists eight positions, the roofline reader that takes the parity rows
from the configuration, and `k8m3-4m-rand-read` declared on the
configuration the benchmark already had.

Both cells are also rehearsed, traced and untraced, by `test_cells.py`
as it stands (its cases are the entries of BENCHMARK.json).  A cell is
looked up in a metric's list by NAME: membership, never the list
letter for letter nor a metric's place (PERF.md, Open questions, PR 32
(a))."""

import os

import numpy as np
import pytest

from benchmark import control, harness, oracle, trace
from benchmark.selfcheck import tiny

CELL = "lrc-k4m2l3-4m-write"
READ_CELL = "k8m3-4m-rand-read"
ROWS = "kernel.encode_crc_rows_roofline"
READ_METRICS = {
    "osd.execute_ms.read", "msgr.recv_ms.read", "osd.subop_read_ms.read",
    "host.cpu_ms_per_op.read", "osd.gather_wait_ms.read",
    "osd.subreads_per_op.read", "client.sends_per_op.read",
    "cache.hit_share.read", "host.idle_gap_named_share.read"}
DECODE_METRICS = {"ec.plan_ms.read", "ec.device_path_ms.read",
                  "ec.batch_stripes.read", "kernel.decode_roofline"}

reference = harness.load_module(harness.HERE, "references", "lrc")
reader = harness.load_module(harness.HERE, "readers", "kernel_roofline_rows")
plain = harness.load_module(harness.HERE, "readers", "kernel_roofline")


def bench():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def metric(name):
    (entry,) = [m for m in bench()["per_layer"] + bench()["end_to_end"]
                if m["name"] == name]
    return entry


def test_configuration_and_traffic():
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert cfg["pool_profile"] == {
        "plugin": "tpu", "technique": "lrc", "k": "4", "m": "2", "l": "3",
        "host_cutover": "1"}
    assert (cfg["reference"], cfg["pool_kind"], cfg["shards"]) == \
        ("lrc", "ec_mapped", 8)
    twin = harness.load_json(harness.HERE, "configs",
                             "ec-k8m3-rados-4m.json")
    for key in ("osds", "mons", "chips", "store", "stripe_unit", "pg_num",
                "object_bytes", "inflight", "conf", "store_flush_policy"):
        assert cfg[key] == twin[key], key
    assert set(cfg["reduced"]) == {"objects", "run_length", "hosts"}
    assert set(cfg["assumed"]) == {"stripe_unit", "host_cutover", "pg_num",
                                   "conf"}
    assert len(cfg["guarantees"]) == 4
    assert "DD__DD__" in cfg["guarantees"][0]
    assert "l = 3" in cfg["guarantees"][3]
    # W's own traffic file, and its one warm-up
    assert cell.workload["traffic"] == "write-new-qd16"
    assert cell.workload["chips"] == 1 and cell.traffic["warm"] == ["encode"]
    (declared,) = [c for c in cell.bench["configs"]
                   if c["name"] == cell.workload["config"]]
    assert declared["reduced"] == ["objects", "run_length", "hosts"]
    for needle in ("ErasureCodeLrc.cc", "parse_kml", "obj_bencher.cc"):
        assert needle in declared["source"], needle
    assert len(declared["source"]) <= 200
    assert all(len(w["why"]) <= 200 for w in cell.bench["workloads"])
    # nothing the benchmark had went: the six cells and four
    # configurations of PR 33 are there, in their order
    assert [w["name"] for w in cell.bench["workloads"]][:6] == [
        "k8m3-4m-write", "k2m1-64k-mixed", "k8m3-4m-deep-scrub",
        "shec-k8m4c3-4m-degraded-read", "k8m3-4m-degraded-read",
        "cauchy-k6m3-4m-write"]


def test_pool_module_lists_the_eight_positions():
    cfg = harness.Cell(CELL).config
    pool = harness.load_module(harness.HERE, "pools", "ec_mapped")
    ec = harness.load_module(harness.HERE, "pools", "ec")
    assert pool.stripes_per_object(cfg) == 256
    assert pool.file_bytes(cfg) == 256 * 4096 == 1 << 20
    assert pool.shape(cfg) == ec.shape(cfg) == (4, 2, 4096)
    assert (pool.create, pool.corrupt) == (ec.create, ec.corrupt)
    assert pool.stored is not ec.stored


def test_metrics_of_the_write_cell():
    cell = harness.Cell(CELL)
    assert {m["name"] for m, _s in cell.end_to_end()} == {"write_mibps",
                                                          "setup_s"}
    mine = {m["name"] for m, _s in cell.per_layer()}
    cauchy = {m["name"] for m, _s in
              harness.Cell("cauchy-k6m3-4m-write").per_layer()}
    # every .write metric C reports but the roofline that takes its
    # rows from pool_profile.m; in its place the one by rows
    assert mine == (cauchy - {"kernel.encode_crc_roofline"}) | {ROWS}
    assert all(m["moves"] == "write_mibps" for m, _s in cell.per_layer())
    assert CELL in metric("write_mibps")["workloads"]
    assert CELL not in metric("kernel.encode_crc_roofline")["workloads"]
    entry, spec = metric(ROWS), harness.load_json(
        harness.HERE, "layer_metrics", ROWS + ".json")
    assert entry["workloads"] == [CELL]
    assert {k: entry[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
        {"layer": "kernels", "unit": "%", "better": "higher",
         "source": "device_trace", "moves": "write_mibps"} == \
        {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")}
    assert spec["reader"] == "kernel_roofline_rows"
    # every metric lists its cells; none is left to every later cell
    assert all("workloads" in m for m in cell.bench["per_layer"])


def test_the_rand_read_cell_is_declared_on_what_was_there():
    cell = harness.Cell(READ_CELL)
    assert cell.workload == dict(cell.workload, config="ec-k8m3-rados-4m",
                                 traffic="rand-read-qd16", chips=1)
    assert cell.traffic == {
        "generator": "closed_loop", "warm": ["encode", "decode"],
        "window_counters": [["cache_hit", ">=", 1]],
        "params": {"clients": 16, "keys": "prewritten",
                   "prewrite_objects": 64, "read_fraction": 1.0,
                   "ramp_seconds": 6.0, "readback_sample": 8}}
    assert {m["name"] for m, _s in cell.end_to_end()} == {"read_mibps",
                                                          "setup_s"}
    listed = {m["name"] for m, _s in cell.per_layer()}
    assert listed == READ_METRICS
    degraded = {m["name"] for m, _s in
                harness.Cell("k8m3-4m-degraded-read").per_layer()}
    assert degraded - listed == DECODE_METRICS
    assert all(m["moves"] == "read_mibps" for m, _s in cell.per_layer())
    for name in DECODE_METRICS:
        assert READ_CELL not in metric(name)["workloads"]
    assert READ_CELL in metric("read_mibps")["workloads"]


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def config(unit):
    return {"pool_profile": {"technique": "lrc", "k": "4", "m": "2",
                             "l": "3"}, "stripe_unit": unit, "shards": 8}


def test_layout_strings_are_the_documented_ones():
    assert reference.layout(4, 2, 3) == (
        "DD__DD__", "DDc_DDc_", ["DDDc____", "____DDDc"])
    assert reference.layout(8, 4, 3)[0] == "DD__DD__DD__DD__"


def test_reference_against_a_stripe_computed_by_hand():
    """One stripe of unit 4: chunks a b c d.  reed_sol_van k=4 m=2 has
    a first row of ones, so g0 = a^b^c^d; its second row is
    oracle's; a k=3 m=1 row is ones, so l0 = a^b^g0 = c^d and l1 =
    c^d^g1."""
    unit = 4
    payload = bytes(range(1, 17))
    a, b, c, d = (np.frombuffer(payload, dtype=np.uint8)
                  .reshape(4, unit))
    row = oracle.reed_sol_van_matrix(4, 2)[1]
    g0 = a ^ b ^ c ^ d
    g1 = np.zeros(unit, dtype=np.uint8)
    for coeff, chunk in zip(row, (a, b, c, d)):
        g1 ^= oracle._MUL[coeff][chunk]
    assert list(oracle.reed_sol_van_matrix(4, 2)[0]) == [1, 1, 1, 1]
    assert list(oracle.reed_sol_van_matrix(3, 1)[0]) == [1, 1, 1]
    want = [a, b, g0, c ^ d, c, d, g1, c ^ d ^ g1]
    got = reference.stored(payload, config(unit))
    assert [data for data, _crc in got] == [w.tobytes() for w in want]
    assert [crc for _d, crc in got] == \
        [int(x) for x in oracle.crc32c(np.stack(want))]


def test_reference_lays_out_whole_objects_and_pads_the_tail():
    unit = 128
    payload = np.random.default_rng(34).integers(
        0, 256, 4 * unit * 3 - 50, dtype=np.uint8).tobytes()
    files = reference.shard_files(payload, 4, 2, 3, unit)
    assert files.shape == (8, 3 * unit)
    rs = oracle.shard_files(payload, 4, 2, unit)
    for pos, row in zip((0, 1, 4, 5, 2, 6), rs):
        assert np.array_equal(files[pos], row), pos
    assert np.array_equal(files[3], files[0] ^ files[1] ^ files[2])
    assert np.array_equal(files[7], files[4] ^ files[5] ^ files[6])


def test_reference_refuses_what_it_cannot_stand_for():
    with pytest.raises(ValueError, match="cannot stand for"):
        reference.stored(b"x" * 64, {
            "pool_profile": {"technique": "reed_sol_van", "k": "4",
                             "m": "2", "l": "3"}, "stripe_unit": 4,
            "shards": 8})
    with pytest.raises(ValueError, match="lists 6 shards"):
        reference.stored(b"x" * 64, dict(config(4), shards=6))
    with pytest.raises(ValueError, match="multiple"):
        reference.stored(b"x" * 64, {
            "pool_profile": {"technique": "lrc", "k": "4", "m": "2",
                             "l": "4"}, "stripe_unit": 4, "shards": 8})


def test_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        text = f.read()
    assert "ceph_tpu" not in text.replace("`ceph_tpu`", "")
    assert "import" in text and "from benchmark import oracle" in text


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------


class R:
    """Stand-in for harness.Readings."""

    def __init__(self, cfg, lines, delta, peaks):
        self.config, self.slice_delta, self.peaks = cfg, delta, peaks
        self.trace = None if lines is None else {"lines": lines}
        self.said = []

    def log(self, msg):
        self.said.append(msg)


def rows_params():
    return harness.load_json(harness.HERE, "layer_metrics",
                             ROWS + ".json")["params"]


def test_reader_reckons_four_rows_and_matches_the_fused_program_alone():
    from benchmark import rooflines
    cfg = harness.Cell(CELL).config
    peaks = rooflines.peaks_for("TPU v5 lite")
    stripes = 256 * 10
    delta = {"bytes_h2d": stripes * 4 * 4096, "bytes_d2h": 0}
    # 3 ms of the fused program, and a decode that is not its business
    lines = {0: {"XLA Modules": [("jit_run_encode_crc(1)", 0, 2_000_000),
                                 ("jit_run_encode_crc(1)", 9, 1_000_000),
                                 ("jit_run_decode(2)", 20, 50_000_000)]}}
    r = R(cfg, lines, delta, peaks)
    value = reader.read(r, rows_params())
    ops = stripes * 2 * 32 * 32 * 4096
    nbytes = stripes * (4 * 4096 + 4 * 4096 + 4 * 8)
    assert reader.work(delta, cfg) == (ops, nbytes)
    least = max(ops / peaks["int8_ops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    assert value == pytest.approx(100.0 * least / 0.003)
    assert 0 < value < 100 and "2 events" in r.said[-1]
    # the accepted formula takes m = 2 from the profile: half the
    # parity's operations, 2 of 4 parity chunks' bytes
    half_ops, fewer = rooflines.encode_crc(delta, cfg)
    assert (half_ops, fewer) == (ops / 2, stripes * (6 * 4096 + 4 * 6))
    # the XLA program's name matches too; a bare jit_run does not
    xla = {0: {"XLA Modules": [("jit_run_xla_encode_crc", 0, 3_000_000)]}}
    assert reader.read(R(cfg, xla, delta, peaks), rows_params()) == \
        pytest.approx(value)
    bare = {0: {"XLA Modules": [("jit_run(7)", 0, 3_000_000)]}}
    assert reader.read(R(cfg, bare, delta, peaks), rows_params()) is None


def test_reader_agrees_with_the_accepted_one_where_rows_are_m():
    from benchmark import rooflines
    cfg = harness.Cell("k8m3-4m-write").config
    delta = {"bytes_h2d": 128 * 8 * 4096 * 7, "bytes_d2h": 0}
    assert reader.work(delta, cfg) == rooflines.encode_crc(delta, cfg)


def test_reader_reads_nothing_where_there_is_nothing_and_never_raises():
    cfg = harness.Cell(CELL).config
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    lines = {0: {"XLA Modules": [("jit_run_encode_crc", 0, 1_000_000)]}}
    assert reader.read(R(cfg, None, {}, peaks), rows_params()) is None
    assert reader.read(R(cfg, lines, {}, peaks), rows_params()) is None
    assert reader.read(R(cfg, lines, {"bytes_h2d": 0}, peaks),
                       rows_params()) is None
    assert reader.read(R(cfg, {0: {}}, {"bytes_h2d": 1 << 20}, peaks),
                       rows_params()) is None


def test_reader_on_the_recorded_device_trace():
    """`fixtures/encode3.xplane.pb`: three runs of a fused encode+CRC
    program recorded on a TPU v5 lite (PR 24's fixture, program names
    of that day: the bare `jit_run`).  The reader's own pattern finds
    nothing in it, as on any parent whose programs carry no name; with
    the recording's pattern it reads the same events as the accepted
    reader and a share under 100%."""
    from benchmark import rooflines
    path = os.path.join(harness.HERE, "fixtures", "encode3.xplane.pb")
    reduced = trace.reduce(path, 1.0, "tpu")
    cfg = harness.Cell(CELL).config
    peaks = rooflines.peaks_for("TPU v5 lite")
    delta = {"bytes_h2d": 3 * 256 * 4 * 4096, "bytes_d2h": 0}
    r = R(cfg, reduced["lines"], delta, peaks)
    assert reader.read(r, rows_params()) is None
    recorded = dict(rows_params(), pattern="jit_run")
    value = reader.read(r, recorded)
    old = plain.read(r, {"work": "encode_crc", "line": "XLA Modules",
                         "pattern": "jit_run"})
    assert value is not None and old is not None
    assert 0 < value < 100 and value > old      # the same time, more work
    assert r.said[-2].split(" events")[0].split()[-1] == \
        r.said[-1].split(" events")[0].split()[-1]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


def run(cell, seed, traced, **kw):
    lines = []
    result = harness.run_cell(cell, seed, 2.0, traced, "cpu",
                              overrides=tiny.overrides(cell),
                              out=lines.append, **kw)
    return result, lines


def test_cell_ends_correct_against_the_layered_reference():
    result, lines = run(CELL, 2**31 + 34, False)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"write_mibps", "setup_s"}
    for needle in ("check stored_mismatches = 0 limit <= 0 ok",
                   "check stored_crc_mismatches = 0 limit <= 0 ok",
                   # all eight positions of four objects
                   "check stored_files_compared = 32 limit >= 1 ok",
                   "check dev_dispatches_in_window = ",
                   "check compiles_in_window = 0", "warm encode"):
        assert needle in text, needle
    assert "FAILED" not in text


def test_traced_run_reads_the_write_metrics():
    result, lines = run(CELL, 35, True)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    metrics = result["metrics"]
    # 64 KiB at the tiny size: four stripes of 4 x 4096 in a 4-bucket
    assert metrics["ec.dispatch_fill.write"]["value"] == 1.0, text
    assert metrics["ec.batch_stripes.write"]["value"] == \
        pytest.approx(4.0, abs=0.5)
    assert metrics["codec.device_stripe_share.write"]["value"] == 100.0
    assert metrics["store.blocks_per_dev_write.write"]["value"] == 4.0
    listed = {m["name"] for m, _s in harness.Cell(CELL).per_layer()}
    # a CPU's trace has no TPU plane: the roofline finds nothing, says
    # so and leaves the line without it
    assert listed - set(metrics) == {ROWS}
    assert f"metric {ROWS}: nothing to read" in text


def test_a_parity_bit_flipped_at_a_mapped_position_is_not_correct():
    """The control: every encode returns the file of its last POSITION
    (7, the second local parity) one bit wrong."""
    lines = []
    result = control.run_control(CELL, 5, 2.0, "parity_bitflip", "cpu",
                                 tiny.overrides(CELL), out=lines.append)
    assert result["correct"] is False
    failed = [l for l in lines if "FAILED" in l]
    assert any("stored_mismatches" in l for l in failed), failed
    assert any(".s7 differs from the reference" in l for l in lines)
    assert not any(f".s{p} differs" in l for l in lines for p in range(7))


def test_a_layout_by_chunk_id_is_not_correct(monkeypatch):
    """A program that laid its files out `DDDD____` (chunk i at
    position i, as before this PR) is readable by itself and wrong
    against the reference position by position."""
    from ceph_tpu.erasure.matrix_codec import MatrixErasureCode
    monkeypatch.setattr(MatrixErasureCode, "get_chunk_mapping",
                        lambda self: [])
    result, lines = run(CELL, 36, False)
    assert result["correct"] is False
    assert any("check readback_mismatches = 0 limit <= 0 ok" in l
               for l in lines)
    assert any("stored_mismatches" in l and "FAILED" in l for l in lines)


def test_rand_read_cell_serves_reads_from_the_cache_and_the_store():
    result, lines = run(READ_CELL, 2**31 + 37, False)
    text = "\n".join(lines)
    assert result["correct"] is True, text
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"read_mibps", "setup_s"}
    assert "check cache_hit_in_window = " in text
    assert "failed_osds" not in text and "FAILED" not in text
    result = control.run_control(READ_CELL, 6, 2.0, "read_bitflip", "cpu",
                                 tiny.overrides(READ_CELL),
                                 out=lines.append)
    assert result["correct"] is False
