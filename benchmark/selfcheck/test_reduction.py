"""The reduction from trace and spans to numbers, on synthetic cases
and on the recorded fixtures (a trace of three fused encode+CRC runs of
one 4 MiB object on a TPU v5 lite, and `dump_historic_ops` docs of a
chip run of `k8m3-4m-write`)."""

import json
import os

import pytest

from benchmark import harness, rooflines, trace

FIX = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
span_self_time = harness.load_module(harness.HERE, "readers",
                                     "span_self_time")
counter_ratio = harness.load_module(harness.HERE, "readers", "counter_ratio")
kernel_roofline = harness.load_module(harness.HERE, "readers",
                                      "kernel_roofline")


class R:
    """Stand-in for harness.Readings."""
    config = {"pool_profile": {"k": "8", "m": "3"}, "stripe_unit": 4096}
    op_docs: list = []
    counter_delta: dict = {}
    slice_delta: dict = {}
    trace = None
    peaks = rooflines.peaks_for("TPU v5 lite")
    log = staticmethod(lambda msg: None)


def test_union_and_gaps():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0),
          ("d", 31.0, 1.0)]
    assert trace.union_ns(ev) == 20.0
    lines = {0: {trace.OPS_LINE: ev}, 1: {trace.OPS_LINE: ev[:1]}}
    assert trace.busy_seconds(lines) == pytest.approx((20 + 10) / 2 / 1e9)
    assert trace.idle_gaps(lines) == [["before c", 15e-9]]
    assert trace.top_ops(lines)[0] == ["a", 20e-9]
    assert trace.time_by_pattern(lines, trace.OPS_LINE, "^[ab]$") == \
        (pytest.approx(30e-9), 3)


def test_self_time_is_duration_minus_children():
    spans = [{"name": "execute", "t0": 0.0, "t1": 10.0},
             {"name": "wal", "t0": 1.0, "t1": 3.0},
             {"name": "store_apply", "t0": 2.0, "t1": 4.0},
             {"name": "replica_wait", "t0": 5.0, "t1": 9.0},
             {"name": "queue", "t0": -2.0, "t1": 0.0}]
    got = dict(span_self_time.self_times(spans))
    assert got["execute"] == pytest.approx(10 - 3 - 4)
    assert got["wal"] == 2.0 and got["replica_wait"] == 4.0
    assert got["queue"] == 2.0


def test_span_reader_means_per_client_op_and_adds_subops():
    r = R()
    r.op_docs = [
        {"kind": "client", "trace_id": "c:1", "description":
         "osd_op(c:1 o ['writefull'])", "spans": [
             {"name": "execute", "t0": 0.0, "t1": 1.0},
             {"name": "wal", "t0": 0.1, "t1": 0.3}]},
        {"kind": "subop", "trace_id": "c:1", "description": "sub_op()",
         "spans": [{"name": "wal", "t0": 0.0, "t1": 0.5}]},
        {"kind": "client", "trace_id": "c:2", "description":
         "osd_op(c:2 o ['read'])", "spans": [
             {"name": "execute", "t0": 0.0, "t1": 0.25}]}]
    p = {"spans": ["wal"], "op": "'writefull'", "subops": True}
    assert span_self_time.read(r, p) == pytest.approx(700.0)
    assert span_self_time.read(r, dict(p, subops=False)) == \
        pytest.approx(200.0)
    assert span_self_time.read(
        r, {"spans": ["execute"], "op": "'read'"}) == pytest.approx(250.0)
    assert span_self_time.read(r, dict(p, op="'append'")) is None


def test_counter_ratio():
    r = R()
    r.counter_delta = {"stripes": 1280, "dev_dispatches": 8, "cache_hit": 0,
                       "cache_miss": 0}
    assert counter_ratio.read(r, {"numerator": ["stripes"], "denominator":
                                  ["dev_dispatches"]}) == 160.0
    assert counter_ratio.read(r, {"numerator": ["cache_hit"], "denominator":
                                  ["cache_hit", "cache_miss"],
                                  "scale": 100}) is None


def test_roofline_arithmetic():
    peaks = rooflines.peaks_for("TPU v5 lite")
    d = {"bytes_h2d": 128 * 8 * 4096, "bytes_d2h": 128 * (3 * 4096 + 44)}
    ops, nbytes = rooflines.encode_crc(d, R.config)
    assert nbytes == 128 * (8 * 4096 + 3 * 4096 + 4 * 11)
    assert ops == 128 * 2 * 64 * 24 * 4096
    least, bound = rooflines.least_seconds(ops, nbytes, peaks)
    assert bound == "hbm" and least == pytest.approx(nbytes / 819e9)
    ops, nbytes = rooflines.decode({"bytes_h2d": 8 * 4096, "bytes_d2h":
                                    2 * 4096}, R.config)
    assert nbytes == 10 * 4096 and ops == 2 * 64 * 16 * 4096
    ops, nbytes = rooflines.crc({"bytes_h2d": 1 << 20, "bytes_d2h": 8},
                                R.config)
    assert rooflines.least_seconds(ops, nbytes, peaks)[1] == "int8"
    with pytest.raises(KeyError):
        rooflines.peaks_for("TPU v9 imaginary")


def test_recorded_chip_trace():
    """Three runs of the fused kernel on one 4 MiB object (128 stripes):
    busy time is their union, the roofline share follows from it."""
    path = os.path.join(FIX, "encode3.xplane.pb")
    red = trace.reduce(path, 1.0, "tpu")
    assert set(red["lines"]) == {0}
    seconds, events = trace.time_by_pattern(
        red["lines"], trace.MODULES_LINE, "jit_run")
    assert events == 3 and 0 < seconds < 0.1
    assert 0 < red["busy_s"] <= seconds * 1.001
    assert red["breakdown"]["device_ops"]
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    r = R()
    r.trace = red
    r.slice_delta = {"bytes_h2d": 3 * 128 * 8 * 4096, "bytes_d2h": 0}
    share = kernel_roofline.read(r, {"work": "encode_crc", "line":
                                     trace.MODULES_LINE,
                                     "pattern": "jit_run"})
    assert 0 < share < 100
    least = 3 * 128 * (11 * 4096 + 44) / 819e9
    assert share == pytest.approx(100 * least / seconds)
    r.slice_delta = {"bytes_h2d": 0, "bytes_d2h": 0}
    assert kernel_roofline.read(r, {"work": "encode_crc", "line":
                                    trace.MODULES_LINE,
                                    "pattern": "jit_run"}) is None


def test_recorded_chip_op_docs():
    with open(os.path.join(FIX, "historic_ops.json")) as f:
        docs = json.load(f)
    r = R()
    r.op_docs = docs
    writes = [d for d in docs if d["kind"] == "client"
              and "'writefull'" in d["description"]]
    assert writes
    for name in ("osd.queue_ms.write", "osd.replica_wait_ms.write",
                 "ec.device_path_ms.write", "store.commit_ms.write"):
        spec = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        v = span_self_time.read(r, spec["params"])
        assert v is not None and v > 0, name
    # self times of one op never add up to more than the op took
    for d in writes:
        total = sum(t for _n, t in span_self_time.self_times(d["spans"]))
        longest = max(s["t1"] for s in d["spans"]) - \
            min(s["t0"] for s in d["spans"])
        assert total <= longest * 1.000001 + 1e-9 or len(
            {s["name"] for s in d["spans"]} & {"ec.coalesce"}) > 0
