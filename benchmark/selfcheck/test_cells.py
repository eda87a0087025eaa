"""Every cell rehearsed end to end at a tiny size on the CPU platform,
the controls that must come out not correct, and a throw-away cell
defined only by new files."""

import json
import os
import shutil
import sys

import pytest

from benchmark import control, harness
from benchmark.selfcheck import tiny

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


def run(cell, seed=3, seconds=2.0, traced=False, **kw):
    lines = []
    result = harness.run_cell(cell, seed, seconds, traced, "cpu",
                              overrides=tiny.overrides(cell), out=lines.append,
                              **kw)
    return result, lines


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    result, lines = run(cell, seed=2**31 + 11)
    assert result["correct"] is True, "\n".join(lines)
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    text = "\n".join(lines)
    for needle in ("platform cpu", "compile cache", "ops completed",
                   "latency samples", "store blockstore at", "check "):
        assert needle in text, needle
    assert "FAILED" not in text


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced(cell):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    result, lines = run(cell, seed=7, seconds=3.0, traced=True)
    assert result["correct"] is True, "\n".join(lines)
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= listed
    # spans and counters are there to read on any platform; only the
    # device-trace metrics may find nothing on a CPU
    missing = listed - set(result["metrics"])
    assert all("roofline" in m or m == "cache.hit_share.scrub"
               for m in missing), missing
    assert result["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} == set(result["breakdown"])


def test_refuses_another_platform(capsys):
    assert harness.run_cell(CELLS[0], 1, 1.0, False, "tpu") is None
    assert "not a chip run" in capsys.readouterr().err


def test_command_fails_without_a_tpu():
    from benchmark import run as entry
    assert entry.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"]) != 0


@pytest.mark.parametrize("cell,fault,check", [
    ("k8m3-4m-write", "parity_bitflip", "stored_mismatches"),
    ("k8m3-4m-deep-scrub", "parity_bitflip", "window_mismatches"),
    ("k2m1-64k-mixed", "read_bitflip", "window_mismatches"),
])
def test_control_comes_out_not_correct(cell, fault, check):
    lines = []
    result = control.run_control(cell, 5, 2.0, fault, "cpu",
                                 tiny.overrides(cell), out=lines.append)
    assert result["correct"] is False
    failed = [l for l in lines if "FAILED" in l]
    assert any(check in l for l in failed), failed


def test_host_served_window_is_not_correct():
    """Routing left to the host (no host_cutover, a crossover no op
    reaches): the device serves nothing in the window."""
    ov = tiny.overrides("k8m3-4m-write")
    ov["config"]["pool_profile"] = {
        "plugin": "jerasure", "k": "8", "m": "3",
        "technique": "reed_sol_van", "backend": "host"}
    lines = []
    traffic_warm = harness.load_json(harness.HERE, "traffic",
                                     "write-new-qd16.json")["warm"]
    ov["warm"] = []
    result = harness.run_cell("k8m3-4m-write", 5, 2.0, False, "cpu",
                              overrides=ov, out=lines.append)
    assert traffic_warm and result["correct"] is False
    assert any("dev_dispatches_in_window" in l and "FAILED" in l
               for l in lines)


def test_compile_inside_the_window_is_not_correct(monkeypatch):
    import threading

    import jax
    import jax.numpy as jnp

    gen = harness.load_module(harness.HERE, "generators", "closed_loop")
    orig = gen.run

    def run_and_compile(ctx, seconds):
        def late():
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        threading.Timer(0.8, late).start()
        return orig(ctx, seconds)

    monkeypatch.setattr(gen, "run", run_and_compile)
    result, lines = run("k8m3-4m-write")
    assert result["correct"] is False
    assert any("compiles_in_window" in l and "FAILED" in l for l in lines)


def test_the_left_out_degraded_read_cell_is_two_entries_away(tmp_path):
    """`k8m3-4m-degraded-read` is not in BENCHMARK.json (PERF.md, Open
    questions: the program does not sustain its load yet), but its
    traffic mix, its warm-up and its per-layer metric are in the tree:
    the entries alone bring it back."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    name = "k8m3-4m-degraded-read"
    bench["workloads"].append({
        "name": name, "config": "ec-k8m3-rados-4m",
        "traffic": "degraded-read-qd16", "chips": 1, "why": "left out"})
    for m in bench["end_to_end"]:
        if m["name"] == "read_mibps":
            m["workloads"].append(name)
    spec = harness.load_json(harness.HERE, "layer_metrics",
                             "kernel.decode_roofline.json")
    bench["per_layer"].append({
        "name": "kernel.decode_roofline", "workloads": [name],
        **{k: spec[k] for k in ("unit", "better", "source", "layer",
                                "moves")}})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(harness.HERE, tmp_path / "benchmark")
    lines = []
    result = harness.run_cell(name, 4, 2.0, False, "cpu",
                              overrides=tiny.overrides(name),
                              root=str(tmp_path), out=lines.append)
    assert result["correct"] is True, "\n".join(lines)
    assert set(result["metrics"]) == {"read_mibps", "setup_s"}
    assert any("failed_osds" in l for l in lines)
    assert result["attempted"] > 0 and result["failed"] == 0
    result = control.run_control(name, 4, 2.0, "read_bitflip", "cpu",
                                 tiny.overrides(name), root=str(tmp_path),
                                 out=lines.append)
    assert result["correct"] is False


REPLICATED_POOL = '''
"""A replicated pool: every acting OSD holds the object whole."""


def file_bytes(config):
    return int(config["object_bytes"])


def create(dep, name):
    dep.rados.create_pool(name, pg_num=int(dep.config["pg_num"]))


def stored(dep, oid):
    pgid, acting, _pg = dep.object_pg(oid)
    out = []
    for o in acting:
        osd = dep.cluster.osds[o]
        out.append((f"{oid}@osd.{o}",
                    bytes(osd.store.read(osd.pgs[pgid].cid, oid)), None))
    return out
'''

REPLICA_REFERENCE = '''
def stored(payload, config):
    return [(payload, None)] * int(config["size"])
'''


def test_a_cell_defined_only_by_new_files(tmp_path):
    """A later PR adds a deployment of another pool kind with its plain
    reference, a mix, a generator and a per-layer metric by adding
    files and entries, editing no file that exists: here a replicated
    pool, which no file of the tree knows, on which nothing needs the
    device."""
    here = tmp_path / "benchmark"
    shutil.copytree(harness.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "selfcheck", "fixtures"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cfg = harness.load_json(harness.HERE, "configs",
                            "ec-k2m1-rados-64k.json")
    for key in ("pool_profile", "stripe_unit"):
        del cfg[key]
    cfg.update(pool_kind="replicated", reference="replica", size=3)
    (here / "configs" / "rep3-new.json").write_text(json.dumps(cfg))
    (here / "pools" / "replicated.py").write_text(REPLICATED_POOL)
    (here / "references" / "replica.py").write_text(REPLICA_REFERENCE)
    (here / "generators" / "write_only.py").write_text(
        "from benchmark.generators import closed_loop as base\n"
        "prepare, verify = base.prepare, base.verify\n"
        "def run(ctx, seconds):\n"
        "    ctx.log('the new generator runs')\n"
        "    return base.run(ctx, seconds)\n")
    (here / "warmers" / "nothing.py").write_text(
        "NEEDS_DATA = False\n"
        "def warm(dep, inflight):\n"
        "    return 'the new warmer runs'\n")
    (here / "traffic" / "new-mix.json").write_text(json.dumps({
        "generator": "write_only", "warm": ["nothing"],
        "window_counters": [["dev_dispatches", "<=", 0]],
        "params": {"clients": 4, "keys": "new", "prewrite_objects": 4,
                   "ramp_seconds": 0.2}}))
    (here / "readers" / "op_count.py").write_text(
        "def read(readings, params):\n"
        "    return float(sum(1 for d in readings.op_docs\n"
        "                     if d['kind'] == params['kind']))\n")
    (here / "layer_metrics" / "osd.client_ops.new.json").write_text(
        json.dumps({"reader": "op_count", "params": {"kind": "client"}}))
    bench["configs"].append({
        "name": "rep3-new", "source": "made up for the self-check",
        "file": "benchmark/configs/rep3-new.json", "reduced": [],
        "why": "throw-away"})
    bench["workloads"].append({
        "name": "rep3-new-write", "config": "rep3-new", "traffic": "new-mix",
        "chips": 1, "why": "throw-away"})
    bench["end_to_end"][0]["workloads"].append("rep3-new-write")
    bench["per_layer"].append({
        "name": "osd.client_ops.new", "unit": "ops", "better": "higher",
        "source": "program_counter", "layer": "OSD op path",
        "moves": "write_mibps", "workloads": ["rep3-new-write"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())
    ov = tiny.overrides("rep3-new-write")
    lines = []
    for traced in (False, True):
        result = harness.run_cell("rep3-new-write", 9, 2.0, traced, "cpu",
                                  overrides=ov, root=str(tmp_path),
                                  here=str(here), out=lines.append)
        assert result["correct"] is True, "\n".join(lines)
        assert result["attempted"] > 0 and result["failed"] == 0
        assert set(result["metrics"]) == (
            {"osd.client_ops.new"} if traced else {"write_mibps", "setup_s"})
    text = "\n".join(lines)
    for needle in ("the new generator runs", "the new warmer runs",
                   "stored files compared with the reference: 12 of 4",
                   "dev_dispatches_in_window = 0 limit <= 0 ok"):
        assert needle in text, needle
    # and the comparison with the new reference is a real one
    (here / "references" / "replica.py").write_text(
        "def stored(payload, config):\n"
        "    return [(payload[:-1] + b'x', None)] * int(config['size'])\n")
    del sys.modules["benchmark.references.replica"]
    result = harness.run_cell("rep3-new-write", 9, 2.0, False, "cpu",
                              overrides=ov, root=str(tmp_path),
                              here=str(here), out=lines.append)
    assert result["correct"] is False
