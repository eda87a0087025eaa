"""BENCHMARK.json against the contract's letter, and against the files
the harness finds by name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s, most=200):
    return 1 <= len(s) <= most and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert bench["paths"] == ["benchmark"]
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells has to fit 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        # every cut of scale the entry lists is explained in the file,
        # and the file states its guarantees and flush policy
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["store_flush_policy"]
        assert cfg["osds"] == 13 and cfg["store"] == "blockstore"
        assert cfg["stripe_unit"] == 4096 and cfg["inflight"] == 16
        # the pool kind and the plain reference are files found by name
        assert os.path.isfile(os.path.join(HERE, "pools",
                                           cfg["pool_kind"] + ".py"))
        assert os.path.isfile(os.path.join(HERE, "references",
                                           cfg["reference"] + ".py"))


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    cfgs = {c["name"] for c in bench["configs"]}
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert _line(w["why"])
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(
            HERE, "generators", traffic["generator"] + ".py"))
        for warm in traffic["warm"]:
            assert os.path.isfile(os.path.join(HERE, "warmers",
                                               warm + ".py"))
        # every cell of this tree is there to drive the device
        assert ["dev_dispatches", ">=", 1] in traffic["window_counters"]


def _reporting(metric, cells):
    return set(metric.get("workloads", cells))


def test_metrics(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    assert len(e2e) == len(bench["end_to_end"])
    assert "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert _reporting(m, cells) <= set(cells)
        assert os.path.isfile(os.path.join(HERE, "e2e_metrics",
                                           m["name"] + ".json"))
    per = {m["name"]: m for m in bench["per_layer"]}
    assert len(per) == len(bench["per_layer"]) and 1 <= len(per) <= 128
    assert not set(per) & set(e2e)
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"])
        # `moves` is an end-to-end metric that every cell reporting
        # this metric also reports
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert _reporting(m, cells) <= _reporting(e2e[m["moves"]], cells)
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            assert m["source"] == "device_trace"
        with open(os.path.join(HERE, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(HERE, "readers",
                                           spec["reader"] + ".py"))
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in _reporting(m, cells)]
        assert len(mine) >= 2, f"{cell}: setup_s and one more"
        assert any(cell in _reporting(m, cells)
                   for m in bench["per_layer"]), cell


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(HERE):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            if f.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
