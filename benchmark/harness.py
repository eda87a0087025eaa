"""One cell, once: boot, warm, measure, check, report.

Driven by data: `BENCHMARK.json` names the cell's configuration and
traffic mix and lists the metrics with the cells that report them; the
files under this directory, found by those names, hold everything
else.  Nothing here knows a cell, a configuration or a metric by name.

The platform is an argument of `run_cell`, not an option of the
command: `benchmark.run` always passes "tpu", and the self-check drives
the same function at a tiny size with "cpu".
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from collections import Counter

from benchmark import cluster as cl
from benchmark import rooflines, trace
from benchmark.payload import Payloads, object_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
MIB = float(1 << 20)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the profiler traces a slice of the window, not all of it
TRACE_START_S = 5.0
TRACE_LEN_S = 5.0
# ring large enough for every op of a traced window on every daemon
TRACED_HISTORY = 200000


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(here: str, kind: str, name: str):
    """The module `name` of one `kind` (generators, readers, warmers,
    pools, references), from the file of that name under `here`/`kind`
    (so that a cell defined only by new files loads)."""
    path = os.path.join(here, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    mod = sys.modules.get(spec.name)
    if mod is None or getattr(mod, "__file__", None) != path:
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell's data, loaded by name from BENCHMARK.json and the files
    under the benchmark's directory."""

    def __init__(self, name: str, root: str = ROOT, here: str = HERE):
        self.bench = load_json(root, "BENCHMARK.json")
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(by_name)})")
        self.workload = by_name[name]
        self.name = name
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.workload["config"])
        self.config = load_json(root, cfg["file"])
        self.traffic = load_json(here, "traffic",
                                 self.workload["traffic"] + ".json")
        self.here = here

    def _reported(self, section: str) -> list[dict]:
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self) -> list[tuple[dict, dict]]:
        return [(m, load_json(self.here, "e2e_metrics", m["name"] + ".json"))
                for m in self._reported("end_to_end")]

    def per_layer(self) -> list[tuple[dict, dict]]:
        return [(m, load_json(self.here, "layer_metrics",
                              m["name"] + ".json"))
                for m in self._reported("per_layer")]


class Readings:
    """What the per-layer readers read."""

    def __init__(self, dep, log):
        self.config = dep.config
        self.log = log
        self.window_ops: list = []       # (kind, t0, t1, ok, bytes)
        self.op_docs: list[dict] = []
        self.counter_delta: dict = {}
        self.slice_delta: dict = {}
        self.trace: dict | None = None
        self.peaks: dict = {}


class Ctx:
    """What a generator sees: the deployment, its payloads, its own
    parameters, and the two calls that mark the window."""

    def __init__(self, dep, payloads, params, seed, log, tracer):
        self.dep, self.payloads, self.params = dep, payloads, params
        self.seed, self.log = seed, log
        self._tracer = tracer
        self.t_open = self.t_close = None
        self.c_open = self.c_close = None

    def open_window(self) -> float:
        self.c_open = self.dep.counters()
        self.t_open = time.monotonic()
        if self._tracer is not None:
            self._tracer.start()
        return self.t_open

    def close_window(self) -> None:
        self.t_close = time.monotonic()
        self.c_close = self.dep.counters()
        if self._tracer is not None:
            self._tracer.join(120.0)


class Tracer(threading.Thread):
    """Traces a slice of the window with the JAX profiler and snapshots
    the counters where the slice starts and stops."""

    def __init__(self, dep, start_s: float, len_s: float, out_dir: str):
        super().__init__(daemon=True, name="bench-tracer")
        self.dep, self.start_s, self.len_s = dep, start_s, len_s
        self.out_dir = out_dir
        self.c0 = self.c1 = None
        self.traced_s = 0.0
        self.error = None

    def run(self) -> None:
        import jax
        try:
            time.sleep(self.start_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.c0 = self.dep.counters()
            t0 = time.monotonic()
            time.sleep(self.len_s)
            self.c1 = self.dep.counters()
            self.traced_s = time.monotonic() - t0
            jax.profiler.stop_trace()
        except Exception as e:       # the line then lacks its trace metrics
            self.error = e
            traceback.print_exc()


class CompileWatch:
    """Counts programs JAX compiled (or fetched from its cache) between
    two instants: there should be none inside the window."""

    def __init__(self):
        import jax
        self.stamps: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.stamps.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for s in self.stamps if t0 <= s <= t1)


def latency_ms(done: list, pct: float) -> float:
    """For the log: a percentile of the window's op latencies, as the
    `op_latency` reader takes it."""
    reader = load_module(HERE, "readers", "op_latency")
    lat = [1000.0 * (t1 - t0) for _k, t0, t1, _ok, _b in done]
    return reader.percentile(lat, pct) if lat else float("nan")


def end_to_end_value(spec: dict, done: list, seconds: float,
                     setup_s: float) -> float | None:
    """One end-to-end metric from the ops completed in the window:
    (kind, t0, t1, ok, bytes)."""
    if spec["kind"] == "setup_s":
        return setup_s
    if spec["kind"] == "rate_mibps":
        return sum(b for k, _0, _1, ok, b in done
                   if ok and k == spec["op"]) / MIB / seconds
    raise KeyError(f"unknown end-to-end metric kind {spec['kind']!r}")


def check_stored(dep, reference, payloads, objects, log) -> list:
    """What the acting OSDs' stores hold of each (key, version), as the
    configuration's pool module lists it, against the configuration's
    plain reference, bit for bit, and the stored CRCs against the
    reference's."""
    data_bad = crc_bad = files = 0
    for key, version in objects:
        oid = object_name(key)
        want = reference.stored(payloads.make(key, version), dep.config)
        got = dep.pool.stored(dep, oid)
        if len(got) != len(want):
            raise cl.CheckFailed(f"{oid}: the pool lists {len(got)} stored "
                                 f"files, the reference {len(want)}")
        for have, (want_data, want_crc) in zip(got, want):
            if have is None:
                continue             # that OSD was failed by the mix
            files += 1
            label, data, stored_crc = have
            if data != want_data:
                data_bad += 1
                log(f"stored {label} differs from the reference")
            if want_crc is not None and stored_crc != want_crc:
                crc_bad += 1
                log(f"crc of {label}: stored {stored_crc:#x}, reference "
                    f"{want_crc:#x}")
    log(f"stored files compared with the reference: {files} of "
        f"{len(objects)} objects")
    return [("stored_mismatches", data_bad, "<=", 0),
            ("stored_crc_mismatches", crc_bad, "<=", 0),
            ("stored_files_compared", files, ">=", 1)]


def process_start_monotonic(fallback: float) -> float:
    """When this process started, on time.monotonic()'s scale."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError):
        pass
    return fallback


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             platform: str = "tpu", t_import: float | None = None,
             overrides: dict | None = None, root: str = ROOT,
             here: str = HERE, out=print) -> dict | None:
    """Run one cell; returns the result object (None when there is no
    device to run on: the caller then prints no result).  `overrides`
    is the self-check's alone (`benchmark.run` passes none): its tiny
    sizes, as patches of the config, the traffic parameters and the
    cluster conf, and the peaks a CPU is to be read against."""
    t_start = process_start_monotonic(t_import or time.monotonic())

    def log(msg: str) -> None:
        out(f"# {msg}")
        sys.stdout.flush()

    cell = Cell(name, root, here)
    ov = overrides or {}
    cell.config.update(ov.get("config", {}))
    params = dict(cell.traffic["params"], **ov.get("params", {}))
    chips = int(cell.workload["chips"])

    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        print(f"benchmark: need {chips} {platform} device(s); jax reports "
              f"{len(devs)} x {devs[0].platform}: not a chip run",
              file=sys.stderr)
        return None
    devs = devs[:chips]
    kind = devs[0].device_kind
    peaks = rooflines.peaks_for(ov.get("peaks_as", kind))
    from ceph_tpu.ops import compile_cache
    from ceph_tpu.ops import pipeline as ec_pipeline
    compile_cache.place()
    cdir = compile_cache.directory()
    entries = len(os.listdir(cdir)) if cdir and os.path.isdir(cdir) else 0
    log(f"cell {name} seed {seed} seconds {seconds} trace {int(traced)}")
    log(f"platform {devs[0].platform} device_kind {kind!r} count "
        f"{len(devs)}")
    log(f"compile cache {cdir} entries {entries}")
    watch = CompileWatch()
    # every program JAX compiles or fetches is named on stderr
    jax.config.update("jax_log_compiles", True)

    extra_conf = dict(ov.get("conf", {}))
    if traced:
        extra_conf["osd_op_history_size"] = TRACED_HISTORY
    pool = load_module(here, "pools", cell.config["pool_kind"])
    reference = (load_module(here, "references", cell.config["reference"])
                 if cell.config.get("reference") else None)
    dep = cl.Deployment(cell.config, pool, extra_conf)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        log(f"store {cell.config['store']} at {dep.store_dir} on "
            f"{cl.fs_type(dep.store_dir)}")
        dep.open_pool()
        log(f"cluster up and pool settled {time.monotonic() - t_start:.1f}s "
            "after process start")
        payloads = Payloads(seed, dep.object_bytes,
                            int(ov.get("payload_bases", 16)))
        tracer = None
        if traced:
            start = min(TRACE_START_S, seconds / 3.0)
            tracer = Tracer(dep, start, min(TRACE_LEN_S, seconds / 3.0),
                            trace_dir)
        ctx = Ctx(dep, payloads, params, seed, log, tracer)
        gen = load_module(here, "generators", cell.traffic["generator"])
        warmers = [(w, load_module(here, "warmers", w))
                   for w in ov.get("warm", cell.traffic["warm"])]
        clients = int(params["clients"])
        # compiled programs first, then the mix's data, then what
        # needs that data
        for warm, mod in warmers:
            if not mod.NEEDS_DATA:
                log(f"warm {warm} {mod.warm(dep, clients)}")
        log(f"prepare {gen.prepare(ctx)}")
        for warm, mod in warmers:
            if mod.NEEDS_DATA:
                log(f"warm {warm} {mod.warm(dep, clients)}")
        if not ec_pipeline.wait_warmups(cl.WARM_BOUND):
            raise cl.CheckFailed("warm-up threads still compiling")

        window = gen.run(ctx, seconds)
        t_open, t_close = window["t_open"], window["t_close"]
        setup_s = t_open - t_start
        done = [op for op in window["ops"] if t_open <= op[2] <= t_close]
        counts = dict(sorted(Counter(op[0] for op in done).items()))
        attempted = len(done)
        failed = sum(1 for op in done if not op[3])
        log(f"window {t_close - t_open:.3f}s after {window['ramp_s']:.3f}s "
            f"ramp; ops completed {counts}, failed {failed}; latency "
            f"samples {attempted} (failed ops included): p50 "
            f"{latency_ms(done, 50):.1f} ms, p95 {latency_ms(done, 95):.1f} ms")
        for err in window["errors"][:5]:
            log(f"op error {err}")
        delta = cl.delta(ctx.c_open, ctx.c_close)
        log(f"counters over the window {delta}")

        # -- correct: every number compared, beside its limit --
        verdict = gen.verify(ctx, window)
        comparisons = [("window_mismatches", len(window["bad"]), "<=", 0)]
        for b in window["bad"][:5]:
            log(f"mismatch {b}")
        comparisons += verdict["comparisons"]
        if reference is not None:
            comparisons += check_stored(dep, reference, payloads,
                                        verdict["stored_objects"], log)
        else:
            log("stored state not compared: the configuration names no "
                "reference")
        if "after_stored_check" in verdict:
            comparisons += verdict["after_stored_check"]()
        end = dep.counters()
        moved = {k: end[k] for k in cl.ZERO_COUNTERS if end[k]}
        comparisons.append(("zero_counters_moved", len(moved), "<=", 0))
        if moved:
            log(f"zero counters moved {moved}")
        comparisons.append(("device_degraded_codecs",
                            end["device_degraded"], "<=", 0))
        # what the mix says the window's counters have to show (that
        # the device served, where the cell is there to drive it)
        for counter, sense, limit in cell.traffic.get("window_counters", []):
            comparisons.append((f"{counter}_in_window", delta[counter],
                                sense, limit))
        comparisons.append(("compiles_in_window",
                            watch.between(t_open, t_close), "<=", 0))
        correct = True
        for what, value, sense, limit in comparisons:
            ok = value <= limit if sense == "<=" else value >= limit
            correct = correct and ok
            log(f"check {what} = {value} limit {sense} {limit} "
                f"{'ok' if ok else 'FAILED'}")

        metrics: dict = {}
        if not traced:
            for m, spec in cell.end_to_end():
                v = end_to_end_value(spec, done, t_close - t_open, setup_s)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs),
                  "memory_peak_bytes": max(
                      (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)}
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if traced:
            rd = Readings(dep, log)
            rd.peaks = peaks
            rd.counter_delta = delta
            rd.window_ops = done
            rd.op_docs = [d for d in dep.historic_ops()
                          if t_open <= d["mstart"] <= t_close]
            log(f"op docs in the window {len(rd.op_docs)}")
            if tracer.error is None and tracer.c1 is not None:
                rd.slice_delta = cl.delta(tracer.c0, tracer.c1)
                xplane = trace.find_xplane(trace_dir)
                rd.trace = trace.reduce(xplane, tracer.traced_s, platform)
                device["busy_s"] = rd.trace["busy_s"]
                device["window_s"] = rd.trace["window_s"]
                result["breakdown"] = rd.trace["breakdown"]
            for m, spec in cell.per_layer():
                reader = load_module(here, "readers", spec["reader"])
                v = reader.read(rd, spec["params"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
                else:
                    log(f"metric {m['name']}: nothing to read")
        return result
    finally:
        dep.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ec_pipeline.wait_warmups(60.0)
