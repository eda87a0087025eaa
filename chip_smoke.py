#!/usr/bin/env python3
"""Chip smoke: the k=8 m=3 ``plugin=tpu`` write/read/scrub/repair path
on one TPU chip, in one process.

    python chip_smoke.py [--seed N]          # one chip (what CI runs)
    python chip_smoke.py --chips 4           # builder-run: the lanes

Every step prints ONE JSON line ``{"step": ..., "ok": ..., "seconds":
..., evidence}``; the first failing step prints its evidence and the
process exits non-zero at once.  Steps run from the inside out (device,
kernels, plugin, cluster) so the first ``"ok": false`` locates the
fault.  After the last step, and only if every step passed, the LAST
line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

There is no option that lets this command pass without a TPU.  The
steps are plain functions taking their sizes as arguments, so the tests
(tests/test_chip_smoke.py) drive the same logic at a tiny size on the
CPU platform, where the XLA formulation serves and the same counters
move.

How the device is reached: the served path enters the device only
after a background warm-up compiled the (kernel, padded shape, device)
it needs, and until then the host serves, correctly and quietly.  So
each phase first DRIVES its normal operation until the counter that
proves the device served it has moved (bounded for a cold compile
cache, seconds waited are printed, a failed warm-up fails the step
with the warm-up's own error), and only then opens the asserted
window, in which the host must not serve at all.  Routing is pinned
with the pool profile's existing ``host_cutover`` key: bring-up has to
exercise the device whatever the measured router would choose.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

K, M = 8, 3
STRIPE_UNIT = 4096
PROFILE = {"k": str(K), "m": str(M), "technique": "reed_sol_van"}
# the host oracle plugin: same generator matrix as plugin=tpu, native
# host region math only (the isa plugin's reed_sol_van is ISA-L's own
# generator — a different code, not comparable byte for byte)
ORACLE = ("jerasure", dict(PROFILE, backend="host"))
# what must stay 0 from the first device touch to the last line
ZERO_COUNTERS = ("device_errors", "quarantines", "drained_to_host",
                 "warm_failures", "result_timeouts",
                 "devset_errors", "route_errors")
# generous for a cold compile cache: tens of seconds per shape
WARM_BOUND = 600.0


class StepFailed(Exception):
    """A step's check did not hold; `evidence` rides its JSON line."""

    def __init__(self, why: str, **evidence):
        super().__init__(why)
        self.evidence = evidence


def check(cond, why: str, **evidence) -> None:
    if not cond:
        raise StepFailed(why, **evidence)


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def run_steps(steps, out=emit) -> bool:
    """Run (name, fn) steps in order; fn() returns its evidence dict or
    raises.  One line per step; stops at the first failure."""
    for name, fn in steps:
        t0 = time.perf_counter()
        try:
            evidence = fn() or {}
        except Exception as e:
            line = {"step": name, "ok": False,
                    "seconds": round(time.perf_counter() - t0, 3),
                    "error": f"{type(e).__name__}: {e}"}
            line.update(getattr(e, "evidence", {}))
            line.update(_failure_evidence())
            out(line)
            return False
        line = {"step": name, "ok": True,
                "seconds": round(time.perf_counter() - t0, 3)}
        line.update(evidence)
        out(line)
    return True


def _failure_evidence() -> dict:
    """The device plane's own counters, if it got far enough to import."""
    mod = sys.modules.get("ceph_tpu.ops.pipeline")
    if mod is None:
        return {}
    try:
        st = mod.stats()
    except Exception as e:       # evidence gathering must not mask the step
        return {"pipeline_stats_error": repr(e)}
    keep = ZERO_COUNTERS + ("dev_dispatches", "host_dispatches",
                            "last_warm_error", "warmups_inflight",
                            "stalled", "devices")
    return {"pipeline": {k: st.get(k) for k in keep}}


def final_line(platform: str, kind: str, count: int) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}})


# -- shared helpers -----------------------------------------------------------


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, *stream])


def _stats() -> dict:
    from ceph_tpu.ops import pipeline as ec_pipeline
    return ec_pipeline.stats()


def _delta(before: dict, after: dict, keys) -> dict:
    return {k: after[k] - before[k] for k in keys}


def windowed(op, keys=("dev_dispatches", "host_dispatches")) -> dict:
    """Run op(); the pipeline counters' delta across it."""
    before = _stats()
    op()
    return _delta(before, _stats(), keys)


def device_only(delta: dict) -> bool:
    """The device served the window and the host served none of it."""
    return delta["dev_dispatches"] > 0 and delta["host_dispatches"] == 0


def _check_clean(st: dict, platform: str) -> None:
    """The `throughout` invariants: nothing degraded, every lane a real
    device of the expected platform, no failed warm-up."""
    bad = {k: st[k] for k in ZERO_COUNTERS if st[k]}
    check(not bad, f"device plane degraded: {bad}",
          last_warm_error=st["last_warm_error"])
    check(not st["stalled"], "pipeline latched host-only (stalled)")
    for idx, lane in st["devices"].items():
        check(lane["device"] != "default",
              f"lane {idx} is the no-device pseudo-lane")
        check(platform == "cpu" or "tpu" in lane["device"].lower(),
              f"lane {idx} device {lane['device']!r} is not a TPU")
        check(not lane["quarantined"], f"lane {idx} quarantined")


def drive_until(op, served, bound: float, what: str) -> float:
    """Drive `op()` until `served()` (the counter that proves the
    device served it moved).  Returns seconds waited.  A failed
    warm-up ends the wait with the warm-up's own error."""
    t0 = time.monotonic()
    w0 = _stats()["warm_failures"]
    tries = 0
    while True:
        op()
        tries += 1
        st = _stats()
        check(st["warm_failures"] == w0,
              f"warm-up failed while waiting for {what}: "
              f"{st['last_warm_error']}")
        if served():
            return round(time.monotonic() - t0, 3)
        check(time.monotonic() - t0 < bound,
              f"{what}: the device never served within {bound:.0f}s "
              f"({tries} tries)", warmups_inflight=st["warmups_inflight"])
        time.sleep(0.05)


def wait_warm(ready, bound: float, what: str) -> float:
    """Poll `ready()` (asks the backend for compiled fns, kicking off
    their warm-ups) until true; fails with the warm-up's own error."""
    t0 = time.monotonic()
    w0 = _stats()["warm_failures"]
    while not ready():
        st = _stats()
        check(st["warm_failures"] == w0,
              f"warm-up failed while warming {what}: "
              f"{st['last_warm_error']}")
        check(time.monotonic() - t0 < bound,
              f"{what} not warm within {bound:.0f}s",
              warmups_inflight=st["warmups_inflight"])
        time.sleep(0.05)
    return round(time.monotonic() - t0, 3)


def _host_crcs(chunks: np.ndarray) -> np.ndarray:
    """(..., L) uint8 -> (...) uint32 through the host CRC32C."""
    from ceph_tpu.ops import crc32c
    flat = np.ascontiguousarray(chunks).reshape(-1, chunks.shape[-1])
    return crc32c.crc32c_batch(flat).reshape(chunks.shape[:-1])


# -- step 0: device -----------------------------------------------------------


def step_device(platform: str = "tpu", count: int = 1) -> dict:
    import jax

    devs = jax.devices()
    check(devs[0].platform == platform,
          f"jax.devices()[0].platform is {devs[0].platform!r}, "
          f"need {platform!r}: this is not a chip run")
    check(len(devs) == count, f"need {count} device(s), jax reports "
          f"{len(devs)}")
    from ceph_tpu import native
    from ceph_tpu.ops import compile_cache
    compile_cache.place()
    tier = ("extension" if native.get_ext() is not None else
            "ctypes" if native.get_lib() is not None else "python")
    cdir = compile_cache.directory()
    entries = len(os.listdir(cdir)) if cdir and os.path.isdir(cdir) else 0
    return {"jax": jax.__version__, "platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs),
            "compile_cache_dir": cdir, "compile_cache_entries": entries,
            "compile_cache_from_env":
                bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "native_tier": tier, "crc32c_hw": native.crc32c_hw()}


# -- step 1: kernels ----------------------------------------------------------


def _timed_first_call(fn, *args):
    """(result as numpy, seconds of the first call = compile + run,
    seconds of the second call)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    return out, round(t1 - t0, 3), round(t2 - t1, 4)


def step_kernels(platform: str = "tpu", seed: int = 0,
                 fused_shapes=((128, K, 4096), (8, K, 1 << 20)),
                 decode_shape=(128, K, 4096),
                 crc_shapes=((1408, 4096), (16, 512 << 10))) -> dict:
    """Each kernel the served path dispatches, on the device, bit-exact
    against the host oracle (gf.encode_np, crc32c)."""
    import jax

    from ceph_tpu.erasure.matrix_codec import TpuBackend
    from ceph_tpu.erasure.registry import registry
    from ceph_tpu.ops import ec_kernels, gf, pallas_ec

    matrix = gf.reed_sol_van_matrix(K, M)
    backend = TpuBackend()
    out: dict = {"kernels": []}
    for i, shape in enumerate(fused_shapes):
        B, k, L = shape
        data = _rng(seed, 1, i).integers(0, 256, shape, dtype=np.uint8)
        fn = backend._fn("fused", matrix, L)      # what the pipeline serves
        if platform == "tpu":
            check(jax.default_backend() == "tpu" and pallas_ec.supports(L),
                  "pallas would resolve interpret=True here")
            hlo = jax.jit(fn).lower(
                jax.ShapeDtypeStruct(shape, np.uint8)).as_text()
            check("tpu_custom_call" in hlo,
                  f"fused {shape}: no tpu_custom_call in the lowering — "
                  "not the Pallas kernel")
        (parity, crcs), first, second = _timed_first_call(fn, data)
        parity, crcs = np.asarray(parity), np.asarray(crcs)
        want_p = np.stack([gf.encode_np(matrix, s) for s in data])
        check(np.array_equal(parity, want_p), f"fused {shape}: parity "
              "differs from gf.encode_np")
        want_c = _host_crcs(np.concatenate([data, want_p], axis=1))
        check(np.array_equal(crcs, want_c), f"fused {shape}: CRCs differ "
              "from host crc32c")
        out["kernels"].append({"kernel": "fused_encode_crc",
                               "shape": list(shape),
                               "first_call_s": first, "second_call_s": second})

    # the decode fn TpuBackend serves for a two-shard loss
    codec = registry.factory(*ORACLE)
    want, lost = [0, 1], {0, 1}
    present = codec.minimum_to_decode(
        want, [c for c in range(K + M) if c not in lost])
    rows = codec._decode_rows(want, present)
    B, k, L = decode_shape
    data = _rng(seed, 2).integers(0, 256, decode_shape, dtype=np.uint8)
    full = np.concatenate(
        [data, np.stack([gf.encode_np(matrix, s) for s in data])], axis=1)
    surv = np.ascontiguousarray(full[:, present])
    fn = backend._fn("bytes", rows)
    rebuilt, first, second = _timed_first_call(fn, surv)
    rebuilt = np.asarray(rebuilt)
    check(np.array_equal(rebuilt, np.stack(
        [gf.encode_np(rows, s) for s in surv])),
        "decode differs from gf.encode_np(rows)")
    check(np.array_equal(rebuilt, data[:, want]),
          "decode did not rebuild the lost data shards")
    out["kernels"].append({"kernel": "decode_bytes",
                           "shape": list(decode_shape), "lost": want,
                           "first_call_s": first, "second_call_s": second})

    # the scrub CRC fold pipeline._warm_crc serves: stripe-chunk rows,
    # and the whole 512 KiB shard files a 4 MiB object's scrub folds
    for i, crc_shape in enumerate(crc_shapes):
        rowsd = _rng(seed, 3, i).integers(0, 256, crc_shape, dtype=np.uint8)
        fn = ec_kernels.make_crc_fn(crc_shape[-1])
        got, first, second = _timed_first_call(fn, rowsd)
        check(np.array_equal(np.asarray(got), _host_crcs(rowsd)),
              f"scrub CRC {crc_shape} differs from host crc32c")
        out["kernels"].append({"kernel": "scrub_crc",
                               "shape": list(crc_shape),
                               "first_call_s": first,
                               "second_call_s": second})
    out["interpret"] = platform != "tpu"
    return out


# -- step 2: plugin -----------------------------------------------------------


def step_plugin(platform: str = "tpu", seed: int = 0, stripes: int = 128,
                L: int = STRIPE_UNIT, bound: float = WARM_BOUND) -> dict:
    """registry.factory("tpu") through ops/pipeline: one object's
    stripes encoded, two lost shards decoded, equal byte for byte to
    the host-only jerasure plugin, served by the device."""
    from ceph_tpu.erasure.registry import registry

    tpu = registry.factory("tpu", dict(PROFILE, host_cutover="1"))
    host = registry.factory(*ORACLE)
    data = _rng(seed, 10).integers(0, 256, (stripes, K, L), dtype=np.uint8)
    want_chunks, want_crcs = host.encode_stripes_with_crcs(data)

    last: dict = {}

    def probe(op):
        """Drive op; remember whether the device alone served it."""
        def run():
            last["dev"] = device_only(windowed(op))
        return run

    def encode():
        last["enc"] = tpu.encode_stripes_with_crcs_async(data).result()

    sc = tpu.stat_counters()
    waited_enc = drive_until(probe(encode), lambda: last["dev"], bound,
                             "plugin encode")
    # asserted window: warm now, the host must not serve
    h0 = sc["host_stripe_passes"]
    d_enc = windowed(encode, ("dev_dispatches", "host_dispatches",
                              "bytes_h2d", "bytes_d2h"))
    chunks, crcs = last["enc"]
    check(np.array_equal(chunks, want_chunks),
          "encode differs from the host plugin")
    check(np.array_equal(crcs, want_crcs),
          "encode CRCs differ from the host plugin")
    check(device_only(d_enc) and sc["host_stripe_passes"] == h0,
          "encode window was not served by the device", delta=d_enc)

    lost = [1, K]                     # one data shard, one parity shard
    want = [1]
    present = tpu.minimum_to_decode(
        want, [c for c in range(K + M) if c not in lost])
    surv = np.ascontiguousarray(want_chunks[:, present])
    want_dec = np.stack([
        np.stack([host.decode_chunks(
            want, {p: s[j] for j, p in enumerate(present)})[c]
            for c in want]) for s in surv])

    def decode():
        last["dec"] = tpu.decode_batch_async(want, present, surv).result()

    waited_dec = drive_until(probe(decode), lambda: last["dev"], bound,
                             "plugin decode")
    d_dec = windowed(decode)
    check(np.array_equal(last["dec"], data[:, want]),
          "decode did not rebuild the lost shard")
    check(np.array_equal(last["dec"], want_dec),
          "decode differs from the host plugin")
    check(device_only(d_dec),
          "decode window was not served by the device", delta=d_dec)
    _check_clean(_stats(), platform)
    return {"routing": "pinned", "stripes": stripes, "chunk": L,
            "waited_encode_s": waited_enc, "waited_decode_s": waited_dec,
            "encode_window": d_enc, "decode_window": d_dec,
            "codec": dict(sc)}


# -- step 3: cluster ----------------------------------------------------------

CLUSTER_CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    # the reference's default: a down OSD is not marked out (and no
    # rebuild starts) inside the degraded-read window; the repair
    # phase marks the one that stays dead out itself
    "mon_osd_down_out_interval": 600.0,
}


def _retry(cluster, fn, window: float = 120.0):
    from ceph_tpu.client import RadosError
    end = time.time() + window
    while True:
        try:
            return fn()
        except RadosError:
            if time.time() > end:
                raise
            cluster.tick(0.3)


def _write_all(io, payloads: dict, inflight: int) -> None:
    """write_full every object with `inflight` ops outstanding."""
    pending: list = []

    def reap():
        c = pending.pop(0)
        c.wait_for_complete(120.0)
        c.result()

    for name, data in payloads.items():
        pending.append(io.aio_write_full(name, data))
        if len(pending) >= inflight:
            reap()
    while pending:
        reap()


def _pool_pgs(cluster, pool_id: int) -> dict:
    """pgid -> (acting, primary PG object) for one pool."""
    m = cluster.leader().osdmon.osdmap
    out = {}
    for pgid in m.all_pgs():
        if pgid.pool != pool_id:
            continue
        _up, acting = m.pg_to_up_acting_osds(pgid)
        primary = next(o for o in acting if o >= 0)
        out[pgid] = (list(acting), cluster.osds[primary].pgs[pgid])
    return out


def _deep_scrub(pgs: dict) -> dict:
    checked, inconsistent = 0, []
    for _pgid, (_acting, pg) in sorted(pgs.items(), key=lambda e: str(e[0])):
        r = pg.scrub(deep=True)
        checked += r["checked"]
        inconsistent += r["inconsistent"]
    return {"checked": checked, "inconsistent": inconsistent}


def _health(rados) -> str:
    rv, out, _ = rados.mon_command({"prefix": "health"})
    return out if isinstance(out, str) else json.dumps(out, default=str)


def step_cluster(platform: str = "tpu", seed: int = 0, n_objects: int = 64,
                 object_bytes: int = 4 << 20, inflight: int = 16,
                 pg_num: int = 8, prod_objects: int = 4,
                 bound: float = WARM_BOUND, conf: dict | None = None) -> dict:
    """MiniCluster(1 mon, 13 OSDs, blockstore) + a k=8 m=3 tpu pool:
    write / read / deep scrub / degraded read / recover.  `conf` adds
    daemon options (the CPU tests cap the virtual devices to one lane,
    the shape of a one-chip host)."""
    from ceph_tpu.utils.config import Config
    from ceph_tpu.vstart import MiniCluster

    store_dir = tempfile.mkdtemp(prefix="chip_smoke_store_")
    cluster = MiniCluster(num_mons=1, num_osds=K + M + 2,
                          conf=Config(dict(CLUSTER_CONF, **(conf or {}))),
                          store_kind="blockstore",
                          store_dir=store_dir).start()
    try:
        return _drive_cluster(cluster, platform, seed, n_objects,
                              object_bytes, inflight, pg_num, prod_objects,
                              bound)
    finally:
        cluster.stop()
        shutil.rmtree(store_dir, ignore_errors=True)


def _drive_cluster(cluster, platform, seed, n_objects, object_bytes,
                   inflight, pg_num, prod_objects, bound) -> dict:
    import jax

    from ceph_tpu.ops import ec_kernels
    from ceph_tpu.ops import pipeline as ec_pipeline
    from ceph_tpu.store import Transaction

    out: dict = {"routing": "pinned", "osds": K + M + 2, "pg_num": pg_num,
                 "objects": n_objects, "object_bytes": object_bytes,
                 "inflight": inflight, "store": "blockstore"}
    rados = cluster.client()
    rados.create_ec_pool(
        "smoke", "k8m3dev",
        dict(PROFILE, plugin="tpu", host_cutover="1"), pg_num=pg_num)
    io = rados.open_ioctx("smoke")
    _retry(cluster, lambda: io.write_full("settle", b"s"))
    io.remove_object("settle")
    payloads = {
        f"obj{i:03d}": _rng(seed, 20, i).integers(
            0, 256, object_bytes, dtype=np.uint8).tobytes()
        for i in range(n_objects)}
    S = -(-object_bytes // (K * STRIPE_UNIT))      # stripes per object

    # ---- write: wait for the device, then the asserted window ----
    warm = {f"warm{i:02d}": payloads[f"obj{i % n_objects:03d}"]
            for i in range(inflight)}
    s_begin = _stats()
    waited = drive_until(
        lambda: _write_all(io, warm, inflight),
        lambda: _stats()["dev_dispatches"] > s_begin["dev_dispatches"],
        bound, "cluster write")
    # every (primary's codec, batch bucket) the window can meet: one
    # object alone, or as many as one dispatch coalesces
    cap = ec_pipeline.get().max_batch
    buckets = sorted({ec_pipeline.next_bucket(S * j)
                      for j in range(1, max(1, cap // S) + 1)})
    pgs = _pool_pgs(cluster, io.pool_id)
    codecs = [pg.osd.get_ec_codec(pg.pool) for _a, pg in pgs.values()]
    codecs = list({id(c): c for c in codecs}.values())
    devices = jax.devices()

    def fused_ready() -> bool:
        return all(
            c.backend.fused_fn_if_ready(
                c.coding_matrix, (b, K, STRIPE_UNIT), d) is not None
            for c in codecs for b in buckets for d in devices)

    waited_all = wait_warm(fused_ready, bound, "every primary's encode fn")
    for name in warm:
        io.remove_object(name)
    s0, c0 = _stats(), _codec_counters(cluster)
    t0 = time.perf_counter()
    _write_all(io, payloads, inflight)
    write_s = time.perf_counter() - t0
    s1, c1 = _stats(), _codec_counters(cluster)
    d_w = _delta(s0, s1, ("dev_dispatches", "host_dispatches", "bytes_h2d",
                          "bytes_d2h", "stripes", "ops"))
    codec_now = _delta(c0, c1, tuple(c0))
    check(d_w["dev_dispatches"] >= 1 and d_w["bytes_h2d"] > 0
          and d_w["bytes_d2h"] > 0, "write window: device counters did not "
          "move", delta=d_w)
    check(d_w["host_dispatches"] == 0, "write window: the host served "
          f"{d_w['host_dispatches']} dispatch(es)", delta=d_w)
    check(codec_now["host_stripe_passes"] == 0
          and codec_now["device_stripe_passes"] >= n_objects,
          "write window: codec pass counters", codec=codec_now)
    # parity-only readback: per padded stripe, m*L parity + 4*(k+m) CRC
    # bytes come down for k*L bytes up
    per_up = K * STRIPE_UNIT
    padded = d_w["bytes_h2d"] // per_up
    check(d_w["bytes_h2d"] % per_up == 0 and d_w["bytes_d2h"] ==
          ec_kernels.encode_readback_bytes(padded, K, M, STRIPE_UNIT),
          "write window: bytes_d2h is not the parity-only readback of "
          "what went up", delta=d_w)
    _check_clean(s1, platform)
    out["write"] = {"waited_first_dev_s": waited,
                    "waited_all_warm_s": waited_all, "buckets": buckets,
                    "codecs": len(codecs), "seconds": round(write_s, 3),
                    "window": d_w, "codec": codec_now}

    # ---- read back through the client ----
    t0 = time.perf_counter()
    for name, want in payloads.items():
        check(io.read(name) == want, f"read {name} differs from the seed")
    out["read"] = {"seconds": round(time.perf_counter() - t0, 3),
                   "objects": n_objects}

    # ---- deep scrub ----
    shard_files = n_objects * (K + M)

    last: dict = {}

    def scrub_pass():
        last["delta"] = windowed(
            lambda: last.update(scrub=_deep_scrub(pgs)))

    waited = drive_until(scrub_pass, lambda: device_only(last["delta"]),
                         bound, "deep scrub")
    scrub_pass()
    r = dict(last["scrub"], delta=last["delta"])
    check(r["checked"] == shard_files and not r["inconsistent"],
          f"clean deep scrub: checked {r['checked']} of {shard_files} "
          f"shard files, {len(r['inconsistent'])} inconsistent",
          inconsistent=r["inconsistent"][:4])
    check(device_only(r["delta"]),
          "scrub window was not served by the device", delta=r["delta"])
    # corrupt one shard under the store: the next scrub must flag
    # exactly that shard file, so the clean scrub was not vacuous
    victim_obj = "obj000"
    m = cluster.leader().osdmon.osdmap
    vpg = m.object_to_pg(io.pool_id, victim_obj)
    acting, pg = pgs[vpg]
    holder = cluster.osds[acting[0]]
    holder.store.apply_transaction(Transaction().write(
        pg.cid, f"{victim_obj}.s0", 0, b"\xff" * 16))
    bad = _deep_scrub({vpg: pgs[vpg]})["inconsistent"]
    check([b["object"] for b in bad] == [f"{victim_obj}.s0"],
          "corrupted shard not flagged exactly", inconsistent=bad[:4])
    # put the object right again (a client rewrite re-encodes it)
    io.write_full(victim_obj, payloads[victim_obj])
    check(not _deep_scrub({vpg: pgs[vpg]})["inconsistent"],
          "rewritten object still inconsistent")
    _check_clean(_stats(), platform)
    out["scrub"] = {"waited_s": waited, "checked": r["checked"],
                    "objects": n_objects, "inconsistent": 0,
                    "window": r["delta"], "corruption_flagged": True,
                    "cache_hits": _stats()["cache_hit"]}

    # ---- repair: lose two non-primary OSDs, read degraded ----
    primaries = {acting[0] for acting, _pg in pgs.values()}
    member = {o for acting, _pg in pgs.values() for o in acting if o >= 0}
    victims = sorted(member - primaries)[:2]
    check(len(victims) == 2, "no two non-primary acting OSDs to kill",
          primaries=sorted(primaries))
    for v in victims:
        cluster.kill_osd(v)
        cluster.wait_for_osd_down(v, timeout=120)

    # a degraded read rebuilds whichever data shards are not among the
    # first k answers: 1..m of them.  The decode matrix is an operand
    # of one executable per (rows shape, batch shape), so warming one
    # pattern of each row count warms every pattern the window meets.
    bucket = ec_pipeline.next_bucket(S)

    def decode_ready() -> bool:
        return all(
            c.backend.device_fn_if_ready(
                "bytes", c._decode_rows(list(range(n)),
                                        list(range(n, n + K))), (),
                (bucket, K, STRIPE_UNIT), d) is not None
            for c in codecs for n in range(1, M + 1) for d in devices)

    waited_all = wait_warm(decode_ready, bound, "every decode row count")

    def read_all(what: str):
        for name, want in payloads.items():
            check(_retry(cluster, lambda n=name: io.read(n)) == want,
                  f"{what} read {name} differs from the seed")

    def degraded_pass():
        last["delta"] = windowed(lambda: read_all("degraded"))

    waited = drive_until(degraded_pass, lambda: device_only(last["delta"]),
                         bound, "degraded read")
    t0 = time.perf_counter()
    degraded_pass()
    d_r = last["delta"]
    check(device_only(d_r),
          "repair window was not served by the device", delta=d_r)
    s_rep = _stats()
    _check_clean(s_rep, platform)
    health = _health(rados)
    check("EC device degraded" not in health, "health: EC device degraded",
          health=health[:300])
    out["repair"] = {"killed": victims, "waited_all_warm_s": waited_all,
                     "waited_s": waited,
                     "degraded_read_s": round(time.perf_counter() - t0, 3),
                     "window": d_r}
    # asserted windows end here: bring one OSD back, mark the other
    # out so its shards rebuild elsewhere, wait for active+clean
    t0 = time.perf_counter()
    cluster.restart_osd(victims[0], timeout=300, wait_clean=False)
    cluster.mark_osd_out(victims[1])
    cluster.wait_for_clean(timeout=900)
    out["repair"]["recover_clean_s"] = round(time.perf_counter() - t0, 3)
    read_all("post-recovery")

    # ---- perf dump + health, as an operator would read them ----
    dump = next(iter(cluster.osds.values())).asok.execute("perf dump")
    check(all(not c.get("device_degraded")
              for c in dump["ec_codecs"].values()),
          "perf dump: a codec is device_degraded", codecs=dump["ec_codecs"])
    _check_clean(dump["ec_pipeline"], platform)
    out["perf_dump"] = {k: dump["ec_pipeline"][k] for k in (
        "dispatches", "dev_dispatches", "host_dispatches", "bytes_h2d",
        "bytes_d2h", "mean_batch_size", "warm_failures", "result_timeouts",
        "warmups_inflight") + ZERO_COUNTERS[:3]}

    # ---- production routing: reported, not asserted ----
    rados.create_ec_pool("smoke-prod", "k8m3prod",
                         dict(PROFILE, plugin="tpu"), pg_num=1)
    iop = rados.open_ioctx("smoke-prod")
    _retry(cluster, lambda: iop.write_full("settle", b"s"))
    b = _stats()
    for rnd in range(4):     # the router samples host first, then device
        for i in range(prod_objects):
            iop.write_full(f"prod{i}", payloads[f"obj{i % n_objects:03d}"])
        ec_pipeline.wait_warmups(bound)
    d_p = _delta(b, _stats(), ("dev_dispatches", "host_dispatches"))
    routing = {}
    for osd in cluster.osds.values():
        c = osd._ec_codecs.get("k8m3prod")
        if c is not None and c.backend.perf_snapshot():
            routing[f"osd.{osd.whoami}"] = c.backend.perf_snapshot()
    out["production_routing"] = {
        "asserted": False, "writes": 4 * prod_objects, "window": d_p,
        "served_by": ("device" if d_p["dev_dispatches"]
                      > d_p["host_dispatches"] else "host"),
        "ema": routing}
    return out


def _codec_counters(cluster) -> dict:
    tot = {"device_stripe_passes": 0, "host_stripe_passes": 0,
           "device_degraded": 0}
    for osd in cluster.osds.values():
        c = osd._ec_codecs.get("k8m3dev")
        if c is not None:
            for k in tot:
                tot[k] += int(c.stat_counters().get(k, 0))
    return tot


# -- four chips: the lanes (builder-run, --chips 4) --------------------------


def step_lanes(platform: str = "tpu", seed: int = 0, n_lanes: int = 4,
               stripes: int = 128, L: int = STRIPE_UNIT, batches: int = 32,
               threads: int = 8, bound: float = WARM_BOUND) -> dict:
    """A plugin-level stream wide enough that every lane dispatches and
    uploads for itself, outputs on the lanes' own devices, bit-exact."""
    import jax

    from ceph_tpu.erasure.registry import registry
    from ceph_tpu.ops import pipeline as ec_pipeline

    devices = jax.devices()[:n_lanes]
    check(len(devices) == n_lanes, f"need {n_lanes} devices")
    tpu = registry.factory("tpu", dict(PROFILE, host_cutover="1"))
    host = registry.factory(*ORACLE)
    datas = [_rng(seed, 30, i).integers(0, 256, (stripes, K, L),
                                        dtype=np.uint8)
             for i in range(batches)]
    wants = [host.encode_stripes_with_crcs(d) for d in datas]

    # every padded shape a stream of S-stripe submissions can put on
    # a lane: 1..cap//S of them coalesced, whole or row-split over
    # 2..n idle lanes
    pipe = ec_pipeline.get()
    rows = {-(-stripes * j // n)
            for j in range(1, max(1, pipe.max_batch // stripes) + 1)
            for n in range(1, n_lanes + 1)}
    shapes = sorted({(ec_pipeline.next_bucket(r), K, L) for r in rows})
    waited_all = wait_warm(
        lambda: all(tpu.backend.fused_fn_if_ready(
            tpu.coding_matrix, sh, d) is not None
            for sh in shapes for d in devices),
        bound, "every (bucket, lane) encode fn")

    # placement itself: the fn warm on device d computes on device d
    placed = []
    for d in devices:
        fn = tpu.backend.fused_fn_if_ready(tpu.coding_matrix,
                                           (stripes, K, L), d)
        parity, crcs = fn(jax.device_put(datas[0], d))
        check(parity.devices() == {d} and crcs.devices() == {d},
              f"outputs for {d} live on {parity.devices()}")
        check(np.array_equal(np.asarray(parity), wants[0][0][:, K:]),
              f"parity on {d} differs from the host plugin")
        placed.append(str(d))

    def stream():
        results = [None] * batches

        def worker(w):
            for i in range(w, batches, threads):
                results[i] = tpu.encode_stripes_with_crcs_async(
                    datas[i]).result()
        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stream.results = results

    def lanes_served() -> bool:
        b, a = stream.before, _stats()
        return (a["host_dispatches"] == b["host_dispatches"]
                and len(a["devices"]) == n_lanes and all(
                    lane["dispatches"] > b["devices"].get(
                        i, {"dispatches": 0})["dispatches"]
                    for i, lane in a["devices"].items()))

    def stream_probe():
        stream.before = _stats()
        stream()

    waited = drive_until(stream_probe, lanes_served, bound,
                         "every lane dispatching")
    s0 = _stats()
    stream()
    s1 = _stats()
    for got, want in zip(stream.results, wants):
        check(np.array_equal(got[0], want[0])
              and np.array_equal(got[1], want[1]),
              "stream encode differs from the host plugin")
    lanes = {}
    for i, lane in s1["devices"].items():
        before = s0["devices"][i]
        lanes[i] = {"device": lane["device"],
                    "dispatches": lane["dispatches"] - before["dispatches"],
                    "bytes_h2d": lane["bytes_h2d"] - before["bytes_h2d"]}
        check(lanes[i]["dispatches"] > 0 and lanes[i]["bytes_h2d"] > 0,
              f"lane {i} did not dispatch/upload in the window", lanes=lanes)
    check(len({v["device"] for v in lanes.values()}) == n_lanes,
          "lanes do not map to distinct devices", lanes=lanes)
    d = _delta(s0, s1, ("dev_dispatches", "host_dispatches",
                        "split_dispatches"))
    check(d["host_dispatches"] == 0, "the host served in the window",
          delta=d)
    _check_clean(s1, platform)
    return {"routing": "pinned", "placed_on": placed,
            "shapes": [list(sh) for sh in shapes],
            "waited_all_warm_s": waited_all, "waited_s": waited,
            "window": d, "lanes": lanes}


# -- entry --------------------------------------------------------------------


def shutdown(bound: float = WARM_BOUND) -> dict:
    """Wait out every warm-up still compiling, then stop the pipeline:
    the process must be able to return from main normally."""
    from ceph_tpu.ops import pipeline as ec_pipeline
    t0 = time.perf_counter()
    check(ec_pipeline.wait_warmups(bound),
          "warm-up threads still compiling at exit")
    st = _stats()
    ec_pipeline.get().stop()
    bad = {k: st[k] for k in ZERO_COUNTERS if st[k]}
    check(not bad, f"device plane degraded at exit: {bad}",
          last_warm_error=st["last_warm_error"])
    return {"waited_warmups_s": round(time.perf_counter() - t0, 3),
            "totals": {k: st[k] for k in (
                "dispatches", "dev_dispatches", "host_dispatches",
                "bytes_h2d", "bytes_d2h") + ZERO_COUNTERS}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the lanes phase on four chips "
                         "(builder-run)")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    dev: dict = {}

    def device():
        dev.update(step_device("tpu", args.chips))
        return dev

    if args.chips == 4:
        steps = [("device", device),
                 ("lanes", lambda: step_lanes("tpu", args.seed, 4))]
    else:
        steps = [("device", device),
                 ("kernels", lambda: step_kernels("tpu", args.seed)),
                 ("plugin", lambda: step_plugin("tpu", args.seed)),
                 ("cluster", lambda: step_cluster("tpu", args.seed))]
    steps.append(("shutdown", shutdown))
    if not run_steps(steps):
        return 1
    emit({"step": "total", "ok": True,
          "seconds": round(time.perf_counter() - t0, 3)})
    print(final_line(dev["platform"], dev["kind"], dev["count"]),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
