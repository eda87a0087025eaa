"""bench.py --smoke as a tier-1 gate: the benchmark's import surface,
plugin wiring and pipeline path are exercised on tiny CPU-safe sizes,
so bench bit-rot is caught here instead of on the slow rig run."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_runs_and_validates():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert lines, (f"no stdout from --smoke (rc={proc.returncode}):\n"
                   f"{proc.stderr[-3000:]}")
    out = json.loads(lines[-1])
    # per-gate asserts FIRST: when the smoke trips, the failure names
    # the gate (the bare returncode hides it behind a stderr tail)
    bad = sorted(k for k, v in out.items()
                 if k.endswith("_ok") and v is False)
    assert not bad, f"--smoke gates failed: {bad}\n{proc.stderr[-3000:]}"
    assert proc.returncode == 0, \
        f"--smoke failed:\n{proc.stderr[-3000:]}"
    assert out["metric"] == "bench_smoke"
    assert out["smoke"] is True
    assert out["ok"] is True            # pipelined == serial == oracle
    assert out["e2e_pipelined_gbs"] > 0
    assert out["e2e_serial_gbs"] > 0
    assert out["pipeline_dispatches"] >= 1
    # multichip surface: the smoke runs sharded on the forced
    # 8-device CPU mesh — placement, mega-batch splitting and the
    # one-chip quarantine drill all really executed
    assert out["devices"] == 8
    assert out["sharded_ok"] is True
    assert out["lanes_used"] >= 2
    assert out["split_dispatches"] >= 1
    assert out["quarantine_ok"] is True
    assert out["quarantines"] >= 1
    assert out["active_after_quarantine"] == 7
    # zero-copy host data path: the write pipeline (rope -> encode
    # staging -> shard-view fan-out -> store) stays within the copy
    # budget — a per-hop copy regression fails CI here
    assert out["copy_ok"] is True
    assert out["host_copies_per_write"] <= out["copy_budget"]
    # serving plane: the seeded mini load harness ran against a real
    # cluster — tail latency sane, zero errors, and the READ path
    # within its copy budget (read-side zero-copy regression gate)
    assert out["load_ok"] is True
    assert out["load_p99_ms"] is not None and out["load_p99_ms"] > 0
    assert out["load_errors"] == 0
    assert out["host_copies_per_read"] <= out["read_copy_budget"]
    # op tracing plane: the tracer-overhead gate ran the same seeded
    # round with tracing off and on — p99 and goodput within 5%, and
    # the traced round produced a per-phase breakdown (queue/execute
    # at minimum), so the plane is cheap enough to leave on
    assert out["trace_overhead_ok"] is True
    assert out["trace_p99_off_ms"] and out["trace_p99_on_ms"]
    assert out["trace_p99_on_ms"] <= out["trace_p99_off_ms"] * 1.05
    assert out["trace_phases"] and "queue" in out["trace_phases"]
    # serve-during-repair: the mini seeded recovery-storm gate — one
    # OSD kill + rebirth under open-loop load: zero client errors,
    # zero stale-byte reads (verify oracle), every recovery-blocked
    # op resumed (counter-balanced), the reserved pool's p99 bounded,
    # and recovery completing
    assert out["storm_ok"] is True
    assert out["storm_errors"] == 0
    assert out["storm_stale_reads"] == 0
    assert out["storm_blocked_ops"] == out["storm_unblocked_ops"]
    assert out["storm_p99_ms"] is not None
    assert out["storm_p99_ms"] < out["storm_p99_bound_ms"]
    assert out["storm_recovery_s"] is not None
    # log-authoritative peering: a full peering round exchanges log
    # BOUNDS only, so wall time at 10x the object count stays flat —
    # an O(objects) term creeping into info/election/recovery fails
    assert out["peering_flat_ok"] is True
    assert out["peering_ms_at_1x"] is not None
    assert out["peering_ms_at_10x"] is not None
    # front doors under fire: the mini mixed-door round (rados + S3 +
    # CephFS + RBD) rode one seeded schedule through a zone
    # partition, a secondary-gateway crash and an OSD kill — zero
    # errors, zero stale reads at every door, the two-zone ledger
    # clean (partitioned delete tombstoned, never resurrected), and
    # the sync agent backing off rather than wedging
    assert out["frontdoor_ok"] is True
    assert out["frontdoor_errors"] == 0
    assert out["frontdoor_stale_reads"] == 0
    assert out["frontdoor_zone_ledger_ok"] is True
    assert out["frontdoor_doors"] == ["cephfs", "rados", "rbd", "s3"]
    assert out["frontdoor_sync_errors"] > 0
    assert out["frontdoor_sync_backoff_secs"] > 0
    # async serving plane: 256 full client sessions held open at once
    # against an ms_type=async cluster — zero errors, tail bounded,
    # peak thread growth bounded by the storm's own driver pool (NOT
    # per-session threads), and zero thread/FD residue after every
    # session closed (connection-churn hygiene)
    assert out["conn_ok"] is True
    assert out["conn_sessions"] >= 256
    assert out["conn_errors"] == 0
    assert out["conn_p99_ms"] is not None
    assert out["conn_p99_ms"] < out["conn_p99_bound_ms"]
    assert out["conn_event_workers"] >= 1
    assert out["conn_peak_threads"] - out["conn_base_threads"] < 256
    assert out["conn_quiesce_threads"] <= out["conn_base_threads"]
    assert out["conn_quiesce_fds"] <= out["conn_base_fds"]
