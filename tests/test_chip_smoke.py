"""chip_smoke.py's own logic, on the CPU.

The command itself has no option that lets it pass without a TPU; its
steps are plain functions, driven here at a tiny size on the CPU
platform (the XLA formulation serves, the same counters move).  What
only a chip can show — platform == "tpu", Pallas with interpret=False,
times — is chip_smoke.py's to assert, on the chip.

  * the step runner stops at the first failing step, prints that step's
    evidence, and never prints a later step or a final "ok";
  * the platform check refuses a CPU;
  * the final line carries exactly the three `device` keys;
  * a warm-up made to fail fails the step with the warm-up's own error;
  * steps 1-3 pass end to end on one lane (13 OSDs, a few 64 KiB
    objects), and the four-chip phase on four virtual devices.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from ceph_tpu.ops import ec_kernels  # noqa: E402
from ceph_tpu.ops import pipeline as ec_pipeline  # noqa: E402


@pytest.fixture
def fresh_plane(monkeypatch):
    """A device plane of its own: the smoke asserts process-wide
    counters are ZERO, and earlier test files in this worker inject
    device faults into the shared pipeline on purpose."""
    def make(**kw):
        ec_pipeline.get().flush()
        pipe = ec_pipeline.EcDevicePipeline(**kw)
        monkeypatch.setattr(ec_pipeline, "_global", pipe)
        monkeypatch.setitem(ec_pipeline._warm, "warm_failures", 0)
        monkeypatch.setitem(ec_pipeline._warm, "last_warm_error", "")
        return pipe
    yield make
    ec_pipeline.get().stop()


class TestStepRunner:
    def test_stops_at_first_failure_with_evidence(self):
        lines, ran = [], []

        def bad():
            ran.append("bad")
            chip_smoke.check(False, "counter did not move",
                             delta={"dev_dispatches": 0})

        ok = chip_smoke.run_steps(
            [("first", lambda: ran.append("first") or {"n": 1}),
             ("second", bad),
             ("third", lambda: ran.append("third"))], out=lines.append)
        assert ok is False and ran == ["first", "bad"]
        assert [l["step"] for l in lines] == ["first", "second"]
        assert lines[0]["ok"] is True and lines[0]["n"] == 1
        assert lines[1]["ok"] is False
        assert "counter did not move" in lines[1]["error"]
        assert lines[1]["delta"] == {"dev_dispatches": 0}
        assert all("seconds" in l for l in lines)

    def test_any_exception_fails_the_step(self):
        lines = []

        def boom():
            raise RuntimeError("libtpu said no")

        assert chip_smoke.run_steps([("device", boom)],
                                    out=lines.append) is False
        assert lines[-1]["ok"] is False
        assert "RuntimeError: libtpu said no" in lines[-1]["error"]

    def test_final_line_has_exactly_the_device_keys(self):
        doc = json.loads(chip_smoke.final_line("tpu", "TPU v5 lite", 1))
        assert doc == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


class TestMain:
    def test_refuses_a_cpu_at_step_0(self, capsys):
        assert chip_smoke.main([]) != 0
        out = capsys.readouterr().out.strip().splitlines()
        last = json.loads(out[-1])
        assert last["step"] == "device" and last["ok"] is False
        assert "not a chip run" in last["error"]
        assert not any(json.loads(l).get("ok") is True for l in out)

    @pytest.mark.parametrize("chips", [1, 4])
    def test_final_line_only_after_every_step(self, chips, capsys,
                                              monkeypatch):
        dev = {"platform": "tpu", "kind": "fake v5e", "count": chips}
        monkeypatch.setattr(chip_smoke, "step_device",
                            lambda platform, count: dict(dev))
        for name in ("step_kernels", "step_plugin", "step_cluster",
                     "step_lanes"):
            monkeypatch.setattr(chip_smoke, name,
                                lambda *a, **k: {"stub": True})
        monkeypatch.setattr(chip_smoke, "shutdown", lambda: {})
        argv = ["--chips", "4"] if chips == 4 else []
        assert chip_smoke.main(argv) == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert json.loads(out[-1]) == {"ok": True, "device": dev}
        steps = [json.loads(l)["step"] for l in out[:-1]]
        assert steps == (["device", "lanes", "shutdown", "total"]
                         if chips == 4 else
                         ["device", "kernels", "plugin", "cluster",
                          "shutdown", "total"])
        # a failing step: non-zero, no final line, nothing after it
        monkeypatch.setattr(
            chip_smoke, "step_lanes" if chips == 4 else "step_plugin",
            lambda *a, **k: chip_smoke.check(False, "host served"))
        assert chip_smoke.main(argv) != 0
        out = capsys.readouterr().out.strip().splitlines()
        last = json.loads(out[-1])
        assert last["ok"] is False and "host served" in last["error"]
        assert "device" not in last


class TestSteps:
    def test_failed_warmup_fails_the_step_with_its_error(
            self, fresh_plane, monkeypatch):
        fresh_plane(device_shards=1)

        def refuse(*a, **k):
            raise RuntimeError("mosaic refused the kernel")

        monkeypatch.setattr(ec_kernels, "make_encode_crc_fn", refuse)
        lines = []
        ok = chip_smoke.run_steps(
            [("plugin", lambda: chip_smoke.step_plugin(
                "cpu", seed=3, stripes=8, bound=30.0))],
            out=lines.append)
        assert ok is False
        assert "mosaic refused the kernel" in lines[0]["error"]
        assert lines[0]["pipeline"]["warm_failures"] >= 1
        assert "mosaic refused" in lines[0]["pipeline"]["last_warm_error"]

    def test_one_lane_steps_end_to_end(self, fresh_plane):
        """Steps 1-3 at a tiny size: every asserted window holds on
        the CPU platform too (one lane, like a one-chip host)."""
        fresh_plane(device_shards=1)
        lines = []
        ok = chip_smoke.run_steps([
            ("kernels", lambda: chip_smoke.step_kernels(
                "cpu", seed=1, fused_shapes=((4, 8, 512),),
                decode_shape=(4, 8, 512), crc_shapes=((44, 512),))),
            ("plugin", lambda: chip_smoke.step_plugin(
                "cpu", seed=1, stripes=8, bound=120.0)),
            ("cluster", lambda: chip_smoke.step_cluster(
                "cpu", seed=1, n_objects=6, object_bytes=64 << 10,
                inflight=4, pg_num=2, prod_objects=2, bound=120.0,
                conf={"osd_ec_device_shards": "1",
                      "osd_ec_hbm_cache_bytes": 128 << 10})),
        ], out=lines.append)
        assert ok, lines[-1]
        cluster = lines[-1]
        assert cluster["write"]["window"]["host_dispatches"] == 0
        assert cluster["write"]["codec"]["device_stripe_passes"] >= 6
        assert cluster["scrub"]["checked"] == 6 * 11
        assert cluster["scrub"]["window"]["dev_dispatches"] >= 1
        assert cluster["repair"]["window"]["dev_dispatches"] >= 1
        assert cluster["repair"]["window"]["host_dispatches"] == 0
        assert cluster["production_routing"]["asserted"] is False

    def test_four_lane_phase(self, fresh_plane):
        """--chips 4's lanes step on four virtual devices."""
        fresh_plane(device_shards=4)
        lines = []
        ok = chip_smoke.run_steps([
            ("lanes", lambda: chip_smoke.step_lanes(
                "cpu", seed=1, n_lanes=4, stripes=16, L=512, batches=8,
                threads=4, bound=120.0)),
        ], out=lines.append)
        assert ok, lines[-1]
        assert len(lines[0]["lanes"]) == 4
