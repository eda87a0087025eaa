"""DurabilityLedger front doors: the acked-write oracle on CephFS and
RGW, not just RADOS.

The PR 5 ledger proved acked RADOS writes survive crash-restart
cycles; this drill proves the SAME machinery (write/delete/verify,
candidate digests, no-torn-state) holds at every front door — CephFS
metadata mutations (file create + data write + size flush, unlink)
and RGW object puts/deletes over real HTTP — across one abrupt OSD
crash + remount shared by both doors.  (The torn-journal MID-write
cases are pinned by the RADOS-path drills in test_chaos.py; the doors
prove the oracle's coverage of the front doors themselves.)
"""

import time

import pytest

from ceph_tpu.client import (CephFSDoor, DurabilityLedger, RGWDoor,
                             RadosError, SwiftDoor)
from ceph_tpu.utils import faults
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 5.0,
    # fail blocked ops fast: the MDS journals metadata under its big
    # lock, and a 30-virtual-second objecter stall there starves every
    # client request for minutes of real time after an OSD kill
    "objecter_op_timeout": 5.0,
}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.get().reset(seed=0)
    yield
    faults.get().reset(seed=0)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    # flight recorder armed for the whole module: the known ~1-in-6
    # "deg: ACKED write lost" flake (ROADMAP known-flakes) now
    # auto-captures every daemon's in-flight/historic ops + pg log
    # summaries the moment verify raises — the dump directory is
    # printed so a flaked CI run hands over the timeline instead of
    # a rerun-and-hope
    from ceph_tpu.utils import optracker
    fr_dir = str(tmp_path_factory.mktemp("flightrec"))
    optracker.recorder().arm(fr_dir)
    print(f"[ledger-doors] flight recorder armed: {fr_dir}")
    c = MiniCluster(num_mons=1, num_osds=3, conf=Config(dict(CONF)),
                    store_kind="filestore",
                    store_dir=str(tmp_path_factory.mktemp("doors"))
                    ).start()
    # settle the data plane before the gateways build their pools
    r = c.client()
    r.create_pool("warmup", pg_num=4)
    io = r.open_ioctx("warmup")
    end = time.time() + 40
    while True:
        try:
            io.write_full("w", b"w")
            break
        except RadosError:
            if time.time() > end:
                raise
            c.tick(0.3)
    yield c
    c.stop()
    if optracker.recorder().records:
        print("[ledger-doors] flight recorder captured: "
              + ", ".join(optracker.recorder().records))
    optracker.recorder().disarm()


@pytest.fixture(scope="module")
def fs_door(cluster):
    from ceph_tpu.fs import CephFS, FsError
    cluster.start_mds("a")
    fs = CephFS(cluster.client("client.fsdoor"))
    end = time.time() + 60
    while True:
        try:
            fs.mount(timeout=10.0)
            break
        except FsError:
            if time.time() > end:
                raise
            cluster.tick(0.5)
    return CephFSDoor(fs, root="/ledger")


@pytest.fixture(scope="module")
def rgw(cluster):
    return cluster.start_rgw()


@pytest.fixture(scope="module")
def rgw_door(rgw):
    return RGWDoor(f"http://127.0.0.1:{rgw.port}", bucket="ldoor")


@pytest.fixture(scope="module")
def swift_door(rgw):
    # the SAME gateway spoken as TempAuth'd Swift v1: one namespace,
    # two dialects — the crash drill must hold for both
    return SwiftDoor(f"http://127.0.0.1:{rgw.port}", container="sdoor")


class TestFrontDoorLedgers:
    def test_acked_mutations_survive_osd_crash_on_every_door(
            self, cluster, fs_door, rgw_door, swift_door):
        """Acked CephFS file creates/writes/unlinks, RGW S3 HTTP
        puts/deletes AND TempAuth'd Swift puts/deletes are
        crash-verified through one abrupt OSD kill + remount (journal
        replay runs on the reborn daemon): every ack any front door
        handed out must read back bit-exact, and an acked
        unlink/DELETE stays gone."""
        retry = lambda: cluster.tick(0.3)        # noqa: E731
        fsl, rgwl = DurabilityLedger(), DurabilityLedger()
        swl = DurabilityLedger()
        for i in range(4):
            assert fsl.write(fs_door, f"f{i}",
                             f"fsdoor-{i}-".encode() * 50,
                             retry_window=120, on_retry=retry)
            assert rgwl.write(rgw_door, f"k{i}",
                              f"rgw-{i}-".encode() * 60,
                              retry_window=120, on_retry=retry)
            assert swl.write(swift_door, f"s{i}",
                             f"swift-{i}-".encode() * 55,
                             retry_window=120, on_retry=retry)
        assert fsl.delete(fs_door, "f3", retry_window=120,
                          on_retry=retry)
        assert rgwl.delete(rgw_door, "k3", retry_window=120,
                           on_retry=retry)
        assert swl.delete(swift_door, "s3", retry_window=120,
                          on_retry=retry)
        cluster.kill_osd(1)               # abrupt: store frozen as-is
        # degraded mutations keep acking and stay covered
        assert fsl.write(fs_door, "f0", b"degraded-rewrite" * 40,
                         retry_window=180, on_retry=retry)
        assert rgwl.write(rgw_door, "deg", b"degraded-put" * 40,
                          retry_window=180, on_retry=retry)
        assert swl.write(swift_door, "sdeg", b"degraded-swift" * 40,
                         retry_window=180, on_retry=retry)
        try:
            cluster.restart_osd(1, timeout=240)
        except TimeoutError:
            print("[ledger-doors] unclean: "
                  + "; ".join(cluster.unclean_pgs() or []))
            raise
        freport = fsl.verify(fs_door, retry_window=180, on_retry=retry)
        assert freport["checked"] == 4, freport
        assert freport["acked_deletes"] == 1, freport
        rreport = rgwl.verify(rgw_door, retry_window=180,
                              on_retry=retry)
        assert rreport["checked"] == 5, rreport
        assert rreport["acked_deletes"] == 1, rreport
        sreport = swl.verify(swift_door, retry_window=180,
                             on_retry=retry)
        assert sreport["checked"] == 5, sreport
        assert sreport["acked_deletes"] == 1, sreport
        # acked deletes stay deleted through the crash cycle, with the
        # door-native errno semantics
        with pytest.raises(RadosError):
            fs_door.read("f3")
        with pytest.raises(RadosError) as ei:
            rgw_door.read("k3")
        assert ei.value.errno == 2
        with pytest.raises(RadosError) as ei:
            swift_door.read("s3")
        assert ei.value.errno == 2


class RebornMarkedDown(AssertionError):
    """The one failure the reproducer below is expected to end in."""


class TestRebornBeforeMarkedDown:
    """An OSD that crashes and is back inside the heartbeat grace: the
    mon has not marked the dead daemon down when the reborn one boots,
    and the survivors' failure reports about the dead one are still on
    their way.  The drill above restarts its OSD at once and meets this
    order by chance under load (the flap in the logs of its "cluster
    not clean" runs, ROADMAP "Known failures and flakes"); here it is
    deterministic: the reports are held at the mon's door (a slow link)
    and let in after the boot."""

    @pytest.mark.xfail(strict=True, raises=RebornMarkedDown, reason=(
        "DEFECT (ROADMAP 'Known failures and flakes', PR 46): "
        "OSDMonitor.handle_failure(target, reporter) names no "
        "incarnation, so reports about the dead daemon that arrive "
        "after the reborn one has booted mark the REBORN one down. "
        "The repair gives MOSDFailure the target's address and has the "
        "mon drop a report whose address is not the map's; then this "
        "marker goes and the rest of the test holds"))
    def test_late_reports_about_the_dead_daemon_spare_the_reborn_one(
            self, tmp_path):
        c = MiniCluster(num_mons=1, num_osds=3, conf=Config(dict(CONF)),
                        store_kind="filestore",
                        store_dir=str(tmp_path)).start()
        try:
            retry = lambda: c.tick(0.3)          # noqa: E731
            r = c.client()
            r.create_pool("reborn", pg_num=8)
            io = r.open_ioctx("reborn")
            c.wait_for_clean(60)
            ledger = DurabilityLedger()
            for i in range(8):
                assert ledger.write(io, f"o{i}", f"acked-{i}-".encode() * 90,
                                    retry_window=60, on_retry=retry)
            # the slow link: every report about osd.1 waits here, as the
            # survivors sent it (whatever a later MOSDFailure carries
            # rides along in *a / **kw)
            osdmon = c.leader().osdmon
            deliver, held = osdmon.handle_failure, {}

            def hold(target, reporter, *a, **kw):
                if target != 1:
                    return deliver(target, reporter, *a, **kw)
                held.setdefault(reporter, (a, kw))

            osdmon.handle_failure = hold
            c.kill_osd(1)             # abrupt: store frozen as-is
            end = time.time() + 60
            need = int(c.conf.mon_osd_min_down_reporters)
            while len(held) < need:   # grace spent, both survivors spoke
                assert time.time() < end, held
                c.tick(0.25)
            assert osdmon.osdmap.is_up(1)      # the mon heard nothing
            reborn = c.restart_osd(1, wait_clean=False)
            assert tuple(osdmon.osdmap.get_addr(1)) == \
                tuple(reborn.msgr.addr)
            # now the reports about the DEAD daemon arrive
            del osdmon.handle_failure
            for reporter, (a, kw) in held.items():
                deliver(1, reporter, *a, **kw)
            # one mon: a proposal commits inside the call
            if not osdmon.osdmap.is_up(1):
                raise RebornMarkedDown(
                    "reports about the dead osd.1 marked the reborn "
                    "one down")
            assert tuple(osdmon.osdmap.get_addr(1)) == \
                tuple(reborn.msgr.addr)
            c.wait_for_clean(120)
            report = ledger.verify(io, retry_window=60, on_retry=retry)
            assert report["checked"] == 8, report
        finally:
            c.stop()
