"""Admin tools: rados CLI (+bench), ceph CLI, crushtool, osdmaptool,
objectstore tool, and standalone daemon entry points.

The tier-3 pattern (qa/workunits style): tools drive a live cluster;
offline tools operate on dumped maps and stopped stores.
"""

import io as io_mod
import os
import subprocess
import sys
import time

import pytest

from ceph_tpu.client import RadosError
from ceph_tpu.tools import (ceph_cli, crushtool, objectstore_tool,
                            osdmaptool, rados_cli)
from ceph_tpu.vstart import MiniCluster


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(num_mons=1, num_osds=3).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def conf_file(cluster, tmp_path_factory):
    path = tmp_path_factory.mktemp("conf") / "ceph.conf"
    mon_host = ",".join(f"{h}:{p}" for h, p in
                        (cluster.monmap.addr_of(n)
                         for n in cluster.monmap.ranks()))
    path.write_text(
        f"[global]\nfsid = {cluster.monmap.fsid}\n"
        f"mon_host = {mon_host}\n"
        f"osd_heartbeat_grace = 8.0\n")
    return str(path)


def run_tool(main, argv) -> tuple[int, str]:
    buf = io_mod.StringIO()
    rc = main(argv, out=buf)
    return rc, buf.getvalue()


class TestRadosCli:
    def test_pool_and_object_lifecycle(self, cluster, conf_file,
                                       tmp_path):
        rc, _ = run_tool(rados_cli.main,
                         ["-c", conf_file, "mkpool", "clipool"])
        assert rc == 0
        src = tmp_path / "in.bin"
        src.write_bytes(b"cli payload " * 100)
        rc, _ = run_tool(rados_cli.main,
                         ["-c", conf_file, "-p", "clipool", "put",
                          "obj1", str(src)])
        assert rc == 0
        dst = tmp_path / "out.bin"
        rc, _ = run_tool(rados_cli.main,
                         ["-c", conf_file, "-p", "clipool", "get",
                          "obj1", str(dst)])
        assert rc == 0
        assert dst.read_bytes() == src.read_bytes()
        rc, out = run_tool(rados_cli.main,
                           ["-c", conf_file, "-p", "clipool", "ls"])
        assert "obj1" in out
        rc, out = run_tool(rados_cli.main,
                           ["-c", conf_file, "-p", "clipool", "stat",
                            "obj1"])
        assert "size 1200" in out
        rc, out = run_tool(rados_cli.main, ["-c", conf_file, "lspools"])
        assert "clipool" in out

    def test_bench(self, cluster, conf_file):
        rc, out = run_tool(
            rados_cli.main,
            ["-c", conf_file, "-p", "clipool", "bench", "2", "write",
             "-b", "4096", "-t", "2"])
        assert rc == 0
        assert "Bandwidth (MB/sec):" in out
        assert "Average IOPS:" in out


class TestCephCli:
    def test_status_and_osd_cmds(self, cluster, conf_file):
        rc, out = run_tool(ceph_cli.main, ["-c", conf_file, "status"])
        assert rc == 0 and "osd:" in out
        rc, out = run_tool(ceph_cli.main, ["-c", conf_file, "osd",
                                           "tree"])
        assert rc == 0
        rc, out = run_tool(ceph_cli.main,
                           ["-c", conf_file, "osd", "pool", "ls"])
        assert "clipool" in out

    def test_ec_profile_roundtrip(self, cluster, conf_file):
        rc, _ = run_tool(ceph_cli.main,
                         ["-c", conf_file, "osd",
                          "erasure-code-profile", "set", "cliprof",
                          "k=2", "m=1", "plugin=tpu"])
        assert rc == 0
        rc, out = run_tool(ceph_cli.main,
                           ["-c", conf_file, "osd",
                            "erasure-code-profile", "get", "cliprof"])
        assert "k=2" in out

    def test_daemon_passthrough(self, cluster, conf_file, tmp_path):
        osd = next(iter(cluster.osds.values()))
        # daemon mode needs a socket; MiniCluster default has none, so
        # spin one up ad hoc
        from ceph_tpu.utils.admin_socket import AdminSocket
        path = str(tmp_path / "t.asok")
        sock = AdminSocket("t", path)
        sock.register("ping", lambda c: {"pong": True})
        sock.start()
        try:
            rc, out = run_tool(ceph_cli.main,
                               ["daemon", path, "ping"])
            assert rc == 0 and '"pong": true' in out
        finally:
            sock.shutdown()


class TestCrushtool:
    def test_build_and_test(self, tmp_path):
        mapfile = str(tmp_path / "crush.bin")
        rc, out = run_tool(crushtool.main,
                           ["--build", "--num-osds", "12",
                            "--num-hosts", "4", "-o", mapfile])
        assert rc == 0 and os.path.exists(mapfile)
        rc, out = run_tool(crushtool.main,
                           ["-i", mapfile, "--test", "--num-rep", "3",
                            "--max-x", "255", "--show-utilization"])
        assert rc == 0
        assert "checked 256 mappings, 0 bad" in out

    def test_distribution_is_reasonable(self, tmp_path):
        buf = io_mod.StringIO()
        from ceph_tpu.crush.map import CrushMap
        cmap = CrushMap.build_flat(8)
        res = crushtool.test_map(cmap, 0, 3, 0, 2047, False, False,
                                 out=buf)
        assert res["bad_mappings"] == 0
        util = res["device_util"]
        avg = sum(util.values()) / len(util)
        assert all(abs(v - avg) / avg < 0.25 for v in util.values())


class TestOsdmaptool:
    def test_print_and_pg_distribution(self, cluster, conf_file,
                                       tmp_path):
        r = cluster.client()
        rv, _out, data = r.mon_command({"prefix": "osd getmap"})
        assert rv == 0 and data
        mapfile = tmp_path / "osdmap.bin"
        mapfile.write_bytes(data)
        rc, out = run_tool(osdmaptool.main,
                           [str(mapfile), "--print"])
        assert rc == 0 and "pool" in out and "osd.0" in out
        rc, out = run_tool(osdmaptool.main,
                           [str(mapfile), "--test-map-pgs"])
        assert rc == 0 and "examined" in out


class TestObjectstoreTool:
    def test_export_import_roundtrip(self, tmp_path):
        from ceph_tpu.store import create as store_create
        from ceph_tpu.store.objectstore import Transaction
        path = str(tmp_path / "osd-data")
        store = store_create("filestore", path)
        store.mkfs()
        store.mount()
        txn = (Transaction().create_collection("pg_9.0")
               .touch("pg_9.0", "obj").write("pg_9.0", "obj", 0, b"data")
               .setattr("pg_9.0", "obj", "k", b"v"))
        store.apply_transaction(txn)
        store.umount()

        export = str(tmp_path / "pg.export")
        rc, out = run_tool(
            objectstore_tool.main,
            ["--data-path", path, "--op", "export", "--pgid", "9.0",
             "--file", export])
        assert rc == 0 and "exported" in out

        path2 = str(tmp_path / "osd-data2")
        store2 = store_create("filestore", path2)
        store2.mkfs()
        store2.umount()
        rc, out = run_tool(
            objectstore_tool.main,
            ["--data-path", path2, "--op", "import", "--file", export])
        assert rc == 0
        rc, out = run_tool(
            objectstore_tool.main,
            ["--data-path", path2, "--op", "list"])
        assert "obj" in out
        rc, out = run_tool(
            objectstore_tool.main,
            ["--data-path", path2, "--op", "dump", "--pgid", "9.0",
             "--oid", "obj"])
        assert '"size": 4' in out


class TestPglogDump:
    """pglog-dump: offline PG log bounds/divergence inspection (the
    log-authoritative peering debug surface for wedged soaks)."""

    @staticmethod
    def _mk(path, entries, watermark=None, les=0, form="keys"):
        """A stopped store holding one PG's log: as the keys an OSD
        writes, or as the blob a store from before them holds."""
        from ceph_tpu.osd.pglog import PGLog, persist_log
        from ceph_tpu.store import create as store_create
        from ceph_tpu.store.objectstore import Transaction
        s = store_create("filestore", str(path))
        s.mkfs()
        s.mount()
        log = PGLog()
        for e in entries:
            log.add(dict(e))
        txn = (Transaction().create_collection("pg_7.0")
               .touch("pg_7.0", "_pgmeta"))
        if form == "keys":
            persist_log(log, s, "pg_7.0", txn)
        else:
            txn.setattr("pg_7.0", "_pgmeta", "log", log.encode())
        if watermark is not None:
            txn.setattr("pg_7.0", "_pgmeta", "backfilling",
                        b"@" + watermark.encode())
        if les:
            txn.setattr("pg_7.0", "_pgmeta", "les",
                        str(les).encode())
        s.apply_transaction(txn)
        s.umount()

    @pytest.mark.parametrize("form", ["keys", "blob"])
    def test_dump_divergence_and_watermark(self, tmp_path, form):
        import json
        from ceph_tpu.tools import pglog_dump

        def e(ev, oid, op="modify"):
            return {"ev": ev, "oid": oid, "op": op, "prior": None,
                    "rollback": None, "shard": None}

        self._mk(tmp_path / "a",
                 [e((1, 1), "x"), e((1, 2), "y"), e((2, 3), "z")],
                 les=2, form=form)
        # (the peer always in the other form: the tool reads either)
        self._mk(tmp_path / "b",
                 [e((1, 1), "x"), e((1, 2), "y"), e((1, 3), "w")],
                 watermark="mmm", les=1,
                 form="blob" if form == "keys" else "keys")
        rc, out = run_tool(pglog_dump.main,
                           ["--data-path", str(tmp_path / "a"),
                            "--pgid", "7.0", "--entries"])
        assert rc == 0
        doc = json.loads(out)
        assert doc["last_update"] == [2, 3]
        assert doc["entries"] == 3 and len(doc["log"]) == 3
        assert doc["last_epoch_started"] == 2
        assert doc["backfill_complete"] is True
        # the mid-backfill peer reports its persisted watermark
        rc, out = run_tool(pglog_dump.main,
                           ["--data-path", str(tmp_path / "b"),
                            "--pgid", "7.0"])
        doc = json.loads(out)
        assert doc["last_backfill"] == "mmm"
        assert doc["backfill_complete"] is False
        # divergence report: b's (1,3) suffix forked off a's history
        rc, out = run_tool(pglog_dump.main,
                           ["--data-path", str(tmp_path / "a"),
                            "--pgid", "7.0",
                            "--peer-path", str(tmp_path / "b")])
        assert rc == 0
        div = json.loads(out)["divergence"]
        mine = div["mine_as_auth"]
        assert mine["rewind_to"] == [1, 2]
        assert [d["ev"] for d in mine["divergent_entries"]] == [[1, 3]]
        assert mine["peer_contained"] is False
        # listing mode + missing pg error path
        rc, out = run_tool(pglog_dump.main,
                           ["--data-path", str(tmp_path / "a")])
        assert rc == 0 and "7.0" in json.loads(out)["pgs"]
        rc, _out = run_tool(pglog_dump.main,
                            ["--data-path", str(tmp_path / "a"),
                             "--pgid", "9.9"])
        assert rc == 1


class TestTraceDump:
    def test_live_cluster_dump_to_chrome_trace(self, cluster,
                                               tmp_path):
        """Smoke: real traced ops off a live cluster's historic ring
        -> trace_dump CLI -> loadable Chrome-trace JSON with complete
        events, span slices and process/thread metadata."""
        import json
        from ceph_tpu.tools import trace_dump
        rados = cluster.client()
        rados.create_pool("tracetool", pg_num=2)
        io = rados.open_ioctx("tracetool")
        end = time.time() + 30
        while True:
            try:
                io.write_full("t0", b"trace me" * 64)
                break
            except RadosError:
                if time.time() > end:
                    raise
                cluster.tick(0.3)
        paths = []
        for osd in cluster.osds.values():
            p = tmp_path / f"{osd.entity}.json"
            p.write_text(json.dumps(
                osd.op_tracker.dump_historic_ops()))
            paths.append(str(p))
        rc, out = run_tool(trace_dump.main, ["--dump", *paths])
        assert rc == 0
        doc = json.loads(out)
        events = doc["traceEvents"]
        assert any(e["ph"] == "X" and "t0" in e["name"]
                   for e in events)
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in events)
        assert any(e["ph"] == "X" and e["cat"] == "span"
                   for e in events)
        # no inputs is a usage error, not a crash
        assert trace_dump.main([]) == 2


class TestStandaloneDaemons:
    def test_process_level_cluster(self, tmp_path):
        """Real processes: 1 mon + 1 osd booted via the entry points,
        driven by the rados CLI over the wire (vstart.sh tier-3, but
        with actual process isolation)."""
        import socket as socket_mod
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        conf = tmp_path / "ceph.conf"
        conf.write_text(
            "[global]\n"
            "fsid = 424242aa-0000-0000-0000-000000000000\n"
            f"mon_host = 127.0.0.1:{port}\n"
            "osd_pool_default_size = 1\n"
            "osd_pool_default_min_size = 1\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH="/root/repo:" + os.environ.get(
                       "PYTHONPATH", ""))
        procs = []
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ceph_tpu.daemons", "mon",
                 "--name", "a", "-c", str(conf)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL))
            time.sleep(1.5)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ceph_tpu.daemons", "osd",
                 "--id", "0", "-c", str(conf)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL))
            # drive it with the CLI from THIS process
            end = time.time() + 60
            while True:
                try:
                    rc, _ = run_tool(rados_cli.main,
                                     ["-c", str(conf), "mkpool", "solo"])
                    assert rc == 0
                    break
                except (RadosError, AssertionError):
                    if time.time() > end:
                        raise
                    time.sleep(1.0)
            payload = tmp_path / "p.bin"
            payload.write_bytes(b"inter-process!" * 10)
            end = time.time() + 30
            while True:
                try:
                    rc, _ = run_tool(
                        rados_cli.main,
                        ["-c", str(conf), "-p", "solo", "put", "x",
                         str(payload)])
                    assert rc == 0
                    break
                except (RadosError, AssertionError):
                    if time.time() > end:
                        raise
                    time.sleep(1.0)
            back = tmp_path / "b.bin"
            rc, _ = run_tool(
                rados_cli.main,
                ["-c", str(conf), "-p", "solo", "get", "x", str(back)])
            assert rc == 0
            assert back.read_bytes() == payload.read_bytes()
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


class TestMonmapTool:
    def test_create_edit_print(self, tmp_path):
        from ceph_tpu.mon.monmap import MonMap
        from ceph_tpu.tools import monmaptool
        path = str(tmp_path / "monmap.bin")
        rc, out = run_tool(monmaptool.main, [
            "--create", "--fsid", "f-1",
            "--add", "a", "127.0.0.1:6789",
            "--add", "b", "127.0.0.1:6790", "-o", path])
        assert rc == 0 and "2 mons" in out
        rc, out = run_tool(monmaptool.main, ["-i", path, "--print"])
        assert rc == 0
        assert "mon.a" in out and "6790" in out and "fsid f-1" in out
        # edit: rm + add bumps the epoch
        path2 = str(tmp_path / "monmap2.bin")
        rc, out = run_tool(monmaptool.main, [
            "-i", path, "--rm", "b", "--add", "c", "127.0.0.1:6791",
            "-o", path2])
        assert rc == 0
        with open(path2, "rb") as f:
            mm = MonMap.decode(f.read())
        assert mm.ranks() == ["a", "c"] and mm.epoch == 2
        # duplicate add refused
        rc, _ = run_tool(monmaptool.main, [
            "-i", path2, "--add", "a", "127.0.0.1:7000"])
        assert rc == 1

    def test_seeds_a_bootable_monitor(self, tmp_path):
        """The tool's output is a real seed: a Monitor boots from it."""
        import socket
        from ceph_tpu.mon import Monitor
        from ceph_tpu.mon.monmap import MonMap
        from ceph_tpu.tools import monmaptool
        s = socket.socket(); s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"; s.close()
        path = str(tmp_path / "seed.bin")
        rc, _ = run_tool(monmaptool.main, [
            "--create", "--fsid", "boot-1", "--add", "a", addr,
            "-o", path])
        assert rc == 0
        with open(path, "rb") as f:
            mm = MonMap.decode(f.read())
        mon = Monitor("a", mm)
        mon.start()
        try:
            deadline = time.time() + 10
            while not mon.is_leader() and time.time() < deadline:
                time.sleep(0.1)
            assert mon.is_leader()
        finally:
            mon.shutdown()


class TestAuthTool:
    def test_keyring_lifecycle(self, tmp_path):
        import base64
        from ceph_tpu.auth import KeyRing
        from ceph_tpu.tools import authtool
        path = str(tmp_path / "keyring")
        rc, out = run_tool(authtool.main, [
            "--create-keyring", path, "--gen-key",
            "--name", "client.admin"])
        assert rc == 0 and "creating" in out
        rc, _ = run_tool(authtool.main, [path, "--gen-key",
                                         "--name", "osd.0"])
        assert rc == 0
        rc, out = run_tool(authtool.main, [path, "--list"])
        assert rc == 0
        assert "[client.admin]" in out and "[osd.0]" in out
        rc, out = run_tool(authtool.main, [path, "--print-key",
                                           "--name", "client.admin"])
        assert rc == 0
        ring = KeyRing.from_file(path)
        assert base64.b64decode(out.strip()) == \
            ring.get("client.admin")
        # import an explicit key
        k = base64.b64encode(b"S" * 24).decode()
        rc, _ = run_tool(authtool.main, [path, "--add-key", k,
                                         "--name", "mds.a"])
        assert rc == 0
        assert KeyRing.from_file(path).get("mds.a") == b"S" * 24


class TestCephfsShell:
    def test_namespace_workflow(self, cluster, conf_file, tmp_path):
        from ceph_tpu.tools import cephfs_shell
        cluster.start_mds("shell-mds")
        src = tmp_path / "local.txt"
        src.write_bytes(b"shell payload\n")
        rc, _ = run_tool(cephfs_shell.main,
                         ["-c", conf_file, "mkdir", "/sh/deep"])
        assert rc == 0
        rc, _ = run_tool(cephfs_shell.main,
                         ["-c", conf_file, "put", str(src),
                          "/sh/deep/f"])
        assert rc == 0
        rc, out = run_tool(cephfs_shell.main,
                           ["-c", conf_file, "cat", "/sh/deep/f"])
        assert rc == 0 and out == "shell payload\n"
        rc, out = run_tool(cephfs_shell.main,
                           ["-c", conf_file, "stat", "/sh/deep/f"])
        assert rc == 0 and "size=14" in out
        rc, _ = run_tool(cephfs_shell.main,
                         ["-c", conf_file, "mv", "/sh/deep/f",
                          "/sh/deep/g"])
        assert rc == 0
        dst = tmp_path / "out.txt"
        rc, _ = run_tool(cephfs_shell.main,
                         ["-c", conf_file, "get", "/sh/deep/g",
                          str(dst)])
        assert rc == 0 and dst.read_bytes() == b"shell payload\n"
        rc, out = run_tool(cephfs_shell.main,
                           ["-c", conf_file, "tree", "/sh"])
        assert rc == 0 and "deep/" in out and "g [14]" in out
        rc, _ = run_tool(cephfs_shell.main,
                         ["-c", conf_file, "rm", "/sh/deep/g"])
        assert rc == 0
        rc, out = run_tool(cephfs_shell.main,
                           ["-c", conf_file, "ls", "/sh/deep"])
        assert rc == 0 and out.strip() == ""
        # errors surface as rc=1, not tracebacks
        rc, out = run_tool(cephfs_shell.main,
                           ["-c", conf_file, "cat", "/nope"])
        assert rc == 1 and "cephfs-shell:" in out
