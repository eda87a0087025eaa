"""A failed OSD marked out of a `plugin=tpu` pool that holds data, with
readers on it (PR 47; the benchmark cell `k8m3-4m-backfill-rand-read`
at a small size): the operator's three steps and nothing else
(`kill_osd`, `mark_osd_down`, `mark_osd_out`), then the map change,
CRUSH, peering, backfill and the shard rebuilds do the rest.

Held here, for a Reed-Solomon k=8 m=3 pool on 13 OSDs and an lrc k=4
m=2 l=3 pool on 10: every read during the repair returns the written
bytes; the cluster comes clean, and `wait_for_clean` does not say so
before the last rebuild; every position of every object equals the
benchmark's plain reference, shard file and CRC; a rebuilt object is
ONE recovery op whichever way it was queued, with `rebuild`,
`rebuild.read` (`path`, `chunks`, `bytes_read`), ONE decode of the lost
positions and no re-encode (PR 48), `rebuild.encode` (the rebuilt
files' CRCs and hinfo) and `rebuild.push` (`shard`, `target`, `bytes`,
acked);
a backfill round is one with `backfill.scan` (`objects`, `pushed`,
`skipped`) and the target's `scan_range` wait inside; the counters add
up; lrc rebuilds a lost shard from the three others of its group.

PR 50 adds a shec k=8 m=4 c=3 pool on 13 OSDs (the cell
`shec-k8m4c3-4m-backfill-rand-read` at a small size): a lost shard is
rebuilt from its shingle's 4 chunks (8 for the two parities that span
all the data); a rebuild's plan is made over the positions that HOLD
the object, so it names no position whose rebuild is still owed and
makes one gather (`rebuild.read` `planned`, `widened`; counters
`rebuild_planned_chunks`, `rebuild_widened`); and the primary's record
of owed (object, position) pairs is empty when the cluster is clean,
after an interval change in the middle of a repair too.
"""

import threading
import time

import numpy as np
import pytest

from benchmark.references import lrc as lrc_reference
from benchmark.references import reed_sol_van as rs_reference
from benchmark.references import shec as shec_reference
from ceph_tpu.client import RadosError
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.pg import shard_oid
from ceph_tpu.osd.pglog import HINFO_KEY, VER_KEY
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils import copyaudit, denc
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

OBJECT_BYTES = 64 * 1024
OBJECTS = 36
UNIT = 4096
POOLS = {
    "rs-k8m3": {
        "osds": 13, "width": 11, "reference": rs_reference,
        "profile": {"plugin": "tpu", "technique": "reed_sol_van",
                    "k": "8", "m": "3", "host_cutover": "1"}},
    "lrc-k4m2l3": {
        "osds": 10, "width": 8, "reference": lrc_reference,
        "profile": {"plugin": "tpu", "technique": "lrc", "k": "4",
                    "m": "2", "l": "3", "host_cutover": "1"}},
    "shec-k8m4c3": {
        "osds": 13, "width": 12, "reference": shec_reference,
        "profile": {"plugin": "tpu", "technique": "shec_multiple",
                    "k": "8", "m": "4", "c": "3", "host_cutover": "1"}},
}
COUNTERS = ("backfill_rounds", "backfill_objects", "rebuild_cache_served",
            "rebuild_local", "rebuild_full", "rebuild_widened",
            "rebuild_planned_chunks", "recovery_pushes")


class Repaired:
    """One cluster after the drill, and what was seen on the way."""


def counters(cluster) -> dict:
    out = dict.fromkeys(COUNTERS, 0)
    for osd in cluster.osds.values():
        block = osd.asok.execute("perf dump")["osd"]
        for name in COUNTERS:
            out[name] += block[name]
    return out


def owed_pairs(cluster) -> dict:
    """The primaries' records of owed (object, position) pairs, by
    OSD, the empty ones left out."""
    return {osd.whoami: {str(pgid): dict(owed) for pgid, owed in
                         dict(osd._rebuilds_pending).items()}
            for osd in cluster.osds.values() if osd._rebuilds_pending}


class OwedWatch(threading.Thread):
    """Samples, while a repair runs, whether the cluster says clean
    and what the primaries still owe: clean first, so that a pair seen
    beside "clean" was owed AFTER the cluster called itself whole."""

    def __init__(self, cluster):
        super().__init__(daemon=True, name="owed-watch")
        self.cluster, self.samples = cluster, []
        self.stop = threading.Event()

    def run(self) -> None:
        while not self.stop.wait(0.05):
            clean = self.cluster.unclean_pgs() == []
            self.samples.append((clean, owed_pairs(self.cluster)))

    def end(self) -> list:
        self.stop.set()
        self.join(10)
        return self.samples


@pytest.fixture(scope="module", params=sorted(POOLS))
def repaired(request):
    spec = POOLS[request.param]
    # a log shorter than a PG's history, so the new member is
    # backfilled; a ring that keeps every op of the drill
    cluster = MiniCluster(
        num_mons=1, num_osds=spec["osds"], store_kind="memstore",
        conf=Config({"osd_heartbeat_interval": 0.5,
                     "osd_heartbeat_grace": 5.0,
                     "mon_osd_min_down_reporters": 2,
                     "osd_pg_log_max_entries": 4,
                     "osd_backfill_scan_batch": 4,
                     "osd_op_history_size": 20000})).start()
    try:
        rados = cluster.client()
        rados.create_ec_pool("bf", "bf-profile", dict(
            spec["profile"], stripe_unit=str(UNIT)), pg_num=4)
        io = rados.open_ioctx("bf")
        end = time.time() + 60
        while True:
            try:
                io.write_full("settle", b"s")
                break
            except RadosError:
                assert time.time() < end
                time.sleep(0.3)
        io.remove_object("settle")
        rng = np.random.default_rng(47)
        objects = {f"obj{i:03d}": rng.integers(
            0, 256, OBJECT_BYTES, dtype=np.uint8).tobytes()
            for i in range(OBJECTS)}
        for name, data in objects.items():
            io.write_full(name, data)
        cluster.wait_for_clean(timeout=60)
        osdmap = cluster.leader().osdmon.osdmap
        pgids = [p for p in osdmap.all_pgs() if p.pool == io.pool_id]
        before = {p: list(osdmap.pg_to_up_acting_osds(p)[1])
                  for p in pgids}
        members = {o for a in before.values() for o in a}
        victim = min(members - {a[0] for a in before.values()})

        out = Repaired()
        out.bad_reads, out.reads = [], 0
        stop = threading.Event()

        def reader(seed: int) -> None:
            pick = np.random.default_rng(seed)
            names = sorted(objects)
            while not stop.is_set():
                name = names[int(pick.integers(len(names)))]
                try:
                    if io.read(name) != objects[name]:
                        out.bad_reads.append(f"{name}: other bytes")
                except RadosError as e:
                    out.bad_reads.append(f"{name}: {e}")
                out.reads += 1

        readers = [threading.Thread(target=reader, args=(i,), daemon=True)
                   for i in range(4)]
        for t in readers:
            t.start()
        # the drill's docs and counters start here: the ring also holds
        # what set-up rebuilt (the `settle` object, written while the
        # pool still peered), which no delta below counts
        out.began = time.monotonic()
        start = counters(cluster)
        audit = copyaudit.snapshot()["sites"]
        cluster.kill_osd(victim)
        cluster.mark_osd_down(victim)
        cluster.mark_osd_out(victim)
        cluster._wait(lambda: all(
            victim not in cluster.leader().osdmon.osdmap
            .pg_to_up_acting_osds(p)[1] for p in pgids), 60,
            "the victim is still acting")
        watch = OwedWatch(cluster)
        watch.start()
        cluster.wait_for_clean(timeout=240)
        out.repairing_at_clean = [
            osd.pg_repairing(p) for osd in cluster.osds.values()
            for p in pgids]
        out.owed_at_clean = owed_pairs(cluster)
        out.owed_samples = watch.end()
        stop.set()
        for t in readers:
            t.join(30)
        # clean is not yet quiet: a push may still be on its way to
        # its ack (the op stays in flight, the counter has counted),
        # and a peering round after clean may resume a session from
        # its watermark for one more round that finds nothing to
        # push.  Counters and docs are taken when no recovery op is in
        # flight, and taken again if one started meanwhile.

        def quiet() -> bool:
            return not any(
                osd.pg_repairing(p) for osd in cluster.osds.values()
                for p in pgids) and not any(
                op["kind"] == "recovery"
                for osd in cluster.osds.values()
                for op in osd.asok.execute("dump_ops_in_flight")["ops"])

        while True:
            cluster._wait(quiet, 60, "recovery ops still in flight")
            end = counters(cluster)
            out.docs = [d for osd in cluster.osds.values() for d in
                        osd.asok.execute("dump_historic_ops")["ops"]]
            if quiet() and counters(cluster) == end:
                break
        out.delta = {k: end[k] - start[k] for k in COUNTERS}
        # host bytes materialized over the drill, by the audit's site
        out.audit_delta = {
            site: v["bytes"] - audit.get(site, {"bytes": 0})["bytes"]
            for site, v in copyaudit.snapshot()["sites"].items()}
        out.cluster, out.io, out.spec = cluster, io, spec
        out.objects, out.victim = objects, victim
        out.pgids, out.before = pgids, before
        out.osdmap = cluster.leader().osdmon.osdmap
        yield out
    finally:
        cluster.stop()


def drill_docs(r, prefix: str) -> list:
    """The recovery docs of the drill whose description starts so."""
    return [d for d in r.docs if d["kind"] == "recovery"
            and d["description"].startswith(prefix)
            and d["mstart"] >= r.began]


def rebuild_docs(r) -> list:
    return drill_docs(r, "rebuild(")


def spans(doc: dict, name: str) -> list:
    return [s for s in doc["spans"] if s["name"] == name]


def test_every_read_during_the_repair_is_bit_exact(repaired):
    assert repaired.reads > 0
    assert repaired.bad_reads == []
    for name, data in repaired.objects.items():
        assert repaired.io.read(name) == data


def test_clean_means_whole_sets_and_no_repair_left(repaired):
    r = repaired
    assert not any(r.repairing_at_clean)
    assert r.cluster.unclean_pgs() == []
    remapped = 0
    for pgid in r.pgids:
        _up, acting = r.osdmap.pg_to_up_acting_osds(pgid)
        assert len(set(acting)) == r.spec["width"]
        assert r.victim not in acting
        assert all(r.osdmap.is_up(o) and r.osdmap.is_in(o) for o in acting)
        remapped += r.victim in r.before[pgid]
    assert remapped >= 1


def test_every_position_equals_the_reference(repaired):
    r = repaired
    config = {"pool_profile": r.spec["profile"], "stripe_unit": UNIT,
              "shards": r.spec["width"]}
    files = 0
    for name, data in r.objects.items():
        pgid = r.osdmap.object_to_pg(r.io.pool_id, name)
        _up, acting = r.osdmap.pg_to_up_acting_osds(pgid)
        want = r.spec["reference"].stored(data, config)
        assert len(want) == r.spec["width"]
        for pos, (want_data, want_crc) in enumerate(want):
            osd = r.cluster.osds[acting[pos]]
            cid = osd.pgs[pgid].cid
            soid = shard_oid(name, pos)
            assert bytes(osd.store.read(cid, soid)) == want_data, soid
            hinfo = denc.loads(osd.store.getattr(cid, soid, HINFO_KEY))
            assert want_crc is None or hinfo["crc"] == want_crc, soid
            files += 1
    assert files == OBJECTS * r.spec["width"]


def pushed_docs(r) -> list:
    """The rebuild docs that pushed.  One without a push is a rebuild
    whose gather did not come back at the object's version (a backfill
    round's returns after its `rebuild.read`, before any push or
    counter, and the session's rescan rebuilds the object again under
    the same id; a log-driven one retries): `unpushed_docs`."""
    return [d for d in rebuild_docs(r) if spans(d, "rebuild.push")]


def unpushed_docs(r) -> list:
    return [d for d in rebuild_docs(r) if not spans(d, "rebuild.push")]


def shard_file(r) -> int:
    return OBJECT_BYTES // int(r.spec["profile"]["k"])


def test_a_rebuilt_object_is_one_op_with_its_spans(repaired):
    docs = pushed_docs(repaired)
    assert docs
    ids = [d["trace_id"] for d in docs]
    assert sorted(set(ids)) == sorted(ids)
    assert {i.split(":")[0] for i in ids} <= {"backfill", "rebuild"}
    assert any(i.startswith("backfill:") for i in ids)
    chunk_file = shard_file(repaired)
    for d in docs:
        (whole,) = spans(d, "rebuild")
        assert "cpu" in whole
        (read,) = spans(d, "rebuild.read")
        assert read["args"]["path"] in ("cache", "local", "full")
        assert whole["t0"] <= read["t0"] and read["t1"] <= whole["t1"]
        if read["args"]["path"] == "cache":
            assert read["args"]["bytes_read"] == 0
            assert not spans(d, "rebuild.encode")
        else:
            assert read["args"]["bytes_read"] == \
                read["args"]["chunks"] * chunk_file
            # nothing is re-encoded: the span is the CRC columns of
            # the rebuilt files, their fold and the hinfo
            (encode,) = spans(d, "rebuild.encode")
            positions = len(spans(d, "rebuild.push"))
            assert encode["args"] == {"positions": positions,
                                      "bytes": positions * chunk_file}
            assert read["t1"] <= encode["t0"]
            assert encode["t1"] <= whole["t1"]
        pushes = spans(d, "rebuild.push")
        for p in pushes:
            assert p["args"]["bytes"] == chunk_file
            assert p["args"].get("acked", True)
            assert p["t0"] >= whole["t0"]
    # the decode left its phases on these docs
    assert any(s["name"].startswith("ec.") for d in docs
               for s in d["spans"])


def test_a_rebuild_without_a_push_is_followed_by_one_with(repaired):
    """A doc that pushed nothing read and stopped there: no encode, no
    counter; a later doc of the same id lands the shard."""
    pushed = {}
    for d in pushed_docs(repaired):
        pushed[d["trace_id"]] = spans(d, "rebuild")[0]["t0"]
    for d in unpushed_docs(repaired):
        (whole,) = spans(d, "rebuild")
        assert not spans(d, "rebuild.encode"), d
        assert all(a["args"]["path"] == "full"
                   for a in spans(d, "rebuild.read")), d
        assert pushed.get(d["trace_id"], 0) > whole["t0"], d


def test_a_rebuild_is_one_plan_one_gather_set_and_one_decode(repaired):
    """A rebuild decodes the lost positions and nothing else: ONE pass
    of the pipeline (on the device `ec.device_compute` with `rows` =
    the positions rebuilt over the object's stripes; `ec.host_encode`
    where the host served it, as it may any pass here on the CPU),
    from the shards the codec's plan reads for those positions, and no
    object is staged or laid out again."""
    r = repaired
    chunk_file = shard_file(r)
    stripes = chunk_file // UNIT
    k = int(r.spec["profile"]["k"])
    docs = [d for d in pushed_docs(r)
            if spans(d, "rebuild.read")[0]["args"]["path"] != "cache"]
    assert docs
    for d in docs:
        positions = len(spans(d, "rebuild.push"))
        on_device = spans(d, "ec.device_compute")
        assert len(on_device) + len(spans(d, "ec.host_encode")) == 1, d
        for s in on_device:
            assert s["args"]["rows"] == positions
            assert s["args"]["stripes"] == stripes
        # the last gather's decode set is the plan's for the rebuilt
        # position, which is not in it: fewer than k where the code
        # has locality, never more than an object's read
        read = spans(d, "rebuild.read")[0]["args"]
        lost = {p["args"]["shard"] for p in spans(d, "rebuild.push")}
        used = spans(d, "gather_wait")[-1]["args"]["chunks"]
        assert not lost & set(used)
        if read["path"] == "local":
            assert len(used) == read["chunks"] < k
        else:
            assert read["chunks"] >= k >= len(used)
    # no rebuild staged an object or laid out k+m files: the copy
    # audit's sites of the encode moved for the set-up's writes alone
    sites = r.audit_delta
    assert sites.get("ec.stage", 0) == 0, sites
    assert sites.get("ec.shard_layout", 0) == 0, sites
    assert sites.get("ec.decode_rebuild", 0) >= len(docs) * chunk_file


def test_the_target_and_the_sources_share_the_rebuilds_trace_id(repaired):
    docs = repaired.docs
    ids = {d["trace_id"] for d in rebuild_docs(repaired)}
    pushed = [d for d in docs if d["kind"] == "recovery"
              and d["description"].startswith("push(")
              and d["trace_id"] in ids]
    assert pushed
    assert all(spans(d, "execute") and spans(d, "store_apply")
               for d in pushed)
    assert any(d["kind"] == "subop" and d["trace_id"] in ids
               and "sub_read(" in d["description"] for d in docs)
    assert any(d["kind"] == "reply" and d["trace_id"] in ids
               and "MPGPushReply" in d["description"] for d in docs)


def test_a_backfill_round_is_one_op(repaired):
    rounds = drill_docs(repaired, "backfill_scan(")
    assert len(rounds) == repaired.delta["backfill_rounds"] > 0
    assert len({d["trace_id"] for d in rounds}) == len(rounds)
    pushed = 0
    for d in rounds:
        (scan,) = spans(d, "backfill.scan")
        (wait,) = spans(d, "backfill.scan_range")
        assert scan["t0"] <= wait["t0"] and wait["t1"] <= scan["t1"]
        args = scan["args"]
        assert args["objects"] == args["pushed"] + args["skipped"]
        pushed += args["pushed"]
    assert pushed == repaired.delta["backfill_objects"] > 0
    assert pushed == sum(1 for d in rebuild_docs(repaired)
                         if d["trace_id"].startswith("backfill:"))


def test_the_counters_add_up(repaired):
    """The counters follow `path` one for one over the docs that
    pushed (a rebuild that read and could not decode counts nowhere)."""
    delta = repaired.delta
    by_path = {"cache": "rebuild_cache_served", "local": "rebuild_local",
               "full": "rebuild_full"}
    seen = dict.fromkeys(by_path.values(), 0)
    for d in pushed_docs(repaired):
        (read,) = spans(d, "rebuild.read")
        seen[by_path[read["args"]["path"]]] += 1
    assert {k: delta[k] for k in seen} == seen
    assert delta["recovery_pushes"] >= sum(seen.values())


def shec_reference_chunks(lost: int, available) -> int:
    """The chunks the plain reference's plan reads for one lost
    position of the shec pool: the parities it takes and the data
    chunks they touch that are in hand."""
    matrix = shec_reference.coding_matrix(8, 4, 3)
    parities, unknowns = shec_reference.plan([lost], available, matrix)
    touched = set() if lost >= 8 else {lost}
    for p in parities + ([lost - 8] if lost >= 8 else []):
        touched |= set(np.flatnonzero(matrix[p]).tolist())
    return len(parities) + len(touched - set(unknowns))


def test_a_code_with_locality_repairs_from_its_group(repaired):
    r = repaired
    reads = [spans(d, "rebuild.read")[0]["args"] for d in pushed_docs(r)]
    technique = r.spec["profile"]["technique"]
    if technique == "reed_sol_van":
        assert {a["path"] for a in reads} <= {"full", "cache"}
        assert all(a["chunks"] == 8 for a in reads if a["path"] == "full")
        return
    local = [a for a in reads if a["path"] == "local"]
    assert local and r.delta["rebuild_local"] == len(local)
    if technique == "lrc":
        assert all(a["chunks"] == 3 and
                   a["bytes_read"] == 3 * OBJECT_BYTES // 4 for a in local)
        return
    # shec: where the PG changed ONE position, every rebuild read what
    # the reference's plan reads for that position among the eleven
    # others: a shingle's 4 for positions 0-9, all 8 data chunks for
    # the two parities that span them
    assert [shec_reference_chunks(p, set(range(12)) - {p})
            for p in range(12)] == [4] * 10 + [8, 8]
    changed = {str(pgid): [i for i, (o, was) in enumerate(zip(
        r.osdmap.pg_to_up_acting_osds(pgid)[1], r.before[pgid]))
        if o != was] for pgid in r.pgids}
    held = 0
    for d in pushed_docs(r):
        pgid = d["trace_id"].split(":")[1]
        if len(changed[pgid]) != 1:
            continue
        (lost,) = {p["args"]["shard"] for p in spans(d, "rebuild.push")}
        assert [lost] == changed[pgid]
        read = spans(d, "rebuild.read")[0]["args"]
        want = shec_reference_chunks(lost, set(range(12)) - {lost})
        assert (read["chunks"], read["planned"], read["widened"]) == \
            (want, want, 0), d
        assert read["path"] == ("local" if want < 8 else "full")
        assert read["bytes_read"] == want * OBJECT_BYTES // 8
        held += 1
    assert held


def test_the_plan_and_widening_counters_add_up(repaired):
    """`rebuild_widened` and the rebuilds that did not widen are the
    rebuilds that read and pushed; `rebuild_planned_chunks` is the sum
    of what their first plans named.  A plan names at most what the
    code reads for an object, and a rebuild that did not widen had in
    hand what its plan named."""
    r = repaired
    reads = [spans(d, "rebuild.read")[0]["args"] for d in pushed_docs(r)]
    reads = [a for a in reads if a["path"] != "cache"]
    assert reads
    widened = sum(a["widened"] for a in reads)
    assert {a["widened"] for a in reads} <= {0, 1}
    assert r.delta["rebuild_widened"] == widened
    assert widened + sum(1 for a in reads if not a["widened"]) == \
        r.delta["rebuild_local"] + r.delta["rebuild_full"]
    assert r.delta["rebuild_planned_chunks"] == \
        sum(a["planned"] for a in reads)
    k = int(r.spec["profile"]["k"])
    for a in reads:
        assert 1 <= a["planned"] <= k
        if not a["widened"]:
            assert a["chunks"] == a["planned"]
    # the plan is made over the positions that hold the object: what
    # still widens is the first rebuild of a session, planned before
    # the role audit said which other positions are owed
    assert widened <= len(r.pgids)


def test_nothing_is_owed_once_the_cluster_is_clean(repaired):
    """The primaries' record of owed (object, position) pairs and
    `pg_repairing` say the same: while a pair is owed the cluster is
    not clean, after the backfill sessions and the role audits it is
    empty, and it was in use on the way."""
    r = repaired
    assert r.owed_at_clean == {}
    assert not any(r.repairing_at_clean)
    assert owed_pairs(r.cluster) == {}
    assert any(owed for _clean, owed in r.owed_samples)
    assert not [owed for clean, owed in r.owed_samples if clean and owed]
    # a pair is an object of the pool at a position of the code
    for _clean, owed in r.owed_samples:
        for by_pg in owed.values():
            for pairs in by_pg.values():
                assert set(pairs) <= set(r.objects)
                assert all(0 <= p < r.spec["width"]
                           for at in pairs.values() for p in at)


def test_a_rebuild_widens_past_a_planned_source_that_is_behind(repaired):
    """The plan for the lost position names a source that has not
    applied the object's version: the version gate passes it by, the
    read widens as an object's read does, and the shard file that
    lands is the reference's.  (After the drill, on the clean pool;
    the source's stamp is put back.)"""
    r = repaired
    name = sorted(r.objects)[0]
    pgid = r.osdmap.object_to_pg(r.io.pool_id, name)
    _up, acting = r.osdmap.pg_to_up_acting_osds(pgid)
    primary = r.cluster.osds[acting[0]]
    pg = primary.pgs[pgid]
    cur = tuple(pg.pglog.objects[name])
    lost = r.spec["width"] - 1
    live = [p for p in range(r.spec["width"]) if p != lost]
    plan = ecutil.minimum_shards(pg._ec_codec(), live, [lost])
    behind = max(plan)                      # a peer's, not position 0
    assert behind != 0 and lost not in plan

    def stamp(position, ver):
        r.cluster.osds[acting[position]].store.apply_transaction(
            Transaction().setattr(pg.cid, shard_oid(name, position),
                                  VER_KEY, repr(ver).encode()))

    holder = r.cluster.osds[acting[lost]]
    soid = shard_oid(name, lost)
    holder.store.apply_transaction(Transaction().remove(pg.cid, soid))
    asked, real = [], primary.ec_fetch_shards

    def spy(pgid_, oid_, targets, **kw):
        asked.append(sorted(s for s, _o in targets))
        return real(pgid_, oid_, targets, **kw)

    primary.ec_fetch_shards = spy
    widened = primary.asok.execute("perf dump")["osd"]["ec_read_widened"]
    try:
        stamp(behind, (cur[0], cur[1] - 1))
        assert primary._ec_rebuild(pgid, name, cur, [(lost, acting[lost])],
                                   retry=False)
    finally:
        del primary.ec_fetch_shards
        stamp(behind, cur)
    # the plan's peers first, then every other holder but the lost
    # one (position 0 is the primary's own file, read and not asked)
    assert asked == [[p for p in plan if p != 0],
                     [p for p in live if p not in plan and p != 0]]
    assert primary.asok.execute("perf dump")["osd"]["ec_read_widened"] \
        == widened + 1
    end = time.time() + 30
    while not holder.store.exists(pg.cid, soid):
        assert time.time() < end, "the push never landed"
        time.sleep(0.05)
    config = {"pool_profile": r.spec["profile"], "stripe_unit": UNIT,
              "shards": r.spec["width"]}
    want_data, want_crc = r.spec["reference"].stored(
        r.objects[name], config)[lost]
    assert bytes(holder.store.read(pg.cid, soid)) == want_data
    hinfo = denc.loads(holder.store.getattr(pg.cid, soid, HINFO_KEY))
    assert want_crc is None or hinfo["crc"] == want_crc
    assert hinfo["shard"] == lost and hinfo["size"] == OBJECT_BYTES
    assert r.io.read(name) == r.objects[name]


def test_a_rebuild_plans_around_a_position_still_owed(repaired):
    """A second position of the lost one's plan (for shec: of its
    shingle) is still owed its shard and holds nothing: the primary
    knows, the plan is made over the positions that hold the object
    and names neither, ONE gather serves (`widened` 0, the counter of
    widened reads unmoved), and what lands is the reference's shard.
    Then the owed position is rebuilt too.  (After the drill, on the
    clean pool.)"""
    r = repaired
    name = sorted(r.objects)[1]
    pgid = r.osdmap.object_to_pg(r.io.pool_id, name)
    _up, acting = r.osdmap.pg_to_up_acting_osds(pgid)
    primary = r.cluster.osds[acting[0]]
    pg = primary.pgs[pgid]
    cur = tuple(pg.pglog.objects[name])
    width = r.spec["width"]
    lost = 1
    first = ecutil.minimum_shards(
        pg._ec_codec(), [p for p in range(width) if p != lost], [lost])
    owed = max(first)                       # a peer's, not position 0
    assert owed not in (0, lost)
    config = {"pool_profile": r.spec["profile"], "stripe_unit": UNIT,
              "shards": width}
    want = r.spec["reference"].stored(r.objects[name], config)
    for position in (lost, owed):
        r.cluster.osds[acting[position]].store.apply_transaction(
            Transaction().remove(pg.cid, shard_oid(name, position)))
    asked, real = [], primary.ec_fetch_shards

    def spy(pgid_, oid_, targets, **kw):
        asked.append(sorted(s for s, _o in targets))
        return real(pgid_, oid_, targets, **kw)

    def perf(counter: str) -> int:
        return primary.asok.execute("perf dump")["osd"][counter]

    def landed(position: int) -> None:
        holder = r.cluster.osds[acting[position]]
        soid = shard_oid(name, position)
        end = time.time() + 30
        while not holder.store.exists(pg.cid, soid):
            assert time.time() < end, "the push never landed"
            time.sleep(0.05)
        want_data, want_crc = want[position]
        assert bytes(holder.store.read(pg.cid, soid)) == want_data
        hinfo = denc.loads(holder.store.getattr(pg.cid, soid, HINFO_KEY))
        assert want_crc is None or hinfo["crc"] == want_crc

    widened = perf("ec_read_widened"), perf("rebuild_widened")
    primary.ec_fetch_shards = spy
    primary._rebuild_owed(pgid, [(name, owed)], +1)
    try:
        assert primary.rebuilds_owed(pgid, name) == {owed}
        assert primary.pg_repairing(pgid) == "recovering"
        trace = f"rebuild:{pgid}:{name}:s{lost}:around"
        assert primary._rebuild_op(trace, pgid, name, cur,
                                   [(lost, acting[lost])], retry=False)
    finally:
        del primary.ec_fetch_shards
        primary._rebuild_owed(pgid, [(name, owed)], -1)
    assert primary.rebuilds_owed(pgid, name) == set()
    assert len(asked) == 1 and not {lost, owed} & set(asked[0])
    assert (perf("ec_read_widened"), perf("rebuild_widened")) == widened
    landed(lost)

    def docs() -> list:
        # the op closes when its push is acknowledged
        return [d for d in primary.asok.execute("dump_historic_ops")["ops"]
                if d["trace_id"] == trace and d["kind"] == "recovery"
                and d["description"].startswith("rebuild(")]

    r.cluster._wait(docs, 30, "the rebuild's op never closed")
    (doc,) = docs()
    read = spans(doc, "rebuild.read")[0]["args"]
    assert read["widened"] == 0 and read["chunks"] == read["planned"]
    # position 0 is the primary's own file: read, not asked
    assert read["planned"] == len(asked[0]) + 1
    assert not {lost, owed} & set(spans(doc, "gather_wait")[-1]
                                  ["args"]["chunks"])
    assert primary._rebuild_op(f"rebuild:{pgid}:{name}:s{owed}:after",
                               pgid, name, cur, [(owed, acting[owed])],
                               retry=False)
    landed(owed)
    assert r.io.read(name) == r.objects[name]


def test_no_owed_pair_outlives_an_interval_change():
    """A second OSD is marked out in the MIDDLE of the repair of the
    first (a backfill session running, role-audit rebuilds owed): the
    PGs change interval under both.  While any pair is owed the
    cluster does not call itself clean, and once it does no primary
    owes anything: no pair of the dead interval is left behind."""
    cluster = MiniCluster(
        num_mons=1, num_osds=8, store_kind="memstore",
        conf=Config({"osd_heartbeat_interval": 0.5,
                     "osd_heartbeat_grace": 5.0,
                     "mon_osd_min_down_reporters": 2,
                     "osd_pg_log_max_entries": 4,
                     "osd_backfill_scan_batch": 4})).start()
    try:
        rados = cluster.client()
        rados.create_ec_pool("owed", "owed-profile", {
            "plugin": "tpu", "technique": "reed_sol_van", "k": "4",
            "m": "2", "host_cutover": "1", "stripe_unit": str(UNIT)},
            pg_num=4)
        io = rados.open_ioctx("owed")
        end = time.time() + 60
        while True:
            try:
                io.write_full("settle", b"s")
                break
            except RadosError:
                assert time.time() < end
                time.sleep(0.3)
        io.remove_object("settle")
        rng = np.random.default_rng(50)
        objects = {f"obj{i:03d}": rng.integers(
            0, 256, 16 * 1024, dtype=np.uint8).tobytes() for i in range(48)}
        for name, data in objects.items():
            io.write_full(name, data)
        cluster.wait_for_clean(timeout=60)
        assert owed_pairs(cluster) == {}
        osdmap = cluster.leader().osdmon.osdmap
        pgids = [p for p in osdmap.all_pgs() if p.pool == io.pool_id]
        primaries = {osdmap.pg_to_up_acting_osds(p)[1][0] for p in pgids}
        first, second = sorted(set(range(8)) - primaries)[:2]

        def fail(victim: int) -> None:
            cluster.kill_osd(victim)
            cluster.mark_osd_down(victim)
            cluster.mark_osd_out(victim)

        fail(first)
        watch = OwedWatch(cluster)
        watch.start()
        # in the middle of both: a session is running and the audit's
        # rebuilds are owed
        cluster._wait(lambda: any(
            osd._backfills_active and osd._rebuilds_pending
            for osd in cluster.osds.values()), 60,
            "no repair under way to interrupt")
        before = {p: tuple(cluster.leader().osdmon.osdmap
                           .pg_to_up_acting_osds(p)[1]) for p in pgids}
        fail(second)
        cluster._wait(lambda: all(
            second not in cluster.leader().osdmon.osdmap
            .pg_to_up_acting_osds(p)[1] for p in pgids), 60,
            "the second victim is still acting")
        after = {p: tuple(cluster.leader().osdmon.osdmap
                          .pg_to_up_acting_osds(p)[1]) for p in pgids}
        assert any(before[p] != after[p] for p in pgids)
        cluster.wait_for_clean(timeout=240)
        at_clean = owed_pairs(cluster)
        samples = watch.end()
        assert at_clean == {}
        assert not any(osd.pg_repairing(p)
                       for osd in cluster.osds.values() for p in pgids)
        assert any(owed for _clean, owed in samples)
        assert not [owed for clean, owed in samples if clean and owed]
        # and it stays so: what the dead interval queued has drained
        time.sleep(1.0)
        assert owed_pairs(cluster) == {}
        for name, data in objects.items():
            assert io.read(name) == data
    finally:
        cluster.stop()
