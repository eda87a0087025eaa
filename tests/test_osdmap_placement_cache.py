"""The OSDMap's placement table: CRUSH's answer for a PG, looked up
while the inputs that decide it stand.

A map that has answered before ("warm") has to answer every question
exactly as a map that never has: `OSDMap.decode(m.encode())`, which
starts without a table.  The table is checked against what the map
holds, not against who changed it, so every change below is made both
ways: through `apply_incremental`, and by assignment the way tests and
tools do it.
"""

import copy
import os
import random
import sys
import threading

import pytest

from ceph_tpu.crush.map import (BUCKET_STRAW2, CrushMap, Rule, Step,
                                STEP_CHOOSE_FIRSTN, STEP_EMIT, STEP_TAKE)
from ceph_tpu.osd import osdmap as osdmap_mod
from ceph_tpu.osd.osdmap import (ERASURE, REPLICATED, OSDMap,
                                 OSDMapIncremental, PgId, Pool)
from ceph_tpu.utils import denc
from ceph_tpu.utils.perf_counters import PerfCountersBuilder

OSDS = 13
# every pool shape the benchmark's configurations use: name -> (type,
# size, min_size, pg_num, rule: ("ec", k, m) | ("lrc", k, m, group) |
# ("rep",))
SHAPES = {
    "ec-8+3": (ERASURE, 11, 9, 8, ("ec", 8, 3)),
    "ec-cauchy-6+3": (ERASURE, 9, 7, 8, ("ec", 6, 3)),
    "ec-shec-8+4": (ERASURE, 12, 9, 8, ("ec", 8, 4)),
    "ec-lrc-k4m2l3": (ERASURE, 8, 5, 8, ("ec", 4, 4)),
    "ec-lrc-k4m2l3-racks": (ERASURE, 8, 5, 8, ("lrc", 4, 4, 4)),
    "ec-2+1": (ERASURE, 3, 2, 16, ("ec", 2, 1)),
    "rep-3": (REPLICATED, 3, 2, 8, ("rep",)),
}


def racks_crush(osds: int = OSDS) -> CrushMap:
    """root -> two racks -> a host an OSD: what a locality rule needs."""
    c = CrushMap()
    root = c.new_bucket(BUCKET_STRAW2, 4, name="default")
    racks = [c.new_bucket(BUCKET_STRAW2, 2, name=f"rack{r}")
             for r in range(2)]
    for o in range(osds):
        c.add_device(o)
        host = c.new_bucket(BUCKET_STRAW2, 1, name=f"host{o}")
        host.add_item(o, 0x10000)
        racks[o % 2].add_item(host.id, host.weight)
    for rack in racks:
        root.add_item(rack.id, rack.weight)
    c.add_rule(Rule("replicated_rule", [
        Step(STEP_TAKE, root.id), Step(STEP_CHOOSE_FIRSTN, 0, 0),
        Step(STEP_EMIT)]))
    return c


def add_pool(m: OSDMap, pid: int, shape: str) -> Pool:
    typ, size, min_size, pg_num, rule = SHAPES[shape]
    if rule[0] == "ec":
        rid = m.crush.make_erasure_rule(f"ec-{pid}", rule[1], rule[2])
    elif rule[0] == "lrc":
        rid = m.crush.make_locality_rule(f"lrc-{pid}", rule[1], rule[2],
                                         rule[3], "rack", "host")
    else:
        rid = 0
    pool = m.pools[pid] = Pool(pid, shape, type=typ, size=size,
                               min_size=min_size, pg_num=pg_num,
                               crush_ruleset=rid)
    m.pool_max = max(m.pool_max, pid)
    return pool


def make_map(shapes=tuple(SHAPES), racks: bool | None = None) -> OSDMap:
    """Thirteen OSDs up and in, and one pool of each shape asked for
    (the vstart map's flat root, or racks where a shape's rule needs
    them)."""
    m = OSDMap()
    m.apply_incremental(OSDMapIncremental(
        epoch=1, new_up={o: ("127.0.0.1", 6800 + o) for o in range(OSDS)}))
    if racks if racks is not None else any(
            SHAPES[s][4][0] == "lrc" for s in shapes):
        m.crush = racks_crush()
    for pid, shape in enumerate(shapes, 1):
        add_pool(m, pid, shape)
    return m


def fresh(m: OSDMap) -> OSDMap:
    return OSDMap.decode(m.encode())


def answers(m: OSDMap, pgid: PgId):
    return (m.pg_to_raw_osds(pgid), m.pg_to_up_acting_osds(pgid),
            m.pg_primary(pgid))


def counters():
    return (PerfCountersBuilder("placement")
            .add_u64_counter("placement_hit")
            .add_u64_counter("placement_miss").create_perf_counters())


# -- (a) every pool shape: warm equals fresh --------------------------------


@pytest.mark.parametrize("shape", list(SHAPES))
def test_warm_map_answers_as_a_fresh_one(shape):
    m = make_map((shape,))
    perf = counters()
    m.count_placement(perf)
    pgs = m.all_pgs()
    assert len(pgs) == SHAPES[shape][3]
    first = {p: answers(m, p) for p in pgs}          # fills the table
    assert perf.value("placement_miss") == len(pgs)
    cold = fresh(m)
    assert cold._placement is None
    for p in pgs:
        want = answers(cold, p)
        assert first[p] == want
        assert answers(m, p) == want                 # from the table
        raw, (up, acting), primary = want
        assert len(raw) == SHAPES[shape][1]
        assert primary is not None and primary in acting
    assert perf.value("placement_miss") == len(pgs)
    assert perf.value("placement_hit") == 5 * len(pgs)


def test_answers_are_the_callers_own_lists():
    m = make_map(("ec-8+3", "rep-3"))
    for p in m.all_pgs():
        want = answers(fresh(m), p)
        raw = m.pg_to_raw_osds(p)
        raw.reverse()
        raw.append(99)
        up, acting = m.pg_to_up_acting_osds(p)
        up.clear()
        assert answers(m, p) == want


# -- (b) every kind of change, both ways ------------------------------------

KINDS = ("down", "up", "out", "in", "reweight", "pg_temp_set",
         "pg_temp_clear", "new_crush", "crush_in_place", "pool_size",
         "pool_ruleset", "pool_pg_num", "pool_recreate")
WALK = ("ec-8+3", "ec-2+1", "rep-3")


class Walk:
    """A seeded sequence of map changes on one warm map.  Each step:
    ask some PGs (so the table holds the OLD answers), change the map,
    compare with a fresh decode."""

    def __init__(self, seed: int, way: str):
        self.rng = random.Random(seed)
        self.way = way
        self.m = make_map(WALK, racks=False)
        self.kinds_done: set[str] = set()
        self.most_pgs = 0

    def _inc(self, **fields) -> None:
        self.m.apply_incremental(
            OSDMapIncremental(epoch=self.m.epoch + 1, **fields))

    def _edited_crush(self, c: CrushMap) -> None:
        """One change through crush/'s own mutators (or, the direct
        way, to what they hold)."""
        rng, root = self.rng, c.bucket_by_name("default")
        pick = rng.randrange(5)
        if pick == 0 and len(root.items) > 12:
            root.remove_item(rng.choice(root.items))
        elif pick == 1:
            gone = sorted(c.devices - set(root.items))
            if gone:
                root.add_item(gone[0], rng.choice((0x8000, 0x10000,
                                                   0x20000)))
            else:
                c.add_device(max(c.devices) + 1)
        elif pick == 2:
            root.weights[rng.randrange(len(root.weights))] = \
                rng.choice((0x4000, 0x10000, 0x30000))
        elif pick == 3:
            c.tunables.chooseleaf_vary_r ^= 1
            c.tunables.choose_total_tries = rng.choice((19, 50))
        else:
            rule = rng.choice(c.rules)
            for step in rule.steps:
                if step.op == "set_chooseleaf_tries":
                    step.arg1 = rng.choice((3, 5, 7))
            c.add_rule(Rule(f"extra{len(c.rules)}", [
                Step(STEP_TAKE, root.id), Step(STEP_CHOOSE_FIRSTN, 0, 0),
                Step(STEP_EMIT)]))

    def change(self, kind: str) -> None:
        m, rng = self.m, self.rng
        direct = self.way == "direct" or (
            self.way == "mixed" and rng.random() < 0.5)
        osd = rng.randrange(OSDS)
        pid = rng.choice(sorted(m.pools))
        pgid = PgId(pid, rng.randrange(m.pools[pid].pg_num))
        if kind in ("out", "reweight"):
            # one OSD short of its weight at a time, or CRUSH spends
            # the test's time on retries: the last one comes back first
            for o, info in m.osds.items():
                if info.state_weight() < 0x10000:
                    if direct:
                        info.in_cluster, info.weight = True, 1.0
                    else:
                        self._inc(new_in=[o], new_weights={o: 1.0})
        if kind == "down":
            if direct:
                m.osds[osd].up = False
            else:
                self._inc(new_down=[osd])
        elif kind == "up":
            if direct:
                m.osds[osd].up = True
            else:
                self._inc(new_up={osd: ("127.0.0.1", 6800 + osd)})
        elif kind == "out":
            if direct:
                m.osds[osd].in_cluster = False
            else:
                self._inc(new_out=[osd])
        elif kind == "in":
            if direct:
                m.osds[osd].in_cluster = True
            else:
                self._inc(new_in=[osd])
        elif kind == "reweight":
            w = rng.choice((0.0, 0.25, 0.5, 0.9, 1.0))
            if direct:
                m.osds[osd].weight = w
            else:
                self._inc(new_weights={osd: w})
        elif kind == "pg_temp_set":
            temp = rng.sample(range(OSDS), m.pools[pid].size)
            if direct:
                m.pg_temp[pgid] = temp
            else:
                self._inc(new_pg_temp={pgid: temp})
        elif kind == "pg_temp_clear":
            held = sorted(m.pg_temp)
            if not held:
                return
            pgid = rng.choice(held)
            if direct:
                del m.pg_temp[pgid]
            else:
                self._inc(new_pg_temp={pgid: []})
        elif kind == "new_crush":
            c = copy.deepcopy(m.crush)
            self._edited_crush(c)
            if direct:
                m.crush = c
            else:
                self._inc(new_crush=denc.dumps(c))
        elif kind == "crush_in_place":
            # no incremental changes a CRUSH map in place
            self._edited_crush(m.crush)
        elif kind in ("pool_size", "pool_ruleset", "pool_pg_num"):
            pool = m.pools[pid] if direct else copy.deepcopy(m.pools[pid])
            if kind == "pool_size":
                pool.size = rng.choice((2, 3, 5, 9, 11))
            elif kind == "pool_ruleset":
                pool.crush_ruleset = rng.randrange(len(m.crush.rules) + 1)
            else:
                pool.pg_num = rng.choice((2, 4, 8, 16))
            if not direct:
                self._inc(new_pools={pid: pool})
        elif kind == "pool_recreate":
            shape = rng.choice(WALK)
            if direct:
                del m.pools[pid]
            else:
                self._inc(removed_pools=[pid])
            self.check(8)        # the table meets a map without the pool
            if direct:
                add_pool(m, pid, shape)
            else:
                staged = copy.deepcopy(m)
                pool = add_pool(staged, pid, shape)
                self._inc(new_pools={pid: pool},
                          new_crush=denc.dumps(staged.crush))
        self.kinds_done.add(kind)

    def some_pgs(self, n: int) -> list[PgId]:
        pgs = self.m.all_pgs()
        return pgs if n >= len(pgs) else self.rng.sample(pgs, n)

    def check(self, n: int) -> None:
        cold = fresh(self.m)
        # the table takes a PG only while it has fewer than the pools
        # have PGs (what they lost goes first): never more than the
        # most the map has had
        self.most_pgs = max(self.most_pgs, len(self.m.all_pgs()))
        for p in self.some_pgs(n):
            assert answers(self.m, p) == answers(cold, p), \
                (p, self.m.epoch)
        table = self.m._placement
        assert table is None or len(table[1]) <= self.most_pgs


@pytest.mark.parametrize("way", ["incremental", "direct", "mixed"])
def test_warm_map_follows_every_change(way):
    walk = Walk(seed=4900 + len(way), way=way)
    walk.check(1000)
    for step in range(208):
        for p in walk.some_pgs(6):
            walk.m.pg_primary(p)                 # warm with the old state
        walk.change(KINDS[step % len(KINDS)] if step < 2 * len(KINDS)
                    else walk.rng.choice(KINDS))
        walk.check(1000 if step % 40 == 0 else 6)
    walk.check(1000)
    assert walk.kinds_done == set(KINDS)


@pytest.mark.parametrize("kind", KINDS)
def test_one_change_on_a_fully_warm_map(kind):
    """Each kind alone, each way, on a map whose table holds every PG:
    no answer of the old state survives the change."""
    for way in ("incremental", "direct"):
        walk = Walk(seed=49, way=way)
        if kind in ("up", "in"):
            walk.change({"up": "down", "in": "out"}[kind])
        if kind == "pg_temp_clear":
            walk.change("pg_temp_set")
        walk.check(1000)
        walk.change(kind)
        walk.check(1000)


def test_removed_pools_pgs_leave_the_table():
    m = make_map(("ec-2+1", "rep-3"))
    for p in m.all_pgs():
        m.pg_primary(p)
    assert len(m._placement[1]) == 24
    # a PG the pool does not have is answered, and not kept
    beyond = PgId(2, 4000)
    assert m.pg_to_raw_osds(beyond) == fresh(m).pg_to_raw_osds(beyond)
    assert beyond not in m._placement[1]
    # nothing below changes an input of CRUSH: the table stays until
    # a new PG finds it as large as the pools, and then starts over
    del m.pools[1]
    m.pools[2].pg_num = 4
    add_pool(m, 3, "rep-3")
    for _ in range(2):
        for p in m.all_pgs():
            m.pg_primary(p)
        assert len(m._placement[1]) <= len(m.all_pgs())
    assert set(m._placement[1]) == set(m.all_pgs())
    # a pool made again under its old id, another size
    del m.pools[3]
    m.pools[3] = Pool(3, "again", size=2, pg_num=8, crush_ruleset=0)
    for p in m.all_pgs():
        assert answers(m, p) == answers(fresh(m), p)
        assert len(m.pg_to_raw_osds(p)) == m.pools[p.pool].size
    assert set(m._placement[1]) == set(m.all_pgs())


# -- (c) the table is no part of the map ------------------------------------


def test_encoding_is_the_same_warm_and_cold():
    m = make_map()
    cold_bytes = m.encode()
    m.count_placement(counters())
    for p in m.all_pgs():
        answers(m, p)
    assert m._placement is not None and m._placement[1]
    assert m.encode() == cold_bytes
    again = OSDMap.decode(cold_bytes)
    assert again._placement is None and again._placement_perf is None
    assert again.encode() == cold_bytes


def test_copies_start_cold_and_answer_the_same():
    m = make_map(("ec-8+3", "rep-3"))
    perf = counters()
    m.count_placement(perf)            # holds a lock: not for copying
    for p in m.all_pgs():
        answers(m, p)
    misses = perf.value("placement_miss")
    for dup in (copy.deepcopy(m), copy.copy(m)):
        assert dup._placement is None and dup._placement_perf is None
        assert dup.encode() == m.encode()
        for p in m.all_pgs():
            assert answers(dup, p) == answers(m, p)
    assert perf.value("placement_miss") == misses
    # the deep copy is its own map
    deep = copy.deepcopy(m)
    deep.osds[0].in_cluster = False
    for p in m.all_pgs():
        assert answers(m, p) == answers(fresh(m), p)
        assert answers(deep, p) == answers(fresh(deep), p)


# -- (d) a warm window does no CRUSH ----------------------------------------


def test_a_warm_window_does_no_crush(monkeypatch):
    m = make_map(("ec-8+3",))
    perf = counters()
    m.count_placement(perf)
    calls = []
    real = osdmap_mod.do_rule

    def counted(*args, **kwargs):
        calls.append(args[2])
        return real(*args, **kwargs)
    monkeypatch.setattr(osdmap_mod, "do_rule", counted)
    pgs = m.all_pgs()
    assert len(pgs) == 8
    for i in range(1000):
        assert m.pg_primary(pgs[i % 8]) is not None
    assert len(calls) == 8
    assert perf.value("placement_miss") == 8
    assert perf.value("placement_hit") == 992
    # a down-mark and a pg_temp are applied to the kept answer: still
    # no CRUSH, and the answers move
    victim = m.pg_primary(pgs[0])
    m.osds[victim].up = False
    m.pg_temp[pgs[1]] = list(reversed(m.pg_to_raw_osds(pgs[1])))
    cold = fresh(m)
    for p in pgs:
        assert answers(m, p) == answers(cold, p)
    assert m.pg_primary(pgs[0]) != victim
    assert len(calls) == 8 + 8         # the fresh map's own
    assert perf.value("placement_miss") == 8
    # out is an input of CRUSH: every PG is worked out once more
    m.osds[victim].in_cluster = False
    for i in range(80):
        m.pg_primary(pgs[i % 8])
    assert perf.value("placement_miss") == 16


def test_nobody_counts_on_a_map_without_an_owner():
    m = make_map(("rep-3",))
    for p in m.all_pgs():
        assert m.pg_primary(p) == fresh(m).pg_primary(p)
    assert m._placement_perf is None


def test_lookups_beside_a_changing_map_end_up_right():
    """Readers on more threads than the machine has cores, switching
    every few bytecodes, while the map changes: whatever a reader got
    meanwhile, nothing stale is left in the table."""
    m = make_map(("ec-2+1", "rep-3"))
    pgs = m.all_pgs()
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                for p in pgs:
                    m.pg_to_up_acting_osds(p)
        except Exception as e:             # pragma: no cover
            errors.append(e)
    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range((os.cpu_count() or 8) + 4)]
    rng = random.Random(7)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for _ in range(24):
            osd = rng.randrange(OSDS)
            m.apply_incremental(OSDMapIncremental(
                epoch=m.epoch + 1,
                **rng.choice(({"new_out": [osd]}, {"new_in": [osd]},
                              {"new_weights": {osd: rng.random()}},
                              {"new_down": [osd]}))))
            # the readers run meanwhile
            assert m.pg_primary(pgs[0]) == fresh(m).pg_primary(pgs[0])
    finally:
        stop.set()
        for t in threads:
            t.join(60)
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads)
    cold = fresh(m)
    for p in pgs:
        assert answers(m, p) == answers(cold, p)
