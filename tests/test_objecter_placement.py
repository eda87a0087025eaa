"""The objecter's targets come from the map's placement table, and a
new epoch is seen at the same send as before the table was there.

An objecter and its MonClient on a messenger that only records what it
is given: maps arrive through `MonClient._handle_osdmap` (the wire's
path), ops leave through `Objecter._send`.  The live clusters of
tests/test_objecter_resend.py hold the counters' place in `perf dump`.
"""

import pytest

from ceph_tpu.client.objecter import Objecter, _Op
from ceph_tpu.mon.client import MonClient
from ceph_tpu.mon.messages import MOSDMapMsg
from ceph_tpu.mon.monmap import MonMap
from ceph_tpu.osd.osdmap import (ERASURE, OSDMap, OSDMapIncremental, PgId,
                                 Pool)
from ceph_tpu.utils import denc
from ceph_tpu.utils.config import Config

OSDS = 13
POOL = 1


class RecordingMessenger:
    """What Objecter and MonClient ask of a messenger, and a list."""

    def __init__(self):
        self.name = "client.placement"
        self.conf = Config()
        self.sent: list[tuple[str, object]] = []

    def add_dispatcher_head(self, d) -> None:
        pass

    def send_message(self, msg, peer_name, peer_addr) -> None:
        self.sent.append((peer_name, msg))


def full_map() -> OSDMap:
    m = OSDMap()
    m.apply_incremental(OSDMapIncremental(
        epoch=1, new_up={o: ("127.0.0.1", 6800 + o) for o in range(OSDS)}))
    rule = m.crush.make_erasure_rule("ec", 8, 3)
    m.pools[POOL] = Pool(POOL, "ec", type=ERASURE, size=11, min_size=9,
                         pg_num=8, crush_ruleset=rule)
    return m


@pytest.fixture()
def client():
    msgr = RecordingMessenger()
    monc = MonClient(msgr, MonMap())
    objecter = Objecter(msgr, monc)
    m = full_map()
    monc._handle_osdmap(MOSDMapMsg(full=m.encode(), incrementals=[],
                                   epoch=m.epoch))
    assert objecter.osdmap.epoch == m.epoch
    return msgr, monc, objecter


def pending_op(objecter, oid: str) -> _Op:
    op = _Op(next(objecter._tid), POOL, oid, [("read", 0, 0)])
    with objecter._lock:
        objecter._ops[op.tid] = op
    return op


def deliver(monc, **fields) -> None:
    inc = OSDMapIncremental(epoch=monc.osdmap.epoch + 1, **fields)
    monc._handle_osdmap(MOSDMapMsg(full=None, incrementals=[denc.dumps(inc)],
                                   epoch=inc.epoch))


def moves(kind: str, m: OSDMap, pgid: PgId, old: int) -> dict:
    """An incremental's fields that take the PG's primary away from
    `old`: a down-mark and a pg_temp are laid over the table's answer,
    out and reweight are inputs of CRUSH."""
    if kind == "down":
        return {"new_down": [old]}
    if kind == "out":
        return {"new_out": [old]}
    if kind == "reweight":
        return {"new_weights": {old: 0.0}}
    acting = m.pg_to_raw_osds(pgid)
    return {"new_pg_temp": {pgid: acting[1:] + acting[:1]}}


@pytest.mark.parametrize("kind", ["down", "out", "reweight", "pg_temp"])
def test_new_epoch_moves_the_target_of_a_warm_objecter(client, kind):
    msgr, monc, objecter = client
    m = objecter.osdmap
    # warm: every PG of the pool has been a target
    oids = {}
    for i in range(400):
        oids.setdefault(m.object_to_pg(POOL, f"o{i}"), f"o{i}")
    assert len(oids) == 8
    for oid in oids.values():
        for _ in range(3):
            objecter._send(pending_op(objecter, oid), "map")
    perf = objecter.perf
    assert perf.value("placement_miss") == 8
    assert perf.value("placement_hit") == 16
    pgid, oid = sorted(oids.items())[0]
    with objecter._lock:
        objecter._ops.clear()
    op = pending_op(objecter, oid)
    old = objecter._send(op, "map")
    assert old == m.pg_primary(pgid) and msgr.sent[-1][0] == f"osd.{old}"
    epoch = m.epoch
    resent_map = perf.value("op_resend_map")
    del msgr.sent[:]

    # the new epoch arrives: the pending op is sent again at once, by
    # the map's handler, to the primary the NEW map names
    deliver(monc, **moves(kind, m, pgid, old))
    now = objecter.osdmap
    assert now.epoch == epoch + 1
    want = OSDMap.decode(now.encode()).pg_primary(pgid)
    assert want is not None and want != old
    assert [(peer, msg.tid, msg.epoch, msg.attempt)
            for peer, msg in msgr.sent] == [
                (f"osd.{want}", op.tid, epoch + 1, 2)]
    assert op.primary == want
    assert perf.value("op_resend_map") == resent_map + 1
    # and so is the next send, whatever causes it
    assert objecter._send(op, "timer") == want
    assert msgr.sent[-1][0] == f"osd.{want}"
    # every other PG's target is what a fresh map says, too
    cold = OSDMap.decode(now.encode())
    for p, o in oids.items():
        assert objecter._send(pending_op(objecter, o), "map") \
            == cold.pg_primary(p)


def test_a_full_map_that_replaces_the_held_one_is_counted_too(client):
    msgr, monc, objecter = client
    perf = objecter.perf
    op = pending_op(objecter, "o0")
    old = objecter._send(op, "map")
    assert (perf.value("placement_miss"), perf.value("placement_hit")) \
        == (1, 0)
    held = objecter.osdmap
    nxt = OSDMap.decode(held.encode())
    nxt.apply_incremental(OSDMapIncremental(epoch=held.epoch + 1,
                                            new_out=[old]))
    monc._handle_osdmap(MOSDMapMsg(full=nxt.encode(), incrementals=[],
                                   epoch=nxt.epoch))
    assert objecter.osdmap is not held
    assert objecter.osdmap.epoch == held.epoch + 1
    # the handler's resend went to the new map's primary: a miss of
    # the new instance, on the same counters
    assert msgr.sent[-1][0] == f"osd.{op.primary}"
    assert op.primary == nxt.pg_primary(
        nxt.object_to_pg(POOL, "o0")) != old
    assert perf.value("placement_miss") == 2
    objecter._send(op, "timer")
    assert perf.value("placement_hit") == 1
    dump = objecter.perf_dump()["objecter"]
    assert dump["placement_miss"] == 2 and dump["placement_hit"] == 1
