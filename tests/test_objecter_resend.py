"""When the objecter sends an op again, and when it tears a link down.

A silent op is resent on evidence: a timer that follows the reply
latency this client has seen from the op's target (floor
objecter_backoff_base), a reset of an established session, a map
change.  A connection is marked down only when the LINK has been quiet
for objecter_silent_kick, never because one op is slow.  Both
messenger stacks stamp `last_recv` on a connection for every frame
they read there, so every cluster test runs on both.
"""

import threading
import time

import pytest

from ceph_tpu.client import Rados, RadosError
from ceph_tpu.client.objecter import (ETIMEDOUT, ObjecterError,
                                      _ReplyLatency)
from ceph_tpu.msg.messenger import Policy
from ceph_tpu.utils import faults
from ceph_tpu.utils.config import Config
from ceph_tpu.vstart import MiniCluster

BASE = 0.2          # objecter_backoff_base of these clusters
KICK = 1.0          # objecter_silent_kick
CONF = {
    "mon_tick_interval": 0.5,
    "osd_heartbeat_interval": 0.5,
    "osd_heartbeat_grace": 8.0,
    "mon_osd_min_down_reporters": 2,
    "mon_osd_down_out_interval": 5.0,
    "objecter_backoff_base": BASE,
    "objecter_silent_kick": KICK,
}
POOL = "resend"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.get().reset(seed=0)
    yield
    faults.get().reset(seed=0)


@pytest.fixture(scope="module", params=["blocking", "async"])
def cluster(request):
    c = MiniCluster(num_mons=1, num_osds=3,
                    conf=Config(dict(CONF, ms_type=request.param))).start()
    c.client().create_pool(POOL, pg_num=8)
    yield c
    c.stop()


_names = iter(range(10 ** 6))


def _client(cluster, policy=None):
    """A client of its own (nothing seen of any target yet) on a
    settled pool, and the conf as the test found it."""
    saved = {k: cluster.conf.get_val(k) for k in (
        "objecter_backoff_base",
        "osd_debug_inject_dispatch_delay_probability",
        "osd_debug_inject_dispatch_delay_duration")}
    rados = Rados(cluster.monmap, f"client.resend{next(_names)}",
                  conf=cluster.conf)
    if policy is not None:
        rados.msgr.set_policy("osd", policy)
    rados.connect()
    io = rados.open_ioctx(POOL)
    _retry(lambda: io.write_full("settle", b"s"))
    yield rados, io
    for k, v in saved.items():
        cluster.conf.set_val(k, v)
    faults.get().reset(seed=0)
    # duplicates a test left in an op shard are served before the next
    _retry(lambda: io.write_full("settle", b"s"))
    rados.shutdown()


client = pytest.fixture(_client)


@pytest.fixture
def lossy_client(cluster):
    """librados' policy towards OSDs (Policy::lossy_client): a session
    that fails is dropped with what it had queued and the dispatchers
    hear of it (ms_handle_reset).  This repo's clients keep the
    messenger's default, a lossless session that redials and replays
    its unacked frames itself, and never see a reset of an OSD link."""
    yield from _client(cluster, Policy.lossy_client())


def _retry(fn, window=60.0):
    end = time.time() + window
    while True:
        try:
            return fn()
        except RadosError:
            if time.time() > end:
                raise
            time.sleep(0.3)


def _primary(rados, io, oid) -> int:
    m = rados.objecter.osdmap
    return m.pg_primary(m.object_to_pg(io.pool_id, oid))


def _oids_on(rados, io, primary, n, prefix):
    """`n` object names of distinct PGs whose primary is `primary`."""
    m = rados.objecter.osdmap
    out, pgs = [], set()
    for i in range(10000):
        oid = f"{prefix}{i}"
        pgid = m.object_to_pg(io.pool_id, oid)
        if m.pg_primary(pgid) == primary and pgid not in pgs:
            pgs.add(pgid)
            out.append(oid)
            if len(out) == n:
                return out
    raise AssertionError(f"no {n} pgs with primary osd.{primary}")


def _counters(rados) -> dict:
    return rados.perf_dump()["objecter"]


def _submit(rados, io, oid, ops, out, timeout=30.0):
    def run():
        t0 = time.monotonic()
        try:
            out["reply"] = rados.objecter.op_submit(io.pool_id, oid, ops,
                                                    timeout=timeout)
        except Exception as e:        # pragma: no cover
            out["error"] = e
        out["took"] = time.monotonic() - t0
    th = threading.Thread(target=run)
    th.start()
    return th


def _drop_first_send(rados, io, oid, ops, src, dst, lift_after):
    """Submit while frames src->dst are lost, lift the loss after
    `lift_after` seconds, and wait for the op."""
    rid = faults.get().drop(dst, 1.0, src=src)
    out: dict = {}
    th = _submit(rados, io, oid, ops, out)
    time.sleep(lift_after)
    faults.get().clear(rid)
    th.join(timeout=60)
    assert not th.is_alive() and "error" not in out, out
    assert out["reply"].result == 0
    return out


# -- the estimator alone ------------------------------------------------------


def test_latency_estimate_follows_first_send_replies_and_holds_a_backoff():
    seen = _ReplyLatency()
    assert seen.timeout(0.5) == 0.5           # nothing seen: the floor
    seen.sample(2.0)
    assert (seen.srtt, seen.dev) == (2.0, 1.0)
    assert seen.timeout(0.5) == 6.0
    for _ in range(40):
        seen.sample(2.0)
    assert 2.0 <= seen.timeout(0.5) < 2.01    # steady replies: no slack
    seen.held = 8.0                           # a resent op took up to 8
    assert seen.timeout(0.5) == 8.0
    seen.sample(2.0)                          # a clean sample lifts it
    assert seen.timeout(0.5) < 2.01
    fast = _ReplyLatency()
    for _ in range(10):
        fast.sample(0.003)
    assert fast.timeout(0.5) == 0.5           # a fast target: the floor


# -- the timer ----------------------------------------------------------------


def test_slow_live_primary_is_sent_once_and_not_kicked(cluster, client):
    """Replies take 1.5 s, longer than the kick window and seven times
    the floor.  Once the objecter has seen that, it waits."""
    rados, io = client
    cluster.conf.set_val("osd_debug_inject_dispatch_delay_duration", 1.5)
    cluster.conf.set_val("osd_debug_inject_dispatch_delay_probability", 1.0)
    # learn without a resend in the way: a floor above the latency
    cluster.conf.set_val("objecter_backoff_base", 5.0)
    for _ in range(3):
        io.write_full("slow", b"w")
    cluster.conf.set_val("objecter_backoff_base", BASE)
    primary = _primary(rados, io, "slow")
    rto = rados.objecter.resend_timeout(primary, True)
    assert 1.5 < rto < 5.0, rto
    assert rados.objecter.resend_timeout(primary, False) == BASE
    conn = rados.msgr.conns[f"osd.{primary}"]
    before = _counters(rados)
    t0 = time.monotonic()
    io.write_full("slow", b"x")
    assert time.monotonic() - t0 >= 1.5 > KICK
    after = _counters(rados)
    assert after["op_send"] == before["op_send"] + 1
    assert after["op_resend"] == before["op_resend"] == 0
    assert after["conn_kick"] == 0
    assert rados.msgr.conns[f"osd.{primary}"] is conn
    assert after["resend_timeout"][f"osd.{primary}/write"] == \
        rados.objecter.resend_timeout(primary, True)


def test_dropped_request_on_an_idle_cluster_is_resent_at_the_floor(
        cluster, client):
    rados, io = client
    for i in range(5):                  # replies in milliseconds
        io.write_full("idle", b"i")
    primary = _primary(rados, io, "idle")
    assert rados.objecter.resend_timeout(primary, True) < 2 * BASE
    out = _drop_first_send(rados, io, "idle", [("writefull", b"again")],
                           src=rados.msgr.name, dst="osd.*",
                           lift_after=BASE / 2)
    # (bounds with room for a loaded machine: the resend leaves at the
    # floor, and its reply comes when the cluster gets to it)
    assert BASE <= out["took"] < 2 * BASE + 1.0, out["took"]
    c = _counters(rados)
    assert 1 <= c["op_resend_timer"] == c["op_resend"] <= 2
    assert c["conn_kick"] == 0
    assert io.read("idle") == b"again"


def test_dropped_reply_is_answered_again_and_the_write_runs_once(
        cluster, client):
    rados, io = client
    io.write_full("once", b"")
    out = _drop_first_send(rados, io, "once", [("append", b"abc")],
                           src="osd.*", dst=rados.msgr.name,
                           lift_after=BASE / 2)
    assert out["took"] >= BASE
    assert _counters(rados)["op_resend_timer"] >= 1
    assert io.read("once") == b"abc"            # not b"abcabc"
    pgid = rados.objecter.osdmap.object_to_pg(io.pool_id, "once")
    pg = cluster.osds[_primary(rados, io, "once")].pgs[pgid]
    assert (rados.msgr.name, out["reply"].tid) in pg._completed_reqs


def test_resent_op_latency_does_not_enter_the_estimate(cluster, client):
    rados, io = client
    for i in range(5):
        io.write_full("karn", b"k")
    primary = _primary(rados, io, "karn")
    seen = rados.objecter._latency[(primary, True)]
    srtt, dev = seen.srtt, seen.dev
    _drop_first_send(rados, io, "karn", [("writefull", b"k2")],
                     src=rados.msgr.name, dst="osd.*", lift_after=BASE / 2)
    assert (seen.srtt, seen.dev) == (srtt, dev)
    # the time it took bounds the target's latency and is held for the
    # target's next op ...
    held = seen.held
    assert BASE <= held < 2 * BASE + 1.0
    assert rados.objecter.resend_timeout(primary, True) == held
    io.write_full("karn", b"k3")
    # ... and an op answered on its first send lifts it
    assert seen.held == 0.0 and (seen.srtt, seen.dev) != (srtt, dev)
    assert rados.objecter.resend_timeout(primary, True) < held


# -- the reset ----------------------------------------------------------------


def test_placement_lookups_are_in_both_perf_dumps(cluster, client):
    """`placement_hit` / `placement_miss`: in the client's `perf dump`
    beside `op_send`, in every OSD's under `osd`.  A client's first
    send to a PG is worked out by CRUSH, every later one looked up;
    an OSD works each PG out once at the epoch that brings the pool
    and looks it up at every later one."""
    rados, io = client
    oid = "placed"
    pgid = rados.objecter.osdmap.object_to_pg(io.pool_id, oid)
    before = _counters(rados)
    assert before["placement_miss"] >= 1          # `settle` was placed
    m = rados.objecter.osdmap
    held = m._placement is not None and pgid in m._placement[1]
    for _ in range(5):
        io.write_full(oid, b"p" * 64)
    after = _counters(rados)
    sends = after["op_send"] - before["op_send"]
    missed = after["placement_miss"] - before["placement_miss"]
    assert sends >= 5 and missed == (0 if held else 1)
    assert after["placement_hit"] - before["placement_hit"] \
        == sends - missed
    pgs = len(rados.objecter.osdmap.all_pgs())
    for osd in cluster.osds.values():
        dump = osd.asok.execute("perf dump")["osd"]
        assert dump["placement_miss"] >= pgs
        assert dump["placement_hit"] >= 0


def _reset_link(rados, peer: str) -> None:
    """Lose the client's socket to `peer` under it, as a peer's reset
    or a dead route would."""
    conn = rados.msgr.conns[peer]
    if hasattr(conn, "worker"):                 # event-loop stack
        conn.worker.call(
            lambda: conn._cur[0]._fail(ConnectionResetError("test")))
    else:
        rados.msgr._loop_call(conn._writer.transport.abort)


def test_connection_reset_resends_the_peers_pending_ops_at_once(
        cluster, lossy_client):
    rados, io = lossy_client
    cluster.conf.set_val("objecter_backoff_base", 5.0)   # no timer in time
    primary = _primary(rados, io, "settle")
    a, b, other = (_oids_on(rados, io, primary, 2, "rs") +
                   _oids_on(rados, io, (primary + 1) % 3, 1, "ro"))
    io.write_full(other, b"o")          # a session to the other osd too
    rid = faults.get().drop("osd.*", 1.0, src=rados.msgr.name)
    outs = [{}, {}, {}]
    ths = [_submit(rados, io, oid, [("writefull", b"r")], out)
           for oid, out in zip((a, b, other), outs)]
    time.sleep(0.3)
    assert all("reply" not in o for o in outs)
    faults.get().clear(rid)
    _reset_link(rados, f"osd.{primary}")
    end = time.monotonic() + 3
    while _counters(rados)["op_resend"] < 2 and time.monotonic() < end:
        time.sleep(0.02)
    # the reset peer's two ops were sent again at once, and no other
    c = _counters(rados)
    assert c["op_resend_reset"] == c["op_resend"] == 2, c
    assert c["op_resend_timer"] == 0 and c["conn_kick"] == 0
    # (the peer's own session back to this client falls with the
    # reset, and a reply written into it meanwhile falls with it: such
    # a reply is fetched by the timer, like the third op's request,
    # which no reset spoke of)
    assert "reply" not in outs[2]
    for th in ths:
        th.join(timeout=30)
    assert all(o["reply"].result == 0 for o in outs), outs
    assert outs[2]["took"] >= 5.0
    assert _counters(rados)["op_resend_timer"] >= 1


def test_unreachable_peer_resets_do_not_resend_in_a_loop(
        cluster, lossy_client):
    """Every dial to a partitioned peer is reset at once.  Those
    resets resend nothing: the ops stay with the timer, which backs
    off."""
    rados, io = lossy_client
    faults.get().partition("client.*", "osd.*")
    with pytest.raises(ObjecterError):
        rados.objecter.op_submit(io.pool_id, "walled",
                                 [("writefull", b"x")], timeout=1.5)
    c = _counters(rados)
    assert c["op_resend_reset"] == 0
    assert 1 <= c["op_resend_timer"] <= 4       # 0.2, 0.6, 1.4


# -- the kick -----------------------------------------------------------------


def test_wedged_link_is_kicked_and_the_op_completes_after_the_heal(
        cluster, client):
    """A link that is up and mute: the client's frames sit in its send
    queue (a 20 s delay on each), so nothing is sent and nothing comes
    back, not even an ack."""
    rados, io = client
    rid = faults.get().delay("osd.*", 20.0, src=rados.msgr.name)
    out: dict = {}
    th = _submit(rados, io, "wedged", [("writefull", b"through")], out)
    end = time.monotonic() + 10
    while _counters(rados)["conn_kick"] == 0 and time.monotonic() < end:
        time.sleep(0.05)
    assert _counters(rados)["conn_kick"] >= 1
    assert "reply" not in out
    faults.get().clear(rid)
    th.join(timeout=30)
    assert not th.is_alive() and "error" not in out, out
    assert out["reply"].result == 0
    assert KICK <= out["took"] < 15
    assert io.read("wedged") == b"through"


def test_link_that_carries_replies_is_not_kicked_under_a_slow_op(
        cluster, client):
    """An op outlives the kick window on a client that knows nothing
    of its target yet, so the timer resends it at 0.2, 0.6 and 1.4 s.
    Every resend is acked and another op's reply arrives meanwhile:
    the link is alive and stays."""
    rados, io = client
    primary = _primary(rados, io, "settle")
    a, b = _oids_on(rados, io, primary, 2, "lk")
    conn = rados.msgr.conns[f"osd.{primary}"]
    cluster.conf.set_val("osd_debug_inject_dispatch_delay_duration", 1.6)
    cluster.conf.set_val("osd_debug_inject_dispatch_delay_probability", 1.0)
    outs = [{}, {}]
    ths = [_submit(rados, io, oid, [("writefull", b"v")], out)
           for oid, out in zip((a, b), outs)]
    for th in ths:
        th.join(timeout=60)
    cluster.conf.set_val("osd_debug_inject_dispatch_delay_probability", 0.0)
    for out in outs:
        assert out["reply"].result == 0 and out["took"] >= 1.6 > KICK
    c = _counters(rados)
    assert c["op_resend_timer"] >= 4            # they were resent ...
    assert c["conn_kick"] == 0                  # ... and the link kept
    assert rados.msgr.conns[f"osd.{primary}"] is conn
    assert conn.last_recv > 0


def test_unanswerable_op_fails_with_etimedout_at_its_deadline(
        cluster, client):
    rados, io = client
    faults.get().partition("client.*", "osd.*")
    t0 = time.monotonic()
    with pytest.raises(ObjecterError) as ei:
        rados.objecter.op_submit(io.pool_id, "never",
                                 [("writefull", b"x")], timeout=2.0)
    took = time.monotonic() - t0
    assert ei.value.errno == ETIMEDOUT
    assert 2.0 <= took < 4.0, took
    assert _counters(rados)["ops_in_flight"] == 0
    faults.get().reset(seed=0)
    _retry(lambda: io.write_full("never", b"now"))
    assert io.read("never") == b"now"
