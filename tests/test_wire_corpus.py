"""Wire/disk format non-regression corpus (the reference's
ceph-object-corpus + test/encoding/readable.sh analog).

One representative instance of every registered message type and denc
struct is encoded; the CRC32C of each encoding is pinned in
tests/data/wire_corpus.json.  A refactor that changes any wire or disk
byte fails here BEFORE it can strand persisted state or break rolling
upgrades between builds.

Regenerate (deliberate format changes only — bump DENC_VERSION and add
an upgrade path when the change touches persisted structs):
    python tests/test_wire_corpus.py --create
"""

import json
import os
import sys

CORPUS_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "wire_corpus.json")


def build_samples() -> dict:
    """name -> bytes for every wire/disk format we promise stability."""
    from ceph_tpu.crush.map import CrushMap
    from ceph_tpu.mon import messages as monm
    from ceph_tpu.mon.monmap import MonMap
    from ceph_tpu.osd import messages as osdm
    from ceph_tpu.osd.osdmap import (OSDMap, OSDMapIncremental, OsdInfo,
                                     PgId, Pool)
    from ceph_tpu.fs import messages as fsm
    from ceph_tpu.utils import denc

    samples: dict[str, bytes] = {}

    def add(name: str, obj) -> None:
        samples[name] = denc.dumps(obj)

    # -- denc structs ------------------------------------------------------
    add("PgId", PgId(3, 7))
    add("Pool", Pool(2, "p", size=3, pg_num=16, snap_seq=5,
                     removed_snaps=[2, 3]))
    add("OsdInfo", OsdInfo(up=True, in_cluster=True, weight=0.5,
                           addr=("127.0.0.1", 6800)))
    inc = OSDMapIncremental(epoch=9)
    inc.new_up[1] = ("127.0.0.1", 6801)
    inc.new_down.append(2)
    inc.new_pool_snap_seq[0] = 4
    inc.new_mgr = ("x", ("127.0.0.1", 6900))
    add("OSDMapIncremental", inc)
    m = OSDMap()
    m.fsid = "corpus-fsid"
    m.apply_incremental(OSDMapIncremental(epoch=1))
    add("OSDMap", m)
    mm = MonMap(fsid="corpus-fsid")
    mm.add("a", ("127.0.0.1", 6789))
    add("MonMap", mm)
    add("CrushMap", CrushMap.build_flat(6, hosts=2))

    # -- messages (header + payload via Message.encode) --------------------
    def addmsg(msg) -> None:
        samples[type(msg).__name__] = msg.encode(seq=7)

    addmsg(monm.MMonElection(op="propose", epoch=3, rank=0, quorum=[]))
    addmsg(monm.MMonPaxos(op="begin", pn=101, version=5, value=b"v",
                          last_committed=4))
    addmsg(monm.MMonSubscribe(what={"osdmap": 0}))
    addmsg(monm.MMonCommand(tid=1, cmd={"prefix": "status"}))
    addmsg(monm.MMonCommandAck(tid=1, retval=0, out="ok", data=b""))
    addmsg(monm.MOSDBoot(osd_id=0, addr=("127.0.0.1", 6800)))
    addmsg(monm.MOSDFailure(target_osd=1, failed_for=12.5))
    addmsg(monm.MOSDMapMsg(full=None, incrementals=[b"i"], epoch=2))
    addmsg(monm.MMgrBeacon(name="x", addr=("127.0.0.1", 6900)))
    addmsg(monm.MMgrReport(entity="osd.0", counters={"osd": {"op": 1}},
                           epoch=2))
    addmsg(monm.MMDSBeacon(name="a", addr=("127.0.0.1", 6901)))
    addmsg(osdm.MOSDOp(tid=4, pgid="0.1", oid="o",
                       ops=[("writefull", b"x")], epoch=2, snapc=None,
                       snapid=None))
    addmsg(osdm.MOSDOpReply(tid=4, result=0, outdata=[], version=(1, 1),
                            epoch=2))
    addmsg(osdm.MOSDRepOp(reqid=("c", 4), pgid="0.1", ops=[],
                          log={"ev": (1, 1), "oid": "o", "op": "modify",
                               "prior": None, "rollback": None,
                               "shard": None}, epoch=2))
    addmsg(osdm.MOSDRepOpReply(reqid=("c", 4), pgid="0.1", result=0))
    addmsg(osdm.MOSDECSubOpWrite(reqid=("c", 5), pgid="0.1", shard=1,
                                 ops=[], log={"ev": (1, 2), "oid": "o",
                                              "op": "modify",
                                              "prior": None,
                                              "rollback": {"type":
                                                           "stash"},
                                              "shard": 1},
                                 roll_forward_to=(1, 1), epoch=2))
    addmsg(osdm.MOSDECSubOpWriteReply(reqid=("c", 5), pgid="0.1",
                                      shard=1, result=0))
    addmsg(osdm.MOSDECSubOpRead(reqid=None, pgid="0.1", shard=1,
                                oid="o", off=0, length=0))
    addmsg(osdm.MOSDECSubOpReadReply(reqid=None, pgid="0.1", shard=1,
                                     result=0, data=b"d", hinfo=None))
    addmsg(osdm.MOSDPing(op="ping", stamp=1.0, epoch=2, pgid="0.0"))
    addmsg(osdm.MWatchNotify(oid="o", pgid="0.1", notify_id=1, cookie=2,
                             payload=b"p"))
    addmsg(osdm.MWatchNotifyAck(oid="o", pgid="0.1", notify_id=1,
                                cookie=2, reply=b"r"))
    addmsg(fsm.MClientRequest(tid=1, op="mkdir", path="/d", size=None,
                              new_path=None))
    addmsg(fsm.MClientReply(tid=1, result=0, data={"ino": 2}))
    return samples


def build_corpus() -> dict:
    from ceph_tpu.ops import crc32c as crc_mod
    return {name: {"len": len(blob),
                   "crc": crc_mod.crc32c(0, blob)}
            for name, blob in sorted(build_samples().items())}


def test_wire_formats_stable():
    assert os.path.exists(CORPUS_PATH), \
        "corpus missing — run: python tests/test_wire_corpus.py --create"
    with open(CORPUS_PATH) as f:
        archived = json.load(f)
    current = build_corpus()
    missing = set(archived) - set(current)
    assert not missing, f"formats disappeared: {sorted(missing)}"
    for name in sorted(archived):
        assert current[name] == archived[name], \
            f"WIRE FORMAT CHANGED: {name} (archived {archived[name]} " \
            f"vs {current[name]}) — bump DENC_VERSION + upgrade path " \
            f"and regenerate deliberately"


def test_all_samples_roundtrip():
    """Every sample decodes back through the registry."""
    from ceph_tpu.msg.message import Message
    from ceph_tpu.utils import denc
    for name, blob in build_samples().items():
        if blob[:4] == b"CTM1":            # message frames
            type_id, plen, seq = Message.parse_header(
                blob[:Message.header_size()])
            msg = Message.decode(type_id, seq,
                                 blob[Message.header_size():])
            assert type(msg).__name__ == name
        else:
            denc.loads(blob)


def test_the_send_path_leaves_a_messages_own_bytes_alone():
    """ISSUE 38: the sender's stamps ride a frame as a field the send
    path adds to a COPY (`messenger.encode_stamped`, both stacks), so
    an unsent message, and one that was sent, encode to the archived
    bytes; the receiver takes the field off again."""
    from ceph_tpu.msg import messenger
    from ceph_tpu.msg.message import Message
    checked = 0
    for name, blob in build_samples().items():
        if blob[:4] not in (b"CTM1", b"CTM2"):
            continue
        msg = Message.decode_frame(blob)
        seq = msg.seq
        msg.src = ""
        assert msg.encode(seq) == blob, name
        frame = b"".join(bytes(b) for b in messenger.encode_stamped(
            msg, seq, 12.5, 3))
        assert "sent_stamp" not in msg.__dict__, name
        assert msg.encode(seq) == blob, name
        assert frame != blob
        got = Message.decode_frame(frame)
        sent = messenger._SENT_STAMP.unpack(got.__dict__["sent_stamp"])
        assert sent[0] == 12.5 and sent[2] == 3 and sent[1] > 0
        assert sent[3] == messenger.MONO_EPOCH_NS
        messenger.stamp_received(
            got, (sent[1] + 0.25, 0.0, sent[1] + 0.5, 0.0, len(frame), 1))
        assert got._sent_stamp == (12.5, sent[1], 3, False)
        assert got.encode(seq) == blob, name
        checked += 1
    assert checked >= 10


if __name__ == "__main__":
    if "--create" in sys.argv:
        os.makedirs(os.path.dirname(CORPUS_PATH), exist_ok=True)
        with open(CORPUS_PATH, "w") as f:
            json.dump(build_corpus(), f, indent=1, sort_keys=True)
        print(f"wrote {CORPUS_PATH} ({len(build_corpus())} formats)")
    else:
        test_wire_formats_stable()
        print("wire corpus OK")
