"""Wire/disk format non-regression corpus (the reference's
ceph-object-corpus + test/encoding/readable.sh analog).

One representative instance of every registered message type and denc
struct is encoded; the CRC32C of each encoding is pinned in
tests/data/wire_corpus.json.  A refactor that changes any wire or disk
byte fails here BEFORE it can strand persisted state or break rolling
upgrades between builds.

The codec has two walks of one format (utils/denc.py; the messenger's
segment lift with it, msg/message.py): the native tier's compiled one
and the Python one behind it.  Every test here runs under both
(conftest's `denc_walk`), so the archive holds each to the bytes the
other writes; the `.ctm2` samples are frames whose large fields ride
out of band, archived from the tree before the compiled walk existed.

Regenerate (deliberate format changes only — bump DENC_VERSION and add
an upgrade path when the change touches persisted structs):
    python tests/test_wire_corpus.py --create
"""

import json
import os
import sys

import pytest

both_walks = pytest.mark.usefixtures("denc_walk")

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

    # -- CTM2: the same types with fields over SEG_THRESHOLD, which
    # leave a _SegRef in the payload and ride behind it as segments
    from ceph_tpu.utils.bufferlist import BufferList

    def addseg(msg) -> None:
        samples[type(msg).__name__ + ".ctm2"] = msg.encode(seq=7)

    blob = bytes(range(256)) * 32                       # 8 KiB
    rope = BufferList(b"ab" * 3000)
    rope.append(blob)
    addseg(osdm.MOSDOp(tid=4, pgid="0.1", oid="o",
                       ops=[("writefull", blob),
                            ("setxattr", "k", b"v" * 600)],
                       epoch=2, snapc=None, snapid=None))
    addseg(osdm.MOSDECSubOpWrite(
        reqid=("c", 5), pgid="0.1", shard=1,
        ops=[("write", "o", 0, memoryview(blob)),
             ("setattr", "o", "hinfo", bytearray(b"h" * 120)),
             ("write", "o", 8192, rope)],
        log={"ev": (1, 2), "oid": "o", "op": "modify", "prior": None,
             "rollback": {"type": "stash"}, "shard": 1},
        roll_forward_to=(1, 1), epoch=2))
    addseg(osdm.MOSDECSubOpReadReply(
        reqid=None, pgid="0.1", shard=1, result=0,
        data={"o": [(0, bytearray(blob)), (8192, BufferList(b"r" * 100))]},
        hinfo={"crcs": [1, 2, 3], "size": 8192}))
    addseg(osdm.MOSDOpReply(tid=4, result=0,
                            outdata=[blob[:4096], blob[:4095], rope],
                            version=(1, 1), epoch=2))
    return samples


def build_corpus() -> dict:
    from ceph_tpu.ops import crc32c as crc_mod
    return {name: {"len": len(blob),
                   "crc": crc_mod.crc32c(0, blob)}
            for name, blob in sorted(build_samples().items())}


@both_walks
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


@both_walks
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
        elif blob[:4] == b"CTM2":          # ... with segments behind
            assert name.endswith(".ctm2")
            msg = Message.decode_frame(blob)
            assert type(msg).__name__ + ".ctm2" == name
            assert msg.encode(msg.seq) == blob
        else:
            denc.loads(blob)


@both_walks
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


def test_both_walks_write_the_archived_bytes(monkeypatch):
    """Sample by sample and not by CRC: what the compiled walk writes
    is what the Python walk writes (and the archive pins either)."""
    from ceph_tpu import native
    if native.get_ext() is None:
        pytest.skip("the native tier's extension cannot be built here")
    mine = build_samples()
    with monkeypatch.context() as m:
        m.setattr(native, "get_ext", lambda: None)
        theirs = build_samples()
    assert mine.keys() == theirs.keys()
    for name in mine:
        assert mine[name] == theirs[name], name
    assert sum(b[:4] == b"CTM2" for b in mine.values()) >= 4


# -- the segment lift and its refusals, under both walks --------------------

def _segref(i):
    from ceph_tpu.msg.message import _SegRef
    return _SegRef(i)


def _segref_without_index():
    ref = _segref(0)
    del ref.__dict__["i"]              # still denc-encodable
    return ref


def _segref_blob(version: int, fields) -> bytes:
    from ceph_tpu.utils import denc
    return (bytes([denc.T_OBJ, 7]) + b"_SegRef" + denc._uvarint(version)
            + denc.py_dumps(fields))


HOSTILE_REFS = [
    ("index out of range", lambda: {"x": _segref(5)}, [b"only-one"]),
    ("index equal to the count", lambda: {"x": _segref(1)}, [b"one"]),
    ("negative index", lambda: {"x": _segref(-1)}, [b"a", b"b"]),
    ("index beyond a machine word", lambda: {"x": _segref(2**70)},
     [b"a"]),
    ("no index at all", lambda: {"x": _segref_without_index()},
     [b"seg"]),
    ("str index", lambda: {"x": _segref("0")}, [b"seg"]),
    ("float index", lambda: {"x": _segref(0.0)}, [b"seg"]),
    ("None index", lambda: {"x": _segref(None)}, [b"seg"]),
    ("a ref and no segments", lambda: {"x": _segref(0)}, []),
    ("a ref and segments None", lambda: {"x": _segref(0)}, None),
    ("a nested ref and no segments",
     lambda: {"x": [1, (_segref(0),)]}, []),
    ("a ref under a dict under a list and no segments",
     lambda: {"x": [{"k": {"j": _segref(0)}}]}, ()),
    ("the second ref of two out of range",
     lambda: {"a": _segref(0), "b": [_segref(1)]}, [b"one"]),
]


@both_walks
@pytest.mark.parametrize("fields,segs", [c[1:] for c in HOSTILE_REFS],
                         ids=[c[0] for c in HOSTILE_REFS])
def test_hostile_segment_refs_refused_alike(fields, segs):
    """A _SegRef is a registered denc type, so any peer can encode
    one: an index that is not an int in range, and any ref in a frame
    that carried no segments, is the corrupt-frame ValueError the
    messenger skips — never an IndexError, never another segment."""
    from ceph_tpu.msg.message import Message
    from ceph_tpu.osd.messages import MOSDOp
    from ceph_tpu.utils import denc
    payload = denc.py_dumps(fields())
    with pytest.raises(ValueError, match="segment ref"):
        Message.decode(MOSDOp.TYPE, 1, payload, segs)


@both_walks
@pytest.mark.parametrize("payload,says", [
    (b"\x07\x00", "must be a field dict"),              # a list
    (b"\x00", "must be a field dict"),                  # None
    (b"\x09\x01\x06\x01x", "truncated input"),
    (b"\x09\x00\x00", "trailing bytes"),
    (b"\x09\x01\x06\x01x" + _segref_blob(2, {"i": 0}), "newer"),
    (b"\x09\x01\x06\x01x" + _segref_blob(0, {"i": 0}),
     "no upgrade path"),
    (b"\x09\x01\x06\x01x" + _segref_blob(1, [0]),
     "bad field container"),
], ids=["list", "none", "truncated", "trailing", "ref of a newer version",
        "ref of an older version", "ref with a list for fields"])
def test_hostile_payloads_refused_alike(payload, says):
    from ceph_tpu.msg.message import Message
    from ceph_tpu.osd.messages import MOSDOp
    from ceph_tpu.utils.denc import DencError
    with pytest.raises(DencError, match=says):
        Message.decode(MOSDOp.TYPE, 1, payload, [b"seg"])


@both_walks
def test_refs_are_put_where_the_walk_reaches_and_nowhere_else():
    """Segments go where `_substitute_segments` went: through lists,
    tuples and dict values from the root.  A bool index is the int it
    is.  A NamedTuple is a tuple to that walk: entered, and a plain
    tuple once something in it changed (what a sender's lift makes of
    one too).  A ref in a dict key, a set or another struct's fields
    is not a place a sender's lift writes one, and neither walk reads
    one there."""
    from ceph_tpu.msg.message import Message, _SegRef
    from ceph_tpu.osd.messages import MOSDOp
    from ceph_tpu.osd.osdmap import PgId, Pool
    from ceph_tpu.utils import denc
    segs = [b"zero", bytearray(b"one")]
    key = _segref(0)
    fields = {"a": _segref(0), "b": [(_segref(1), {"k": _segref(True)})],
              "keyed": {key: 1}, "in_set": {_segref(1)},
              "named": PgId(_segref(0), 7), "plain": PgId(1, 7),
              "in_struct": Pool(1, _segref(0))}
    msg = Message.decode(MOSDOp.TYPE, 9, denc.py_dumps(fields), segs)
    assert msg.a is segs[0] and msg.seq == 9
    assert msg.b[0][0] is segs[1] and msg.b[0][1]["k"] is segs[1]
    assert [type(k) for k in msg.keyed] == [_SegRef]
    assert [type(m) for m in msg.in_set] == [_SegRef]
    assert type(msg.named) is tuple and msg.named[0] is segs[0]
    assert type(msg.plain) is PgId
    assert type(msg.in_struct.name) is _SegRef
    with pytest.raises(ValueError, match="segment ref"):
        Message.decode(MOSDOp.TYPE, 9, denc.py_dumps(
            {"named": PgId(_segref(0), 7)}), [])


@both_walks
def test_leaves_past_the_segment_table_ride_inline():
    """More large leaves than `_SEG_MAX`: the table takes what it can
    hold and the rest stay in the payload, as they always did."""
    from ceph_tpu.msg import message
    from ceph_tpu.osd.messages import MOSDOp
    leaf = bytes(message.SEG_THRESHOLD)
    n = message._SEG_MAX + 3
    msg = MOSDOp(tid=1, ops=[leaf] * n)
    iov = msg.encode_iov(seq=2)
    (nsegs,) = message._SEG_COUNT.unpack(bytes(iov[1][:4]))
    assert nsegs == message._SEG_MAX
    assert len(iov[2]) > 3 * len(leaf)          # three ride in the payload
    out = message.Message.decode_frame(b"".join(bytes(b) for b in iov))
    assert len(out.ops) == n and all(bytes(b) == leaf for b in out.ops)


def test_leaves_past_the_segment_table_same_bytes(monkeypatch):
    from ceph_tpu import native
    from ceph_tpu.msg import message
    from ceph_tpu.osd.messages import MOSDOp
    if native.get_ext() is None:
        pytest.skip("the native tier's extension cannot be built here")
    leaf = bytes(message.SEG_THRESHOLD)
    msg = MOSDOp(tid=1, ops=[leaf] * (message._SEG_MAX + 3), tail=leaf)
    mine = msg.encode_iov(seq=2)
    monkeypatch.setattr(native, "get_ext", lambda: None)
    theirs = msg.encode_iov(seq=2)
    assert len(mine) == len(theirs)
    assert all(bytes(a) == bytes(b) for a, b in zip(mine[:3], theirs[:3]))
    assert all(a is b for a, b in zip(mine[3:], theirs[3:]))


@both_walks
def test_inline_leaves_are_still_audited():
    """`msg.inline` (utils/copyaudit.py): a bytes-like field of 512
    bytes or more that is too small for a segment is a host copy the
    audit sees; smaller ones are control-field noise; a small rope is
    flattened (its own site) and rides inline; dict keys are not
    fields."""
    from ceph_tpu.msg.message import Message
    from ceph_tpu.osd.messages import MOSDOp
    from ceph_tpu.utils import copyaudit
    from ceph_tpu.utils.bufferlist import BufferList
    msg = MOSDOp(tid=1, small=b"s" * 511, floor=b"f" * 512,
                 ops=[("w", bytearray(b"a" * 4095)),
                      {"k": memoryview(b"m" * 600)}],
                 seg=b"S" * 4096, rope=BufferList(b"r" * 700),
                 keyed={b"k" * 900: 1}, _local=b"l" * 2000)

    def sites():
        s = copyaudit.snapshot()["sites"]
        return {k: (s.get(k) or {"copies": 0, "bytes": 0})
                for k in ("msg.inline", "bufferlist.flatten")}

    before = sites()
    frame = msg.encode(seq=1)
    after = sites()
    assert after["msg.inline"]["copies"] - \
        before["msg.inline"]["copies"] == 3
    assert after["msg.inline"]["bytes"] - \
        before["msg.inline"]["bytes"] == 512 + 4095 + 600
    assert after["bufferlist.flatten"]["copies"] - \
        before["bufferlist.flatten"]["copies"] == 1
    out = Message.decode_frame(frame)
    assert out.rope == b"r" * 700 and out.seg == b"S" * 4096
    assert not hasattr(out, "_local")


if __name__ == "__main__":
    if "--create" in sys.argv:
        os.makedirs(os.path.dirname(CORPUS_PATH), exist_ok=True)
        with open(CORPUS_PATH, "w") as f:
            json.dump(build_corpus(), f, indent=1, sort_keys=True)
        print(f"wrote {CORPUS_PATH} ({len(build_corpus())} formats)")
    else:
        test_wire_formats_stable()
        print("wire corpus OK")
