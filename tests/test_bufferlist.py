"""BufferList rope + CTM2 data-segment wire path.

Property suite: every rope operation (append of mixed source types,
zero-copy slice, concat, iov reassembly, chained crc32c) is checked
against a plain-bytes oracle, including zero-length and unaligned
slices.  Wire suite: large payloads ride out-of-band data segments
bit-exact — through plain sockets, through cephx-signed sockets, and
through the FaultSet socket-kill/reconnect resend path — and CTM1
frames still decode (magic-gated back-compat).
"""

import queue
import time

import numpy as np
import pytest

from ceph_tpu.msg import Dispatcher, Message, Messenger, register_message
from ceph_tpu.msg.message import MAGIC, MAGIC2, SEG_THRESHOLD, _HDR
from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.utils.bufferlist import (BufferList, as_buffer, concat,
                                       iov_of, wrap_payload)
from ceph_tpu.utils.config import Config


class TestRopeProperties:
    def _mixed_sources(self, rng):
        """(piece-as-exotic-type, piece-as-bytes) pairs."""
        out = []
        for _ in range(rng.integers(1, 9)):
            n = int(rng.choice([0, 1, 7, 128, 4096, 10000]))
            raw = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            kind = rng.integers(0, 4)
            if kind == 0:
                out.append((raw, raw))
            elif kind == 1:
                out.append((memoryview(raw), raw))
            elif kind == 2:
                out.append((np.frombuffer(raw, dtype=np.uint8), raw))
            else:
                out.append((BufferList(raw), raw))
        return out

    def test_append_vs_oracle(self):
        rng = np.random.default_rng(11)
        for _ in range(30):
            bl = BufferList()
            oracle = b""
            for piece, raw in self._mixed_sources(rng):
                bl.append(piece)
                oracle += raw
            assert len(bl) == len(oracle)
            assert bl.to_bytes() == oracle
            assert bl == oracle
            assert bytes(bl) == oracle

    def test_slice_vs_oracle_unaligned(self):
        rng = np.random.default_rng(13)
        bl = BufferList()
        oracle = b""
        for piece, raw in self._mixed_sources(rng):
            bl.append(piece)
            oracle += raw
        n = len(oracle)
        cases = [(0, 0), (0, n), (n, 0), (n, 5)]
        for _ in range(60):
            off = int(rng.integers(0, n + 1))
            length = int(rng.integers(0, n - off + 2))
            cases.append((off, length))
        for off, length in cases:
            got = bl.slice(off, length)
            want = oracle[off: off + length]
            assert got.to_bytes() == want, (off, length)
            assert len(got) == len(want)
        # python slice syntax, and slices share memory (zero-copy)
        assert bl[3: n - 7].to_bytes() == oracle[3: n - 7]
        if bl.num_segments:
            seg0 = bl.iov()[0]
            sl = bl.slice(0, len(seg0))
            assert np.shares_memory(np.frombuffer(sl.iov()[0],
                                                  dtype=np.uint8),
                                    np.frombuffer(seg0, dtype=np.uint8))

    def test_iov_reassembly_and_concat(self):
        rng = np.random.default_rng(17)
        parts = self._mixed_sources(rng)
        bl = concat(p for p, _raw in parts)
        oracle = b"".join(raw for _p, raw in parts)
        assert b"".join(bytes(s) for s in bl.iov()) == oracle
        assert sum(len(s) for s in iov_of(bl)) == len(oracle)
        # appending a rope shares segments
        bl2 = BufferList(bl)
        assert bl2.num_segments == bl.num_segments
        assert bl2 == bl

    def test_crc32c_chained_vs_oracle(self):
        rng = np.random.default_rng(19)
        for seed in (0, 1, 0xDEADBEEF):
            bl = BufferList()
            oracle = b""
            for piece, raw in self._mixed_sources(rng):
                bl.append(piece)
                oracle += raw
            assert bl.crc32c(seed) == crc_mod.crc32c(seed, oracle)
        assert BufferList().crc32c(7) == 7          # empty rope: seed

    def test_indexing(self):
        bl = BufferList(b"abc")
        bl.append(b"defg")
        assert bl[0] == ord("a") and bl[4] == ord("e")
        assert bl[-1] == ord("g")
        with pytest.raises(IndexError):
            bl[7]

    def test_wrap_payload_contract(self):
        raw = b"imm"
        assert wrap_payload(raw) is raw              # immutable: shared
        mv = memoryview(raw)
        assert wrap_payload(mv) is mv
        ba = bytearray(b"mut")
        out = wrap_payload(ba)
        assert isinstance(out, bytes)                # snapshot
        ba[0] = 0
        assert out == b"mut"
        bl = BufferList(b"x" * 10)
        assert wrap_payload(bl) is bl

    def test_as_buffer(self):
        one = BufferList(b"single-seg")
        v = as_buffer(one)
        assert isinstance(v, memoryview) and bytes(v) == b"single-seg"
        two = BufferList(b"a" * 4)
        two.append(b"b" * 4)
        assert as_buffer(two) == b"aaaabbbb"         # flatten (audited)
        assert as_buffer(b"plain") == b"plain"


class QueueDispatcher(Dispatcher):
    def __init__(self):
        self.q: queue.Queue = queue.Queue()

    def ms_dispatch(self, conn, msg):
        self.q.put((conn, msg))
        return True

    def get(self, timeout=10):
        return self.q.get(timeout=timeout)


@register_message
class MSeg(Message):
    TYPE = 9100


def make_msgr(name, conf=None):
    m = Messenger(name, conf=conf)
    m.bind(("127.0.0.1", 0))
    disp = QueueDispatcher()
    m.add_dispatcher_tail(disp)
    m.start()
    return m, disp


@pytest.mark.usefixtures("denc_walk")
class TestDataSegments:
    def test_large_fields_ride_segments(self):
        """Fields over the threshold leave the denc payload and ride
        as iovec segments — sharing the sender's buffer, not copying."""
        blob = bytes(range(256)) * 64          # 16 KiB
        rope = BufferList(b"ab" * 4000)
        rope.append(blob)
        msg = MSeg(a=blob, ops=[("writefull", rope)], small=b"s")
        iov = msg.encode_iov(seq=3)
        assert bytes(iov[0][:4]) == MAGIC2
        assert any(b is blob for b in iov), "payload must ride uncopied"
        out = Message.decode_frame(msg.encode(seq=3))
        assert out.a == blob
        assert bytes(out.ops[0][1]) == rope.to_bytes()
        assert out.small == b"s"

    def test_small_frames_stay_ctm1(self):
        msg = MSeg(x=1, blob=b"tiny" * 10)
        iov = msg.encode_iov(seq=1)
        assert bytes(iov[0][:4]) == MAGIC
        # CTM1 back-compat: the v1 parse path still decodes it
        frame = msg.encode(seq=1)
        type_id, plen, seq = Message.parse_header(
            frame[:Message.header_size()])
        out = Message.decode(type_id, seq, frame[Message.header_size():])
        assert out.blob == b"tiny" * 10 and out.seq == 1

    def test_hostile_segment_refs_rejected(self):
        """A _SegRef is a registered denc type, so any peer can encode
        one: out-of-range / negative indices and refs in segment-free
        frames must raise the corrupt-frame ValueError (which the
        messenger skips cleanly) — never IndexError, and never silent
        wrong-segment substitution."""
        from ceph_tpu.msg.message import _SegRef
        from ceph_tpu.utils import denc

        def frame_with(fields, segs):
            payload = denc.dumps(fields)
            return payload, segs

        broken = _SegRef(0)
        del broken.__dict__["i"]                      # denc-encodable
        for fields, segs in (
                ({"x": _SegRef(5)}, [b"only-one"]),   # out of range
                ({"x": _SegRef(-1)}, [b"a", b"b"]),   # negative alias
                ({"x": [1, (_SegRef(0),)]}, []),      # ref, no segments
                ({"x": broken}, [b"seg"]),            # no index at all
                ({"x": _SegRef("0")}, [b"seg"]),      # non-int index
        ):
            payload, segs = frame_with(fields, segs)
            with pytest.raises(ValueError):
                Message.decode(MSeg.TYPE, 1, payload, segs)

    def test_socket_roundtrip_bit_exact(self):
        a, _ = make_msgr("a")
        b, bd = make_msgr("b")
        try:
            rng = np.random.default_rng(5)
            blobs = [rng.integers(0, 256, size=n, dtype=np.uint8
                                  ).tobytes()
                     for n in (SEG_THRESHOLD, 1 << 16, (1 << 20) + 13)]
            for i, blob in enumerate(blobs):
                rope = BufferList(blob[: 1000])
                rope.append(blob[1000:])
                a.send_message(
                    MSeg(i=i, payload=blob, rope=rope), "b", b.addr)
            for i, blob in enumerate(blobs):
                _, msg = bd.get()
                assert msg.i == i
                assert msg.payload == blob
                assert bytes(msg.rope) == blob
        finally:
            a.shutdown()
            b.shutdown()

    def test_signed_segments_roundtrip(self):
        """cephx signing covers header + table + payload + segments as
        an iovec fold; a signed large-payload frame verifies and a
        tampered segment would fail (same-digest-as-joined contract)."""
        from ceph_tpu.auth import cephx, generate_key
        key = generate_key()

        def mk(name):
            conf = Config({"ms_connect_timeout": 2.0,
                           "ms_max_backoff": 0.5})
            conf.set_val("auth_cluster_required", "cephx")
            conf.set_val("key", key)
            conf.apply_changes()
            m = Messenger(name, conf=conf)
            m.bind(("127.0.0.1", 0))
            d = QueueDispatcher()
            m.add_dispatcher_tail(d)
            m.start()
            return m, d

        a, _ = mk("client.a")
        b, bd = mk("osd.0")
        try:
            blob = bytes(range(256)) * 256     # 64 KiB, segmented
            a.send_message(MSeg(payload=blob), "osd.0", b.addr)
            _, msg = bd.get()
            assert msg.payload == blob
        finally:
            a.shutdown()
            b.shutdown()
        # the iov signature equals the joined-frame signature
        skey = b"k" * 32
        parts = [b"C", b"hdr", b"payload", b"seg0", b"seg1"]
        assert cephx.sign_iov(skey, parts) == cephx.sign(
            skey, b"".join(parts))

    def test_segments_survive_socket_kill_resend(self):
        """FaultSet-style socket kills mid-stream: the lossless resend
        path replays iovec frames (segments included) bit-exact and in
        order."""
        conf = Config({"ms_inject_socket_failures": 4})
        a, _ = make_msgr("a", conf)
        b, bd = make_msgr("b")
        try:
            rng = np.random.default_rng(23)
            n = 25
            blobs = [rng.integers(0, 256, size=8192, dtype=np.uint8
                                  ).tobytes() for _ in range(n)]
            for i, blob in enumerate(blobs):
                a.send_message(MSeg(i=i, payload=blob), "b", b.addr)
            got = {}
            deadline = time.time() + 30
            while len(got) < n and time.time() < deadline:
                _, msg = bd.get(timeout=30)
                got[msg.i] = msg.payload
            assert sorted(got) == list(range(n))
            for i, blob in enumerate(blobs):
                assert got[i] == blob, f"payload {i} corrupted by resend"
        finally:
            a.shutdown()
            b.shutdown()
