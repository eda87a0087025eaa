"""Messenger tests: delivery, ordering, loopback, reconnect, injection."""

import queue
import threading
import time

import pytest

from ceph_tpu.msg import (Dispatcher, Message, Messenger, Policy,
                          register_message)
from ceph_tpu.utils.config import Config


@register_message
class MPing(Message):
    TYPE = 9001


@register_message
class MData(Message):
    TYPE = 9002


class QueueDispatcher(Dispatcher):
    def __init__(self):
        self.q: queue.Queue = queue.Queue()
        self.resets = []

    def ms_dispatch(self, conn, msg):
        self.q.put((conn, msg))
        return True

    def ms_handle_reset(self, conn):
        self.resets.append(conn)

    def get(self, timeout=5):
        return self.q.get(timeout=timeout)


def make_msgr(name, conf=None):
    m = Messenger(name, conf=conf)
    m.bind(("127.0.0.1", 0))
    disp = QueueDispatcher()
    m.add_dispatcher_tail(disp)
    m.start()
    return m, disp


class TestWire:
    def test_roundtrip_encoding(self):
        msg = MData(a=1, blob=b"\x00\xff" * 100, name="x")
        frame = msg.encode(seq=42)
        type_id, plen, seq = Message.parse_header(
            frame[: Message.header_size()])
        out = Message.decode(type_id, seq, frame[Message.header_size():])
        assert isinstance(out, MData)
        assert out.a == 1 and out.blob == b"\x00\xff" * 100
        assert out.seq == 42

    def test_unknown_type_raises(self):
        with pytest.raises(ValueError):
            Message.decode(55555, 0, b"")


class TestDelivery:
    def test_basic_send(self):
        a, _ = make_msgr("a")
        b, bd = make_msgr("b")
        try:
            a.send_message(MData(x=7), "b", b.addr)
            conn, msg = bd.get()
            assert msg.x == 7
            assert msg.src == "a"
            assert conn.peer_name == "a"
        finally:
            a.shutdown()
            b.shutdown()

    def test_reply_via_peer_addr(self):
        a, ad = make_msgr("a")
        b, bd = make_msgr("b")
        try:
            a.send_message(MPing(n=1), "b", b.addr)
            conn, msg = bd.get()
            # reply using the peer address learned from the banner
            b.send_message(MPing(n=2), conn.peer_name, conn.peer_addr)
            _, reply = ad.get()
            assert reply.n == 2 and reply.src == "b"
        finally:
            a.shutdown()
            b.shutdown()

    def test_ordering_many_messages(self):
        a, _ = make_msgr("a")
        b, bd = make_msgr("b")
        try:
            for i in range(200):
                a.send_message(MData(i=i), "b", b.addr)
            got = [bd.get()[1].i for _ in range(200)]
            assert got == list(range(200))
        finally:
            a.shutdown()
            b.shutdown()

    def test_loopback_fast_dispatch(self):
        a, ad = make_msgr("a")
        try:
            a.send_message(MPing(n=5), "a", a.addr)
            conn, msg = ad.get()
            assert msg.n == 5
            assert conn.peer_name == "a"
        finally:
            a.shutdown()

    def test_large_message(self):
        a, _ = make_msgr("a")
        b, bd = make_msgr("b")
        try:
            blob = bytes(range(256)) * 40000   # ~10 MB
            a.send_message(MData(blob=blob), "b", b.addr)
            _, msg = bd.get(timeout=15)
            assert msg.blob == blob
        finally:
            a.shutdown()
            b.shutdown()


class TestResilience:
    def test_lossless_reconnect_after_peer_restart(self):
        a, _ = make_msgr("a")
        b, bd = make_msgr("b")
        port = b.addr[1]
        try:
            a.send_message(MData(i=1), "b", b.addr)
            assert bd.get()[1].i == 1
            b.shutdown()
            # peer down: queue a message while unreachable (lossless
            # policy keeps it and retries with backoff)
            a.send_message(MData(i=2), "b", ("127.0.0.1", port))
            time.sleep(0.3)
            b2 = Messenger("b")
            b2.bind(("127.0.0.1", port))
            bd2 = QueueDispatcher()
            b2.add_dispatcher_tail(bd2)
            b2.start()
            _, msg = bd2.get(timeout=10)
            assert msg.i == 2
            b2.shutdown()
        finally:
            a.shutdown()

    def test_socket_failure_injection_still_delivers(self):
        conf = Config({"ms_inject_socket_failures": 10})
        a, _ = make_msgr("a", conf)
        b, bd = make_msgr("b")   # clean receiving side
        try:
            n = 100
            for i in range(n):
                a.send_message(MData(i=i), "b", b.addr)
            got = sorted(bd.get(timeout=20)[1].i for _ in range(n))
            assert got == list(range(n))
        finally:
            a.shutdown()
            b.shutdown()

    def test_sender_restart_fresh_seq_space_delivers(self):
        """A restarted peer (new incarnation nonce, seq restarts at 1)
        must not have its first frames dropped by the acceptor's stale
        in_seq from the previous incarnation."""
        b, bd = make_msgr("b")
        try:
            a1, _ = make_msgr("a")
            for i in range(5):
                a1.send_message(MData(i=i), "b", b.addr)
            for i in range(5):
                assert bd.get()[1].i == i
            a1.shutdown()        # acceptor-side conn "a" keeps in_seq=5
            a2, _ = make_msgr("a")   # restart: fresh nonce, seq from 1
            for i in range(10, 13):
                a2.send_message(MData(i=i), "b", b.addr)
            got = [bd.get()[1].i for _ in range(3)]
            assert got == [10, 11, 12]
            a2.shutdown()
        finally:
            b.shutdown()

    def test_undecodable_frame_skipped_link_survives(self):
        """A corrupt payload frame is dropped with an error, but the
        connection and subsequent frames keep flowing."""
        import socket
        import struct as _s

        from ceph_tpu.msg import messenger as msgr_mod
        from ceph_tpu.msg.message import _HDR, MAGIC

        b, bd = make_msgr("b")
        try:
            s = socket.create_connection(b.addr, timeout=5)
            name = b"evil"
            addr = msgr_mod._pack_addr(("127.0.0.1", 1))
            s.sendall(msgr_mod._BANNER.pack(
                msgr_mod.BANNER_MAGIC, 7, len(name), len(addr))
                + name + addr)
            rep = s.recv(msgr_mod._BANNER_REPLY.size)
            assert len(rep) == msgr_mod._BANNER_REPLY.size
            # frame 1: valid header, garbage payload
            garbage = b"\xfe\xfd\xfc"
            s.sendall(_HDR.pack(MAGIC, MData.TYPE, len(garbage), 1)
                      + garbage)
            # frame 2: a real message
            good = MData(i=99)
            good.src = "evil"
            s.sendall(good.encode(seq=2))
            _, msg = bd.get(timeout=5)
            assert msg.i == 99
            s.close()
        finally:
            b.shutdown()

    def test_lossy_client_reset_notifies(self):
        conf = Config()
        a, ad = make_msgr("a", conf)
        a.set_default_policy(Policy.lossy_client())
        try:
            # connect to a dead port: lossy -> reset, no retry loop
            a.send_message(MData(i=1), "dead", ("127.0.0.1", 1))
            deadline = time.time() + 5
            while time.time() < deadline and not ad.resets:
                time.sleep(0.05)
            assert ad.resets, "expected ms_handle_reset for lossy conn"
        finally:
            a.shutdown()


# -- the accepted side's frame reader (msg/messenger.py `_FrameReader`) ------

import asyncio  # noqa: E402
import math  # noqa: E402
import socket  # noqa: E402

from ceph_tpu.auth import cephx  # noqa: E402
from ceph_tpu.msg import messenger as msgr_mod  # noqa: E402
from ceph_tpu.msg.message import _HDR, MAGIC  # noqa: E402
from ceph_tpu.utils import faults  # noqa: E402

SKEY = b"k" * 32
MIB4 = 4 << 20


class FakeTransport:
    """What `_FrameReader` asks of its transport, written down in
    order."""

    def __init__(self, events):
        self.events = events
        self.closed = False
        self.paused = False

    def write(self, data):
        self.events.append(("ack", bytes(data)))

    def pause_reading(self):
        self.paused = True
        self.events.append(("pause",))

    def resume_reading(self):
        self.paused = False

    def close(self):
        self.closed = True


class Rig:
    """A `_FrameReader` with no socket under it: bytes go in through
    get_buffer / buffer_updated as a selector transport hands them."""

    def __init__(self, skey=None, conf=None, recv_buf=None):
        self.loop = asyncio.new_event_loop()
        self.msgr = Messenger("rx", conf=conf)
        self.msgr._loop = self.loop
        self.events: list = []
        self.msgr._deliver = lambda conn, msg: self.events.append(
            ("msg", msg))
        self.conn = msgr_mod.Connection(self.msgr, "tx", None,
                                        Policy.lossless_peer())
        self.transport = FakeTransport(self.events)
        # a smaller receive buffer puts a shorter field on the path of
        # a field with a buffer of its own
        was = msgr_mod.RECV_BUF
        msgr_mod.RECV_BUF = self.recv_buf = recv_buf or was
        try:
            self.reader = msgr_mod._FrameReader(self.msgr, self.conn,
                                                self.transport, skey)
        finally:
            msgr_mod.RECV_BUF = was

    def push(self, data, piece=None):
        """As a socket would: a piece a read, never more than the
        buffer the protocol offers."""
        view = memoryview(data)
        piece = piece or len(view)
        while len(view) and not self.transport.closed:
            assert not self.transport.paused
            buf = self.reader.get_buffer(-1)
            n = min(piece, len(buf), len(view))
            assert n > 0
            buf[:n] = view[:n]
            view = view[n:]
            self.reader.buffer_updated(n)
        return len(view)

    def close(self):
        self.reader._finish(None)
        self.loop.close()

    def delivered(self):
        return [e[1] for e in self.events if e[0] == "msg"]

    def acks(self):
        return [e[1] for e in self.events if e[0] == "ack"]


def wire(msg, seq, skey=None):
    msg.src = "tx"
    iov = msg.encode_iov(seq)
    frame = b"".join(bytes(b) for b in iov)
    if skey is not None:
        frame += cephx.sign_iov(skey, [b"C", *iov])
    return frame


def ack_for(seq, skey=None):
    ack = _HDR.pack(MAGIC, Messenger.ACK_TYPE, 0, seq)
    if skey is not None:
        ack += cephx.sign(skey, b"S" + ack)
    return ack


def pattern(n, salt):
    unit = bytes((i * 131 + salt) & 0xFF for i in range(251))
    return (unit * (n // 251 + 1))[:n]


def mixed_stream(skey, big):
    """A recorded stream of every kind of frame an accepted socket
    sees, and what the parent's reader (header, body, a readexactly a
    segment, signature, ack, dup test, decode) makes of it: the fields
    of the messages delivered and the acks written, both in order."""
    blob = pattern(big, 1)
    several = [pattern(65536, s) for s in (2, 3, 4)]
    ack_in = _HDR.pack(MAGIC, Messenger.ACK_TYPE, 0, 7)
    if skey is not None:
        ack_in += cephx.sign(skey, b"C" + ack_in)
    garbage = b"\xfe\xfd\xfc"
    bad = _HDR.pack(MAGIC, MData.TYPE, len(garbage), 5) + garbage
    if skey is not None:
        bad += cephx.sign(skey, b"C" + bad)
    frames = [
        wire(MPing(n=1), 1, skey),                      # CTM1
        ack_in,                                         # an ack: no reply
        wire(MData(i=2, blob=blob), 2, skey),           # CTM2, one segment
        wire(MPing(n=3), 3, skey),
        wire(MData(i=4, parts=several, tail=pattern(5000, 9),
                   small=b"s" * 100), 4, skey),         # CTM2, four
        bad,                                            # acked, skipped
        wire(MPing(n=99), 3, skey),                     # dup: acked only
        wire(MPing(n=6), 6, skey),
    ]
    want_msgs = [("MPing", {"n": 1}), ("MData", {"i": 2, "blob": blob}),
                 ("MPing", {"n": 3}),
                 ("MData", {"i": 4, "parts": several,
                            "tail": pattern(5000, 9),
                            "small": b"s" * 100}),
                 ("MPing", {"n": 6})]
    want_acks = [ack_for(s, skey) for s in (1, 2, 3, 4, 5, 3, 6)]
    return b"".join(frames), want_msgs, want_acks


def by_readexactly(stream, skey):
    """The same stream through the frame loop as `readexactly` on a
    StreamReader feeds it (the dialed side's driver, and the accepted
    side's before it had a reader of its own): a field a call, cut
    exactly.  Returns the events in order."""
    rig = Rig(skey)
    events: list = []
    rig.msgr._deliver = lambda conn, msg: events.append(("msg", msg))
    frames = rig.msgr._frames(rig.conn,
                              lambda ack: events.append(("ack", bytes(ack))),
                              lambda: 1, skey, accepted=True)
    pos = 0
    try:
        want, n = frames.send(None)
        while pos + n <= len(stream):
            assert want == "read"
            field, pos = stream[pos:pos + n], pos + n
            want, n = frames.send(field)
    finally:
        frames.close()
        rig.close()
    assert pos == len(stream)
    return events


def fields_of(msg):
    return (type(msg).__name__,
            {k: v for k, v in msg.__dict__.items()
             if k not in ("src", "seq") and not k.startswith("_")})


class TestFrameReader:
    # a byte at a time over 4.4 MiB would take a minute of Python: the
    # two finest cuts carry a 128 KiB + 1 segment, and a 64 KiB receive
    # buffer keeps it one with a buffer of its own (RECV_BUF and longer)
    @pytest.mark.parametrize("piece,big,signed", [
        (1, (128 << 10) + 1, False), (1, (128 << 10) + 1, True),
        (7, (128 << 10) + 1, False), (7, (128 << 10) + 1, True),
        (7, MIB4, False),
        (4096, MIB4, False), (4096, MIB4, True),
        (256 << 10, MIB4, False), (256 << 10, MIB4, True),
        (None, MIB4, False), (None, MIB4, True)],
        ids=["1B-plain", "1B-cephx", "7B-plain", "7B-cephx", "7B-4M-plain",
             "4K-4M-plain", "4K-4M-cephx", "256K-4M-plain",
             "256K-4M-cephx", "whole-4M-plain", "whole-4M-cephx"])
    def test_mixed_stream_any_cut(self, piece, big, signed):
        skey = SKEY if signed else None
        stream, want_msgs, want_acks = mixed_stream(skey, big)
        rig = Rig(skey, recv_buf=None if big == MIB4 else 64 << 10)
        try:
            assert rig.push(stream, piece) == 0
            got = [fields_of(m) for m in rig.delivered()]
            assert [g[0] for g in got] == [w[0] for w in want_msgs]
            for (_, g), (_, w) in zip(got, want_msgs):
                assert g == w
            assert rig.acks() == want_acks
            assert rig.conn.in_seq == 6 and not rig.transport.closed
            # ... which is what a readexactly a field makes of it
            ref = by_readexactly(stream, skey)
            def plain(events):
                return [e if e[0] == "ack" else ("msg", fields_of(e[1]))
                        for e in events]
            assert plain(rig.events) == plain(ref)
            # acks and deliveries interleave as the frames came
            order = [e[0] for e in rig.events]
            assert order == ["ack", "msg", "ack", "msg", "ack", "msg",
                             "ack", "msg", "ack", "ack", "ack", "msg"]
            assert rig.msgr.perf.dump()["bytes_recv"] == len(stream) - (
                8 * cephx.SIG_LEN if signed else 0)
        finally:
            rig.close()

    def test_reads_stamp_counts_the_reads_that_fed_the_frame(self):
        stream, _, _ = mixed_stream(None, MIB4)
        whole = Rig()
        cut = Rig()
        try:
            whole.push(stream)
            cut.push(stream, 256 << 10)
            by_whole = [m._recv_reads for m in whole.delivered()]
            by_cut = [m._recv_reads for m in cut.delivered()]
            # whole: the 4 MiB segment's head comes with the first read
            # of the receive buffer, its rest in one read of its own
            assert by_whole[0] == 1 and by_whole[1] == 2
            # cut: the first read brings the frames in front, the
            # header and the segment's head (a piece, or the receive
            # buffer where that is smaller), each further one a piece
            piece = 256 << 10
            head = min(piece, msgr_mod.RECV_BUF)
            assert by_cut[1] == 1 + (MIB4 - head) // piece + 1
            big = whole.delivered()[1]
            assert big._recv_bytes > MIB4
            assert big._recv_complete_stamp >= big._recv_stamp
        finally:
            whole.close()
            cut.close()

    @pytest.mark.parametrize("n", [32 << 10, 128 << 10],
                             ids=["from-recv-buffer", "own-buffer"])
    def test_a_segment_is_not_the_next_frames_buffer(self, n):
        first, second = pattern(n, 5), pattern(n, 6)
        rig = Rig(recv_buf=64 << 10)
        try:
            rig.push(wire(MData(blob=first), 1) + wire(MData(blob=second), 2)
                     + wire(MData(blob=second), 3), 50000)
            a, b, c = rig.delivered()
            assert a.blob == first and b.blob == second
            assert c.blob == second and b.blob is not c.blob
            # what a long segment is handed on as is never written again
            assert isinstance(a.blob, bytes if n < rig.recv_buf
                              else bytearray)
        finally:
            rig.close()

    def test_bad_signature_mid_stream_closes_without_an_ack(self):
        good = wire(MPing(n=1), 1, SKEY)
        forged = bytearray(wire(MData(blob=pattern(70000, 1)), 2, SKEY))
        forged[-cephx.SIG_LEN - 10] ^= 1
        rig = Rig(SKEY)
        try:
            rig.push(good + bytes(forged) + wire(MPing(n=3), 3, SKEY), 9000)
            assert [m.n for m in rig.delivered()] == [1]
            assert rig.acks() == [ack_for(1, SKEY)]
            assert rig.transport.closed and rig.conn.in_seq == 1
            assert rig.reader.done.done() and \
                rig.reader.done.exception() is None
        finally:
            rig.close()

    def test_partition_between_header_and_last_segment_acks_nothing(self):
        faults.get().reset(seed=0)
        frame = wire(MData(blob=pattern(200000, 1)), 1)
        rig = Rig()
        try:
            rig.push(frame[:100000], 30000)
            assert not rig.events
            faults.get().partition("tx", "rx")
            rig.push(frame[100000:], 30000)
            assert rig.events == [] and rig.transport.closed
            assert rig.conn.in_seq == 0 and rig.conn.last_recv == 0.0
        finally:
            faults.get().reset(seed=0)
            rig.close()

    def test_recv_delay_keeps_order(self):
        faults.get().reset(seed=0)
        conf = Config()
        conf.set_val("ms_inject_delay_probability", 1.0)
        conf.set_val("ms_inject_delay_max", 0.02)
        rig = Rig(conf=conf)
        try:
            stream = b"".join(wire(MPing(n=i), i) for i in (1, 2, 3))
            buf = rig.reader.get_buffer(-1)
            buf[:len(stream)] = stream
            rig.reader.buffer_updated(len(stream))
            # the first frame is acknowledged and held; nothing behind
            # it is read, acknowledged or delivered meanwhile
            assert [e[0] for e in rig.events] == ["ack", "pause"]
            assert rig.transport.paused
            rig.loop.run_until_complete(asyncio.sleep(0.2))
            assert [e[0] for e in rig.events] == [
                "ack", "pause", "msg", "ack", "pause", "msg",
                "ack", "pause", "msg"]
            assert [m.n for m in rig.delivered()] == [1, 2, 3]
            assert not rig.transport.paused
        finally:
            faults.get().reset(seed=0)
            rig.close()

    def test_undecodable_and_dup_do_not_stop_the_link(self):
        garbage = b"\xfe\xfd\xfc"
        rig = Rig()
        try:
            rig.push(_HDR.pack(MAGIC, MData.TYPE, len(garbage), 1) + garbage
                     + wire(MPing(n=2), 2) + wire(MPing(n=9), 1), 5)
            assert [m.n for m in rig.delivered()] == [2]
            assert rig.acks() == [ack_for(1), ack_for(2), ack_for(1)]
            assert not rig.transport.closed
        finally:
            rig.close()

    def test_bad_magic_ends_the_connection_with_the_error(self):
        rig = Rig()
        try:
            rig.push(b"XXXX" + bytes(20))
            assert rig.transport.closed
            assert isinstance(rig.reader.done.exception(), ValueError)
        finally:
            rig.close()


def raw_banner(name=b"raw", nonce=7):
    addr = msgr_mod._pack_addr(("127.0.0.1", 1))
    return msgr_mod._BANNER.pack(msgr_mod.BANNER_MAGIC, nonce, len(name),
                                 len(addr)) + name + addr


class TestFrameReaderOnSockets:
    @pytest.mark.parametrize("n", [100, MIB4], ids=["small", "4M"])
    def test_bytes_read_with_the_banner_are_not_lost(self, n):
        """A connector that does not wait for the banner reply: its
        first frame sits in the handshake's StreamReader (and, past
        twice that stream's limit, in a paused socket) when the frame
        reader takes over."""
        b, bd = make_msgr("b")
        try:
            blob = pattern(n, 3)
            s = socket.create_connection(b.addr, timeout=5)
            s.sendall(raw_banner() + wire(MData(blob=blob), 1)
                      + wire(MPing(n=2), 2))
            _, first = bd.get(timeout=10)
            _, second = bd.get(timeout=10)
            assert first.blob == blob and second.n == 2
            got = b""
            want = msgr_mod._BANNER_REPLY.size + 2 * _HDR.size
            s.settimeout(5)
            while len(got) < want:
                got += s.recv(want - len(got))
            assert got[msgr_mod._BANNER_REPLY.size:] == \
                ack_for(1) + ack_for(2)
            s.close()
        finally:
            b.shutdown()

    def test_a_4m_segment_takes_few_reads_and_says_so(self):
        """With the whole frame queued in the kernel before the first
        read, the receive buffer takes the head and the segment's own
        buffer the rest."""
        b, bd = make_msgr("b")
        room = MIB4 + (256 << 10)
        try:
            # an accepted socket inherits the listener's buffer size
            for lsock in b._server.sockets:
                lsock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, room)
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, room)
            s.settimeout(10)
            s.connect(b.addr)
            s.sendall(raw_banner())
            rep = s.recv(msgr_mod._BANNER_REPLY.size)
            assert len(rep) == msgr_mod._BANNER_REPLY.size
            held = threading.Event()
            release = threading.Event()

            def hold():
                held.set()
                release.wait(10)
            b._loop_call(hold)          # the loop thread reads nothing
            assert held.wait(5)
            blob = pattern(MIB4, 8)
            sender = threading.Thread(
                target=s.sendall, args=(wire(MData(blob=blob), 1),))
            sender.start()
            sender.join(2)              # all of it queued, room allowing
            release.set()
            conn, msg = bd.get(timeout=10)
            sender.join(10)
            assert msg.blob == blob
            rcvbuf = b._server.sockets[0].getsockopt(
                socket.SOL_SOCKET, socket.SO_RCVBUF)
            assert msg._recv_reads <= math.ceil(MIB4 / rcvbuf) + 2
            assert msg._recv_bytes > MIB4
            s.close()
        finally:
            b.shutdown()
