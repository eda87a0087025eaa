"""denc: the versioned data-only wire/disk codec (utils/denc.py).

Mirrors the reference's encoding discipline tests
(test/encoding/test_denc.cc): primitive roundtrips, struct versioning
with compat failure on newer versions, and clean errors on hostile or
corrupt input (the property pickle lacked).

The codec has two walks of one format: the native tier's compiled one
(native/pyext.cc) and the Python one that serves where the extension
cannot be built.  Every case of the classes below runs under both
(conftest's `denc_walk`), and `TestTwoWalks` holds each to the other:
the same bytes, the same values of the same types, the same refusals.
"""

import random
import sys
import threading
from typing import NamedTuple

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.utils import denc
from ceph_tpu.utils.denc import DencError, denc_type

both_walks = pytest.mark.usefixtures("denc_walk")


def rt(obj):
    return denc.loads(denc.dumps(obj))


@both_walks
class TestPrimitives:
    def test_scalars(self):
        for v in (None, True, False, 0, 1, -1, 2**100, -(2**100),
                  127, 128, 1 << 63, 0.0, -2.5, float("inf")):
            assert rt(v) == v
            assert type(rt(v)) is type(v)

    def test_bytes_str(self):
        assert rt(b"") == b""
        assert rt(b"\x00\xff" * 100) == b"\x00\xff" * 100
        assert rt("héllo☃") == "héllo☃"

    def test_containers(self):
        v = {"a": [1, 2, (3, b"x")], ("t", 1): {4, 5}, 2: None}
        assert rt(v) == v
        assert type(rt((1, 2))) is tuple
        assert type(rt([1, 2])) is list

    def test_ndarray(self):
        a = np.arange(24, dtype=np.uint8).reshape(2, 3, 4)
        b = rt(a)
        np.testing.assert_array_equal(a, b)
        assert b.dtype == a.dtype
        s = rt(np.float32(1.5))
        assert s == 1.5

    def test_trailing_bytes_rejected(self):
        with pytest.raises(DencError):
            denc.loads(denc.dumps(1) + b"x")


@denc_type
class Point:
    DENC_VERSION = 2

    def __init__(self, x, y, z=0):
        self.x, self.y, self.z = x, y, z

    def __eq__(self, other):
        return (self.x, self.y, self.z) == (other.x, other.y, other.z)

    @staticmethod
    def _denc_upgrade(fields, version):
        if version == 1:
            fields = dict(fields)
            fields.setdefault("z", 0)
        return fields


@both_walks
class TestStructs:
    def test_roundtrip(self):
        p = rt(Point(1, 2, 3))
        assert p == Point(1, 2, 3)

    def test_private_fields_skipped(self):
        p = Point(1, 2)
        p._cache = "scratch"
        q = rt(p)
        assert not hasattr(q, "_cache")

    def test_old_version_upgrades(self):
        # hand-build a v1 frame: obj tag, name, version=1, fields
        out = bytearray([denc.T_OBJ])
        out += denc._uvarint(len(b"Point")) + b"Point"
        out += denc._uvarint(1)
        out += denc.dumps({"x": 7, "y": 8})
        p = denc.loads(bytes(out))
        assert p == Point(7, 8, 0)

    def test_newer_version_rejected(self):
        out = bytearray([denc.T_OBJ])
        out += denc._uvarint(len(b"Point")) + b"Point"
        out += denc._uvarint(3)
        out += denc.dumps({"x": 7, "y": 8})
        with pytest.raises(DencError, match="newer"):
            denc.loads(bytes(out))

    def test_unknown_type_rejected(self):
        out = bytearray([denc.T_OBJ])
        out += denc._uvarint(len(b"NoSuchThing")) + b"NoSuchThing"
        out += denc._uvarint(1)
        out += denc.dumps({})
        with pytest.raises(DencError, match="unknown"):
            denc.loads(bytes(out))

    def test_unregistered_type_not_encodable(self):
        class Rogue:
            pass
        with pytest.raises(DencError, match="not denc-encodable"):
            denc.dumps(Rogue())


@both_walks
class TestHostileInput:
    """Corrupt frames raise DencError — never execute code, never
    raise from arbitrary depth."""

    def test_truncated(self):
        frame = denc.dumps({"a": [1, 2, 3], "b": b"xyz"})
        for cut in range(len(frame)):
            with pytest.raises(DencError):
                denc.loads(frame[:cut])

    def test_bad_tag(self):
        with pytest.raises(DencError):
            denc.loads(b"\xfe")

    def test_fuzz_random_bytes(self):
        rng = np.random.default_rng(42)
        for _ in range(300):
            blob = rng.integers(0, 256, rng.integers(1, 60),
                                dtype=np.uint8).tobytes()
            try:
                denc.loads(blob)
            except DencError:
                pass  # the only acceptable failure mode

    def test_huge_varint_rejected(self):
        with pytest.raises(DencError):
            denc.loads(bytes([denc.T_INT]) + b"\xff" * 200)

    def test_ndarray_size_mismatch(self):
        # declared shape (1,) x uint8 but 8 payload bytes
        out = bytearray([denc.T_NDARRAY])
        out += denc._uvarint(3) + b"|u1"
        out += denc._uvarint(1) + denc._uvarint(1)
        out += denc._uvarint(8) + b"\x00" * 8
        with pytest.raises(DencError, match="mismatch"):
            denc.loads(bytes(out))

    def test_object_dtype_rejected(self):
        out = bytearray([denc.T_NDARRAY])
        out += denc._uvarint(3) + b"|O8"
        out += denc._uvarint(1) + denc._uvarint(1)
        out += denc._uvarint(8) + b"\x00" * 8
        with pytest.raises(DencError):
            denc.loads(bytes(out))


@both_walks
class TestSystemTypes:
    def test_osdmap_roundtrip(self):
        from ceph_tpu.osd.osdmap import OSDMap, OSDMapIncremental, Pool
        m = OSDMap()
        inc = OSDMapIncremental(epoch=1)
        inc.new_pools[0] = Pool(id=0, name="data", pg_num=4)
        inc.new_up[0] = ("127.0.0.1", 5000)
        m.apply_incremental(inc)
        m2 = OSDMap.decode(m.encode())
        assert m2.epoch == 1
        assert m2.pools[0].name == "data"
        assert m2.pg_to_raw_osds.__self__  # bound, real object

    def test_monmap_roundtrip(self):
        from ceph_tpu.mon.monmap import MonMap
        mm = MonMap(fsid="f")
        mm.add("a", ("127.0.0.1", 1))
        m2 = MonMap.decode(mm.encode())
        assert m2.mons == {"a": ("127.0.0.1", 1)}

    def test_pgid_namedtuple(self):
        from ceph_tpu.osd.osdmap import PgId
        p = rt(PgId(3, 0x1f))
        assert isinstance(p, PgId)
        assert p.pool == 3 and p.seed == 0x1f

    def test_message_roundtrip(self):
        from ceph_tpu.msg.message import Message
        from ceph_tpu.osd.messages import MOSDOp
        msg = MOSDOp(tid=1, pgid="0.1", oid="foo",
                     ops=[("writefull", b"data")], epoch=3)
        msg.src = "client.1"
        frame = msg.encode(seq=9)
        tid, plen, seq = Message.parse_header(frame[:Message.header_size()])
        out = Message.decode(tid, seq, frame[Message.header_size():])
        assert out.oid == "foo"
        assert out.ops == [("writefull", b"data")]

    def test_message_hostile_payload(self):
        from ceph_tpu.msg.message import Message
        from ceph_tpu.osd.messages import MOSDOp
        with pytest.raises(DencError):
            Message.decode(MOSDOp.TYPE, 0, b"\x93\x01\x02\x03")


@both_walks
class TestSchemaUpgrades:
    def test_old_pool_and_incremental_blobs_decode(self):
        """Pre-snap/pre-mgr blobs must upgrade, not AttributeError —
        mons replay stored incrementals across code upgrades."""
        from ceph_tpu.osd.osdmap import OSDMap, OSDMapIncremental, Pool
        import ceph_tpu.utils.denc as denc_mod

        def encode_as_version(obj, version, drop):
            fields = {k: v for k, v in obj.__dict__.items()
                      if not k.startswith("_") and k not in drop}
            out = bytearray()
            out.append(denc_mod.T_OBJ)
            name = type(obj).__name__.encode()
            out += denc_mod._uvarint(len(name)) + name
            out += denc_mod._uvarint(version)
            denc_mod._encode(fields, out)
            return bytes(out)

        pool = Pool(1, "p")
        blob = encode_as_version(pool, 1, {"snap_seq", "removed_snaps"})
        old = denc_mod.loads(blob)
        assert old.snap_seq == 0 and old.removed_snaps == []

        inc = OSDMapIncremental(epoch=1)
        blob = encode_as_version(
            inc, 1, {"new_pool_snap_seq", "new_removed_snaps",
                     "new_mgr"})
        old_inc = denc_mod.loads(blob)
        assert old_inc.new_mgr is None
        assert old_inc.new_pool_snap_seq == {}
        # and it applies cleanly
        m = OSDMap()
        m.apply_incremental(old_inc)
        assert m.epoch == 1

    def test_newer_version_rejected(self):
        from ceph_tpu.osd.osdmap import Pool
        import ceph_tpu.utils.denc as denc_mod
        out = bytearray()
        out.append(denc_mod.T_OBJ)
        out += denc_mod._uvarint(len(b"Pool")) + b"Pool"
        out += denc_mod._uvarint(99)
        denc_mod._encode({}, out)
        with pytest.raises(denc_mod.DencError):
            denc_mod.loads(bytes(out))


@denc_type
class Span(NamedTuple):
    lo: int
    hi: int


@denc_type
class Legacy:
    """A struct whose stored v1 has no way up to v2."""
    DENC_VERSION = 2

    def __init__(self):
        self.a = 1


def _obj_blob(name: bytes, version: int, fields) -> bytes:
    return (bytes([denc.T_OBJ]) + denc._uvarint(len(name)) + name
            + denc._uvarint(version) + denc.py_dumps(fields))


def _nested(n: int, leaf=None):
    """`leaf` under n lists: it decodes at depth n."""
    for _ in range(n):
        leaf = [leaf]
    return leaf


def _same(a, b) -> bool:
    """Equal values of equal types, all the way down (a NaN its own
    equal, dicts in the same order)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and a.shape == b.shape and \
            bool((a == b).all())
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_same, a, b))
    if isinstance(a, dict):
        return list(a) == list(b) and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (Point, Legacy)):
        return _same(a.__dict__, b.__dict__)
    return a == b or (a != a and b != b)


_INTS = (0, 1, -1, 63, 64, -64, -65, 127, 128, 2**31, -2**31, 2**62,
         2**63 - 1, 2**63, -2**63, -2**63 - 1, 2**64 - 1, 2**64,
         2**200, -2**200, 2**599, -(2**599))


def _tree(rng: random.Random, depth: int):
    """A random value of every kind the codec knows; containers while
    `depth` lasts."""
    k = rng.randrange(17 if depth > 0 else 10)
    if k == 0:
        return None
    if k == 1:
        return rng.random() < 0.5
    if k == 2:
        return rng.choice(_INTS + (rng.randrange(-10**6, 10**6),))
    if k == 3:
        return rng.choice((0.0, -2.5, float("inf"), float("nan"),
                           1e300, rng.random()))
    if k == 4:
        return rng.randbytes(rng.randrange(40))
    if k == 5:
        return rng.choice(("", "abc", "h\u00e9llo\u2603", "x" * 200))
    if k == 6:
        return bytearray(rng.randbytes(rng.randrange(9)))
    if k == 7:
        return memoryview(rng.randbytes(rng.randrange(9)))
    if k == 8:
        return rng.choice((np.int64(-5), np.uint64(2**64 - 1),
                           np.float32(1.5), np.float64(-0.25)))
    if k == 9:
        return rng.choice((
            Point(1, (2, b"y"), [3]), Span(3, 2**70),
            np.arange(6, dtype=np.int16).reshape(2, 3),
            np.zeros((0, 4), dtype=np.float32)))
    if k in (10, 11):
        return [_tree(rng, depth - 1) for _ in range(rng.randrange(5))]
    if k == 12:
        return tuple(_tree(rng, depth - 1)
                     for _ in range(rng.randrange(5)))
    if k in (13, 14):
        keys = ("a", "b", 1, 2, (1, "t"), b"k", None, True, 2.5)
        return {rng.choice(keys): _tree(rng, depth - 1)
                for _ in range(rng.randrange(5))}
    if k == 15:
        return {rng.choice((1, 2, "s", b"b", (1, 2), None))
                for _ in range(rng.randrange(4))}
    return frozenset(rng.randrange(5) for _ in range(3))


def _outcome(loads, blob):
    try:
        return "value", loads(blob)
    except DencError as e:
        return "refused", str(e)


@pytest.fixture(scope="module")
def ext():
    e = native.get_ext()
    if e is None:
        pytest.skip("the native tier's extension cannot be built here "
                    "(no g++, or no Python.h)")
    return e


class TestTwoWalks:
    """The compiled walk against the Python one, value by value."""

    @pytest.mark.parametrize("seed", range(8))
    def test_random_trees_same_bytes_same_values(self, ext, seed):
        rng = random.Random(seed)
        for _ in range(400):
            tree = _tree(rng, 5)
            blob = ext.denc_dumps(tree)
            assert blob == denc.py_dumps(tree), repr(tree)
            assert _same(ext.denc_loads(blob), denc.py_loads(blob)), \
                repr(tree)

    @pytest.mark.parametrize("value", [
        [], (), {}, set(), frozenset(), b"", "", bytearray(),
        memoryview(b""), [[], ((), {}), {"k": set()}],
        {"s": Point(1, 2, 3), "n": Span(1, 2), "a": np.eye(3)},
        [Span(0, -1), (Point([1], {"k": b"v"}),)],
        list(_INTS), {n: -n for n in _INTS},
        {"big": np.arange(5000, dtype=np.uint8), "b": b"x" * 70000},
    ], ids=lambda v: type(v).__name__)
    def test_named_cases_cross_decode(self, ext, value):
        blob = ext.denc_dumps(value)
        assert blob == denc.py_dumps(value)
        want = denc.py_loads(blob)
        assert _same(ext.denc_loads(blob), want)
        # each reads the other's bytes, and its own, to the same value
        assert _same(ext.denc_loads(denc.py_dumps(value)), want)
        assert _same(ext.denc_loads(bytearray(blob)), want)
        assert _same(ext.denc_loads(memoryview(blob)[::1]), want)

    @pytest.mark.parametrize("n,ok", [(100, True), (101, False)])
    def test_nesting_limit_is_the_same(self, ext, n, ok):
        tree = _nested(n)
        blob = ext.denc_dumps(tree)
        assert blob == denc.py_dumps(tree)
        for loads in (ext.denc_loads, denc.py_loads):
            if ok:
                assert _same(loads(blob), tree)
            else:
                with pytest.raises(DencError, match="nesting too deep"):
                    loads(blob)

    def test_every_truncation_and_flip_has_one_outcome(self, ext):
        rng = random.Random(11)
        sample = {"a": [1, -2**70, (2.5, "h\u00e9")], "b": b"xyz" * 9,
                  "s": {1, 2}, "p": Point(1, 2), "n": Span(4, 5),
                  "arr": np.arange(4, dtype=np.int32), 7: None}
        blob = denc.py_dumps(sample)
        for cut in range(len(blob)):
            with pytest.raises(DencError):
                ext.denc_loads(blob[:cut])
            with pytest.raises(DencError):
                denc.py_loads(blob[:cut])
            for _ in range(4):
                bad = blob[:cut] + bytes([rng.randrange(256)]) + \
                    blob[cut + 1:]
                got = _outcome(ext.denc_loads, bad)
                want = _outcome(denc.py_loads, bad)
                assert got[0] == want[0], (cut, bad)
                if got[0] == "refused":
                    assert got[1] == want[1], (cut, bad)
                else:
                    assert _same(got[1], want[1]), (cut, bad)

    def test_random_bytes_have_one_outcome(self, ext):
        rng = random.Random(42)
        for _ in range(3000):
            blob = rng.randbytes(rng.randrange(1, 60))
            got = _outcome(ext.denc_loads, blob)
            want = _outcome(denc.py_loads, blob)
            assert got[0] == want[0] and (
                got[1] == want[1] if got[0] == "refused"
                else _same(got[1], want[1])), blob

    def test_unencodable_values_refused_alike(self, ext):
        class Rogue:
            pass

        class Sub(bytes):
            pass
        for bad in (Rogue(), [1, {"k": Rogue()}], Sub(b"x"), {1, Rogue},
                    3j, np.bool_(True), range(3)):
            for dumps in (ext.denc_dumps, denc.py_dumps):
                with pytest.raises(DencError, match="not denc-encodable"):
                    dumps(bad)
        with pytest.raises(UnicodeEncodeError):
            ext.denc_dumps("\ud800")
        with pytest.raises(UnicodeEncodeError):
            denc.py_dumps("\ud800")

    def test_a_value_that_holds_itself_ends_in_recursion_error(self, ext):
        loop = []
        loop.append(loop)
        for dumps in (ext.denc_dumps, denc.py_dumps):
            with pytest.raises(RecursionError):
                dumps(loop)

    def test_a_container_changed_under_the_walk_is_refused(self, ext):
        """A helper of a value's own (`_denc_fields`) runs inside the
        compiled walk with the container's length already written: one
        that changes the container ends the walk, it cannot run off
        the end."""
        holder: list = []

        @denc_type
        class Shrinker:
            def _denc_fields(self):
                del holder[1:]
                return {}

        try:
            holder[:] = [Shrinker(), 1, 2, 3]
            with pytest.raises(RuntimeError, match="changed size"):
                ext.denc_dumps(holder)
            d = {"a": Shrinker(), "b": 1}
            Shrinker._denc_fields = lambda self: d.clear() or {}
            with pytest.raises(RuntimeError, match="changed size"):
                ext.denc_dumps(d)
        finally:
            del denc._registry["Shrinker"]

    def test_threads_share_the_walk(self, ext):
        """Values that go back to Python mid-walk let other threads in:
        sixteen threads, a short switch interval, every result whole."""
        tree = {"p": [Point(i, (i, b"x" * i)) for i in range(20)],
                "a": np.arange(64, dtype=np.int64), "n": Span(1, 2**80)}
        want = denc.py_dumps(tree)
        wrong: list = []

        def work():
            for _ in range(150):
                blob = ext.denc_dumps(tree)
                if blob != want or ext.denc_dumps(
                        ext.denc_loads(blob)) != want:
                    wrong.append(blob)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not wrong


REFUSALS = [
    ("truncated", denc.py_dumps([1, "two", b"three"])[:-2],
     "truncated input"),
    ("truncated length", bytes([denc.T_BYTES]) + b"\xff" * 11 + b"\x01",
     "truncated input"),
    ("trailing bytes", denc.py_dumps(1) + b"x", "1 trailing bytes"),
    ("nesting over 100", denc.py_dumps(_nested(101)), "nesting too deep"),
    ("varint over 600 bits", bytes([denc.T_INT]) + b"\xff" * 87 + b"\x00",
     "varint too long"),
    ("bad utf-8", bytes([denc.T_STR, 2]) + b"\xc3\x28", "bad utf-8"),
    ("unhashable dict key",
     bytes([denc.T_DICT, 1, denc.T_LIST, 0, denc.T_NONE]),
     "unhashable dict key"),
    ("unhashable set member", bytes([denc.T_SET, 1, denc.T_DICT, 0]),
     "unhashable set member"),
    ("bad tag", b"\x0d", "bad tag 0x0d"),
    ("bad tag inside", bytes([denc.T_LIST, 2, denc.T_NONE, 0xFE]),
     "bad tag 0xfe"),
    ("unknown type", _obj_blob(b"NoSuchThing", 1, {}), "unknown denc type"),
    ("unknown type before its fields",
     _obj_blob(b"NoSuchThing", 1, {})[:-1], "unknown denc type"),
    ("newer version", _obj_blob(b"Point", 3, {"x": 1, "y": 2}),
     "newer than supported"),
    ("version beyond a word",
     bytes([denc.T_OBJ, 5]) + b"Point" + b"\xff" * 10 + b"\x01"
     + denc.py_dumps({}), "newer than supported"),
    ("no upgrade path", _obj_blob(b"Legacy", 1, {"a": 1}),
     "no upgrade path"),
    ("field container not a dict", _obj_blob(b"Point", 2, [1, 2]),
     "bad field container"),
    ("namedtuple fields", _obj_blob(b"Span", 1, {"lo": 1}),
     "bad fields for Span"),
    ("object dtype",
     bytes([denc.T_NDARRAY, 3]) + b"|O8" + bytes([1, 1, 8]) + bytes(8),
     "object dtypes"),
    ("ndarray size mismatch",
     bytes([denc.T_NDARRAY, 3]) + b"|u1" + bytes([1, 1, 8]) + bytes(8),
     "mismatch"),
    ("ndarray dimensions",
     bytes([denc.T_NDARRAY, 3]) + b"|u1" + bytes([33]), "too many"),
    ("ndarray truncated",
     bytes([denc.T_LIST, 1, denc.T_NDARRAY, 3]) + b"|u1" + bytes([1, 4, 4]),
     "truncated input"),
]


@both_walks
class TestEveryRefusal:
    """Each way the Python walk refuses a blob, the compiled one
    refuses it too: a DencError that says the same."""

    @pytest.mark.parametrize("blob,says", [r[1:] for r in REFUSALS],
                             ids=[r[0] for r in REFUSALS])
    def test_refused(self, blob, says):
        with pytest.raises(DencError, match=says):
            denc.loads(blob)
        with pytest.raises(DencError, match=says):
            denc.loads(bytearray(blob))

    def test_not_bytes_at_all(self):
        with pytest.raises(TypeError):
            denc.loads(None)


class TestCounters:
    """`denc.counters()` (every daemon's `perf dump`, block `denc`)
    says which walk served."""

    def test_each_walk_counts_its_own_passes(self, denc_walk):
        before = denc.counters()
        for _ in range(5):
            assert denc.loads(denc.dumps({"a": [1, 2]})) == {"a": [1, 2]}
        after = denc.counters()
        mine, other = (("native_calls", "python_calls")
                       if denc_walk == "native"
                       else ("python_calls", "native_calls"))
        assert after[mine] - before[mine] == 10
        assert after[other] == before[other]
        assert after["value_callbacks"] == before["value_callbacks"]
        assert denc.python_calls == after["python_calls"]

    def test_values_handed_back_are_counted(self, ext):
        before = denc.counters()
        blob = denc.dumps([Point(1, 2), Span(1, 2), np.int64(3), 2**64,
                           np.zeros(2), "plain", 7])
        mid = denc.counters()
        assert mid["value_callbacks"] - before["value_callbacks"] == 5
        denc.loads(blob)
        after = denc.counters()
        # two structs, the big int and the array come back through
        # Python; the numpy scalar went out as a plain int
        assert after["value_callbacks"] - mid["value_callbacks"] == 4
        assert denc.native_calls == after["native_calls"]
        assert after["python_calls"] == before["python_calls"]
