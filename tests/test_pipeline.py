"""Cross-op EC dispatch pipeline: coalescing, shape-bucket padding,
futures, measured-routing amortization, and degrade draining.

The tier-1 contracts pinned here:
  * padded shape-bucket dispatches are BIT-EXACT vs the unpadded host
    oracle for odd batch sizes across bucket boundaries (encode and
    decode);
  * an injected `tpu_error` landing mid-queue degrades the plugin and
    drains every queued/in-flight op to the host matrix-codec path
    with results identical to a pure-host codec — nothing lost or
    corrupted;
  * a REAL device_fn failure (exception, not injected flag) takes the
    same drain path;
  * the documented batch_stripes=N profile key is parsed, validated,
    and used as the coalesce-size cap;
  * crc32c_batch (the vectorized host scrub fold) matches the scalar
    reference byte-for-byte.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.erasure.interface import ErasureCodeError
from ceph_tpu.erasure.registry import registry
from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.ops import ec_kernels, gf
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.get().reset(seed=0)
    yield
    faults.get().reset(seed=0)
    # a test that exhausted/quarantined device lanes on the GLOBAL
    # pipeline must not leak host-only dispatch into later tests
    pipe = ec_pipeline.get()
    st = pipe.stats()
    if st["devices"] and any(d["quarantined"]
                             for d in st["devices"].values()):
        pipe.reset_devices()


def _tpu(profile):
    return registry.factory("tpu", dict(profile))


def _oracle(profile):
    p = {k: v for k, v in profile.items()
         if k in ("k", "m", "technique", "w", "packetsize")}
    return registry.factory("jerasure", p)


# ---------------------------------------------------------------------------
# shape-bucket padding
# ---------------------------------------------------------------------------


def test_next_bucket_and_pad():
    assert [ec_pipeline.next_bucket(n) for n in (1, 2, 3, 4, 5, 9, 17)] \
        == [1, 2, 4, 4, 8, 16, 32]
    arr = np.arange(3 * 2 * 4, dtype=np.uint8).reshape(3, 2, 4)
    padded = ec_pipeline.pad_batch(arr)
    assert padded.shape == (4, 2, 4)
    assert np.array_equal(padded[:3], arr)
    assert not padded[3:].any()
    same = np.zeros((4, 2, 4), dtype=np.uint8)
    assert ec_pipeline.pad_batch(same) is same


@pytest.mark.parametrize("B", [1, 3, 5, 7, 9, 17])
@pytest.mark.parametrize("L", [128, 384, 640])
def test_padded_bucket_encode_crc_bitexact(B, L):
    """Property: the fused kernel on a zero-padded power-of-two bucket,
    sliced back to B, matches the unpadded host oracle exactly — for
    odd B straddling bucket boundaries and non-power-of-two L."""
    k, m = 3, 2
    rng = np.random.default_rng(B * 1000 + L)
    matrix = gf.reed_sol_van_matrix(k, m)
    stripes = rng.integers(0, 256, size=(B, k, L), dtype=np.uint8)
    fn = ec_kernels.make_encode_crc_fn(matrix, L)
    padded = ec_pipeline.pad_batch(stripes)
    assert padded.shape[0] == ec_pipeline.next_bucket(B)
    parity, crcs = fn(padded)
    parity = np.asarray(parity)[:B]
    crcs = np.asarray(crcs)[:B]
    expect_parity = np.stack([gf.encode_np(matrix, stripes[b])
                              for b in range(B)])
    assert np.array_equal(parity, expect_parity)
    for b in range(B):
        allc = np.concatenate([stripes[b], expect_parity[b]], axis=0)
        for c in range(k + m):
            assert int(crcs[b, c]) == crc_mod.crc32c_sw(
                0, allc[c].tobytes())


@pytest.mark.parametrize("B", [1, 3, 5, 9])
def test_padded_bucket_decode_bitexact(B):
    """Same property for the decode rows-matrix path."""
    k, m, L = 4, 2, 256
    rng = np.random.default_rng(B)
    matrix = gf.reed_sol_van_matrix(k, m)
    gen = gf.systematic_generator(matrix, k)
    data = rng.integers(0, 256, size=(B, k, L), dtype=np.uint8)
    parity = np.stack([gf.encode_np(matrix, data[b]) for b in range(B)])
    allc = np.concatenate([data, parity], axis=1)
    present = [1, 3, 4, 5]
    dmat = gf.decode_matrix(gen, k, present)
    fn = ec_kernels.make_codec_fn(dmat)
    stack = np.ascontiguousarray(allc[:, present])
    out = np.asarray(fn(ec_pipeline.pad_batch(stack)))[:B]
    assert np.array_equal(out, data)


# ---------------------------------------------------------------------------
# pipeline mechanics
# ---------------------------------------------------------------------------


def test_pipeline_coalesces_concurrent_submissions():
    calls = []

    def host_fn(batch):
        calls.append(batch.shape[0])
        return (batch,)

    chan = ec_pipeline.PipelineChannel(key=("t", 1), host_fn=host_fn)
    pipe = ec_pipeline.EcDevicePipeline(depth=1)
    try:
        futs = [pipe.submit(chan, np.full((2, 8), i, dtype=np.uint8))
                for i in range(10)]
        for i, f in enumerate(futs):
            path, (out,) = f.result(timeout=20)
            assert path == "host"
            assert out.shape == (2, 8) and (out == i).all()
        stats = pipe.stats()
        assert stats["ops"] == 10
        assert stats["stripes"] == 20
        assert stats["dispatches"] == len(calls) <= 10
        assert stats["mean_batch_size"] >= 2.0 or len(calls) == 10
    finally:
        pipe.stop()


def test_pipeline_respects_max_coalesce():
    sizes = []

    def host_fn(batch):
        sizes.append(batch.shape[0])
        return (batch,)

    chan = ec_pipeline.PipelineChannel(key=("t", 2), host_fn=host_fn,
                                       max_coalesce=3)
    pipe = ec_pipeline.EcDevicePipeline(depth=1)
    try:
        # stall the dispatcher with a first slow item so the rest queue
        ev = threading.Event()
        slow = ec_pipeline.PipelineChannel(
            key=("t", "slow"),
            host_fn=lambda b: (ev.wait(10), (b,))[1])
        first = pipe.submit(slow, np.zeros((1, 4), dtype=np.uint8))
        futs = [pipe.submit(chan, np.zeros((2, 4), dtype=np.uint8))
                for _ in range(4)]
        ev.set()
        first.result(timeout=20)
        for f in futs:
            f.result(timeout=20)
        # 8 stripes, cap 3 -> no host batch exceeded one 2-stripe pair
        # plus one more (2+2 <= 3 is false, so singles of 2)
        assert all(s <= 3 for s in sizes)
    finally:
        pipe.stop()


def test_scrub_channel_yields_to_write_under_contention():
    """Per-pool pipeline QoS: with both classes queued, the scrub CRC
    channel yields its (older!) dispatch slot to client-write encode
    work and the qos_scrub_yields counter records it."""
    order = []

    def mk(name):
        def host_fn(batch, _n=name):
            order.append(_n)
            return (batch,)
        return host_fn

    scrub = ec_pipeline.PipelineChannel(
        key=("t", "scrub"), host_fn=mk("scrub"), qos_class="scrub")
    write = ec_pipeline.PipelineChannel(
        key=("t", "write"), host_fn=mk("write"))
    ev = threading.Event()
    slow = ec_pipeline.PipelineChannel(
        key=("t", "slow-q"),
        host_fn=lambda b: (ev.wait(10), (b,))[1])
    pipe = ec_pipeline.EcDevicePipeline(depth=1, scrub_weight=0.25)
    try:
        first = pipe.submit(slow, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.1)          # dispatcher wedged inside `slow`
        fs = pipe.submit(scrub, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.02)         # scrub item is strictly OLDER
        fw = pipe.submit(write, np.zeros((1, 4), dtype=np.uint8))
        ev.set()
        first.result(timeout=20)
        fs.result(timeout=20)
        fw.result(timeout=20)
        assert order.index("write") < order.index("scrub")
        assert pipe.stats()["qos_scrub_yields"] >= 1
    finally:
        ev.set()
        pipe.stop()


def test_scrub_weight_one_restores_fifo():
    """scrub_weight >= 1 disables yielding: strict FIFO across
    classes (the older scrub item dispatches first)."""
    order = []

    def mk(name):
        def host_fn(batch, _n=name):
            order.append(_n)
            return (batch,)
        return host_fn

    scrub = ec_pipeline.PipelineChannel(
        key=("t", "scrub2"), host_fn=mk("scrub"), qos_class="scrub")
    write = ec_pipeline.PipelineChannel(
        key=("t", "write2"), host_fn=mk("write"))
    ev = threading.Event()
    slow = ec_pipeline.PipelineChannel(
        key=("t", "slow-q2"),
        host_fn=lambda b: (ev.wait(10), (b,))[1])
    pipe = ec_pipeline.EcDevicePipeline(depth=1, scrub_weight=1.0)
    try:
        first = pipe.submit(slow, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.1)
        fs = pipe.submit(scrub, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.02)
        fw = pipe.submit(write, np.zeros((1, 4), dtype=np.uint8))
        ev.set()
        first.result(timeout=20)
        fs.result(timeout=20)
        fw.result(timeout=20)
        assert order.index("scrub") < order.index("write")
        assert pipe.stats()["qos_scrub_yields"] == 0
    finally:
        ev.set()
        pipe.stop()


def test_pipeline_host_error_sets_future_exception():
    def host_fn(batch):
        raise RuntimeError("boom")

    chan = ec_pipeline.PipelineChannel(key=("t", 3), host_fn=host_fn)
    pipe = ec_pipeline.EcDevicePipeline()
    try:
        fut = pipe.submit(chan, np.zeros((1, 4), dtype=np.uint8))
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=20)
    finally:
        pipe.stop()


def test_pipeline_device_error_drains_to_host():
    """A device_fn that blows up mid-stream: on_error fires, the batch
    re-runs on host, results stay correct, later batches keep flowing."""
    errors = []

    def device_fn(padded):
        raise RuntimeError("device on fire")

    chan = ec_pipeline.PipelineChannel(
        key=("t", 4),
        host_fn=lambda b: (b + 1,),
        device_fn=device_fn,
        route=lambda nbytes: True,
        on_error=lambda e: errors.append(str(e)))
    pipe = ec_pipeline.EcDevicePipeline()
    try:
        futs = [pipe.submit(chan, np.full((1, 4), i, dtype=np.uint8))
                for i in range(5)]
        for i, f in enumerate(futs):
            path, (out,) = f.result(timeout=20)
            assert path == "host"
            assert (out == i + 1).all()
        assert errors
        assert pipe.stats()["device_errors"] >= 1
    finally:
        pipe.stop()


def test_pipeline_survives_on_error_callback_raising():
    """A failing device fetch whose on_error callback ALSO raises must
    resolve the futures (with the error) and leave the pipeline live
    for the next submission — never a dead collector + hung callers."""
    class _Lazy:
        def __iter__(self):
            raise RuntimeError("fetch failed")

    chan = ec_pipeline.PipelineChannel(
        key=("t", 5),
        host_fn=lambda b: (b,),
        device_fn=lambda padded: _Lazy(),   # blows up at collect
        route=lambda nbytes: True,
        on_error=lambda e: (_ for _ in ()).throw(
            RuntimeError("on_error broken")))
    pipe = ec_pipeline.EcDevicePipeline()
    try:
        fut = pipe.submit(chan, np.zeros((1, 4), dtype=np.uint8))
        with pytest.raises(RuntimeError):
            fut.result(timeout=20)
        # pipeline still serves after the failure
        ok = ec_pipeline.PipelineChannel(key=("t", 6),
                                         host_fn=lambda b: (b,))
        path, (out,) = pipe.submit(
            ok, np.ones((2, 4), dtype=np.uint8)).result(timeout=20)
        assert path == "host" and out.shape == (2, 4)
    finally:
        pipe.stop()


def test_stall_latch_keeps_new_work_flowing(monkeypatch):
    """A device fetch that HANGS (no exception) wedges a lane's
    collector; once every usable lane's overlap window stays full
    past STALL_TIMEOUT the dispatcher must latch host-only dispatch
    so new work keeps flowing instead of the whole process's EC I/O
    freezing.  Pinned to ONE device lane: with spare chips the
    pipeline rightly routes around a wedged lane instead of
    latching."""
    monkeypatch.setattr(ec_pipeline, "STALL_TIMEOUT", 0.2)
    ev = threading.Event()

    class _Blocker:
        def __array__(self, dtype=None):
            ev.wait(30)
            return np.zeros((1, 4), dtype=np.uint8)

    chan = ec_pipeline.PipelineChannel(
        key=("t", 7), host_fn=lambda b: (b + 1,),
        device_fn=lambda p: (_Blocker(),), route=lambda n: True)
    pipe = ec_pipeline.EcDevicePipeline(depth=1, coalesce_wait=0.01,
                                        device_shards=1)
    try:
        f1 = pipe.submit(chan, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.1)     # collector picks f1 up and wedges
        f2 = pipe.submit(chan, np.zeros((1, 4), dtype=np.uint8))
        time.sleep(0.1)     # f2 dispatched into the full window
        f3 = pipe.submit(chan, np.full((1, 4), 3, dtype=np.uint8))
        path, (out,) = f3.result(timeout=20)
        assert path == "host" and (out == 4).all()
        assert pipe.stats()["stalled"]
    finally:
        ev.set()
        pipe.stop()


def test_pipelined_encode_self_serves_on_wedged_pipeline(monkeypatch):
    """A producer blocked past RESULT_TIMEOUT computes its encode on
    the host itself — correct bytes, no infinite hang."""
    from concurrent.futures import Future
    from ceph_tpu.erasure import plugin_tpu
    monkeypatch.setattr(ec_pipeline, "RESULT_TIMEOUT", 0.2)
    codec = _tpu({"k": "2", "m": "1"})
    oracle = _oracle({"k": "2", "m": "1"})
    rng = np.random.default_rng(11)
    stripes = rng.integers(0, 256, size=(3, 2, 128), dtype=np.uint8)
    wedged = plugin_tpu._PipelinedEncode(codec, stripes, Future())
    allc, crcs = wedged.result()       # never-resolving future
    allc_o, crcs_o = oracle.encode_stripes_with_crcs(stripes)
    assert np.array_equal(allc, allc_o)
    assert np.array_equal(crcs, crcs_o)


def test_crc_channel_latches_host_after_device_error():
    """A real post-warm device failure on the scrub CRC channel must
    latch the channel to the host fold (no per-batch retry storm)."""
    assert not ec_pipeline._crc_device_dead
    chan = ec_pipeline.crc_channel(64)
    try:
        assert chan.route(64) is True
        ec_pipeline._crc_on_error(RuntimeError("device died"))
        assert ec_pipeline._crc_device_dead
        assert chan.route(64) is False
        # host path still produces correct CRCs through the pipeline
        rng = np.random.default_rng(9)
        arr = rng.integers(0, 256, size=(3, 64), dtype=np.uint8)
        _path, (crcs,) = ec_pipeline.get().submit(
            chan, arr).result(timeout=30)
        for i in range(3):
            assert int(crcs[i]) == crc_mod.crc32c_sw(
                0, arr[i].tobytes())
    finally:
        ec_pipeline._crc_device_dead = False


# ---------------------------------------------------------------------------
# plugin integration: degrade draining + bit-exactness
# ---------------------------------------------------------------------------


def test_tpu_error_mid_queue_matches_pure_host_codec():
    """Injected tpu_error lands while encodes are queued: every result
    (queued before AND submitted after) must match the pure-host
    codec bit-for-bit, and the plugin must degrade, not error."""
    profile = {"k": "3", "m": "2", "technique": "reed_sol_van",
               "host_cutover": "1"}      # prefer device -> fault path
    codec = _tpu(profile)
    oracle = _oracle(profile)
    rng = np.random.default_rng(42)
    batches = [rng.integers(0, 256, size=(B, 3, 256), dtype=np.uint8)
               for B in (1, 3, 2, 5, 1, 4, 2, 3)]
    handles = [codec.encode_stripes_with_crcs_async(b)
               for b in batches[:4]]
    faults.get().tpu_device_error(1.0)     # mid-queue
    handles += [codec.encode_stripes_with_crcs_async(b)
                for b in batches[4:]]
    for arr, h in zip(batches, handles):
        allc, crcs = h.result(timeout=60)
        allc_o, crcs_o = oracle.encode_stripes_with_crcs(arr)
        assert np.array_equal(allc, allc_o)
        assert np.array_equal(crcs, crcs_o)
    assert codec.degraded
    assert "device" in codec.degrade_reason


def test_real_device_failure_degrades_and_drains():
    """A device_fn exception (not the injected flag) must degrade the
    codec via on_error and still produce host-correct results."""
    profile = {"k": "2", "m": "1", "host_cutover": "1"}
    codec = _tpu(profile)
    oracle = _oracle(profile)

    # sabotage the backend: fused fn "ready" but explodes on use
    def bad_fused(matrix, shape, device=None):
        def fn(batch):
            raise RuntimeError("device collapsed")
        return fn

    codec.backend.fused_fn_if_ready = bad_fused
    rng = np.random.default_rng(7)
    stripes = rng.integers(0, 256, size=(3, 2, 128), dtype=np.uint8)
    allc, crcs = codec.encode_stripes_with_crcs(stripes)
    assert codec.degraded
    allc_o, crcs_o = oracle.encode_stripes_with_crcs(stripes)
    assert np.array_equal(allc, allc_o)
    assert np.array_equal(crcs, crcs_o)


def test_pipelined_decode_matches_host():
    profile = {"k": "4", "m": "2", "technique": "reed_sol_van"}
    codec = _tpu(profile)
    rng = np.random.default_rng(3)
    stripes = rng.integers(0, 256, size=(5, 4, 256), dtype=np.uint8)
    allc, _ = codec.encode_stripes_with_crcs(stripes)
    want, present = [0, 2], [1, 3, 4, 5]
    stack = np.ascontiguousarray(allc[:, present])
    out = np.asarray(
        codec.decode_batch_async(want, present, stack).result(60))
    assert np.array_equal(out[:, 0], stripes[:, 0])
    assert np.array_equal(out[:, 1], stripes[:, 2])


# ---------------------------------------------------------------------------
# batch_stripes profile key
# ---------------------------------------------------------------------------


def test_batch_stripes_parsed_and_wired():
    codec = _tpu({"k": "2", "m": "1", "batch_stripes": "8"})
    assert codec.batch_stripes == 8
    chan = codec._encode_channel(128)
    assert chan.max_coalesce == 8
    # default: no per-codec cap (pipeline global cap applies)
    codec2 = _tpu({"k": "2", "m": "1"})
    assert codec2.batch_stripes is None
    assert codec2._encode_channel(128).max_coalesce is None


@pytest.mark.parametrize("bad", ["0", "-3", "x", ""])
def test_batch_stripes_validation(bad):
    with pytest.raises(ErasureCodeError):
        _tpu({"k": "2", "m": "1", "batch_stripes": bad})


# ---------------------------------------------------------------------------
# vectorized host CRC fold (degraded-mode scrub throughput)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L", [1, 7, 8, 9, 100, 4096])
def test_crc32c_batch_matches_scalar(L):
    rng = np.random.default_rng(L)
    arr = rng.integers(0, 256, size=(6, L), dtype=np.uint8)
    got = crc_mod.crc32c_batch(arr, seed=0xDEADBEEF)
    for i in range(6):
        assert int(got[i]) == crc_mod.crc32c_sw(
            0xDEADBEEF, arr[i].tobytes())


def test_crc32c_batch_pure_python_fallback(monkeypatch):
    """The vectorized slicing-by-8 path (native ext masked off)."""
    import ceph_tpu.native as native
    monkeypatch.setattr(native, "available", lambda: False)
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 256, size=(4, 333), dtype=np.uint8)
    got = crc_mod.crc32c_batch(arr)
    for i in range(4):
        assert int(got[i]) == crc_mod.crc32c_sw(0, arr[i].tobytes())


def test_encode_with_crcs_host_fallback_vectorized():
    """Degraded-mode encode_with_crcs: batched CRC fold, same bytes."""
    codec = _tpu({"k": "3", "m": "2"})
    faults.get().tpu_device_error(1.0)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(4, 3, 256), dtype=np.uint8)
    parity, crcs = codec.encode_with_crcs(data)
    assert codec.degraded
    for b in range(4):
        expect_p = gf.encode_np(codec.coding_matrix, data[b])
        assert np.array_equal(parity[b], expect_p)
        allc = np.concatenate([data[b], expect_p], axis=0)
        for c in range(5):
            assert int(crcs[b, c]) == crc_mod.crc32c_sw(
                0, allc[c].tobytes())
