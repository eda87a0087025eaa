"""Multi-chip sharded EC dispatch: placement, mega-batch splitting,
per-lane quarantine + redrain.

conftest.py forces an 8-device CPU host platform, so these exercise
the REAL multi-device placement/split/quarantine code paths the TPU
pod runs — the tier-1 contracts pinned here:

  * sharded dispatch (split across chips, odd batch sizes, uneven
    shards) is BIT-EXACT vs a single-device pipeline vs the host
    oracle;
  * a device failure on one chip of eight quarantines THAT lane only:
    its work redrains onto surviving chips bit-identically, the codec
    does NOT degrade, and the quarantine counters move;
  * an injected `tpu_error` targeted at one device index does the
    same through the plugin path (untargeted injection still degrades
    the whole codec, as PR 1/2 pinned);
  * host fallback (and the owner's on_error degrade) happens only
    once EVERY chip is quarantined;
  * the plugin's fused encode+CRC and the scrub CRC channel, row-split
    over the lanes, equal the host oracles for odd S and L, whatever
    the batch's size against LANE_STAGE_BYTES.
"""

import threading
import time

import numpy as np
import pytest

from ceph_tpu.erasure.registry import registry
from ceph_tpu.ops import crc32c as crc_mod
from ceph_tpu.ops import ec_kernels, gf, hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.get().reset(seed=0)
    yield
    faults.get().reset(seed=0)
    pipe = ec_pipeline.get()
    st = pipe.stats()
    if st["devices"] and any(d["quarantined"]
                             for d in st["devices"].values()):
        pipe.reset_devices()


K, M, L = 3, 2, 256
MATRIX = gf.reed_sol_van_matrix(K, M)


def _host_fn(batch):
    from ceph_tpu.erasure.matrix_codec import NumpyBackend
    return (np.asarray(NumpyBackend().apply_bytes(MATRIX, batch)),)


def _ready_device_fn(bad_indices=(), errors=None):
    """A device fn that is ALWAYS warm (CPU jit compiles inline in
    ~100ms) so placement/split runs deterministically; devices whose
    jax id is in `bad_indices` blow up like a dead chip."""
    fn = ec_kernels.make_codec_fn(MATRIX)

    def device_fn(padded, device=None):
        if device is not None and device.id in bad_indices:
            if errors is not None:
                errors.append(device.id)
            raise RuntimeError(f"chip {device.id} down")
        return (fn(padded),)

    return device_fn


def _submit_odd_batches(pipe, chan, seed=0):
    """Stagger odd-sized submissions so coalescing builds mega-batches
    that straddle bucket boundaries and split unevenly."""
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, 256, size=(B, K, L), dtype=np.uint8)
               for B in (1, 3, 5, 7, 2, 9, 4, 17, 1, 6)]
    futs = [pipe.submit(chan, b) for b in batches]
    return batches, [f.result(timeout=60) for f in futs]


def _assert_oracle(batches, results, want_path=None):
    for arr, (path, (parity,)) in zip(batches, results):
        if want_path is not None:
            assert path == want_path
        expect = np.stack([gf.encode_np(MATRIX, arr[b])
                           for b in range(arr.shape[0])])
        assert np.array_equal(np.asarray(parity), expect)


def test_sharded_split_bitexact_vs_single_device_and_oracle():
    """Odd batch sizes + uneven splits across 8 chips == 1 chip ==
    host oracle, bit for bit."""
    chan = ec_pipeline.PipelineChannel(
        key=("mc", "enc"), host_fn=_host_fn,
        device_fn=_ready_device_fn(), route=lambda n: True)
    sharded = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                           coalesce_wait=0.001)
    single = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                          coalesce_wait=0.001,
                                          device_shards=1)
    try:
        b8, r8 = _submit_odd_batches(sharded, chan)
        b1, r1 = _submit_odd_batches(single, chan)
        _assert_oracle(b8, r8)
        _assert_oracle(b1, r1)
        for (p8, (o8,)), (p1, (o1,)) in zip(r8, r1):
            assert np.array_equal(np.asarray(o8), np.asarray(o1))
        st8, st1 = sharded.stats(), single.stats()
        assert st8["dev_dispatches"] >= 1
        assert st8["active_devices"] == 8
        assert st1["active_devices"] == 1
        used = [d for d in st8["devices"].values()
                if d["dispatches"] > 0]
        assert len(used) >= 2, st8["devices"]
    finally:
        sharded.stop()
        single.stop()


def test_large_batch_splits_across_idle_lanes():
    """One coalesced mega-batch splits into per-chip shards (uneven
    row counts included) and reassembles in submit order."""
    chan = ec_pipeline.PipelineChannel(
        key=("mc", "split"), host_fn=_host_fn,
        device_fn=_ready_device_fn(), route=lambda n: True)
    pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                        coalesce_wait=0.001)
    try:
        rng = np.random.default_rng(7)
        arr = rng.integers(0, 256, size=(13, K, L), dtype=np.uint8)
        path, (parity,) = pipe.submit(chan, arr).result(timeout=60)
        assert path == "dev"
        expect = np.stack([gf.encode_np(MATRIX, arr[b])
                           for b in range(13)])
        assert np.array_equal(np.asarray(parity), expect)
        st = pipe.stats()
        assert st["split_dispatches"] >= 1, st
        used = [d for d in st["devices"].values()
                if d["dispatches"] > 0]
        assert len(used) >= 2
    finally:
        pipe.stop()


WARM = 120.0        # device fns compile on background threads


def _plugin_encode_on_lanes(monkeypatch, lanes, batch, cache=None):
    """`batch` through a fresh plugin=tpu codec on a pipeline of
    `lanes` lanes, again and again until the device served it (and
    cut it across lanes, where it has the rows for that)."""
    pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                        coalesce_wait=0.001,
                                        device_shards=lanes)
    monkeypatch.setattr(ec_pipeline, "_global", pipe)
    codec = registry.factory("tpu", {"k": str(K), "m": str(M),
                                     "technique": "reed_sol_van",
                                     "host_cutover": "1"})
    split = lanes > 1 and batch.shape[0] > 1 and cache is None
    try:
        end = time.monotonic() + WARM
        while True:
            st0 = pipe.stats()
            dev0 = codec.stat_counters()["device_stripe_passes"]
            out = codec.encode_stripes_with_crcs_async(
                batch.copy(), cache=cache).result(60)
            st1 = pipe.stats()
            if codec.stat_counters()["device_stripe_passes"] > dev0 \
                    and (not split or st1["split_dispatches"]
                         > st0["split_dispatches"]):
                break
            assert time.monotonic() < end, st1
            time.sleep(0.05)
        assert not codec.degraded and st1["device_errors"] == 0
        if cache is not None:
            entries = hbm_cache.get()
            assert entries.commit(cache.cid, cache.oid, cache.version)
            assert entries.lookup(cache.cid, cache.oid).data_bytes() \
                == batch.tobytes()
        if split:
            used = [d for d in st1["devices"].values()
                    if d["dispatches"] > 0]
            assert len(used) >= 2, st1["devices"]
        return out
    finally:
        pipe.stop()


@pytest.mark.parametrize("S,length,lanes,stage_bytes", [
    (1, 192, 8, None),      # minimal batch: nothing to cut
    (5, 250, 8, None),      # odd S, odd L, one row a lane
    (3, 100, 4, None),      # fewer rows than lanes
    (5, 250, 8, 1024),      # a batch of LANE_STAGE_BYTES and more
])
def test_plugin_split_bitexact_vs_one_lane_and_oracle(
        monkeypatch, S, length, lanes, stage_bytes):
    """Fused encode + CRC through the plugin, row-split over the
    lanes == the same batch on one lane == the jerasure oracle.
    LANE_STAGE_BYTES bounds what is compiled ahead for the HBM
    cache's item slices and nothing else: a batch over it is placed
    and cached like any other."""
    if stage_bytes is not None:
        monkeypatch.setattr(ec_pipeline, "LANE_STAGE_BYTES", stage_bytes)
        assert S * K * length >= stage_bytes
    rng = np.random.default_rng(S * 1000 + length)
    batch = rng.integers(0, 256, size=(S, K, length), dtype=np.uint8)
    oracle = registry.factory("jerasure", {"k": str(K), "m": str(M),
                                           "technique": "reed_sol_van"})
    allc_o, crcs_o = oracle.encode_stripes_with_crcs(batch)
    for n in (lanes, 1):
        allc, crcs = _plugin_encode_on_lanes(monkeypatch, n, batch)
        np.testing.assert_array_equal(allc, allc_o)
        np.testing.assert_array_equal(crcs, crcs_o)
    if stage_bytes is not None:
        hbm_cache.configure(64 << 20)
        intent = hbm_cache.CacheIntent("pg_mc", "big", (1, 1),
                                       batch.nbytes, length)
        allc, crcs = _plugin_encode_on_lanes(monkeypatch, lanes, batch,
                                             cache=intent)
        np.testing.assert_array_equal(allc, allc_o)
        np.testing.assert_array_equal(crcs, crcs_o)


@pytest.mark.parametrize("size", [2048, 1000])
def test_scrub_crc_channel_splits_bitexact_vs_host_crc32c(
        monkeypatch, size):
    """A deep-scrub CRC batch cut over four lanes: every row's CRC
    equals crc32c on the host, for a size the two-level fold takes
    and for one it does not."""
    monkeypatch.setattr(ec_pipeline, "_crc_device_dead", False)
    pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                        coalesce_wait=0.001,
                                        device_shards=4)
    chan = ec_pipeline.crc_channel(size)
    rng = np.random.default_rng(size)
    batch = rng.integers(0, 256, size=(7, size), dtype=np.uint8)
    want = np.array([crc_mod.crc32c_sw(0, row.tobytes())
                     for row in batch], dtype=np.uint32)
    try:
        end = time.monotonic() + WARM
        while True:
            st0 = pipe.stats()
            path, (out,) = pipe.submit(chan, batch.copy()).result(60)
            np.testing.assert_array_equal(np.asarray(out), want)
            st1 = pipe.stats()
            if path == "dev" and st1["split_dispatches"] \
                    > st0["split_dispatches"]:
                break
            assert time.monotonic() < end, st1
            time.sleep(0.05)
        used = [d for d in st1["devices"].values() if d["dispatches"] > 0]
        assert len(used) >= 2, st1["devices"]
        assert st1["device_errors"] == 0
    finally:
        pipe.stop()


# whole batches on one lane each, and batches cut across the lanes
@pytest.mark.parametrize("split_min", [64, 1])
def test_one_bad_chip_quarantines_lane_and_redrains(split_min):
    """A real device failure on one chip of eight: that lane
    quarantines, its batch (or the whole split batch its part belonged
    to) redrains to surviving chips bit-exactly, and the channel
    owner's on_error (codec degrade) does NOT fire."""
    degraded = []
    errors: list = []
    chan = ec_pipeline.PipelineChannel(
        key=("mc", "bad1"), host_fn=_host_fn,
        device_fn=_ready_device_fn(bad_indices=(0,), errors=errors),
        route=lambda n: True,
        on_error=lambda e: degraded.append(e))
    pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=split_min,
                                        coalesce_wait=0.001)
    try:
        batches, results = _submit_odd_batches(pipe, chan)
        _assert_oracle(batches, results)
        st = pipe.stats()
        assert st["quarantines"] == 1, st
        assert st["devices"]["0"]["quarantined"]
        assert st["active_devices"] == 7
        assert st["redrained"] >= 1
        assert (st["split_dispatches"] >= 1) == (split_min == 1)
        assert errors, "bad chip never probed"
        assert not degraded, "codec degraded despite 7 live chips"
        # the quarantined lane takes no further dispatches
        q_before = st["devices"]["0"]["dispatches"]
        more, res = _submit_odd_batches(pipe, chan, seed=1)
        _assert_oracle(more, res)
        assert pipe.stats()["devices"]["0"]["dispatches"] == q_before
    finally:
        pipe.stop()


def test_all_chips_quarantined_falls_back_to_host_and_degrades():
    """Host fallback ONLY when every chip is quarantined — and then
    the owner's on_error fires (the plugin degrade hook)."""
    degraded = []
    chan = ec_pipeline.PipelineChannel(
        key=("mc", "allbad"), host_fn=_host_fn,
        device_fn=_ready_device_fn(bad_indices=tuple(range(8))),
        route=lambda n: True,
        on_error=lambda e: degraded.append(e))
    pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=64,
                                        coalesce_wait=0.001)
    try:
        rng = np.random.default_rng(3)
        arr = rng.integers(0, 256, size=(5, K, L), dtype=np.uint8)
        path, (parity,) = pipe.submit(chan, arr).result(timeout=60)
        assert path == "host"
        expect = np.stack([gf.encode_np(MATRIX, arr[b])
                           for b in range(5)])
        assert np.array_equal(np.asarray(parity), expect)
        st = pipe.stats()
        assert st["active_devices"] == 0
        assert st["quarantines"] == 8
        assert degraded, "owner never heard the exhaustion"
    finally:
        pipe.stop()


# small batches, and batches with rows enough to cut across the lanes
@pytest.mark.parametrize("sizes", [(1, 3, 2, 5), (32, 40)])
def test_targeted_tpu_error_quarantines_without_codec_degrade(sizes):
    """Injected `tpu_error 1.0 <device>` through the PLUGIN path: the
    pipeline quarantines that chip's lane at placement time, results
    stay bit-exact, and the codec does NOT degrade."""
    pipe = ec_pipeline.get()
    pipe.reset_devices()
    splits0 = pipe.stats()["split_dispatches"]
    codec = registry.factory("tpu", {"k": "2", "m": "1",
                                     "host_cutover": "1"})
    oracle = registry.factory("jerasure", {"k": "2", "m": "1"})
    faults.get().tpu_device_error(1.0, device="0")
    rng = np.random.default_rng(11)
    batches = [rng.integers(0, 256, size=(B, 2, 128), dtype=np.uint8)
               for B in sizes]
    handles = [codec.encode_stripes_with_crcs_async(b)
               for b in batches]
    for arr, h in zip(batches, handles):
        allc, crcs = h.result(timeout=60)
        allc_o, crcs_o = oracle.encode_stripes_with_crcs(arr)
        assert np.array_equal(allc, allc_o)
        assert np.array_equal(crcs, crcs_o)
    assert not codec.degraded
    st = pipe.stats()
    assert st["quarantines"] >= 1
    assert st["devices"]["0"]["quarantined"]
    assert st["active_devices"] == 7
    if max(sizes) >= 2 * pipe.split_min:
        assert st["split_dispatches"] > splits0


def test_untargeted_tpu_error_still_degrades_codec():
    """The PR 1/2 contract is unchanged: an untargeted device error
    degrades the whole codec to the host matrix-codec path."""
    codec = registry.factory("tpu", {"k": "2", "m": "1",
                                     "host_cutover": "1"})
    faults.get().tpu_device_error(1.0)
    rng = np.random.default_rng(13)
    stripes = rng.integers(0, 256, size=(3, 2, 128), dtype=np.uint8)
    allc, crcs = codec.encode_stripes_with_crcs(stripes)
    assert codec.degraded
    oracle = registry.factory("jerasure", {"k": "2", "m": "1"})
    allc_o, crcs_o = oracle.encode_stripes_with_crcs(stripes)
    assert np.array_equal(allc, allc_o)
    assert np.array_equal(crcs, crcs_o)


def test_reset_devices_clears_quarantine():
    chan = ec_pipeline.PipelineChannel(
        key=("mc", "reset"), host_fn=_host_fn,
        device_fn=_ready_device_fn(bad_indices=(1,)),
        route=lambda n: True)
    pipe = ec_pipeline.EcDevicePipeline(depth=1, split_min=64,
                                        coalesce_wait=0.001)
    try:
        # force a dispatch onto every lane until lane 1 trips
        deadline = time.time() + 30
        while time.time() < deadline:
            arrs, res = _submit_odd_batches(pipe, chan)
            _assert_oracle(arrs, res)
            if pipe.stats()["quarantines"]:
                break
        assert pipe.stats()["quarantines"] == 1
        pipe.reset_devices()
        st = pipe.stats()
        assert st["active_devices"] in (0, 8)   # rebuilt lazily
        arrs, res = _submit_odd_batches(pipe, chan, seed=2)
        _assert_oracle(arrs, res)
        assert pipe.stats()["active_devices"] >= 7
    finally:
        pipe.stop()
