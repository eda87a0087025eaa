"""A size mix runs on a closed set of device programs.

After the warm-ups of a k=4 m=2 codec's buckets (the fused encode at
every power of two up to the batch cap, and what the first staging of
an item of each bucket starts on a warm thread), staging items of any
row count at any offset of a coalesced dispatch, extending an entry by
`append_through`, and checking a cache-served read of any entry acquire
NO further program (the harness's `CompileWatch` rule: JAX's
backend-compile event), and every served byte is the payload's.
"""

import jax
import numpy as np
import pytest

from ceph_tpu.ops import ec_kernels, gf, hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.ops.crc32c import crc32c_batch
from ceph_tpu.utils import faults

K, M, L = 4, 2, 896             # a chunk size no other test compiles
MATRIX = gf.reed_sol_van_matrix(K, M)
CAP = 256
APPEND_ROWS = 32                # a 512 KiB append at a 16 KiB stripe
FULL_ROWS = 93                  # the longest tail object of the mix
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _clean():
    faults.get().reset(seed=0)
    # room for all 200 entries at their full length at once
    hbm_cache.configure(512 << 20)
    hbm_cache.get().clear()
    yield
    hbm_cache.get().clear()
    hbm_cache.configure(64 << 20)


@pytest.fixture
def compiles():
    stamps = []

    def on(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            stamps.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    yield stamps
    jax.monitoring.unregister_event_duration_listener(on)


def _encode(data: np.ndarray):
    parity = np.stack([gf.encode_np(MATRIX, s) for s in data])
    chunks = np.concatenate([data, parity], axis=1)
    crcs = np.stack([crc32c_batch(c) for c in chunks]).astype(np.uint32)
    return parity, crcs


def test_a_size_mix_acquires_no_program_after_warm_up(compiles):
    fn = ec_kernels.make_encode_crc_fn(MATRIX, L)
    chan = ec_pipeline.PipelineChannel(
        key=("size-mix", K, M, L), host_fn=None,
        device_fn=lambda padded, device=None: fn(padded),
        route=lambda nbytes: True, max_coalesce=CAP)
    dev = jax.devices()[0]
    buckets = [1 << e for e in range(CAP.bit_length())]
    for rows in buckets:            # what a codec's warm-up compiles
        jax.block_until_ready(fn(jax.device_put(
            np.zeros((rows, K, L), np.uint8), dev)))
    pipe = ec_pipeline.EcDevicePipeline(depth=1, device_shards=1,
                                        coalesce_wait=0.001)
    cache = hbm_cache.get()
    rng = np.random.default_rng(42)
    payloads: dict[str, np.ndarray] = {}

    def submit(oid: str, rows: int):
        data = rng.integers(0, 256, size=(rows, K, L), dtype=np.uint8)
        payloads[oid] = data
        return pipe.submit(chan, data, cache=hbm_cache.CacheIntent(
            "pg_mix", oid, (1, 1), rows * K * L, L))

    try:
        # the first staging of an item of each bucket starts the
        # cache's programs of that bucket on a warm thread
        # (an append over a partial tail stripe re-encodes that stripe
        # with its 32 new ones: 33 rows, the bucket of 64)
        for b in buckets:
            if b <= 2 * APPEND_ROWS:
                assert submit(f"warm{b}", b).result(timeout=120)[0] == "dev"
        assert ec_pipeline.wait_warmups(300)
        assert ec_pipeline.warm_stats()["warm_failures"] == 0
        programs = hbm_cache.stats()["programs"]
        del compiles[:]

        # 200 submissions of 1-32 rows, held back eight at a time so
        # that they coalesce at whatever offsets their sizes add up to
        lane = None
        st0 = pipe.stats()
        for rnd in range(25):
            with pipe._lock:
                lane = pipe._devset.lanes[0]
                lane.staging += 1
            futs = [submit(f"o{rnd}.{i}", int(rng.integers(1, 33)))
                    for i in range(8)]
            with pipe._lock:
                lane.staging -= 1
                pipe._fetch_cv.notify_all()
            for i, fut in enumerate(futs):
                assert fut.result(timeout=120)[0] == "dev"
                # (the cache keeps 64 staged entries at most)
                assert cache.commit("pg_mix", f"o{rnd}.{i}", (1, 1))
        st1 = pipe.stats()
        assert st1["dispatches"] - st0["dispatches"] < 200, \
            "nothing coalesced"

        # every entry grows by 512 KiB appends to the longest tail of
        # the mix, and is read back through the device-side check
        served = 0
        for oid in [o for o in payloads if o.startswith("o")]:
            data, version = payloads[oid], 1
            while data.shape[0] < FULL_ROWS:
                # the old last stripe is re-encoded with the delta, as
                # an append over a partial tail stripe does
                full_before = data.shape[0] - 1
                add = min(APPEND_ROWS, FULL_ROWS - data.shape[0])
                tail = np.concatenate([data[full_before:], rng.integers(
                    0, 256, size=(add, K, L), dtype=np.uint8)])
                parity, crcs = _encode(tail)
                data = np.concatenate([data[:full_before], tail])
                assert cache.append_through(
                    "pg_mix", oid, (1, version), (1, version + 1),
                    data.size, L, full_before,
                    ec_pipeline.pad_batch(tail),
                    ec_pipeline.pad_batch(parity), crcs)
                version += 1
                assert cache.commit("pg_mix", oid, (1, version))
            ent = cache.lookup("pg_mix", oid, version=(1, version))
            before = cache.stats()["verified"]
            assert ent.stripes == FULL_ROWS
            assert ent.data_bytes() == data.tobytes(), oid
            assert cache.stats()["verified"] == before + 1, \
                "a served read went unchecked"
            payloads[oid] = data
            served += 1
        assert served == 200
        assert compiles == []
        assert hbm_cache.stats()["programs"] == programs

        # a recovery's fetch of one shard file
        ent = cache.lookup("pg_mix", "o0.1")
        parity, _crcs = _encode(payloads["o0.1"])
        assert ent.shard_bytes(K + 1) == parity[:, 1].tobytes()
        assert ent.shard_bytes(2) == payloads["o0.1"][:, 2].tobytes()

        # a flipped byte in an entry is refused by the check
        ent = cache.lookup("pg_mix", "o0.0")
        seg = ent.segs[-1]
        seg.data = seg.data.at[seg.row0, 0, 7].add(1)
        fails = cache.stats()["verify_fail"]
        assert ent.data_bytes() is None
        assert cache.stats()["verify_fail"] == fails + 1
        assert cache.lookup("pg_mix", "o0.0") is None
    finally:
        pipe.stop()
