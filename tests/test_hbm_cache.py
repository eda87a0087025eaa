"""HBM-resident EC stripe cache + zero-copy transfer plane contracts.

The tier-1 contracts pinned here:

  * accounting — stage/commit/lookup move the hit/miss/insert
    counters; an uncommitted (staged-only) entry never serves; a
    wrong-version lookup is a miss; LRU eviction keeps resident bytes
    within ``osd_ec_hbm_cache_bytes`` and recent touches survive;
  * store coherence — every applied store transaction is scanned:
    overwrite/append/truncate/remove/clone/move of a cached object's
    shard files invalidates the entry UNLESS the transaction attests
    the entry's exact version via the per-shard version xattr (the EC
    write fan-out landing the same content on more shards); a raw
    un-attested write (silent bitrot, test corruption) always
    invalidates, so a cache hit is as trustworthy as the disk read it
    replaces;
  * quarantine — a device failure drops the quarantined lane's
    entries (never serve from a chip in an unknown state) and the
    redrained work still resolves bit-exact vs the host oracle;
  * transfer plane — a warm device dispatch uploads exactly the
    padded data batch and reads back ONLY parity + CRCs (the
    bytes_h2d / bytes_d2h counters prove the no-data-echo identity);
  * cost-aware placement — measured per-(shape, chip) service-time
    EMAs override the least-loaded pick for a measured-faster lane,
    counted in cost_placements / cost_diverged; the knob off restores
    pure least-loaded.
"""

import numpy as np
import pytest

from ceph_tpu.ops import ec_kernels, gf, hbm_cache
from ceph_tpu.ops import pipeline as ec_pipeline
from ceph_tpu.ops.crc32c import crc32c_batch
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.utils import faults

K, M, L = 3, 2, 256
MATRIX = gf.reed_sol_van_matrix(K, M)
VER_KEY = "_v"


@pytest.fixture(autouse=True)
def _clean():
    faults.get().reset(seed=0)
    hbm_cache.configure(64 << 20)
    hbm_cache.get().clear()
    yield
    faults.get().reset(seed=0)
    hbm_cache.get().clear()
    hbm_cache.configure(64 << 20)


def _entry_arrays(rng, S=2):
    data = rng.integers(0, 256, size=(S, K, L), dtype=np.uint8)
    parity = np.stack([gf.encode_np(MATRIX, data[s])
                       for s in range(S)])
    chunks = np.concatenate([data, parity], axis=1)
    crcs = np.stack([crc32c_batch(chunks[s]) for s in range(S)]) \
        .astype(np.uint32)
    return data, parity, crcs


def _stage_commit(cache, cid, oid, version, rng, S=2):
    data, parity, crcs = _entry_arrays(rng, S)
    intent = hbm_cache.CacheIntent(cid, oid, version, S * K * L, L)
    cache.stage(intent, 0, data, parity, crcs)
    assert cache.commit(cid, oid, version)
    return data, parity, crcs


class TestAccounting:
    def test_stage_commit_lookup_roundtrip(self):
        rng = np.random.default_rng(1)
        cache = hbm_cache.HbmStripeCache()
        data, parity, crcs = _stage_commit(cache, "pg_a", "obj",
                                           (1, 1), rng)
        ent = cache.lookup("pg_a", "obj", version=(1, 1))
        assert ent is not None
        assert ent.data_bytes() == data.tobytes()
        # per-shard fetch: data shards then parity shards
        for j in range(K):
            assert ent.shard_bytes(j) == data[:, j].tobytes()
        for j in range(M):
            assert ent.shard_bytes(K + j) == parity[:, j].tobytes()
        assert np.array_equal(ent.crcs, crcs)
        st = cache.stats()
        assert st["insert"] == 1 and st["hit"] == 1
        assert st["entries"] == 1 and st["pending"] == 0

    def test_staged_but_uncommitted_never_serves(self):
        rng = np.random.default_rng(2)
        cache = hbm_cache.HbmStripeCache()
        data, parity, crcs = _entry_arrays(rng)
        intent = hbm_cache.CacheIntent("pg_a", "obj", (1, 1),
                                       2 * K * L, L)
        cache.stage(intent, 0, data, parity, crcs)
        assert cache.lookup("pg_a", "obj") is None
        st = cache.stats()
        assert st["miss"] == 1 and st["hit"] == 0
        assert st["pending"] == 1 and st["entries"] == 0

    def test_wrong_version_lookup_misses(self):
        rng = np.random.default_rng(3)
        cache = hbm_cache.HbmStripeCache()
        _stage_commit(cache, "pg_a", "obj", (1, 1), rng)
        assert cache.lookup("pg_a", "obj", version=(1, 2)) is None
        assert cache.lookup("pg_a", "obj", version=(1, 1)) is not None

    def test_pending_entries_respect_byte_budget(self):
        """Staged-but-uncommitted entries pin device HBM exactly like
        committed ones: total resident bytes (committed + pending)
        must stay within capacity, oldest pending evicted first — an
        orphaned stage (producer died before commit) can't overcommit
        the chip."""
        rng = np.random.default_rng(6)
        one = _entry_arrays(rng)[0].nbytes * 2   # ~entry size bound
        cache = hbm_cache.HbmStripeCache(capacity=3 * one)
        for i in range(8):
            data, parity, crcs = _entry_arrays(rng)
            cache.stage(hbm_cache.CacheIntent("pg_a", f"o{i}", (1, i),
                                              2 * K * L, L),
                        0, data, parity, crcs)
            st = cache.stats()
            assert st["bytes"] + st["pending_bytes"] <= cache.capacity
        # newest pendings survived the budget, oldest were dropped
        assert cache.stats()["pending"] >= 1
        assert not cache.commit("pg_a", "o0", (1, 0))

    def test_configure_shrink_evicts_immediately(self):
        """Lowering osd_ec_hbm_cache_bytes at runtime takes effect at
        once — not at the next commit — so a read-only workload can't
        hold the old budget indefinitely."""
        rng = np.random.default_rng(7)
        cache = hbm_cache.configure(64 << 20)
        for i in range(4):
            _stage_commit(cache, "pg_a", f"o{i}", (1, i + 1), rng)
        big = cache.stats()["bytes"]
        assert big > 0
        hbm_cache.configure(big // 2)
        st = cache.stats()
        assert st["bytes"] + st["pending_bytes"] <= big // 2
        # most-recently-used survive
        assert cache.lookup("pg_a", "o3") is not None

    def test_drop_lane_spares_other_lanes_entries(self):
        """Regression: quarantining a lane drops only entries RESIDENT
        on that chip.  A rewrite's pending entry staged on the failed
        lane must not take down the same object's still-valid
        committed entry on a healthy chip (and vice versa)."""
        rng = np.random.default_rng(5)
        cache = hbm_cache.HbmStripeCache()
        data, parity, crcs = _entry_arrays(rng)
        intent = hbm_cache.CacheIntent("pg_a", "obj", (1, 1),
                                       2 * K * L, L)
        cache.stage(intent, 0, data, parity, crcs)
        assert cache.commit("pg_a", "obj", (1, 1))     # lane 0
        d2, p2, c2 = _entry_arrays(rng)
        cache.stage(hbm_cache.CacheIntent("pg_a", "obj", (1, 2),
                                          2 * K * L, L),
                    1, d2, p2, c2)                     # lane 1 pending
        cache.drop_lane(1)
        # committed lane-0 entry survives; the lane-1 pending is gone
        ent = cache.lookup("pg_a", "obj", version=(1, 1))
        assert ent is not None and ent.data_bytes() == data.tobytes()
        assert not cache.commit("pg_a", "obj", (1, 2))
        # reverse: pending on the healthy lane survives a committed
        # entry's lane failing, and can still commit
        cache.stage(hbm_cache.CacheIntent("pg_a", "obj", (1, 3),
                                          2 * K * L, L),
                    1, d2, p2, c2)
        cache.drop_lane(0)
        assert cache.lookup("pg_a", "obj", version=(1, 1)) is None
        assert cache.commit("pg_a", "obj", (1, 3))
        ent = cache.lookup("pg_a", "obj", version=(1, 3))
        assert ent is not None and ent.data_bytes() == d2.tobytes()

    def test_commit_wrong_version_rejected(self):
        rng = np.random.default_rng(4)
        cache = hbm_cache.HbmStripeCache()
        data, parity, crcs = _entry_arrays(rng)
        intent = hbm_cache.CacheIntent("pg_a", "obj", (1, 7),
                                       2 * K * L, L)
        cache.stage(intent, 0, data, parity, crcs)
        assert not cache.commit("pg_a", "obj", (1, 8))
        assert cache.lookup("pg_a", "obj") is None

    def test_lru_respects_capacity_and_recency(self):
        rng = np.random.default_rng(5)
        one = None
        cache = hbm_cache.HbmStripeCache(capacity=1)
        # discover one entry's footprint, then budget for exactly 3
        data, parity, crcs = _entry_arrays(rng)
        one = hbm_cache.CacheEntry(
            hbm_cache.CacheIntent("c", "o", (1, 1), 2 * K * L, L),
            0, data, parity, crcs).nbytes
        cache = hbm_cache.HbmStripeCache(capacity=3 * one)
        for i in range(3):
            _stage_commit(cache, "pg_a", f"obj{i}", (1, i + 1), rng)
        # touch obj0 so obj1 is the LRU victim of the next insert
        assert cache.lookup("pg_a", "obj0") is not None
        _stage_commit(cache, "pg_a", "obj3", (1, 4), rng)
        st = cache.stats()
        assert st["bytes"] <= 3 * one
        assert st["evict"] == 1
        assert cache.lookup("pg_a", "obj1") is None      # evicted
        assert cache.lookup("pg_a", "obj0") is not None  # survived
        assert cache.lookup("pg_a", "obj3") is not None

    def test_oversized_entry_never_stages(self):
        rng = np.random.default_rng(6)
        cache = hbm_cache.HbmStripeCache(capacity=16)
        data, parity, crcs = _entry_arrays(rng)
        intent = hbm_cache.CacheIntent("pg_a", "big", (1, 1),
                                       2 * K * L, L)
        cache.stage(intent, 0, data, parity, crcs)
        assert not cache.commit("pg_a", "big", (1, 1))
        assert cache.stats()["entries"] == 0

    def test_zero_capacity_disables(self):
        rng = np.random.default_rng(7)
        cache = hbm_cache.HbmStripeCache(capacity=0)
        data, parity, crcs = _entry_arrays(rng)
        cache.stage(hbm_cache.CacheIntent("pg_a", "o", (1, 1),
                                          2 * K * L, L),
                    0, data, parity, crcs)
        assert not cache.commit("pg_a", "o", (1, 1))
        assert cache.stats()["entries"] == 0


class TestStoreCoherence:
    """The object-store hook: every applied transaction is scanned and
    un-attested shard-data mutations invalidate (module docstring of
    ops/hbm_cache.py)."""

    def _cached(self, store, cid="pg_c", oid="victim",
                version=(1, 1)):
        rng = np.random.default_rng(11)
        cache = hbm_cache.get()
        data, _p, _c = _stage_commit(cache, cid, oid, version, rng)
        # the shard files the store holds (content irrelevant to the
        # scan — only the op names matter)
        store.apply_transaction(Transaction().create_collection(cid))
        txn = Transaction()
        for j in range(K + M):
            txn.write(cid, f"{oid}.s{j}", 0, b"shardbytes")
            txn.setattr(cid, f"{oid}.s{j}", VER_KEY,
                        repr(tuple(version)).encode())
        store.apply_transaction(txn)
        # the versioned shard landing did NOT invalidate (attested)
        assert cache.lookup(cid, oid, version=version) is not None
        return cache

    @pytest.mark.parametrize("mutate", [
        lambda t: t.write("pg_c", "victim.s1", 2, b"\xbe\xef"),
        lambda t: t.write("pg_c", "victim.s0", 4096, b"tail"),
        lambda t: t.truncate("pg_c", "victim.s2", 1),
        lambda t: t.zero("pg_c", "victim.s1", 0, 4),
        lambda t: t.remove("pg_c", "victim.s3"),
        lambda t: t.clone("pg_c", "victim.s0", "victim.s1"),
        lambda t: t.collection_move_rename("pg_c", "victim.s0",
                                           "pg_c", "stash"),
    ], ids=["overwrite", "append", "truncate", "zero", "remove",
            "clone-onto", "move-away"])
    def test_unattested_mutation_invalidates(self, mutate):
        store = MemStore()
        cache = self._cached(store)
        inval0 = cache.stats()["invalidate"]
        txn = Transaction()
        mutate(txn)
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim") is None
        assert cache.stats()["invalidate"] == inval0 + 1

    def test_same_version_fanout_keeps_entry(self):
        """A peer sub-write / recovery push of the SAME version is the
        cached content landing on more shards — attested, kept."""
        store = MemStore()
        cache = self._cached(store, version=(1, 5))
        txn = Transaction()
        txn.write("pg_c", "victim.s2", 0, b"same content")
        txn.setattr("pg_c", "victim.s2", VER_KEY,
                    repr((1, 5)).encode())
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim",
                            version=(1, 5)) is not None

    def test_newer_version_write_invalidates(self):
        store = MemStore()
        cache = self._cached(store, version=(1, 5))
        txn = Transaction()
        txn.write("pg_c", "victim.s2", 0, b"new content")
        txn.setattr("pg_c", "victim.s2", VER_KEY,
                    repr((1, 6)).encode())
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim") is None

    def test_rewrite_keeps_attested_fresh_pending(self):
        """Regression: a rewrite of a cached object stages a fresh
        pending entry at the new version, then its store txn applies
        attesting that version.  The scan must judge committed and
        pending INDEPENDENTLY — drop the stale committed entry but
        keep the attested pending one, so the rewrite's commit lands
        and hot objects stay covered write after write (the old
        keep-condition dropped both, losing coverage on every other
        rewrite)."""
        store = MemStore()
        cache = self._cached(store, version=(1, 1))
        rng = np.random.default_rng(12)
        data, parity, crcs = _entry_arrays(rng)
        cache.stage(hbm_cache.CacheIntent("pg_c", "victim", (1, 2),
                                          2 * K * L, L),
                    0, data, parity, crcs)
        txn = Transaction()
        for j in range(K + M):
            txn.write("pg_c", f"victim.s{j}", 0, b"new bytes")
            txn.setattr("pg_c", f"victim.s{j}", VER_KEY,
                        repr((1, 2)).encode())
        store.apply_transaction(txn)
        # stale committed entry gone, fresh pending commits and serves
        assert cache.lookup("pg_c", "victim", version=(1, 1)) is None
        assert cache.commit("pg_c", "victim", (1, 2))
        ent = cache.lookup("pg_c", "victim", version=(1, 2))
        assert ent is not None and ent.data_bytes() == data.tobytes()

    def test_stash_ops_do_not_invalidate(self):
        """Rollback-stash traffic is NOT a shard mutation: the EC
        write path stashes the prior object and later trims acked
        stashes — neither changes current shard bytes (a write would
        otherwise self-invalidate at stash-trim time).  A stash
        RESTORE writes to the shard file itself and still
        invalidates."""
        store = MemStore()
        cache = self._cached(store)
        stash = "victim.s0@(1, 0)"
        txn = Transaction()
        txn.try_clone("pg_c", "victim.s0", stash)
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim") is not None
        store.apply_transaction(Transaction().try_remove("pg_c", stash))
        assert cache.lookup("pg_c", "victim") is not None
        # the restore direction targets the shard file: invalidates
        txn = Transaction()
        txn.write("pg_c", stash, 0, b"old bytes")
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim") is not None
        store.apply_transaction(
            Transaction().clone("pg_c", stash, "victim.s0"))
        assert cache.lookup("pg_c", "victim") is None

    def test_rmcoll_drops_whole_collection(self):
        store = MemStore()
        cache = self._cached(store)
        store.apply_transaction(Transaction().remove_collection("pg_c"))
        assert cache.lookup("pg_c", "victim") is None

    def test_unrelated_objects_and_collections_unaffected(self):
        store = MemStore()
        cache = self._cached(store)
        store.apply_transaction(Transaction().create_collection("pg_z"))
        txn = Transaction()
        txn.write("pg_c", "bystander.s1", 0, b"x")
        txn.write("pg_z", "victim.s1", 0, b"x")
        store.apply_transaction(txn)
        assert cache.lookup("pg_c", "victim") is not None


def _fused_channel(bad_indices=(), key=("hbm", "enc")):
    """An always-warm fused encode+CRC channel (CPU jit compiles
    inline) whose device fn blows up like a dead chip on the listed
    jax device ids."""
    fused = ec_kernels.make_encode_crc_fn(MATRIX, L)

    def device_fn(padded, device=None):
        if device is not None and device.id in bad_indices:
            raise RuntimeError(f"chip {device.id} down")
        return fused(padded)

    def host_fn(batch):
        parity = np.stack([gf.encode_np(MATRIX, batch[s])
                           for s in range(batch.shape[0])])
        chunks = np.concatenate([batch, parity], axis=1)
        crcs = np.stack([crc32c_batch(chunks[s])
                         for s in range(batch.shape[0])])
        return parity, crcs.astype(np.uint32)

    return ec_pipeline.PipelineChannel(
        key=key, host_fn=host_fn, device_fn=device_fn,
        route=lambda n: True)


class TestPipelineIntegration:
    def test_encode_stages_entry_and_counts_transfer(self):
        """A cache-tagged device encode leaves its stripes in HBM
        (slices of the uploaded input + computed parity — zero extra
        transfer) and the lane counters account exactly the padded
        upload and the parity+CRC readback."""
        chan = _fused_channel()
        pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=64,
                                            coalesce_wait=0.001)
        cache = hbm_cache.get()
        rng = np.random.default_rng(21)
        try:
            data = rng.integers(0, 256, size=(2, K, L),
                                dtype=np.uint8)
            intent = hbm_cache.CacheIntent("pg_p", "obj", (3, 9),
                                           2 * K * L, L)
            st0 = pipe.stats()
            path, (parity, crcs) = pipe.submit(
                chan, data, cache=intent).result(timeout=60)
            assert path == "dev"
            st1 = pipe.stats()
            # transfer identity: upload == padded data batch, readback
            # == parity + CRC vector only (no data-shard echo)
            S_pad = ec_pipeline.next_bucket(2)
            assert st1["bytes_h2d"] - st0["bytes_h2d"] == \
                S_pad * K * L
            assert st1["bytes_d2h"] - st0["bytes_d2h"] == \
                ec_kernels.encode_readback_bytes(S_pad, K, M, L)
            # entry staged by the collector, serves after commit
            assert cache.commit("pg_p", "obj", (3, 9))
            ent = cache.lookup("pg_p", "obj", version=(3, 9))
            assert ent is not None
            assert ent.data_bytes() == data.tobytes()
            expect_parity = np.stack([gf.encode_np(MATRIX, data[s])
                                      for s in range(2)])
            for j in range(M):
                assert ent.shard_bytes(K + j) == \
                    expect_parity[:, j].tobytes()
            assert np.array_equal(ent.crcs, np.asarray(crcs))
            # what a deep scrub folds from the cache is the host's
            # CRC32C of every chunk, data and parity
            chunks = np.concatenate([data, expect_parity], axis=1)
            assert np.array_equal(
                ent.crcs, np.stack([crc32c_batch(chunks[s])
                                    for s in range(2)]))
            # cached reads are D2H-only: pipeline h2d must not move,
            # and the lookup counts as a hit in the pipeline's block
            st2 = pipe.stats()
            assert st2["bytes_h2d"] == st1["bytes_h2d"]
            assert st2["cache_hit"] == st1["cache_hit"] + 1
        finally:
            pipe.stop()

    def test_split_sized_tagged_batch_still_stages(self):
        """Regression (caught by the live-cluster drive): a cache-
        tagged batch big enough for the idle-lane splitter must still
        stage — row-split group parts can't stage (an item's rows
        straddle lanes), so placement cuts tagged batches at ITEM
        boundaries only; a single-item batch rides whole on one lane.
        Before the fix, 64 KiB objects never cached: every encode
        split across two idle lanes and the cache stayed empty."""
        chan = _fused_channel(key=("hbm", "split"))
        pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=1,
                                            coalesce_wait=0.001)
        cache = hbm_cache.get()
        rng = np.random.default_rng(23)
        try:
            S = 8      # untagged, this splits across the 8 idle lanes
            data = rng.integers(0, 256, size=(S, K, L),
                                dtype=np.uint8)
            intent = hbm_cache.CacheIntent("pg_s", "obj", (5, 1),
                                           S * K * L, L)
            path, _ = pipe.submit(chan, data,
                                  cache=intent).result(timeout=60)
            assert path == "dev"
            assert cache.commit("pg_s", "obj", (5, 1))
            ent = cache.lookup("pg_s", "obj", version=(5, 1))
            assert ent is not None
            assert ent.data_bytes() == data.tobytes()
            expect = np.stack([gf.encode_np(MATRIX, data[s])
                               for s in range(S)])
            for j in range(M):
                assert ent.shard_bytes(K + j) == \
                    expect[:, j].tobytes()
            # two tagged items in flight together (item-aligned split
            # or separate dispatches — either way BOTH must stage,
            # each whole on its own lane)
            d2 = [rng.integers(0, 256, size=(4, K, L), dtype=np.uint8)
                  for _ in range(2)]
            futs = [pipe.submit(chan, d2[i],
                                cache=hbm_cache.CacheIntent(
                                    "pg_s", f"o{i}", (5, 2 + i),
                                    4 * K * L, L))
                    for i in range(2)]
            for f in futs:
                f.result(timeout=60)
            for i in range(2):
                assert cache.commit("pg_s", f"o{i}", (5, 2 + i))
                e = cache.lookup("pg_s", f"o{i}")
                assert e is not None and \
                    e.data_bytes() == d2[i].tobytes()
        finally:
            pipe.stop()

    def test_split_part_entry_drops_with_its_lane_alone(self):
        """Two tagged ops that coalesce are cut at the item boundary:
        each part stages its item on its own lane, so losing one of
        the two lanes drops that object's entry and spares the
        other's."""
        chan = _fused_channel(key=("hbm", "parts"))
        pipe = ec_pipeline.EcDevicePipeline(depth=1, split_min=1,
                                            device_shards=2,
                                            coalesce_wait=0.001)
        cache = hbm_cache.get()
        rng = np.random.default_rng(24)
        try:
            pipe.submit(chan, np.zeros((1, K, L), np.uint8)).result(
                timeout=60)                 # builds the device set
            st0 = pipe.stats()
            # hold both lanes' one slot so that the two ops queue
            # together and ride one placed batch
            with pipe._lock:
                lanes = pipe._devset.lanes
                for lane in lanes:
                    lane.staging += 1
            datas = [rng.integers(0, 256, size=(4, K, L), dtype=np.uint8)
                     for _ in range(2)]
            futs = [pipe.submit(chan, d, cache=hbm_cache.CacheIntent(
                "pg_s", f"p{i}", (6, i), 4 * K * L, L))
                for i, d in enumerate(datas)]
            with pipe._lock:
                for lane in lanes:
                    lane.staging -= 1
                pipe._fetch_cv.notify_all()
            for f in futs:
                assert f.result(timeout=60)[0] == "dev"
            st1 = pipe.stats()
            assert st1["split_dispatches"] == st0["split_dispatches"] + 1
            ents = []
            for i in range(2):
                assert cache.commit("pg_s", f"p{i}", (6, i))
                ents.append(cache.lookup("pg_s", f"p{i}"))
            assert {e.lane for e in ents} == {0, 1}
            cache.drop_lane(ents[0].lane)
            assert cache.lookup("pg_s", "p0") is None
            kept = cache.lookup("pg_s", "p1")
            assert kept is not None and \
                kept.data_bytes() == datas[1].tobytes()
        finally:
            pipe.stop()

    def test_quarantine_drops_lane_entries_and_redrains_bitexact(self):
        """A device failure on the chip holding cached entries drops
        them (redrain re-uploads from host, never serves stale HBM)
        and the redrained work still matches the host oracle."""
        cache = hbm_cache.get()
        warm = _fused_channel(key=("hbm", "warm"))
        pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=64,
                                            coalesce_wait=0.001)
        rng = np.random.default_rng(22)
        try:
            data = rng.integers(0, 256, size=(1, K, L),
                                dtype=np.uint8)
            intent = hbm_cache.CacheIntent("pg_q", "obj", (1, 1),
                                           K * L, L)
            path, _ = pipe.submit(warm, data,
                                  cache=intent).result(timeout=60)
            assert path == "dev"
            assert cache.commit("pg_q", "obj", (1, 1))
            ent = cache.lookup("pg_q", "obj")
            assert ent is not None
            victim_lane = ent.lane
            victim_dev = pipe._ensure_devset().lanes[victim_lane] \
                .device
            # every dispatch on the victim chip now dies; keep
            # submitting until placement lands one there
            bad = _fused_channel(bad_indices={victim_dev.id},
                                 key=("hbm", "bad"))
            drops0 = cache.stats()["lane_drops"]
            batches, results = [], []
            for i in range(32):
                b = rng.integers(0, 256, size=(1, K, L),
                                 dtype=np.uint8)
                batches.append(b)
                # sequential submit+wait: the placement rotation
                # visits every lane within 8 whole-batch dispatches,
                # so the victim chip is hit deterministically
                results.append(pipe.submit(bad, b).result(timeout=60))
                if pipe.stats()["quarantines"]:
                    break
            st = pipe.stats()
            assert st["quarantines"] >= 1, st
            # redrained results: bit-exact vs the host oracle
            for b, (_path, (parity, crcs)) in zip(batches, results):
                expect = np.stack([gf.encode_np(MATRIX, b[s])
                                   for s in range(b.shape[0])])
                assert np.array_equal(np.asarray(parity), expect)
            # the quarantined lane's entries are GONE
            assert cache.lookup("pg_q", "obj") is None
            assert cache.stats()["lane_drops"] > drops0
        finally:
            pipe.stop()


class TestCostAwarePlacement:
    def _seed_emas(self, pipe, nbytes, fast_lane=0,
                   fast=1e-9, slow=1e-3):
        ds = pipe._ensure_devset()
        bucket = (max(nbytes, 1) - 1).bit_length()
        for lane in ds.lanes:
            lane.spb[bucket] = {
                "spb": fast if lane.index == fast_lane else slow,
                "n": 5}
        return ds

    def test_measured_faster_lane_overrides_least_loaded(self):
        chan = _fused_channel(key=("hbm", "cost"))
        pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=64,
                                            coalesce_wait=0.0,
                                            cost_aware=True)
        rng = np.random.default_rng(31)
        try:
            # warm the fn on every lane the rotation visits first
            for _ in range(8):
                pipe.submit(chan, rng.integers(
                    0, 256, size=(1, K, L),
                    dtype=np.uint8)).result(timeout=60)
            ds = self._seed_emas(pipe, K * L, fast_lane=0)
            st0 = pipe.stats()
            d0 = {i: l.dispatches for i, l in enumerate(ds.lanes)}
            for _ in range(8):
                pipe.submit(chan, rng.integers(
                    0, 256, size=(1, K, L),
                    dtype=np.uint8)).result(timeout=60)
            st1 = pipe.stats()
            assert st1["cost_placements"] > st0["cost_placements"]
            # the rotation's least-loaded pick visits every lane; the
            # measured-cost override must have redirected to lane 0
            assert st1["cost_diverged"] > st0["cost_diverged"]
            gained = {i: l.dispatches - d0[i]
                      for i, l in enumerate(ds.lanes)}
            assert gained[0] == 8, gained
        finally:
            pipe.stop()

    def test_knob_off_restores_least_loaded(self):
        chan = _fused_channel(key=("hbm", "nocost"))
        pipe = ec_pipeline.EcDevicePipeline(depth=2, split_min=64,
                                            coalesce_wait=0.0,
                                            cost_aware=False)
        rng = np.random.default_rng(32)
        try:
            for _ in range(4):
                pipe.submit(chan, rng.integers(
                    0, 256, size=(1, K, L),
                    dtype=np.uint8)).result(timeout=60)
            self._seed_emas(pipe, K * L, fast_lane=0)
            for _ in range(8):
                pipe.submit(chan, rng.integers(
                    0, 256, size=(1, K, L),
                    dtype=np.uint8)).result(timeout=60)
            st = pipe.stats()
            assert st["cost_aware"] is False
            assert st["cost_placements"] == 0
            assert st["cost_diverged"] == 0
        finally:
            pipe.stop()

    def test_perf_dump_carries_cache_and_transfer_counters(self):
        """The observability contract bench/operators rely on: the
        shared pipeline's stats carry the transfer + cache counter
        set."""
        st = ec_pipeline.stats()
        for key in ("bytes_h2d", "bytes_d2h", "cost_placements",
                    "cost_diverged", "cache_hit", "cache_miss",
                    "cache_evict", "cache_insert", "cache_invalidate",
                    "cache_lane_drops", "cache_bytes",
                    "cache_capacity", "cache_entries"):
            assert key in st, key


class TestCoalescedDispatchCompilesNothing:
    """What the first dispatch of two coalesced ops needs (the fused
    fn at the coalesced bucket, the programs that cut each item's rows
    out of the batch for the HBM cache) is compiled when its parts are
    first warmed, not on the threads that serve it."""

    @pytest.fixture
    def compiles(self):
        """A call that starts counting: the programs JAX compiles (or
        fetches from its cache) from then on."""
        import jax
        stamps, listeners = [], []

        def start():
            def on(event, _secs, **_kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    stamps.append(event)
            listeners.append(on)
            jax.monitoring.register_event_duration_secs_listener(on)
            return stamps
        yield start
        for on in listeners:
            jax.monitoring.unregister_event_duration_listener(on)

    def test_item_slices_are_warm_before_two_ops_coalesce(self, compiles):
        import jax
        S, Lx = 4, 384              # shapes no other test compiles
        matrix = gf.reed_sol_van_matrix(K, M)
        fn = ec_kernels.make_encode_crc_fn(matrix, Lx)
        chan = ec_pipeline.PipelineChannel(
            key=("hbm", "coalesce"), host_fn=None,
            device_fn=lambda padded, device=None: fn(padded),
            route=lambda nbytes: True, max_coalesce=2 * S)
        for rows in (S, 2 * S):     # what a codec's warm-up compiles
            jax.block_until_ready(fn(jax.device_put(
                np.zeros((rows, K, Lx), np.uint8), jax.devices()[0])))
        pipe = ec_pipeline.EcDevicePipeline(depth=1, device_shards=1,
                                            coalesce_wait=0.001)
        rng = np.random.default_rng(29)
        cache = hbm_cache.get()

        def submit(i):
            data = rng.integers(0, 256, size=(S, K, Lx), dtype=np.uint8)
            return data, pipe.submit(chan, data, cache=hbm_cache.CacheIntent(
                "pg_w", f"o{i}", (1, i), S * K * Lx, Lx))
        try:
            _d, fut = submit(0)                     # one op alone
            assert fut.result(timeout=60)[0] == "dev"
            assert ec_pipeline.wait_warmups(60)
            stamps = compiles()
            st0 = pipe.stats()
            # hold the lane's one slot so that two ops queue together
            with pipe._lock:
                lane = pipe._devset.lanes[0]
                lane.staging += 1
            subs = [submit(i) for i in (1, 2)]
            with pipe._lock:
                lane.staging -= 1
                pipe._fetch_cv.notify_all()
            for _d, fut in subs:
                assert fut.result(timeout=60)[0] == "dev"
            st1 = pipe.stats()
            assert st1["dispatches"] - st0["dispatches"] == 1, \
                "the two ops did not coalesce"
            assert stamps == []
            for i, (data, _f) in zip((1, 2), subs):
                assert cache.commit("pg_w", f"o{i}", (1, i))
                assert cache.lookup("pg_w", f"o{i}").data_bytes() == \
                    data.tobytes()
        finally:
            pipe.stop()

    def test_one_fn_object_a_key_under_concurrent_warm_ups(self, compiles):
        """Two shapes of one fn warm on two threads at once: both must
        be compiled on the fn the backend keeps, or the one left out
        compiles on the thread that first serves it."""
        import time
        import jax
        from ceph_tpu.erasure.matrix_codec import TpuBackend
        matrix = gf.reed_sol_van_matrix(K, M)
        stamps = compiles()
        for trial in range(4):
            backend = TpuBackend()
            shapes = [(b, K, 640 + 128 * trial) for b in (2, 4, 8)]
            for shape in shapes:
                backend.fused_fn_if_ready(matrix, shape)
            end = time.monotonic() + 120
            while not all(backend.fused_fn_if_ready(matrix, s) is not None
                          for s in shapes):
                assert time.monotonic() < end
                time.sleep(0.02)
            assert ec_pipeline.wait_warmups(60)
            before = len(stamps)
            for shape in shapes:
                jax.block_until_ready(backend.fused_fn_if_ready(
                    matrix, shape)(np.zeros(shape, np.uint8)))
            assert len(stamps) == before, trial
