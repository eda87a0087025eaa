"""EC stripe math + batched object encode/decode (osd/ECUtil.{h,cc}).

stripe_info_t (/root/reference/src/osd/ECUtil.h:35-85) gives the
logical<->chunk offset algebra: an object is a sequence of stripes of
stripe_width = k * chunk_size logical bytes; the shard file at acting
position p is one chunk of every stripe, concatenated: chunk p, unless
the codec maps its chunks (`get_chunk_mapping`: lrc lays `DD__DD__`),
then the chunk whose mapping is p (`shard_chunks`).  Everything here
that names a shard names a POSITION; chunk ids are the codec's own
affair.  The reference encodes stripe-by-stripe
(ECUtil::encode loop, ECUtil.cc:99-138) and chains per-shard CRC32C
(HashInfo::append, ECUtil.cc:140-154).  Here the whole object's stripes
form ONE (S, k, L) batch: a single fused device pass yields every
parity chunk and every scrub CRC, and the per-shard cumulative CRC is
folded on host with the carry-less combine — so the OSD data path rides
the MXU exactly where the reference rides SSE/AVX.
"""

from __future__ import annotations

import numpy as np

from ..erasure.interface import CHUNK_ALIGN, ErasureCodeError
from ..ops import crc32c as crc_mod
from ..utils import copyaudit
from ..utils.bufferlist import as_buffer, iov_of

DEFAULT_STRIPE_UNIT = 4096


class StripeInfo:
    """stripe_info_t: offset algebra between logical and chunk space."""

    def __init__(self, k: int, stripe_unit: int = DEFAULT_STRIPE_UNIT):
        if stripe_unit % CHUNK_ALIGN:
            stripe_unit = -(-stripe_unit // CHUNK_ALIGN) * CHUNK_ALIGN
        self.k = k
        self.chunk_size = stripe_unit
        self.stripe_width = k * stripe_unit

    # -- logical axis (ECUtil.h:59-85) ------------------------------------

    def logical_to_prev_stripe_offset(self, off: int) -> int:
        return off - (off % self.stripe_width)

    def logical_to_next_stripe_offset(self, off: int) -> int:
        return -(-off // self.stripe_width) * self.stripe_width

    def aligned_logical_offset_to_chunk_offset(self, off: int) -> int:
        assert off % self.stripe_width == 0
        return off // self.k

    def aligned_chunk_offset_to_logical_offset(self, off: int) -> int:
        assert off % self.chunk_size == 0
        return off * self.k

    def offset_len_to_stripe_bounds(self, off: int,
                                    length: int) -> tuple[int, int]:
        """(first_stripe_offset, aligned_length) covering [off, off+len)."""
        start = self.logical_to_prev_stripe_offset(off)
        end = self.logical_to_next_stripe_offset(off + length)
        return start, end - start

    # -- sizes -------------------------------------------------------------

    def stripe_count(self, logical_size: int) -> int:
        return max(1, -(-logical_size // self.stripe_width))

    def logical_size_to_shard_size(self, logical_size: int) -> int:
        return self.stripe_count(logical_size) * self.chunk_size


def shard_chunks(codec) -> list[int]:
    """The chunk id held at each shard position: the inverse of the
    codec's chunk mapping, the identity for a codec that has none."""
    mapping = codec.get_chunk_mapping()
    out = list(range(codec.get_chunk_count()))
    for chunk, pos in enumerate(mapping):
        out[pos] = chunk
    return out


def chunk_shards(codec) -> list[int]:
    """The shard position of each chunk id (the codec's mapping)."""
    return codec.get_chunk_mapping() or list(range(codec.get_chunk_count()))


def minimum_shards(codec, have, want=None) -> list[int]:
    """The shard positions the codec reads, among those in `have`, to
    give the shards at positions `want` (default: the data chunks, a
    client's read).  Raises ErasureCodeError where it cannot."""
    at, of = chunk_shards(codec), shard_chunks(codec)
    chunks = (range(codec.get_data_chunk_count()) if want is None
              else [of[p] for p in want])
    return sorted(at[c] for c in codec.minimum_to_decode(
        chunks, [of[p] for p in have]))


def fold_shard_crcs(stripe_crcs: np.ndarray, chunk_size: int,
                    upto: int | None = None) -> list[int]:
    """Fold the first `upto` stripes' chunk CRCs (S, km) into one
    cumulative CRC per shard with the carry-less combine — the
    chained-seed model of HashInfo::append.  upto=0 -> 0 per shard
    (CRC32C of the empty prefix under seed-chaining).

    One pairwise GF(2) reduction over all columns (crc32c_fold:
    log2(upto) numpy steps on byte tables of the advance matrices, no
    call a stripe); XOR on uint32, so exact at any stripe count."""
    S = len(stripe_crcs)
    if upto is not None and not 0 <= upto <= S:
        raise IndexError(f"upto {upto} outside the {S} stripes")
    return crc_mod.crc32c_fold(stripe_crcs[:upto], chunk_size).tolist()


class EncodeHandle:
    """In-flight whole-object encode: the stripes ride the shared
    device pipeline (coalescing with every other producer) while the
    caller builds its transactions/log entries; .result() blocks for
    (per-shard files, per-stripe chunk CRCs) at commit time.

    Shard files are ZERO-COPY views: one contiguous (km, S*L) relayout
    of the encode output (the only materialization — the shard-major
    transpose the store layout requires), then each shard is a
    memoryview row of it.  The views ride transaction writes, peer
    sub-op messages (out-of-band CTM2 segments) and store applies
    without ever becoming per-shard bytes objects."""

    __slots__ = ("_get", "_get_parts", "_src", "_at")

    def __init__(self, get, get_parts=None, src=None, at=None):
        self._get = get
        self._get_parts = get_parts
        self._src = src             # codec handle: phase stamps source
        self._at = at               # chunk id -> shard position, or None

    def result(self, timeout=None) -> tuple[list[memoryview], np.ndarray]:
        if self._get_parts is not None:
            # parts path: shards lay out straight from (stripes,
            # parity) — the joined (S, km, L) intermediate never exists
            stripes, parity, stripe_crcs = self._get_parts(timeout)
            S, k, L = stripes.shape
            km = k + parity.shape[1]
            shards = np.empty((km, S, L), dtype=np.uint8)
            shards[:k] = stripes.transpose(1, 0, 2)
            shards[k:] = parity.transpose(1, 0, 2)
        else:
            allc, stripe_crcs = self._get(timeout)
            S, km, L = allc.shape
            shards = np.ascontiguousarray(allc.transpose(1, 0, 2))
        if self._at:
            # chunk c lies at position at[c]: the files are views, so
            # only the order they are handed out in changes; the CRC
            # columns follow their chunks
            of = np.argsort(self._at)
            order, stripe_crcs = of.tolist(), np.asarray(stripe_crcs)[:, of]
        else:
            order = range(km)
        # op tracing: turn the pipeline's phase stamps (coalesce wait,
        # H2D staging, device compute, D2H — or the host drain) into
        # spans on whatever op this thread is executing; free when
        # nothing is traced
        from ..utils import optracker
        optracker.note_pipeline_phases(
            getattr(self._src, "trace_phases", None))
        # (km, S*L): the shard-major relayout — ONE copy for all km
        # shard files (audited), rows are views of it
        shards = shards.reshape(km, S * L)
        copyaudit.note("ec.shard_layout", shards.nbytes)
        return ([memoryview(shards[c]) for c in order],
                np.asarray(stripe_crcs))


def encode_object_async(codec, sinfo: StripeInfo, payload: bytes,
                        cache=None, qos=None) -> EncodeHandle:
    """Submit a whole-object encode; see EncodeHandle.

    The shard file at position p holds one chunk of every stripe, the
    one the codec maps there (the reference's shard layout; files and
    CRC columns come back in POSITION order); zero-padding of the tail
    stripe is part of the encoded
    state, as in ErasureCode::encode_prepare.  The raw (S, km) CRC
    matrix lets callers fold both the full-file CRC and the
    full-stripe-prefix CRC an append will chain from.

    `cache` (an ops.hbm_cache.CacheIntent) tags the encode for the
    HBM stripe cache: a device dispatch keeps the encoded stripes on
    its chip so later scrubs/recoveries of this object never re-upload
    (the caller commits the entry once the shards are on disk).

    `payload` may be bytes, a memoryview, or a BufferList rope — rope
    segments stage straight into the (S, k, L) batch buffer, so the
    whole client->encode journey costs exactly this ONE copy (the
    audited `ec.stage` site)."""
    plen = len(payload)
    S = sinfo.stripe_count(plen)
    L = sinfo.chunk_size
    buf = np.zeros(S * sinfo.stripe_width, dtype=np.uint8)
    off = 0
    for seg in iov_of(payload):
        n = len(seg)
        buf[off: off + n] = np.frombuffer(seg, dtype=np.uint8)
        off += n
    copyaudit.note("ec.stage", plen)
    stripes = buf.reshape(S, sinfo.k, L)
    at = codec.get_chunk_mapping() or None
    if hasattr(codec, "encode_stripes_with_crcs_async"):
        try:
            handle = codec.encode_stripes_with_crcs_async(
                stripes, cache=cache, qos=qos)
        except TypeError:   # non-pipeline codec: no cache/qos support
            handle = codec.encode_stripes_with_crcs_async(stripes)
        parts = getattr(handle, "result_parts", None)
        return EncodeHandle(lambda t: handle.result(t),
                            get_parts=parts, src=handle, at=at)
    out = codec.encode_stripes_with_crcs(stripes)
    return EncodeHandle(lambda t: out, at=at)


def encode_object_ex(codec, sinfo: StripeInfo, payload: bytes,
                     qos=None) -> tuple[list[bytes], np.ndarray]:
    """Whole-batch encode -> (per-shard files, per-stripe chunk CRCs).
    `qos` tags the dispatch-lane pick (recovery rebuilds ride the
    @recovery class when one is configured)."""
    return encode_object_async(codec, sinfo, payload, qos=qos).result()


def encode_object(codec, sinfo: StripeInfo,
                  payload: bytes) -> tuple[list[bytes], list[int]]:
    """Whole-object encode -> (per-shard files, per-shard CRCs)."""
    shards, stripe_crcs = encode_object_ex(codec, sinfo, payload)
    return shards, fold_shard_crcs(stripe_crcs, sinfo.chunk_size)


def _shard_arrays(codec, sinfo: StripeInfo, shards: dict[int, bytes],
                  logical_size: int) -> tuple[dict[int, np.ndarray], int]:
    """({chunk id: its (S, L) rows} of the whole shard files among
    `shards`, which are keyed by position; S)."""
    of = shard_chunks(codec)
    shard_size = sinfo.logical_size_to_shard_size(logical_size)
    S = shard_size // sinfo.chunk_size
    return {of[int(p)]: np.frombuffer(as_buffer(s), dtype=np.uint8)
            .reshape(S, sinfo.chunk_size)
            for p, s in shards.items() if len(s) == shard_size}, S


def _rebuild_chunks(codec, arrs: dict[int, np.ndarray], want: list[int],
                    S: int, L: int, qos=None) -> None:
    """Add the chunks `want` to `arrs`, rebuilt from the chunks the
    codec's plan reads among those in it, in ONE batched device/host
    pass across all stripes; only the rebuilt chunks materialize
    (audited ``ec.decode_rebuild``)."""
    present = codec.minimum_to_decode(want, arrs.keys())
    if any(p not in arrs for p in present):
        raise ErasureCodeError(
            f"need chunks {present}, have {sorted(arrs)}")
    if hasattr(codec, "decode_batch"):
        stack = np.stack([arrs[p] for p in present], axis=1)
        # pipeline-coalesced when available: concurrent rebuilds
        # with one decode pattern share a device dispatch
        if hasattr(codec, "decode_batch_async"):
            try:
                # `qos` tags the decode lane pick: a rebuild's decode
                # rides @recovery under the repair cap, not the
                # client best-effort class
                handle = codec.decode_batch_async(
                    want, present, stack, qos=qos)
            except TypeError:   # non-pipeline codec: no qos kwarg
                handle = codec.decode_batch_async(
                    want, present, stack)
            rebuilt = np.asarray(handle.result())
            # decode-path phase spans (the PR 12 follow-up): the
            # rebuild's device window (coalesce/H2D/compute/D2H or
            # host drain) stamps the current op — a recovery
            # rebuild's device time shows up under its
            # recovery_wait breakdown instead of vanishing
            from ..utils import optracker
            optracker.note_pipeline_phases(
                getattr(handle, "trace_phases", None))
        else:
            rebuilt = np.asarray(
                codec.decode_batch(want, present, stack))
        for idx, c in enumerate(want):
            # (S, idx, L) slice is strided: the rebuilt chunk is
            # the decode OUTPUT materializing — the only copy a
            # degraded read pays, and only for the missing chunks
            chunk = np.ascontiguousarray(rebuilt[:S, idx])
            copyaudit.note("ec.decode_rebuild", chunk.nbytes)
            arrs[c] = chunk
    else:
        for s in range(S):
            out = codec.decode_chunks(
                want, {p: arrs[p][s] for p in present})
            for c in want:
                arrs.setdefault(c, np.empty((S, L), dtype=np.uint8))
                arrs[c][s] = out[c]
        for c in want:
            # same materialization as the batched path above —
            # the per-read copy floor must not under-report for
            # codecs without decode_batch
            copyaudit.note("ec.decode_rebuild", arrs[c].nbytes)


def rebuild_shards(codec, sinfo: StripeInfo, shards: dict[int, bytes],
                   lost: list[int], logical_size: int,
                   qos=None) -> dict[int, memoryview]:
    """The shard files at positions `lost`, rebuilt from the shard
    files in hand (keyed by position) WITHOUT the object in between:
    every code's rebuild and scrub repair.  The codec's plan for those
    chunks reads what it needs of the files (k of them for
    Reed-Solomon and cauchy, a local group's l for lrc, a shingle for
    shec) and ONE decode of len(lost) rows gives the lost chunks,
    parities included, directly: byte for byte the files an encode of
    the object lays out, so their CRC columns are `crc32c_batch` of
    their own rows."""
    of = shard_chunks(codec)
    arrs, S = _shard_arrays(codec, sinfo, shards, logical_size)
    _rebuild_chunks(codec, arrs, [of[p] for p in lost], S,
                    sinfo.chunk_size, qos)
    return {p: memoryview(arrs[of[p]]).cast("B") for p in lost}


def decode_object(codec, sinfo: StripeInfo, shards: dict[int, bytes],
                  logical_size: int, qos=None):
    """Reassemble logical bytes from the shard files in hand (keyed
    by shard position: k of them, or the fewer a codec's plan reads)
    as a ZERO-COPY :class:`~ceph_tpu.utils.bufferlist.BufferList`.

    Intact data shards contribute per-stripe chunk VIEWS straight over
    the shard buffers (the decode_concat fast path, without the join);
    missing data chunks are rebuilt in ONE batched device/host pass
    across all stripes rather than stripe-at-a-time, and only the
    rebuilt chunks materialize (audited ``ec.decode_rebuild``).  The
    old whole-object relayout+``tobytes`` copied every read once; now
    the host read floor matches the write floor — payload bytes
    materialize only where the copy audit says so."""
    from ..utils.bufferlist import BufferList
    k = codec.get_data_chunk_count()
    L = sinfo.chunk_size
    arrs, S = _shard_arrays(codec, sinfo, shards, logical_size)
    want = [i for i in range(k) if i not in arrs]
    if want:
        _rebuild_chunks(codec, arrs, want, S, L, qos)
    rope = BufferList()
    remaining = logical_size
    for s in range(S):
        if remaining <= 0:
            break
        for i in range(k):
            if remaining <= 0:
                break
            take = min(L, remaining)
            mv = memoryview(arrs[i][s])
            rope.append(mv[:take] if take < L else mv)
            remaining -= take
    return rope
