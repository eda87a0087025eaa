"""What a rebuild dispatches in a pool whose code repairs by plan, at
the cell's shapes: the codec is asked for its plan of every one of the
k+m positions lost alone, and again with one and with two further
positions unavailable (the other positions an out OSD changed in the
PG, which hold nothing yet: a broken shingle falls back to a wider
set), and the decode executable is waited for at every (row set shape,
padded batch) those plans give.  A plan that reads fewer than k chunks
rides the (1 x k) operand with zero columns (`plugin_tpu`
`decode_batch_async`), so the shapes are few; they are taken from the
plans and not assumed.  Then the rebuild's own call on a dummy object,
for each position from its one-loss plan and from the plan with that
plan's first source taken away, each result compared with the shard the
encode laid out: whatever those calls compile beyond the executables
is compiled in set-up and not in the window.

Beside what `_ec.py` names, of the program:
  osd.pg_repairing, perf dump osd.rebuild_full
        `rebuild.require_repair_state`, asked first
  codec.minimum_to_decode
        the plan, as `osd.ecutil.minimum_shards` asks it
  osd.ecutil.StripeInfo, osd.ecutil.encode_object_ex,
  osd.ecutil.minimum_shards, osd.ecutil.rebuild_shards
        the dummy object's shard files, and the calls
        `pg._ec_read_step` makes for a rebuild (`want` given)
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmark.pools import ec
from benchmark.warmers import _ec, rebuild

NEEDS_DATA = False
FURTHER_UNAVAILABLE = 2


def plans(codec, width: int) -> dict:
    """(lost position, the others unavailable) -> the sorted chunks the
    codec's plan reads, for every position lost alone and with up to
    `FURTHER_UNAVAILABLE` more positions unavailable; a set the code
    cannot plan is left out (the rebuild then widens)."""
    from ceph_tpu.erasure.interface import ErasureCodeError
    out: dict = {}
    for lost in range(width):
        others = [p for p in range(width) if p != lost]
        for n in range(FURTHER_UNAVAILABLE + 1):
            for empty in itertools.combinations(others, n):
                avail = [p for p in others if p not in empty]
                try:
                    out[lost, empty] = sorted(
                        codec.minimum_to_decode([lost], avail))
                except ErasureCodeError:
                    pass
    return out


def operands(codec, k: int, width: int) -> dict:
    """(rows, columns) -> one decode operand of that shape as the
    pipeline is handed it (columns padded to k), one for every shape
    the plans give."""
    by_shape: dict = {}
    for (lost, _empty), reads in plans(codec, width).items():
        shape = (1, max(k, len(reads)))
        if shape not in by_shape:
            rows = codec._decode_rows([lost], reads)
            wide = np.zeros(shape, dtype=np.uint8)
            wide[:, :rows.shape[1]] = rows
            by_shape[shape] = wide
    return by_shape


def warm(dep, inflight: int) -> dict:
    import jax
    from ceph_tpu.osd import ecutil
    rebuild.require_repair_state(dep)
    k, m, unit = ec.shape(dep.config)
    buckets, devices = _ec.batch_buckets(dep, inflight), jax.devices()
    found: dict = {}

    def probe(codec) -> bool:
        if id(codec) not in found:
            found[id(codec)] = operands(codec, k, k + m)
        return all([codec.backend.device_fn_if_ready(
            "bytes", rows, (), (b, rows.shape[1], unit), d) is not None
            for rows in found[id(codec)].values()
            for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "rebuild row sets")
    codec = _ec.codecs(dep)[0]
    sinfo = ecutil.StripeInfo(k, unit)
    payload = (bytes(range(256)) * (dep.object_bytes // 256 + 1))[
        :dep.object_bytes]
    shards, _crcs = ecutil.encode_object_ex(codec, sinfo, payload)
    one_loss, sizes = [], {}
    for lost in range(k + m):
        others = [p for p in range(k + m) if p != lost]
        first = ecutil.minimum_shards(codec, others, [lost])
        again = ecutil.minimum_shards(
            codec, [p for p in others if p != first[0]], [lost])
        one_loss.append(len(first))
        for reads in (first, again):
            got = ecutil.rebuild_shards(
                codec, sinfo, {p: shards[p] for p in reads}, [lost],
                dep.object_bytes)
            if bytes(got[lost]) != bytes(shards[lost]):
                raise RuntimeError(
                    f"warm rebuild_shingle: position {lost} from {reads} "
                    "is not the shard the encode laid out")
            sizes[len(reads)] = sizes.get(len(reads), 0) + 1
    return {"rebuild_buckets": buckets,
            "row_set_shapes": sorted(next(iter(found.values()))),
            "one_loss_chunks": one_loss,
            "dummy_rebuilds_by_chunks": dict(sorted(sizes.items())),
            "waited_rebuild_s": round(waited, 3)}
