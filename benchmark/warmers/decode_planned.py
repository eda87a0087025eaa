"""Decode for a pool whose code decodes by plan: ask the codec for its
plan of every pattern of 1..c lost chunks among the k+m (a pattern it
cannot plan ends set-up: the code's guarantee is any c), and wait for
the decode executable at every (rows shape, padded batch).  The decode
matrix is an operand of one executable per shape, and a plan that
reads fewer than k chunks rides the (r x k) operand with zero columns,
so one operand of each row count warms them all.  Row counts go up to
m, not c: a read is served from the first set that decodes, and that
set may lack up to m data chunks however few OSDs are down (four
parities and four data chunks of (8, 4, 3) decode; seen on the chip,
PR 28, as a compile 8 s into a run).  Through the names `decode.py`
uses (`_ec.py` lists them)."""

from __future__ import annotations

import itertools

import numpy as np

from benchmark.pools import ec
from benchmark.warmers import _ec

NEEDS_DATA = False


def plans(codec, k: int, m: int, c: int) -> dict:
    """rows rebuilt -> one (r x k) decode operand of that row count, for
    1..m rows, after planning every pattern of up to c lost chunks."""
    by_rows: dict = {}
    every = range(k + m)
    for n in range(1, c + 1):
        for lost in itertools.combinations(every, n):
            want = [i for i in lost if i < k]
            if not want:
                continue            # parities alone: a read decodes nothing
            present = codec.minimum_to_decode(
                want, [i for i in every if i not in lost])
            rows = codec._decode_rows(want, present)
            if len(want) not in by_rows:
                wide = np.zeros((len(want), k), dtype=np.uint8)
                wide[:, :rows.shape[1]] = rows
                by_rows[len(want)] = wide
    for r in range(1, m + 1):       # the shape is what an executable is of
        by_rows.setdefault(r, np.zeros((r, k), dtype=np.uint8))
    return by_rows


def warm(dep, inflight: int) -> dict:
    import jax
    k, m, unit = ec.shape(dep.config)
    c = int(dep.config["pool_profile"]["c"])
    buckets, devices = _ec.batch_buckets(dep, inflight), jax.devices()
    operands: dict = {}

    def probe(codec) -> bool:
        if id(codec) not in operands:
            operands[id(codec)] = plans(codec, k, m, c)
        return all([codec.backend.device_fn_if_ready(
            "bytes", rows, (), (b, k, unit), d) is not None
            for rows in operands[id(codec)].values()
            for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "planned decode fns")
    return {"decode_buckets": buckets,
            "decode_rows": sorted(next(iter(operands.values()))),
            "waited_decode_s": round(waited, 3)}
