"""Decode at every padded batch for 1..m rebuilt rows.  The decode
matrix is an operand of one executable per (rows shape, batch shape),
so one pattern of each row count warms them all."""

from __future__ import annotations

from benchmark.pools import ec
from benchmark.warmers import _ec

NEEDS_DATA = False


def warm(dep, inflight: int) -> dict:
    import jax
    k, m, unit = ec.shape(dep.config)
    buckets, devices = _ec.batch_buckets(dep, inflight), jax.devices()

    def probe(c) -> bool:
        return all([c.backend.device_fn_if_ready(
            "bytes", c._decode_rows(list(range(n)), list(range(n, n + k))),
            (), (b, k, unit), d) is not None
            for n in range(1, m + 1) for b in buckets for d in devices])

    waited = _ec.wait_codecs(dep, probe, "decode fns")
    return {"decode_buckets": buckets, "waited_decode_s": round(waited, 3)}
