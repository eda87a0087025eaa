"""`closed_loop`, letter for letter, for a pool whose code decodes by
plan: set-up, loop and read-back are that module's own functions.  One
comparison is added to its verdict: of the reads it made for the
read-back, the chunks each was decoded from (`chunks` of the read's
`gather_wait` span on the primary's op doc) are a set the
configuration's plain REFERENCE accepts (`decodable(chunks, config)`),
limit 0 that are not.  A code that is not MDS can be handed k chunks
that do not decode; the bytes of such a read are caught by the
read-back, and this says why.

The docs are what `dump_historic_ops` still holds after the read-back
(the reads are the newest ops of their primaries, at most `clients` +
`readback_sample` of them); a read the HBM cache served gathered
nothing and is not counted.
"""

from __future__ import annotations

import importlib
import time

from benchmark.generators import closed_loop

prepare = closed_loop.prepare
run = closed_loop.run


def decode_sets(docs: list[dict], since: float) -> list[list[int]]:
    """The chunk sets of the client reads that started after `since`
    and gathered."""
    return [span["args"]["chunks"]
            for d in docs
            if d["kind"] == "client" and "'read'" in d["description"]
            and d["mstart"] >= since
            for span in d["spans"]
            if span["name"] == "gather_wait" and "chunks" in
            span.get("args", {})]


def verify(ctx, window: dict) -> dict:
    began = time.monotonic()
    verdict = closed_loop.verify(ctx, window)
    reference = importlib.import_module(
        f"benchmark.references.{ctx.dep.config['reference']}")
    sets = decode_sets(ctx.dep.historic_ops(), began)
    refused = [s for s in sets
               if not reference.decodable(s, ctx.dep.config)]
    for s in refused[:5]:
        ctx.log(f"decode set {s}: the reference's planner refuses it")
    ctx.log(f"decode sets of the read-back: {len(sets)} gathered, "
            f"{len(refused)} the reference refuses")
    verdict["comparisons"] += [
        ("decode_sets_refused", len(refused), "<=", 0),
        ("decode_sets_checked", len(sets), ">=", 1)]
    return verdict
