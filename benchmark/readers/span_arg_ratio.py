"""A ratio of two counts that named spans carry as args, summed over
the root ops of the window and the docs under their trace ids.

Root ops and their docs are chosen as `op_span_time` chooses them: a
client write is one trace id (`<client>:<tid>`; a resent op's docs share
it), and the docs of the listed kinds that carry it are that op's (its
sub-ops on other daemons).

Parameters:
  root_kind, root_match, kinds, match   as in `op_span_time`
  span         name of the spans that carry the counts (`wal`)
  numerator    arg whose values are added (`blocks`: blocks a store
               commit wrote, copy-on-write and deferred)
  denominator  arg whose values are added (`dev_writes`: device write
               calls the commit made for them)

Where no span of the selected docs carries both args (a program from
before the counts existed) or the denominator did not move, there is
nothing to read.
"""

from __future__ import annotations

from benchmark.readers.op_span_time import members, roots


def read(readings, params) -> float | None:
    ids = roots(readings.op_docs, params)
    top, bottom = params["numerator"], params["denominator"]
    num = den = found = 0
    for doc in members(readings.op_docs, params, ids):
        for span in doc["spans"]:
            args = span.get("args") or {}
            if span["name"] == params["span"] and top in args \
                    and bottom in args:
                num += args[top]
                den += args[bottom]
                found += 1
    if den <= 0:
        readings.log(f"span arg ratio: {len(ids)} root ops, {found} "
                     f"{params['span']} spans with {top} and {bottom}, "
                     f"{bottom} {den}")
        return None
    readings.log(f"span arg ratio: {num} {top} over {den} {bottom} on "
                 f"{found} {params['span']} spans of {len(ids)} root ops")
    return num / den
