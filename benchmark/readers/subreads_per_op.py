"""How many sub-reads a client read sent, as a mean over the reads
that sent any.

A degraded or cache-missing EC read asks shard OSDs for their shards;
each `sub_read` op there leaves a doc under the client op's trace id.
A read the HBM cache serves sends none and is not in the mean (the log
says how many there were).

Parameters:
  op      substring of the client op's description that selects the
          ops (`'read'`)
  match   substring of a sub-read doc's description (`sub_read(`)

Where no selected op has a sub-read doc (a window of cache hits, or a
program whose sub-reads make no op) there is nothing to read.
"""

from __future__ import annotations

from collections import Counter


def read(readings, params) -> float | None:
    ops = {d["trace_id"] for d in readings.op_docs
           if d["kind"] == "client" and d["trace_id"]
           and params["op"] in d["description"]}
    sent = Counter(d["trace_id"] for d in readings.op_docs
                   if d["kind"] == "subop" and d["trace_id"] in ops
                   and params["match"] in d["description"])
    if not sent:
        return None
    readings.log(f"sub-reads per op: {len(sent)} of {len(ops)} reads sent "
                 f"{sum(sent.values())}, most {max(sent.values())}, "
                 f"least {min(sent.values())}")
    return sum(sent.values()) / len(sent)
