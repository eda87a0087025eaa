"""RADOS ops the OSDs served for each op of the window: what a layer
above the client (a gateway) costs below it.

Parameters:
  any   substrings of a client op doc's description; a doc that has
        one of them counts (`'writefull'`, `'append'`, `'call'`: the
        writes; `'read'`: the reads)
  kind  the kind of the window's ops that divides the count (`write`:
        the acknowledged PUTs; `read`: the verified GETs)

An OSD op is one trace id (`<client>:<tid>`): a resent op leaves
several docs and counts once.  The docs are `dump_historic_ops` docs of
the client ops that started inside the window; the window's ops are the
benchmark's own records (kind, t0, t1, ok, bytes).
"""

from __future__ import annotations


def read(readings, params) -> float | None:
    ops = sum(1 for kind, _t0, _t1, ok, _b in readings.window_ops
              if ok and kind == params["kind"])
    served = {doc["trace_id"] for doc in readings.op_docs
              if doc["kind"] == "client"
              and any(s in doc["description"] for s in params["any"])}
    if not ops or not served:
        return None
    return len(served) / ops
