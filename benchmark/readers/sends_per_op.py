"""How many times the client sent an op, as a mean over the ops.

The objecter numbers its sends of one op (`attempt` 1, 2, ...) and the
OSD puts the number on the op's doc; a resent op leaves one doc a send
under one trace id.  An op's sends are the highest `attempt` among its
docs, so a send whose doc fell out of the window still counts.

Parameters:
  op   substring of the client op's description that selects the ops
       (`'writefull'`, `'read'`)

Where no selected doc carries `attempt` (a program from before the
client sent it) there is nothing to read.
"""

from __future__ import annotations


def read(readings, params) -> float | None:
    sends: dict = {}
    for doc in readings.op_docs:
        if doc["kind"] == "client" and params["op"] in doc["description"] \
                and doc.get("attempt") is not None:
            tid = doc["trace_id"]
            sends[tid] = max(sends.get(tid, 0), int(doc["attempt"]))
    if not sends:
        return None
    readings.log(f"sends per op: {len(sends)} ops, most "
                 f"{max(sends.values())}, resent "
                 f"{sum(1 for n in sends.values() if n > 1)}")
    return sum(sends.values()) / len(sends)
