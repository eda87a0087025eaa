"""Mean CPU time, in milliseconds, that named spans burned per root op.

A span closed by the thread that opened it carries `cpu`: the seconds
of that thread's CPU clock spent inside it (`time.thread_time()`), and
`msgr.recv` / `msgr.dispatch` carry the messenger loop thread's.  Wall
time many times `cpu` means the thread waited: for the interpreter, a
lock, an fsync.

Parameters: `root_kind`, `root_match`, `kinds`, `match` as `op_span_time`
has them, and
  spans   names of the spans whose `cpu` is added.  Of the named spans
          of one doc that carry `cpu`, only the outermost count: one
          nested inside another named span is already in that one's
          `cpu`.

Where none of the selected docs has a named span with `cpu` (a program
from before spans carried it) there is nothing to read.
"""

from __future__ import annotations

from benchmark.readers import op_span_time as ost


def outermost_cpu(spans: list[dict], names: set) -> tuple[float, int]:
    """(cpu seconds, spans counted) over the named spans of one doc
    that carry `cpu` and lie inside no other such span."""
    mine = [s for s in spans if s["name"] in names and "cpu" in s]
    total, count = 0.0, 0
    for i, s in enumerate(mine):
        nested = any(
            j != i and o["t0"] <= s["t0"] and s["t1"] <= o["t1"]
            and (o["t1"] - o["t0"] > s["t1"] - s["t0"] or j < i)
            for j, o in enumerate(mine))
        if not nested:
            total += s["cpu"]
            count += 1
    return total, count


def read(readings, params) -> float | None:
    ids = ost.roots(readings.op_docs, params)
    if not ids:
        return None
    names = set(params["spans"])
    total, found = 0.0, 0
    for doc in ost.members(readings.op_docs, params, ids):
        cpu, count = outermost_cpu(doc["spans"], names)
        total += cpu
        found += count
    if not found:
        readings.log(f"span cpu time: {len(ids)} root ops, no span of "
                     f"{sorted(names)} carries cpu")
        return None
    return 1000.0 * total / len(ids)
