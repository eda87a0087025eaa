"""Mean self time, in milliseconds, of named spans per client op.

An op is one trace id (`<client>:<tid>`): a client that resends an op
it thinks silent leaves several docs of one op on the primary, and
their spans are all that op's.

Parameters:
  spans   span names whose self times are added
  op      substring of the client op's description that selects the ops
          (`write_full`, `read`)
  subops  true: the spans of the op's sub-ops on other daemons (same
          trace id) are added to the op's own

A span's self time is its duration minus the part of it that spans
nested inside it cover.  The docs are `dump_historic_ops` docs of the
ops that started inside the window.
"""

from __future__ import annotations


def self_times(spans: list[dict]) -> list[tuple[str, float]]:
    """(name, self seconds) of every span of one op doc."""
    out = []
    for i, s in enumerate(spans):
        inside = sorted(
            (max(c["t0"], s["t0"]), min(c["t1"], s["t1"]))
            for j, c in enumerate(spans)
            if j != i and c["t0"] >= s["t0"] and c["t1"] <= s["t1"]
            and (c["t1"] - c["t0"] < s["t1"] - s["t0"] or j > i))
        covered, end = 0.0, s["t0"]
        for a, b in inside:
            if b > end:
                covered += b - max(a, end)
                end = b
        out.append((s["name"], (s["t1"] - s["t0"]) - covered))
    return out


def read(readings, params) -> float | None:
    names = set(params["spans"])
    kinds = ("client", "subop") if params.get("subops") else ("client",)
    ops = {doc["trace_id"] for doc in readings.op_docs
           if doc["kind"] == "client" and params["op"] in doc["description"]}
    if not ops:
        return None
    total = 0.0
    for doc in readings.op_docs:
        if doc["kind"] in kinds and doc["trace_id"] in ops:
            total += sum(t for n, t in self_times(doc["spans"])
                         if n in names)
    return 1000.0 * total / len(ops)
