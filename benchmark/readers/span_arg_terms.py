"""A ratio of two sums of span args, each sum taken over terms that
name their own docs: what `span_arg_ratio` does for one span of one
kind of root op, for counts that lie on docs of different kinds (a
tier's promotes and flushes over its clients' writes).

Parameters:
  numerator, denominator   lists of terms, their sums divided
  scale                    multiplies the ratio (100 for a share in %)

A term:
  kind    kind of the docs it reads (`client`, `tier_promote`, ...)
  match   optional: substring of their description (`'write'`)
  span    name of the spans that carry the arg
  arg     the arg; every such span of the window's docs adds its value
  equals  optional: add 1 for every span whose arg has this value
          instead (`hit` 0: the misses)
  per_op  optional, true: the span is one an OP, and an op is one trace
          id (`<client>:<tid>`): a client that resends an op it thinks
          silent leaves several docs of it, each with the span, and
          only the earliest of them counts (what the op met when it
          first came)

Where no span of the window carries a denominator's arg (a program
from before the spans existed) or the denominator is 0, there is
nothing to read.
"""

from __future__ import annotations


def term_sum(docs: list[dict], term: dict) -> tuple[float, int]:
    """(sum, spans found) of one term."""
    match = term.get("match", "")
    spans: list[tuple] = []            # (trace id, t0, the arg's value)
    for doc in docs:
        if doc["kind"] != term["kind"] or match not in doc["description"]:
            continue
        for span in doc["spans"]:
            args = span.get("args") or {}
            if span["name"] == term["span"] and term["arg"] in args:
                spans.append((doc.get("trace_id"), span.get("t0", 0.0),
                              args[term["arg"]]))
    if term.get("per_op"):
        first: dict = {}
        for tid, t0, value in sorted(spans, key=lambda s: s[1]):
            first.setdefault(tid, value)
        values = list(first.values())
    else:
        values = [value for _tid, _t0, value in spans]
    if "equals" in term:
        total = float(sum(1 for v in values if v == term["equals"]))
    else:
        total = float(sum(values))
    return total, len(values)


def read(readings, params) -> float | None:
    sums = {}
    for side in ("numerator", "denominator"):
        parts = [term_sum(readings.op_docs, t) for t in params[side]]
        sums[side] = (sum(v for v, _n in parts), sum(n for _v, n in parts))
    (num, n_num), (den, n_den) = sums["numerator"], sums["denominator"]
    if n_den == 0 or den <= 0:
        readings.log(f"span arg terms: {n_den} denominator spans, sum {den}")
        return None
    readings.log(f"span arg terms: {num} over {den} ({n_num} and {n_den} "
                 f"spans)")
    return float(params.get("scale", 1.0)) * num / den
