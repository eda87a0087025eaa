"""Mean self time, in milliseconds, of named spans per root op.

A root op is one trace id among the docs of one kind whose description
matches: a client op (`<client>:<tid>`; a resent op's docs share it), a
PG scrub (`scrub:<osd>:<pgid>:<n>`).  The docs of the listed kinds that
carry a root's trace id are that op's: its sub-ops on other daemons
(writes and `sub_read`s), a scrub's scans on its peers.

Parameters:
  root_kind   kind of the docs that define the root ops
  root_match  substring of their description that selects them
  kinds       kinds of the docs whose spans are added (the root's too,
              if listed)
  match       optional: substring of the description of the docs added
              (`sub_read(` keeps a read's sub-reads from anything else
              under its trace id)
  spans       span names whose self times are added

Self time as `span_self_time` has it: a span's duration minus what
spans nested inside it cover.  Where no doc of the window has a span of
these names (a program from before the span existed) there is nothing
to read.
"""

from __future__ import annotations

from benchmark.readers.span_self_time import self_times


def roots(docs: list[dict], params: dict) -> set:
    """Trace ids of the root ops the parameters select."""
    return {d["trace_id"] for d in docs
            if d["kind"] == params["root_kind"] and d["trace_id"]
            and params["root_match"] in d["description"]}


def members(docs: list[dict], params: dict, ids: set):
    """The docs whose spans belong to the selected root ops."""
    kinds = set(params["kinds"])
    match = params.get("match", "")
    for d in docs:
        if d["kind"] in kinds and d["trace_id"] in ids \
                and match in d["description"]:
            yield d


def read(readings, params) -> float | None:
    ids = roots(readings.op_docs, params)
    if not ids:
        return None
    names = set(params["spans"])
    total, found = 0.0, 0
    for doc in members(readings.op_docs, params, ids):
        for name, t in self_times(doc["spans"]):
            if name in names:
                total += t
                found += 1
    if not found:
        readings.log(f"op span time: {len(ids)} root ops, none of "
                     f"{sorted(names)} on their docs")
        return None
    return 1000.0 * total / len(ids)
