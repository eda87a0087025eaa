"""What the window's rebuilds did, from their op docs.

A rebuilt object is one doc of kind `recovery` whose description starts
`rebuild(` (`osd/recovery_svc.py` `_rebuild_op`): `rebuild` is the
worker thread's part, `rebuild.read` inside it carries `path`
(`cache`, `local`, `full`), `chunks` and `bytes_read`, and each
`rebuild.push` runs from the send to the target's ack and carries
`shard`, `target`, `bytes`.  A backfill round is a doc `backfill_scan(`;
the docs of a backfill session carry trace ids that start `backfill:`.

Parameters:
  what      objects_per_s   rebuild docs a second of the window
            push_mibps      MiB of acknowledged `rebuild.push` bytes a
                            second of the window
            rebuild_ms      mean, over the rebuild docs, from the start
                            of `rebuild` to the end of its last span
                            (the last push acknowledged)
            read_per_rebuilt_byte
                            `bytes_read` of the `rebuild.read` spans
                            over `bytes` of the `rebuild.push` spans
            active_share    share of the window covered by the docs
                            whose trace id starts with one of
                            `prefixes`, a gap shorter than `bridge_s`
                            (between two of them, or between one and
                            an edge of the window) counted as covered:
                            a session between two ops, not an idle one
  prefixes  active_share: trace id prefixes
  bridge_s  active_share: seconds

The window is taken from the docs themselves, first `mstart` to last
over every doc of it (the clients' reads run from edge to edge): the
readings carry no other clock.  A program from before these docs and
spans has nothing to read.
"""

from __future__ import annotations

MIB = float(1 << 20)


def rebuild_docs(docs: list[dict]) -> list[dict]:
    return [d for d in docs if d["kind"] == "recovery"
            and d["description"].startswith("rebuild(")]


def spans_named(docs: list[dict], name: str):
    for d in docs:
        for s in d["spans"]:
            if s["name"] == name:
                yield s


def window_of(docs: list[dict]) -> tuple[float, float]:
    starts = [d["mstart"] for d in docs]
    return min(starts), max(starts)


def covered(intervals: list[tuple], t0: float, t1: float,
            bridge: float) -> float:
    """Seconds of [t0, t1] the intervals cover, gaps under `bridge`
    closed."""
    total, end = 0.0, t0
    for a, b in sorted(intervals):
        a, b = max(a, t0), min(b, t1)
        if b <= end:
            continue
        total += b - (end if a - end < bridge else a)
        end = b
    if t1 - end < bridge:
        total += t1 - end
    return total


def read(readings, params) -> float | None:
    docs = readings.op_docs
    rebuilt = rebuild_docs(docs)
    if not rebuilt:
        return None
    t0, t1 = window_of(docs)
    seconds = t1 - t0
    what = params["what"]
    if what == "objects_per_s":
        return len(rebuilt) / seconds if seconds > 0 else None
    pushes = [s for s in spans_named(rebuilt, "rebuild.push")
              if s.get("args", {}).get("acked", True)]
    pushed = sum(s["args"]["bytes"] for s in pushes)
    if what == "push_mibps":
        return pushed / MIB / seconds if seconds > 0 and pushes else None
    if what == "rebuild_ms":
        took = [max(s["t1"] for s in d["spans"]) - r["t0"]
                for d in rebuilt for r in d["spans"]
                if r["name"] == "rebuild"]
        if not took:
            return None
        own = [s["t1"] - s["t0"] for s in spans_named(rebuilt, "rebuild")]
        readings.log(f"rebuilds: {len(took)} docs, the worker's part "
                     f"{1000.0 * sum(own) / len(own):.1f} ms a rebuild, "
                     f"with the wait for the last ack "
                     f"{1000.0 * sum(took) / len(took):.1f} ms")
        return 1000.0 * sum(took) / len(took)
    if what == "read_per_rebuilt_byte":
        reads = list(spans_named(rebuilt, "rebuild.read"))
        paths: dict = {}
        for s in reads:
            paths[s["args"]["path"]] = paths.get(s["args"]["path"], 0) + 1
        readings.log(f"rebuild reads by path {paths}")
        return (sum(s["args"]["bytes_read"] for s in reads) / pushed
                if pushed else None)
    if what == "active_share":
        mine = [(d["mstart"], d["mstart"] + d["duration"]) for d in docs
                if d["trace_id"].startswith(tuple(params["prefixes"]))]
        if not mine or seconds <= 0:
            return None
        share = covered(mine, t0, t1, float(params["bridge_s"])) / seconds
        readings.log(f"active share: {len(mine)} docs of "
                     f"{params['prefixes']} cover {share:.4f} of "
                     f"{seconds:.1f}s")
        return share
    raise KeyError(f"unknown recovery reading {what!r}")
