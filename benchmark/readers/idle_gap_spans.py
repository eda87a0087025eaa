"""What the host did in the chip's longest idle gaps, and the share of
those gaps that the program's own spans name, in %.

The device trace counts nanoseconds since the profiler's session
began; the program's spans live on `time.monotonic()`.  The harness
hands a reader neither the session's start nor the host plane, so the
offset between the two clocks is found from the data.  Every device
program of the pipeline is one dispatch, and the op docs hold each
dispatch as spans: `ec.device_compute` from the moment the dispatch
thread issued the program to the moment the collector began to fetch
its result, `ec.d2h` from there to the fetched result.  The program
ran somewhere between issue and fetched result, so the offset is the
one under which the most `XLA Modules` events of chip 0 that match the
pattern fall inside such an interval.  Each (event, interval) pair
allows a range of offsets; a sweep over the ranges' ends finds the
range most events agree on, and the offset is taken near its lower end
(`find_offset` says why).

With the offset, for each of chip 0's longest gaps between `XLA Ops`
(as `benchmark.trace.idle_gaps` lists them) the reader logs: the op
whose dispatch ended the gap, the spans of that op that covered the
gap with seconds each, the part of the gap before that op existed, and
the three span names with most op-seconds over the gap across all docs.

The number: of the idle seconds in those gaps, the share that lies in
gaps whose ending dispatch was matched to a tracked op and is covered
by that op's spans or precedes its arrival (its first stamp:
`msgr.recv`, else `mstart`).

Parameters:
  line        the trace line whose events are the programs' runs
  pattern     regular expression on those events' names
  gaps        how many of the longest gaps (10)
  min_inside  least share of the matching events that must fall inside
              a dispatch interval under the offset (0.8); under it
              there is nothing to read, and the log says why

No trace, no matching event or no dispatch span (a cell that ran
nothing on the device under a tracked op) means nothing to read.
"""

from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
# how soon after its issue an idle chip starts a program, at least
LAUNCH_S = 1e-4


def dispatch_intervals(docs: list[dict]) -> dict:
    """{(issue, fetched): [docs]}: every dispatch the docs hold, from
    the start of an `ec.device_compute` span to the end of the `ec.d2h`
    span that begins where it ends (to its own end where there is
    none).  Ops coalesced into one dispatch share an interval."""
    out: dict = {}
    for doc in docs:
        fetched = {s["t0"]: s["t1"] for s in doc["spans"]
                   if s["name"] == "ec.d2h"}
        for s in doc["spans"]:
            if s["name"] == "ec.device_compute":
                key = (s["t0"], fetched.get(s["t1"], s["t1"]))
                out.setdefault(key, []).append(doc)
    return out


def find_offset(modules: list, intervals: list) -> tuple:
    """(offset seconds, events inside, runner-up) such that event time
    in seconds + offset is on the spans' clock; runner-up is (events
    inside, seconds from the offset) of the best other range.  `modules` are (start
    seconds, duration seconds); `intervals` (t0, t1).  The offset is
    taken near the lower end of the range on which the most events
    lie wholly inside an interval: a program cannot start before it
    was issued, and on an idle chip it starts within a fraction of a
    millisecond, while the fetch after it may take milliseconds, so
    the lower end is the tight one (a tenth of a millisecond in, or
    the middle of a narrower range).  Of several ranges with the same
    count the lowest is taken, for the same reason.  The other ranges
    are those at least a millisecond from the chosen one."""
    ends = []
    for i, (m, d) in enumerate(modules):
        for a, b in intervals:
            lo, hi = a - m, b - (m + d)
            if hi >= lo:
                ends.append((lo, 0, i))
                ends.append((hi, 1, i))
    if not ends:
        return None, 0, (0, 0.0)
    ends.sort()
    held: dict = {}
    best, best_lo, best_hi = 0, None, None
    peaks = []                       # (count, lo) of every local best
    for x, closing, i in ends:
        if not closing:
            held[i] = held.get(i, 0) + 1
            if len(held) > best:
                best, best_lo, best_hi = len(held), x, None
            peaks.append((len(held), x))
        else:
            if len(held) == best and best_hi is None:
                best_hi = x
            held[i] -= 1
            if not held[i]:
                del held[i]
    offset = best_lo + min(LAUNCH_S, (best_hi - best_lo) / 2.0)
    runner_up = max(((c, -abs(x - offset)) for c, x in peaks
                     if not best_lo - 1e-3 <= x <= best_hi + 1e-3),
                    default=(0, 0.0))
    return offset, best, (runner_up[0], -runner_up[1])


def longest_gaps(ops: list, n: int) -> list:
    """(gap start ns, gap end ns, name of the op that ended it) of the
    n longest gaps between consecutive ops."""
    gaps, end = [], None
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((end, start, name))
        end = max(end or 0.0, start + dur)
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:n]


def overlap(t0: float, t1: float, g0: float, g1: float) -> float:
    return max(0.0, min(t1, g1) - max(t0, g0))


def union_in(spans: list, g0: float, g1: float) -> float:
    """Seconds of [g0, g1] that the spans cover."""
    total, end = 0.0, g0
    for a, b in sorted((max(s["t0"], g0), min(s["t1"], g1))
                       for s in spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def first_stamp(doc: dict) -> float:
    return min([doc["mstart"]] + [s["t0"] for s in doc["spans"]])


def read(readings, params) -> float | None:
    log = readings.log
    if readings.trace is None or not readings.trace["lines"]:
        return None
    lines = readings.trace["lines"]
    chip = lines[min(lines)]
    rx = re.compile(params["pattern"])
    modules = sorted((start / 1e9, dur / 1e9)
                     for name, start, dur in chip.get(params["line"], [])
                     if rx.search(name))
    by_interval = dispatch_intervals(readings.op_docs)
    if not modules or not by_interval:
        log(f"idle gaps: {len(modules)} programs match "
            f"{params['pattern']!r}, {len(by_interval)} dispatch spans in "
            "the docs: no offset to find")
        return None
    intervals = sorted(by_interval)
    offset, inside, runner_up = find_offset(modules, intervals)
    share = inside / len(modules)
    computes = {(s["t0"], s["t1"]) for doc in readings.op_docs
                for s in doc["spans"] if s["name"] == "ec.device_compute"}
    only_compute = sum(
        1 for m, d in modules if offset is not None and any(
            a <= m + offset and m + d + offset <= b for a, b in computes))
    log(f"idle gaps: clock offset {offset!r} s puts {inside} of "
        f"{len(modules)} programs ({100.0 * share:.1f}%) inside a dispatch "
        f"span (issue to fetched result; {only_compute} inside "
        f"ec.device_compute alone); the best other offset, "
        f"{1000.0 * runner_up[1]:.1f} ms away, puts {runner_up[0]} inside; "
        f"{len(intervals)} dispatch spans")
    if share < float(params.get("min_inside", 0.8)):
        log("idle gaps: under the least share: the spans' clock cannot "
            "be placed on the trace's, nothing to read")
        return None

    def owner(end_ns: float):
        """The docs of the dispatch whose program contains, or is the
        first after, the op that ended a gap."""
        e = end_ns / 1e9
        for m, d in modules:
            if m + d >= e:
                hits = [iv for iv in intervals if iv[0] <= m + offset
                        and m + d + offset <= iv[1]]
                if hits:
                    return by_interval[min(hits,
                                           key=lambda iv: iv[1] - iv[0])]
                return []
        return []

    idle = named = 0.0
    gaps = longest_gaps(chip.get(OPS_LINE, []),
                        int(params.get("gaps", 10)))
    for n, (g0_ns, g1_ns, ender) in enumerate(gaps, 1):
        g0, g1 = g0_ns / 1e9 + offset, g1_ns / 1e9 + offset
        idle += g1 - g0
        by_name: dict = {}
        for doc in readings.op_docs:
            for s in doc["spans"]:
                sec = overlap(s["t0"], s["t1"], g0, g1)
                if sec > 0:
                    by_name[s["name"]] = by_name.get(s["name"], 0.0) + sec
        top = ", ".join(f"{k} {v:.3f}" for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:3])
        head = f"idle gap {n}: {g1 - g0:.6f} s before {ender[:60]!r}"
        docs = owner(g1_ns)
        if not docs:
            log(f"{head}; ended by no tracked op's dispatch; most "
                f"op-seconds in it: {top or 'none'}")
            continue
        doc = docs[0]
        born = first_stamp(doc)
        before = overlap(g0, min(g1, born), g0, g1)
        covered = union_in(doc["spans"], max(g0, min(born, g1)), g1)
        named += min(g1 - g0, before + covered)
        mine: dict = {}
        for s in doc["spans"]:
            sec = overlap(s["t0"], s["t1"], g0, g1)
            if sec > 0:
                mine[s["name"]] = mine.get(s["name"], 0.0) + sec
        spans = ", ".join(f"{k} {v:.3f}" for k, v in sorted(
            mine.items(), key=lambda kv: -kv[1])[:5])
        log(f"{head}; ended by the dispatch of {doc['trace_id']!r} "
            f"({doc['kind']} {doc['description'][:50]!r} on "
            f"{doc.get('daemon', '?')}"
            f"{', +%d coalesced' % (len(docs) - 1) if len(docs) > 1 else ''}"
            f"); its spans over the gap: {spans or 'none'}; before it "
            f"existed {before:.3f} s; most op-seconds in the gap: "
            f"{top or 'none'}")
    if idle <= 0:
        return None
    return 100.0 * named / idle
