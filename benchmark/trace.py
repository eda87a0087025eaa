"""Reduction of a JAX profiler trace (`.xplane.pb`) to numbers.

Only what the profiler wrote is read: planes, their lines, and events
with a start and a duration in nanoseconds (`jax.profiler.ProfileData`).

  * device planes are named `/device:TPU:<n>`; their `XLA Ops` line
    holds one event per operation the chip ran, `XLA Modules` one per
    executed program;
  * busy time is the union of the op intervals of one chip, averaged
    over the chips; the idle share the driver derives is 1 - busy/window;
  * `device_ops` are the operations that took most time, under the
    names the trace shows; `idle_gaps` the longest intervals in which
    no operation ran on chip 0, each named after the operation that
    ended it (no host span is on the profiler's clock yet).

On the CPU platform (the self-check's rehearsal only; the command
itself refuses a CPU) there is no device plane: the XLA CPU client's
own thread lines stand in, so that the same code path is rehearsed.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def device_lines(profile, platform: str = "tpu") -> dict:
    """{chip: {line name: [(name, start_ns, duration_ns), ...]}}"""
    out: dict = {}
    for plane in profile.planes:
        if platform == "tpu":
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if not m:
                continue
            chip = out.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                chip[line.name] = [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events]
        elif plane.name == "/host:CPU":
            chip = out.setdefault(0, {OPS_LINE: [], MODULES_LINE: []})
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events if e.duration_ns > 0]
                    chip[OPS_LINE] += evs
                    chip[MODULES_LINE] += evs
    return out


def union_ns(events) -> float:
    """Total length of the union of (name, start, duration) intervals."""
    total, end = 0.0, float("-inf")
    for _n, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_seconds(lines: dict) -> float:
    """Mean over the chips of the union of their op intervals."""
    if not lines:
        return 0.0
    return sum(union_ns(chip.get(OPS_LINE, []))
               for chip in lines.values()) / len(lines) / 1e9


def time_by_pattern(lines: dict, line: str, pattern: str) -> tuple:
    """(seconds, events) of the events on `line` whose name matches
    `pattern`, summed over the chips."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for chip in lines.values():
        for name, _start, dur in chip.get(line, []):
            if rx.search(name):
                total += dur
                count += 1
    return total / 1e9, count


def short_name(name: str) -> str:
    """An op's name as the trace shows it, cut to its head: the TPU
    names an op by its whole HLO line, `%name = type op(operands)`."""
    head, sep, rest = name.partition(" = ")
    if not sep or not head.startswith("%"):
        return name[:120]
    if rest.startswith("("):            # a tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    op = re.match(r"[\w\-]+", rest)
    return f"{head} {op.group(0)} {shape}" if op else name[:120]


def top_ops(lines: dict, n: int = 10) -> list:
    acc: dict = {}
    for chip in lines.values():
        for name, _start, dur in chip.get(OPS_LINE, []):
            name = short_name(name)
            acc[name] = acc.get(name, 0.0) + dur
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(lines: dict, n: int = 10) -> list:
    """The longest gaps between consecutive ops of the first chip."""
    if not lines:
        return []
    events = sorted(lines[min(lines)].get(OPS_LINE, []),
                    key=lambda e: e[1])
    gaps, end = [], None
    for name, start, dur in events:
        if end is not None and start > end:
            gaps.append((start - end, short_name(name)))
        end = max(end or 0.0, start + dur)
    gaps.sort(key=lambda g: -g[0])
    return [[f"before {name}", ns / 1e9] for ns, name in gaps[:n]]


def reduce(path: str, window_s: float, platform: str = "tpu") -> dict:
    lines = device_lines(load(path), platform)
    return {"lines": lines, "busy_s": busy_seconds(lines),
            "window_s": window_s,
            "breakdown": {"device_ops": top_ops(lines),
                          "idle_gaps": idle_gaps(lines)}}
