"""A kernel's share of its roofline, in %, over the traced slice.

Parameters:
  work      the function of `benchmark/rooflines.py` that turns the
            slice's transfer counters into operations and bytes
  line      the trace line whose events are the kernel's runs
  pattern   regular expression on those events' names.  Every jitted
            function of `ops/pallas_ec.py` and `ops/ec_kernels.py` is
            an inner `def run`, so today every kernel's program is
            called `jit_run`: the pattern cannot tell encode from
            decode from CRC, and the share is that kernel's only in a
            cell whose slice runs one kind (each metric file says so
            under `limits`).  A PR that names the kernels needs a
            `benchmark` PR that points the patterns at the new names.

The least time the chip needs (the larger of operations over the int8
peak and bytes over the HBM peak, both from `benchmark/peaks.json`)
over the summed device time of the matching events.  Both sides cover
the same slice: the counters are snapshotted where the profiler starts
and stops.  No event, or no transfer, means nothing to read.
"""

from __future__ import annotations

from benchmark import rooflines, trace


def read(readings, params) -> float | None:
    if readings.trace is None:
        return None
    seconds, events = trace.time_by_pattern(
        readings.trace["lines"], params["line"], params["pattern"])
    d = readings.slice_delta
    if not events or seconds <= 0 or d["bytes_h2d"] <= 0:
        return None
    ops, nbytes = rooflines.WORK[params["work"]](d, readings.config)
    least, bound = rooflines.least_seconds(ops, nbytes, readings.peaks)
    readings.log(f"roofline {params['work']}: {events} events "
                 f"{seconds:.6f}s device, least {least:.6f}s ({bound}-bound)"
                 f", ops {ops:.3e}, bytes {nbytes:.3e}")
    return 100.0 * least / seconds
