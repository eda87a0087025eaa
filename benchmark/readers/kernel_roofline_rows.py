"""The fused encode+CRC kernel's share of its roofline, in %, over the
traced slice, for a code whose parity rows are NOT the profile's `m`:
`kernel_roofline.py` with the rows taken from the configuration.

`rooflines.encode_crc` takes the parity rows from `pool_profile.m`.
An lrc pool's profile says m=2 and its composed generator has n - k =
4 rows (two global parities, two local ones), so that formula would
reckon half this code's work.  Here n is the configuration's `shards`
(the files an object has) and the rows are n - k; the formulae are
`rooflines.encode_crc`'s: per padded stripe the kernel reads k*L data
bytes and writes (n-k)*L parity bytes and 4*n CRC bytes, and the
parity is a GF(2) product of the (8(n-k) x 8k) bit matrix with (8k x
L) bits, 2*8k*8(n-k)*L int8 operations.  A configuration without
`shards` has k + m files, and the two readers agree.

Parameters:
  line      the trace line whose events are the kernel's runs
  pattern   regular expression on those events' names: the fused
            program by its OWN name (`jit_run_encode_crc`, the Pallas
            program; `jit_run_xla_encode_crc` where Pallas does not
            run), not the bare `jit_run` every program matches

No trace, no matching event or no transfer means nothing to read.
"""

from __future__ import annotations

from benchmark import rooflines, trace


def work(d: dict, config: dict) -> tuple[float, float]:
    """(operations, bytes) of the slice's fused encode+CRC work."""
    prof = config["pool_profile"]
    k, L = int(prof["k"]), int(config["stripe_unit"])
    n = int(config.get("shards", k + int(prof["m"])))
    stripes = d["bytes_h2d"] / (k * L)
    nbytes = stripes * (k * L + (n - k) * L + 4 * n)
    ops = stripes * 2 * (8 * k) * (8 * (n - k)) * L
    return ops, nbytes


def read(readings, params) -> float | None:
    if readings.trace is None:
        return None
    seconds, events = trace.time_by_pattern(
        readings.trace["lines"], params["line"], params["pattern"])
    d = readings.slice_delta
    if not events or seconds <= 0 or d.get("bytes_h2d", 0) <= 0:
        return None
    ops, nbytes = work(d, readings.config)
    least, bound = rooflines.least_seconds(ops, nbytes, readings.peaks)
    readings.log(f"roofline encode_crc by rows: {events} events "
                 f"{seconds:.6f}s device, least {least:.6f}s ({bound}-bound)"
                 f", ops {ops:.3e}, bytes {nbytes:.3e}")
    return 100.0 * least / seconds
