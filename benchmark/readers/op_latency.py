"""A percentile, in milliseconds, of the latency of the client ops that
completed in the window, submit to completion, on the host's clock.

Parameters:
  percentile  which (95 for a p95)
  op          optional: only ops of this kind (`read`, `write`)

A failed op counts with the time it took to fail: a tail that left out
the ops that died at their timeout would get better as more of them
died.  The tail is a per-layer metric, not an end-to-end one: in a
closed loop behind one GIL it swings from run to run by more than an
end-to-end bound may allow (PERF.md, section 2).
"""

from __future__ import annotations


def percentile(values: list[float], pct: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, -(-len(s) * pct // 100) - 1)]


def read(readings, params) -> float | None:
    kind = params.get("op")
    lat = [1000.0 * (t1 - t0) for k, t0, t1, _ok, _b in readings.window_ops
           if kind is None or k == kind]
    if not lat:
        return None
    readings.log(f"op latency: p{params['percentile']} of {len(lat)} samples")
    return percentile(lat, params["percentile"])
