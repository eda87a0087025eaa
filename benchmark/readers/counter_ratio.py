"""A ratio of counter deltas over the window.

Parameters:
  numerator     counter names, their deltas added
  denominator   counter names, their deltas added
  scale         multiplies the ratio (100 for a share in %)

Counters are the flat snapshot `benchmark.cluster.Deployment.counters`
takes at window open and close.  A denominator that did not move means
there was nothing to read.
"""

from __future__ import annotations


def read(readings, params) -> float | None:
    d = readings.counter_delta
    den = sum(d[name] for name in params["denominator"])
    if den <= 0:
        return None
    num = sum(d[name] for name in params["numerator"])
    return float(params.get("scale", 1.0)) * num / den
