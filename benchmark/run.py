"""python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once.  Earlier lines (each starting with `#`)
say what ran; the LAST line of standard output is the one JSON object
with `correct`, `attempted`, `failed`, `metrics`, `device` and, when
traced, `breakdown`.  With `--trace 0` the metrics are the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics.

There is no option that lets this command pass without a TPU: where
JAX finds none, or fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), "tpu", T_IMPORT)
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        return 1
    if result is None:
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # daemons' worker threads must not keep a finished run alive
    os._exit(code)
