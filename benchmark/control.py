"""python -m benchmark.control --workload <name> --seed <n> --seconds <s> --fault <fault>

The control of `correct`: the same cell, through the same harness, with
ONE guarantee of the configuration broken underneath the served path.
The run has to come out with `correct` false; this command exits 0 when
it does and 1 when the broken system passed.  The benchmark's own runs
never run it.

The system runs no model and states no precision, so the control is
not a lower precision but a broken guarantee:

  parity_bitflip  every encode returns its last parity shard with one
                  bit wrong: the arithmetic is inexact by the smallest
                  amount there is.  Clients never read parity, so only
                  the shard-by-shard comparison with the plain
                  reference (and the stored CRC) can see it.
  read_bitflip    every client read returns one bit wrong: an answer
                  altered where it is produced.
"""

from __future__ import annotations

import time

T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402


@contextmanager
def parity_bitflip():
    from ceph_tpu.osd import ecutil
    orig = ecutil.EncodeHandle.result

    def result(self, timeout=None):
        shards, crcs = orig(self, timeout)
        shards[-1][0] ^= 1
        return shards, crcs

    ecutil.EncodeHandle.result = result
    try:
        yield
    finally:
        ecutil.EncodeHandle.result = orig


@contextmanager
def read_bitflip():
    from ceph_tpu.client import rados
    orig = rados.IoCtx.read

    def read(self, oid, length=0, offset=0):
        data = bytearray(orig(self, oid, length, offset))
        if data:
            data[len(data) // 2] ^= 1
        return bytes(data)

    rados.IoCtx.read = read
    try:
        yield
    finally:
        rados.IoCtx.read = orig


FAULTS = {"parity_bitflip": parity_bitflip, "read_bitflip": read_bitflip}


def run_control(workload: str, seed: int, seconds: float, fault: str,
                platform: str = "tpu", overrides: dict | None = None,
                **kw) -> dict | None:
    from benchmark import harness
    with FAULTS[fault]():
        return harness.run_cell(workload, seed, seconds, False, platform,
                                T_IMPORT, overrides, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    args = ap.parse_args(argv)
    result = run_control(args.workload, args.seed, args.seconds, args.fault)
    if result is None:
        return 1
    print(json.dumps({"control": args.fault, "workload": args.workload,
                      "seed": args.seed, "correct": result["correct"],
                      "control_failed_as_it_must":
                          result["correct"] is False}), flush=True)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
