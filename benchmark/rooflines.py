"""The operations and bytes each kernel's work needs, computed from
the window's counters, and the least time a chip could take for them.

The counters are the pipeline's transfer counters over the traced
slice: every stripe that went up was padded to its dispatch's bucket,
and the kernel ran on the padded batch, so `bytes_h2d` and `bytes_d2h`
say exactly what the kernel read and wrote.

  encode_crc  per padded stripe the fused kernel reads k*L data bytes
              and writes m*L parity bytes and 4*(k+m) CRC bytes; parity
              is a GF(2) product of the (8m x 8k) bit matrix with
              (8k x L) bits: 2*8k*8m*L int8 operations.
  decode      the kernel reads k*L survivor bytes a padded stripe and
              writes r*L rebuilt bytes (r = rows rebuilt; what came
              down says how many): 2*8k*8r*L operations.
  crc         the fold reads the shard bytes and writes 4 bytes a row;
              CRC32C is a GF(2) product of a (32 x 8n) matrix with the
              n bytes' bits: 2*32*8 operations a byte.  Only bytes that
              went up are folded on the device: a shard whose object
              the HBM cache still holds is checked on the HOST, by a
              carry-less combine of the per-stripe CRCs kept since its
              encode (`osd/scrubber.py` `_scan_ec_deep`), with no
              dispatch at all, so cache hits are in neither the work
              nor the device time.

Each takes the slice's counter deltas and the cell's configuration (k,
m and the stripe unit L come from its `pool_profile` and
`stripe_unit`).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "benchmark/peaks.json: add it with its source")
    return table["devices"][device_kind]


def _kml(config: dict) -> tuple[int, int, int]:
    prof = config["pool_profile"]
    return int(prof["k"]), int(prof["m"]), int(config["stripe_unit"])


def encode_crc(d: dict, config: dict) -> tuple[float, float]:
    k, m, L = _kml(config)
    stripes = d["bytes_h2d"] / (k * L)
    nbytes = stripes * (k * L + m * L + 4 * (k + m))
    ops = stripes * 2 * (8 * k) * (8 * m) * L
    return ops, nbytes


def decode(d: dict, config: dict) -> tuple[float, float]:
    k, _m, _L = _kml(config)
    nbytes = d["bytes_h2d"] + d["bytes_d2h"]
    ops = 2 * 8 * k * 8 * d["bytes_d2h"]      # 2*8k*8r*L with r*L = down
    return ops, nbytes


def crc(d: dict, config: dict) -> tuple[float, float]:
    nbytes = d["bytes_h2d"] + d["bytes_d2h"]
    ops = 2 * 32 * 8 * d["bytes_h2d"]
    return ops, nbytes


WORK = {"encode_crc": encode_crc, "decode": decode, "crc": crc}


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, which bound) the chip needs at its published peaks."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8") if t_ops > t_mem else (t_mem, "hbm")
