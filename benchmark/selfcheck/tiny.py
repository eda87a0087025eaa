"""The tiny sizes the self-check rehearses every cell at."""

from __future__ import annotations

CONF = {"osd_ec_device_shards": "1", "osd_ec_hbm_cache_bytes": 256 << 10}
PREWRITE = {"k8m3-4m-write": 4, "k2m1-64k-mixed": 48,
            "k8m3-4m-degraded-read": 8, "k8m3-4m-deep-scrub": 8}


def overrides(cell: str, **extra) -> dict:
    return dict({
        "config": {"object_bytes": 16384 if "64k" in cell else 65536,
                   "pg_num": 2},
        "conf": dict(CONF),
        "params": {"ramp_seconds": 0.3,
                   "prewrite_objects": PREWRITE.get(cell, 4)},
        "payload_bases": 4,
        "peaks_as": "TPU v5 lite"}, **extra)
