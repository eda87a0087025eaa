"""Encode-bytes non-regression corpus.

The analog of the reference's ceph_erasure_code_non_regression
(test/erasure-code/ceph_erasure_code_non_regression.cc:71 --create /
--check against ceph-erasure-code-corpus): every plugin x technique x
config encodes a pinned pseudorandom input and the CRC32C of every
chunk must match the archived corpus.  A kernel or matrix refactor
that silently changes on-disk parity fails here before it can strand
data written by an older build.

Regenerate (only for deliberate, documented format changes):
    python tests/test_corpus.py --create
"""

import json
import os
import sys

import numpy as np
import pytest

CORPUS_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "encode_corpus.json")

CONFIGS = [
    ("jerasure", {"technique": "reed_sol_van", "k": "3", "m": "2"}),
    ("jerasure", {"technique": "reed_sol_r6_op", "k": "4", "m": "2"}),
    ("jerasure", {"technique": "cauchy_orig", "k": "4", "m": "3",
                  "packetsize": "128"}),
    ("jerasure", {"technique": "cauchy_good", "k": "6", "m": "3",
                  "packetsize": "128"}),
    ("jerasure", {"technique": "liberation", "k": "5", "m": "2",
                  "w": "7", "packetsize": "128"}),
    ("jerasure", {"technique": "blaum_roth", "k": "5", "m": "2",
                  "w": "6", "packetsize": "128"}),
    ("jerasure", {"technique": "liber8tion", "k": "6", "m": "2",
                  "packetsize": "128"}),
    ("isa", {"technique": "reed_sol_van", "k": "8", "m": "3"}),
    ("isa", {"technique": "cauchy", "k": "4", "m": "3"}),
    ("tpu", {"technique": "reed_sol_van", "k": "8", "m": "3"}),
    ("tpu", {"technique": "isa_reed_sol_van", "k": "6", "m": "2"}),
    ("tpu", {"technique": "cauchy_good", "k": "6", "m": "3",
             "packetsize": "32"}),
    ("shec", {"k": "5", "m": "3", "c": "2"}),
    ("lrc", {"k": "4", "m": "2", "l": "3"}),
    # the same code as a technique of the tpu plugin: the same bytes
    ("tpu", {"technique": "lrc", "k": "4", "m": "2", "l": "3"}),
]


def _key(plugin: str, profile: dict) -> str:
    items = ",".join(f"{k}={v}" for k, v in sorted(profile.items()))
    return f"{plugin}({items})"


def build_corpus() -> dict:
    from ceph_tpu.erasure.registry import registry
    from ceph_tpu.ops import crc32c as crc_mod

    data = bytes(np.random.default_rng(0xCEF).integers(
        0, 256, 100_000, dtype=np.uint8))
    out = {}
    for plugin, profile in CONFIGS:
        codec = registry.factory(plugin, dict(profile))
        km = codec.get_chunk_count()
        chunks = codec.encode(range(km), data)
        out[_key(plugin, profile)] = {
            "chunk_size": len(chunks[0]),
            "crcs": [crc_mod.crc32c(0, chunks[i]) for i in range(km)],
        }
    return out


@pytest.mark.usefixtures("denc_walk")
def test_encode_corpus_stable():
    """Under the native tier's extension and without it (conftest's
    `denc_walk`: the codec's Python walk, and the GF and CRC entry
    points on their own fallbacks): the same parity bytes."""
    assert os.path.exists(CORPUS_PATH), \
        "corpus missing — run: python tests/test_corpus.py --create"
    with open(CORPUS_PATH) as f:
        archived = json.load(f)
    current = build_corpus()
    assert set(current) == set(archived), (
        sorted(set(current) ^ set(archived)))
    for key in sorted(archived):
        assert current[key] == archived[key], \
            f"encode bytes CHANGED for {key}: archived {archived[key]} " \
            f"vs current {current[key]} — on-disk parity would diverge"


if __name__ == "__main__":
    if "--create" in sys.argv:
        os.makedirs(os.path.dirname(CORPUS_PATH), exist_ok=True)
        with open(CORPUS_PATH, "w") as f:
            json.dump(build_corpus(), f, indent=1, sort_keys=True)
        print(f"wrote {CORPUS_PATH}")
    else:
        test_encode_corpus_stable()
        print("corpus check OK")
