"""LRC (locally repairable code, the reference's k/m/l form, w=8):
shard files by POSITION and their CRC32C, layer by layer and NOT
composed, from `benchmark/oracle.py` (numpy GF(2^8)), importing nothing
of the program.  It refuses a configuration of another technique
rather than compare it with the wrong code.

The layout (Ceph `src/erasure-code/lrc/ErasureCodeLrc.cc` `parse_kml`
and `layers_init`, `doc/rados/operations/erasure-code-lrc.rst`; that
checkout is not on this machine, so it is written from their published
description):

  * k + m is `groups` = (k+m)/l local groups; the mapping string is
    `groups` times k/groups 'D', m/groups '_' and one more '_': for
    k=4 m=2 l=3, `DD__DD__`.  Data chunk i lies at the i-th 'D'.
  * the global layer marks the same 'D's and the m/groups positions
    after each run of them 'c' (`DDc_DDc_`): jerasure `reed_sol_van`
    k m over the data chunks, its parities at the 'c's in order.
  * local layer g marks positions g*(l+1) .. g*(l+1)+l-1 'D' and the
    next one 'c' (`DDDc____`, `____DDDc`): `reed_sol_van` l 1 over
    what lies at those l positions, global parity included.

Only what the benchmark compares is here: the eight shard files in
position order and their CRCs.  (Which chunk sets decode is held by
the CPU tests, `tests/test_lrc_tpu.py`, against a layered decoder.)
"""

from __future__ import annotations

import numpy as np

from benchmark import oracle


def _profile(config: dict) -> tuple[int, int, int]:
    prof = config["pool_profile"]
    if prof.get("technique") != "lrc":
        raise ValueError(f"the lrc reference cannot stand for technique "
                         f"{prof.get('technique')!r}")
    k, m, l = int(prof["k"]), int(prof["m"]), int(prof["l"])
    if (k + m) % l or k % ((k + m) // l) or m % ((k + m) // l):
        raise ValueError(f"lrc needs k + m a multiple of l, and k and m "
                         f"multiples of (k+m)/l; got {k} {m} {l}")
    return k, m, l


def layout(k: int, m: int, l: int) -> tuple[str, str, list[str]]:
    """(mapping, the global layer, the local layers), as strings over
    the k + m + (k+m)/l positions."""
    groups = (k + m) // l
    kg, mg = k // groups, m // groups
    mapping = ("D" * kg + "_" * (mg + 1)) * groups
    whole = ("D" * kg + "c" * mg + "_") * groups
    local = ["".join(("D" * l + "c") if g == i else "_" * (l + 1)
                     for i in range(groups)) for g in range(groups)]
    return mapping, whole, local


def shard_files(payload: bytes, k: int, m: int, l: int,
                stripe_unit: int) -> np.ndarray:
    """(positions, shard_size) uint8: every shard file of one object,
    row p the file at position p."""
    mapping, whole, local = layout(k, m, l)
    rs = oracle.shard_files(payload, k, m, stripe_unit)   # data, then parity
    files = np.zeros((len(mapping), rs.shape[1]), dtype=np.uint8)
    files[[p for p, ch in enumerate(whole) if ch == "D"]] = rs[:k]
    files[[p for p, ch in enumerate(whole) if ch == "c"]] = rs[k:]
    (row,) = oracle.reed_sol_van_matrix(l, 1)
    for layer in local:
        (out,) = [p for p, ch in enumerate(layer) if ch == "c"]
        for coeff, p in zip(row, [p for p, ch in enumerate(layer)
                                  if ch == "D"]):
            files[out] ^= oracle._MUL[coeff][files[p]]
    return files


def stored(payload: bytes, config: dict) -> list:
    k, m, l = _profile(config)
    files = shard_files(payload, k, m, l, int(config["stripe_unit"]))
    if len(files) != int(config["shards"]):
        raise ValueError(f"the configuration lists {config['shards']} "
                         f"shards, lrc k={k} m={m} l={l} has {len(files)}")
    crcs = oracle.crc32c(files)
    return [(f.tobytes(), int(c)) for f, c in zip(files, crcs)]
