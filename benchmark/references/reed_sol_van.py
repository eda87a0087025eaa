"""jerasure `reed_sol_van` (w=8) shard files and their CRC32C, from
`benchmark/oracle.py`: numpy GF(2^8), importing nothing of the
program.  It refuses a configuration of another technique rather than
compare it with the wrong matrix."""

from __future__ import annotations

from benchmark import oracle


def stored(payload: bytes, config: dict) -> list:
    prof = config["pool_profile"]
    if prof["technique"] != "reed_sol_van":
        raise ValueError(f"the reed_sol_van reference cannot stand for "
                         f"technique {prof['technique']!r}")
    files = oracle.shard_files(payload, int(prof["k"]), int(prof["m"]),
                               int(config["stripe_unit"]))
    crcs = oracle.crc32c(files)
    return [(f.tobytes(), int(c)) for f, c in zip(files, crcs)]
