"""Static copy audit: flag byte-materialization patterns in the
zero-copy hot path.

The data-path layers (msg/, client/, osd/backend_ec.py + ecutil.py,
erasure/, store/) promise payload bytes are materialized only at the
audited runtime sites (utils/copyaudit.py).  This pass greps the code
— comments and string literals blanked via tokenize, so prose never
trips it — for the three patterns that re-introduce host copies:

    bytes(...)      flattening a view/rope into a fresh bytes object
    .tobytes()      materializing a numpy array
    b"".join(...)   gathering segments into one buffer

against a per-file budget (the audited, deliberate uses that remain:
metadata encoding, read-side gathers, the WAL flatten).  A new copy in
a hot-path file either fits the budget or fails tier-1 CI
(tests/test_copy_audit.py) until the budget is consciously raised.

Run standalone:  python -m ceph_tpu.tools.copy_audit [--repo PATH]
"""

from __future__ import annotations

import io
import os
import re
import tokenize

PATTERNS = {
    "bytes()": re.compile(r"(?<![\w.])bytes\("),
    ".tobytes()": re.compile(r"\.tobytes\("),
    "b''.join()": re.compile(r"b(?:''|\"\")\s*\.join\("),
}

# hot-path files and their copy budgets: {pattern: allowed count}.
# Budgets are the CURRENT deliberate uses — every one is either
# metadata-sized (xattr/omap/wire-control values), a read-side gather
# the issue leaves in place, or the designed WAL flatten.  Raising a
# budget is a reviewed decision, not a side effect.
ALLOWLIST: dict[str, dict[str, int]] = {
    # message.py: the u64 segment-length table join (control bytes,
    # not payload) + encode()'s explicit legacy joiner for tests/tools
    "ceph_tpu/msg/message.py": {"bytes()": 1, "b''.join()": 2},
    # messenger.py: a field SHORTER than the accepted side's receive
    # buffer (headers, bodies, segments under RECV_BUF = 1 MiB) is cut
    # out of that buffer once; a longer one is received into its own
    # buffer and never copied (PR 33)
    "ceph_tpu/msg/messenger.py": {"bytes()": 1},
    "ceph_tpu/msg/__init__.py": {},
    "ceph_tpu/client/rados.py": {"bytes()": 4},
    # striper read reassembly is now a zero-copy rope (PR 9 closed the
    # read-side gap): ANY new copy pattern here fails the audit
    "ceph_tpu/client/striper.py": {},
    "ceph_tpu/client/objecter.py": {},
    "ceph_tpu/osd/backend_ec.py": {"b''.join()": 1},
    "ceph_tpu/osd/ecutil.py": {},
    # device-path files: hbm_cache's one .tobytes() is the
    # shard_bytes D2H fetch (a read serve, not a staging copy);
    # ec_kernels' are the jit-cache matrix keys (metadata-sized
    # generator bits, never payload).
    "ceph_tpu/ops/pipeline.py": {},
    "ceph_tpu/ops/hbm_cache.py": {".tobytes()": 1},
    "ceph_tpu/ops/ec_kernels.py": {".tobytes()": 3},
    # decode_concat / decode_object return chunk-view ropes; the only
    # read-side materialization left is the audited rebuilt-chunk copy
    # (ec.decode_rebuild) on degraded reads
    "ceph_tpu/erasure/interface.py": {},
    "ceph_tpu/erasure/plugin_tpu.py": {},
    "ceph_tpu/erasure/matrix_codec.py": {".tobytes()": 2},
    "ceph_tpu/erasure/plugin_jerasure.py": {},
    "ceph_tpu/erasure/plugin_isa.py": {},
    "ceph_tpu/erasure/plugin_shec.py": {},
    "ceph_tpu/erasure/plugin_lrc.py": {},
    "ceph_tpu/erasure/registry.py": {},
    "ceph_tpu/store/objectstore.py": {"bytes()": 2},
    "ceph_tpu/store/memstore.py": {"bytes()": 2},
    "ceph_tpu/store/filestore.py": {"bytes()": 1},
    "ceph_tpu/store/kstore.py": {"bytes()": 2},
    # blockstore: a read that is not the whole blocks of one run is put
    # together in a bytearray (bytes(out)), and the path-less test
    # device copies what it is asked for; a whole-run read returns the
    # device read's own buffer.  The .tobytes() and one join pack
    # METADATA (a run's checksums, four bytes a 4 KiB block; the run
    # heads of an onode); the other join glues a clone's read of a run
    # this same transaction wrote part of.
    "ceph_tpu/store/blockstore.py": {"bytes()": 2, ".tobytes()": 4,
                                     "b''.join()": 2},
    "ceph_tpu/store/__init__.py": {},
}


def _code_lines(src: str, blank_strings: bool = True) -> list[str]:
    """Source lines with comments (and optionally string literals)
    blanked, so prose never trips the pattern scan."""
    lines = src.splitlines()
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return lines
    kinds = (tokenize.COMMENT, tokenize.STRING) if blank_strings \
        else (tokenize.COMMENT,)
    for tok in toks:
        if tok.type not in kinds:
            continue
        (srow, scol), (erow, ecol) = tok.start, tok.end
        for row in range(srow - 1, erow):
            line = lines[row]
            a = scol if row == srow - 1 else 0
            b = ecol if row == erow - 1 else len(line)
            lines[row] = line[:a] + " " * (b - a) + line[b:]
    return lines


def scan_source(src: str) -> dict[str, list[int]]:
    """pattern -> 1-based line numbers of each hit in `src`."""
    hits: dict[str, list[int]] = {}
    # bytes()/tobytes() scan fully-blanked code; the b"".join pattern
    # IS a string literal, so it scans comment-blanked lines instead
    blanked = _code_lines(src)
    with_strings = _code_lines(src, blank_strings=False)
    for name, pat in PATTERNS.items():
        lines = with_strings if "join" in name else blanked
        for lineno, line in enumerate(lines, start=1):
            for _ in pat.finditer(line):
                hits.setdefault(name, []).append(lineno)
    return hits


def audit(repo: str | None = None) -> list[str]:
    """Violations ([] = clean): hot-path files whose copy-pattern
    count exceeds the allowlisted budget, or allowlisted files that
    vanished (a rename silently escaping the audit)."""
    if repo is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    out: list[str] = []
    for rel, budget in sorted(ALLOWLIST.items()):
        path = os.path.join(repo, rel)
        if not os.path.exists(path):
            out.append(f"{rel}: allowlisted file missing "
                       f"(renamed out of the audit?)")
            continue
        with open(path, encoding="utf-8") as f:
            hits = scan_source(f.read())
        for name in PATTERNS:
            got = hits.get(name, [])
            allowed = budget.get(name, 0)
            if len(got) > allowed:
                out.append(
                    f"{rel}: {len(got)} x {name} at lines {got} "
                    f"(budget {allowed}) — a new host copy in the "
                    f"zero-copy path; use views/BufferList or raise "
                    f"the budget deliberately")
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=None,
                    help="repo root (default: derived from this file)")
    args = ap.parse_args(argv)
    violations = audit(args.repo)
    for v in violations:
        print(v)
    if not violations:
        print("copy audit clean: hot-path copy patterns within budget")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
