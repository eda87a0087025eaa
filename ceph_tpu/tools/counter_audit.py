"""Static counter-coverage lint: every perf counter the code declares
or increments must be pinned by the observability test schema.

The perf-dump surface is load-bearing (the benchmark's readers, health
flags, the mgr export) — a counter added in a hot path but absent from
tests/test_observability.py ships untested and undocumented: nothing
fails when a refactor silently stops incrementing it.  This pass
(tier-1 via tests/test_counter_audit.py, the copy_audit pattern):

  * scans ``ceph_tpu/`` for PerfCounters declarations
    (``add_u64_counter("x")`` / ``add_time_avg("x")`` / ...) and
    increment sites (``.inc("x")`` / ``.tinc("x")`` / ``.dec("x")``,
    including ternaries like ``.inc("op_w" if w else "op_r")``);
  * scans the EC device plane's plain-dict counters too
    (``_c["x"] += n`` in ops/pipeline.py and ops/hbm_cache.py, the
    warm-up registry's ``_warm["x"] += 1``) — they ride perf dump's
    ``ec_pipeline`` block;
  * requires every discovered name to appear as a quoted string in
    tests/test_observability.py (the schema assertions).

Comments and docstrings are tokenize-blanked before the scan, so
prose mentioning a counter neither hides nor fakes coverage.

Run standalone:  python -m ceph_tpu.tools.counter_audit [--repo PATH]
"""

from __future__ import annotations

import io
import os
import re
import tokenize

# a counter name: how every perf counter in the tree is spelled —
# single-char lower bound so short names ("op") cannot silently
# escape the audit
_NAME = re.compile(r"[\"']([a-z][a-z0-9_]*)[\"']")
# declaration + increment call heads; the name literal(s) follow on
# the same (or the continuation) line
_CALLS = re.compile(
    r"\.(?:inc|tinc|dec|add_u64_counter|add_u64|add_time_avg|"
    r"add_time|add_histogram)\(")

# plain-dict counter increments of the EC device plane
_DICT_INCS = re.compile(
    r"\b_(?:c|warm)\[[\"']([a-z][a-z0-9_]*)[\"']\]\s*\+=")

TEST_FILE = "tests/test_observability.py"


def _blanked(src: str) -> str:
    """Source with comments and string PREFIXES kept but docstrings/
    comments blanked — counter-name string literals must survive, so
    only COMMENT tokens and standalone (expression-statement) strings
    are stripped."""
    lines = src.splitlines()
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return src
    for i, tok in enumerate(toks):
        blank = tok.type == tokenize.COMMENT
        if tok.type == tokenize.STRING:
            # a string starting a logical line is a docstring/bare
            # string — prose, not a counter name argument
            prev = next((t for t in reversed(toks[:i])
                         if t.type not in (tokenize.NL,
                                           tokenize.NEWLINE,
                                           tokenize.INDENT,
                                           tokenize.DEDENT,
                                           tokenize.COMMENT)), None)
            if prev is None or prev.type == tokenize.NEWLINE or \
                    prev.string in (";", ":"):
                blank = True
        if not blank:
            continue
        (srow, scol), (erow, ecol) = tok.start, tok.end
        for row in range(srow - 1, erow):
            line = lines[row]
            a = scol if row == srow - 1 else 0
            b = ecol if row == erow - 1 else len(line)
            lines[row] = line[:a] + " " * (b - a) + line[b:]
    return "\n".join(lines)


def scan_counters(src: str) -> dict[str, list[int]]:
    """name -> 1-based lines where a perf counter is declared or
    incremented in `src`."""
    out: dict[str, list[int]] = {}
    lines = _blanked(src).splitlines()
    for lineno, line in enumerate(lines, start=1):
        for name in _DICT_INCS.findall(line):
            out.setdefault(name, []).append(lineno)
        for m in _CALLS.finditer(line):
            # names live in the call's argument text: the rest of
            # this line plus the next (continuation) line covers
            # every call shape in the tree — and EVERY literal in the
            # call counts (a ternary picks one at runtime)
            tail = line[m.end():]
            # follow into the continuation line only while the call's
            # parens are still open — once the call closed on this
            # line, the NEXT statement's literals are not arguments
            # (e.g. a `yield ("read", n)` protocol step after an inc)
            if tail.count(")") <= tail.count("(") and \
                    lineno < len(lines):
                tail += " " + lines[lineno]
            for name in _NAME.findall(tail):
                out.setdefault(name, []).append(lineno)
    return out


def audit(repo: str | None = None) -> list[str]:
    """Violations ([] = clean): counters declared/incremented in
    ceph_tpu/ that the observability test schema never names."""
    if repo is None:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    test_path = os.path.join(repo, TEST_FILE)
    if not os.path.exists(test_path):
        return [f"{TEST_FILE}: missing (renamed out of the audit?)"]
    with open(test_path, encoding="utf-8") as f:
        test_src = f.read()
    covered = set(_NAME.findall(test_src))
    out: list[str] = []
    pkg = os.path.join(repo, "ceph_tpu")
    for dirpath, _dirs, files in sorted(os.walk(pkg)):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                hits = scan_counters(f.read())
            rel = os.path.relpath(path, repo)
            for name, linenos in sorted(hits.items()):
                if name not in covered:
                    out.append(
                        f"{rel}:{linenos[0]}: perf counter "
                        f"\"{name}\" is not asserted in {TEST_FILE} "
                        f"— add it to the schema test so it cannot "
                        f"ship undocumented/untested")
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
        print("counter audit clean: every perf counter is pinned by "
              "the observability schema tests")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
