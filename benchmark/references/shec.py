"""SHEC (shingled erasure code, technique `multiple`, w=8): shard
files, their CRC32C, which chunk sets decode and the decode itself,
from `benchmark/oracle.py` (numpy GF(2^8)), importing nothing of the
program.  It refuses a configuration of another technique rather than
compare it with the wrong matrix.

The equations (Ceph `src/erasure-code/shec/ErasureCodeShec.cc`; that
checkout is not on this machine, so they are written from its
published description, and each departure is noted):

  * coding matrix: jerasure's `reed_sol_vandermonde_coding_matrix(k,
    m, 8)` (`oracle.reed_sol_van_matrix`) with entries zeroed by
    shingle windows.  The m parities split into two groups (m1, c1)
    and (m2, c2) = (m - m1, c - c1).  In row rr of a group (mg, cg)
    the columns from ((rr+cg)*k/mg) % k round to (rr*k/mg) % k are
    zero, so the row covers the cg*k/mg columns from (rr*k/mg) % k on.
  * the split is the one with the least recovery efficiency r_e1 among
    those with c1 <= c/2, mg >= cg and a group empty in both numbers
    or in neither, the first such in the order c1 = 0.., m1 = 0..;
    r_e1 = (sum of the rows' widths + for each data column the width
    of the narrowest row covering it) / (k + m).
  * decode is a plan: for the wanted chunks and the available ones,
    the smallest set of available parities whose rows, restricted to
    the data chunks they touch that are not available, have full
    column rank; those unknowns are the system's solution, by
    Gaussian elimination over GF(2^8).  The code is not MDS.

Departures: the reference searches its subsets in another order and
may settle on another of several equally small ones; which sets decode
is the same.  Only what the benchmark compares is here: shard files,
CRCs, `decodable`, and `decode` for the self-check.
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmark import oracle


def _windows(k: int, groups) -> list[tuple[int, int]]:
    """(first covered column, width) of every parity row, group after
    group."""
    return [((rr * k) // mg % k, ((rr + cg) * k) // mg - (rr * k) // mg)
            for mg, cg in groups for rr in range(mg)]


def _r_e1(k: int, groups) -> float:
    narrowest = [10 ** 8] * k       # a column no row covers
    total = 0
    for start, width in _windows(k, groups):
        total += width
        for j in range(width):
            col = (start + j) % k
            narrowest[col] = min(narrowest[col], width)
    return (total + sum(narrowest)) / (k + sum(mg for mg, _c in groups))


def split(k: int, m: int, c: int) -> list[tuple[int, int]]:
    """[(m1, c1), (m2, c2)] of technique `multiple`."""
    best = None
    for c1 in range(c // 2 + 1):
        for m1 in range(m + 1):
            m2, c2 = m - m1, c - c1
            if m1 < c1 or m2 < c2 or (m1 == 0) != (c1 == 0) \
                    or (m2 == 0) != (c2 == 0):
                continue
            r = _r_e1(k, [(m1, c1), (m2, c2)])
            if best is None or r < best[0] - 1e-12:
                best = (r, [(m1, c1), (m2, c2)])
    if best is None:
        raise ValueError(f"no shec split for k={k} m={m} c={c}")
    return best[1]


def coding_matrix(k: int, m: int, c: int) -> np.ndarray:
    """(m, k) uint8: the shingled coding matrix."""
    if not 0 < c <= m <= k:
        raise ValueError(f"shec needs 0 < c <= m <= k, got {k} {m} {c}")
    full = oracle.reed_sol_van_matrix(k, m)
    out = np.zeros_like(full)
    for rr, (start, width) in enumerate(_windows(k, split(k, m, c))):
        cols = [(start + j) % k for j in range(width)]
        out[rr, cols] = full[rr, cols]
    return out


def _profile(config: dict) -> tuple[int, int, int]:
    prof = config["pool_profile"]
    if prof["technique"] != "shec_multiple":
        raise ValueError(f"the shec reference cannot stand for technique "
                         f"{prof['technique']!r}")
    return int(prof["k"]), int(prof["m"]), int(prof["c"])


def shard_files(payload: bytes, k: int, m: int, c: int,
                stripe_unit: int) -> np.ndarray:
    """(k+m, shard_size) uint8: every shard file of one object (ECUtil
    stripe_info_t layout, as `oracle.shard_files`)."""
    width = k * stripe_unit
    stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(stripes, k, stripe_unit).transpose(1, 0, 2) \
        .reshape(k, stripes * stripe_unit)
    matrix = coding_matrix(k, m, c)
    parity = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for r in range(m):
        for col in np.flatnonzero(matrix[r]):
            parity[r] ^= oracle._MUL[matrix[r, col]][data[col]]
    return np.concatenate([data, parity], axis=0)


def stored(payload: bytes, config: dict) -> list:
    k, m, c = _profile(config)
    files = shard_files(payload, k, m, c, int(config["stripe_unit"]))
    crcs = oracle.crc32c(files)
    return [(f.tobytes(), int(crc)) for f, crc in zip(files, crcs)]


def _eliminate(a: np.ndarray, b: np.ndarray | None = None):
    """Gauss-Jordan over GF(2^8) on a copy of `a` (rows x cols), the
    same row operations on `b`: (rank, reduced a, reduced b)."""
    a = a.astype(np.uint8).copy()
    b = None if b is None else b.astype(np.uint8).copy()
    rank = 0
    for col in range(a.shape[1]):
        piv = next((r for r in range(rank, a.shape[0]) if a[r, col]), None)
        if piv is None:
            continue
        if piv != rank:
            a[[rank, piv]] = a[[piv, rank]]
            if b is not None:
                b[[rank, piv]] = b[[piv, rank]]
        inv = oracle.gf_inv(int(a[rank, col]))
        a[rank] = oracle._MUL[inv][a[rank]]
        if b is not None:
            b[rank] = oracle._MUL[inv][b[rank]]
        for r in range(a.shape[0]):
            f = int(a[r, col])
            if r != rank and f:
                a[r] ^= oracle._MUL[f][a[rank]]
                if b is not None:
                    b[r] ^= oracle._MUL[f][b[rank]]
        rank += 1
    return rank, a, b


def plan(want, available, matrix: np.ndarray):
    """(parities, unknown data chunks) by which `want` is had from
    `available`, the fewest parities first; None where no subset of
    the available parities decodes."""
    m, k = matrix.shape
    want, available = set(want), set(available)
    support = [set(np.flatnonzero(matrix[r]).tolist()) for r in range(m)]
    need0 = {i for i in want if i < k}
    for p in want:
        if p >= k and p not in available:
            need0 |= support[p - k]
    parities = sorted(p - k for p in available if p >= k)
    for n in range(len(parities) + 1):
        for ps in itertools.combinations(parities, n):
            need = need0.union(*(support[p] for p in ps))
            unknowns = sorted(need - available)
            if len(unknowns) > n:
                continue
            if unknowns:
                rank, _a, _b = _eliminate(
                    matrix[np.ix_(list(ps), unknowns)])
                if rank < len(unknowns):
                    continue
            return list(ps), unknowns
    return None


def decodable(chunks, config: dict) -> bool:
    """Does the planner accept `chunks` for a read of the object (all
    k data chunks)?"""
    k, m, c = _profile(config)
    return plan(range(k), chunks, coding_matrix(k, m, c)) is not None


def decode(shards: dict, k: int, m: int, c: int) -> np.ndarray:
    """(k, L) uint8: the data chunks from the shards in hand ({chunk
    id: (L,) uint8}); ValueError where they do not decode."""
    matrix = coding_matrix(k, m, c)
    found = plan(range(k), shards, matrix)
    if found is None:
        raise ValueError(f"shec cannot decode from {sorted(shards)}")
    ps, unknowns = found
    data = {i: np.asarray(shards[i], dtype=np.uint8)
            for i in range(k) if i in shards}
    if unknowns:
        rhs = []
        for p in ps:
            acc = np.asarray(shards[p + k], dtype=np.uint8).copy()
            for col in np.flatnonzero(matrix[p]):
                if col not in unknowns:
                    acc ^= oracle._MUL[matrix[p, col]][data[col]]
            rhs.append(acc)
        _rank, _a, solved = _eliminate(
            matrix[np.ix_(list(ps), unknowns)], np.stack(rhs))
        for i, u in enumerate(unknowns):
            data[u] = solved[i]
    return np.stack([data[i] for i in range(k)])
