"""The SHEC reference against `oracle.py` and against vectors checked
by hand: the shingles of (8, 4, 3) and (4, 3, 2), which chunk sets
decode, and that what it decodes is what was encoded."""

import itertools

import numpy as np
import pytest

from benchmark import oracle
from benchmark.references import shec

CONFIG = {"pool_profile": {"technique": "shec_multiple", "k": "8",
                           "m": "4", "c": "3"}, "stripe_unit": 128}


def supports(k, m, c):
    return ["".join("1" if x else "0" for x in row)
            for row in shec.coding_matrix(k, m, c)]


def test_shingles_by_hand():
    # (8, 4, 3): r_e1 is least for two local parities over halves and
    # two over everything: (4+4+8+8 + 8*4) / 12 = 4.67, against 6.0
    # for one group of four rows six wide
    assert shec.split(8, 4, 3) == [(2, 1), (2, 2)]
    assert supports(8, 4, 3) == ["11110000", "00001111", "11111111",
                                 "11111111"]
    assert shec._r_e1(8, [(2, 1), (2, 2)]) == pytest.approx(56 / 12)
    assert shec._r_e1(8, [(0, 0), (4, 3)]) == pytest.approx(72 / 12)
    # (4, 3, 2): one parity over everything, two over wrapped halves
    assert shec.split(4, 3, 2) == [(1, 1), (2, 1)]
    assert supports(4, 3, 2) == ["1111", "1100", "0011"]


def test_nonzero_entries_are_reed_sol_van():
    full = oracle.reed_sol_van_matrix(8, 4)
    cut = shec.coding_matrix(8, 4, 3)
    assert np.array_equal(cut[cut != 0], full[cut != 0])
    assert np.all(cut[0, :4] == 1)      # jerasure's first row: all ones


def test_which_sets_decode():
    matrix = shec.coding_matrix(8, 4, 3)
    every = range(12)
    # any c = 3 lost chunks decode
    for lost in itertools.combinations(every, 3):
        assert shec.plan(lost, set(every) - set(lost), matrix) is not None
    # 70 of the 495 ways to hold 8 chunks do not give the object
    refused = [have for have in itertools.combinations(every, 8)
               if shec.plan(range(8), have, matrix) is None]
    assert len(refused) == 70
    # by hand: a whole half lost leaves three equations (its local
    # parity and the two global ones) for four unknowns
    assert (4, 5, 6, 7, 8, 9, 10, 11) in refused
    assert (0, 1, 2, 3, 8, 9, 10, 11) in refused
    # one lost data chunk: its three neighbours and the local parity
    assert shec.plan([0], range(1, 12), matrix) == ([0], [0])
    assert shec.decodable([0, 1, 2, 3, 4, 5, 6, 8, 9], CONFIG) is True
    assert shec.decodable([0, 1, 2, 3, 8, 9, 10, 11], CONFIG) is False


def test_decode_gives_back_what_was_encoded():
    rng = np.random.default_rng(5)
    payload = rng.integers(0, 256, 8 * 128 * 3 - 17, dtype=np.uint8).tobytes()
    files = shec.shard_files(payload, 8, 4, 3, 128)
    assert files.shape == (12, 3 * 128)
    # data shards are the payload in ECUtil's layout
    assert np.array_equal(files[:8], oracle.shard_files(payload, 8, 3,
                                                        128)[:8])
    for lost in [(0,), (3, 4), (0, 1, 8), (2, 9, 11), (5, 6, 10)]:
        have = {i: files[i] for i in range(12) if i not in lost}
        assert np.array_equal(shec.decode(have, 8, 4, 3), files[:8]), lost
    with pytest.raises(ValueError):
        shec.decode({i: files[i] for i in (0, 1, 2, 3, 8, 9, 10, 11)},
                    8, 4, 3)


def test_stored_and_its_crcs():
    payload = bytes(range(256)) * 16
    out = shec.stored(payload, CONFIG)
    assert len(out) == 12
    files = shec.shard_files(payload, 8, 4, 3, 128)
    for (data, crc), f in zip(out, files):
        assert data == f.tobytes()
        assert crc == int(oracle.crc32c(f[None, :])[0])
    with pytest.raises(ValueError):
        shec.stored(payload, {"pool_profile": {
            "technique": "reed_sol_van", "k": "8", "m": "4", "c": "3"},
            "stripe_unit": 128})
