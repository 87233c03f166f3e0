"""``work.py`` against counts made by hand on a 4x4 CSR.

    A = [[1 1 . .]      rows: 0 -> {0, 1}, 1 -> {0, 2}, 2 -> {1}, 3 -> {}
         [1 . 1 .]
         [. 1 . .]      A @ A: row 0 -> rows 0, 1 of A: 4 partial products,
         [. . . .]      columns {0, 1, 2}; row 1 -> rows 0, 2: 3, {0, 1};
                        row 2 -> row 1: 2, {0, 2}; row 3: 0.
"""
import numpy as np

import work

IP = np.array([0, 2, 4, 5, 5])
IDX = np.array([0, 1, 0, 2, 1])


def test_flop_by_hand():
    assert work.flop(IP, IDX, IP) == 9
    assert list(work.row_flop(IP, IDX, IP)) == [4, 3, 2, 0]


def test_flop_ignores_padding_past_nnz():
    assert work.flop(IP, np.concatenate([IDX, [3, 3, 3]]), IP) == 9


def test_compulsory_bytes_of_a_square():
    # A read once (5 entries) serves both operands; C has 3 + 2 + 2 entries
    assert work.compulsory_bytes(IP, IDX, IP, nnz_c=7, a_row0=0) == 8 * 12


def test_compulsory_bytes_of_row_shards():
    # rows 0-1: 4 own entries, B rows {0, 1, 2} touched, 0-1 are its own
    # rows, so only row 2 (1 entry) more; C rows 0-1 hold 5 entries
    assert work.compulsory_bytes(IP[:3], IDX[:4], IP, nnz_c=5,
                                 a_row0=0) == 8 * (4 + 1 + 5)
    # rows 2-3: 1 own entry, touches B row 1 (2 entries); C rows hold 2
    assert work.compulsory_bytes(IP[2:] - 4, IDX[4:], IP, nnz_c=2,
                                 a_row0=2) == 8 * (1 + 2 + 2)


def test_compulsory_bytes_of_distinct_operands():
    eye = np.arange(5)                   # 4x4 identity: one entry a row
    assert work.flop(IP, IDX, eye) == 5
    # B rows {0, 1, 2} touched, 1 entry each; C = A has 5 entries
    assert work.compulsory_bytes(IP, IDX, eye, nnz_c=5) == 8 * (5 + 3 + 5)


def test_roofline_seconds_takes_the_larger_bound():
    assert work.roofline_seconds(18, 96, 10.0, 8.0) == 12.0
    assert work.roofline_seconds(180, 96, 10.0, 8.0) == 18.0
