"""The comparison that decides ``correct``, on a hand-made product."""
import numpy as np

import check

IP = np.array([0, 2, 4, 5, 5])
IDX = np.array([0, 1, 0, 2, 1])
VAL = np.array([1.0, 2.0, 3.0, 4.0, 5.0])


def _ref():
    return check.reference(IP, IDX, VAL, 4)


def test_reference_by_hand():
    # row 0 = 1 * A[0] + 2 * A[1] = [1 + 6, 2, 8, 0]
    ref = _ref()
    assert ref.toarray()[0].tolist() == [7.0, 2.0, 8.0, 0.0]
    assert ref.nnz == 7


def _got():
    ref = _ref()
    ip = ref.indptr.copy()
    idx, val = ref.indices.copy(), ref.data.astype(np.float32)
    # the program may leave a row's entries in any order
    idx[:3], val[:3] = idx[:3][::-1].copy(), val[:3][::-1].copy()
    return ip, idx, val


def test_unsorted_rows_compare_equal():
    assert check.compare(_got(), _ref()) == {"rows_off": 0,
                                             "value_rel_err": 0.0}


def test_wrong_value_is_seen():
    ip, idx, val = _got()
    val[1] *= 1.01
    got = check.compare((ip, idx, val), _ref())
    assert got["rows_off"] == 0
    assert abs(got["value_rel_err"] - 0.01) < 1e-6


def test_wrong_column_missing_row_and_duplicate_are_seen():
    ip, idx, val = _got()
    idx[3] = 3                                   # row 1: wrong column
    assert check.compare((ip, idx, val), _ref())["rows_off"] == 1
    ip, idx, val = _got()
    idx[1] = idx[0]                              # row 0: repeated column
    assert check.compare((ip, idx, val), _ref())["rows_off"] == 1
    ip, idx, val = _got()
    ip = np.minimum(ip, ip[2])                   # rows 2-3 left out
    assert check.compare((ip, idx, val), _ref())["rows_off"] == 1


def test_malformed_row_pointer_fails_everything():
    ip, idx, val = _got()
    ip[2] = 99
    got = check.compare((ip, idx, val), _ref())
    assert got["rows_off"] == 4 and got["value_rel_err"] == float("inf")


def test_nan_is_never_within_a_limit():
    ip, idx, val = _got()
    val[0] = np.nan
    assert check.compare((ip, idx, val), _ref())["value_rel_err"] == \
        float("inf")
