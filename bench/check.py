"""The plain reference and the comparison that decides ``correct``.

The reference is ``scipy.sparse`` in float64 on the benchmark's own host
copy of the inputs (the pattern it generated and the values it drew); it
imports nothing of the program.  The comparison takes what the timed path
returned, as host arrays, and reports two numbers:

* ``rows_off`` -- rows of C whose column set differs from the reference
  (a missing, extra, repeated or out-of-range column, or a wrong row
  length).  Exact: its limit is 0.
* ``value_rel_err`` -- the largest ``|c - ref| / |ref|`` over the entries
  both hold.  Every input value is positive, so no reference entry is
  near zero and the relative error is well defined.
"""
from __future__ import annotations

import numpy as np


def reference(indptr, indices, values, n_cols: int):
    """``A @ A`` in float64 by scipy, rows sorted."""
    import scipy.sparse as sp
    ip = np.asarray(indptr, np.int64)
    nnz = int(ip[-1])
    m = ip.size - 1
    a = sp.csr_matrix((np.asarray(values, np.float64)[:nnz],
                       np.asarray(indices)[:nnz], ip), shape=(m, n_cols))
    c = (a @ a).tocsr()
    c.sort_indices()
    return c


def _keys(indptr, indices, n_cols: int):
    ip = np.asarray(indptr, np.int64)
    rows = np.repeat(np.arange(ip.size - 1, dtype=np.int64), np.diff(ip))
    return rows, rows * n_cols + np.asarray(indices, np.int64)[:ip[-1]]


def compare(got, ref) -> dict:
    """``got = (indptr, indices, values)`` of the program's C on the host
    (entries of a row in any order); ``ref`` is :func:`reference`'s
    result.  Returns ``{"rows_off": int, "value_rel_err": float}``."""
    n = ref.shape[1]
    g_ip, g_idx, g_val = (np.asarray(x) for x in got)
    m = ref.shape[0]
    if g_ip.shape != (m + 1,) or g_ip[0] != 0 or np.any(np.diff(
            g_ip.astype(np.int64)) < 0) or g_ip[-1] > g_idx.size:
        return {"rows_off": m, "value_rel_err": float("inf")}
    nnz = int(g_ip[-1])
    g_idx = g_idx[:nnz].astype(np.int64)
    g_val = g_val[:nnz].astype(np.float64)
    g_rows, g_keys = _keys(g_ip, g_idx, n)
    bad_col = (g_idx < 0) | (g_idx >= n)
    order = np.argsort(g_keys, kind="stable")
    g_rows, g_keys, g_val = g_rows[order], g_keys[order], g_val[order]
    bad_col = bad_col[order]
    r_rows, r_keys = _keys(ref.indptr, ref.indices, n)
    r_val = np.asarray(ref.data, np.float64)

    off = set(g_rows[bad_col].tolist())
    dup = np.flatnonzero(g_keys[1:] == g_keys[:-1])
    off.update(g_rows[dup].tolist())
    only_g = ~np.isin(g_keys, r_keys)
    only_r = ~np.isin(r_keys, g_keys)
    off.update(g_rows[only_g].tolist())
    off.update(r_rows[only_r].tolist())

    both, gi, ri = np.intersect1d(g_keys, r_keys, assume_unique=False,
                                  return_indices=True)
    if both.size:
        err = np.abs(g_val[gi] - r_val[ri]) / np.abs(r_val[ri])
        rel = float(np.max(err)) if err.size else 0.0
        if not np.isfinite(rel):
            rel = float("inf")
    else:
        rel = 0.0 if r_keys.size == 0 else float("inf")
    return {"rows_off": len(off), "value_rel_err": rel}
