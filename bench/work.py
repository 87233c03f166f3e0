"""Work counts of a sparse product, kept with the benchmark.

Everything here is computed on the host from the benchmark's own CSR
arrays (numpy), never from what the program returns, so a change to the
program cannot change the yardstick.

* ``flop`` -- partial products of ``A @ B``: the sum over A's entries
  ``(i, k)`` of the length of B's row ``k`` (the paper's "flop"; the
  paper's MFLOP/s rate counts ``2 * flop``, a multiply and an add).
* ``compulsory_bytes`` -- the least traffic any kernel that forms C needs:
  every entry (4-byte column index and 4-byte value) of each distinct row
  of the inputs that the product touches, read once, and every entry of C
  written once.  When A's rows are rows of B (``A @ A``, or a row shard of
  A times A) a row serves both roles and counts once.  Row pointers are
  left out: they are under 0.1% of the entries at these sizes, and a
  kernel handed the plan's row pointers need not read them again.  So no
  kernel can beat this count and a roofline share built on it stays
  under 100%.
"""
from __future__ import annotations

import numpy as np

#: bytes of one stored entry: int32 column index + float32 value
ENTRY_BYTES = 8


def row_lengths(indptr) -> np.ndarray:
    return np.diff(np.asarray(indptr, np.int64))


def flop(a_indptr, a_indices, b_indptr) -> int:
    """Partial products of ``A @ B`` (``a_indices`` may carry padding
    past ``a_indptr[-1]``; it is ignored)."""
    ip = np.asarray(a_indptr, np.int64)
    cols = np.asarray(a_indices)[:ip[-1]]
    return int(row_lengths(b_indptr)[cols].sum())


def row_flop(a_indptr, a_indices, b_indptr) -> np.ndarray:
    """Partial products of each row of ``A @ B``."""
    ip = np.asarray(a_indptr, np.int64)
    cols = np.asarray(a_indices)[:ip[-1]]
    per_entry = row_lengths(b_indptr)[cols]
    csum = np.concatenate([[0], np.cumsum(per_entry)])
    return csum[ip[1:]] - csum[ip[:-1]]


def compulsory_bytes(a_indptr, a_indices, b_indptr, nnz_c: int,
                     a_row0: int | None = None) -> int:
    """Least bytes moved by any kernel forming ``C = A @ B``.

    ``a_row0`` says that A is rows ``a_row0 .. a_row0 + m_a`` of B itself
    (``A @ A``: ``a_row0=0``; a row shard of A times A: the shard's first
    row); then rows of B that A also holds count once.  ``None`` means A
    and B are distinct matrices.
    """
    ip = np.asarray(a_indptr, np.int64)
    cols = np.asarray(a_indices)[:ip[-1]]
    b_len = row_lengths(b_indptr)
    touched = np.zeros(b_len.size, bool)
    touched[cols] = True
    a_entries = int(ip[-1] - ip[0])
    if a_row0 is not None:
        m_a = ip.size - 1
        touched[a_row0:a_row0 + m_a] = False    # read once, as A's rows
    b_entries = int(b_len[touched].sum())
    return ENTRY_BYTES * (a_entries + b_entries + int(nnz_c))


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """Least time the chip needs: the larger of compute and traffic time
    (``flops`` counts multiplies and adds, so ``2 * flop``)."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
