"""Jit'd wrapper for the SpMM kernel (CSR x dense)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import CSR
import repro.core.schedule as sched
from repro.kernels import _compat
from repro.kernels._smem import padded_len
from . import kernel as K


def _pad(x: jax.Array, n: int) -> jax.Array:
    return jnp.pad(x, (0, n - x.shape[0]))


def spmm_pallas(a: CSR, x: jax.Array, *, n_bins: int = 8,
                interpret: bool | None = None) -> jax.Array:
    """y = A @ X; X dense (n, k), returns (m, k)."""
    interpret = _compat.resolve_interpret(interpret)
    m, n = a.shape
    k = x.shape[1]
    # the work per row is nnz(a_i*) * k, so nnz-based bins balance it
    offsets = sched.rows_to_bins(a.row_nnz(), n_bins)
    l_ip, l_a = padded_len(m + 1, K.WIN), padded_len(a.cap, K.WIN)
    call = K.spmm_call(n_bins, m, n, k, l_ip, l_a, x.dtype, interpret)
    y = call(offsets, _pad(a.indptr.astype(jnp.int32), l_ip),
             _pad(a.indices, l_a), _pad(a.data.astype(jnp.float32), l_a),
             x.reshape(n, 1, k))
    return y.reshape(m, k)
