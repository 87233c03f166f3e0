"""Public entry points for the propagation-blocking SpGEMM kernels.

The lifecycle mirrors ``spgemm_hash``: all inspection happens host-side
in ``core.pb.plan_pb`` (counted as ``"inspect"`` here), and the two
numeric phases -- bucket scatter and per-bucket merge -- run over frozen
plan arrays only.  ``pb_scatter`` and ``pb_merge`` stay separate public
ops because the distributed layer exchanges the partial-product buffers
between them (scatter on the producer chip, all-to-all, merge on the
consumer chip); ``spgemm_pb`` composes them for the single-device path.

``KERNEL_CALLS`` counts invocations per phase so tests can pin the
zero-re-inspection property: repeat executes must bump only
``scatter``/``merge`` (or their ``batched_`` twins under vmap), never
``inspect``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import custom_batching

from repro.core.formats import CSR

from repro.kernels import _compat
from . import kernel as K

KERNEL_CALLS = {
    "inspect": 0,
    "scatter": 0,
    "merge": 0,
    "batched_scatter": 0,
    "batched_merge": 0,
}


def reset_kernel_calls() -> None:
    for k in KERNEL_CALLS:
        KERNEL_CALLS[k] = 0


def kernel_call_counts() -> dict:
    return dict(KERNEL_CALLS)


# ---------------------------------------------------------------------------
# vmap-dispatching entries (same shape as spgemm_hash ops)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _scatter_entry(n_buckets: int, bucket_cap: int, cap_a: int, cap_b: int,
                   interpret: bool):
    plain = K.scatter_call(n_buckets, bucket_cap, cap_a, cap_b, interpret)

    @custom_batching.custom_vmap
    def entry(bucket_nnz, src_a, src_b, a_data, b_data):
        KERNEL_CALLS["scatter"] += 1
        return plain(bucket_nnz, src_a, src_b, a_data, b_data)

    @entry.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [x if bd else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bd in zip(args, in_batched)]
        KERNEL_CALLS["batched_scatter"] += 1
        batched = K.batched_scatter_call(axis_size, n_buckets, bucket_cap,
                                         cap_a, cap_b, interpret)
        return batched(*args), True

    return entry


@functools.lru_cache(maxsize=128)
def _merge_entry(n_buckets: int, bucket_cap: int, cap_c: int,
                 interpret: bool):
    plain = K.merge_call(n_buckets, bucket_cap, cap_c, interpret)

    @custom_batching.custom_vmap
    def entry(bucket_nnz, seg, pp):
        KERNEL_CALLS["merge"] += 1
        return plain(bucket_nnz, seg, pp)

    @entry.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [x if bd else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bd in zip(args, in_batched)]
        KERNEL_CALLS["batched_merge"] += 1
        batched = K.batched_merge_call(axis_size, n_buckets, bucket_cap,
                                       cap_c, interpret)
        return batched(*args), True

    return entry


# ---------------------------------------------------------------------------
# public phase ops
# ---------------------------------------------------------------------------

def pb_scatter(a_data, b_data, src_a, src_b, bucket_nnz, *,
               interpret: bool | None = None):
    """Propagate phase: expand partial products into bucket-major order.

    Returns ``pp`` of shape ``(n_buckets, bucket_cap)`` (float32), pad
    lanes zeroed.  All index arrays come frozen out of a ``PBPlan``.
    """
    interpret = _compat.resolve_interpret(interpret)
    n_buckets, bucket_cap = src_a.shape
    entry = _scatter_entry(n_buckets, bucket_cap, a_data.shape[0],
                           b_data.shape[0], interpret)
    return entry(bucket_nnz, src_a, src_b, a_data.astype(jnp.float32),
                 b_data.astype(jnp.float32))


def pb_merge(pp, seg, bucket_nnz, cap_c: int, *,
             interpret: bool | None = None):
    """Merge phase: reduce each bucket into its disjoint output slots.

    Returns ``data_c`` of shape ``(cap_c,)`` (float32).  Safe to run per
    bucket independently: the plan guarantees buckets never share an
    output slot (one column segment per bucket).
    """
    interpret = _compat.resolve_interpret(interpret)
    n_buckets, bucket_cap = pp.shape
    entry = _merge_entry(n_buckets, bucket_cap, cap_c, interpret)
    return entry(bucket_nnz, seg, pp)


def spgemm_pb(a: CSR, b: CSR, cap_c: int, *, src_a, src_b, seg, bucket_nnz,
              indptr_c, cols_c, interpret: bool | None = None) -> CSR:
    """Planned propagation-blocking SpGEMM (plus_times), numeric only.

    Every structural decision -- bucket layout, source gathers, output
    slots, C's sorted column structure -- is frozen in the plan arrays;
    this function is trace-safe and touches no data-dependent shapes.
    """
    pp = pb_scatter(a.data, b.data, src_a, src_b, bucket_nnz,
                    interpret=interpret)
    data = pb_merge(pp, seg, bucket_nnz, cap_c, interpret=interpret)
    nnz_c = indptr_c[-1]
    valid = jnp.arange(cap_c, dtype=jnp.int32) < nnz_c
    data = jnp.where(valid, data, 0).astype(a.data.dtype)
    cols = jnp.where(valid, cols_c, 0)
    m, n = a.shape[0], b.shape[1]
    return CSR(indptr_c, cols, data, nnz_c, (m, n), sorted_cols=True)
