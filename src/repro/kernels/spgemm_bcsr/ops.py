"""Jit'd wrapper for BCSR SpGEMM: symbolic at block granularity (reusing the
scalar hash symbolic kernel on the block *pattern*), then the MXU numeric
kernel.  The paper's two-phase structure is unchanged; only the currency is
tiles instead of scalars.

Inspector-executor path (``core.bcsr``): ``bcsr_inspect`` is the whole
Fig. 6/7 inspection at block granularity -- equal-flop block-row bins,
static + per-bin table sizes, and the exact block-nnz row pointer of C via
the scalar symbolic kernel on the occupancy patterns.  ``plan_bcsr`` runs
it once (eagerly) and freezes the result; ``spgemm_bcsr(...,
schedule=(offsets, bin_tsize), indptr_cb=...)`` then skips it entirely, so
a structure-identical repeat product stages the numeric kernel alone.

Trace contexts: with a plan-frozen schedule every dynamic value is an
ordinary traced array, so the planned path runs under ``jit`` and --
through a ``custom_vmap`` rule dispatching the batched grid of
``kernel.py`` -- under ``vmap`` over fleets of block-value members.

``KERNEL_CALLS`` counts, at trace time, which phase was staged:
``symbolic`` is the block-granularity inspection (schedule + symbolic
kernel), so planned repeat executes are proven to re-inspect zero times;
``numeric``/``batched_numeric`` are the MXU Pallas entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.core.formats import CSR, BCSR
import repro.core.schedule as sched
from repro.kernels import _compat
from repro.kernels.spgemm_hash import kernel as HK
from repro.kernels.spgemm_hash import ops as hash_ops
from . import kernel as K

#: Trace-time dispatch counters (see module docstring).
KERNEL_CALLS = {"symbolic": 0, "numeric": 0, "batched_numeric": 0}


def reset_kernel_calls() -> None:
    """Zero the trace-time dispatch counters (test/bench helper)."""
    for k in KERNEL_CALLS:
        KERNEL_CALLS[k] = 0


def kernel_call_counts() -> dict:
    """Snapshot of :data:`KERNEL_CALLS`."""
    return dict(KERNEL_CALLS)


def _pattern_csr(a: BCSR) -> CSR:
    """Block-occupancy pattern of a BCSR as a scalar CSR over the block grid."""
    gm, gn = a.grid
    ones = jnp.where(a.valid_mask(), 1.0, 0.0).astype(jnp.float32)
    return CSR(a.indptr, a.indices, ones, a.nnzb, (gm, gn), sorted_cols=True)


def bcsr_inspect(a: BCSR, b: BCSR, *, n_bins: int = 8, vector: bool = False,
                 table_size: int | None = None, interpret: bool | None = None,
                 eager: bool = False):
    """Block-granularity inspection: Fig. 6 schedule + Fig. 7 table sizing +
    symbolic block-nnz, all on the occupancy patterns of A and B.

    Returns ``(flop, offsets, bin_tsize, table_size, row_nnzb, indptr_cb)``
    where ``flop`` is the per-block-row *block* flop profile (the
    load-balance weight and the verifier's probe-termination bound).
    ``eager=True`` uses the un-jitted schedule so the int32 flop-overflow
    guard can fire on concrete inputs (the planner's path).
    """
    KERNEL_CALLS["symbolic"] += 1
    pa, pb = _pattern_csr(a), _pattern_csr(b)
    gm = pa.n_rows

    mk = sched.make_schedule_eager if eager else sched.make_schedule
    flop, offsets, tsize = mk(pa, pb, n_bins)
    if table_size is None:
        table_size = sched.lowest_p2(
            int(min(int(jnp.max(flop)), pb.n_cols)) + 1)
    table_size = max(table_size, HK.CHUNK)
    bin_tsize = sched.bin_table_sizes(tsize, pb.n_cols, table_size,
                                      floor=HK.CHUNK)

    # Phase 1 (symbolic): exact block-nnz per block row of C, via the
    # scalar hash symbolic kernel on the block patterns.
    row_nnzb = hash_ops.spgemm_hash_symbolic(
        pa, pb, vector=vector, table_size=table_size,
        interpret=interpret, schedule=(offsets, bin_tsize))
    indptr_cb = sched.prefix_sum(row_nnzb).astype(jnp.int32)
    return flop, offsets, bin_tsize, table_size, row_nnzb, indptr_cb


# ---------------------------------------------------------------------------
# trace-context entry point: the plain numeric kernel, made vmappable
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _numeric_entry(n_bins: int, gm: int, bcap_a: int, bcap_b: int,
                   bcap_c: int, block_a, block_b, table_size: int,
                   vector: bool, interpret: bool):
    plain = K.numeric_call(n_bins, gm, bcap_a, bcap_b, bcap_c, block_a,
                           block_b, table_size, vector, interpret)

    @custom_batching.custom_vmap
    def num(offsets, bin_tsize, indptr_a, indptr_b, indptr_c,
            a_bcol, a_blk, b_bcol, b_blk):
        KERNEL_CALLS["numeric"] += 1
        bcols, blocks = plain(offsets, bin_tsize, indptr_a, indptr_b,
                              indptr_c, a_bcol, a_blk, b_bcol, b_blk)
        return bcols, blocks

    @num.def_vmap
    def _rule(axis_size, in_batched, *args):
        KERNEL_CALLS["batched_numeric"] += 1
        args = [x if bd else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bd in zip(args, in_batched)]
        bcols, blocks = K.batched_numeric_call(
            axis_size, n_bins, gm, bcap_a, bcap_b, bcap_c, block_a, block_b,
            table_size, vector, interpret)(*args)
        return (bcols, blocks), (True, True)

    return num


def spgemm_bcsr(a: BCSR, b: BCSR, bcap_c: int, *, n_bins: int = 8,
                vector: bool = False, table_size: int | None = None,
                interpret: bool | None = None,
                schedule=None, indptr_cb: jax.Array | None = None) -> BCSR:
    """C = A @ B on BCSR operands. Block rows of C are unsorted (C8).

    ``schedule=(offsets, bin_tsize)`` skips the block-level Fig. 6
    inspection (pass a static ``table_size`` alongside); ``indptr_cb=``
    additionally skips the symbolic kernel -- the planned execute path
    stages the MXU numeric kernel alone.
    """
    interpret = _compat.resolve_interpret(interpret)
    bm, bk = a.block
    bk2, bn = b.block
    assert bk == bk2 and a.shape[1] == b.shape[0], (a.block, b.block)
    gm = a.grid[0]

    if schedule is None or indptr_cb is None:
        assert schedule is None and indptr_cb is None, \
            "pass schedule and indptr_cb together (both from bcsr_inspect)"
        _, offsets, bin_tsize, table_size, _, indptr_cb = bcsr_inspect(
            a, b, n_bins=n_bins, vector=vector, table_size=table_size,
            interpret=interpret)
    else:
        offsets, bin_tsize = schedule
        assert table_size is not None, \
            "a precomputed schedule needs its static table_size"
        table_size = max(table_size, HK.CHUNK)
    n_bins = offsets.shape[0] - 1

    # Phase 2 (numeric): MXU tile products into the hash-addressed VMEM bank.
    num = _numeric_entry(n_bins, gm, a.bcap, b.bcap, bcap_c, a.block,
                         b.block, table_size, vector, interpret)
    bcols_c, blocks_c = num(offsets, bin_tsize, a.indptr, b.indptr, indptr_cb,
                            a.indices, a.blocks.astype(jnp.float32),
                            b.indices, b.blocks.astype(jnp.float32))
    nnzb_c = indptr_cb[-1]
    valid = jnp.arange(bcap_c, dtype=jnp.int32) < nnzb_c
    bcols_c = jnp.where(valid, bcols_c, 0)
    blocks_c = jnp.where(valid[:, None, None], blocks_c, 0).astype(a.dtype)
    return BCSR(indptr_cb, bcols_c, blocks_c, nnzb_c,
                (a.shape[0], b.shape[1]), (bm, bn))
