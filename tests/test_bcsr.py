"""Differential suite for the planned BCSR subsystem (DESIGN.md sec. 17).

The oracle is ``scipy.sparse.bsr_matrix``: re-blocking a CSR must
reproduce scipy's BSR structure bit for bit (indptr + sorted block
columns), and the planned block product must reproduce the scipy BSR
product's structure exactly -- indptr bitwise, per-row block-column
*sets* (the kernel emits hash order; sortedness is not part of the
contract, per the paper's C8 finding) -- and its values bitwise on
dyadic inputs.  Both sides keep structurally-present but numerically
zero blocks (the structural-product contract), so the comparisons are
exact even for partially-filled tiles.

Also pinned here: the ragged-edge round-trip (``bcsr_to_csr(csr_to_bcsr
(a))`` preserves nnz exactly -- the prune epilogue regression), empty
rows / empty operands, sorted and unsorted inputs, semiring routing
(boolean never reaches the (+, x)-only block path), zero re-inspection
on repeat executes (counter-verified), and the ``"bcsr"`` plan-cache
kind.  The trace-context (jit/vmap) counter proofs live in
``tests/test_trace_contexts.py``; the hypothesis property layer at the
bottom consumes ``_fuzz.bcsr_case``.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp  # noqa: E402

from repro.core import (BCSRPlan, clear_plan_cache, plan_bcsr,  # noqa: E402
                        plan_cache_stats, plan_spgemm, spgemm)
from repro.core.formats import BCSR, bcsr_to_csr, csr_to_bcsr  # noqa: E402
from repro.core.recipe import choose_algorithm  # noqa: E402
from repro.kernels.spgemm_bcsr import ops as bcsr_ops  # noqa: E402
from repro.kernels.spgemm_bcsr import ref as bcsr_ref  # noqa: E402
from _fuzz import (block_clustered_dense, csr_of,  # noqa: E402
                   rand_dense, scramble_rows)

sp = pytest.importorskip("scipy.sparse")


def _bsr(d: np.ndarray, block):
    # scipy's dense -> BSR conversion may leave a block row's columns
    # unsorted (scipy 1.17, when the block row's first nonzero lies in a
    # later tile than another of its tiles); the oracle compares sorted
    m = sp.bsr_matrix(np.asarray(d, np.float32), blocksize=block)
    m.sort_indices()
    return m


def _assert_bcsr_matches_scipy(ours: BCSR, oracle) -> None:
    """Bitwise structure equality of a conversion against scipy BSR
    (both sides emit sorted block columns)."""
    nnzb = int(ours.nnzb)
    assert nnzb == oracle.indices.shape[0]
    assert np.array_equal(np.asarray(ours.indptr), oracle.indptr)
    assert np.array_equal(np.asarray(ours.indices)[:nnzb], oracle.indices)
    assert np.array_equal(np.asarray(ours.blocks)[:nnzb],
                          oracle.data.astype(np.float32))


def _assert_product_matches_scipy(c: BCSR, oracle) -> None:
    """Planned-product structure vs the scipy BSR product: indptr
    bitwise, block columns per row as sets (kernel order is hash order),
    dense values bitwise."""
    nnzb = int(c.nnzb)
    assert nnzb == oracle.indices.shape[0]
    ip = np.asarray(c.indptr)
    assert np.array_equal(ip, oracle.indptr)
    bcols = np.asarray(c.indices)[:nnzb]
    for i in range(len(ip) - 1):
        assert (set(bcols[ip[i]:ip[i + 1]].tolist())
                == set(oracle.indices[ip[i]:ip[i + 1]].tolist())), i
    assert np.array_equal(np.asarray(c.to_dense()),
                          np.asarray(oracle.todense(), np.float32))


# ---------------------------------------------------------------------------
# scipy BSR differential: conversion + planned product
# ---------------------------------------------------------------------------

BLOCK_GRID = [
    # (bm, bk, bn, gm, gk, gn): square and rectangular tiles, incl. 1x1
    (1, 1, 1, 5, 4, 6),
    (2, 2, 2, 4, 3, 5),
    (4, 4, 4, 3, 4, 2),
    (8, 8, 8, 2, 2, 2),
    (2, 4, 8, 3, 2, 2),
    (4, 2, 1, 2, 3, 4),
]


@pytest.mark.parametrize("bm,bk,bn,gm,gk,gn", BLOCK_GRID)
@pytest.mark.parametrize("density", (0.3, 0.7))
def test_csr_to_bcsr_matches_scipy_bsr(bm, bk, bn, gm, gk, gn, density):
    """Re-blocking a CSR reproduces scipy's BSR structure bitwise."""
    ad = block_clustered_dense(gm, gk, bm, bk, density, seed=bm * 100 + gk)
    ab = csr_to_bcsr(csr_of(ad), (bm, bk))
    _assert_bcsr_matches_scipy(ab, _bsr(ad, (bm, bk)))


@pytest.mark.parametrize("bm,bk,bn,gm,gk,gn", BLOCK_GRID)
def test_planned_product_matches_scipy_bsr(bm, bk, bn, gm, gk, gn):
    """The frozen block plan's product == scipy's BSR product: structure
    exactly (set order within rows), dense values bitwise."""
    ad = block_clustered_dense(gm, gk, bm, bk, 0.5, seed=7 * bm + bk)
    bd = block_clustered_dense(gk, gn, bk, bn, 0.5, seed=7 * bn + gk + 1)
    ab = csr_to_bcsr(csr_of(ad), (bm, bk))
    bb = csr_to_bcsr(csr_of(bd), (bk, bn))
    plan = plan_bcsr(ab, bb, cache=False)
    assert isinstance(plan, BCSRPlan) and plan.block_c == (bm, bn)
    c = plan.execute(ab, bb)
    _assert_product_matches_scipy(
        c, (_bsr(ad, (bm, bk)) @ _bsr(bd, (bk, bn))).astype(np.float32))


def test_partially_filled_tiles_keep_structural_zero_blocks():
    """A structurally-present product block whose values are all zero
    (tile misalignment, no cancellation) stays in the pattern on both
    sides -- the structural-product contract."""
    ad = np.zeros((4, 4), np.float32)
    ad[0, 0], ad[1, 0] = 1.0, 2.0       # A tile: nonzeros in tile col 0
    bd = np.zeros((4, 4), np.float32)
    bd[1, 0] = 1.0                      # B tile: nonzeros in tile row 1
    ab = csr_to_bcsr(csr_of(ad), (2, 2))
    bb = csr_to_bcsr(csr_of(bd), (2, 2))
    c = plan_bcsr(ab, bb, cache=False).execute(ab, bb)
    oracle = _bsr(ad, (2, 2)) @ _bsr(bd, (2, 2))
    assert int(c.nnzb) == 1 == oracle.indices.shape[0]
    _assert_product_matches_scipy(c, oracle)


def test_unsorted_input_rows():
    """Row-scrambled (unsorted) CSR input re-blocks to the same BCSR as
    its sorted twin -- the Table-1 unsorted-input case at block
    granularity."""
    ad = block_clustered_dense(4, 4, 4, 4, 0.5, seed=13)
    srt = csr_to_bcsr(csr_of(ad), (4, 4))
    uns = csr_to_bcsr(scramble_rows(csr_of(ad)), (4, 4))
    assert int(srt.nnzb) == int(uns.nnzb)
    assert np.array_equal(np.asarray(srt.indptr), np.asarray(uns.indptr))
    assert np.array_equal(np.asarray(srt.to_dense()),
                          np.asarray(uns.to_dense()))


def test_empty_rows_and_empty_operands():
    """Empty block rows, an all-zero A, and an all-zero product are all
    legal plans that execute to the correct (empty) result."""
    ad = block_clustered_dense(4, 3, 2, 2, 0.6, seed=17)
    ad[2:4, :] = 0.0                    # empty block row
    bd = block_clustered_dense(3, 4, 2, 2, 0.6, seed=18)
    ab, bb = csr_to_bcsr(csr_of(ad), (2, 2)), csr_to_bcsr(csr_of(bd), (2, 2))
    c = plan_bcsr(ab, bb, cache=False).execute(ab, bb)
    _assert_product_matches_scipy(c, (_bsr(ad, (2, 2)) @ _bsr(bd, (2, 2))))

    z = BCSR.from_dense(jnp.zeros((8, 6), jnp.float32), (2, 2))
    plan = plan_bcsr(z, bb, cache=False)
    assert int(plan.nnzb_c) == 0
    out = np.asarray(plan.execute(z, bb).to_dense())
    assert out.shape == (8, 8) and not out.any()


# ---------------------------------------------------------------------------
# ragged edges: non-tile-multiple shapes + the prune-epilogue regression
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,block", [
    ((19, 23), (4, 4)), ((19, 23), (8, 8)), ((7, 5), (2, 4)),
    ((9, 16), (4, 4)), ((16, 9), (8, 2)),
])
def test_ragged_roundtrip_preserves_nnz(shape, block):
    """``bcsr_to_csr(csr_to_bcsr(a))`` on non-tile-multiple shapes is the
    identity: same nnz as the input (the prune epilogue drops the zero
    padding the partial edge tiles store), same dense view."""
    ad = rand_dense(shape[0], shape[1], 0.35, seed=shape[0] + block[0])
    a = csr_of(ad)
    rt = bcsr_to_csr(csr_to_bcsr(a, block))
    assert int(rt.nnz) == int(a.nnz) == int(np.count_nonzero(ad))
    assert np.array_equal(np.asarray(rt.to_dense()), ad)


def test_ragged_planned_product_matches_dense():
    """Planned block product on ragged shapes with rectangular tiles is
    bitwise the dense oracle (partial edge tiles are zero-padded storage;
    the logical shape crops back)."""
    ad = rand_dense(19, 23, 0.4, seed=23)
    bd = rand_dense(23, 17, 0.4, seed=24)
    ab = csr_to_bcsr(csr_of(ad), (4, 4))
    bb = csr_to_bcsr(csr_of(bd), (4, 8))
    plan = plan_bcsr(ab, bb, cache=False)
    got = np.asarray(plan.execute(ab, bb).to_dense())
    assert got.shape == (19, 17)
    assert np.array_equal(got, ad @ bd)
    assert np.array_equal(got, np.asarray(bcsr_ref.numeric_ref(ab, bb)))


# ---------------------------------------------------------------------------
# inspector-executor contract: zero re-inspection, cache kind, dispatcher
# ---------------------------------------------------------------------------

def test_repeat_execute_zero_reinspection():
    """A frozen ``BCSRPlan`` re-inspects nothing: repeat executes run the
    numeric kernel only, proven by the block kernel's call counters."""
    ad = block_clustered_dense(4, 3, 4, 4, 0.6, seed=29)
    bd = block_clustered_dense(3, 4, 4, 4, 0.6, seed=30)
    ab, bb = csr_to_bcsr(csr_of(ad), (4, 4)), csr_to_bcsr(csr_of(bd), (4, 4))
    plan = plan_bcsr(ab, bb, cache=False)
    bcsr_ops.reset_kernel_calls()
    for _ in range(3):
        plan.execute(ab, bb).blocks.block_until_ready()
    calls = bcsr_ops.kernel_call_counts()
    assert calls["symbolic"] == 0, calls
    assert calls["numeric"] == 3, calls


def test_plan_cache_bcsr_kind():
    """``plan_bcsr`` lands in the shared LRU under the ``"bcsr"`` kind;
    a repeat plan on the same structures is a hit that re-inspects
    nothing."""
    clear_plan_cache()
    ad = block_clustered_dense(3, 3, 4, 4, 0.7, seed=31)
    bd = block_clustered_dense(3, 3, 4, 4, 0.7, seed=32)
    ab, bb = csr_to_bcsr(csr_of(ad), (4, 4)), csr_to_bcsr(csr_of(bd), (4, 4))
    p1 = plan_bcsr(ab, bb)
    stats = plan_cache_stats()
    assert stats["kinds"]["bcsr"] >= 1, stats
    bcsr_ops.reset_kernel_calls()
    p2 = plan_bcsr(ab, bb)
    assert p2 is p1
    assert bcsr_ops.kernel_call_counts()["symbolic"] == 0
    assert plan_cache_stats()["hits"] > stats["hits"]


def test_plan_spgemm_bcsr_routing_end_to_end():
    """``plan_spgemm(algorithm="bcsr")`` nests a frozen block plan and
    its CSR-in/CSR-out execute matches the hash planned path bitwise."""
    ad = block_clustered_dense(3, 3, 8, 8, 0.8, seed=33)
    bd = block_clustered_dense(3, 3, 8, 8, 0.8, seed=34)
    a, b = csr_of(ad), csr_of(bd)
    plan = plan_spgemm(a, b, algorithm="bcsr", cache=False)
    assert plan.algorithm == "bcsr"
    assert isinstance(plan.bcsr_plan, BCSRPlan)
    got = plan.execute(a, b)
    ref = plan_spgemm(a, b, algorithm="hash", cache=False).execute(a, b)
    assert int(got.nnz) == int(ref.nnz)
    assert np.array_equal(np.asarray(got.to_dense()),
                          np.asarray(ref.to_dense()))


# ---------------------------------------------------------------------------
# semiring coverage: boolean never reaches the (+, x)-only block path
# ---------------------------------------------------------------------------

def test_boolean_routing_and_explicit_rejection():
    """The recipe never routes boolean products to bcsr; pinning bcsr
    with a general semiring raises; the boolean product on block-dense
    input still computes correctly through the hash family."""
    ad = block_clustered_dense(3, 3, 8, 8, 0.9, seed=35)
    a = csr_of(ad)
    assert choose_algorithm(a, a, probe_blocks=True) == "bcsr"
    assert choose_algorithm(a, a, probe_blocks=True,
                            semiring="boolean") != "bcsr"
    with pytest.raises(NotImplementedError):
        plan_spgemm(a, a, algorithm="bcsr", semiring="boolean", cache=False)
    with pytest.raises(NotImplementedError):
        spgemm(a, a, cap_c=a.n_rows * a.n_rows, algorithm="bcsr",
               semiring="boolean")
    out = spgemm(a, a, cap_c=int((np.count_nonzero(ad @ ad))),
                 semiring="boolean")
    got = np.asarray(out.to_dense())
    assert np.array_equal(got != 0, (ad @ ad) != 0)
    assert set(np.unique(got)) <= {0.0, 1.0}


# ---------------------------------------------------------------------------
# vector-probe variant parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vector", (False, True))
def test_vector_probe_variant_bitwise(vector):
    """Scalar and vectorized block probes agree bitwise with scipy."""
    ad = block_clustered_dense(3, 4, 4, 4, 0.6, seed=37)
    bd = block_clustered_dense(4, 3, 4, 4, 0.6, seed=38)
    ab, bb = csr_to_bcsr(csr_of(ad), (4, 4)), csr_to_bcsr(csr_of(bd), (4, 4))
    plan = plan_bcsr(ab, bb, vector=vector, cache=False)
    c = plan.execute(ab, bb)
    _assert_product_matches_scipy(
        c, (_bsr(ad, (4, 4)) @ _bsr(bd, (4, 4))).astype(np.float32))


# ---------------------------------------------------------------------------
# hypothesis property layer (optional extra)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from _fuzz import bcsr_case
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(bcsr_case())
    def test_fuzz_planned_bcsr_vs_scipy(case):
        """Property layer: any block-clustered product (rectangular
        tiles, thinned tiles, empty operands) planned and executed
        through the block path matches the scipy BSR oracle exactly."""
        ad, bd, (bm, bk, bn) = case
        ab = csr_to_bcsr(csr_of(ad), (bm, bk))
        bb = csr_to_bcsr(csr_of(bd), (bk, bn))
        _assert_bcsr_matches_scipy(ab, _bsr(ad, (bm, bk)))
        plan = plan_bcsr(ab, bb, cache=False)
        c = plan.execute(ab, bb)
        _assert_product_matches_scipy(
            c, (_bsr(ad, (bm, bk)) @ _bsr(bd, (bk, bn))).astype(np.float32))
        rt = bcsr_to_csr(c)
        assert np.array_equal(np.asarray(rt.to_dense()),
                              np.asarray(c.to_dense()))
