"""Planner subsystem + capacity bugfix regressions (DESIGN.md section 10).

Hypothesis-free on purpose (like test_semiring.py): this is the coverage
for the four capacity bugfixes and the plan-reuse contract, and it must
run even without the optional property-testing extra.

Contracts:
  * heap honors the caller's ``cap_c`` -- output shapes equal across
    algorithms (static-shape/jit-reuse contract);
  * heap row overflow *drops* the overflow and keeps the first ``row_cap``
    entries intact (vs the old silent overwrite of the last slot);
  * ``symbolic(flop_cap=exact)`` == ``symbolic()`` with the default
    worst-case buffer;
  * the int32 prefix-sum guard raises instead of mis-binning;
  * cached-plan execute == fresh ``spgemm`` across all semirings x masks,
    with zero schedule/symbolic recomputation and correct cache keying.
"""
import dataclasses
import importlib
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import (clear_plan_cache, plan_cache_stats, plan_spgemm,
                        spgemm, spgemm_heap, symbolic)
from repro.core import schedule as sched_pkg  # noqa: F401  # verify: allow(dead-import) -- deliberate import check
import repro.core.schedule as sched
from repro.core.plan import structure_key
from repro.data.rmat import rmat_csr

ALL_SEMIRINGS = ("plus_times", "boolean", "min_plus", "plus_first")


def _pair(seed=3, scale=5, ef=3):
    a = rmat_csr(scale, ef, "G500", seed=seed)
    b = rmat_csr(scale, ef, "ER", seed=seed + 100)
    cd = np.asarray(a.to_dense()) @ np.asarray(b.to_dense())
    return a, b, cd


def _dense_semiring(a, b, sr_name):
    ad, bd = np.asarray(a.to_dense()), np.asarray(b.to_dense())
    ap, bp = ad != 0, bd != 0
    if sr_name == "plus_times":
        return ad @ bd
    if sr_name == "boolean":
        return ((ap @ bp) > 0).astype(np.float32)
    if sr_name == "plus_first":
        return ad @ bp.astype(np.float32)
    if sr_name == "min_plus":
        s = np.where(ap[:, :, None] & bp[None, :, :],
                     ad[:, :, None] + bd[None, :, :], np.inf)
        out = s.min(axis=1)
        return np.where(np.isinf(out), 0.0, out).astype(np.float32)
    raise AssertionError(sr_name)


# ---------------------------------------------------------------------------
# Bugfix regressions
# ---------------------------------------------------------------------------

def test_heap_honors_cap_c_shapes_equal_across_algorithms():
    """spgemm(algorithm='heap') must return the same static output shapes
    as every other algorithm for the same cap_c (jit-reuse contract)."""
    a, b, cd = _pair()
    cap = int((cd != 0).sum()) + 8
    kw = dict(row_cap=int(max((cd != 0).sum(axis=1))) + 1,
              k_width=int(np.asarray(a.row_nnz()).max()) + 1)
    ch = spgemm(a, b, cap, algorithm="heap", **kw)
    for algo in ("esc", "hash"):
        c = spgemm(a, b, cap, algorithm=algo)
        assert ch.indices.shape == c.indices.shape == (cap,), algo
        assert ch.data.shape == c.data.shape == (cap,), algo
    assert np.allclose(np.asarray(ch.to_dense()), cd, atol=1e-3)
    # direct call without cap_c keeps the legacy m * row_cap panel size
    legacy = spgemm_heap(a, b, **kw)
    assert legacy.cap == a.n_rows * kw["row_cap"]


def test_heap_overflow_drops_instead_of_overwriting():
    """A row exceeding row_cap keeps its first row_cap (smallest-column)
    entries with correct values; overflow is dropped, never merged into
    the last slot."""
    a, b, cd = _pair(seed=7)
    full_cap = int((cd != 0).sum()) + 8
    k_width = int(np.asarray(a.row_nnz()).max()) + 1
    for row_cap in (1, 2, 3):
        c = spgemm_heap(a, b, row_cap=row_cap, k_width=k_width,
                        cap_c=full_cap)
        ip, cols, vals = (np.asarray(c.indptr), np.asarray(c.indices),
                          np.asarray(c.data))
        for i in range(a.n_rows):
            keep = np.nonzero(cd[i])[0][:row_cap]
            got_c = cols[ip[i]:ip[i + 1]]
            got_v = vals[ip[i]:ip[i + 1]]
            assert np.array_equal(got_c, keep), (row_cap, i)
            assert np.allclose(got_v, cd[i][keep], atol=1e-3), (row_cap, i)


def test_symbolic_flop_cap_equivalence():
    a, b, _ = _pair(seed=5)
    rn0, ip0, flop, total = symbolic(a, b)
    rn1, ip1, _, _ = symbolic(a, b, flop_cap=int(total))
    assert np.array_equal(np.asarray(rn0), np.asarray(rn1))
    assert np.array_equal(np.asarray(ip0), np.asarray(ip1))
    # masked variant too (the planner's path)
    mask = rmat_csr(5, 4, "ER", seed=9)
    rm0, im0, _, _ = symbolic(a, b, mask=mask)
    rm1, im1, _, _ = symbolic(a, b, mask=mask, flop_cap=int(total))
    assert np.array_equal(np.asarray(rm0), np.asarray(rm1))
    assert np.array_equal(np.asarray(im0), np.asarray(im1))


def test_rows_to_bins_overflow_guard():
    """int32 mode: the guard raises instead of mis-binning.  x64 mode
    (the CI leg with JAX_ENABLE_X64=1): accumulation is promoted to
    int64, the guard stays silent, and the huge input bins *exactly* --
    the promotion path that is otherwise only exercised implicitly."""
    import jax
    huge = jnp.full((8,), 2**30, jnp.int32)   # total 2^33 >> int32
    if jax.config.jax_enable_x64:
        sched.guard_i32_flop(huge, "rows_to_bins")          # no raise
        off = np.asarray(sched.rows_to_bins(huge, 4))
        assert off[0] == 0 and off[-1] == 8
        # uniform rows: the equal-flop partition is exact under int64
        assert np.array_equal(off, [0, 2, 4, 6, 8])
        assert np.asarray(sched.bin_flop(huge, jnp.asarray(off))).sum() \
            == 8 * 2**30
    else:
        with pytest.raises(OverflowError, match="overflows the int32"):
            sched.rows_to_bins(huge, 8)
        with pytest.raises(OverflowError):
            sched.guard_i32_flop(huge, "bin_flop")
    # sane totals stay silent and exact in both modes
    ok = jnp.full((8,), 1000, jnp.int32)
    off = np.asarray(sched.rows_to_bins(ok, 4))
    assert off[0] == 0 and off[-1] == 8


def test_rows_to_bins_exact_when_total_times_bins_passes_int32():
    """A total under 2^31 bins exactly even where ``total * (n_bins - 1)``
    would wrap int32 (G500 scale 16 has 400M flop, x7 targets = 2.8e9):
    the result equals the int64 host partition."""
    rng = np.random.default_rng(0)
    flop = rng.integers(0, 2**16, size=4096).astype(np.int32)
    flop[17] = 2**30                      # one heavy row; total < 2^31
    assert flop.astype(np.int64).sum() < 2**31 <= \
        flop.astype(np.int64).sum() * 7
    off = np.asarray(sched.rows_to_bins(jnp.asarray(flop), 8))
    assert np.array_equal(off, sched.equal_weight_partition(flop, 8))


# ---------------------------------------------------------------------------
# Plan construction, caching, and reuse
# ---------------------------------------------------------------------------

def test_plan_records_exact_capacities_and_choice():
    a, b, cd = _pair()
    clear_plan_cache()
    plan = plan_spgemm(a, b)
    assert plan.nnz_c == int((cd != 0).sum()) == plan.cap_c
    assert plan.total_flop == plan.flop_cap
    assert plan.row_cap == int(max((cd != 0).sum(axis=1)))
    assert plan.algorithm in ("esc", "heap", "hash", "hash_vector", "dense")
    bt = np.asarray(plan.bin_tsize)
    assert bt.shape == (plan.n_bins,)
    assert np.all((bt & (bt - 1)) == 0) and bt.max() <= plan.table_size


@pytest.mark.parametrize("algo", ("esc", "heap", "hash", "hash_vector"))
def test_plan_execute_matches_fresh_spgemm(algo):
    a, b, cd = _pair(seed=11)
    clear_plan_cache()
    plan = plan_spgemm(a, b, algorithm=algo)
    c = plan.execute(a, b)
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3), algo
    assert int(c.nnz) == int((cd != 0).sum()), algo


@pytest.mark.parametrize("semiring", ALL_SEMIRINGS)
@pytest.mark.parametrize("masked", (False, True))
@pytest.mark.parametrize("complement", (False, True))
def test_plan_reuse_equals_fresh_across_semirings_and_masks(
        semiring, masked, complement):
    """Cached-plan execute == fresh spgemm over the test_semiring grid."""
    if complement and not masked:
        pytest.skip("complement needs a mask")
    a = rmat_csr(5, 3, "G500", seed=11)
    b = rmat_csr(5, 3, "ER", seed=111)
    mask = rmat_csr(5, 4, "ER", seed=7) if masked else None
    cd = _dense_semiring(a, b, semiring)
    if masked:
        md = np.asarray(mask.to_dense()) != 0
        cd = np.where(~md if complement else md, cd, 0.0)
    cap = int((cd != 0).sum()) + 8

    clear_plan_cache()
    plan = plan_spgemm(a, b, semiring=semiring, mask=mask,
                       complement_mask=complement)
    # second plan request: structure-identical -> cache hit, same object
    plan2 = plan_spgemm(a, b, semiring=semiring, mask=mask,
                        complement_mask=complement)
    assert plan2 is plan
    assert plan_cache_stats()["hits"] == 1

    c_plan = plan.execute(a, b)
    c_fresh = spgemm(a, b, cap, algorithm=plan.algorithm, semiring=semiring,
                     mask=mask, complement_mask=complement,
                     **({"row_cap": plan.row_cap, "k_width": plan.k_width}
                        if plan.algorithm == "heap" else {}))
    assert np.allclose(np.asarray(c_plan.to_dense()), cd, atol=1e-3)
    assert np.allclose(np.asarray(c_plan.to_dense()),
                       np.asarray(c_fresh.to_dense()), atol=1e-3)


def test_plan_execute_no_reinspection():
    """The executor must not touch schedule or the symbolic kernel."""
    a, b, cd = _pair(seed=2)
    clear_plan_cache()
    plan = plan_spgemm(a, b, algorithm="hash")
    counts = {}

    def counted(module_name, attr):
        mod = importlib.import_module(module_name)
        orig = getattr(mod, attr)

        def wrapper(*args, **kw):
            counts[attr] = counts.get(attr, 0) + 1
            return orig(*args, **kw)

        setattr(mod, attr, wrapper)
        return mod, attr, orig

    patched = [counted("repro.core.schedule", "make_schedule"),
               counted("repro.core.schedule", "rows_to_bins"),
               counted("repro.core.schedule", "flops_per_row"),
               counted("repro.kernels.spgemm_hash.kernel", "symbolic_call")]
    try:
        c = plan.execute(a, b)
    finally:
        for mod, attr, orig in patched:
            setattr(mod, attr, orig)
    assert counts == {}, f"execute re-inspected: {counts}"
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3)


def test_plan_cache_keys_on_structure_not_values():
    a, b, cd = _pair(seed=4)
    clear_plan_cache()
    plan = plan_spgemm(a, b)
    # same structure, new values -> hit; result reflects the new values
    a2 = dataclasses.replace(a, data=a.data * 3.0)
    assert plan_spgemm(a2, b) is plan
    c2 = plan.execute(a2, b)
    assert np.allclose(np.asarray(c2.to_dense()), 3.0 * cd, atol=1e-3)
    assert structure_key(a2) == structure_key(a)
    # different structure -> miss
    a3 = rmat_csr(5, 3, "G500", seed=5)
    assert structure_key(a3) != structure_key(a)
    assert plan_spgemm(a3, b) is not plan
    # different request on the same structure -> its own plan
    assert plan_spgemm(a, b, semiring="boolean") is not plan


def test_plan_heap_matches_dispatcher_sortedness_contract():
    """Explicit heap on unsorted inputs fails loudly (like spgemm_heap);
    only the recipe's auto choice is demoted to the hash family."""
    a, b, _ = _pair(seed=3)
    au = a.with_unsorted_flag()
    clear_plan_cache()
    with pytest.raises(AssertionError, match="sorted inputs"):
        plan_spgemm(au, b, algorithm="heap")
    assert plan_spgemm(au, b).algorithm != "heap"


def test_plan_bucket_caps_power_of_two_and_correct():
    a, b, cd = _pair(seed=12)
    clear_plan_cache()
    p = plan_spgemm(a, b, algorithm="hash", bucket_caps=True)
    for cap in (p.cap_c, p.flop_cap, p.row_cap):
        assert cap & (cap - 1) == 0, cap            # powers of two
    assert p.cap_c >= p.nnz_c and p.flop_cap >= p.total_flop
    assert p.nnz_c == int((cd != 0).sum())          # counts stay exact
    c = p.execute(a, b)
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3)
    # bucketed and exact requests are distinct cache entries
    assert plan_spgemm(a, b, algorithm="hash") is not p


def test_plan_cache_lru_bound():
    from repro.core import plan as plan_mod
    clear_plan_cache()
    old_cap = plan_mod.PLAN_CACHE_CAPACITY
    plan_mod.PLAN_CACHE_CAPACITY = 2
    try:
        a, b, _ = _pair(seed=20)
        p1 = plan_spgemm(a, b)
        p2 = plan_spgemm(a, b, semiring="boolean")
        assert plan_spgemm(a, b) is p1              # refreshes p1's recency
        p3 = plan_spgemm(a, b, semiring="plus_first")
        assert plan_cache_stats()["size"] == 2
        assert plan_spgemm(a, b) is p1              # survived (recently used)
        assert plan_spgemm(a, b, semiring="plus_first") is p3
        assert plan_spgemm(a, b, semiring="boolean") is not p2  # evicted
    finally:
        plan_mod.PLAN_CACHE_CAPACITY = old_cap


def test_plan_cache_stats_reports_zero_for_empty_kinds():
    """A cold cache reports every registered kind with a zero count --
    dashboards can index stats['kinds'][kind] unconditionally instead of
    KeyError-ing until the first plan of that kind lands."""
    from repro.core import PLAN_KINDS
    clear_plan_cache()
    kinds = plan_cache_stats()["kinds"]
    assert set(PLAN_KINDS) <= set(kinds)
    assert all(kinds[k] == 0 for k in PLAN_KINDS)
    a, b, _ = _pair(seed=30)
    plan_spgemm(a, b)
    kinds = plan_cache_stats()["kinds"]
    assert kinds["spgemm"] == 1
    assert all(kinds[k] == 0 for k in PLAN_KINDS if k != "spgemm")


def test_plan_execute_rejects_mismatched_structure():
    a, b, _ = _pair(seed=6)
    clear_plan_cache()
    plan = plan_spgemm(a, b)
    other = rmat_csr(4, 3, "ER", seed=1)          # 16x16: wrong shape
    with pytest.raises(AssertionError, match="plan is for"):
        plan.execute(other, other)
    # same shape/cap, different nnz -> caught by the cheap check
    smaller = rmat_csr(5, 2, "G500", seed=99)
    if smaller.cap == a.cap:
        pytest.skip("rng produced equal caps; cheap check not exercised")
    with pytest.raises(AssertionError):
        plan.execute(smaller, b)


def test_spgemm_plan_kwarg_and_sorted_epilogue():
    a, b, cd = _pair(seed=8)
    clear_plan_cache()
    plan = plan_spgemm(a, b, algorithm="hash", sorted_output=True)
    c = spgemm(a, b, plan=plan)
    assert c.sorted_cols
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3)
    cols, ip = np.asarray(c.indices), np.asarray(c.indptr)
    for i in range(c.n_rows):
        assert np.all(np.diff(cols[ip[i]:ip[i + 1]]) > 0)


def test_planned_bfs_and_triangles_match_unplanned():
    """The example's plan-cached loops give unchanged results."""
    from examples.graph_analytics import (multi_source_bfs,
                                          multi_source_bfs_masked,
                                          triangle_count)
    from repro.data.rmat import symmetrize
    clear_plan_cache()
    a = symmetrize(rmat_csr(6, 6, "G500", seed=2))
    ad = np.asarray(a.to_dense()).astype(np.int64)
    brute = int(np.trace(np.linalg.matrix_power(ad, 3)) // 6)
    assert triangle_count(a) == brute
    sources = [0, 5, 21]
    d_dense = np.asarray(multi_source_bfs(a, sources, n_hops=4))
    d_mask = np.asarray(multi_source_bfs_masked(a, sources, n_hops=4))
    assert np.array_equal(d_dense, d_mask)
    before = plan_cache_stats()
    d_again = np.asarray(multi_source_bfs_masked(a, sources, n_hops=4))
    after = plan_cache_stats()
    assert np.array_equal(d_mask, d_again)
    assert after["misses"] == before["misses"], \
        "repeat BFS must plan nothing new"
    assert after["hits"] > before["hits"]


def test_plan_cache_restore_refreshes_recency():
    """Re-storing an existing key at capacity must refresh its recency
    (pop-before-insert): the old in-place overwrite kept the key's stale
    dict position, so a just-refreshed plan was evicted as "least
    recent" by the very next store."""
    from repro.core import plan as plan_mod
    from repro.core.plan import cache_store, cache_lookup
    clear_plan_cache()
    old_cap = plan_mod.PLAN_CACHE_CAPACITY
    plan_mod.PLAN_CACHE_CAPACITY = 2
    try:
        cache_store(("spgemm", "k1"), "v1")
        cache_store(("spgemm", "k2"), "v2")
        cache_store(("spgemm", "k1"), "v1-refreshed")  # re-store at capacity
        cache_store(("spgemm", "k3"), "v3")            # must evict k2, not k1
        assert cache_lookup(("spgemm", "k1")) == "v1-refreshed"
        assert cache_lookup(("spgemm", "k3")) == "v3"
        assert cache_lookup(("spgemm", "k2")) is None  # the true LRU victim
    finally:
        plan_mod.PLAN_CACHE_CAPACITY = old_cap
        clear_plan_cache()
