"""Hash-table saturation regression (paper section 4, Fig. 7-8).

The hash accumulator's sensitive edge is the table boundary: the sizing
rule ``lowest_p2(min(N_col, max_row_flop) + 1)`` keeps the load factor
< 1 so linear probes terminate, but the *per-bin* sizes ride in as data
(scalar prefetch), so a schedule override can legally run a row at
**load factor 1.0** -- every slot occupied, the last insertion taking the
single remaining empty slot, every later probe terminating only because
its key is already resident.  The flush loop must then emit exactly
``table_size`` entries.  One row past the boundary, the natural sizing
must double the table.

Covered for the Pallas kernels (``spgemm_hash``, scalar and vectorized
probing -- at table size == CHUNK the vector path degenerates to a single
chunk, its own edge) and the jnp fallback (``spgemm_hash_jnp``), sorted
and unsorted output, plus the planner path that freezes per-bin sizes --
and, under ``jax.vmap``, the batched grid at the same boundaries: the
numeric kernel over a two-member value fleet, the symbolic kernel over
a two-member structure fleet (the saturated table is per-program
scratch: members must not observe each other's slots).

Values are dyadic so every comparison is exact (bitwise on the dense
view).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp  # noqa: E402

from repro.core import CSR, plan_spgemm, spgemm_hash_jnp  # noqa: E402
from repro.kernels.spgemm_hash import ops as hash_ops  # noqa: E402
from repro.kernels.spgemm_hash.kernel import CHUNK  # noqa: E402
from _fuzz import VALS  # noqa: E402


def _pair_with_row_flop(d: int):
    """(A, B) whose C row 0 has exactly ``d`` distinct columns (flop d)
    and row 1 the same ``d`` columns with flop ``2d`` (duplicates that
    must accumulate across an already-saturated table)."""
    a = CSR.from_numpy_coo([0, 1, 1], [0, 0, 1], [1.0, 1.0, 0.5], (2, 2))
    rows = np.concatenate([np.zeros(d, np.int64), np.ones(d, np.int64)])
    cols = np.concatenate([np.arange(d), np.arange(d)])
    vals = VALS[np.arange(2 * d) % len(VALS)]
    b = CSR.from_numpy_coo(rows, cols, vals, (2, d))
    return a, b


def _oracle(a: CSR, b: CSR) -> np.ndarray:
    return np.asarray(a.to_dense(), np.float64) @ \
        np.asarray(b.to_dense(), np.float64)


def _check(c: CSR, cd: np.ndarray, sorted_output: bool):
    assert np.array_equal(np.asarray(c.to_dense(), np.float64), cd)
    if sorted_output:
        cols, ip = np.asarray(c.indices), np.asarray(c.indptr)
        for i in range(c.n_rows):
            assert np.all(np.diff(cols[ip[i]:ip[i + 1]]) > 0), i


@pytest.mark.parametrize("sorted_output", (False, True))
@pytest.mark.parametrize("vector", (False, True))
def test_pallas_hash_load_factor_one(vector, sorted_output):
    """Forced per-bin table == distinct column count: load factor 1.0.

    The schedule override pins ``bin_tsize`` to exactly ``d = CHUNK``
    (the smallest admissible table), so row 0 fills every slot and row 1
    re-probes a full table for each duplicate.  The flush must emit all
    ``d`` entries per row and the values must be exact.
    """
    d = CHUNK                                     # 8: p2, vector-minimal
    a, b = _pair_with_row_flop(d)
    cd = _oracle(a, b)
    offsets = jnp.asarray([0, 2], jnp.int32)
    bin_tsize = jnp.asarray([d], jnp.int32)
    c = hash_ops.spgemm_hash(a, b, cap_c=2 * d, vector=vector,
                             table_size=d, schedule=(offsets, bin_tsize))
    assert not c.sorted_cols
    ip = np.asarray(c.indptr)
    assert ip[1] - ip[0] == d and ip[2] - ip[1] == d   # table fully flushed
    if sorted_output:
        c = c.sort_rows()
    _check(c, cd, sorted_output)


@pytest.mark.parametrize("sorted_output", (False, True))
@pytest.mark.parametrize("vector", (False, True))
def test_pallas_hash_one_past_fill_doubles_table(vector, sorted_output):
    """One row past the exact-fill point: d = CHUNK + 1 distinct columns.

    The natural sizing must choose the next power of two (2 * CHUNK) --
    the +1 in ``lowest_p2(min(N_col, flop) + 1)`` is what forbids load
    factor 1.0 without an override -- and the results stay exact.
    """
    d = CHUNK + 1
    a, b = _pair_with_row_flop(d)
    cd = _oracle(a, b)
    offsets, bin_tsize, table_size = hash_ops.hash_schedule(a, b, n_bins=1)
    assert table_size == 2 * CHUNK                 # doubled, not saturated
    assert int(np.asarray(bin_tsize)[0]) == 2 * CHUNK
    c = hash_ops.spgemm_hash(a, b, cap_c=2 * d, vector=vector,
                             table_size=table_size,
                             schedule=(offsets, bin_tsize))
    if sorted_output:
        c = c.sort_rows()
    _check(c, cd, sorted_output)


def _vmap_saturation_fleet(a, b, d, vector, table_size, schedule):
    """Run a two-member value fleet on the saturating structure under
    ``jax.vmap`` and return per-member ``(indptr, dense)`` stacks plus the
    kernel-counter delta.  The schedule override closes over the vmapped
    call, so the ``custom_vmap`` rule must broadcast it onto the batched
    grid.  The fleet shares its structure, so the symbolic kernel runs
    once, unbatched; :func:`test_batched_symbolic_load_factor_one_under_vmap`
    batches the structure instead."""
    import dataclasses

    import jax

    member_vals = jnp.stack([a.data, a.data * jnp.float32(2.0)])

    def one(v):
        c = hash_ops.spgemm_hash(dataclasses.replace(a, data=v), b,
                                 cap_c=2 * d, vector=vector,
                                 table_size=table_size, schedule=schedule)
        return c.indptr, c.to_dense()

    hash_ops.reset_kernel_calls()
    ips, denses = jax.vmap(one)(member_vals)
    return member_vals, np.asarray(ips), np.asarray(denses), \
        hash_ops.kernel_call_counts()


@pytest.mark.parametrize("vector", (False, True))
def test_batched_grid_load_factor_one_under_vmap(vector):
    """The load-factor-1.0 pin lifted onto the batched-grid kernel: every
    vmapped member runs row 0 at a completely full table and row 1
    re-probing it for each duplicate, and must flush exactly ``d`` slots
    with exact values -- per member."""
    d = CHUNK
    a, b = _pair_with_row_flop(d)
    offsets = jnp.asarray([0, 2], jnp.int32)
    bin_tsize = jnp.asarray([d], jnp.int32)
    member_vals, ips, denses, counts = _vmap_saturation_fleet(
        a, b, d, vector, table_size=d, schedule=(offsets, bin_tsize))
    assert counts["symbolic"] > 0 and counts["batched_numeric"] > 0
    for e in range(2):
        assert ips[e, 1] - ips[e, 0] == d and ips[e, 2] - ips[e, 1] == d
        a_e = CSR(a.indptr, a.indices, member_vals[e], a.nnz, a.shape,
                  sorted_cols=a.sorted_cols)
        assert np.array_equal(denses[e].astype(np.float64),
                              _oracle(a_e, b)), e


@pytest.mark.parametrize("vector", (False, True))
def test_batched_grid_one_past_fill_doubles_table_under_vmap(vector):
    """One past exact fill under ``vmap``: the natural sizing's doubled
    table (2 * CHUNK) rides into the batched grid as data and every
    member stays exact."""
    d = CHUNK + 1
    a, b = _pair_with_row_flop(d)
    offsets, bin_tsize, table_size = hash_ops.hash_schedule(a, b, n_bins=1)
    assert table_size == 2 * CHUNK                 # doubled, not saturated
    member_vals, ips, denses, counts = _vmap_saturation_fleet(
        a, b, d, vector, table_size=table_size,
        schedule=(offsets, bin_tsize))
    assert counts["symbolic"] > 0 and counts["batched_numeric"] > 0
    for e in range(2):
        assert ips[e, 1] - ips[e, 0] == d and ips[e, 2] - ips[e, 1] == d
        a_e = CSR(a.indptr, a.indices, member_vals[e], a.nnz, a.shape,
                  sorted_cols=a.sorted_cols)
        assert np.array_equal(denses[e].astype(np.float64),
                              _oracle(a_e, b)), e


@pytest.mark.parametrize("vector", (False, True))
def test_batched_symbolic_load_factor_one_under_vmap(vector):
    """A fleet of two A *structures* (index sets) under ``vmap`` with no
    ``indptr_c``: the batched symbolic kernel counts every member's rows
    at a pinned load-factor-1.0 table, per-member strides keep the
    members apart, and the trailing row left out of the schedule is
    counted zero by the kernel's closing pad.

    B rows 0 and 1 hold the two halves of ``d`` columns, B row 2 all of
    them.  Member 0 takes A rows ``[2] [0 1] []``, member 1 ``[0] [2 0] []``
    -- row counts ``(d, d, 0)`` and ``(d/2, d, 0)``; each row that reaches
    ``d`` fills the table.
    """
    import dataclasses

    import jax

    d = CHUNK
    h = d // 2
    rows = np.concatenate([np.zeros(h), np.ones(h), np.full(d, 2)])
    cols = np.concatenate([np.arange(h), np.arange(h, d), np.arange(d)])
    vals = VALS[np.arange(2 * d) % len(VALS)]
    b = CSR.from_numpy_coo(rows.astype(np.int64), cols, vals, (3, d))
    a = CSR.from_numpy_coo([0, 1, 1], [2, 0, 1], [1.0, 1.0, 0.5], (3, 3))
    idx = jnp.stack([a.indices, a.indices.at[:3].set(jnp.asarray([0, 2, 0]))])
    schedule = (jnp.asarray([0, 2], jnp.int32),     # row 2: past the bin
                jnp.asarray([d], jnp.int32))

    def one(ix):
        c = hash_ops.spgemm_hash(dataclasses.replace(a, indices=ix), b,
                                 cap_c=2 * d, vector=vector, table_size=d,
                                 schedule=schedule)
        return c.indptr, c.to_dense()

    hash_ops.reset_kernel_calls()
    ips, denses = jax.vmap(one)(idx)
    counts = hash_ops.kernel_call_counts()
    assert counts["batched_symbolic"] > 0
    assert np.array_equal(np.diff(np.asarray(ips), axis=1),
                          [[d, d, 0], [h, d, 0]])
    for e in range(2):
        a_e = dataclasses.replace(a, indices=idx[e])
        assert np.array_equal(np.asarray(denses[e], np.float64),
                              _oracle(a_e, b)), e


@pytest.mark.parametrize("sorted_output", (False, True))
@pytest.mark.parametrize("d", (CHUNK, CHUNK + 1))
def test_hash_jnp_at_fill_boundary(d, sorted_output):
    """The jnp fallback on the same saturating structures, both sides of
    the boundary, sorted and unsorted -- contract-equivalent results."""
    a, b = _pair_with_row_flop(d)
    cd = _oracle(a, b)
    c = spgemm_hash_jnp(a, b, cap_c=2 * d)
    assert not c.sorted_cols
    if sorted_output:
        c = c.sort_rows()
    _check(c, cd, sorted_output)


def test_planned_hash_at_natural_max_load():
    """Through the planner: N_col < flop pins the table at
    ``lowest_p2(N_col + 1)``, the fullest load the natural sizing admits
    (``N_col / lowest_p2(N_col + 1)``; 1 - 1/16 here).  The frozen
    per-bin sizes must survive plan -> execute with exact results."""
    n = 15                                         # table = 16, load 15/16
    a = CSR.from_numpy_coo([0, 0], [0, 1], [1.0, 0.5], (1, 2))
    rows = np.concatenate([np.zeros(n, np.int64), np.ones(n, np.int64)])
    cols = np.concatenate([np.arange(n), np.arange(n)])
    vals = VALS[np.arange(2 * n) % len(VALS)]
    b = CSR.from_numpy_coo(rows, cols, vals, (2, n))
    cd = _oracle(a, b)
    plan = plan_spgemm(a, b, algorithm="hash", cache=False)
    assert plan.table_size == 16
    assert plan.nnz_c == n
    c = plan.execute(a, b)
    _check(c, cd, sorted_output=False)
    # row flop is 2n = 30 > n: the distinct count saturates at N_col
    ip = np.asarray(c.indptr)
    assert ip[1] - ip[0] == n
