"""Light-weight load-balanced scheduling (paper section 4.1, Fig. 6).

``RowsToThreads``: count flop per output row, prefix-sum, then find each
worker's start row with a binary search (``LOWBND``).  On KNL the workers
were OpenMP threads under *static* scheduling; here the same partition is
used three ways:

  1. Pallas grid programs: bin b processes rows ``offset[b]:offset[b+1]``
     (fed through scalar prefetch);
  2. mesh chips in distributed SpGEMM (equal-flop row partitions per chip);
  3. the serving engine's batch scheduler (equal-token request bins).

The paper's argument -- static scheduling is cheap but needs up-front
balancing -- is *structural* on TPU: a Pallas grid is static by construction,
so this module is what makes static assignment viable, exactly as on KNL.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .formats import CSR

#: largest value an int32 prefix sum may reach without wrapping.
_I32_MAX = 2**31 - 1


def _acc_dtype():
    """Accumulator dtype for flop prefix sums: int64 when x64 is enabled
    (overflow becomes impossible), int32 otherwise (exact at proxy scale,
    DESIGN.md section 9, guarded by :func:`guard_i32_flop`)."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def guard_i32_flop(flop, what: str = "rows_to_bins"):
    """Fail loudly instead of mis-binning on int32 prefix-sum overflow.

    The quantity that must fit int32 is the *total* flop (the last entry
    of the prefix sum; :func:`rows_to_bins` forms its partition targets
    without multiplying the total).  Three regimes:

      * x64 enabled: accumulation is promoted to int64 -- nothing to guard;
      * concrete ``flop`` (the planner's eager path): check exactly in
        numpy int64 and raise ``OverflowError``;
      * traced without x64 (e.g. inside ``make_schedule``'s jit): the check
        cannot run -- callers that may see >2^31 total flop must plan
        eagerly (``core.plan``) or enable x64.
    """
    if jax.config.jax_enable_x64:
        return
    if isinstance(flop, jax.core.Tracer):
        return
    total = int(np.asarray(flop, dtype=np.int64).sum())
    if total > _I32_MAX:
        raise OverflowError(
            f"{what}: total flop {total} overflows the int32 prefix sum; "
            f"enable jax_enable_x64 or shard the product (DESIGN.md "
            f"section 9)")


def flops_per_row(a: CSR, b: CSR) -> jax.Array:
    """flop[i] = sum_{k in a_i*} nnz(b_k*)  -- Fig. 6 step 1.

    This is both the load-balance weight and the hash-table sizing bound
    (Fig. 7 lines 5-12): row i of C touches at most flop[i] distinct columns.
    """
    rnz = (b.indptr[a.indices + 1] - b.indptr[a.indices]).astype(jnp.int32)
    rnz = jnp.where(a.valid_mask(), rnz, 0)
    return jax.ops.segment_sum(rnz, a.row_ids(), num_segments=a.n_rows)


def masked_row_bound(flop: jax.Array, mask: CSR,
                     complement: bool = False) -> jax.Array:
    """Per-row nnz(C) upper bound under a structural mask (DESIGN.md
    section 7): a non-complemented mask caps row i of C at nnz(mask_i*), a
    complemented mask at ``n_cols - nnz(mask_i*)``.  This is the capacity
    math the symbolic phase and the launcher use when a mask is present --
    the mask shrinks the *static* allocation, not just the dynamic nnz.
    """
    mrow = mask.row_nnz().astype(flop.dtype)
    lim = (jnp.int32(mask.n_cols) - mrow) if complement else mrow
    return jnp.minimum(flop, lim)


def prefix_sum(x: jax.Array) -> jax.Array:
    """Exclusive-then-inclusive prefix sum, (n+1,): ps[0]=0, ps[-1]=total."""
    return jnp.concatenate([jnp.zeros((1,), x.dtype),
                            jnp.cumsum(x, dtype=x.dtype)])


def lowbnd(vec: jax.Array, value: jax.Array) -> jax.Array:
    """Minimum id such that vec[id] >= value (Fig. 6 line 14)."""
    return jnp.searchsorted(vec, value, side="left").astype(jnp.int32)


def rows_to_bins(flop: jax.Array, n_bins: int) -> jax.Array:
    """Fig. 6 steps 2: equal-flop partition; returns offsets (n_bins+1,).

    Invariants (property-tested):
      * offsets[0] == 0, offsets[-1] == n_rows, monotone non-decreasing;
      * every bin's flop <= ceil(total/n_bins) + max_row_flop.
    """
    m = flop.shape[0]
    # Exact arithmetic without float64: int32 accumulation is exact below
    # 2^31 (the proxy-scale regime, DESIGN.md section 9); the guard raises
    # on concrete inputs that would wrap, and x64 promotes to int64.
    guard_i32_flop(flop, "rows_to_bins")
    acc = _acc_dtype()
    ps = prefix_sum(flop.astype(acc))
    total = ps[-1]
    k = jnp.arange(1, n_bins, dtype=acc)
    # floor(total * k / n_bins) without forming total * k, which wraps
    # int32 long before the total does (400M flop at G500 scale 16)
    targets = (total // n_bins) * k + ((total % n_bins) * k) // n_bins
    # ps is over row *boundaries*; bin b starts at the first row whose
    # cumulative flop reaches target b.
    cuts = lowbnd(ps[1:], targets + 1)
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), cuts.astype(jnp.int32),
        jnp.full((1,), m, jnp.int32)])
    return jnp.minimum(offsets, m)


def bin_row_assignment(offsets: jax.Array, n_rows: int) -> jax.Array:
    """Inverse view: bin id of every row, (n_rows,)."""
    r = jnp.arange(n_rows, dtype=jnp.int32)
    return (jnp.searchsorted(offsets, r, side="right") - 1).astype(jnp.int32)


def bin_flop(flop: jax.Array, offsets: jax.Array) -> jax.Array:
    """Total flop per bin (n_bins,) -- the balance metric."""
    guard_i32_flop(flop, "bin_flop")
    ps = prefix_sum(flop.astype(_acc_dtype()))
    return ps[offsets[1:]] - ps[offsets[:-1]]


def max_flop_per_bin_row(flop: jax.Array, offsets: jax.Array) -> jax.Array:
    """Per-bin max row flop (n_bins,) -- Fig. 7 lines 5-12: each worker sizes
    its private hash table once, to the max flop of any row in its bin, and
    reuses it for every row (the paper's thread-private allocation, C5)."""
    n_bins = offsets.shape[0] - 1
    bins = bin_row_assignment(offsets, flop.shape[0])
    return jax.ops.segment_max(flop, bins, num_segments=n_bins)


def make_schedule_eager(a: CSR, b: CSR, n_bins: int):
    """Un-jitted Fig. 6 pipeline -- the single source of truth.

    Returns (flop, offsets, bin_table_size); ``bin_table_size`` is the
    per-bin hash-table bound of Fig. 7 line 10: ``min(N_col,
    max-row-flop-in-bin)`` (power-of-two rounding happens where the static
    size is needed: kernel instantiation / :func:`bin_table_sizes`).

    The planner calls this form directly: on concrete inputs the int32
    overflow guard inside :func:`rows_to_bins` actually fires (under
    :func:`make_schedule`'s jit the values are tracers and it cannot).
    """
    flop = flops_per_row(a, b)
    offsets = rows_to_bins(flop, n_bins)
    tsize = jnp.minimum(max_flop_per_bin_row(flop, offsets),
                        jnp.int32(b.n_cols))
    return flop, offsets, tsize


make_schedule = partial(jax.jit, static_argnames=("n_bins",))(
    make_schedule_eager)
make_schedule.__doc__ = "Jitted :func:`make_schedule_eager`."


def equal_weight_partition(weights, n_parts: int) -> np.ndarray:
    """Host-side equal-weight contiguous partition (Fig. 6 at mesh scale).

    The exact int64 twin of :func:`rows_to_bins` for *shard* boundaries:
    mesh layout is static, so the partition is computed eagerly in numpy
    (no overflow guard needed -- int64 accumulation is always exact here).
    Returns ``row_starts`` of shape ``(n_parts + 1,)`` with the same
    invariants as ``rows_to_bins``: starts[0] == 0, starts[-1] == n_rows,
    monotone, and every part's weight <= ceil(total/n_parts) + max weight.

    Degenerate inputs rebalance instead of collapsing: an all-zero weight
    vector has no flop to balance, so rows are split evenly (the old
    zero-total prefix sent every ``searchsorted`` cut to ``n``, handing
    part 0 the whole matrix and every other part zero rows).  With
    ``n_parts > n`` some parts are necessarily empty; the cuts spread
    them across the range rather than piling the empties at the tail.
    """
    w = np.asarray(weights, dtype=np.int64)
    assert w.ndim == 1, w.shape
    assert n_parts >= 1, n_parts
    n = w.shape[0]
    ps = np.concatenate([np.zeros(1, np.int64), np.cumsum(w, dtype=np.int64)])
    total = ps[-1]
    if total == 0:
        return (n * np.arange(n_parts + 1, dtype=np.int64)) // n_parts
    targets = (total * np.arange(1, n_parts, dtype=np.int64)) // n_parts
    cuts = np.searchsorted(ps[1:], targets + 1, side="left")
    starts = np.concatenate([np.zeros(1, np.int64), cuts,
                             np.full(1, n, np.int64)])
    return np.minimum(starts, n)


def chained_flop_bound(row_nnz_prev: jax.Array, b: CSR) -> jax.Array:
    """A-priori per-row flop bound for the *next* product of a chain.

    Stage ``k+1`` of a chain multiplies the (not-yet-materialized)
    intermediate ``C_k`` by the next operand ``B``; before ``C_k``'s column
    structure exists, the only exact inputs are the previous stage's
    symbolic counts ``row_nnz_prev = nnz(c_k,i*)``.  Row ``i`` of stage
    ``k+1`` touches at most one B row per intermediate entry, so

        flop_{k+1}[i] <= nnz(c_k,i*) * max_j nnz(b_j*)

    This is the chained capacity math of DESIGN.md section 12: it bounds
    the next stage's expansion buffer and hash-table sizes from recorded
    plan state alone, and it is what :func:`repro.core.recipe.recommend`'s
    ``a_row_nnz`` hook consumes for mid-chain algorithm choice.  Once the
    chain planner materializes the intermediate, the exact
    :func:`flops_per_row` replaces this bound.
    """
    bmax = jnp.max(b.row_nnz()).astype(jnp.int32) if b.n_rows else \
        jnp.int32(0)
    return row_nnz_prev.astype(jnp.int32) * bmax


def lowest_p2(x: int) -> int:
    """Static helper: minimum 2^n >= x (Fig. 7 line 12)."""
    p = 1
    while p < x:
        p *= 2
    return p


def lowest_p2_arr(x: jax.Array) -> jax.Array:
    """Traceable :func:`lowest_p2` over an int32 array.

    Exponent via float32 log2 with an exactness patch-up (float rounding can
    land one power low); exact for values < 2^24, far above any table size a
    VMEM scratch can hold.
    """
    x = jnp.maximum(x.astype(jnp.int32), 1)
    e = jnp.ceil(jnp.log2(x.astype(jnp.float32))).astype(jnp.int32)
    p = jnp.left_shift(jnp.int32(1), jnp.clip(e, 0, 30))
    return jnp.where(p < x, p * 2, p)


def bin_table_sizes(tsize: jax.Array, n_cols: int, table_size: int,
                    floor: int = 1) -> jax.Array:
    """Per-bin hash-table sizes (Fig. 7 lines 9-12), padded to powers of two.

    ``tsize`` is ``make_schedule``'s per-bin max-row-flop bound; each bin's
    table is the lowest power of two >= ``min(tsize_b, n_cols) + 1`` (the +1
    keeps the load factor < 1 so linear probes terminate), clamped into
    ``[floor, table_size]`` where ``table_size`` is the static scratch
    allocation (the global bin max) and ``floor`` is the vector-probe chunk
    width when chunked probing is on.  Traceable, so plans can be built
    under an outer jit as long as ``table_size`` is pinned.
    """
    t = jnp.minimum(tsize.astype(jnp.int32), jnp.int32(n_cols)) + 1
    return jnp.clip(lowest_p2_arr(t), jnp.int32(max(floor, 1)),
                    jnp.int32(table_size))


#: default propagation-blocking bucket budget: the average number of
#: partial products a column bucket should hold.  Sized so one bucket's
#: gather indices + products fit comfortably in VMEM/cache during the
#: merge (the paper's "bin fits in L2" rule, DESIGN.md section 18).
PB_BUCKET_BUDGET = 2048


def pb_bucket_layout(n_cols: int, n_buckets: int | None = None, *,
                     total_flop: int | None = None,
                     budget: int = PB_BUCKET_BUDGET) -> tuple:
    """Column-bucket layout for propagation-blocking SpGEMM.

    Returns ``(bucket_w, n_buckets)`` with ``bucket_w`` a power of two:
    bucket of column ``c`` is ``c // bucket_w`` (one shift -- the radix
    step), and ``n_buckets = ceil(n_cols / bucket_w)`` buckets cover
    ``[0, n_cols)`` contiguously.

    With ``n_buckets=None`` the count is derived from ``total_flop``:
    enough buckets that the *average* bucket holds <= ``budget`` partial
    products (never more buckets than columns).  An explicit request is
    honored up to p2 rounding -- the returned count can be smaller when
    rounding ``bucket_w`` up swallows trailing buckets.
    """
    assert n_cols >= 1, n_cols
    if n_buckets is None:
        want = max(1, -(-(total_flop or 0) // budget))
        n_buckets = min(want, n_cols)
    n_buckets = max(1, min(int(n_buckets), n_cols))
    bucket_w = lowest_p2(-(-n_cols // n_buckets))
    return bucket_w, -(-n_cols // bucket_w)
