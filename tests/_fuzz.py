"""Reusable structure generators + property-based strategies for the
differential SpGEMM suites.

One home for the CSR/product generators that used to live inline in
``test_differential.py``, so every fuzz layer (single products, batched
fleets, future suites) draws from the same structure space: rectangular
shapes, empty rows/columns, empty matrices, duplicate-free sorted and
*unsorted* CSRs, dyadic values.

The pure-numpy helpers in the first half (``VALS``, :func:`rand_dense`,
:func:`csr_of`, :func:`scramble_rows`, :func:`member_value_fleet`, the
trace-context runner :func:`run_planned_hash_in_context`) import
unconditionally -- the deterministic grids of ``test_differential.py`` /
``test_batch.py`` / ``test_hash_saturation.py`` /
``test_trace_contexts.py`` share them with no optional dependency.  The
hypothesis *strategies* in the second half exist only when the optional
``hypothesis`` extra is installed; consumers guard exactly like the old
inline layers did::

    try:
        from _fuzz import product_case      # ImportError without the extra
        HAVE_HYPOTHESIS = True
    except ImportError:
        HAVE_HYPOTHESIS = False

Values are drawn from dyadic rationals ({0.5, 1.0, 1.5, 2.0}) so fp32
products and sums are exact and every comparison can be bitwise; they are
also strictly positive, which sidesteps the dense-oracle explicit-zero
caveat documented on ``repro.core.spgemm.spgemm_dense``.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core import CSR

#: dyadic, strictly positive: exact fp32 arithmetic, no explicit zeros.
VALS = np.array([0.5, 1.0, 1.5, 2.0], np.float32)

SEMIRINGS = ("plus_times", "boolean", "min_plus", "plus_first")
ALGOS = ("esc", "heap", "hash", "hash_jnp")


def rand_dense(m: int, n: int, density: float, seed: int) -> np.ndarray:
    """Dense dyadic-valued matrix with the given fill fraction."""
    rng = np.random.default_rng(seed)
    d = rng.choice(VALS, size=(m, n))
    return np.where(rng.random((m, n)) < density, d, 0.0).astype(np.float32)


def csr_of(d: np.ndarray, cap: int | None = None) -> CSR:
    """Sorted, duplicate-free CSR of a dense matrix."""
    r, c = np.nonzero(d)
    return CSR.from_numpy_coo(r, c, d[r, c], d.shape, cap=cap)


def block_clustered_dense(gm: int, gn: int, bm: int, bn: int,
                          density: float, seed: int) -> np.ndarray:
    """Block-clustered dyadic dense matrix: a ``gm x gn`` occupancy grid
    of fully dense ``bm x bn`` tiles -- the structure the BCSR recipe
    routing keys on.  Dyadic values keep every comparison bitwise."""
    rng = np.random.default_rng(seed)
    occ = (rng.random((gm, gn)) < density).astype(np.float32)
    vals = rng.choice(VALS, size=(gm * bm, gn * bn)).astype(np.float32)
    return np.kron(occ, np.ones((bm, bn), np.float32)) * vals


def member_value_fleet(ad: np.ndarray, n_members: int, seed: int) -> np.ndarray:
    """``(n_members, nnz)`` dyadic value stacks on ``ad``'s fixed pattern.

    The traced-context suites vmap one structure-frozen plan over these
    per-member values; row 0 is ``ad``'s own values so member 0 doubles
    as the eager-path case.
    """
    rng = np.random.default_rng(seed)
    nnz = int(np.count_nonzero(ad))
    vals = rng.choice(VALS, size=(n_members, nnz)).astype(np.float32)
    if nnz:
        r, c = np.nonzero(ad)
        vals[0] = ad[r, c]
    return vals


def run_planned_hash_in_context(a: CSR, b: CSR, member_vals: np.ndarray,
                                context: str, vector: bool = False):
    """Execute one structure-frozen hash plan inside a trace context.

    Plans ``a @ b`` once with the real Pallas hash kernel, then executes
    it over ``member_vals`` -- a ``(E, nnz_a)`` stack of value fleets on
    A's fixed sparsity pattern -- inside the requested context:

      * ``"vmap"``: ``jax.vmap`` of the plan's execute over member values
        (dispatches the batched-grid kernel via its ``custom_vmap`` rule);
      * ``"shard_map"``: a one-device in-process ``shard_map`` whose body
        runs the plan's execute per member (the plain kernel traces
        inside the SPMD body);
      * ``"both"``: the ``shard_map`` body vmaps over the member axis.

    Returns ``(dense, counts)``: the ``(E, m, n)`` dense results and the
    kernel-call counter delta, so callers can assert the Pallas kernel
    (not the jnp twin) was staged.  Dyadic values make every comparison
    against a per-product-rounding oracle bitwise despite the kernel's
    FMA accumulation (see ``repro.kernels.spgemm_hash.ops``).
    """
    import dataclasses
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import plan_spgemm
    from repro.kernels.spgemm_hash import ops as hash_ops

    algorithm = "hash_vector" if vector else "hash"
    plan = plan_spgemm(a, b, algorithm=algorithm)
    e = member_vals.shape[0]
    pad = a.cap - member_vals.shape[1]
    vals = np.concatenate(
        [member_vals, np.zeros((e, pad), np.float32)], axis=1) \
        if pad else member_vals
    vals = jnp.asarray(vals)

    def one(v):
        return plan.execute(dataclasses.replace(a, data=v), b).to_dense()

    hash_ops.reset_kernel_calls()
    before = hash_ops.kernel_call_counts()
    if context == "vmap":
        dense = jax.vmap(one)(vals)
    else:
        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
        if context == "shard_map":
            body = lambda v: jnp.stack([one(v[i]) for i in range(e)])
        elif context == "both":
            body = lambda v: jax.vmap(one)(v)
        else:
            raise ValueError(f"unknown trace context {context!r}")
        # check_vma=False matches the production executors in
        # core.distributed: custom_vmap_call has no replication rule
        dense = jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False)(vals)
    counts = {k: v - before[k]
              for k, v in hash_ops.kernel_call_counts().items()}
    return np.asarray(dense), counts


def scramble_rows(a: CSR) -> CSR:
    """Unsorted twin: reverse each row's entries, flag ``sorted_cols=False``.

    Deterministic (no RNG), duplicate-free by construction, and the dense
    view is unchanged -- the canonical way every suite builds the
    "Table 1 unsorted input" case.
    """
    ip = np.asarray(a.indptr)
    ind = np.asarray(a.indices).copy()
    dat = np.asarray(a.data).copy()
    for i in range(a.n_rows):
        ind[ip[i]:ip[i + 1]] = ind[ip[i]:ip[i + 1]][::-1]
        dat[ip[i]:ip[i + 1]] = dat[ip[i]:ip[i + 1]][::-1]
    return CSR(jnp.asarray(ip), jnp.asarray(ind), jnp.asarray(dat),
               a.nnz, a.shape, sorted_cols=False)


PLAN_PERTURBATIONS = ("cap_c", "bin_tsize")


def perturb_plan(plan, which: str):
    """A structurally-broken twin of a frozen hash :class:`SpGEMMPlan`.

    The layer-1 verifier (:func:`repro.verify.check_plan_vcs`) must
    *reject* every twin this produces and keep passing the untouched
    plan -- the differential contract of ``tests/test_verify.py``:

      * ``"cap_c"``: output capacity dropped below the planned exact
        ``nnz_c`` (breaks ``store-capacity`` / ``nnz-consistent``);
      * ``"bin_tsize"``: every per-bin hash table halved -- now either
        under the kernel's CHUNK floor (``table-p2-range``) or too small
        for its bin's worst row (``probe-termination`` /
        ``flush-bound``).

    Returns a new frozen plan; the input is never mutated.
    """
    import dataclasses
    if which == "cap_c":
        bad = max(int(plan.nnz_c) - 1, 0)
        return dataclasses.replace(plan, cap_c=bad)
    if which == "bin_tsize":
        assert plan.bin_tsize is not None, "perturbation needs a hash plan"
        halved = jnp.maximum(jnp.asarray(plan.bin_tsize) // 2, 1)
        return dataclasses.replace(plan, bin_tsize=halved)
    raise ValueError(f"unknown plan perturbation {which!r}")


# ---------------------------------------------------------------------------
# Hypothesis strategies (optional extra; absent => the names don't exist
# and `from _fuzz import product_case` raises ImportError, which is the
# guard every consumer already uses)
# ---------------------------------------------------------------------------

try:
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    #: dims drawn from a tiny fixed set so examples share compiled programs.
    DIMS = st.sampled_from((3, 5, 8))
    DENSITIES = st.sampled_from((0.0, 0.2, 0.5, 0.9))

    @st.composite
    def dense_with_structure(draw, m: int, n: int, seed: int) -> np.ndarray:
        """Dense matrix with optionally-forced empty rows/columns."""
        d = rand_dense(m, n, draw(DENSITIES), seed)
        if draw(st.booleans()) and m > 1:      # force some empty rows
            kill = draw(st.sets(st.integers(0, m - 1), max_size=m // 2))
            for i in kill:
                d[i, :] = 0.0
        if draw(st.booleans()) and n > 1:      # force some empty columns
            kill = draw(st.sets(st.integers(0, n - 1), max_size=n // 2))
            for j in kill:
                d[:, j] = 0.0
        return d

    @st.composite
    def csr_case(draw, m: int | None = None, n: int | None = None,
                 allow_unsorted: bool = True):
        """One CSR plus its dense view: ``(a, ad)``.

        Rectangular by default (independent row/col dims), possibly with
        empty rows/cols or fully empty, possibly row-scrambled unsorted.
        """
        m = draw(DIMS) if m is None else m
        n = draw(DIMS) if n is None else n
        seed = draw(st.integers(0, 2**16))
        ad = draw(dense_with_structure(m, n, seed))
        a = csr_of(ad)
        if allow_unsorted and draw(st.booleans()):
            a = scramble_rows(a)
        return a, ad

    @st.composite
    def product_case(draw):
        """One product request: ``(ad, bd, md, complement, semiring, algo)``.

        The single-product differential layer's case shape (dense operands
        + optional dense mask + semantic fields); the consumer builds CSRs
        and compares against its oracle.
        """
        m, k, n = draw(DIMS), draw(DIMS), draw(DIMS)
        seed = draw(st.integers(0, 2**16))
        density = draw(DENSITIES)
        ad = rand_dense(m, k, density, seed)
        bd = rand_dense(k, n, density, seed + 1)
        masked = draw(st.booleans())
        md = rand_dense(m, n, 0.5, seed + 2) if masked else None
        complement = draw(st.booleans()) if masked else False
        semiring = draw(st.sampled_from(SEMIRINGS))
        algo = draw(st.sampled_from(ALGOS))
        return ad, bd, md, complement, semiring, algo

    @st.composite
    def traced_context_case(draw, max_members: int = 3):
        """A planned-product-under-trace-context case:
        ``(ad, bd, member_vals, context)``.

        ``ad``/``bd`` fix one product structure; ``member_vals`` is an
        ``(E, nnz_a)`` dyadic value stack on A's pattern (row 0 = ``ad``'s
        own values); ``context`` picks where the structure-frozen plan
        executes: under ``vmap``, inside a ``shard_map`` body, or both
        nested.  Consumed by :func:`run_planned_hash_in_context`.
        """
        m, k, n = draw(DIMS), draw(DIMS), draw(DIMS)
        seed = draw(st.integers(0, 2**16))
        ad = draw(dense_with_structure(m, k, seed))
        bd = rand_dense(k, n, draw(DENSITIES), seed + 1)
        context = draw(st.sampled_from(("vmap", "shard_map", "both")))
        e = draw(st.integers(2, max_members))
        member_vals = member_value_fleet(ad, e, draw(st.integers(0, 2**16)))
        vector = draw(st.booleans())
        return ad, bd, member_vals, context, vector

    #: tile dims for the BCSR strategy (tiny, so examples share programs)
    BLOCK_DIMS = st.sampled_from((1, 2, 4))

    @st.composite
    def bcsr_case(draw):
        """One block product: ``(ad, bd, (bm, bk, bn))``.

        A tiles ``(bm, bk)``, B tiles ``(bk, bn)`` on independent
        occupancy grids; tiles are optionally thinned below full density
        (partially-filled blocks), and either operand may be all-zero.
        The consumer re-blocks with ``csr_to_bcsr`` / ``BCSR.from_dense``
        and compares the planned block product against the scipy BSR
        oracle.
        """
        bm, bk, bn = draw(BLOCK_DIMS), draw(BLOCK_DIMS), draw(BLOCK_DIMS)
        gm, gk, gn = (draw(st.integers(1, 4)) for _ in range(3))
        seed = draw(st.integers(0, 2**16))
        ad = block_clustered_dense(gm, gk, bm, bk, draw(DENSITIES), seed)
        bd = block_clustered_dense(gk, gn, bk, bn, draw(DENSITIES),
                                   seed + 1)
        if draw(st.booleans()):     # partially-filled A tiles
            rng = np.random.default_rng(seed + 2)
            ad = ad * (rng.random(ad.shape) < 0.7)
        if draw(st.booleans()):     # partially-filled B tiles
            rng = np.random.default_rng(seed + 3)
            bd = bd * (rng.random(bd.shape) < 0.7)
        return ad.astype(np.float32), bd.astype(np.float32), (bm, bk, bn)

    @st.composite
    def perturbed_plan_case(draw):
        """A hash-plannable product plus a schedule perturbation kind:
        ``(ad, bd, which)``.  The consumer plans ``hash``, applies
        :func:`perturb_plan`, and asserts the layer-1 VCs reject the
        twin while the untouched plan keeps passing."""
        m, k, n = draw(DIMS), draw(DIMS), draw(DIMS)
        seed = draw(st.integers(0, 2**16))
        # nonzero density: a perturbable plan needs at least one product
        ad = rand_dense(m, k, draw(st.sampled_from((0.2, 0.5, 0.9))), seed)
        bd = rand_dense(k, n, draw(st.sampled_from((0.5, 0.9))), seed + 1)
        which = draw(st.sampled_from(PLAN_PERTURBATIONS))
        return ad, bd, which

    @st.composite
    def batch_case(draw, min_products: int = 2, max_products: int = 6):
        """A fleet of CSR products for ``spgemm_batch`` fuzzing.

        Returns ``(pairs, semiring)`` where ``pairs`` is a list of
        ``(A_i, B_i)`` CSRs: heterogeneous rectangular shapes and
        densities, empty rows/cols, sorted/unsorted members -- optionally
        all sharing one B (the shared-operand fleet shape, e.g.
        per-expert dispatch against one feature matrix).
        """
        n_products = draw(st.integers(min_products, max_products))
        semiring = draw(st.sampled_from(SEMIRINGS))
        share_b = draw(st.booleans())
        pairs = []
        if share_b:
            k, n = draw(DIMS), draw(DIMS)
            b, _ = draw(csr_case(m=k, n=n))
            for _ in range(n_products):
                a, _ = draw(csr_case(n=k))
                pairs.append((a, b))
        else:
            for _ in range(n_products):
                k = draw(DIMS)
                a, _ = draw(csr_case(n=k))
                b, _ = draw(csr_case(m=k))
                pairs.append((a, b))
        return pairs, semiring

    @st.composite
    def degenerate_partition_case(draw):
        """A ``(weights, n_parts)`` pair biased toward the partition
        degeneracies: all-zero weights, zero-weight spans, single rows,
        and ``n_parts > n_rows``.  The consumer checks the
        ``equal_weight_partition`` invariants (cover, monotone, balance,
        and no all-rows-in-part-0 collapse on zero totals)."""
        shape = draw(st.sampled_from(("zeros", "spans", "random", "tiny")))
        if shape == "zeros":
            n = draw(st.integers(1, 16))
            w = np.zeros(n, np.int64)
        elif shape == "tiny":
            n = draw(st.integers(1, 3))
            w = np.asarray(draw(st.lists(st.integers(0, 4),
                                         min_size=n, max_size=n)), np.int64)
        else:
            n = draw(st.integers(4, 16))
            rng = np.random.default_rng(draw(st.integers(0, 2**16)))
            w = rng.integers(0, 9, n).astype(np.int64)
            if shape == "spans":       # zero out a contiguous span
                i = draw(st.integers(0, n - 1))
                j = draw(st.integers(i, n))
                w[i:j] = 0
        n_parts = draw(st.sampled_from((1, 2, 3, 8, 32)))
        return w, n_parts

    @st.composite
    def pb_case(draw):
        """A low-compression-factor product for the propagation-blocking
        differential layer: ``(ad, bd, n_buckets)``.

        Wide-ish B with thin rows keeps flop / nnz(C) near 1 (few
        collisions to merge -- PB's home regime); the strategy still mixes
        in denser draws so the bucket merge sees real duplicate columns.
        """
        m, k = draw(DIMS), draw(DIMS)
        n = draw(st.sampled_from((8, 16, 32)))
        seed = draw(st.integers(0, 2**16))
        ad = draw(dense_with_structure(m, k, seed))
        bd = rand_dense(k, n, draw(st.sampled_from((0.05, 0.1, 0.3))),
                        seed + 1)
        n_buckets = draw(st.sampled_from((1, 2, 4)))
        return ad, bd, n_buckets
