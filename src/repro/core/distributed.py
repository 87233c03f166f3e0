"""Plan-aware distributed SpGEMM/SpMM over a device mesh (DESIGN.md §11).

The paper's two-level load-balance story (equal-flop partition across
threads, then per-thread hash/heap kernels) is lifted one level further:
rows are assigned to *chips* by the same equal-flop prefix-sum partition
(``schedule.equal_weight_partition``, the int64 host twin of
``rows_to_bins``), and each chip's local product is a planned single-node
SpGEMM -- three-level balancing, mirroring the inspector-executor split
that distributed SpGEMM work (Gu et al., arXiv:2002.11302; the DBCSR port,
arXiv:1708.03604) applies across nodes.

Algorithms:
  * ``spgemm_1d``: A row-partitioned over a mesh axis, B replicated -> C
    row-partitioned (distributed Gustavson: A stays put).  Takes
    ``algorithm=``/``semiring=``/``mask=`` like the single-node dispatcher,
    or a frozen :class:`DistributedPlan` (``plan_spgemm_1d``).
  * ``spmm_1d``: CSR x dense tall-skinny; returns the assembled global
    ``(m, k)`` product (rectangular-safe -- no square assumption).
  * ``spgemm_summa``: outer-product SUMMA over one mesh axis: K is split
    into ``k_panels`` panels; chip ``s`` owns the A column-blocks and B
    row-blocks of its panels, streams them through planned local products,
    and the partial C's are merged with a reduce-scatter
    (``jax.lax.psum_scatter``) that leaves C row-partitioned.

Everything host-side here is **sparse-native**: sharding slices the CSR
arrays directly (never ``to_dense``).  The only dense intermediate in the
whole subsystem is SUMMA's partial-C accumulator, which is what the
reduce-scatter merge sums (its elementwise ``+`` must be the semiring's
``add`` with identity 0 -- hence the ``min_plus`` rejection below).

Local products dispatch through :func:`repro.core.spgemm.spgemm`.  The
*planned* hash family runs the real Pallas kernel inside ``shard_map``:
``plan_spgemm_1d`` / ``plan_spgemm_summa`` freeze each shard's (or
panel's) schedule -- bin offsets, per-bin table sizes, ``indptr_c`` -- as
stacked arrays threaded through the executor with ``P(axis)`` specs, so
every dynamic value arrives as a traced array while the scratch table
stays a static per-plan maximum.  Only the *planless* traced path
(``spgemm_1d`` without a plan, general semirings, masks) still
substitutes ``hash_jnp``, which keeps the identical contract (two-phase
capacity, unsorted select output) and doubles as the reference oracle in
the differential tests.
"""
from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro import tracing
from .formats import CSR
from .plan import SpGEMMPlan, plan_spgemm, structure_key, cache_lookup, \
    cache_store
from .schedule import equal_weight_partition, flops_per_row
from .semiring import Semiring, resolve_semiring
from .spgemm import spgemm, spmm


def _pad8(x: int) -> int:
    """Static capacities padded to a lane multiple (like shard_csr_rows)."""
    return -(-max(int(x), 1) // 8) * 8


# ----------------------------------------------------------------------------
# Row-sharded CSR (the distributed operand/result currency)
# ----------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardedCSR:
    """Row-partitioned CSR: ``parts`` arrays carry a leading shard dim.

    ``parts`` is a CSR whose every array leaf is stacked ``(S, ...)``; its
    static ``shape`` is the *local* ``(rows_cap, n_cols)`` where
    ``rows_cap`` is the max shard height (equal-flop partitions produce
    unequal row counts; short shards are padded with trailing empty rows so
    the one SPMD program covers every shard).  ``row_starts`` records the
    global partition; ``n_rows_global`` the unpadded global row count.
    """
    parts: CSR
    row_starts: Tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    n_rows_global: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_shards(self) -> int:
        return len(self.row_starts) - 1

    @property
    def rows_cap(self) -> int:
        return self.parts.n_rows

    @property
    def cap_per(self) -> int:
        """Per-shard entry capacity (``parts.cap`` would read the shard
        count off the stacked leading dim)."""
        return self.parts.indices.shape[-1]

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows_global, self.parts.n_cols)

    def local(self, s: int) -> CSR:
        """Shard ``s`` as a standalone (padded-height) CSR."""
        return jax.tree.map(lambda x: x[s], self.parts)


jax.tree_util.register_dataclass(
    ShardedCSR, data_fields=["parts"],
    meta_fields=["row_starts", "n_rows_global"])


def shard_csr_rows(a: CSR, n_shards: int, b: CSR | None = None,
                   weights=None, row_starts=None) -> ShardedCSR:
    """Sparse-native row sharding with equal-flop boundaries.

    The partition weight is, in order of preference: explicit ``weights``;
    the planner's per-row flop counts ``flops_per_row(a, b)`` when the
    right-hand operand is known; else nnz per row (the flop proxy).  Shard
    boundaries come from :func:`schedule.equal_weight_partition` -- the
    paper's Fig. 6 prefix-sum split, at mesh scale.  ``row_starts``
    overrides the partition outright (used to co-shard masks/outputs with
    an existing operand).

    Never densifies: shards are direct slices of the CSR arrays (a row
    partition of row-major CSR is contiguous), padded to a uniform
    per-shard capacity (lane multiple of 8) and a uniform row count.
    """
    m = a.n_rows
    if row_starts is None:
        if weights is None:
            weights = flops_per_row(a, b) if b is not None else a.row_nnz()
        w = np.asarray(weights, np.int64)
        assert w.shape == (m,), (w.shape, m)
        row_starts = equal_weight_partition(w, n_shards)
    starts = tuple(int(r) for r in np.asarray(row_starts))
    assert len(starts) == n_shards + 1 and starts[0] == 0 \
        and starts[-1] == m, (starts, m)
    ip = np.asarray(a.indptr, np.int64)
    ind = np.asarray(a.indices)
    dat = np.asarray(a.data)
    spans = [(starts[s], starts[s + 1]) for s in range(n_shards)]
    rows_cap = max(max(r1 - r0 for r0, r1 in spans), 1)
    counts = [int(ip[r1] - ip[r0]) for r0, r1 in spans]
    cap_per = _pad8(max(counts))
    indptr_s = np.zeros((n_shards, rows_cap + 1), np.int32)
    indices_s = np.zeros((n_shards, cap_per), np.int32)
    data_s = np.zeros((n_shards, cap_per), dat.dtype)
    for s, (r0, r1) in enumerate(spans):
        loc = (ip[r0:r1 + 1] - ip[r0]).astype(np.int32)
        indptr_s[s, :r1 - r0 + 1] = loc
        indptr_s[s, r1 - r0 + 1:] = loc[-1]        # trailing empty pad rows
        indices_s[s, :counts[s]] = ind[ip[r0]:ip[r1]]
        data_s[s, :counts[s]] = dat[ip[r0]:ip[r1]]
    parts = CSR(jnp.asarray(indptr_s), jnp.asarray(indices_s),
                jnp.asarray(data_s),
                jnp.asarray(np.asarray(counts, np.int32)),
                (rows_cap, a.n_cols), sorted_cols=a.sorted_cols)
    return ShardedCSR(parts, starts, m)


def reshard_rows(a: CSR, like: ShardedCSR) -> ShardedCSR:
    """Shard ``a`` with an existing partition (masks follow their output)."""
    assert a.n_rows == like.n_rows_global, (a.shape, like.shape)
    return shard_csr_rows(a, like.n_shards, row_starts=like.row_starts)


def unshard_rows(c_sh: ShardedCSR, cap: int | None = None) -> CSR:
    """Assemble a row-sharded result back into one global CSR (host-side,
    sparse concatenation -- within-row entry order, hence sortedness, is
    preserved).

    ``cap`` pins the assembled capacity; pass the original operand's
    ``cap`` to make a shard -> unshard round trip bitwise (same structure
    key, so plan reuse matches the single-node path).  The default keeps
    the sharded operand's slack (``n_shards * cap_per``) rather than
    silently shrinking to ``nnz``, which made every round trip a new
    structure."""
    parts, starts = c_sh.parts, c_sh.row_starts
    ip = np.asarray(parts.indptr)
    ind = np.asarray(parts.indices)
    dat = np.asarray(parts.data)
    row_nnz, idx, vals = [], [], []
    for s in range(c_sh.n_shards):
        local_m = starts[s + 1] - starts[s]
        live = int(ip[s, local_m])
        row_nnz.append(np.diff(ip[s, :local_m + 1]))
        idx.append(ind[s, :live])
        vals.append(dat[s, :live])
    row_nnz = np.concatenate(row_nnz) if row_nnz else np.zeros(0, np.int64)
    idx = np.concatenate(idx)
    vals = np.concatenate(vals)
    nnz = int(idx.size)
    if cap is None:
        cap = max(c_sh.n_shards * c_sh.cap_per, 1)
    assert cap >= nnz, (cap, nnz)
    indices = np.zeros(cap, np.int32)
    data = np.zeros(cap, dat.dtype)
    indices[:nnz] = idx
    data[:nnz] = vals
    indptr = np.zeros(c_sh.n_rows_global + 1, np.int32)
    np.cumsum(row_nnz, out=indptr[1:])
    return CSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(data),
               jnp.asarray(nnz, jnp.int32), c_sh.shape,
               sorted_cols=parts.sorted_cols)


# ----------------------------------------------------------------------------
# Local product dispatch (shared by the 1D and SUMMA executors)
# ----------------------------------------------------------------------------

#: shard_map-side algorithm substitutions: ``dense`` is the test oracle --
#: run the ESC engine instead of densifying per shard.  The hash family is
#: NOT substituted anymore: planned executors thread frozen schedules
#: through shard_map and run the real Pallas kernel (``_local_spgemm``
#: falls back to ``hash_jnp`` only when no schedule is available -- the
#: planless traced path, where eager inspection cannot run).
_LOCAL_ALGO = {"dense": "esc"}


def _local_spgemm(a_loc: CSR, b_loc: CSR, mask_loc: Optional[CSR], *,
                  algorithm: str, semiring: str, complement_mask: bool,
                  sorted_output: bool, cap_c: int,
                  flop_cap: Optional[int], row_cap: Optional[int],
                  k_width: Optional[int], table_size: int = 0,
                  hash_sched=None, pb_sched=None) -> CSR:
    """One shard's product, dispatched through the single-node front door.

    ``hash_sched=(offsets, bin_tsize, indptr_c)`` is this shard's frozen
    hash schedule (traced arrays are fine -- that is the point); with it
    the hash family runs the numeric-only Pallas kernel.  Without it a
    hash request inside a trace would need eager inspection, so the
    planless path keeps the documented ``hash_jnp`` substitution.

    ``pb_sched=(src_a, src_b, seg, bucket_nnz, indptr_c, cols_c)`` is the
    shard's frozen propagation-blocking geometry (DESIGN.md section 18);
    with it the PB scatter/merge Pallas pair runs numeric-only.  A
    planless ``pb`` request substitutes ``esc`` -- the same sorted-output
    contract without needing eager inspection inside the trace.
    """
    algo = _LOCAL_ALGO.get(algorithm, algorithm)
    if algo in ("hash", "hash_vector") and hash_sched is None:
        algo = "hash_jnp"
    if algo == "pb":
        if pb_sched is None:
            algo = "esc"
        else:
            from repro.kernels.spgemm_pb import ops as pb_ops
            src_a, src_b, seg, bucket_nnz, indptr_c, cols_c = pb_sched
            return pb_ops.spgemm_pb(
                a_loc, b_loc, cap_c, src_a=src_a, src_b=src_b, seg=seg,
                bucket_nnz=bucket_nnz, indptr_c=indptr_c, cols_c=cols_c)
    kw = {}
    if algo in ("esc", "hash_jnp") and flop_cap is not None:
        kw["flop_cap"] = flop_cap
    if algo in ("hash", "hash_vector"):
        kw["schedule"] = (hash_sched[0], hash_sched[1])
        kw["indptr_c"] = hash_sched[2]
        kw["table_size"] = table_size
    if algo == "heap":
        if row_cap is not None:
            kw["row_cap"] = row_cap
        if k_width is not None:
            kw["k_width"] = k_width
    return spgemm(a_loc, b_loc, cap_c, algorithm=algo, semiring=semiring,
                  mask=mask_loc, complement_mask=complement_mask,
                  sorted_output=sorted_output, **kw)


def _build_1d_fn(mesh: Mesh, axis: str, masked: bool, statics: dict,
                 with_sched: bool = False, with_pb: bool = False):
    """shard_map'd SPMD body for the 1D row-partitioned product.

    With ``with_sched`` the last three operands are the plan's stacked
    hash schedules, row-sharded like A (``P(axis)``): each shard slices
    off its own ``(offsets, bin_tsize, indptr_c)`` and the local product
    runs the Pallas hash kernel on them.  With ``with_pb`` (mutually
    exclusive) the last *six* operands are the stacked propagation-
    blocking geometry ``(src_a, src_b, seg, bucket_nnz, indptr_c,
    cols_c)`` and the local product runs the PB scatter/merge pair.
    """
    assert not (with_sched and with_pb)

    def local(a_parts, b_rep, *rest):
        a_loc = jax.tree.map(lambda x: x[0], a_parts)
        m_loc = (jax.tree.map(lambda x: x[0], rest[0])
                 if masked else None)
        hs = tuple(r[0] for r in rest[-3:]) if with_sched else None
        ps = tuple(r[0] for r in rest[-6:]) if with_pb else None
        c = _local_spgemm(a_loc, b_rep, m_loc, hash_sched=hs, pb_sched=ps,
                          **statics)
        return jax.tree.map(lambda x: x[None], c)

    in_specs = (P(axis), P()) + ((P(axis),) if masked else ()) + \
        ((P(axis), P(axis), P(axis)) if with_sched else ()) + \
        ((P(axis),) * 6 if with_pb else ())
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=P(axis), check_vma=False)


# ----------------------------------------------------------------------------
# DistributedPlan: per-shard SpGEMMPlans frozen under one structure key
# ----------------------------------------------------------------------------

@dataclass(frozen=True)
class DistributedPlan:
    """Frozen mesh-scale recipe for one (sharded-A, B) structure pair.

    Holds the shard partition and one :class:`SpGEMMPlan` per shard; the
    executor's static capacities are the per-shard maxima (shard_map runs
    one SPMD program, so capacities must be uniform -- each shard's *exact*
    numbers stay available in ``plans`` for audit).  Cached in the same LRU
    as single-node plans under a ``("dist_1d", digest)`` key.
    """
    key: tuple = dataclasses.field(repr=False)
    row_starts: Tuple[int, ...]
    algorithm: str
    semiring: str
    complement_mask: bool
    sorted_output: bool
    mask_sh: Optional[ShardedCSR] = dataclasses.field(repr=False)
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]
    cap_a: int
    cap_b: int
    nnz_b: int
    plans: Tuple[SpGEMMPlan, ...] = dataclasses.field(repr=False)
    cap_c: int
    flop_cap: int
    row_cap: int
    k_width: int
    nnz_c: int
    #: static Pallas scratch allocation: max over shards' natural table
    #: sizes (each shard's per-bin sizes clamp against its own table at
    #: plan time, so the uniform allocation never changes shard results).
    table_size: int = 0
    #: stacked per-shard hash schedules ``(offsets (S, n_bins+1),
    #: bin_tsize (S, n_bins), indptr_c (S, rows_cap+1))``, threaded
    #: through shard_map with ``P(axis)`` specs; ``None`` unless the plan
    #: resolved to the hash family on a plain plus_times product.
    hash_sched: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = \
        dataclasses.field(default=None, repr=False)
    #: stacked per-shard propagation-blocking geometry ``(src_a
    #: (S, nb, bcap), src_b (S, nb, bcap), seg (S, nb, bcap), bucket_nnz
    #: (S, nb), indptr_c (S, rows_cap+1), cols_c (S, cap_c))``, threaded
    #: through shard_map with ``P(axis)`` specs; ``None`` unless the plan
    #: resolved to ``"pb"`` on a plus_times product.  Shards are padded
    #: to uniform bucket count / capacities (pad lanes carry
    #: ``bucket_nnz``-masked zeros, so they are never read).
    pb_sched: Optional[Tuple[jax.Array, ...]] = \
        dataclasses.field(default=None, repr=False)

    def check_structure(self, a_sh: ShardedCSR, b: CSR) -> None:
        assert a_sh.row_starts == self.row_starts, \
            "operand partition differs from the planned shard boundaries"
        assert a_sh.shape == self.shape_a and b.shape == self.shape_b, \
            f"plan is for {self.shape_a}x{self.shape_b}, " \
            f"got {a_sh.shape}x{b.shape}"
        assert a_sh.cap_per == self.cap_a and b.cap == self.cap_b, \
            "operand capacities differ from the planned structure"
        if not isinstance(b.nnz, jax.core.Tracer):
            assert int(b.nnz) == self.nnz_b, \
                "B nnz differs from the planned structure (replan)"

    def _statics(self, sorted_output: Optional[bool]) -> dict:
        so = self.sorted_output if sorted_output is None else sorted_output
        return dict(algorithm=self.algorithm, semiring=self.semiring,
                    complement_mask=self.complement_mask,
                    sorted_output=so, cap_c=self.cap_c,
                    flop_cap=self.flop_cap, row_cap=self.row_cap,
                    k_width=self.k_width, table_size=self.table_size)

    def _executor(self, mesh: Mesh, axis: str,
                  sorted_output: Optional[bool] = None):
        statics = self._statics(sorted_output)
        return _memoized_executor(
            self, (mesh, axis, statics["sorted_output"]),
            lambda: _build_1d_fn(mesh, axis, self.mask_sh is not None,
                                 statics,
                                 with_sched=self.hash_sched is not None,
                                 with_pb=self.pb_sched is not None))

    def execute(self, mesh: Mesh, a_sh: ShardedCSR, b: CSR,
                axis: str = "data",
                sorted_output: Optional[bool] = None) -> ShardedCSR:
        """Numeric phase only: zero re-inspection, uniform static caps.

        ``sorted_output`` overrides the plan's recorded sortedness for
        this call (``None`` keeps it) -- a pure per-shard sort epilogue,
        exactly like :meth:`repro.core.plan.SpGEMMPlan.execute`, so one
        cached distributed plan serves sorted and unsorted consumers (the
        distributed chain keeps intermediates unsorted this way)."""
        self.check_structure(a_sh, b)
        args = (a_sh.parts, b)
        if self.mask_sh is not None:
            args = args + (self.mask_sh.parts,)
        if self.hash_sched is not None:
            args = args + self.hash_sched
        if self.pb_sched is not None:
            args = args + self.pb_sched
        out = self._executor(mesh, axis, sorted_output)(*args)
        return ShardedCSR(out, self.row_starts, self.shape_a[0])

    __call__ = execute

    def execute_shards_host(self, a_sh: ShardedCSR, b: CSR,
                            sorted_output: Optional[bool] = None
                            ) -> ShardedCSR:
        """Mesh-free executor twin: every shard's local product, eagerly.

        Runs the exact ``_local_spgemm`` body the shard_map executor runs
        -- same algorithm substitutions, same uniform static capacities --
        shard by shard on the host's default device, and restacks the
        results.  Structure- and value-identical to :meth:`execute` on a
        mesh (the SPMD body is deterministic given structure), which is
        what lets the chain planner (``core.chain.plan_chain_1d``)
        materialize intermediate *sharded* structure at plan time without
        owning a mesh; also a single-process debugging aid.
        """
        self.check_structure(a_sh, b)
        statics = self._statics(sorted_output)
        outs = []
        for s in range(len(self.row_starts) - 1):
            m_loc = self.mask_sh.local(s) if self.mask_sh is not None \
                else None
            hs = None if self.hash_sched is None else \
                tuple(x[s] for x in self.hash_sched)
            ps = None if self.pb_sched is None else \
                tuple(x[s] for x in self.pb_sched)
            outs.append(_local_spgemm(a_sh.local(s), b, m_loc,
                                      hash_sched=hs, pb_sched=ps,
                                      **statics))
        parts = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return ShardedCSR(parts, self.row_starts, self.shape_a[0])

    def kernel_counts(self, a_sh: ShardedCSR, b: CSR
                      ) -> list[dict[str, int]]:
        """Run the numeric hash kernel once per shard with its counters on
        and return one dict of ``kernels.spgemm_hash.kernel.COUNTERS`` per
        shard.  Each run is the one the mesh executor hands that shard's
        chip -- its rows of A, its slice of the stacked schedule, the
        uniform static capacities -- run shard by shard on one device, like
        :meth:`execute_shards_host`.  The counts depend on the structure
        only.  Raises ``ValueError`` for a plan that runs no hash kernel
        (no stacked hash schedule) and where a shard's ``total_flop``
        reaches 2^31, past the counters' int32."""
        if self.hash_sched is None:
            raise ValueError(f"plan {self.algorithm!r} ({self.semiring}, "
                             f"masked={self.mask_sh is not None}) does not "
                             "run the hash kernel")
        if any(p.total_flop >= 2 ** 31 for p in self.plans):
            raise ValueError("a shard's total_flop would overflow the "
                             "kernel's int32 counters")
        self.check_structure(a_sh, b)
        from repro.kernels.spgemm_hash import ops as hash_ops
        dev = jax.devices()[0]
        b = jax.device_put(b, dev)
        offsets, bin_tsize, _ = self.hash_sched
        vector = self.algorithm == "hash_vector"
        return [hash_ops.numeric_counts(
            jax.device_put(a_sh.local(s), dev), b, self.cap_c,
            schedule=(offsets[s], bin_tsize[s]), table_size=self.table_size,
            vector=vector) for s in range(len(self.plans))]


def _memoized_executor(plan, cache_key, build):
    """Jitted executor cache on a frozen plan dataclass, keyed by whatever
    static context the executor was built for -- (mesh, axis) for SUMMA,
    (mesh, axis, sorted_output) for the 1D plan (shared by both)."""
    cache = plan.__dict__.get("_executors")
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_executors", cache)
    fn = cache.get(cache_key)
    if fn is None:
        fn = jax.jit(build())
        cache[cache_key] = fn
    return fn


def _shard_flops(a_sh: ShardedCSR, b: CSR) -> list[int]:
    """Partial products of each shard of A times B (host, int64)."""
    b_len = np.diff(np.asarray(b.indptr, np.int64))
    ip = np.asarray(a_sh.parts.indptr, np.int64)
    ind = np.asarray(a_sh.parts.indices)
    return [int(b_len[ind[s, :ip[s, -1]]].sum())
            for s in range(a_sh.n_shards)]


def sharded_structure_key(sh: ShardedCSR) -> bytes:
    """Digest of a ShardedCSR's structure (partition + stacked pattern).

    The mesh twin of :func:`repro.core.plan.structure_key`: hashes the
    stacked ``indptr``/``indices``/``nnz`` arrays in one pass and memoizes
    on the (long-lived) instance, so repeat plan-cache lookups cost O(1)
    instead of re-slicing and re-hashing every shard.
    """
    cached = sh.__dict__.get("_structure_digest")
    if cached is not None:
        return cached
    p = sh.parts
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((sh.row_starts, sh.n_rows_global, p.shape,
                   p.indices.shape, p.sorted_cols)).encode())
    h.update(np.asarray(p.indptr).tobytes())
    h.update(np.asarray(p.indices).tobytes())
    h.update(np.asarray(p.nnz).tobytes())
    digest = h.digest()
    object.__setattr__(sh, "_structure_digest", digest)
    return digest


@tracing.span("repro.plan_1d")
def plan_spgemm_1d(a_sh: ShardedCSR, b: CSR, *, algorithm: str = "auto",
                   semiring: str | Semiring = "plus_times",
                   mask: CSR | ShardedCSR | None = None,
                   complement_mask: bool = False,
                   sorted_output: bool = False, n_bins: int = 8,
                   cache: bool = True) -> DistributedPlan:
    """Inspect every shard once and freeze a :class:`DistributedPlan`.

    ``algorithm="auto"`` is resolved by shard 0's recipe choice and then
    forced on every shard (shard_map is SPMD: one program).  The mask (in
    global output coordinates) is co-sharded with A's row partition.  The
    plan is cached in the shared LRU under one blake2b digest of all shard
    structures + B + mask + partition + semantic fields, so a repeat
    product on the same structures replans nothing.

    Records the span ``repro.plan_1d`` around the whole call, cache lookup
    included, and inside it ``repro.plan_1d.shards`` (the per-shard
    planning, which holds the shards' own ``repro.plan`` spans) and
    ``repro.plan_1d.freeze`` (stacked schedules, capacities, cache store).
    """
    sr = resolve_semiring(semiring)
    mask_sh = None
    if mask is not None:
        mask_sh = mask if isinstance(mask, ShardedCSR) \
            else reshard_rows(mask, a_sh)
        assert mask_sh.row_starts == a_sh.row_starts, \
            "mask must be sharded with A's row partition"
    S = a_sh.n_shards
    key = ("dist_1d", sharded_structure_key(a_sh), structure_key(b),
           None if mask_sh is None else sharded_structure_key(mask_sh),
           sr.name, complement_mask, sorted_output, algorithm, n_bins)
    if cache:
        hit = cache_lookup(key)
        if hit is not None:
            return hit

    with tracing.span("repro.plan_1d.shards"):
        a_locals = [a_sh.local(s) for s in range(S)]
        mask_locals = ([mask_sh.local(s) for s in range(S)] if mask_sh
                       else None)
        algo = algorithm
        plans = []
        # One static ESC expansion bound for every shard, the largest
        # shard's exact flop: the shards share every other shape of
        # ``symbolic``, so its one program serves them all, where each
        # shard's own flop would compile it once per shard.  Not rounded
        # up: the largest expansion, which sets the planning chip's peak,
        # stays exact.
        flop_cap = max(max(_shard_flops(a_sh, b)), 1)
        for s in range(S):
            p = plan_spgemm(a_locals[s], b, algorithm=algo, semiring=sr.name,
                            mask=mask_locals[s] if mask_locals else None,
                            complement_mask=complement_mask,
                            sorted_output=sorted_output, n_bins=n_bins,
                            use_case="dist", cache=cache, _flop_cap=flop_cap)
            if algo == "auto":
                algo = p.algorithm          # shard 0 resolves; rest uniform
            plans.append(p)

    with tracing.span("repro.plan_1d.freeze"):
        # Freeze the per-shard hash schedules as stacked arrays: shards are
        # padded to a uniform ``rows_cap`` (flat trailing indptr) and share
        # ``n_bins``, so every shard's (offsets, bin_tsize, indptr_c) is
        # shape-uniform and stacks along the shard axis -- exactly what
        # ``shard_map`` needs to hand each chip its own schedule.  Each
        # shard's bin sizes were clamped against its *own* table at plan
        # time, so the uniform static ``table_size`` (the shard max) is inert
        # and per-shard results stay bitwise the per-shard planned results.
        table_size = 0
        hash_sched = None
        if algo in ("hash", "hash_vector") and mask_sh is None and \
                sr.name == "plus_times":
            table_size = max(p.table_size for p in plans)
            hash_sched = (jnp.stack([p.offsets for p in plans]),
                          jnp.stack([p.bin_tsize for p in plans]),
                          jnp.stack([p.indptr_c for p in plans]))

        # Freeze the per-shard propagation-blocking geometry the same way
        # (DESIGN.md section 18).  PB's bucket layout is per-shard (each
        # shard's flop total picks its own bucket width), so the shards are
        # first re-planned with a forced common bucket count -- every shard
        # sees the same ``n_cols``, so a common count yields one common p2
        # width -- then padded to the max bucket capacity / output capacity.
        # Pad lanes sit beyond ``bucket_nnz`` and are never read by either
        # the Pallas pair or the jnp twin.  Mask pruning happened at plan
        # time (structural), so the masked product still runs the mask-free
        # kernels; general semirings keep ``pb_sched=None`` and the SPMD body
        # substitutes esc.
        cap_c_u = _pad8(max(p.cap_c for p in plans))
        pb_sched = None
        if algo == "pb" and sr.name == "plus_times":
            from .pb import plan_pb
            nb = max(p.pb_plan.n_buckets for p in plans)
            pbs = [plan_pb(a_locals[s], b, semiring=sr.name,
                           mask=mask_locals[s] if mask_locals else None,
                           complement_mask=complement_mask, n_buckets=nb,
                           cache=cache) for s in range(S)]
            assert all(q.n_buckets == pbs[0].n_buckets for q in pbs)
            bcap = max(q.bucket_cap for q in pbs)

            def lanes(x, cap):   # pad trailing lane axis to the shard max
                x = np.asarray(x)
                return np.pad(x, [(0, 0)] * (x.ndim - 1) +
                              [(0, cap - x.shape[-1])])

            pb_sched = (
                jnp.stack([jnp.asarray(lanes(q.src_a, bcap)) for q in pbs]),
                jnp.stack([jnp.asarray(lanes(q.src_b, bcap)) for q in pbs]),
                jnp.stack([jnp.asarray(lanes(q.seg, bcap)) for q in pbs]),
                jnp.stack([q.bucket_nnz for q in pbs]),
                jnp.stack([q.indptr_c for q in pbs]),
                jnp.stack([jnp.asarray(lanes(q.cols_c, cap_c_u))
                           for q in pbs]))

        plan = DistributedPlan(
            key=key, row_starts=a_sh.row_starts, algorithm=algo,
            semiring=sr.name, complement_mask=complement_mask,
            sorted_output=sorted_output, mask_sh=mask_sh, shape_a=a_sh.shape,
            shape_b=b.shape, cap_a=a_sh.cap_per, cap_b=b.cap,
            nnz_b=int(b.nnz), plans=tuple(plans),
            cap_c=cap_c_u,
            flop_cap=max(max(p.flop_cap for p in plans), 1),
            row_cap=max(p.row_cap for p in plans),
            k_width=max(p.k_width for p in plans),
            nnz_c=sum(p.nnz_c for p in plans),
            table_size=table_size, hash_sched=hash_sched, pb_sched=pb_sched)
        if cache:
            cache_store(key, plan)
    return plan


def shard_batch(pairs, n_shards: int, weights=None
                ) -> Tuple[Tuple[int, ...], ...]:
    """Round-robin *whole products* of a fleet across mesh chips.

    The batched subsystem's unit of distribution is the product, not the
    row: a fleet of small independent products (``core.batch``) has no
    cross-product reduction, so each chip simply owns a sub-fleet and
    runs its own :func:`repro.core.batch.plan_batch` -- embarrassingly
    parallel, zero collectives (the DBCSR batched-multiply distribution
    shape, vs the row partition ``shard_csr_rows`` uses for one large
    product).

    ``pairs`` is the fleet (only its length is read) or an int count.
    Plain round-robin by default; with ``weights`` (e.g. each product's
    ``total_flop`` from a plan, or ``nnz``) the round-robin visits
    products in descending weight order, so consecutive heavy products
    land on different chips -- the fleet analogue of the equal-flop row
    partition.  Returns ``n_shards`` tuples of product indices; every
    index appears exactly once.
    """
    n = pairs if isinstance(pairs, int) else len(pairs)
    assert n_shards >= 1, n_shards
    if weights is None:
        order = range(n)
    else:
        w = np.asarray(weights)
        assert w.shape == (n,), (w.shape, n)
        order = np.argsort(-w, kind="stable")
    assign: list = [[] for _ in range(n_shards)]
    for pos, i in enumerate(order):
        assign[pos % n_shards].append(int(i))
    return tuple(tuple(s) for s in assign)


# ----------------------------------------------------------------------------
# 1D row-partitioned products
# ----------------------------------------------------------------------------

def spgemm_1d(mesh: Mesh, a_sh: ShardedCSR, b: CSR, cap_c: int | None = None,
              flop_cap: int | None = None, axis: str = "data", *,
              algorithm: str = "esc",
              semiring: str | Semiring = "plus_times",
              mask: CSR | ShardedCSR | None = None,
              complement_mask: bool = False, sorted_output: bool = False,
              plan: DistributedPlan | None = None) -> ShardedCSR:
    """Row-partitioned SpGEMM: local shards of A x replicated B.

    With ``plan=`` (from :func:`plan_spgemm_1d`) every capacity and the
    algorithm/semiring/mask come from the plan and nothing is recomputed.
    Without a plan, ``cap_c`` is the per-shard output capacity and the
    explicit ``algorithm`` dispatches through :func:`spgemm` (``auto``
    needs inspection -- use the planner).
    """
    if plan is not None:
        return plan.execute(mesh, a_sh, b, axis=axis)
    assert cap_c is not None, "spgemm_1d needs cap_c unless plan= is given"
    if algorithm == "auto":
        raise ValueError(
            "algorithm='auto' needs inspection; use plan_spgemm_1d")
    sr = resolve_semiring(semiring)
    mask_sh = None
    if mask is not None:
        mask_sh = mask if isinstance(mask, ShardedCSR) \
            else reshard_rows(mask, a_sh)
        assert mask_sh.row_starts == a_sh.row_starts, \
            "mask must be sharded with A's row partition"
    statics = dict(algorithm=algorithm, semiring=sr.name,
                   complement_mask=complement_mask,
                   sorted_output=sorted_output, cap_c=cap_c,
                   flop_cap=flop_cap, row_cap=None, k_width=None)
    # no frozen schedule on the planless path: a hash request falls back
    # to hash_jnp inside _local_spgemm (use plan_spgemm_1d for Pallas)
    fn = _build_1d_fn(mesh, axis, mask_sh is not None, statics)
    args = (a_sh.parts, b) + ((mask_sh.parts,) if mask_sh else ())
    return ShardedCSR(fn(*args), a_sh.row_starts, a_sh.n_rows_global)


def _gather_rows(y: jax.Array, a_sh: ShardedCSR) -> jax.Array:
    """Drop per-shard pad rows from a stacked (S, rows_cap, k) result and
    reassemble the global (m, k) order (rectangular/unequal-shard safe --
    this replaces the old square-only ``reshape(nxt, (n, k))``)."""
    S, rows_cap = a_sh.n_shards, a_sh.rows_cap
    starts = a_sh.row_starts
    idx = np.concatenate(
        [np.arange(starts[s + 1] - starts[s], dtype=np.int64) + s * rows_cap
         for s in range(S)])
    flat = y.reshape((S * rows_cap,) + y.shape[2:])
    return flat[jnp.asarray(idx, jnp.int32)]


def spmm_1d(mesh: Mesh, a_sh: ShardedCSR, x: jax.Array,
            axis: str = "data") -> jax.Array:
    """Row-partitioned SpMM: y = A @ X with dense X of shape (n_cols, k).

    X is replicated (tall-skinny: k << n); the result is assembled to the
    global ``(n_rows, k)`` layout, which is correct for rectangular A and
    unequal (equal-flop) shard heights alike.
    """
    assert x.shape[0] == a_sh.shape[1], (x.shape, a_sh.shape)

    def local(a_parts, x_rep):
        a_loc = jax.tree.map(lambda v: v[0], a_parts)
        return spmm(a_loc, x_rep)[None]

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                   out_specs=P(axis), check_vma=False)
    return _gather_rows(fn(a_sh.parts, x), a_sh)


def multi_source_bfs(mesh: Mesh, a_sh: ShardedCSR, sources: jax.Array,
                     n: int, n_iters: int, axis: str = "data") -> jax.Array:
    """Multi-source BFS via repeated SpMM (paper section 5.5 use case).

    ``sources`` (k,) vertex ids; returns (n, k) hop-distance matrix (-1 =
    unreached).  Frontier is the dense tall-skinny matrix; one SpMM per hop.
    """
    assert a_sh.shape == (n, n), \
        f"BFS adjacency must be square (n, n); got {a_sh.shape}"
    k = sources.shape[0]
    frontier = jnp.zeros((n, k), jnp.float32).at[sources,
                                                 jnp.arange(k)].set(1.0)
    dist = jnp.where(frontier > 0, 0, -1).astype(jnp.int32)

    def body(i, state):
        frontier, dist = state
        nxt = spmm_1d(mesh, a_sh, frontier, axis=axis)   # (n, k), assembled
        new = (nxt > 0) & (dist < 0)
        dist = jnp.where(new, i + 1, dist)
        return new.astype(jnp.float32), dist

    _, dist = jax.lax.fori_loop(0, n_iters, body, (frontier, dist))
    return dist


# ----------------------------------------------------------------------------
# SUMMA: outer-product K-panel schedule with reduce-scatter merge
# ----------------------------------------------------------------------------

def summa_panel_bounds(k_dim: int, n_shards: int,
                       k_panels: int | None = None) -> Tuple[Tuple[int, int],
                                                             ...]:
    """The K-panel schedule: ``k_panels`` contiguous panels of the
    contraction dimension, ``k_panels / n_shards`` owned per chip.

    ``k_panels`` defaults to one panel per chip and must be a multiple of
    ``n_shards`` no larger than K -- anything else raises (no silently
    ignored arguments).  K need *not* be a multiple of ``k_panels``:
    panels are ``ceil(K / k_panels)`` wide with a ragged (short, possibly
    empty) tail, so prime contraction dims schedule fine.  The first
    panel is always the widest -- executors size buffers off it.
    """
    if k_panels is None:
        k_panels = n_shards
    if k_panels % n_shards != 0:
        raise ValueError(
            f"k_panels={k_panels} must be a multiple of the mesh axis size "
            f"{n_shards} (each chip owns k_panels/n_shards panels)")
    if k_panels > k_dim:
        raise ValueError(
            f"k_panels={k_panels} exceeds the contraction dim {k_dim}")
    step = -(-k_dim // k_panels)
    return tuple((min(i * step, k_dim), min((i + 1) * step, k_dim))
                 for i in range(k_panels))


def _shard_summa(a: CSR, b: CSR, n_shards: int, k_panels: int):
    """Sparse-native operand layout for the outer-product schedule.

    Panel ``p`` (owned by chip ``p // (k_panels/n_shards)``) gets A's
    column block and B's row block for K-range ``bounds[p]``: the column
    block is a host-side entry filter (order-preserving, so sortedness
    survives); the row block is a contiguous CSR slice.  Returns stacked
    CSRs with leading dims ``(S, P)`` plus the per-panel **entry-gather
    indices** ``(a_take, b_take)`` mapping each panel slot back to its
    global entry -- the structural part of the decomposition the plan
    freezes, so repeat executes re-gather only *values* (one device
    gather) instead of re-running this host pass.
    """
    bounds = summa_panel_bounds(a.n_cols, n_shards, k_panels)
    k_panels = len(bounds)
    per = k_panels // n_shards
    m, n = a.n_rows, b.n_cols
    step = bounds[0][1] - bounds[0][0]

    ip_a = np.asarray(a.indptr, np.int64)
    ind_a = np.asarray(a.indices)
    dat_a = np.asarray(a.data)
    live_a = int(ip_a[-1])
    rows_a = np.repeat(np.arange(m), np.diff(ip_a))
    ip_b = np.asarray(b.indptr, np.int64)
    ind_b = np.asarray(b.indices)
    dat_b = np.asarray(b.data)

    a_blocks, b_blocks = [], []
    for lo, hi in bounds:
        sel = (ind_a[:live_a] >= lo) & (ind_a[:live_a] < hi)
        take_a = np.nonzero(sel)[0].astype(np.int32)
        r = rows_a[take_a]
        counts = np.bincount(r, minlength=m)
        indptr = np.zeros(m + 1, np.int32)
        np.cumsum(counts, out=indptr[1:])
        a_blocks.append((indptr, (ind_a[take_a] - lo).astype(np.int32),
                         dat_a[take_a], take_a))
        lo_p, hi_p = int(ip_b[lo]), int(ip_b[hi])
        take_b = np.arange(lo_p, hi_p, dtype=np.int32)
        b_blocks.append(((ip_b[lo:hi + 1] - ip_b[lo]).astype(np.int32),
                         ind_b[take_b].astype(np.int32), dat_b[take_b],
                         take_b))

    cap_a = _pad8(max(blk[1].size for blk in a_blocks))
    cap_b = _pad8(max(blk[1].size for blk in b_blocks))

    def stack(blocks, n_ptr, cap, dtype):
        ptr = np.zeros((n_shards, per, n_ptr), np.int32)
        idx = np.zeros((n_shards, per, cap), np.int32)
        val = np.zeros((n_shards, per, cap), dtype)
        take = np.zeros((n_shards, per, cap), np.int32)
        nnz = np.zeros((n_shards, per), np.int32)
        for pg, (p_ptr, p_idx, p_val, p_take) in enumerate(blocks):
            s, p = pg // per, pg % per
            ptr[s, p, :p_ptr.size] = p_ptr
            ptr[s, p, p_ptr.size:] = p_ptr[-1]   # ragged panel: pad rows
            idx[s, p, :p_idx.size] = p_idx
            val[s, p, :p_idx.size] = p_val
            take[s, p, :p_idx.size] = p_take
            nnz[s, p] = p_idx.size
        return ptr, idx, val, take, nnz

    pa, ia, va, ta, na = stack(a_blocks, m + 1, cap_a, dat_a.dtype)
    pb, ib, vb, tb, nb = stack(b_blocks, step + 1, cap_b, dat_b.dtype)
    a_parts = CSR(jnp.asarray(pa), jnp.asarray(ia), jnp.asarray(va),
                  jnp.asarray(na), (m, step), sorted_cols=a.sorted_cols)
    b_parts = CSR(jnp.asarray(pb), jnp.asarray(ib), jnp.asarray(vb),
                  jnp.asarray(nb), (step, n), sorted_cols=b.sorted_cols)
    return a_parts, b_parts, bounds, jnp.asarray(ta), jnp.asarray(tb)


@dataclass(frozen=True)
class SummaPlan:
    """Frozen outer-product SUMMA schedule: per-(chip, panel) plans, the
    global symbolic result that sizes the row-sharded output, and the
    *panel structure* itself (stacked indptr/indices with zeroed data,
    plus entry-gather indices).  Values deliberately stay out -- like
    ``SpGEMMPlan``, a re-weighted operand pair reuses the plan -- so
    ``execute`` re-gathers only ``data`` with one device gather per
    operand instead of re-running the host decomposition."""
    key: tuple = dataclasses.field(repr=False)
    n_shards: int
    k_panels: int
    bounds: Tuple[Tuple[int, int], ...]
    algorithm: str
    semiring: str
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]
    cap_a: int
    cap_b: int
    nnz_a: int
    nnz_b: int
    plans: Tuple[SpGEMMPlan, ...] = dataclasses.field(repr=False)
    a_struct: CSR = dataclasses.field(repr=False)   # stacked, data zeroed
    b_struct: CSR = dataclasses.field(repr=False)
    a_take: jax.Array = dataclasses.field(repr=False)
    b_take: jax.Array = dataclasses.field(repr=False)
    cap_c: int               # uniform per-panel local product capacity
    flop_cap: int
    row_cap: int
    k_width: int
    out_cap: int             # uniform per-row-shard output capacity
    row_starts_out: Tuple[int, ...]
    nnz_c: int
    #: static scratch allocation (max over panel plans) and stacked
    #: per-(chip, panel) hash schedules ``(offsets (S, per, n_bins+1),
    #: bin_tsize (S, per, n_bins), indptr_c (S, per, m+1))`` -- the SUMMA
    #: twin of ``DistributedPlan.hash_sched``.
    table_size: int = 0
    hash_sched: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = \
        dataclasses.field(default=None, repr=False)

    def check_structure(self, a: CSR, b: CSR) -> None:
        assert a.shape == self.shape_a and b.shape == self.shape_b, \
            f"plan is for {self.shape_a}x{self.shape_b}, " \
            f"got {a.shape}x{b.shape}"
        assert a.cap == self.cap_a and b.cap == self.cap_b, \
            "operand capacities differ from the planned structure"
        for op, planned in ((a, self.nnz_a), (b, self.nnz_b)):
            if not isinstance(op.nnz, jax.core.Tracer):
                assert int(op.nnz) == planned, \
                    "operand nnz differs from the planned structure"

    def execute(self, mesh: Mesh, a: CSR, b: CSR,
                axis: str = "data") -> ShardedCSR:
        """Numeric phase only: gather current values into the frozen panel
        structure (device-side), run the panel loop + reduce-scatter."""
        self.check_structure(a, b)
        fn = _memoized_executor(self, (mesh, axis),
                                lambda: _build_summa_fn(self, mesh, axis))
        args = (self.a_struct, self.a_take, a.data,
                self.b_struct, self.b_take, b.data)
        if self.hash_sched is not None:
            args = args + self.hash_sched
        out = fn(*args)
        return ShardedCSR(out, self.row_starts_out, self.shape_a[0])

    __call__ = execute


def _build_summa_fn(plan: SummaPlan, mesh: Mesh, axis: str):
    """SPMD body: gather values into the frozen panel structure, stream
    the chip's K-panels through planned local products, accumulate the
    dense partial C, reduce-scatter over rows."""
    per = plan.k_panels // plan.n_shards
    m, n = plan.shape_a[0], plan.shape_b[1]
    statics = dict(algorithm=plan.algorithm, semiring=plan.semiring,
                   complement_mask=False, sorted_output=False,
                   cap_c=plan.cap_c, flop_cap=plan.flop_cap,
                   row_cap=plan.row_cap, k_width=plan.k_width,
                   table_size=plan.table_size)
    boolean = plan.semiring == "boolean"
    with_sched = plan.hash_sched is not None

    def gather(struct, take, data):
        s_loc = jax.tree.map(lambda x: x[0], struct)     # (per, ...) local
        lane = jnp.arange(take.shape[-1], dtype=jnp.int32)
        live = lane[None, :] < s_loc.nnz[:, None]        # (per, cap)
        vals = jnp.where(live, data[take[0]], 0).astype(data.dtype)
        return dataclasses.replace(s_loc, data=vals)

    def local(a_struct, a_take, a_data, b_struct, b_take, b_data, *sched):
        a_loc = gather(a_struct, a_take, a_data)    # (per, ...) stacked
        b_loc = gather(b_struct, b_take, b_data)
        # this chip's (per, ...) schedule stack, one slice per K-panel
        hs_loc = tuple(r[0] for r in sched) if with_sched else None
        acc = jnp.zeros((m, n), a_data.dtype)
        for p in range(per):
            a_p = jax.tree.map(lambda x: x[p], a_loc)
            b_p = jax.tree.map(lambda x: x[p], b_loc)
            hs = tuple(x[p] for x in hs_loc) if with_sched else None
            c_p = _local_spgemm(a_p, b_p, None, hash_sched=hs, **statics)
            # the reduce-scatter merge is an elementwise +, which is the
            # semiring add for every semiring this path admits (boolean
            # partials are 0/1 counts, thresholded after the scatter)
            # verify: allow(no-densify) -- the reduce-scatter merge is
            # defined on the dense partial; re-sparsified right after
            acc = acc + c_p.to_dense()  # verify: allow(no-densify)
        part = jax.lax.psum_scatter(acc, axis, scatter_dimension=0,
                                    tiled=True)
        if boolean:
            part = (part > 0).astype(acc.dtype)
        c_loc = CSR.from_dense(part, cap=plan.out_cap)
        return jax.tree.map(lambda x: x[None], c_loc)

    in_specs = (P(axis), P(axis), P(), P(axis), P(axis), P()) + \
        ((P(axis), P(axis), P(axis)) if with_sched else ())
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=P(axis), check_vma=False)


def plan_spgemm_summa(a: CSR, b: CSR, n_shards: int,
                      k_panels: int | None = None, *,
                      algorithm: str = "auto",
                      semiring: str | Semiring = "plus_times",
                      n_bins: int = 8, cache: bool = True) -> SummaPlan:
    """Inspect the outer-product SUMMA schedule once and freeze it.

    Runs the *global* plan first (resolving ``auto`` and yielding the exact
    ``row_nnz_c`` that sizes the row-sharded output), then one plan per
    (chip, panel) local product.  Cached under a ``("summa", digest)`` key
    in the shared LRU.

    The merge is a dense-accumulator reduce-scatter, so the semiring's
    ``add`` must be arithmetic ``+`` with identity 0: ``plus_times`` /
    ``plus_first`` directly, ``boolean`` via a post-scatter threshold.
    ``min_plus`` (identity +inf) is rejected.
    """
    sr = resolve_semiring(semiring)
    if sr.name == "min_plus":
        raise NotImplementedError(
            "spgemm_summa's reduce-scatter merge needs an add-identity of "
            "0; min_plus (identity +inf) needs the 1D path (spgemm_1d)")
    m = a.n_rows
    if m % n_shards != 0:
        raise ValueError(
            f"reduce-scatter tiles C rows equally: n_rows={m} must be "
            f"divisible by the mesh axis size {n_shards}")
    bounds = summa_panel_bounds(a.n_cols, n_shards, k_panels)
    k_panels = len(bounds)

    h = hashlib.blake2b(digest_size=16)
    h.update(structure_key(a))
    h.update(structure_key(b))
    h.update(repr((n_shards, k_panels, sr.name, algorithm,
                   n_bins)).encode())
    key = ("summa", h.digest())
    if cache:
        hit = cache_lookup(key)
        if hit is not None:
            return hit

    # Global inspection: exact output structure -> per-row-shard capacity,
    # and the recipe's algorithm choice resolved on the whole product.
    gplan = plan_spgemm(a, b, algorithm=algorithm, semiring=sr.name,
                        n_bins=n_bins, use_case="dist", cache=cache)
    algo = gplan.algorithm
    row_nnz = np.asarray(gplan.row_nnz_c, np.int64)
    rows_per = m // n_shards
    out_cap = _pad8(int(row_nnz.reshape(n_shards, rows_per).sum(axis=1)
                        .max()))
    row_starts_out = tuple(range(0, m + 1, rows_per))

    a_parts, b_parts, _, a_take, b_take = _shard_summa(a, b, n_shards,
                                                       k_panels)
    per = k_panels // n_shards
    plans = []
    for s in range(n_shards):
        for p in range(per):
            a_p = jax.tree.map(lambda x: x[s, p], a_parts)
            b_p = jax.tree.map(lambda x: x[s, p], b_parts)
            plans.append(plan_spgemm(a_p, b_p, algorithm=algo,
                                     semiring=sr.name, n_bins=n_bins,
                                     use_case="dist", cache=cache))

    # Per-(chip, panel) frozen hash schedules, stacked (S, per, ...):
    # every panel plan shares n_bins and the global row count m, so the
    # arrays are shape-uniform.  Boolean is general (post-scatter
    # threshold notwithstanding, the *local* product is a boolean-semiring
    # call) and keeps the jnp body, exactly like the 1D path.
    table_size = 0
    hash_sched = None
    if algo in ("hash", "hash_vector") and sr.name == "plus_times":
        table_size = max(p.table_size for p in plans)

        def stack2(field):
            rows = [jnp.stack([field(plans[s * per + p])
                               for p in range(per)])
                    for s in range(n_shards)]
            return jnp.stack(rows)

        hash_sched = (stack2(lambda p: p.offsets),
                      stack2(lambda p: p.bin_tsize),
                      stack2(lambda p: p.indptr_c))

    plan = SummaPlan(
        key=key, n_shards=n_shards, k_panels=k_panels, bounds=bounds,
        algorithm=algo, semiring=sr.name, shape_a=a.shape, shape_b=b.shape,
        cap_a=a.cap, cap_b=b.cap, nnz_a=int(a.nnz), nnz_b=int(b.nnz),
        plans=tuple(plans),
        a_struct=dataclasses.replace(
            a_parts, data=jnp.zeros_like(a_parts.data)),
        b_struct=dataclasses.replace(
            b_parts, data=jnp.zeros_like(b_parts.data)),
        a_take=a_take, b_take=b_take,
        cap_c=_pad8(max(p.cap_c for p in plans)),
        flop_cap=max(max(p.flop_cap for p in plans), 1),
        row_cap=max(p.row_cap for p in plans),
        k_width=max(p.k_width for p in plans),
        out_cap=out_cap, row_starts_out=row_starts_out,
        nnz_c=gplan.nnz_c, table_size=table_size, hash_sched=hash_sched)
    if cache:
        cache_store(key, plan)
    return plan


def spgemm_summa(mesh: Mesh, a: CSR, b: CSR, axis: str = "data",
                 k_panels: int | None = None, *, algorithm: str = "auto",
                 semiring: str | Semiring = "plus_times", n_bins: int = 8,
                 plan: SummaPlan | None = None) -> ShardedCSR:
    """Outer-product SUMMA over one mesh axis; C comes back row-sharded.

    Chip ``s`` owns K-panels ``[s*per, (s+1)*per)`` of A's column blocks
    and B's row blocks, streams them through planned sparse local products,
    and the dense partial C's are merged by a reduce-scatter along
    ``axis``.  ``k_panels`` (default: one per chip) sets the panel count
    of the stream -- invalid values raise, see :func:`summa_panel_bounds`.
    """
    n_shards = mesh.shape[axis]
    if plan is None:
        plan = plan_spgemm_summa(a, b, n_shards, k_panels,
                                 algorithm=algorithm, semiring=semiring,
                                 n_bins=n_bins)
    else:
        if plan.n_shards != n_shards:
            raise ValueError(f"plan is for {plan.n_shards} shards, mesh "
                             f"axis {axis!r} has {n_shards}")
        if k_panels is not None and plan.k_panels != k_panels:
            raise ValueError(f"plan holds k_panels={plan.k_panels}, "
                             f"call requested {k_panels}")
    return plan.execute(mesh, a, b, axis=axis)


# ----------------------------------------------------------------------------
# Propagation-blocking SUMMA: bucket exchange instead of dense reduce-scatter
# ----------------------------------------------------------------------------

@dataclass(frozen=True)
class PBSummaPlan:
    """Frozen propagation-blocking merge for the outer-product schedule.

    The classic SUMMA executor (:class:`SummaPlan`) merges K-panel
    partials through a *dense* ``(m, n)`` accumulator and a
    ``psum_scatter`` -- O(m*n) words on the wire regardless of sparsity.
    This plan replaces that merge with the PB exchange (DESIGN.md
    section 18, after Gu et al.'s propagation blocking): the inspector
    expands every panel partial product, assigns it to the chip that
    owns its output *row* (bucket = destination chip), and freezes per
    ``(source, dest)`` gather indices into the chips' panel value
    arrays.  Execute is then numeric-only and three steps per chip:

      1. **scatter** -- multiply local panel values into per-destination
         bucket buffers (the single-node PB scatter kernel, buckets =
         chips),
      2. **exchange** -- one ``all_to_all`` routes each bucket to its
         row owner: O(flop) words total, the communication-avoiding win
         on low-compression products where ``flop ~ nnz(C) << m*n``,
      3. **merge** -- segment-add received products into the frozen
         local output slots (the single-node PB merge kernel; the
         sequential bucket grid makes cross-source accumulation into one
         slot safe).

    Values stay out of the plan: like :class:`SummaPlan`, execute
    re-gathers only ``data`` through the frozen ``a_take``/``b_take``.
    plus_times only (the Pallas pair's contract).
    """
    key: tuple = dataclasses.field(repr=False)
    n_shards: int
    k_panels: int
    bounds: Tuple[Tuple[int, int], ...]
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]
    cap_a: int
    cap_b: int
    nnz_a: int
    nnz_b: int
    a_struct: CSR = dataclasses.field(repr=False)   # stacked, data zeroed
    b_struct: CSR = dataclasses.field(repr=False)
    a_take: jax.Array = dataclasses.field(repr=False)
    b_take: jax.Array = dataclasses.field(repr=False)
    #: per-(source, dest) product capacity: the exchange moves
    #: ``n_shards * xcap`` f32 words per chip
    xcap: int
    #: ``[s, d, lane]`` -> flattened ``(per * panel_cap)`` slot in chip
    #: s's gathered panel values (A resp. B)
    src_a: jax.Array = dataclasses.field(repr=False)
    src_b: jax.Array = dataclasses.field(repr=False)
    pair_nnz: jax.Array = dataclasses.field(repr=False)   # (S, S) [src, dst]
    #: ``[d, s, lane]`` -> chip d's local output slot for the lane-th
    #: product received from source s (dest-major: lives on the receiver)
    seg: jax.Array = dataclasses.field(repr=False)
    recv_nnz: jax.Array = dataclasses.field(repr=False)   # (S, S) [dst, src]
    cols_out: jax.Array = dataclasses.field(repr=False)   # (S, out_cap)
    indptr_out: jax.Array = dataclasses.field(repr=False)  # (S, rows_per+1)
    out_nnz: jax.Array = dataclasses.field(repr=False)    # (S,)
    out_cap: int
    row_starts_out: Tuple[int, ...]
    nnz_c: int
    total_flop: int
    semiring: str = "plus_times"
    provenance: str = "planned"

    def check_structure(self, a: CSR, b: CSR) -> None:
        assert a.shape == self.shape_a and b.shape == self.shape_b, \
            f"plan is for {self.shape_a}x{self.shape_b}, " \
            f"got {a.shape}x{b.shape}"
        assert a.cap == self.cap_a and b.cap == self.cap_b, \
            "operand capacities differ from the planned structure"
        for op, planned in ((a, self.nnz_a), (b, self.nnz_b)):
            if not isinstance(op.nnz, jax.core.Tracer):
                assert int(op.nnz) == planned, \
                    "operand nnz differs from the planned structure"

    def execute(self, mesh: Mesh, a: CSR, b: CSR,
                axis: str = "data") -> ShardedCSR:
        """Numeric phase only: gather values, scatter / exchange / merge."""
        self.check_structure(a, b)
        fn = _memoized_executor(self, (mesh, axis),
                                lambda: _build_pb_summa_fn(self, mesh, axis))
        out = fn(self.a_struct, self.a_take, a.data,
                 self.b_struct, self.b_take, b.data,
                 self.src_a, self.src_b, self.pair_nnz, self.seg,
                 self.recv_nnz, self.cols_out, self.indptr_out,
                 self.out_nnz)
        return ShardedCSR(out, self.row_starts_out, self.shape_a[0])

    __call__ = execute


def _build_pb_summa_fn(plan: PBSummaPlan, mesh: Mesh, axis: str):
    """SPMD body: gather panel values, PB-scatter into per-chip buckets,
    all_to_all exchange, PB-merge into the frozen local output."""
    from repro.kernels.spgemm_pb import ops as pb_ops
    n = plan.shape_b[1]
    rows_per = plan.shape_a[0] // plan.n_shards

    def flatvals(struct, take, data):
        s_loc = jax.tree.map(lambda x: x[0], struct)     # (per, ...) local
        lane = jnp.arange(take.shape[-1], dtype=jnp.int32)
        live = lane[None, :] < s_loc.nnz[:, None]        # (per, cap)
        return jnp.where(live, data[take[0]], 0).astype(
            data.dtype).reshape(-1)                      # (per * cap,)

    def local(a_struct, a_take, a_data, b_struct, b_take, b_data,
              src_a, src_b, pair_nnz, seg, recv_nnz, cols_out,
              indptr_out, out_nnz):
        av = flatvals(a_struct, a_take, a_data)
        bv = flatvals(b_struct, b_take, b_data)
        # scatter: bucket g holds this chip's products destined for chip g
        pp = pb_ops.pb_scatter(av, bv, src_a[0], src_b[0], pair_nnz[0])
        # exchange: row d goes to chip d; received row s came from chip s
        pp = jax.lax.all_to_all(pp, axis, split_axis=0, concat_axis=0)
        data = pb_ops.pb_merge(pp, seg[0], recv_nnz[0], plan.out_cap)
        lane = jnp.arange(plan.out_cap, dtype=jnp.int32)
        valid = lane < out_nnz[0]
        c_loc = CSR(indptr_out[0], jnp.where(valid, cols_out[0], 0),
                    jnp.where(valid, data, 0).astype(a_data.dtype),
                    out_nnz[0], (rows_per, n), sorted_cols=True)
        return jax.tree.map(lambda x: x[None], c_loc)

    in_specs = (P(axis), P(axis), P(), P(axis), P(axis), P()) + \
        (P(axis),) * 8
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=P(axis), check_vma=False)


def plan_spgemm_pb_summa(a: CSR, b: CSR, n_shards: int,
                         k_panels: int | None = None, *,
                         cache: bool = True) -> PBSummaPlan:
    """Inspect the PB-merge SUMMA schedule once and freeze it.

    Reuses :func:`_shard_summa`'s panel decomposition (so the frozen
    operand layout is bitwise the classic SUMMA one), then expands every
    panel's partial products on the host, derives the exact global output
    structure, and packs per-``(source, dest)`` bucket gather indices.
    Cached in the shared LRU under the classic plan's ``("summa", ...)``
    kind with a ``pb-merge`` marker in the digest.
    """
    from .schedule import guard_i32_flop
    sr = resolve_semiring("plus_times")
    m = a.n_rows
    if m % n_shards != 0:
        raise ValueError(
            f"the PB exchange tiles C rows equally: n_rows={m} must be "
            f"divisible by the mesh axis size {n_shards}")
    bounds = summa_panel_bounds(a.n_cols, n_shards, k_panels)
    k_panels = len(bounds)

    h = hashlib.blake2b(digest_size=16)
    h.update(structure_key(a))
    h.update(structure_key(b))
    h.update(repr(("pb-merge", n_shards, k_panels, sr.name)).encode())
    key = ("summa", h.digest())
    if cache:
        hit = cache_lookup(key)
        if hit is not None:
            return hit

    a_parts, b_parts, _, a_take, b_take = _shard_summa(a, b, n_shards,
                                                       k_panels)
    per = k_panels // n_shards
    cap_pa = a_parts.indices.shape[-1]
    cap_pb = b_parts.indices.shape[-1]
    pa = np.asarray(a_parts.indptr, np.int64)    # (S, per, m+1)
    ia = np.asarray(a_parts.indices)
    na = np.asarray(a_parts.nnz, np.int64)
    pbp = np.asarray(b_parts.indptr, np.int64)   # (S, per, step+1)
    ib = np.asarray(b_parts.indices)

    # Expand every panel's partial products: one (row, col, src-slot-a,
    # src-slot-b, source-chip) record per scalar multiply.  Slots index
    # the *flattened* (per * cap) gathered panel value arrays -- exactly
    # the layout the executor's ``flatvals`` produces.
    R, C, SA, SB, SRC = [], [], [], [], []
    for s in range(n_shards):
        for p in range(per):
            cnt_a = int(na[s, p])
            if cnt_a == 0:
                continue
            rows = np.repeat(np.arange(m), np.diff(pa[s, p]))[:cnt_a]
            kloc = ia[s, p, :cnt_a]                 # panel-local column
            starts = pbp[s, p][kloc]
            counts = pbp[s, p][kloc + 1] - starts
            total = int(counts.sum())
            if total == 0:
                continue
            j = np.repeat(np.arange(cnt_a), counts)
            off = np.arange(total) - np.repeat(
                np.cumsum(counts) - counts, counts)
            t = starts[j] + off
            R.append(rows[j])
            C.append(ib[s, p][t])
            SA.append((p * cap_pa + j).astype(np.int64))
            SB.append((p * cap_pb + t).astype(np.int64))
            SRC.append(np.full(total, s, np.int64))
    if R:
        R = np.concatenate(R); C = np.concatenate(C)
        SA = np.concatenate(SA); SB = np.concatenate(SB)
        SRC = np.concatenate(SRC)
    else:
        R = C = SA = SB = SRC = np.zeros(0, np.int64)
    total_flop = int(R.size)
    guard_i32_flop(total_flop, what="pb-summa expansion")

    # Exact global output structure (sorted rows-major), sliced per dest
    # chip: rows are contiguous per chip, so a chip's slots are the
    # global slots minus its first row's offset.
    rows_per = m // n_shards
    uo = np.lexsort((C, R))
    Rs, Cs = R[uo], C[uo]
    new = np.ones(total_flop, bool)
    if total_flop:
        new[1:] = (Rs[1:] != Rs[:-1]) | (Cs[1:] != Cs[:-1])
    slot_sorted = np.cumsum(new) - 1
    slot = np.empty(total_flop, np.int64)
    slot[uo] = slot_sorted
    nnz_c = int(new.sum()) if total_flop else 0
    ur, uc = Rs[new] if total_flop else Rs, Cs[new] if total_flop else Cs
    row_nnz = np.bincount(ur, minlength=m)
    g_indptr = np.zeros(m + 1, np.int64)
    np.cumsum(row_nnz, out=g_indptr[1:])
    per_dest = (g_indptr[np.arange(1, n_shards + 1) * rows_per]
                - g_indptr[np.arange(n_shards) * rows_per])
    out_cap = _pad8(max(int(per_dest.max(initial=0)), 1))
    out_nnz = per_dest.astype(np.int32)
    cols_out = np.zeros((n_shards, out_cap), np.int32)
    indptr_out = np.zeros((n_shards, rows_per + 1), np.int32)
    for d in range(n_shards):
        lo, hi = int(g_indptr[d * rows_per]), \
            int(g_indptr[(d + 1) * rows_per])
        cols_out[d, :hi - lo] = uc[lo:hi]
        indptr_out[d] = (g_indptr[d * rows_per:(d + 1) * rows_per + 1]
                         - lo)

    # Pack (source, dest) buckets: bucket = destination chip (the row
    # owner).  ``seg`` is dest-major -- it rides on the receiver, mapping
    # each product that arrives from source s into a local output slot.
    dest = R // rows_per if total_flop else R
    pair = SRC * n_shards + dest
    pair_nnz = np.bincount(pair, minlength=n_shards * n_shards) \
        .reshape(n_shards, n_shards).astype(np.int32)
    xcap = _pad8(max(int(pair_nnz.max(initial=0)), 1))
    order = np.lexsort((C, R, pair))
    pr = pair[order]
    starts = np.zeros(n_shards * n_shards, np.int64)
    np.cumsum(pair_nnz.reshape(-1)[:-1], out=starts[1:])
    lane = np.arange(total_flop) - starts[pr]
    src_a = np.zeros((n_shards, n_shards, xcap), np.int32)
    src_b = np.zeros((n_shards, n_shards, xcap), np.int32)
    seg = np.full((n_shards, n_shards, xcap), out_cap - 1, np.int32)
    s_of, d_of = pr // n_shards, pr % n_shards
    src_a[s_of, d_of, lane] = SA[order]
    src_b[s_of, d_of, lane] = SB[order]
    seg[d_of, s_of, lane] = (slot[order]
                             - g_indptr[d_of * rows_per]).astype(np.int32)
    recv_nnz = pair_nnz.T.copy()

    plan = PBSummaPlan(
        key=key, n_shards=n_shards, k_panels=k_panels, bounds=bounds,
        shape_a=a.shape, shape_b=b.shape, cap_a=a.cap, cap_b=b.cap,
        nnz_a=int(a.nnz), nnz_b=int(b.nnz),
        a_struct=dataclasses.replace(
            a_parts, data=jnp.zeros_like(a_parts.data)),
        b_struct=dataclasses.replace(
            b_parts, data=jnp.zeros_like(b_parts.data)),
        a_take=a_take, b_take=b_take, xcap=xcap,
        src_a=jnp.asarray(src_a), src_b=jnp.asarray(src_b),
        pair_nnz=jnp.asarray(pair_nnz), seg=jnp.asarray(seg),
        recv_nnz=jnp.asarray(recv_nnz), cols_out=jnp.asarray(cols_out),
        indptr_out=jnp.asarray(indptr_out), out_nnz=jnp.asarray(out_nnz),
        out_cap=out_cap,
        row_starts_out=tuple(range(0, m + 1, rows_per)),
        nnz_c=nnz_c, total_flop=total_flop)
    if cache:
        cache_store(key, plan)
    return plan


def spgemm_pb_summa(mesh: Mesh, a: CSR, b: CSR, axis: str = "data",
                    k_panels: int | None = None, *,
                    plan: PBSummaPlan | None = None) -> ShardedCSR:
    """Outer-product SUMMA with the propagation-blocking merge.

    Same operand layout and K-panel stream as :func:`spgemm_summa`, but
    the partial-product merge is the PB bucket exchange (one
    ``all_to_all`` of O(flop) words) instead of the dense ``(m, n)``
    reduce-scatter -- the communication-avoiding lane for low-compression
    products.  plus_times only; C comes back row-sharded.
    """
    n_shards = mesh.shape[axis]
    if plan is None:
        plan = plan_spgemm_pb_summa(a, b, n_shards, k_panels)
    else:
        if plan.n_shards != n_shards:
            raise ValueError(f"plan is for {plan.n_shards} shards, mesh "
                             f"axis {axis!r} has {n_shards}")
        if k_panels is not None and plan.k_panels != k_panels:
            raise ValueError(f"plan holds k_panels={plan.k_panels}, "
                             f"call requested {k_panels}")
    return plan.execute(mesh, a, b, axis=axis)
