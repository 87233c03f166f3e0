"""Inspector-executor SpGEMM planner (DESIGN.md section 10).

The paper's two-phase method (Fig. 7) and ``RowsToThreads`` scheduling
(Fig. 6) are pure *inspection*: for a fixed sparsity structure they can be
computed once and reused across every numeric product.  That is exactly the
repeated-product shape of graph workloads (multi-source BFS iterations,
triangle counting, A.A chains) and of a serving system answering many
products over the same graph -- the symbolic/numeric split-and-reuse that
Deveci et al. (arXiv:1801.03065) make a first-class API in KokkosKernels.

:func:`plan_spgemm` runs the full inspection once -- flop counting, equal-
flop binning, per-bin hash-table sizing, the exact symbolic phase, and the
recipe's algorithm choice -- and freezes the result in a :class:`SpGEMMPlan`.
``plan.execute(a, b)`` (or ``spgemm(a, b, plan=plan)``) then runs only the
numeric work: no schedule, no symbolic kernel, no recipe, and -- because
every capacity in the plan is a deterministic static int -- no retracing
once each (algorithm, capacity) program is compiled.

Plans are cached under a **structure key**: a blake2b digest of each
operand's ``(shape, cap, nnz, indptr, indices)`` plus the request's
semantic fields (semiring, mask structure, complement flag, sortedness,
algorithm, use case, n_bins).  Values deliberately do not enter the key --
a re-weighted graph with the same adjacency hits the cached plan.
Invalidation is by construction: a structural change produces a different
key, and :func:`clear_plan_cache` empties the table wholesale.  Every key
leads with a string **kind** namespace -- ``"spgemm"`` here; the
distributed plans (``"dist_1d"``/``"summa"``) and chain plans
(``"chain"``/``"chain_1d"``/``"gram"``) share the same LRU under their
own kinds (:func:`plan_cache_stats` reports per-kind occupancy).

Planning is a host-side (eager) operation: the exact capacities must be
concrete Python ints to become static shapes.  ``execute`` is
trace-friendly -- it only calls the already-specialized numeric
primitives, and since the plan-frozen hash schedules ride as array
operands (not static arguments), the planned hash path runs unchanged
under ``jit``, ``vmap`` (a batched grid over members via the kernels'
``custom_vmap`` rule), and inside ``shard_map`` bodies (DESIGN.md
section 14).  ``spgemm_hash_jnp`` survives in the dispatch only as the
reference oracle and as the body for general semirings.

:func:`plan_spgemm` records three host spans (:mod:`repro.tracing`):
``repro.plan`` around the whole call, cache lookup included, and inside
it ``repro.plan.profile`` (flop profile, bins, table sizes) and
``repro.plan.symbolic`` (ESC symbolic through the nnz(C) sync).
"""
from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from .formats import CSR
from .semiring import Semiring, resolve_semiring
from . import schedule as sched
from .spgemm import (_canon_mask, _check_mask, finalize, spgemm_dense,
                     spgemm_esc, spgemm_hash_jnp, spgemm_heap, symbolic)


def structure_key(a: CSR) -> bytes:
    """Digest of a CSR's *structure* (pattern + static layout), not values.

    Covers shape, capacity, nnz, and the indptr/indices arrays (padded
    tails are zeros by the CSR contract, so whole-array hashing is
    deterministic).  Two CSRs with equal keys run identically through
    schedule + symbolic, which is what makes plan reuse sound.
    """
    cached = a.__dict__.get("_structure_digest")
    if cached is not None:
        return cached
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((a.shape, a.cap, int(a.nnz), a.sorted_cols)).encode())
    h.update(np.asarray(a.indptr).tobytes())
    h.update(np.asarray(a.indices).tobytes())
    digest = h.digest()
    # memoize on the (frozen, immutable-field) instance: jax arrays cannot
    # be mutated in place and dataclasses.replace builds a fresh object, so
    # the digest can never go stale; repeat lookups (the serving loop's
    # per-hop cache hits) skip the O(nnz) host transfer + hash
    object.__setattr__(a, "_structure_digest", digest)
    return digest


#: plan cache: PlanKey tuple -> SpGEMMPlan (insertion-ordered; LRU-bounded
#: so a serving loop over many structures cannot grow host/device memory
#: without bound -- each entry pins O(m) arrays plus the mask CSR)
_CACHE: dict = {}
_STATS = {"hits": 0, "misses": 0}
#: maximum cached plans; oldest-used evicted first.
PLAN_CACHE_CAPACITY = 256

#: every plan-kind namespace that may appear as a cache key's leading
#: string.  ``plan_cache_stats()["kinds"]`` reports a zero entry for each
#: registered kind even on a cold cache, so dashboards can key on a kind
#: unconditionally; new plan families register here when they add a kind.
PLAN_KINDS = ("spgemm", "dist_1d", "summa", "chain", "chain_1d", "gram",
              "batch", "batch_power", "bcsr", "pb")


def plan_cache_stats() -> dict:
    """Copy of the cache counters: ``{'hits', 'misses', 'size', 'kinds'}``.

    ``kinds`` counts live entries per plan *kind* -- the string namespace
    every key leads with: ``"spgemm"`` (single-node), ``"dist_1d"`` /
    ``"summa"`` (``core.distributed``), ``"chain"`` / ``"chain_1d"`` /
    ``"gram"`` (``core.chain``), ``"batch"`` / ``"batch_power"``
    (``core.batch``).  Every kind in :data:`PLAN_KINDS` is present in the
    dict -- zero when it has no live entries -- so a cold cache never
    KeyErrors a dashboard.  All kinds share one LRU, one capacity bound
    (:data:`PLAN_CACHE_CAPACITY`), and one :func:`clear_plan_cache`.
    """
    kinds: dict = {kind: 0 for kind in PLAN_KINDS}
    for key in _CACHE:
        kind = key[0] if isinstance(key[0], str) else "spgemm"
        kinds[kind] = kinds.get(kind, 0) + 1
    return {**_STATS, "size": len(_CACHE), "kinds": kinds}


def clear_plan_cache() -> None:
    """Empty the shared plan LRU (all kinds) and reset the hit/miss
    counters.  Plans already held by callers stay valid -- the cache only
    governs lookup, never plan lifetime."""
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0


def cache_lookup(key: tuple):
    """Consult the shared plan LRU (counts a hit or a miss).

    The cache is deliberately kind-agnostic: single-node ``SpGEMMPlan``s and
    the distributed plans of ``core.distributed`` live in one table under
    disjoint key namespaces, so one capacity bound and one ``clear`` govern
    every frozen inspection product.
    """
    hit = _CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        _CACHE[key] = _CACHE.pop(key)              # LRU: move to newest
        return hit
    _STATS["misses"] += 1
    return None


def cache_store(key: tuple, value) -> None:
    """Insert into the shared plan LRU, evicting least-recent past capacity.

    Pop-before-insert: a re-stored existing key must move to the newest
    position, exactly like a :func:`cache_lookup` hit.  Plain
    ``_CACHE[key] = value`` would overwrite in place and keep the key's
    *old* dict position, so a just-refreshed plan could be evicted as
    "least recent" by the very next store (regression-pinned by
    ``tests/test_plan.py::test_plan_cache_restore_refreshes_recency``).
    """
    _CACHE.pop(key, None)                          # refresh recency on re-store
    _CACHE[key] = value
    while len(_CACHE) > PLAN_CACHE_CAPACITY:
        _CACHE.pop(next(iter(_CACHE)))             # evict least-recent


def _plan_key(a: CSR, b: CSR, mask: Optional[CSR], sr_name: str,
              complement_mask: bool, sorted_output: bool, algorithm: str,
              use_case: Optional[str], n_bins: int) -> tuple:
    # "spgemm" is this key's kind namespace: every plan family in the
    # shared LRU (dist_1d / summa / chain / chain_1d / gram) leads with a
    # distinct string, so keys can never collide across kinds and
    # plan_cache_stats can report per-kind occupancy.
    return ("spgemm", structure_key(a), structure_key(b),
            None if mask is None else structure_key(mask),
            sr_name, complement_mask, sorted_output, algorithm, use_case,
            n_bins)


@dataclass(frozen=True)
class SpGEMMPlan:
    """Frozen product recipe for one (A-structure, B-structure) pair.

    Everything the executor needs, nothing recomputed: the flop profile and
    equal-flop bin offsets (Fig. 6), the per-bin power-of-two hash-table
    sizes and the static scratch allocation (Fig. 7 lines 9-12), the exact
    ``indptr_c``/capacities from the symbolic phase, and the recipe's
    algorithm choice.  All capacities are Python ints -- static shapes --
    so structure-identical executes hit the jit dispatch cache.
    """
    key: tuple = dataclasses.field(repr=False)
    algorithm: str
    semiring: str
    complement_mask: bool
    sorted_output: bool
    mask: Optional[CSR] = dataclasses.field(repr=False)
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]
    cap_a: int
    cap_b: int
    nnz_a: int
    nnz_b: int
    n_bins: int
    # --- inspection products -------------------------------------------
    flop: jax.Array = dataclasses.field(repr=False)      # per-row flop
    total_flop: int
    flop_cap: int            # exact expansion bound for esc/jnp-hash paths
    offsets: jax.Array = dataclasses.field(repr=False)   # (n_bins + 1,)
    bin_tsize: jax.Array = dataclasses.field(repr=False)  # (n_bins,) p2
    table_size: int          # static scratch allocation (bin max, p2)
    row_nnz_c: jax.Array = dataclasses.field(repr=False)
    indptr_c: jax.Array = dataclasses.field(repr=False)
    nnz_c: int
    cap_c: int               # exact nnz(C) as a static capacity
    row_cap: int             # heap: max nnz(c_i*)
    k_width: int             # heap: max nnz(a_i*)
    #: where the algorithm choice came from: ``"explicit"`` (caller pinned
    #: it), ``"heuristic"`` (Table-4 recipe), or ``"measured"`` (autotune
    #: DB / microbenchmark, DESIGN.md section 16).
    provenance: str = "explicit"
    #: BCSR routing only (``algorithm == "bcsr"``): the tile shape the CSR
    #: operands are re-blocked into and the frozen block-level plan
    #: (:class:`repro.core.bcsr.BCSRPlan`) the execute runs through.
    block: Optional[Tuple[int, int]] = None
    bcsr_plan: object = dataclasses.field(default=None, repr=False)
    #: PB routing only (``algorithm == "pb"``): the frozen propagation-
    #: blocking plan (:class:`repro.core.pb.PBPlan`) the execute runs
    #: through -- bucket geometry and output structure both frozen, so
    #: repeat executes stay numeric-only (DESIGN.md section 18).
    pb_plan: object = dataclasses.field(default=None, repr=False)

    # -------------------------------------------------------------------
    def check_structure(self, a: CSR, b: CSR, strict: bool = False) -> None:
        """Cheap (shapes/caps/nnz) or strict (re-hash) structure check.

        Executing a plan against a *different* structure silently produces
        wrong capacities, so the cheap check always runs; ``strict=True``
        re-digests both operands (costs a host transfer -- debugging aid).
        """
        assert a.shape == self.shape_a and b.shape == self.shape_b, \
            f"plan is for {self.shape_a}x{self.shape_b}, " \
            f"got {a.shape}x{b.shape}"
        assert a.cap == self.cap_a and b.cap == self.cap_b, \
            "operand capacities differ from the planned structure"
        for op, planned in ((a, self.nnz_a), (b, self.nnz_b)):
            # each operand guarded independently: jit over just one of
            # them (e.g. a re-weighted B in a serving loop) must not trip
            # a concretization error on the other's check
            if not isinstance(op.nnz, jax.core.Tracer):
                assert int(op.nnz) == planned, \
                    "operand nnz differs from the planned structure " \
                    "(replan or clear_plan_cache)"
        if strict:
            assert (structure_key(a), structure_key(b)) == self.key[1:3], \
                "operand structure differs from the planned structure"

    def execute(self, a: CSR, b: CSR,
                sorted_output: Optional[bool] = None) -> CSR:
        """Numeric phase only: same contract as ``spgemm`` with this plan's
        recorded algorithm/semiring/mask, zero re-inspection.

        ``sorted_output`` overrides the plan's recorded sortedness for this
        call (``None`` keeps it).  Sorting is a pure epilogue
        (:func:`repro.core.spgemm.finalize`) -- it changes no capacity and
        no accumulator state -- so one cached plan legally serves both the
        sorted and the unsorted consumer; the chain executor uses this to
        keep intermediates unsorted under a plan whose final output is
        sorted on request (DESIGN.md section 12).
        """
        self.check_structure(a, b)
        sr = resolve_semiring(self.semiring)
        general = sr.name != "plus_times" or self.mask is not None
        algo = self.algorithm
        if algo == "dense":
            out = spgemm_dense(a, b, self.cap_c, semiring=sr,
                                 mask=self.mask,
                                 complement_mask=self.complement_mask)
        elif algo == "esc":
            out = spgemm_esc(a, b, self.cap_c, flop_cap=self.flop_cap,
                               semiring=sr, mask=self.mask,
                               complement_mask=self.complement_mask)
        elif algo == "heap":
            out = spgemm_heap(a, b, row_cap=self.row_cap,
                                k_width=self.k_width, cap_c=self.cap_c,
                                semiring=sr, mask=self.mask,
                                complement_mask=self.complement_mask)
        elif algo == "bcsr":
            # re-block the CSR operands into the planned tile grid (bcap
            # pinned by the plan so the conversion is shape-stable under
            # trace), run the frozen block plan, flatten back to CSR.
            from .bcsr import BCSRPlan
            from .formats import BCSR, bcsr_to_csr
            bp = self.bcsr_plan
            assert isinstance(bp, BCSRPlan) and self.block is not None, \
                "bcsr plan is missing its nested block plan"
            ab = BCSR.from_dense(a.to_dense(), bp.block_a, bcap=bp.bcap_a)  # verify: allow(no-densify)
            bb = BCSR.from_dense(b.to_dense(), bp.block_b, bcap=bp.bcap_b)  # verify: allow(no-densify)
            out = bcsr_to_csr(bp.execute(ab, bb), cap=self.cap_c)
        elif algo == "pb":
            # run the nested propagation-blocking plan (scatter + merge
            # over frozen bucket geometry); pad the exact-capacity output
            # up to this plan's cap_c when bucket_caps rounded it.
            from .pb import PBPlan
            pbp = self.pb_plan
            assert isinstance(pbp, PBPlan), \
                "pb plan is missing its nested bucket plan"
            out = pbp.execute(a, b)
            if out.cap < self.cap_c:
                pad = self.cap_c - out.cap
                out = CSR(out.indptr, jnp.pad(out.indices, (0, pad)),
                          jnp.pad(out.data, (0, pad)), out.nnz, out.shape,
                          out.sorted_cols)
        elif algo in ("hash", "hash_vector", "hash_jnp"):
            if general or algo == "hash_jnp":
                out = spgemm_hash_jnp(a, b, self.cap_c,
                                        flop_cap=self.flop_cap, semiring=sr,
                                        mask=self.mask,
                                        complement_mask=self.complement_mask)
            else:
                from repro.kernels.spgemm_hash import ops as hash_ops
                out = hash_ops.spgemm_hash(
                    a, b, self.cap_c, vector=(algo == "hash_vector"),
                    table_size=self.table_size,
                    schedule=(self.offsets, self.bin_tsize),
                    indptr_c=self.indptr_c)
        else:
            raise ValueError(f"plan holds unknown algorithm {algo!r}")
        so = self.sorted_output if sorted_output is None else sorted_output
        return finalize(out, so)

    __call__ = execute

    def kernel_counts(self, a: CSR, b: CSR) -> dict[str, int]:
        """Run this plan's numeric hash kernel once with its counters on
        and return them (``kernels.spgemm_hash.kernel.COUNTERS``), summed
        over members.  They depend on the structure only, so they count
        every ``execute`` of this plan.  Raises ``ValueError`` for a plan
        that does not run the kernel (not ``hash``/``hash_vector``
        plus-times unmasked) and where ``total_flop`` reaches 2^31, past
        the counters' int32."""
        if (self.algorithm not in ("hash", "hash_vector")
                or self.semiring != "plus_times" or self.mask is not None):
            raise ValueError(f"plan {self.algorithm!r} ({self.semiring}, "
                             f"masked={self.mask is not None}) does not run "
                             "the hash kernel")
        if self.total_flop >= 2 ** 31:
            raise ValueError(f"total_flop {self.total_flop} would overflow "
                             "the kernel's int32 counters")
        self.check_structure(a, b)
        from repro.kernels.spgemm_hash import ops as hash_ops
        return hash_ops.numeric_counts(
            a, b, self.cap_c, schedule=(self.offsets, self.bin_tsize),
            table_size=self.table_size,
            vector=(self.algorithm == "hash_vector"))


@tracing.span("repro.plan")
def plan_spgemm(a: CSR, b: CSR, *, algorithm: str = "auto",
                semiring: str | Semiring = "plus_times",
                mask: Optional[CSR] = None, complement_mask: bool = False,
                sorted_output: bool = False, use_case: Optional[str] = None,
                n_bins: int = 8, cache: bool = True,
                bucket_caps: bool = False, a_row_nnz=None,
                autotune: bool = False, autotune_db=None,
                block: Tuple[int, int] = (8, 8),
                _flop_cap: Optional[int] = None) -> SpGEMMPlan:
    """Run the full inspection once and freeze it as a :class:`SpGEMMPlan`.

    With ``cache=True`` (default) the structure-keyed cache is consulted
    first: a structure-identical repeat request returns the existing plan
    and skips schedule + symbolic + recipe entirely.

    ``autotune=True`` (with ``algorithm="auto"``) resolves the algorithm
    through the measured recipe instead of the Table-4 heuristics: the
    persistent autotune DB (:mod:`repro.autotune`) is consulted under the
    structure/backend key, a miss microbenchmarks the candidates on the
    actual operands and persists the winner, and any DB trouble degrades
    to the heuristic with a warning.  The plan records where its choice
    came from in :attr:`SpGEMMPlan.provenance` (``"measured"`` vs
    ``"heuristic"`` vs ``"explicit"``), a winning hash-table-size variant
    is applied to the frozen schedule, and ``autotune_db`` overrides the
    default DB path.  Autotuned and heuristic requests are distinct plan
    cache entries.

    ``bucket_caps=True`` rounds the static capacities (``cap_c``,
    ``flop_cap``, heap ``row_cap``) up to powers of two.  Exact capacities
    (the default) allocate nothing beyond nnz(C), but every distinct
    structure then compiles its own numeric program; bucketing trades a
    <2x allocation slack for program sharing across *similar* structures
    -- the right call inside loops whose structure drifts every iteration
    (e.g. BFS frontiers, MCL expansion) where exactness would retrace
    each hop.

    ``a_row_nnz`` marks A as a chain intermediate: pass the previous
    stage's recorded ``plan.row_nnz_c`` and the recipe's A-side statistics
    come from that recorded structure instead of the handed-in buffer
    (``recipe.recommend``'s mid-chain hook; used by ``core.chain``).

    ``block`` is the tile shape the ``"bcsr"`` routing re-blocks the CSR
    operands into (A tiles ``block``, B tiles ``(block[1], block[1])``);
    it only matters when the resolved algorithm is ``"bcsr"`` (explicit,
    recipe block-density routing, or a measured autotune lane) -- the plan
    then nests a frozen :class:`repro.core.bcsr.BCSRPlan` built at
    planning time, so repeat executes stay numeric-only at both
    granularities (DESIGN.md section 17).

    ``_flop_cap`` (internal, for planners of several products of one
    shape) replaces the exact flop as ESC ``symbolic``'s static expansion
    bound; it must be at least this product's flop.  ``symbolic``'s
    results do not depend on the bound, so products planned with one
    common bound share one compiled program (``plan_spgemm_1d``'s shards).
    """
    sr = resolve_semiring(semiring)
    arn_digest = None
    if a_row_nnz is not None:
        # a_row_nnz can steer the recipe's auto choice, so it must reach
        # the cache key; digest rather than store the array itself.
        arn_digest = hashlib.blake2b(np.asarray(a_row_nnz).tobytes(),
                                     digest_size=8).digest()
    block = tuple(block)
    key = _plan_key(a, b, mask, sr.name, complement_mask, sorted_output,
                    algorithm, use_case, n_bins) + (bucket_caps, arn_digest,
                                                    autotune, block,
                                                    _flop_cap)
    if cache:
        hit = cache_lookup(key)
        if hit is not None:
            return hit

    from repro.kernels.spgemm_hash import kernel as HK
    _check_mask(a, b, mask)
    mask = _canon_mask(mask)
    n = b.n_cols

    with tracing.span("repro.plan.profile"):
        # Fig. 6: flop profile + equal-flop bins.  The eager form is the
        # same code path make_schedule jits, but here the inputs are
        # concrete so the int32 overflow guard raises loudly instead of
        # mis-binning.
        flop, offsets, tsize = sched.make_schedule_eager(a, b, n_bins)
        max_row_flop = int(jnp.max(flop)) if flop.size else 0
        total_flop = int(jnp.sum(flop))

        # Fig. 7 lines 9-12: static scratch allocation = global-max p2
        # bound; per-bin effective sizes ride in the plan as prefetched
        # scalars.
        table_size = max(sched.lowest_p2(min(max_row_flop, n) + 1),
                         HK.CHUNK)
        bin_tsize = sched.bin_table_sizes(tsize, n, table_size,
                                          floor=HK.CHUNK)

    # Symbolic phase with the exact flop bound -- the worst-case
    # O(cap_a * min(cap_b, n)) default buffer is never allocated on replan.
    flop_cap = max(total_flop, 1)
    if _flop_cap is not None:
        if _flop_cap < total_flop:
            raise ValueError(f"_flop_cap {_flop_cap} is below the product's "
                             f"flop {total_flop}")
        flop_cap = _flop_cap
    if bucket_caps:
        flop_cap = sched.lowest_p2(flop_cap)
    with tracing.span("repro.plan.symbolic", flop_cap=flop_cap):
        row_nnz_c, indptr_c, _, _ = symbolic(
            a, b, mask=mask, complement_mask=complement_mask,
            flop_cap=flop_cap)
        nnz_c = int(jnp.sum(row_nnz_c))
    cap_c = max(nnz_c, 1)
    row_cap = max(int(jnp.max(row_nnz_c)), 1)
    k_width = max(int(jnp.max(a.row_nnz())), 1)
    if bucket_caps:
        cap_c = sched.lowest_p2(cap_c)
        row_cap = sched.lowest_p2(row_cap)

    if algorithm == "heap" and not (a.sorted_cols and b.sorted_cols):
        # match the direct dispatcher: an explicit heap request on
        # unsorted inputs fails loudly (spgemm_heap's own contract)
        raise AssertionError("heap path requires sorted inputs")
    provenance = "explicit"
    table_scale = 1
    if algorithm == "auto":
        uc = use_case if use_case is not None else \
            ("masked" if mask is not None else "AxA")
        if autotune:
            from repro.autotune import measured_recommend
            choice = measured_recommend(
                a, b, sorted_output=sorted_output, semiring=sr.name,
                mask=mask, complement_mask=complement_mask,
                row_nnz_c=row_nnz_c, db=autotune_db)
            if choice is not None:
                algorithm = choice.algorithm
                table_scale = choice.table_scale
                provenance = "measured"
        if algorithm == "auto":      # no autotune, or DB degraded
            from .recipe import recommend
            algorithm, _ = recommend(a, b, sorted_output=sorted_output,
                                     use_case=uc, semiring=sr.name,
                                     mask=mask,
                                     complement_mask=complement_mask,
                                     row_nnz_c=row_nnz_c,
                                     a_row_nnz=a_row_nnz)
            provenance = "heuristic"
        if algorithm == "heap" and not (a.sorted_cols and b.sorted_cols):
            # recipe picked heap on its merits, but the inputs cannot feed
            # it; hash keeps the unsorted contract
            algorithm = "hash"
    if table_scale != 1 and algorithm in ("hash", "hash_vector"):
        # winning table-size variant: scale the static scratch allocation
        # and per-bin effective sizes together.  Everything stays p2
        # (p2 * p2-scale) and clipped to [CHUNK, table_size] with the
        # scratch capped at p2(n_cols + 1) -- a table wider than every
        # column is pure waste -- so every schedule VC of
        # repro.verify.bounds keeps holding on the scaled plan.
        table_size = max(min(table_size * table_scale,
                             sched.lowest_p2(n + 1)), HK.CHUNK)
        bin_tsize = jnp.clip(bin_tsize.astype(jnp.int32) * table_scale,
                             jnp.int32(HK.CHUNK), jnp.int32(table_size))
    bcsr_plan = None
    if algorithm == "bcsr":
        if sr.name != "plus_times" or mask is not None:
            raise NotImplementedError(
                "the bcsr block path supports plus_times unmasked "
                "products only; plan esc/heap/hash instead")
        # nest the block-granularity inspection now (DESIGN.md section 17):
        # re-block the operand patterns once, plan the block product under
        # the shared LRU's "bcsr" kind, and freeze both levels together.
        from .bcsr import plan_bcsr
        from .formats import csr_to_bcsr
        ab = csr_to_bcsr(a, block)
        bb = csr_to_bcsr(b, (block[1], block[1]))
        bcsr_plan = plan_bcsr(ab, bb, n_bins=n_bins, cache=cache)
    pb_plan = None
    if algorithm == "pb":
        # nest the propagation-blocking inspection now (DESIGN.md
        # section 18): bucket the outer-product expansion once under the
        # shared LRU's "pb" kind and freeze both levels together.  PB
        # handles general semirings (jnp twin) and masks (structural
        # plan-time pruning), so no routing restriction applies here.
        from .pb import plan_pb
        pb_plan = plan_pb(a, b, semiring=sr.name, mask=mask,
                          complement_mask=complement_mask, cache=cache)

    plan = SpGEMMPlan(
        key=key, algorithm=algorithm, semiring=sr.name,
        complement_mask=complement_mask, sorted_output=sorted_output,
        mask=mask, shape_a=a.shape, shape_b=b.shape, cap_a=a.cap,
        cap_b=b.cap, nnz_a=int(a.nnz), nnz_b=int(b.nnz), n_bins=n_bins,
        flop=flop, total_flop=total_flop, flop_cap=flop_cap,
        offsets=offsets, bin_tsize=bin_tsize, table_size=table_size,
        row_nnz_c=row_nnz_c, indptr_c=indptr_c, nnz_c=nnz_c, cap_c=cap_c,
        row_cap=row_cap, k_width=k_width, provenance=provenance,
        block=block if algorithm == "bcsr" else None, bcsr_plan=bcsr_plan,
        pb_plan=pb_plan)
    if cache:
        cache_store(key, plan)
    return plan
