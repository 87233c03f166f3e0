"""Layer 1: trace planned executors to jaxprs and prove their contracts.

Three proof families per executor case, mirroring what Nagasaka et
al.'s inspector-executor split actually promises:

**Schedule verification conditions** (:func:`check_plan_vcs`) are exact
checks on the plan's *frozen* arrays -- the hash bins partition the
rows, every per-bin p2 table is large enough for its rows' symbolic
counts (so probes terminate and flushes fit), the output indptr is
monotone and lands exactly on ``nnz_c <= cap_c``, and the
flop-scaled quantities ``schedule.guard_i32_flop`` admits stay under
``2**31 - 1`` recomputed in exact Python integers.

**Interval site proofs** walk the execute jaxpr with
:class:`repro.verify.intervals.JaxprAnalyzer`: every Pallas
``get``/``swap``, ``scatter`` and ``dynamic_slice`` index must come
back ``proved`` / ``guarded`` / ``discharged`` -- the only discharge in
the repo is the hash kernel's flush cursor (``indptr_c[i] + cnt``),
which is relational and covered by the store-capacity + flush-bound
VCs, hence only granted after those VCs pass.

**Primitive budgets** pin the no-reinspection / no-densify story: a
planned execute must stage *exactly* the numeric primitives its
algorithm owns -- one numeric Pallas call per hash product (a second
would be the symbolic kernel re-inspecting), the single numeric
expansion ``sort`` for ESC-family algorithms, zero ``sort`` for heap
and planned hash, and zero ``dot_general`` anywhere (SUMMA's dense
partial accumulator is scatter-based by design and stays
``dot_general``-free).

Fixtures are tiny and deterministic; tracing never executes a kernel,
so everything here runs on any backend in a few seconds.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from repro.core import CSR
from repro.core import schedule as sched
from repro.kernels.spgemm_hash import kernel as HK

from .intervals import TOP, Ival, JaxprAnalyzer, VIOLATION, UNPROVED_READ
from .report import VC, CaseReport

_I32_MAX = 2**31 - 1


# ---------------------------------------------------------------------------
# schedule verification conditions (concrete, exact)
# ---------------------------------------------------------------------------

def _vc(name: str, ok, detail: str = "") -> VC:
    return VC(name, bool(ok), detail)


def _check_hash_schedule(offsets, bin_tsize, indptr_c, *, n_rows: int,
                         n_cols: int, cap_c: int, table_size: int,
                         flop=None, exact_cover: bool = True,
                         label: str = "") -> List[VC]:
    """The four hash-executor VCs on one (offsets, bin_tsize, indptr_c)
    schedule.  ``flop`` (the frozen per-row symbolic flop) enables the
    exact probe-termination recompute; without it (stacked batch/dist
    schedules don't carry flop) the structural form is checked.
    ``exact_cover=False`` admits padded schedules (batch classes round
    a member's ``m`` up to the class shape, so ``offsets[-1]`` is the
    member's true row count, <= the padded ``n_rows``)."""
    pre = f"{label}: " if label else ""
    offsets = np.asarray(offsets)
    bin_tsize = np.asarray(bin_tsize)
    indptr_c = np.asarray(indptr_c)
    vcs: List[VC] = []

    # bins partition the rows
    cover_ok = (offsets[-1] == n_rows if exact_cover
                else offsets[-1] <= n_rows)
    part_ok = (offsets.ndim == 1 and offsets[0] == 0
               and cover_ok and np.all(np.diff(offsets) >= 0))
    vcs.append(_vc("offsets-partition", part_ok,
                   f"{pre}bins cover [0, {int(offsets[-1])}] within "
                   f"[0, {n_rows}) contiguously"))

    # p2 tables within [CHUNK, table_size]
    bt = bin_tsize.astype(np.int64)
    p2_ok = np.all((bt & (bt - 1)) == 0) and np.all(bt >= HK.CHUNK) \
        and np.all(bt <= table_size)
    vcs.append(_vc("table-p2-range", p2_ok,
                   f"{pre}per-bin tables p2 in [{HK.CHUNK}, {table_size}]"))

    # probes terminate: each bin's table exceeds its rows' worst row
    if flop is not None and part_ok:
        flop = np.asarray(flop)[:n_rows].astype(np.int64)
        need = np.empty(len(bin_tsize), np.int64)
        for b in range(len(bin_tsize)):
            rows = flop[int(offsets[b]):int(offsets[b + 1])]
            worst = int(rows.max()) if rows.size else 0
            need[b] = sched.lowest_p2(min(worst, n_cols) + 1)
        term_ok = np.all(bt >= np.minimum(need, table_size))
        vcs.append(_vc("probe-termination", term_ok,
                       f"{pre}bin_tsize >= p2(min(max bin flop, n)+1)"))

    # output indptr is monotone and lands exactly on nnz_c <= cap_c
    nnz_c = int(indptr_c[-1])
    cap_ok = (indptr_c[0] == 0 and np.all(np.diff(indptr_c) >= 0)
              and nnz_c <= cap_c)
    vcs.append(_vc("store-capacity", cap_ok,
                   f"{pre}indptr_c monotone, nnz_c={nnz_c} <= cap_c={cap_c}"))

    # flushes fit: each row's exact count leaves a free probe slot
    row_nnz = np.diff(indptr_c.astype(np.int64))
    flush_ok = True
    if part_ok:
        for b in range(len(bin_tsize)):
            rows = row_nnz[int(offsets[b]):int(offsets[b + 1])]
            if rows.size and int(rows.max()) > int(bt[b]) - 1:
                flush_ok = False
    vcs.append(_vc("flush-bound", flush_ok,
                   f"{pre}row_nnz_c[i] <= bin_tsize[bin(i)] - 1"))
    return vcs


def _check_spgemm_vcs(plan) -> List[VC]:
    vcs: List[VC] = []
    m, n = plan.shape_a[0], plan.shape_b[1]
    flop = np.asarray(plan.flop).astype(np.int64)[:m]

    # i32 admissibility, recomputed in exact Python ints the way
    # schedule.guard_i32_flop admits it
    total = int(flop.sum())
    vcs.append(_vc("i32-flop", total == int(plan.total_flop)
                   and total <= _I32_MAX,
                   f"total_flop={total} <= 2^31-1"))
    vcs.append(_vc("expansion-capacity", int(plan.flop_cap) >= total,
                   f"flop_cap={plan.flop_cap} >= total_flop={total}"))

    row_nnz = np.asarray(plan.row_nnz_c).astype(np.int64)
    vcs.append(_vc("row-capacity",
                   int(plan.row_cap) >= (int(row_nnz.max()) if m else 0),
                   f"row_cap={plan.row_cap} >= max row_nnz_c"))
    vcs.append(_vc("nnz-consistent",
                   int(np.asarray(plan.indptr_c)[-1]) == int(plan.nnz_c)
                   and int(plan.nnz_c) <= int(plan.cap_c),
                   f"nnz_c={plan.nnz_c} <= cap_c={plan.cap_c}"))

    if plan.offsets is not None and plan.bin_tsize is not None:
        vcs += _check_hash_schedule(
            plan.offsets, plan.bin_tsize, plan.indptr_c, n_rows=m,
            n_cols=n, cap_c=int(plan.cap_c), table_size=int(plan.table_size),
            flop=flop)
    return vcs


def _check_bcsr_vcs(plan) -> List[VC]:
    """Block-granularity VCs for one frozen :class:`BCSRPlan`: the hash
    schedule invariants hold verbatim over the *block* grid (block rows
    are the rows, block columns of B the hash keys), plus the block-shape
    compatibility and i32 admissibility the planner promised."""
    vcs: List[VC] = []
    gm = -(-plan.shape_a[0] // plan.block_a[0])
    gn_b = -(-plan.shape_b[1] // plan.block_b[1])
    flop = np.asarray(plan.flop).astype(np.int64)[:gm]

    vcs.append(_vc("block-compatible",
                   plan.block_a[1] == plan.block_b[0],
                   f"A tile inner {plan.block_a[1]} == B tile outer "
                   f"{plan.block_b[0]}"))

    total = int(flop.sum())
    vcs.append(_vc("i32-flop", total == int(plan.total_flop)
                   and total <= _I32_MAX,
                   f"block total_flop={total} <= 2^31-1"))
    vcs.append(_vc("nnz-consistent",
                   int(np.asarray(plan.indptr_cb)[-1]) == int(plan.nnzb_c)
                   and int(plan.nnzb_c) <= int(plan.bcap_c),
                   f"nnzb_c={plan.nnzb_c} <= bcap_c={plan.bcap_c}"))

    vcs += _check_hash_schedule(
        plan.offsets, plan.bin_tsize, plan.indptr_cb, n_rows=gm,
        n_cols=gn_b, cap_c=int(plan.bcap_c),
        table_size=int(plan.table_size), flop=flop)
    return vcs


def _check_pb_vcs(plan) -> List[VC]:
    """Propagation-blocking VCs for one frozen :class:`PBPlan`: the
    bucket layout covers the output columns, every bucket's packed
    products fit its static capacity, all frozen gather/segment indices
    are in-bounds, and -- the PB race-freedom invariant -- every live
    product's output column lands inside its own bucket's column range,
    so buckets write disjoint output slots and merge independently."""
    vcs: List[VC] = []
    n = plan.shape_b[1]
    nb, bw = int(plan.n_buckets), int(plan.bucket_w)
    bucket_nnz = np.asarray(plan.bucket_nnz).astype(np.int64)
    src_a = np.asarray(plan.src_a)
    src_b = np.asarray(plan.src_b)
    seg = np.asarray(plan.seg)
    indptr_c = np.asarray(plan.indptr_c).astype(np.int64)
    cols_c = np.asarray(plan.cols_c).astype(np.int64)

    vcs.append(_vc("bucket-cover",
                   bw >= 1 and (bw & (bw - 1)) == 0 and nb * bw >= n,
                   f"{nb} buckets x p2 width {bw} cover {n} columns"))

    total = int(bucket_nnz.sum())
    vcs.append(_vc("i32-flop", total == int(plan.total_flop)
                   and total <= _I32_MAX,
                   f"sum(bucket_nnz)={total} == total_flop, <= 2^31-1"))
    vcs.append(_vc("bucket-capacity",
                   int(bucket_nnz.max(initial=0)) <= int(plan.bucket_cap),
                   f"max bucket_nnz <= bucket_cap={plan.bucket_cap}"))

    lane = np.arange(src_a.shape[-1])
    live = lane[None, :] < bucket_nnz[:, None]
    src_ok = (np.all((src_a >= 0) & (src_a < plan.cap_a) | ~live)
              and np.all((src_b >= 0) & (src_b < plan.cap_b) | ~live))
    vcs.append(_vc("gather-bounds", src_ok,
                   f"live src_a < cap_a={plan.cap_a}, "
                   f"src_b < cap_b={plan.cap_b}"))
    seg_ok = np.all((seg >= 0) & (seg < max(int(plan.cap_c), 1)) | ~live)
    vcs.append(_vc("segment-bounds", seg_ok,
                   f"live seg < cap_c={plan.cap_c}"))

    # race freedom: a live product in bucket g merges into an output slot
    # whose column is in [g*bw, (g+1)*bw)
    g = np.arange(nb)[:, None]
    col_of = cols_c[np.clip(seg, 0, max(int(plan.cap_c) - 1, 0))]
    disjoint = np.all((col_of // bw == g) | ~live)
    vcs.append(_vc("bucket-disjoint", disjoint,
                   "every live product's output column lies in its own "
                   "bucket's range (buckets write disjoint slots)"))

    nnz_c = int(indptr_c[-1])
    vcs.append(_vc("store-capacity",
                   indptr_c[0] == 0 and np.all(np.diff(indptr_c) >= 0)
                   and nnz_c == int(plan.nnz_c)
                   and nnz_c <= int(plan.cap_c),
                   f"indptr_c monotone, nnz_c={nnz_c} <= "
                   f"cap_c={plan.cap_c}"))
    return vcs


def _check_stacked_hash_vcs(hash_sched, *, n_rows: int, n_cols: int,
                            cap_c: int, table_size: int,
                            label: str) -> List[VC]:
    """Structural hash VCs over a stacked ``(..., n_bins+1/n_bins/m+1)``
    schedule (batch classes, distributed shards, SUMMA panels)."""
    offsets, bin_tsize, indptr_c = (np.asarray(x) for x in hash_sched)
    lead = offsets.shape[:-1]
    offsets = offsets.reshape(-1, offsets.shape[-1])
    bin_tsize = bin_tsize.reshape(-1, bin_tsize.shape[-1])
    indptr_c = indptr_c.reshape(-1, indptr_c.shape[-1])
    merged: Dict[str, VC] = {}
    for i in range(offsets.shape[0]):
        for vc in _check_hash_schedule(
                offsets[i], bin_tsize[i], indptr_c[i], n_rows=n_rows,
                n_cols=n_cols, cap_c=cap_c, table_size=table_size,
                exact_cover=False, label=f"{label}[{i}/{lead}]"):
            prev = merged.get(vc.name)
            if prev is None or (prev.ok and not vc.ok):
                merged[vc.name] = vc
    return list(merged.values())


def check_plan_vcs(plan) -> List[VC]:
    """Concrete verification conditions for any plan kind (dispatches on
    the plan's type; container plans recurse into their members)."""
    from repro.core.batch import BatchedPlan
    from repro.core.bcsr import BCSRPlan
    from repro.core.chain import ChainPlan, GramPlan
    from repro.core.distributed import DistributedPlan, SummaPlan
    from repro.core.pb import PBPlan
    from repro.core.plan import SpGEMMPlan

    if isinstance(plan, BCSRPlan):
        return _check_bcsr_vcs(plan)

    if isinstance(plan, PBPlan):
        return _check_pb_vcs(plan)

    if isinstance(plan, SpGEMMPlan):
        vcs = _check_spgemm_vcs(plan)
        if plan.bcsr_plan is not None:
            # bcsr-routed CSR plan: the nested block plan's VCs gate too
            vcs += [VC(f"bcsr.{vc.name}", vc.ok, vc.detail)
                    for vc in _check_bcsr_vcs(plan.bcsr_plan)]
        if plan.pb_plan is not None:
            # pb-routed CSR plan: the nested PB plan's VCs gate too
            vcs += [VC(f"pb.{vc.name}", vc.ok, vc.detail)
                    for vc in _check_pb_vcs(plan.pb_plan)]
        return vcs

    if isinstance(plan, ChainPlan):
        vcs: List[VC] = []
        for k, stage in enumerate(plan.stages):
            for vc in _check_spgemm_vcs(stage):
                vcs.append(VC(f"stage{k}.{vc.name}", vc.ok, vc.detail))
        return vcs

    if isinstance(plan, GramPlan):
        return [VC(f"gram.{vc.name}", vc.ok, vc.detail)
                for vc in _check_spgemm_vcs(plan.product)]

    if isinstance(plan, BatchedPlan):
        vcs = []
        for ci, cls in enumerate(plan.classes):
            members = [i for i in range(plan.n_products)
                       if plan.class_of[i] == ci]
            nnz_ok = all(plan.nnz_cs[i] <= cls.cap_c for i in members)
            vcs.append(_vc(f"class{ci}.member-capacity", nnz_ok,
                           f"every member nnz_c <= class cap_c={cls.cap_c}"))
            if cls.hash_sched is not None:
                for vc in _check_stacked_hash_vcs(
                        cls.hash_sched, n_rows=cls.shape_a[0],
                        n_cols=cls.shape_b[1], cap_c=int(cls.cap_c),
                        table_size=int(cls.table_size),
                        label=f"class{ci}"):
                    vcs.append(VC(f"class{ci}.{vc.name}", vc.ok, vc.detail))
        return vcs

    if isinstance(plan, DistributedPlan):
        vcs = []
        uniform_ok = all(
            int(p.cap_c) <= int(plan.cap_c)
            and int(p.table_size) <= int(plan.table_size)
            for p in plan.plans)
        vcs.append(_vc("uniform-statics", uniform_ok,
                       "per-shard exact capacities fit the uniform "
                       "SPMD allocation"))
        for s, p in enumerate(plan.plans):
            for vc in _check_spgemm_vcs(p):
                vcs.append(VC(f"shard{s}.{vc.name}", vc.ok, vc.detail))
        if plan.hash_sched is not None:
            rows = max(p.shape_a[0] for p in plan.plans)
            vcs += _check_stacked_hash_vcs(
                plan.hash_sched, n_rows=rows,
                n_cols=plan.shape_b[1], cap_c=int(plan.cap_c),
                table_size=int(plan.table_size), label="shard")
        return vcs

    if isinstance(plan, SummaPlan):
        vcs = []
        uniform_ok = all(
            int(p.cap_c) <= int(plan.cap_c)
            and int(p.table_size) <= int(plan.table_size)
            for p in plan.plans)
        vcs.append(_vc("uniform-statics", uniform_ok,
                       "per-panel exact capacities fit the uniform "
                       "SPMD allocation"))
        bounds_ok = all(0 <= lo <= hi <= plan.shape_a[1]
                        for lo, hi in plan.bounds)
        vcs.append(_vc("panel-bounds", bounds_ok,
                       "k-panel boundaries within [0, K]"))
        for s, p in enumerate(plan.plans):
            for vc in _check_spgemm_vcs(p):
                vcs.append(VC(f"panel{s}.{vc.name}", vc.ok, vc.detail))
        if plan.hash_sched is not None:
            vcs += _check_stacked_hash_vcs(
                plan.hash_sched, n_rows=plan.shape_a[0],
                n_cols=plan.shape_b[1], cap_c=int(plan.cap_c),
                table_size=int(plan.table_size), label="panel")
        return vcs

    raise TypeError(f"no verification conditions for {type(plan).__name__}")


# ---------------------------------------------------------------------------
# trace harnesses + seeding
# ---------------------------------------------------------------------------

def _csr_args(c: CSR) -> Tuple[Any, ...]:
    return (c.indptr, c.indices, c.data, c.nnz)


def _csr_seeds(c: CSR) -> List[Ival]:
    """Admitted input intervals for one CSR operand: the structure
    contract every caller promises (indptr/nnz within the static
    capacity, column ids within the operand's width)."""
    n = c.shape[1]
    return [Ival(0, int(c.cap)), Ival(0, max(int(n) - 1, 0)), TOP,
            Ival(0, int(c.cap))]


def _rebuild(c: CSR, parts) -> CSR:
    ip, ix, dat, nnz = parts
    return dataclasses.replace(c, indptr=ip, indices=ix, data=dat, nnz=nnz)


def _bcsr_args(x) -> Tuple[Any, ...]:
    return (x.indptr, x.indices, x.blocks, x.nnzb)


def _bcsr_seeds(x) -> List[Ival]:
    """Admitted input intervals for one BCSR operand: indptr/nnzb within
    the static block capacity, block-column ids within the block grid."""
    gn = -(-x.shape[1] // x.block[1])
    return [Ival(0, int(x.bcap)), Ival(0, max(gn - 1, 0)), TOP,
            Ival(0, int(x.bcap))]


def _rebuild_bcsr(x, parts):
    ip, ix, blk, nnzb = parts
    return dataclasses.replace(x, indptr=ip, indices=ix, blocks=blk,
                               nnzb=nnzb)


def _dyadic_dense(m: int, n: int, density: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    vals = rng.choice(np.array([0.5, 1.0, 1.5, 2.0], np.float32),
                      size=(m, n))
    return np.where(rng.random((m, n)) < density, vals, 0.0
                    ).astype(np.float32)


def _block_dyadic(gm: int, gn: int, bm: int, bn: int, density: float,
                  seed: int) -> np.ndarray:
    """Block-clustered dyadic dense fixture: a ``gm x gn`` occupancy grid
    of fully dense ``bm x bn`` tiles with values from {0.5, 1, 1.5, 2}
    (exactly representable, so kernel-vs-oracle comparisons are bitwise)."""
    rng = np.random.default_rng(seed)
    occ = (rng.random((gm, gn)) < density).astype(np.float32)
    vals = rng.choice(np.array([0.5, 1.0, 1.5, 2.0], np.float32),
                      size=(gm * bm, gn * bn))
    return np.kron(occ, np.ones((bm, bn), np.float32)) * vals


def _csr_of(d: np.ndarray, cap: Optional[int] = None) -> CSR:
    r, c = np.nonzero(d)
    return CSR.from_numpy_coo(r, c, d[r, c], d.shape, cap=cap)


def _analyze_traced(trace_fn: Callable, flat_args: Sequence[Any],
                    seeds: Sequence[Ival],
                    discharges: Dict[str, bool]) -> JaxprAnalyzer:
    closed = jax.make_jaxpr(trace_fn)(*flat_args)
    analyzer = JaxprAnalyzer(discharges=discharges)
    analyzer.analyze(closed, list(seeds))
    return analyzer


def _flush_discharge(vcs: Sequence[VC]) -> Dict[str, bool]:
    """The hash flush cursor's discharge is granted only when the
    concrete store-capacity + flush-bound VCs actually passed."""
    need = {"store-capacity", "flush-bound"}
    got = {vc.name.split(".")[-1] for vc in vcs if vc.ok}
    return {"flush-capacity": need <= got}


# primitive budgets: what a *numeric-only* execute may stage ------------

#: inspection primitives that must never appear in *any* execute jaxpr:
#: planning inspects structure once (host-side sort/unique/nonzero live
#: there); an execute staging one is re-inspection by definition
_FORBIDDEN = {"unique": 0, "nonzero": 0, "argwhere": 0}


def _algo_budget(algorithm: str, general: bool,
                 sorted_output: bool) -> Dict[str, int]:
    if algorithm in ("hash", "hash_vector") and not general:
        return {"pallas_call": 1, "sort": 1 if sorted_output else 0,
                "dot_general": 0, **_FORBIDDEN}
    if algorithm == "heap":
        return {"pallas_call": 0, "sort": 0, "dot_general": 0, **_FORBIDDEN}
    # esc / hash_jnp / any general-semiring or masked fallback: one
    # numeric expansion sort (the output comes out sorted, so the
    # epilogue never adds another)
    return {"pallas_call": 0, "sort": 1, "dot_general": 0, **_FORBIDDEN}


def _budget_check(expected: Dict[str, int],
                  counts: Dict[str, int]) -> Dict[str, Any]:
    got = {k: int(counts.get(k, 0)) for k in expected}
    return {"expected": expected, "got": got, "ok": got == expected}


def _case(kind: str, name: str, algorithm: str, vcs: List[VC],
          analyzer: JaxprAnalyzer,
          expected: Dict[str, int]) -> CaseReport:
    from collections import Counter
    site_counts = dict(Counter(s.status for s in analyzer.sites))
    census = {k: int(v) for k, v in analyzer.counts.items()
              if k in ("pallas_call", "sort", "dot_general", "scatter",
                       "scatter-add", "gather", "dynamic_slice", "while",
                       "scan", "cumsum", "i32-sum-proved",
                       "i32-sum-unbounded", "custom_vmap_call")}
    def site_dict(s):
        return {"kind": s.kind, "path": s.path, "detail": s.detail,
                "status": s.status, "index": s.index, "bound": s.bound}
    return CaseReport(
        kind=kind, name=name, algorithm=algorithm, vcs=vcs,
        site_counts=site_counts, census=census,
        budget=_budget_check(expected, analyzer.counts),
        violations=[site_dict(s) for s in analyzer.sites
                    if s.status == VIOLATION],
        warnings=[site_dict(s) for s in analyzer.sites
                  if s.status == UNPROVED_READ])


# ---------------------------------------------------------------------------
# per-kind verifiers
# ---------------------------------------------------------------------------

def verify_spgemm(plan, a: CSR, b: CSR, name: str = "") -> CaseReport:
    """Prove one frozen :class:`SpGEMMPlan` against its executor jaxpr."""
    vcs = check_plan_vcs(plan)

    def trace(ai, aj, ax, an, bi, bj, bx, bn, _plan=plan):
        return _plan.execute(_rebuild(a, (ai, aj, ax, an)),
                             _rebuild(b, (bi, bj, bx, bn)))

    analyzer = _analyze_traced(trace, _csr_args(a) + _csr_args(b),
                               _csr_seeds(a) + _csr_seeds(b),
                               _flush_discharge(vcs))
    sr_general = plan.semiring != "plus_times" or plan.mask is not None
    expected = _algo_budget(plan.algorithm, sr_general, plan.sorted_output)
    return _case("spgemm", name or f"spgemm/{plan.algorithm}",
                 plan.algorithm, vcs, analyzer, expected)


def verify_bcsr(plan, a, b, name: str = "") -> CaseReport:
    """Prove one frozen :class:`repro.core.bcsr.BCSRPlan` against its
    executor jaxpr.  The budget pins the register-tiled story: exactly
    one numeric Pallas call (a second would be the block symbolic kernel
    re-inspecting), zero ``sort`` (block rows come out hash-ordered by
    contract), and exactly one ``dot_general`` -- the MXU tile MAC inside
    the kernel body, the only dense product a planned block execute may
    stage."""
    vcs = check_plan_vcs(plan)

    def trace(ai, aj, ax, an, bi, bj, bx, bn, _plan=plan):
        return _plan.execute(_rebuild_bcsr(a, (ai, aj, ax, an)),
                             _rebuild_bcsr(b, (bi, bj, bx, bn)))

    analyzer = _analyze_traced(trace, _bcsr_args(a) + _bcsr_args(b),
                               _bcsr_seeds(a) + _bcsr_seeds(b),
                               _flush_discharge(vcs))
    expected = {"pallas_call": 1, "sort": 0, "dot_general": 1, **_FORBIDDEN}
    return _case("bcsr", name or "bcsr/planned", "bcsr", vcs, analyzer,
                 expected)


def verify_pb(plan, a: CSR, b: CSR, name: str = "") -> CaseReport:
    """Prove one frozen :class:`repro.core.pb.PBPlan` against its
    executor jaxpr.  The budget pins the propagation-blocking story:
    exactly two numeric Pallas calls on the plus_times fast path -- the
    column-bucket scatter and the per-bucket merge, kept separate so the
    mesh path can insert an ``all_to_all`` between them -- zero ``sort``
    (the output order was frozen at plan time), and zero ``dot_general``.
    A general-semiring plan runs the jnp twin: zero Pallas calls, still
    sort-free (the segment reduction is scatter-based)."""
    vcs = check_plan_vcs(plan)

    def trace(ai, aj, ax, an, bi, bj, bx, bn, _plan=plan):
        return _plan.execute(_rebuild(a, (ai, aj, ax, an)),
                             _rebuild(b, (bi, bj, bx, bn)))

    analyzer = _analyze_traced(trace, _csr_args(a) + _csr_args(b),
                               _csr_seeds(a) + _csr_seeds(b),
                               _flush_discharge(vcs))
    n_pallas = 2 if plan.semiring == "plus_times" else 0
    expected = {"pallas_call": n_pallas, "sort": 0, "dot_general": 0,
                **_FORBIDDEN}
    return _case("pb", name or "pb/planned", "pb", vcs, analyzer, expected)


def verify_batch(plan, pairs: Sequence[Tuple[CSR, CSR]],
                 name: str = "") -> CaseReport:
    """Prove one :class:`BatchedPlan` against its class programs."""
    vcs = check_plan_vcs(plan)
    flat_args: List[Any] = []
    seeds: List[Ival] = []
    for a, b in pairs:
        flat_args += _csr_args(a) + _csr_args(b)
        seeds += _csr_seeds(a) + _csr_seeds(b)

    def trace(*flat, _plan=plan):
        rebuilt = []
        it = iter(range(0, len(flat), 8))
        for (a, b), off in zip(pairs, it):
            rebuilt.append((_rebuild(a, flat[off:off + 4]),
                            _rebuild(b, flat[off + 4:off + 8])))
        return _plan.execute(rebuilt)

    analyzer = _analyze_traced(trace, flat_args, seeds,
                               _flush_discharge(vcs))
    expected = {"pallas_call": 0, "sort": 0, "dot_general": 0, **_FORBIDDEN}
    general = plan.semiring != "plus_times"
    for cls in plan.classes:
        for k, v in _algo_budget(cls.algorithm,
                                 general or cls.mask_parts is not None,
                                 plan.sorted_output).items():
            expected[k] = expected.get(k, 0) + v
    algos = ",".join(sorted({c.algorithm for c in plan.classes}))
    return _case("batch", name or f"batch/{algos}", algos, vcs,
                 analyzer, expected)


def verify_dist_1d(plan, a_sh, b: CSR, name: str = "") -> CaseReport:
    """Prove one :class:`DistributedPlan` via its mesh-free executor twin
    (``execute_shards_host`` runs the exact shard_map body per shard, so
    the traced jaxpr contains every shard's local product)."""
    vcs = check_plan_vcs(plan)
    n_shards = len(plan.plans)

    def trace(ai, aj, ax, an, bi, bj, bx, bn, _plan=plan):
        parts = _rebuild(a_sh.parts, (ai, aj, ax, an))
        a2 = dataclasses.replace(a_sh, parts=parts)
        return _plan.execute_shards_host(a2, _rebuild(b, (bi, bj, bx, bn)))

    flat_args = _csr_args(a_sh.parts) + _csr_args(b)
    cap_per = int(a_sh.cap_per)
    seeds = [Ival(0, cap_per), Ival(0, max(plan.shape_a[1] - 1, 0)), TOP,
             Ival(0, cap_per)] + _csr_seeds(b)
    analyzer = _analyze_traced(trace, flat_args, seeds,
                               _flush_discharge(vcs))
    sr_general = plan.semiring != "plus_times" or plan.mask_sh is not None
    per_shard = _algo_budget(plan.algorithm, sr_general, plan.sorted_output)
    expected = {k: v * n_shards for k, v in per_shard.items()}
    return _case("dist_1d", name or f"dist_1d/{plan.algorithm}",
                 plan.algorithm, vcs, analyzer, expected)


def verify_summa(plan, mesh, a: CSR, b: CSR, name: str = "") -> CaseReport:
    """Prove one :class:`SummaPlan` through its shard_map executor."""
    vcs = check_plan_vcs(plan)

    def trace(ax, bx, _plan=plan):
        a2 = dataclasses.replace(a, data=ax)
        b2 = dataclasses.replace(b, data=bx)
        return _plan.execute(mesh, a2, b2)

    analyzer = _analyze_traced(trace, (a.data, b.data), [TOP, TOP],
                               _flush_discharge(vcs))
    n_local = len(plan.plans)        # n_shards x panels-per-shard
    per = _algo_budget(plan.algorithm, plan.semiring != "plus_times",
                       False)
    # the panel loop runs per-shard inside one SPMD program: the jaxpr
    # stages panels-per-shard bodies, each shard executing them in SPMD
    per_shard_panels = n_local // plan.n_shards
    expected = {k: v * per_shard_panels for k, v in per.items()}
    # plus exactly one sort: the CSR.from_dense compaction epilogue that
    # re-sparsifies the reduce-scattered dense partial per shard program
    expected["sort"] = expected.get("sort", 0) + 1
    return _case("summa", name or f"summa/{plan.algorithm}",
                 plan.algorithm, vcs, analyzer, expected)


def verify_chain(plan, mats: Sequence[CSR], name: str = "") -> CaseReport:
    """Prove one :class:`ChainPlan` end to end across its stages."""
    vcs = check_plan_vcs(plan)
    flat_args: List[Any] = []
    seeds: List[Ival] = []
    for m in mats:
        flat_args += _csr_args(m)
        seeds += _csr_seeds(m)

    def trace(*flat, _plan=plan):
        rebuilt = [_rebuild(m, flat[off:off + 4])
                   for m, off in zip(mats, range(0, len(flat), 4))]
        return _plan.execute(*rebuilt)

    analyzer = _analyze_traced(trace, flat_args, seeds,
                               _flush_discharge(vcs))
    expected = {"pallas_call": 0, "sort": 0, "dot_general": 0, **_FORBIDDEN}
    last = len(plan.stages) - 1
    for k, stage in enumerate(plan.stages):
        so = plan.sorted_output if k == last else plan.sort_intermediates
        general = stage.semiring != "plus_times" or stage.mask is not None
        for key, v in _algo_budget(stage.algorithm, general, so).items():
            expected[key] = expected.get(key, 0) + v
    algos = ",".join(s.algorithm for s in plan.stages)
    return _case("chain", name or f"chain/{algos}", algos, vcs,
                 analyzer, expected)


# ---------------------------------------------------------------------------
# the --all fixture sweep
# ---------------------------------------------------------------------------

def run_layer1(kinds: Optional[Sequence[str]] = None) -> List[CaseReport]:
    """Trace-and-prove the standard fixture sweep over all plan kinds.

    Fixtures are small, dyadic and seed-pinned; tracing stages but never
    runs kernels, so the sweep is backend-independent and fast.  Returns
    one :class:`CaseReport` per case; the CLI turns them into the gating
    JSON document.
    """
    from repro.core import (plan_batch, plan_bcsr, plan_chain, plan_pb,
                            plan_spgemm, plan_spgemm_1d, plan_spgemm_summa)
    from repro.core.distributed import shard_csr_rows
    from repro.core.formats import BCSR

    kinds = set(kinds or ("spgemm", "batch", "dist_1d", "summa", "chain",
                          "bcsr", "pb"))
    cases: List[CaseReport] = []

    ad = _dyadic_dense(16, 12, 0.3, 0)
    bd = _dyadic_dense(12, 10, 0.35, 1)
    a, b = _csr_of(ad), _csr_of(bd)

    if "spgemm" in kinds:
        for algo in ("hash", "hash_vector", "esc", "heap", "hash_jnp"):
            plan = plan_spgemm(a, b, algorithm=algo)
            cases.append(verify_spgemm(plan, a, b))
        plan = plan_spgemm(a, b, algorithm="hash", sorted_output=True)
        cases.append(verify_spgemm(plan, a, b,
                                   name="spgemm/hash sorted"))

    if "batch" in kinds:
        pairs = [(a, b),
                 (_csr_of(_dyadic_dense(8, 12, 0.4, 2)), b),
                 (_csr_of(_dyadic_dense(5, 6, 0.5, 3)),
                  _csr_of(_dyadic_dense(6, 7, 0.5, 4)))]
        plan = plan_batch(pairs)
        cases.append(verify_batch(plan, pairs))

    if "dist_1d" in kinds:
        a_sh = shard_csr_rows(a, 2)
        plan = plan_spgemm_1d(a_sh, b, algorithm="hash")
        cases.append(verify_dist_1d(plan, a_sh, b))

    if "summa" in kinds:
        sad = _dyadic_dense(8, 8, 0.4, 5)
        sbd = _dyadic_dense(8, 6, 0.4, 6)
        sa, sb = _csr_of(sad), _csr_of(sbd)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        plan = plan_spgemm_summa(sa, sb, n_shards=1, k_panels=2)
        cases.append(verify_summa(plan, mesh, sa, sb))

    if "bcsr" in kinds:
        ba = BCSR.from_dense(_block_dyadic(4, 3, 4, 4, 0.6, 8), (4, 4))
        bb2 = BCSR.from_dense(_block_dyadic(3, 4, 4, 8, 0.6, 9), (4, 8))
        plan = plan_bcsr(ba, bb2)
        cases.append(verify_bcsr(plan, ba, bb2))
        # rectangular-tile variant at a different bin count
        ba2 = BCSR.from_dense(_block_dyadic(5, 4, 2, 4, 0.5, 10), (2, 4))
        bb3 = BCSR.from_dense(_block_dyadic(4, 5, 4, 2, 0.5, 11), (4, 2))
        plan = plan_bcsr(ba2, bb3, n_bins=3)
        cases.append(verify_bcsr(plan, ba2, bb3, name="bcsr/rect-tiles"))

    if "pb" in kinds:
        plan = plan_pb(a, b)
        cases.append(verify_pb(plan, a, b))
        # multi-bucket + masked variant: structural pruning at plan time,
        # so the masked product still stages the mask-free Pallas pair
        md = (_dyadic_dense(16, 10, 0.5, 12) > 0).astype(np.float32)
        plan = plan_pb(a, b, mask=_csr_of(md), n_buckets=4)
        cases.append(verify_pb(plan, a, b, name="pb/masked-4buckets"))

    if "chain" in kinds:
        cd = _dyadic_dense(10, 7, 0.4, 7)
        c = _csr_of(cd)
        plan = plan_chain([a, b, c], algorithm="hash")
        cases.append(verify_chain(plan, [a, b, c]))
        plan = plan_chain([a, b, c], algorithm="esc")
        cases.append(verify_chain(plan, [a, b, c], name="chain/esc-all"))

    return cases
