"""Interval abstract interpretation over jaxprs, for bounds proofs.

The domain is deliberately coarse -- one ``[lo, hi]`` pair summarizing a
whole array, with an optional exact concrete payload for plan-frozen
constants -- because the properties being proved are coarse: *every*
index an executor can feed a store/slice/probe stays inside the
planned capacity or p2 table size, and *every* bounded int32 sum stays
under ``2**31 - 1``.  Arithmetic on plan constants (offsets, bin table
sizes, output indptr) folds exactly through a small numpy whitelist, so
schedule-derived indices keep tight bounds instead of widening.

The walker descends through nested jaxprs (``pjit``,
``custom_vmap_call``, ``while``/``cond``/``scan``, ``pallas_call``),
models Pallas refs as monotone stores (reads of an input/prefetch ref
return the backing operand's interval; writes to output/scratch refs
join), runs while-loops to a widened fixpoint with condition-based
narrowing (the ``fori_loop`` pattern ``i < hi`` tightens the index
carry), and records a :class:`Site` verdict for every indexed memory
access it meets:

``proved``
    the index interval is inside ``[0, dim)`` (or the static slice is).
``guarded``
    out-of-range lanes are dropped/clamped by construction
    (``FILL_OR_DROP`` scatters, clamped ``dynamic_slice`` starts).
``discharged:<vc>``
    the interval alone is not relational enough (the hash kernel's
    flush cursor ``indptr_c[i] + cnt``), but a named verification
    condition checked concretely against the plan's frozen schedule
    covers it -- see :func:`repro.verify.bounds.check_plan_vcs`.
``unproved-read``
    a ``PROMISE_IN_BOUNDS`` gather whose index interval could not be
    bounded.  Reads cannot corrupt state (XLA clamps them), so this is
    reported as a warning, not a violation.
``violation``
    an unproved, unguarded, undischarged *write* index.
"""
from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.extend.core

_INF = math.inf
_I32_MAX = 2**31 - 1

# verdict strings, ordered from best to worst
PROVED = "proved"
GUARDED = "guarded"
DISCHARGED = "discharged"       # reported as "discharged:<vc-name>"
UNPROVED_READ = "unproved-read"
VIOLATION = "violation"


class Ival:
    """``[lo, hi]`` over every element of an array (Python numbers, so
    int arithmetic is exact and never wraps), plus an optional exact
    concrete payload for plan-frozen constants."""

    __slots__ = ("lo", "hi", "concrete")

    def __init__(self, lo, hi, concrete: Optional[np.ndarray] = None):
        self.lo, self.hi, self.concrete = lo, hi, concrete

    # -- constructors ---------------------------------------------------
    @staticmethod
    def of_concrete(x) -> "Ival":
        arr = np.asarray(x)
        if arr.size == 0:
            return Ival(0, 0, arr)
        if arr.dtype == bool:
            return Ival(0, 1, arr)
        if not np.issubdtype(arr.dtype, np.number):
            return TOP
        lo, hi = arr.min(), arr.max()
        if np.issubdtype(arr.dtype, np.integer):
            return Ival(int(lo), int(hi), arr)
        if np.isnan(lo) or np.isnan(hi):
            return TOP
        return Ival(float(lo), float(hi), arr)

    # -- lattice --------------------------------------------------------
    @property
    def is_top(self) -> bool:
        return self.lo == -_INF and self.hi == _INF

    def join(self, other: "Ival") -> "Ival":
        return Ival(min(self.lo, other.lo), max(self.hi, other.hi))

    def same_bounds(self, other: "Ival") -> bool:
        return self.lo == other.lo and self.hi == other.hi

    def widen(self, other: "Ival") -> "Ival":
        """Classic interval widening: any bound that moved jumps to inf."""
        lo = self.lo if other.lo >= self.lo else -_INF
        hi = self.hi if other.hi <= self.hi else _INF
        return Ival(lo, hi)

    def within(self, lo, hi) -> bool:
        return self.lo >= lo and self.hi <= hi

    def __repr__(self):
        return f"[{self.lo}, {self.hi}]"


TOP = Ival(-_INF, _INF)
BOOL = Ival(0, 1)


def _is_ival(x) -> bool:
    return isinstance(x, Ival)


class RefState:
    """Abstract state of one Pallas ref: shape, role, stored interval.

    ``role`` is ``prefetch`` / ``in`` / ``out`` / ``scratch``.  Reads
    return ``val``; writes join into it (monotone, so the while-loop
    fixpoint converges).  Input and prefetch refs start at the backing
    operand's interval; output and scratch refs start at TOP (their
    initial contents are unspecified) -- kernels never use those reads
    as indices, only as accumulator values.
    """

    __slots__ = ("shape", "role", "val", "label")

    def __init__(self, shape: Tuple[int, ...], role: str, val: Ival,
                 label: str = ""):
        self.shape, self.role, self.val, self.label = shape, role, val, label

    def __repr__(self):
        return f"Ref<{self.role}{list(self.shape)}>{self.val}"


@dataclasses.dataclass
class Site:
    """One checked memory-access (or overflow-candidate) site."""
    kind: str                 # get / swap / scatter / gather / dynamic_slice / i32-sum
    path: str                 # nesting path, e.g. "pjit/custom_vmap_call/pallas_call/while"
    detail: str
    status: str               # PROVED / GUARDED / "discharged:<vc>" / ...
    index: Optional[Tuple[float, float]] = None
    bound: Optional[int] = None

    @property
    def ok(self) -> bool:
        return self.status != VIOLATION


def _aval_shape(var) -> Tuple[int, ...]:
    aval = var.aval
    inner = getattr(aval, "inner_aval", None)
    if inner is not None:
        aval = inner
    return tuple(getattr(aval, "shape", ()))


def _aval_dtype(var):
    aval = var.aval
    inner = getattr(aval, "inner_aval", None)
    if inner is not None:
        aval = inner
    return getattr(aval, "dtype", None)


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


# numpy folds for exact propagation of plan-frozen constants; anything
# not listed (or that raises) falls back to interval arithmetic.
_FOLDS = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "max": np.maximum, "min": np.minimum,
    "neg": np.negative, "abs": np.abs,
    "cumsum": lambda x, **kw: np.cumsum(x, axis=kw.get("axis", 0)),
    "reduce_sum": lambda x, **kw: np.sum(x, axis=tuple(kw["axes"]) or None),
    "reduce_max": lambda x, **kw: np.max(x, axis=tuple(kw["axes"]) or None),
    "reduce_min": lambda x, **kw: np.min(x, axis=tuple(kw["axes"]) or None),
    "squeeze": lambda x, **kw: np.squeeze(x, axis=tuple(kw["dimensions"])),
    "reshape": lambda x, **kw: np.reshape(x, kw["new_sizes"]),
    "convert_element_type": lambda x, **kw: np.asarray(
        x, dtype=kw["new_dtype"]),
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "rem": np.remainder, "clamp": lambda lo, x, hi: np.clip(x, lo, hi),
}
_FOLD_SIZE_LIMIT = 1 << 20


class JaxprAnalyzer:
    """Walks a (closed) jaxpr with the interval domain, recording
    :class:`Site` verdicts and a primitive census.

    ``discharges`` maps verification-condition names that the caller
    has *already proved concretely* on the plan's frozen schedule (see
    ``bounds.check_plan_vcs``) to True; the only site class that leans
    on one is the hash kernel's output flush (``flush-capacity``).
    """

    def __init__(self, discharges: Optional[Dict[str, bool]] = None):
        self.sites: List[Site] = []
        self.counts: Counter = Counter()
        self.discharges = dict(discharges or {})
        self._grid: List[Tuple[int, ...]] = []   # pallas grid stack
        self._path: List[str] = []
        self._record = True

    # ------------------------------------------------------------------
    def analyze(self, closed_jaxpr, in_ivals: Sequence[Ival]) -> List[Ival]:
        jaxpr = closed_jaxpr.jaxpr
        env: Dict[Any, Any] = {}
        for var, const in zip(jaxpr.constvars, closed_jaxpr.consts):
            env[var] = Ival.of_concrete(np.asarray(const))
        assert len(jaxpr.invars) == len(in_ivals), \
            f"seeded {len(in_ivals)} inputs, jaxpr takes {len(jaxpr.invars)}"
        for var, ival in zip(jaxpr.invars, in_ivals):
            env[var] = ival
        return self._eval_jaxpr(jaxpr, env)

    # ------------------------------------------------------------------
    def _read(self, env, atom) -> Any:
        if hasattr(atom, "val"):              # Literal
            return Ival.of_concrete(np.asarray(atom.val))
        return env.get(atom, TOP)

    def _eval_jaxpr(self, jaxpr, env) -> List[Ival]:
        for eqn in jaxpr.eqns:
            self._eval_eqn(eqn, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _path_str(self) -> str:
        return "/".join(self._path) or "<top>"

    def _site(self, kind, detail, status, index=None, bound=None):
        if self._record:
            idx = None if index is None else (index.lo, index.hi)
            self.sites.append(Site(kind, self._path_str(), detail, status,
                                   idx, bound))

    # ------------------------------------------------------------------
    def _eval_eqn(self, eqn, env) -> None:
        prim = eqn.primitive.name
        if self._record:
            self.counts[prim] += 1
        invals = [self._read(env, v) for v in eqn.invars]

        if prim in ("pjit", "jit", "closed_call", "core_call",
                    "custom_vmap_call",
                    "custom_jvp_call", "custom_vjp_call",
                    "custom_vjp_call_jaxpr", "remat", "checkpoint",
                    "shard_map"):
            # shard_map descent with full-array operand intervals is
            # sound: every shard's slice interval is contained in them.
            outs = self._descend_call(eqn, invals)
        elif prim == "while":
            outs = self._while(eqn, invals)
        elif prim == "cond":
            outs = self._cond(eqn, invals)
        elif prim == "scan":
            outs = self._scan(eqn, invals)
        elif prim == "pallas_call":
            outs = self._pallas(eqn, invals)
        elif prim in ("get", "masked_load"):
            outs = [self._ref_get(eqn, invals)]
        elif prim in ("swap", "masked_swap"):
            outs = [self._ref_swap(eqn, invals)]
        elif prim == "addupdate":
            self._ref_swap(eqn, invals)
            outs = []
        else:
            outs = [self._transfer(prim, eqn, invals)]

        for var, out in zip(eqn.outvars, list(outs) + [TOP] * 8):
            env[var] = out

        # int32 overflow candidates: any bounded integer sum whose
        # interval escapes i32 is a violation; unbounded ones are censused
        # (the concrete flop-scaling VC covers the schedule quantities).
        # Products are excluded -- the hash kernel's Knuth multiply wraps
        # int32 by design before masking the result into the table.
        if prim in ("add", "cumsum", "reduce_sum"):
            dt = _aval_dtype(eqn.outvars[0]) if eqn.outvars else None
            if dt is not None and np.issubdtype(dt, np.integer) \
                    and np.dtype(dt).itemsize <= 4:
                out = outs[0] if outs else TOP
                if out.hi == _INF or out.lo == -_INF:
                    if self._record:
                        self.counts["i32-sum-unbounded"] += 1
                elif out.hi > _I32_MAX or out.lo < -_I32_MAX - 1:
                    self._site("i32-sum", f"{prim} interval {out} escapes "
                               "int32", VIOLATION, out, _I32_MAX)
                elif self._record:
                    self.counts["i32-sum-proved"] += 1

    # -- generic transfer functions ------------------------------------
    def _transfer(self, prim, eqn, invals) -> Ival:
        # exact fold when every operand is a small concrete constant
        fold = _FOLDS.get(prim)
        if fold is not None and invals and \
                all(_is_ival(v) and v.concrete is not None for v in invals) \
                and all(v.concrete.size <= _FOLD_SIZE_LIMIT for v in invals):
            try:
                return Ival.of_concrete(fold(*[v.concrete for v in invals],
                                             **eqn.params))
            except Exception:
                pass
        a = invals[0] if invals else TOP
        b = invals[1] if len(invals) > 1 else TOP
        if not _is_ival(a):
            a = TOP
        if not _is_ival(b):
            b = TOP

        if prim == "add":
            return Ival(a.lo + b.lo, a.hi + b.hi)
        if prim == "sub":
            return Ival(a.lo - b.hi, a.hi - b.lo)
        if prim == "mul":
            cands = [x * y for x in (a.lo, a.hi) for y in (b.lo, b.hi)
                     if not (math.isinf(x) and y == 0)
                     and not (math.isinf(y) and x == 0)]
            cands = cands or [0]
            return Ival(min(cands), max(cands))
        if prim == "neg":
            return Ival(-a.hi, -a.lo)
        if prim == "max":
            return Ival(max(a.lo, b.lo), max(a.hi, b.hi))
        if prim == "min":
            return Ival(min(a.lo, b.lo), min(a.hi, b.hi))
        if prim == "clamp":      # clamp(lo, x, hi)
            lo, x, hi = invals[0], invals[1], invals[2]
            return Ival(max(x.lo, lo.lo) if lo.lo != -_INF else x.lo,
                        min(x.hi, hi.hi) if hi.hi != _INF else x.hi)
        if prim == "and":
            # x & m  with m >= 0  is in [0, m.hi]; symmetric in operands
            bounds = [v.hi for v in (a, b) if v.lo >= 0]
            if bounds:
                return Ival(0, min(bounds))
            return TOP
        if prim in ("or", "xor"):
            if a.lo >= 0 and b.lo >= 0 and a.hi != _INF and b.hi != _INF:
                m = max(int(a.hi), int(b.hi))
                return Ival(0, (1 << m.bit_length()) - 1)
            return TOP
        if prim == "rem":
            if b.lo > 0 and a.lo >= 0:
                return Ival(0, b.hi - 1)
            return TOP
        if prim == "div":
            if b.lo > 0 and a.lo >= 0 and a.hi != _INF:
                return Ival(a.lo // b.hi if b.hi != _INF else 0,
                            a.hi // b.lo)
            return TOP
        if prim in ("shift_right_arithmetic", "shift_right_logical"):
            if a.lo >= 0 and b.lo >= 0 and b.hi != _INF:
                hi = a.hi if a.hi == _INF else int(a.hi) >> int(b.lo)
                return Ival(int(a.lo) >> int(b.hi), hi)
            return TOP
        if prim == "iota":
            n = eqn.params["shape"][eqn.params["dimension"]]
            return Ival(0, max(int(n) - 1, 0))
        if prim in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite",
                    "reduce_and", "reduce_or", "not"):
            return BOOL
        if prim == "select_n":
            out = invals[1]
            for v in invals[2:]:
                out = out.join(v)
            return out
        if prim in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                    "rev", "slice", "copy", "stop_gradient", "sort",
                    "expand_dims", "real", "imag", "reduce_max",
                    "reduce_min", "dynamic_slice", "optimization_barrier",
                    "reduce_precision"):
            # shape/order-preserving on values (dynamic_slice start clamp
            # is checked separately in _eval_eqn's caller via _dyn_slice)
            if prim == "dynamic_slice":
                self._dyn_slice(eqn, invals)
            if prim == "sort":
                # multi-operand sort returns every operand permuted
                return invals[0]
            return a
        if prim == "convert_element_type":
            return a
        if prim == "concatenate":
            out = invals[0]
            for v in invals[1:]:
                out = out.join(v)
            return out
        if prim == "pad":
            return a.join(invals[1])           # payload ∪ padding value
        if prim in ("argmax", "argmin"):
            axes = eqn.params.get("axes", ())
            shape = _aval_shape(eqn.invars[0])
            n = max((int(shape[ax]) for ax in axes), default=_size(shape))
            return Ival(0, max(n - 1, 0))
        if prim == "reduce_sum":
            axes = eqn.params.get("axes", ())
            shape = _aval_shape(eqn.invars[0])
            n = _size([shape[ax] for ax in axes]) if axes else _size(shape)
            return Ival(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
        if prim in ("cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod"):
            shape = _aval_shape(eqn.invars[0])
            ax = eqn.params.get("axis", 0)
            n = int(shape[ax]) if shape else 1
            if prim == "cumsum":
                return Ival(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
            return a
        if prim == "gather":
            self._gather(eqn, invals)
            mode = str(eqn.params.get("mode", ""))
            if "FILL" in mode:
                return a.join(Ival(0, 0))      # OOB lanes read the fill
            return a
        if prim in ("scatter", "scatter-add", "scatter-max", "scatter-min",
                    "scatter_add", "scatter-mul"):
            return self._scatter(prim, eqn, invals)
        if prim == "dynamic_update_slice":
            self._dus(eqn, invals)
            return a.join(invals[1])
        if prim == "program_id":
            axis = eqn.params.get("axis", 0)
            grid = self._grid[-1] if self._grid else ()
            n = int(grid[axis]) if axis < len(grid) else 0
            return Ival(0, max(n - 1, 0))
        if prim == "num_programs":
            return Ival(1, _INF)
        if prim in ("sign",):
            return Ival(-1, 1)
        if prim == "square" or (prim == "integer_pow"
                                and eqn.params.get("y") == 2):
            cands = [a.lo * a.lo, a.hi * a.hi]
            lo = 0 if a.lo <= 0 <= a.hi else min(cands)
            return Ival(lo, max(cands))
        # unknown primitive: descend into any nested jaxpr conservatively,
        # return TOP
        self._descend_unknown(eqn)
        return TOP

    # -- indexed-access checks -----------------------------------------
    def _check_index(self, kind, ival: Ival, dim: int, what: str,
                     write: bool, mode: str = "") -> None:
        if _is_ival(ival) and ival.within(0, dim - 1):
            self._site(kind, what, PROVED, ival, dim)
            return
        if "FILL" in mode or "DROP" in mode or "CLIP" in mode:
            self._site(kind, what, GUARDED, ival, dim)
            return
        if not write:
            self._site(kind, f"{what} (clamped read)", UNPROVED_READ,
                       ival, dim)
            return
        # unproved write: a named VC can discharge the hash flush cursor
        vc = "flush-capacity"
        if self.discharges.get(vc):
            self._site(kind, what, f"{DISCHARGED}:{vc}", ival, dim)
            return
        self._site(kind, what, VIOLATION, ival, dim)

    def _gather(self, eqn, invals) -> None:
        mode = str(eqn.params.get("mode", ""))
        dnums = eqn.params["dimension_numbers"]
        src_shape = _aval_shape(eqn.invars[0])
        idx = invals[1]
        dims = [int(src_shape[d]) for d in dnums.start_index_map] or [1]
        self._check_index("gather", idx, min(dims),
                          f"gather into shape {list(src_shape)}",
                          write=False, mode=mode)

    def _scatter(self, prim, eqn, invals) -> Ival:
        mode = str(eqn.params.get("mode", ""))
        dnums = eqn.params["dimension_numbers"]
        dst_shape = _aval_shape(eqn.invars[0])
        idx = invals[1]
        dims = [int(dst_shape[d])
                for d in dnums.scatter_dims_to_operand_dims] or [1]
        self._check_index("scatter", idx, min(dims),
                          f"{prim} into shape {list(dst_shape)}",
                          write=True, mode=mode)
        return invals[0].join(invals[2]) if prim != "scatter-add" else \
            Ival(invals[0].lo + min(invals[2].lo, 0) * 4,
                 invals[0].hi + max(invals[2].hi, 0) *
                 max(_size(_aval_shape(eqn.invars[1])), 1)) \
            if invals[0].hi != _INF and invals[2].hi != _INF else TOP

    def _dyn_slice(self, eqn, invals) -> None:
        shape = _aval_shape(eqn.invars[0])
        sizes = eqn.params["slice_sizes"]
        for ax, start in enumerate(invals[1:1 + len(shape)]):
            dim, sz = int(shape[ax]), int(sizes[ax])
            limit = dim - sz
            if _is_ival(start) and start.within(0, limit):
                self._site("dynamic_slice", f"axis {ax} of {list(shape)}",
                           PROVED, start, dim)
            else:
                # XLA clamps dynamic_slice starts into range by definition
                self._site("dynamic_slice", f"axis {ax} of {list(shape)}",
                           GUARDED, start, dim)

    def _dus(self, eqn, invals) -> None:
        shape = _aval_shape(eqn.invars[0])
        upd = _aval_shape(eqn.invars[1])
        for ax, start in enumerate(invals[2:2 + len(shape)]):
            dim, sz = int(shape[ax]), int(upd[ax])
            if _is_ival(start) and start.within(0, dim - sz):
                self._site("dynamic_update_slice",
                           f"axis {ax} of {list(shape)}", PROVED, start, dim)
            else:        # clamped like dynamic_slice
                self._site("dynamic_update_slice",
                           f"axis {ax} of {list(shape)}", GUARDED, start, dim)

    # -- Pallas refs ----------------------------------------------------
    def _ref_operands(self, eqn, invals):
        """``(indexers, stored)`` of a state primitive: its NDIndexers with
        the abstract index values in place (None where unreadable), and
        the value a swap or addupdate stores (None otherwise).
        ``get``/``swap``/``addupdate`` carry the indexers as a ``tree``
        after the ref and the stored value;
        Pallas's ``masked_load``/``masked_swap`` flatten ``(ref,
        transforms, value or mask, ...)`` as one ``args_tree``."""
        prim = eqn.primitive.name
        args_tree = eqn.params.get("args_tree")
        if args_tree is not None:
            try:
                _, transforms, third, _ = args_tree.unflatten(invals)
            except Exception:
                return None, None
            return transforms, third if prim == "masked_swap" else None
        stored = invals[1] if prim in ("swap", "addupdate") \
            and len(invals) > 1 else None
        tree = eqn.params.get("tree")
        if tree is None:
            return None, stored
        n_idx = tree.num_leaves
        # swap carries the stored value after the ref; indices follow.
        idx_vals = invals[len(invals) - n_idx:] if n_idx else []
        try:
            obj = jax.tree_util.tree_unflatten(tree, idx_vals)
        except Exception:
            return None, stored
        return obj, stored

    def _indexer_dims(self, eqn, invals) -> Optional[List[Tuple[Any, int]]]:
        """Pairs of (index abstract value | static Slice, dim size) per
        indexed axis, from the state primitive's NDIndexers."""
        ref_shape = _aval_shape(eqn.invars[0])
        obj, _ = self._ref_operands(eqn, invals)
        if obj is None:
            return None
        indexers = obj if isinstance(obj, (tuple, list)) else (obj,)
        out: List[Tuple[Any, int]] = []
        dims = list(ref_shape)
        for indexer in indexers:
            idx = getattr(indexer, "indices", None)
            if idx is None:
                return None
            for ax, elem in enumerate(idx):
                if ax >= len(dims):
                    return None
                out.append((elem, int(dims[ax])))
        return out

    def _check_ref_access(self, eqn, invals, write: bool) -> None:
        ref = invals[0]
        role = ref.role if isinstance(ref, RefState) else "?"
        pairs = self._indexer_dims(eqn, invals)
        kind = "swap" if write else "get"
        what = f"{role} ref {getattr(ref, 'label', '')}".strip()
        if pairs is None:
            self._site(kind, f"{what}: unrecognized indexer",
                       UNPROVED_READ if not write else VIOLATION)
            return
        for elem, dim in pairs:
            if _is_ival(elem):
                self._check_index(kind, elem, dim, f"{what} dim {dim}",
                                  write=write)
                continue
            # static or dynamic-start Slice
            start = getattr(elem, "start", None)
            size = getattr(elem, "size", None)
            stride = getattr(elem, "stride", 1) or 1
            if start is None:
                continue          # e.g. full-slice sentinel: whole axis
            if _is_ival(start):
                limit = dim - (int(size) - 1) * int(stride) - 1 \
                    if size is not None else dim - 1
                self._check_index(kind, start, max(limit + 1, 0),
                                  f"{what} slice start (dim {dim})",
                                  write=write)
            elif isinstance(start, int):
                last = start + ((int(size) - 1) * int(stride)
                                if size is not None else 0)
                ok = 0 <= start and last < dim
                self._site(kind, f"{what} static slice [{start}:+{size}] "
                           f"of dim {dim}", PROVED if ok else VIOLATION,
                           Ival(start, last), dim)

    def _ref_get(self, eqn, invals) -> Ival:
        self._check_ref_access(eqn, invals, write=False)
        ref = invals[0]
        return ref.val if isinstance(ref, RefState) else TOP

    def _ref_swap(self, eqn, invals) -> Ival:
        self._check_ref_access(eqn, invals, write=True)
        ref = invals[0]
        stored = self._ref_operands(eqn, invals)[1]
        stored = stored if _is_ival(stored) else TOP
        if isinstance(ref, RefState):
            old = ref.val
            ref.val = ref.val.join(stored)
            return old
        return TOP

    # -- nested structures ----------------------------------------------
    def _find_callee(self, eqn):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "call"):
            cj = eqn.params.get(key)
            if cj is not None and hasattr(cj, "jaxpr"):
                return cj
            # shard_map stores an *open* Jaxpr (no consts); close it
            if cj is not None and hasattr(cj, "eqns") \
                    and hasattr(cj, "invars") and not getattr(
                        cj, "constvars", True):
                return jax.extend.core.ClosedJaxpr(cj, [])
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                return v
        return None

    def _descend_call(self, eqn, invals) -> List[Ival]:
        cj = self._find_callee(eqn)
        if cj is None or len(cj.jaxpr.invars) != len(invals):
            self._descend_unknown(eqn)
            return [TOP] * len(eqn.outvars)
        self._path.append(eqn.primitive.name)
        try:
            return self.analyze(cj, invals)
        finally:
            self._path.pop()

    def _descend_unknown(self, eqn) -> None:
        """Sound fallback: walk nested jaxprs with TOP inputs."""
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                cj = x if hasattr(x, "jaxpr") and hasattr(
                    getattr(x, "jaxpr"), "eqns") else None
                if cj is not None:
                    self._path.append(eqn.primitive.name + "?")
                    try:
                        self.analyze(cj, [TOP] * len(cj.jaxpr.invars))
                    finally:
                        self._path.pop()

    # -- control flow ---------------------------------------------------
    def _narrow_by_cond(self, cond_cj, cond_consts: List[Ival],
                        carries: List[Ival]) -> List[Ival]:
        """Tighten carries using the loop condition, for the fori pattern
        ``lt i hi`` (and friends) where ``i`` is a carry."""
        jaxpr = cond_cj.jaxpr
        env: Dict[Any, Any] = {}
        allv = list(cond_consts) + list(carries)
        for var, ival in zip(jaxpr.invars, allv):
            env[var] = ival
        for var, const in zip(jaxpr.constvars, cond_cj.consts):
            env[var] = Ival.of_concrete(np.asarray(const))
        narrowed = list(carries)
        pos = {v: i for i, v in enumerate(jaxpr.invars)}
        n_consts = len(cond_consts)

        def carry_index(atom) -> int:       # literals are never carries
            return -1 if hasattr(atom, "val") else pos.get(atom, -1) - \
                n_consts

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("lt", "le", "gt", "ge") and len(eqn.invars) == 2:
                a, b = eqn.invars
                av, bv = self._read(env, a), self._read(env, b)
                if name in ("gt", "ge"):       # a > b  ==  b < a
                    a, b, av, bv = b, a, bv, av
                    name = "lt" if name == "gt" else "le"
                ub = bv.hi - (1 if name == "lt" else 0)
                i = carry_index(a)
                if 0 <= i < len(narrowed) and ub != _INF:
                    c = narrowed[i]
                    narrowed[i] = Ival(c.lo, min(c.hi, ub))
                lb = av.lo + (1 if name == "lt" else 0)
                j = carry_index(b)
                if 0 <= j < len(narrowed) and lb != -_INF:
                    c = narrowed[j]
                    narrowed[j] = Ival(max(c.lo, lb), c.hi)
        return narrowed

    def _while(self, eqn, invals) -> List[Ival]:
        p = eqn.params
        cond_cj, body_cj = p["cond_jaxpr"], p["body_jaxpr"]
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = invals[:nc]
        body_consts = invals[nc:nc + nb]
        init = [v if _is_ival(v) or isinstance(v, RefState) else TOP
                for v in invals[nc + nb:]]
        carries = list(init)

        def ivals_only(xs):
            return [x if _is_ival(x) else TOP for x in xs]

        record, self._record = self._record, False
        try:
            for it in range(6):
                narrowed = self._narrow_by_cond(
                    cond_cj, ivals_only(cond_consts), ivals_only(carries))
                body_in = [c if isinstance(c, RefState) else n
                           for c, n in zip(carries, narrowed)]
                outs = self.analyze(body_cj, list(body_consts) + body_in)
                new = []
                stable = True
                for c, o in zip(carries, outs):
                    if isinstance(c, RefState):
                        new.append(c)          # refs join in place
                        continue
                    o = o if _is_ival(o) else TOP
                    j = c.join(o)
                    if not j.same_bounds(c):
                        stable = False
                        j = c.widen(j) if it >= 2 else j
                    new.append(j)
                carries = new
                if stable:
                    break
        finally:
            self._record = record

        # final, recorded pass over the body at the stable invariant
        narrowed = self._narrow_by_cond(
            cond_cj, ivals_only(cond_consts), ivals_only(carries))
        body_in = [c if isinstance(c, RefState) else n
                   for c, n in zip(carries, narrowed)]
        self._path.append("while")
        try:
            self.analyze(body_cj, list(body_consts) + body_in)
        finally:
            self._path.pop()
        return carries

    def _cond(self, eqn, invals) -> List[Ival]:
        branches = eqn.params["branches"]
        ops = invals[1:]
        outs: Optional[List[Ival]] = None
        self._path.append("cond")
        try:
            for br in branches:
                res = self.analyze(br, ops)
                res = [r if _is_ival(r) else TOP for r in res]
                outs = res if outs is None else \
                    [a.join(b) for a, b in zip(outs, res)]
        finally:
            self._path.pop()
        return outs or []

    def _scan(self, eqn, invals) -> List[Ival]:
        p = eqn.params
        body = p["jaxpr"]
        n_consts, n_carry = p["num_consts"], p["num_carry"]
        consts = invals[:n_consts]
        carries = [v if _is_ival(v) else TOP
                   for v in invals[n_consts:n_consts + n_carry]]
        xs = [v if _is_ival(v) else TOP for v in invals[n_consts + n_carry:]]
        record, self._record = self._record, False
        try:
            for it in range(6):
                outs = self.analyze(body, list(consts) + carries + xs)
                new_c = []
                stable = True
                for c, o in zip(carries, outs[:n_carry]):
                    o = o if _is_ival(o) else TOP
                    j = c.join(o)
                    if not j.same_bounds(c):
                        stable = False
                        j = c.widen(j) if it >= 2 else j
                    new_c.append(j)
                carries = new_c
                if stable:
                    break
        finally:
            self._record = record
        self._path.append("scan")
        try:
            outs = self.analyze(body, list(consts) + carries + xs)
        finally:
            self._path.pop()
        ys = [o if _is_ival(o) else TOP for o in outs[n_carry:]]
        return carries + ys

    # -- pallas ----------------------------------------------------------
    def _pallas(self, eqn, invals) -> List[Ival]:
        jaxpr = eqn.params["jaxpr"]
        gm = eqn.params.get("grid_mapping")
        grid = tuple(int(g) for g in getattr(gm, "grid", ()) or ())
        n_prefetch = int(getattr(gm, "num_index_operands", 0) or 0)
        n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
        n_out = len(eqn.outvars)
        kern_invars = jaxpr.invars
        n_in = len(kern_invars) - n_prefetch - n_out - n_scratch

        refs: List[RefState] = []
        for i, var in enumerate(kern_invars):
            if i < n_prefetch:
                role, backing = "prefetch", invals[i]
            elif i < n_prefetch + n_in:
                role, backing = "in", invals[i]
            elif i < n_prefetch + n_in + n_out:
                role, backing = "out", TOP
            else:
                role, backing = "scratch", TOP
            backing = backing if _is_ival(backing) else TOP
            refs.append(RefState(_aval_shape(var), role, backing,
                                 label=f"{role}{i}"))

        env: Dict[Any, Any] = {}
        for var, ref in zip(kern_invars, refs):
            env[var] = ref
        for var in jaxpr.constvars:
            env[var] = TOP

        self._grid.append(grid)
        self._path.append("pallas_call")
        try:
            self._eval_jaxpr(jaxpr, env)
        finally:
            self._path.pop()
            self._grid.pop()
        return [TOP] * n_out
