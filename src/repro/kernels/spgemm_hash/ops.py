"""Jit'd wrapper around the hash SpGEMM Pallas kernel.

Assembles the full two-phase pipeline of paper Fig. 7:

  1. ``RowsToThreads`` (core.schedule): flop per row -> equal-flop bins;
  2. table sizing (Fig. 7 lines 9-12): the *static* scratch allocation is
     ``lowest_p2(min(N_col, max_row_flop) + 1)`` (the +1 keeps the load
     factor < 1 so probes terminate), and each bin additionally carries its
     own power-of-two effective size ``bin_tsize[b] =
     lowest_p2(min(N_col, max-row-flop-in-bin) + 1)`` threaded into the
     kernels via scalar prefetch -- so a bin of light rows probes and
     flushes a small table instead of paying for the single worst row in
     the whole matrix;
  3. symbolic kernel -> exact row nnz -> indptr_C (prefix sum);
  4. numeric kernel -> (indices, values), unsorted within rows (C8).

Static-shape note: the scratch table size must be a Python int, so when the
inputs are concrete (the normal eager call) it is derived from the measured
max row flop exactly as the paper sizes per-thread tables; under an outer
``jit``/dry-run trace the caller must pin ``table_size``.  The per-bin
sizes are data (prefetched scalars), so they stay exact either way.

Inspector-executor path (``core.plan``): ``schedule=`` takes a precomputed
``(offsets, bin_tsize)`` pair and ``indptr_c=`` the symbolic phase's exact
row pointer, so a structure-identical repeat product runs the numeric
kernel alone.

Trace contexts: with a plan-frozen schedule (and static ``table_size``)
every dynamic value is an ordinary traced array, so the planned path runs
under ``jit``, inside ``shard_map`` bodies, and -- through a ``custom_vmap``
rule that runs the same kernel over a ``(members, bins)`` grid -- under
``vmap`` over fleet members.  Only the *inspection* (``hash_schedule`` with
no pinned ``table_size``) needs concrete inputs.  ``spgemm_hash_jnp``
remains solely as a reference oracle for differential tests and as the
documented fallback for general semirings / masks and planless traced
calls.

Rounding contract vs the oracle: the kernel forms each product and then
adds it, as the jnp twin and scipy do, but a backend may contract the two
into a fused multiply-add (one rounding per probe; the host LLVM backend
does, matching the paper's AVX-512 FMA kernels).  Sparsity
pattern, row pointers, and output ordering agree bitwise always; values
agree bitwise whenever the arithmetic is exactly representable (the
dyadic fuzz values), and to 1 ulp per accumulated product otherwise.

``KERNEL_CALLS`` counts, at trace time, which Pallas entry was
staged -- tests use it to prove the real kernel (not the jnp twin) is in a
compiled program.  :func:`numeric_counts` runs the numeric kernel once
with its run-time counters on (``kernel.COUNTERS``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching

from repro.core.formats import CSR
import repro.core.schedule as sched
from repro.kernels import _compat
from repro.kernels._smem import padded_len
from . import kernel as K

#: Trace-time dispatch counters: how many times each Pallas entry point was
#: staged into a computation (eager call or jit trace; dispatch-cache hits
#: do not re-count).  Keys: symbolic, numeric, batched_symbolic,
#: batched_numeric -- the ``batched_*`` entries are the vmap-rule calls of
#: the same kernel over a member grid.
KERNEL_CALLS = {"symbolic": 0, "numeric": 0,
                "batched_symbolic": 0, "batched_numeric": 0}


def reset_kernel_calls() -> None:
    """Zero the trace-time dispatch counters (test/bench helper)."""
    for k in KERNEL_CALLS:
        KERNEL_CALLS[k] = 0


def kernel_call_counts() -> dict:
    """Snapshot of :data:`KERNEL_CALLS`."""
    return dict(KERNEL_CALLS)


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


def _static_table_size(flop, n: int, table_size: int | None) -> int:
    if table_size is None:
        if not _is_concrete(flop):
            raise ValueError("under trace, pass a static table_size")
        table_size = sched.lowest_p2(
            int(min(int(jnp.max(flop)), n)) + 1)
    return max(table_size, K.CHUNK)


def hash_schedule(a: CSR, b: CSR, n_bins: int,
                  table_size: int | None = None):
    """Fig. 6 + Fig. 7 lines 9-12: bins, static scratch size, per-bin sizes.

    Returns ``(offsets, bin_tsize, table_size)`` -- everything the kernels
    need besides the CSR payloads.  This is the inspection the planner
    (``core.plan``) runs once and reuses.
    """
    flop, offsets, tsize = sched.make_schedule(a, b, n_bins)
    table_size = _static_table_size(flop, b.n_cols, table_size)
    bin_tsize = sched.bin_table_sizes(tsize, b.n_cols, table_size,
                                      floor=K.CHUNK)
    return offsets, bin_tsize, table_size


# ---------------------------------------------------------------------------
# trace-context entry points: one kernel, plain or over a batched grid
# ---------------------------------------------------------------------------
# ``jax.vmap`` has no batching rule for a pallas_call whose scalar-prefetch
# *schedule semantics* differ per member, so each entry wraps the kernel in
# a ``custom_vmap`` whose rule runs the same kernel over a grid
# ``(n_members, n_bins)`` instead of ``(1, n_bins)``.  Operands that are
# not batched (e.g. a shared B, or a schedule closed over by a vmapped
# caller) are passed once with member stride 0; within an operand group
# (schedule / indptr of A / A entries / B entries) a batched member forces
# the group's other arrays to be broadcast.

def _pad_flat(x: jax.Array, n: int) -> jax.Array:
    """Pad the last axis to ``n`` with zeros and flatten."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])]
    return jnp.pad(x, pad).reshape(-1)


@functools.lru_cache(maxsize=256)
def _entry(numeric: bool, n_bins: int, m: int, cap_a: int, cap_b: int,
           cap_c: int, table_size: int, vector: bool, interpret: bool,
           counters: bool = False):
    lens = (padded_len(m + 1, K.WIN_A), padded_len(cap_a, K.WIN_A),
            padded_len(cap_b, K.WIN_B))
    n_out = cap_c if numeric else m
    out_len = K.stream_len(n_out)
    # operand groups: schedule, indptr_a, A entries, B entries
    groups = ((0, 1), (2,), (3, 4, 5), (6, 7)) if numeric else \
        ((0, 1), (2,), (3, 4), (5,))
    name = "numeric" if numeric else "symbolic"

    def run(n_members, in_batched, args):
        args, strides = list(args), []
        for gi, grp in enumerate(groups):
            batched = any(in_batched[k] for k in grp)
            for k in grp:
                x = args[k]
                if batched and not in_batched[k]:
                    x = jnp.broadcast_to(x, (n_members,) + x.shape)
                args[k] = x.reshape(-1) if gi == 0 else \
                    _pad_flat(x, lens[gi - 1])
            strides.append((1 if gi == 0 else lens[gi - 1])
                           if batched else 0)
        build = K.numeric_call if numeric else K.symbolic_call
        call = build(n_members, n_bins, m, lens, out_len,
                     tuple(strides) + (out_len,), table_size, vector,
                     interpret, counters)
        outs = call(*args)
        if counters:
            *outs, cnt = outs
        outs = [o.reshape(n_members, out_len)[:, :n_out] for o in outs]
        if counters:
            outs.append(cnt.reshape(n_members, K.CWIN)[:, :len(K.COUNTERS)])
        return outs

    @custom_batching.custom_vmap
    def entry(*args):
        KERNEL_CALLS[name] += 1
        outs = [o[0] for o in run(1, [False] * len(args), args)]
        return tuple(outs) if len(outs) > 1 else outs[0]

    @entry.def_vmap
    def _rule(axis_size, in_batched, *args):
        KERNEL_CALLS["batched_" + name] += 1
        outs = run(axis_size, in_batched, args)
        if len(outs) > 1:
            return tuple(outs), (True,) * len(outs)
        return outs[0], True

    return entry


def _a_entries(a: CSR, b: CSR):
    """Per A entry: start and length of the B row it selects."""
    bs = b.indptr[a.indices].astype(jnp.int32)
    bl = b.indptr[a.indices + 1].astype(jnp.int32) - bs
    return bs, bl


def _resolve_schedule(a: CSR, b: CSR, n_bins: int, table_size, schedule):
    if schedule is None:
        return hash_schedule(a, b, n_bins, table_size)
    offsets, bin_tsize = schedule
    assert table_size is not None, \
        "a precomputed schedule needs its static table_size"
    return offsets, bin_tsize, max(table_size, K.CHUNK)


def spgemm_hash(a: CSR, b: CSR, cap_c: int, *, n_bins: int = 8,
                vector: bool = False, table_size: int | None = None,
                interpret: bool | None = None,
                semiring="plus_times", mask: CSR | None = None,
                complement_mask: bool = False,
                schedule=None, indptr_c: jax.Array | None = None) -> CSR:
    """C = A @ B via the hash kernel. Returns CSR with sorted_cols=False.

    The Pallas kernel is specialized to the arithmetic semiring; requests
    with a non-default ``semiring`` or a ``mask`` take the jnp fallback
    (``core.spgemm.spgemm_hash_jnp``), which keeps the same contract
    (two-phase capacity, probe-time mask pruning, unsorted select output).

    ``schedule=(offsets, bin_tsize)`` skips the Fig. 6 inspection (pass a
    static ``table_size`` alongside); ``indptr_c=`` additionally skips the
    symbolic kernel -- the planned execute path runs numeric only.
    """
    from repro.core.semiring import resolve_semiring
    if resolve_semiring(semiring).name != "plus_times" or mask is not None:
        from repro.core.spgemm import spgemm_hash_jnp
        return spgemm_hash_jnp(a, b, cap_c, semiring=semiring, mask=mask,
                               complement_mask=complement_mask)
    interpret = _compat.resolve_interpret(interpret)
    m, n = a.n_rows, b.n_cols
    offsets, bin_tsize, table_size = _resolve_schedule(a, b, n_bins,
                                                       table_size, schedule)
    n_bins = offsets.shape[0] - 1
    bs, bl = _a_entries(a, b)
    ip_a = a.indptr.astype(jnp.int32)

    if indptr_c is None:
        sym = _entry(False, n_bins, m, a.cap, b.cap, 0, table_size, vector,
                     interpret)
        row_nnz = sym(offsets, bin_tsize, ip_a, bs, bl, b.indices)
        indptr_c = sched.prefix_sum(row_nnz).astype(jnp.int32)

    num = _entry(True, n_bins, m, a.cap, b.cap, cap_c, table_size, vector,
                 interpret)
    cols_c, vals_c = num(offsets, bin_tsize, ip_a, bs, bl,
                         a.data.astype(jnp.float32), b.indices,
                         b.data.astype(jnp.float32))
    nnz_c = indptr_c[-1]
    valid = jnp.arange(cap_c, dtype=jnp.int32) < nnz_c
    cols_c = jnp.where(valid, cols_c, 0)
    vals_c = jnp.where(valid, vals_c, 0).astype(a.dtype)
    return CSR(indptr_c, cols_c, vals_c, nnz_c, (m, n), sorted_cols=False)


def spgemm_hash_symbolic(a: CSR, b: CSR, *, n_bins: int = 8,
                         vector: bool = False, table_size: int | None = None,
                         interpret: bool | None = None,
                         schedule=None) -> jax.Array:
    """Symbolic phase only: exact nnz(C) per row."""
    interpret = _compat.resolve_interpret(interpret)
    offsets, bin_tsize, table_size = _resolve_schedule(a, b, n_bins,
                                                       table_size, schedule)
    n_bins = offsets.shape[0] - 1
    bs, bl = _a_entries(a, b)
    sym = _entry(False, n_bins, a.n_rows, a.cap, b.cap, 0, table_size,
                 vector, interpret)
    return sym(offsets, bin_tsize, a.indptr.astype(jnp.int32), bs, bl,
               b.indices)


def _counted(numeric: bool, a: CSR, b: CSR, cap_c: int, schedule,
             table_size: int, vector: bool, interpret: bool | None):
    """One run of the planned kernel with counters on: its raw outputs
    (``row_nnz``, or ``cols, vals`` of length ``cap_c``) and its
    ``kernel.COUNTERS`` as host ints, summed over members."""
    interpret = _compat.resolve_interpret(interpret)
    offsets, bin_tsize = schedule
    bs, bl = _a_entries(a, b)
    args = [offsets, bin_tsize, a.indptr.astype(jnp.int32), bs, bl]
    if numeric:
        args.append(a.data.astype(jnp.float32))
    args.append(b.indices)
    if numeric:
        args.append(b.data.astype(jnp.float32))
    call = _entry(numeric, offsets.shape[0] - 1, a.n_rows, a.cap, b.cap,
                  cap_c if numeric else 0, max(table_size, K.CHUNK), vector,
                  interpret, counters=True)
    *outs, cnt = call(*args)
    totals = np.asarray(cnt, np.int64).reshape(-1, len(K.COUNTERS)).sum(0)
    return outs, dict(zip(K.COUNTERS, map(int, totals)))


def numeric_counts(a: CSR, b: CSR, cap_c: int, *, schedule,
                   table_size: int, vector: bool = False,
                   interpret: bool | None = None) -> dict[str, int]:
    """``kernel.COUNTERS`` of one numeric run of the planned kernel
    (``schedule=(offsets, bin_tsize)`` and its static ``table_size``).
    They depend on the pattern only, so one run counts every product of
    the same structure.  Counters are int32 per member."""
    return _counted(True, a, b, cap_c, schedule, table_size, vector,
                    interpret)[1]
