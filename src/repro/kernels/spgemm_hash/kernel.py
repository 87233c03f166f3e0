"""Pallas TPU kernel: hash-accumulator SpGEMM (paper Figs. 7 & 8).

Faithful structure, TPU-resident state:

  * grid = (members, equal-flop row bins from ``core.schedule``) (C1;
    Fig. 6) -- the Pallas grid replaces the OpenMP static thread pool.  A
    plain product is the one-member case; ``vmap`` fleets and row shards
    are the many-member case of the same kernel;
  * per-program hash table in **VMEM scratch** laid out ``(rows, 128)``
    (C5: thread-private memory, sized once to the max per-row flop --
    Fig. 7 lines 5-14 -- and *reinitialized per row*, not reallocated);
  * power-of-two table, multiply hash (Fig. 8).  A probe step loads one
    128-lane table row and finds the key or the first empty lane with an
    iota-masked ``min`` (the TPU stand-in for x86 ``ctz``):

      - ``vector=False`` is linear probing (Fig. 8a): the hash names a
        *slot*; the first step only considers lanes at or after it, later
        steps whole rows, so slots are visited in exactly the order
        ``h, h+1, ...`` of the scalar algorithm;
      - ``vector=True`` is chunked probing (Fig. 8b): the hash names a
        *row* (a chunk of ``min(tsize, 128)`` slots) and any lane of it may
        take the key;
  * two phases: ``symbolic`` counts nnz per row, ``numeric`` fills values
    (section 2: the two-phase method gives exact output capacity);
  * output rows are emitted **unsorted** (C8) in table-scan order; sorting
    is an explicit epilogue owned by the caller (Table 1 "Any/Select").
    The numeric flush moves a whole table row at a time: the occupied
    lanes are compacted to the front of the row, in lane order, by
    log-step rolls (:func:`_compact`, eight rows at once as one tile),
    and appended to the output as one vector.

Memory plumbing (what the TPU compiler admits):

  * CSR operands and outputs stay in HBM (``memory_space=pl.ANY``).  The
    kernel reads them through small SMEM *windows* that are DMA'd in when
    an index leaves the window.  Output leaves through a staging window
    that is DMA'd out when full: SMEM for the symbolic kernel's one count
    per row (``_smem.Stream``), VMEM rows of 128 lanes for the numeric
    kernel's compacted table rows (``_smem.RowStream``), whose HBM outputs
    are viewed as ``(len / 128, 128)``.  Scalars therefore only ever move
    between SMEM and the scalar unit; VMEM is touched only by whole
    128-lane rows of the hash table and of the output stage.
  * 1-D HBM arrays are tiled by ``ALIGN`` words, so every DMA starts at a
    multiple of ``ALIGN`` and moves a multiple of it (``kernels._smem``).
    ``ops.py`` pads the operands to whole windows; output streams are
    padded likewise and reshaped and sliced back by ``ops.py``.
  * Only the bin schedule (``offsets``, ``bin_tsize``) is scalar-prefetched,
    so SMEM use is independent of the matrix size.
  * Bins of one member run in order on one core (``"arbitrary"``), and the
    output of bin ``b + 1`` starts where bin ``b`` stopped (rows are
    contiguous), so one output stream per member runs across its bins.

Counters (``counters=True``): each member keeps an SMEM int32 vector of
the :data:`COUNTERS`, zeroed where its stream opens and DMA'd to one
extra output where it closes.  They depend only on the sparsity pattern
and the schedule, never on the values.  With ``counters=False`` the
kernel has no counter scratch, output or op.

The A side reaches the kernel pre-gathered per A entry: ``a_bs[j] =
indptr_b[a_idx[j]]`` and ``a_bl[j]`` (that B row's length), so no random
access into ``indptr_b`` happens in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._smem import (ALIGN, RowStream, Stream, Window,
                                 _copy_all, count)

#: Knuth multiplicative constant (wraps mod 2^32; int32 two's complement).
HASH_CONST = -1640531527   # == 2654435761 mod 2^32 (Python int -> inlined literal)

#: Smallest admissible table (and the per-bin floor of ``bin_tsize``).
CHUNK = 8

#: Lanes of one table row: the width of one probe step.
LANES = 128

#: Window sizes (words): the A-entry group (and the indptr of A), the
#: B-row group (SMEM), and the output staging window (SMEM for the
#: symbolic kernel, ``WIN_OUT // LANES`` VMEM rows for the numeric one).
WIN_A = 2048
WIN_B = 1024
WIN_OUT = 2048

EMPTY = -1

#: The counter vector of a counted kernel, in order: probe-loop steps,
#: table rows cleared per row of A (the numeric flush scans as many),
#: refills of the indptr, A-entry and B-row windows, output windows
#: written to HBM, and table rows the numeric flush compacts and writes
#: (those holding an entry).
COUNTERS = ("probe_steps", "table_rows", "ip_loads", "a_loads", "b_loads",
            "out_flushes", "flush_rows")
(PROBE_STEPS, TABLE_ROWS, IP_LOADS, A_LOADS, B_LOADS, OUT_FLUSHES,
 FLUSH_ROWS) = range(len(COUNTERS))
#: Words of one member's counter vector (SMEM and output): one HBM tile.
CWIN = ALIGN


def _hash(key: jax.Array, mask: jax.Array) -> jax.Array:
    return (key * HASH_CONST) & mask


def stream_len(n: int) -> int:
    """Length of an output stream of ``n`` words: whole staging windows."""
    return max(-(-n // WIN_OUT) * WIN_OUT, WIN_OUT)


def table_rows(table_size: int) -> int:
    """Rows of the ``(rows, LANES)`` scratch table for a p2 ``table_size``."""
    return max(table_size // LANES, 1)


def _lane():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _clear(tkey_ref, tval_ref, n_rows, rows):
    """Reset the table prefix ``[0, n_rows)`` rows: keys EMPTY, values 0
    (eight rows per store where the ``rows``-row scratch has them)."""
    def put(sl):
        tkey_ref[sl, :] = jnp.full((sl.size, LANES), EMPTY, jnp.int32)
        if tval_ref is not None:
            tval_ref[sl, :] = jnp.zeros((sl.size, LANES), jnp.float32)

    n8 = n_rows // 8 if rows >= 8 else jnp.int32(0)

    def blk(r, c):
        put(pl.ds(pl.multiple_of(r * 8, 8), 8))
        return c

    def one(r, c):
        put(pl.ds(r, 1))
        return c

    if rows >= 8:
        jax.lax.fori_loop(0, n8, blk, 0)
    jax.lax.fori_loop(n8 * 8, n_rows, one, 0)


def _compact(key, val):
    """Move the occupied lanes (``key != EMPTY``) of each ``(rows, LANES)``
    table row to its front, keeping their order; lanes past them are
    EMPTY keys and unspecified values.

    An occupied lane moves left by the number of empty lanes before it
    (a log-step prefix count), one bit of that distance per step, lowest
    first.  Two entries never land on one lane: the distances they have
    moved so far differ by at most the empty lanes between them.
    """
    lane = _lane()
    emp = (key == EMPTY).astype(jnp.int32)
    before = emp
    for k in range(LANES.bit_length() - 1):
        s = 1 << k
        before = before + jnp.where(lane >= s, pltpu.roll(before, s, 1), 0)
    dist = before - emp
    for k in range(LANES.bit_length() - 1):
        s = 1 << k
        go = (key != EMPTY) & ((dist & s) != 0)
        # lane p takes what moves out of lane p + s
        k_in = pltpu.roll(jnp.where(go, key, EMPTY), LANES - s, 1)
        take = k_in != EMPTY
        key = jnp.where(take, k_in, jnp.where(go, EMPTY, key))
        val = jnp.where(take, pltpu.roll(val, LANES - s, 1), val)
        dist = jnp.where(take, pltpu.roll(dist, LANES - s, 1), dist)
    return key, val


def _probe(tkey_ref, key, tsz, row_mask, vector):
    """Find ``key`` or the slot it should take; returns ``(row, lane, new,
    steps)``, ``steps`` being the probe loop's iterations.

    ``tsz`` is the (traced, p2) effective table size; tables narrower than
    a row use the first ``tsz`` lanes of row 0.  ``row_mask`` is the static
    scratch row mask, which keeps every row index provably in bounds.
    """
    lane = _lane()
    live = lane < jnp.minimum(tsz, LANES)
    rmask = jnp.maximum(tsz >> 7, 1) - 1
    if vector:
        r0, first = _hash(key, rmask), jnp.int32(0)
    else:
        h = _hash(key, tsz - 1)
        r0, first = h >> 7, h & (LANES - 1)

    def cond(st):
        return st[2] >= LANES

    def body(st):
        r, it, _, _ = st
        row = tkey_ref[pl.ds(r & row_mask, 1), :]
        elig = live & (lane >= jnp.where(it == 0, first, 0))
        # one cross-lane min per step: a hit scores its lane, an empty
        # slot its lane + LANES, anything else 2 * LANES -- so the min is
        # the hit if there is one, else the first empty lane (+ LANES),
        # else 2 * LANES (row full: probe on)
        score = jnp.where(elig & (row == key), lane,
                          jnp.where(elig & (row == EMPTY), lane + LANES,
                                    2 * LANES))
        s = jnp.min(score)
        found = jnp.where(s < 2 * LANES, s & (LANES - 1), LANES)
        nxt = jnp.where(found < LANES, r, (r + 1) & rmask)
        return nxt, it + 1, found, (s >= LANES).astype(jnp.int32)

    r, steps, found, new = jax.lax.while_loop(
        cond, body, (r0, jnp.int32(0), jnp.int32(LANES), jnp.int32(0)))
    return r & row_mask, found, new, steps


def _hash_kernel(off_ref, tsz_ref, *refs, numeric, vector, n_bins, m,
                 table_size, strides, lens, counters):
    n_in = 6 if numeric else 4
    n_out = 2 if numeric else 1
    ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
    scratch = refs[n_in + n_out:]
    if counters:
        cnt_hbm, *scratch, cnt_ref = scratch

    def ctr(slot):
        return (cnt_ref, slot) if counters else None

    if numeric:
        ip_hbm, bs_hbm, bl_hbm, av_hbm, bi_hbm, bv_hbm = ins
        (ip_w, bs_w, bl_w, av_w, bi_w, bv_w, stage_k, stage_v, wbase,
         sstate, tkey_ref, tval_ref, sems) = scratch
        a_group = ([bs_hbm, bl_hbm, av_hbm], [bs_w, bl_w, av_w])
        b_group = ([bi_hbm, bv_hbm], [bi_w, bv_w])
        stage = [stage_k, stage_v]
    else:
        ip_hbm, bs_hbm, bl_hbm, bi_hbm = ins
        ip_w, bs_w, bl_w, bi_w, stage_k, wbase, sstate, tkey_ref, sems = \
            scratch
        tval_ref = None
        a_group = ([bs_hbm, bl_hbm], [bs_w, bl_w])
        b_group = ([bi_hbm], [bi_w])
        stage = [stage_k]
    s_sched, s_ip, s_a, s_b, s_out = strides
    l_ip, l_a, l_b = lens
    row_mask = table_rows(table_size) - 1

    e = pl.program_id(0)
    b = pl.program_id(1)
    n_a, n_b = len(a_group[0]), len(b_group[0])
    ipw = Window([ip_hbm], [ip_w], wbase, 0, sems, 0, WIN_A, ctr(IP_LOADS))
    aw = Window(*a_group, wbase, 1, sems, 1, WIN_A, ctr(A_LOADS))
    bw = Window(*b_group, wbase, 2, sems, 1 + n_a, WIN_B, ctr(B_LOADS))
    # member e owns output words [e * s_out, (e + 1) * s_out): a numeric
    # stream past cap_c is truncated there, never written into member e + 1
    out = (RowStream if numeric else Stream)(
        outs, stage, sstate, sems, 1 + n_a + n_b, WIN_OUT,
        end=(e + 1) * s_out, counter=ctr(OUT_FLUSHES))
    for w in (ipw, aw, bw):
        w.reset()
    ip0, a0, b0 = e * s_ip, e * s_a, e * s_b
    ip_last, a_last, b_last = (ip0 + l_ip - WIN_A, a0 + l_a - WIN_A,
                               b0 + l_b - WIN_B)

    @pl.when(b == 0)
    def _open():
        out.start(e * s_out)
        if counters:
            for k in range(len(COUNTERS)):
                cnt_ref[k] = jnp.int32(0)

    es = e if s_sched else 0           # schedules: per member or shared
    lo = off_ref[es * (n_bins + 1) + b]
    hi = off_ref[es * (n_bins + 1) + b + 1]
    tsz = jnp.minimum(tsz_ref[es * n_bins + b], jnp.int32(table_size))
    n_rows = jnp.maximum(tsz >> 7, 1)
    lane = _lane()
    # table rows the numeric flush compacts together, as one (8, 128) tile
    grp = min(8, row_mask + 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (grp, 1), 0)

    def do_row(i, _):
        _clear(tkey_ref, tval_ref, n_rows, row_mask + 1)
        count(ctr(TABLE_ROWS), n_rows)
        (p0,) = ipw.read(ip0 + i, ip_last)
        (p1,) = ipw.read(ip0 + i + 1, ip_last)

        def do_a(j, cnt):
            avals = aw.read(a0 + j, a_last)
            bs, bl = avals[0], avals[1]

            def do_b(t, cnt):
                bvals = bw.read(b0 + t, b_last)
                key = bvals[0]
                r, found, new, steps = _probe(tkey_ref, key, tsz, row_mask,
                                              vector)
                count(ctr(PROBE_STEPS), steps)
                sel = lane == found
                row = tkey_ref[pl.ds(r, 1), :]
                tkey_ref[pl.ds(r, 1), :] = jnp.where(sel, key, row)
                if numeric:
                    # one product, then one add: per-product rounding on
                    # the vector unit (the scipy order of operations)
                    prod = jnp.full((1, LANES), avals[2], jnp.float32) * \
                        bvals[1]
                    v = tval_ref[pl.ds(r, 1), :]
                    tval_ref[pl.ds(r, 1), :] = jnp.where(sel, v + prod, v)
                return cnt + new

            return jax.lax.fori_loop(bs, bs + bl, do_b, cnt)

        cnt = jax.lax.fori_loop(p0, p1, do_a, jnp.int32(0))
        if not numeric:
            out.push(cnt)
            return 0

        # Flush occupied slots in table order -> **unsorted** columns (C8),
        # one compacted table row at a time.  Only this bin's [0, tsz)
        # prefix can be occupied; rows past it are masked off.
        def flush_group(g, c):
            rows = pl.ds(pl.multiple_of((g * grp) & row_mask, grp), grp)
            kt = jnp.where(sub + g * grp < n_rows, tkey_ref[rows, :], EMPTY)
            n_occ = jnp.sum((kt != EMPTY).astype(jnp.int32), axis=1,
                            keepdims=True)
            kt, vt = _compact(kt, tval_ref[rows, :])
            for k in range(grp):
                n_k = jnp.sum(jnp.where(sub == k, n_occ, 0))

                @pl.when(n_k > 0)
                def _emit():
                    count(ctr(FLUSH_ROWS))
                    out.push(n_k, kt[k:k + 1], vt[k:k + 1])
            return c

        jax.lax.fori_loop(0, (n_rows + grp - 1) // grp, flush_group, 0)
        return 0

    jax.lax.fori_loop(lo, hi, do_row, 0)

    @pl.when(b == n_bins - 1)
    def _close():
        if not numeric:
            # rows past the last bin (padded batch members) count zero
            def pad(_, c):
                out.push(jnp.int32(0))
                return c
            jax.lax.fori_loop(hi, m, pad, 0)
            out.flush()
        else:
            out.close()
        if counters:
            _copy_all([(cnt_ref, cnt_hbm.at[pl.ds(
                pl.multiple_of(e * CWIN, CWIN), CWIN)])], sems, 0)


@functools.lru_cache(maxsize=256)
def _hash_call(numeric: bool, n_members: int, n_bins: int, m: int,
              lens: tuple, out_len: int, strides: tuple, table_size: int,
              vector: bool, interpret: bool, counters: bool = False):
    """Cached builder: one jitted ``pallas_call`` per static config.

    ``lens = (l_ip, l_a, l_b)`` are the padded per-member lengths of the
    flat operands (see :func:`padded_len`); ``out_len`` the per-member
    output stream length (:func:`stream_len`); ``strides = (sched, ip, a,
    b, out)`` the flat distance between consecutive members of each
    operand group -- 0 for an operand shared by every member (the
    schedule entry is 1 for per-member schedules, 0 for a shared one).

    Operands (all flat, 1-D): ``offsets`` and ``bin_tsize`` (scalar
    prefetch), then ``indptr_a, a_bs, a_bl[, a_val], b_idx[, b_val]`` in
    HBM.  Returns ``row_nnz`` (symbolic) or ``(cols, vals)`` (numeric),
    each ``n_members * out_len`` words (the numeric ones shaped
    ``(n_members * out_len // LANES, LANES)``); ``counters=True`` appends
    the ``n_members * CWIN`` counter output (member ``e``'s :data:`COUNTERS`
    at ``e * CWIN``).
    """
    kernel = functools.partial(
        _hash_kernel, numeric=numeric, vector=vector, n_bins=n_bins, m=m,
        table_size=table_size, strides=strides, lens=lens,
        counters=counters)
    smem = pltpu.SMEM
    n_a = 3 if numeric else 2
    n_b = 2 if numeric else 1
    n_out = 2 if numeric else 1
    rows = table_rows(table_size)
    stage_rows = (WIN_OUT // LANES + 1, LANES)      # the last is spare
    scratch = ([smem((WIN_A,), jnp.int32)]
               + [smem((WIN_A,), jnp.int32)] * 2
               + ([smem((WIN_A,), jnp.float32)] if numeric else [])
               + [smem((WIN_B,), jnp.int32)]
               + ([smem((WIN_B,), jnp.float32)] if numeric else [])
               + ([pltpu.VMEM(stage_rows, dt) for dt in (jnp.int32,
                                                          jnp.float32)]
                  if numeric else [smem((WIN_OUT,), jnp.int32)])
               + [smem((3,), jnp.int32), smem((2,), jnp.int32),
                  pltpu.VMEM((rows, LANES), jnp.int32)]
               + ([pltpu.VMEM((rows, LANES), jnp.float32)] if numeric
                  else [])
               + [pltpu.SemaphoreType.DMA((1 + n_a + n_b + n_out,))]
               + ([smem((CWIN,), jnp.int32)] if counters else []))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    n_in = 1 + n_a + n_b
    dtypes = [jnp.int32, jnp.float32][:n_out]
    out_shape = [jax.ShapeDtypeStruct(
        (n_members * out_len // LANES, LANES) if numeric
        else (n_members * out_len,), dt) for dt in dtypes]
    if counters:
        out_shape.append(jax.ShapeDtypeStruct((n_members * CWIN,),
                                              jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # offsets, bin_tsize
        grid=(n_members, n_bins),
        in_specs=[any_spec] * n_in,
        out_specs=[any_spec] * len(out_shape),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="spgemm_hash_" + ("numeric" if numeric else "symbolic"),
    )
    return jax.jit(call)


def symbolic_call(n_members: int, n_bins: int, m: int, lens: tuple,
                  out_len: int, strides: tuple, table_size: int,
                  vector: bool, interpret: bool, counters: bool = False):
    """Symbolic phase (exact nnz per row of C); see :func:`_hash_call`."""
    return _hash_call(False, n_members, n_bins, m, lens, out_len, strides,
                      table_size, vector, interpret, counters)


def numeric_call(n_members: int, n_bins: int, m: int, lens: tuple,
                 out_len: int, strides: tuple, table_size: int,
                 vector: bool, interpret: bool, counters: bool = False):
    """Numeric phase (unsorted columns and values); see
    :func:`_hash_call`."""
    return _hash_call(True, n_members, n_bins, m, lens, out_len, strides,
                      table_size, vector, interpret, counters)
