"""Pallas TPU kernel: block-row Gustavson SpGEMM over BCSR (TPU adaptation).

This is the paper's hash algorithm lifted to the tile granularity the MXU
needs (DESIGN.md sections 2 + 17): the unit of sparsity is a dense
``(bm, bk)`` tile, the hash keys are **block**-column ids, and the
accumulator is a bank of ``(bm, bn)`` VMEM tiles addressed by the hash
table -- i.e. Fig. 7 where `insert` allocates an MXU accumulator tile
instead of a scalar.

Per grid program (one equal-flop bin of block rows, C1):
  for block-row i in bin:                      # Gustavson outer loop
    reinit table                               # C5: reuse, don't realloc
    for j in A.block_row(i):                   # A tiles
      for t in B.block_row(A.bcol[j]):         # B tiles
        slot = hash_probe(B.bcol[t])           # C2: linear probing
        acc[slot] += A.block[j] @ B.block[t]   # MXU (preferred f32 accum)
    flush occupied slots -> C blocks           # unsorted block order (C8)

Like the scalar hash kernel, every bin probes and flushes only its own
power-of-two effective table prefix (Fig. 7 lines 9-12): ``bin_tsize``
rides in as a prefetched scalar so a bin of light block rows never scans
the single worst row's table -- with ``(bm, bn)`` accumulator tiles the
flush saving is ``bm * bn`` times the scalar kernel's.

The batched-grid variant (``batched_numeric_call``) adds a leading grid
dimension over fleet members -- grid ``(n_members, n_bins)``, member
payloads blocked ``(1, bcap[, bm, bk])`` by BlockSpec, schedules as 2-D
prefetched scalars indexed ``[member, bin]`` -- exactly the shape
``spgemm_hash`` uses so the planned BCSR path traces under ``vmap``
through the ``custom_vmap`` rule in ``ops.py``.  The scratch bank is
shared across the whole grid: the block-row loop reinitializes it per
block row, so member programs cannot observe each other.

The scalar-CSR hash kernel (`spgemm_hash`) handles the sparse regime where
blocks would be mostly empty; `core.recipe` arbitrates (block density term).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spgemm_hash.kernel import EMPTY, HASH_CONST

#: Vector probe width (keys compared per step in vector mode).
CHUNK = 8


def _hash(key: jax.Array, mask: jax.Array) -> jax.Array:
    return (key * HASH_CONST) & mask


def _probe_scalar(tkey_ref, key, table_size):
    """Linear probing (Fig. 8a): return slot holding `key` or first empty.

    ``table_size`` may be a static int or a traced per-bin scalar; either
    way it must be a power of two.  The probed key rides in the loop carry
    so the cond never reads the ref.
    """
    mask = jnp.int32(table_size) - 1

    def cond(state):
        _, k = state
        return (k != key) & (k != EMPTY)

    def body(state):
        idx, _ = state
        nidx = (idx + 1) & mask
        return nidx, tkey_ref[nidx]

    idx0 = _hash(key, mask)
    idx, _ = jax.lax.while_loop(cond, body, (idx0, tkey_ref[idx0]))
    return idx


def _probe_vector(tkey_ref, key, table_size):
    """Chunked probing (Fig. 8b): compare CHUNK table entries per step.

    The hash addresses a *chunk*; within a chunk, hit/empty lanes are found
    with a masked iota-min.  Falls through to the next chunk on a full miss
    (linear probing over chunks).  ``table_size`` is >= CHUNK.
    """
    cmask = jnp.int32(table_size) // CHUNK - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK,), 0)
    BIG = CHUNK + 1

    def load(chunk_id):
        return tkey_ref[pl.ds(chunk_id * CHUNK, CHUNK)]

    def cond(state):
        _, ks = state
        return ~jnp.any((ks == key) | (ks == EMPTY))

    def body(state):
        chunk_id, _ = state
        nid = (chunk_id + 1) & cmask
        return nid, load(nid)

    c0 = _hash(key, cmask)
    chunk_id, ks = jax.lax.while_loop(cond, body, (c0, load(c0)))
    hit_lane = jnp.min(jnp.where(ks == key, lane, BIG))
    empty_lane = jnp.min(jnp.where(ks == EMPTY, lane, BIG))
    lane_id = jnp.where(hit_lane < BIG, hit_lane, empty_lane)
    return chunk_id * CHUNK + lane_id


class _View:
    """1-D view of a ref's row ``lead`` so ``_block_row_loop`` runs
    unchanged on batched operands (``ref[lead, i]``)."""

    def __init__(self, ref, lead):
        self._ref, self._lead = ref, lead

    def __getitem__(self, i):
        return self._ref[self._lead, i]

    def __setitem__(self, i, v):
        self._ref[self._lead, i] = v


def _block_row_loop(i, *, indptr_a_ref, indptr_b_ref, a_bcol_ref, a_blk_ref,
                    b_bcol_ref, b_blk_ref, tkey_ref, tacc_ref, tsz, vector):
    """Fig. 1 inner loops for one output *block* row, hash accumulation.

    ``tsz`` is this bin's effective table size (a power of two <= the
    static scratch allocation); probes never leave the ``[0, tsz)``
    prefix, so accumulator tiles past it stay zero and cost nothing but
    the vectorized whole-bank reinit.
    """
    probe = _probe_vector if vector else _probe_scalar
    # Fig. 7: "reuses that hash table ... by reinitializing for each row".
    tkey_ref[...] = jnp.full_like(tkey_ref, EMPTY)
    tacc_ref[...] = jnp.zeros_like(tacc_ref)

    def do_a(j, _):
        k = a_bcol_ref[j]
        a_blk = a_blk_ref[j]                      # (bm, bk) VMEM tile

        def do_b(t, _):
            c = b_bcol_ref[t]
            slot = probe(tkey_ref, c, tsz)
            tkey_ref[slot] = c
            # MXU tile product with f32 accumulation (the PR-6 rounding
            # contract: the backend may fuse each scalar lane into FMAs,
            # so bitwise claims vs per-product-rounding oracles hold on
            # dyadic values and to 1 ulp per product otherwise).
            tacc_ref[slot] = tacc_ref[slot] + jnp.dot(
                a_blk, b_blk_ref[t], preferred_element_type=jnp.float32)
            return 0

        return jax.lax.fori_loop(indptr_b_ref[k], indptr_b_ref[k + 1],
                                 do_b, 0)

    jax.lax.fori_loop(indptr_a_ref[i], indptr_a_ref[i + 1], do_a, 0)


def _numeric_kernel(offsets_ref, tsize_ref, indptr_a_ref, indptr_b_ref,
                    indptr_c_ref, a_bcol_ref, a_blk_ref, b_bcol_ref,
                    b_blk_ref, out_bcol_ref, out_blk_ref, tkey_ref,
                    tacc_ref, *, table_size, vector):
    bin_id = pl.program_id(0)
    # per-bin effective table size (prefetched; clamped to the allocation)
    tsz = jnp.minimum(tsize_ref[bin_id], jnp.int32(table_size))

    @pl.when(bin_id == 0)
    def _init():
        out_bcol_ref[...] = jnp.zeros_like(out_bcol_ref)
        out_blk_ref[...] = jnp.zeros_like(out_blk_ref)

    def do_block_row(i, _):
        _block_row_loop(
            i, indptr_a_ref=indptr_a_ref, indptr_b_ref=indptr_b_ref,
            a_bcol_ref=a_bcol_ref, a_blk_ref=a_blk_ref,
            b_bcol_ref=b_bcol_ref, b_blk_ref=b_blk_ref,
            tkey_ref=tkey_ref, tacc_ref=tacc_ref, tsz=tsz, vector=vector)
        # Flush occupied slots in table order -> **unsorted** block
        # columns (C8).  Only this bin's [0, tsz) prefix can be occupied.
        base = indptr_c_ref[i]

        def flush(s, cnt):
            key = tkey_ref[s]
            occupied = key != EMPTY
            pos = base + cnt

            @pl.when(occupied)
            def _():
                out_bcol_ref[pos] = key
                out_blk_ref[pos] = tacc_ref[s]

            return cnt + occupied.astype(jnp.int32)

        jax.lax.fori_loop(0, tsz, flush, jnp.int32(0))
        return 0

    jax.lax.fori_loop(offsets_ref[bin_id], offsets_ref[bin_id + 1],
                      do_block_row, 0)


@functools.lru_cache(maxsize=128)
def numeric_call(n_bins: int, gm: int, bcap_a: int, bcap_b: int, bcap_c: int,
                 block_a, block_b, table_size: int, vector: bool,
                 interpret: bool):
    """Cached builder for the plain (1-D grid) numeric phase.

    Call signature of the returned function:
    ``(offsets, bin_tsize, indptr_a, indptr_b, indptr_c,
       a_bcol, a_blk, b_bcol, b_blk)`` -> ``(out_bcol, out_blk)``.
    """
    bm, bk = block_a
    bk2, bn = block_b
    assert bk == bk2, (block_a, block_b)
    kernel = functools.partial(_numeric_kernel, table_size=table_size,
                               vector=vector)
    full1 = lambda n: pl.BlockSpec((n,), lambda b, *p: (0,))
    full3 = lambda n, r, c: pl.BlockSpec((n, r, c), lambda b, *p: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # offsets, bin_tsize, indptr_a/b, indptr_c
        grid=(n_bins,),
        in_specs=[full1(bcap_a), full3(bcap_a, bm, bk),
                  full1(bcap_b), full3(bcap_b, bk, bn)],
        out_specs=[full1(bcap_c), full3(bcap_c, bm, bn)],
        scratch_shapes=[pltpu.VMEM((table_size,), jnp.int32),
                        pltpu.VMEM((table_size, bm, bn), jnp.float32)],
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bcap_c,), jnp.int32),
                   jax.ShapeDtypeStruct((bcap_c, bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    ))


# ---------------------------------------------------------------------------
# batched grid: one extra grid dimension over fleet members
# ---------------------------------------------------------------------------

def _batched_numeric_kernel(offsets_ref, tsize_ref, indptr_a_ref,
                            indptr_b_ref, indptr_c_ref, a_bcol_ref,
                            a_blk_ref, b_bcol_ref, b_blk_ref, out_bcol_ref,
                            out_blk_ref, tkey_ref, tacc_ref, *,
                            table_size, vector):
    e = pl.program_id(0)                      # fleet member
    b = pl.program_id(1)                      # equal-flop block-row bin
    tsz = jnp.minimum(tsize_ref[e, b], jnp.int32(table_size))
    ic = _View(indptr_c_ref, e)               # prefetched: full 2-D array
    oc, ob = _View(out_bcol_ref, 0), _View(out_blk_ref, 0)

    @pl.when(b == 0)
    def _init():
        out_bcol_ref[...] = jnp.zeros_like(out_bcol_ref)
        out_blk_ref[...] = jnp.zeros_like(out_blk_ref)

    def do_block_row(i, _):
        _block_row_loop(
            i, indptr_a_ref=_View(indptr_a_ref, e),
            indptr_b_ref=_View(indptr_b_ref, e),
            a_bcol_ref=_View(a_bcol_ref, 0), a_blk_ref=_View(a_blk_ref, 0),
            b_bcol_ref=_View(b_bcol_ref, 0), b_blk_ref=_View(b_blk_ref, 0),
            tkey_ref=tkey_ref, tacc_ref=tacc_ref, tsz=tsz, vector=vector)
        base = ic[i]

        def flush(s, cnt):
            key = tkey_ref[s]
            occupied = key != EMPTY
            pos = base + cnt

            @pl.when(occupied)
            def _():
                oc[pos] = key
                ob[pos] = tacc_ref[s]

            return cnt + occupied.astype(jnp.int32)

        jax.lax.fori_loop(0, tsz, flush, jnp.int32(0))
        return 0

    jax.lax.fori_loop(offsets_ref[e, b], offsets_ref[e, b + 1],
                      do_block_row, 0)


@functools.lru_cache(maxsize=128)
def batched_numeric_call(n_members: int, n_bins: int, gm: int, bcap_a: int,
                         bcap_b: int, bcap_c: int, block_a, block_b,
                         table_size: int, vector: bool, interpret: bool):
    """Batched-grid numeric phase: grid ``(n_members, n_bins)``.

    Mirrors :func:`numeric_call` with a leading member axis on every
    operand: schedules ``(n_members, n_bins+1)`` / ``(n_members,
    n_bins)``, block payloads ``(n_members, bcap[, bm, bk])``, outputs
    ``(n_members, bcap_c[, bm, bn])``.  The scratch bank is shared across
    the whole grid -- the block-row loop reinitializes it per block row,
    so member programs cannot observe each other.
    """
    bm, bk = block_a
    bk2, bn = block_b
    assert bk == bk2, (block_a, block_b)
    kernel = functools.partial(_batched_numeric_kernel,
                               table_size=table_size, vector=vector)
    bfull1 = lambda n: pl.BlockSpec((1, n), lambda e, b, *p: (e, 0))
    bfull3 = lambda n, r, c: pl.BlockSpec((1, n, r, c),
                                          lambda e, b, *p: (e, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # offsets, bin_tsize, indptr_a/b, indptr_c
        grid=(n_members, n_bins),
        in_specs=[bfull1(bcap_a), bfull3(bcap_a, bm, bk),
                  bfull1(bcap_b), bfull3(bcap_b, bk, bn)],
        out_specs=[bfull1(bcap_c), bfull3(bcap_c, bm, bn)],
        scratch_shapes=[pltpu.VMEM((table_size,), jnp.int32),
                        pltpu.VMEM((table_size, bm, bn), jnp.float32)],
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_members, bcap_c), jnp.int32),
                   jax.ShapeDtypeStruct((n_members, bcap_c, bm, bn),
                                        jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    ))
