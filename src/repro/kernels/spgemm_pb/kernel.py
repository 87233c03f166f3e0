"""Pallas TPU kernel pair: propagation-blocking SpGEMM merge.

Gu/Moreira/Edelsohn/Azad ("Bandwidth-Optimized Parallel Algorithms for
SpGEMM using Propagation Blocking", PAPERS.md) split the outer-product
formulation into a *propagate* phase that buckets partial products by
column segment and a *merge* phase that reduces each bucket privately --
no global hash table, no random scatter across the whole output: every
memory stream is a contiguous bucket that fits in cache.  Here the bucket
layout is frozen at plan time (``core.pb``), so both phases are pure
numeric gathers over plan arrays (DESIGN.md section 18):

  scatter (grid over buckets):
    pp[g, i] = a_data[src_a[g, i]] * b_data[src_b[g, i]]   i < bucket_nnz[g]
  merge (grid over buckets):
    out[seg[g, i]] += pp[g, i]                             i < bucket_nnz[g]

``src_a``/``src_b`` gather straight from the operands' value arrays (the
plan resolved every CSR walk already), and ``seg`` maps each partial
product to its output slot in the *column-sorted* CSR of C.  Because a
bucket owns a contiguous column range, all duplicates of one output
coordinate live in exactly one bucket -- bucket programs write disjoint
output slots, which is what makes the merge a private, sequential-grid
scatter-add instead of an atomic or a psum over a dense accumulator.

Keeping scatter and merge as a *pair* (not one fused kernel) is
deliberate: the distributed lift inserts the all-to-all exchange between
them (scatter on the producer chip, merge on the consumer chip), so the
single-node and mesh paths share both kernels.

The batched variants add a leading grid dimension over fleet members --
grid ``(n_members, n_buckets)`` -- exactly the shape the hash/bcsr
kernels use, so the planned PB path traces under ``vmap`` through the
``custom_vmap`` rules in ``ops.py``.

Rounding contract (PR 6): one multiply rounding per partial product and
one add rounding per merge step, same accumulation order as the frozen
plan; the jnp twin (``ref.py``) reduces with ``segment_sum`` in the same
bucket-major order, so values agree bitwise on dyadic values and to 1 ulp
per product otherwise.  All gather/scatter indices are clipped to their
static capacity so the verifier's interval analysis can discharge the
in-bounds obligations (``repro.verify.bounds``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _full(spec_len):
    # whole-array block shared by every grid program
    return pl.BlockSpec((spec_len,), lambda g, *prefetch: (0,))


def _bucket(cap):
    # one bucket's row of a (n_buckets, cap) operand per grid program
    return pl.BlockSpec((1, cap), lambda g, *prefetch: (g, 0))


# ---------------------------------------------------------------------------
# scatter: expand one bucket's partial products from the operand values
# ---------------------------------------------------------------------------

def _scatter_kernel(bucket_nnz_ref, src_a_ref, src_b_ref, a_val_ref,
                    b_val_ref, pp_ref, *, cap_a, cap_b):
    g = pl.program_id(0)
    pp_ref[...] = jnp.zeros_like(pp_ref)       # pad lanes stay 0

    def body(i, _):
        ja = jnp.clip(src_a_ref[0, i], 0, cap_a - 1)
        jb = jnp.clip(src_b_ref[0, i], 0, cap_b - 1)
        pp_ref[0, i] = a_val_ref[ja] * b_val_ref[jb]
        return 0

    jax.lax.fori_loop(0, bucket_nnz_ref[g], body, 0)


@functools.lru_cache(maxsize=256)
def scatter_call(n_buckets: int, bucket_cap: int, cap_a: int, cap_b: int,
                 interpret: bool):
    """Cached builder for the bucket-scatter grid.

    Call signature: ``(bucket_nnz, src_a, src_b, a_data, b_data)`` ->
    ``pp`` of shape ``(n_buckets, bucket_cap)`` (float32, pad lanes 0).
    """
    kernel = functools.partial(_scatter_kernel, cap_a=cap_a, cap_b=cap_b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # bucket_nnz
        grid=(n_buckets,),
        in_specs=[_bucket(bucket_cap), _bucket(bucket_cap),
                  _full(cap_a), _full(cap_b)],
        out_specs=_bucket(bucket_cap),
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_buckets, bucket_cap), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    ))


# ---------------------------------------------------------------------------
# merge: reduce one bucket's products into its (disjoint) output slots
# ---------------------------------------------------------------------------

def _merge_kernel(bucket_nnz_ref, seg_ref, pp_ref, out_ref, *, cap_c):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        s = jnp.clip(seg_ref[g, i], 0, cap_c - 1)
        out_ref[s] = out_ref[s] + pp_ref[0, i]
        return 0

    jax.lax.fori_loop(0, bucket_nnz_ref[g], body, 0)


@functools.lru_cache(maxsize=256)
def merge_call(n_buckets: int, bucket_cap: int, cap_c: int, interpret: bool):
    """Cached builder for the per-bucket merge grid.

    Call signature: ``(bucket_nnz, seg, pp)`` -> ``data_c`` of shape
    ``(cap_c,)`` (float32).  ``seg`` rides in SMEM as a prefetched scalar
    array: the merge's control stream (output slots) never touches VMEM.
    """
    kernel = functools.partial(_merge_kernel, cap_c=cap_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # bucket_nnz, seg
        grid=(n_buckets,),
        in_specs=[_bucket(bucket_cap)],
        out_specs=_full(cap_c),
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap_c,), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    ))


# ---------------------------------------------------------------------------
# batched grid: one extra grid dimension over fleet members
# ---------------------------------------------------------------------------

def _bbucket(cap):
    return pl.BlockSpec((1, 1, cap), lambda e, g, *prefetch: (e, g, 0))


def _bfull(cap):
    return pl.BlockSpec((1, cap), lambda e, g, *prefetch: (e, 0))


def _batched_scatter_kernel(bucket_nnz_ref, src_a_ref, src_b_ref, a_val_ref,
                            b_val_ref, pp_ref, *, cap_a, cap_b):
    e = pl.program_id(0)
    g = pl.program_id(1)
    pp_ref[...] = jnp.zeros_like(pp_ref)

    def body(i, _):
        ja = jnp.clip(src_a_ref[0, 0, i], 0, cap_a - 1)
        jb = jnp.clip(src_b_ref[0, 0, i], 0, cap_b - 1)
        pp_ref[0, 0, i] = a_val_ref[0, ja] * b_val_ref[0, jb]
        return 0

    jax.lax.fori_loop(0, bucket_nnz_ref[e, g], body, 0)


@functools.lru_cache(maxsize=256)
def batched_scatter_call(n_members: int, n_buckets: int, bucket_cap: int,
                         cap_a: int, cap_b: int, interpret: bool):
    """Batched scatter: grid ``(n_members, n_buckets)``, member payloads
    blocked to one member per program."""
    kernel = functools.partial(_batched_scatter_kernel, cap_a=cap_a,
                               cap_b=cap_b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_members, n_buckets),
        in_specs=[_bbucket(bucket_cap), _bbucket(bucket_cap),
                  _bfull(cap_a), _bfull(cap_b)],
        out_specs=_bbucket(bucket_cap),
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_members, n_buckets, bucket_cap),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    ))


def _batched_merge_kernel(bucket_nnz_ref, seg_ref, pp_ref, out_ref, *,
                          cap_c):
    e = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        s = jnp.clip(seg_ref[e, g, i], 0, cap_c - 1)
        out_ref[0, s] = out_ref[0, s] + pp_ref[0, 0, i]
        return 0

    jax.lax.fori_loop(0, bucket_nnz_ref[e, g], body, 0)


@functools.lru_cache(maxsize=256)
def batched_merge_call(n_members: int, n_buckets: int, bucket_cap: int,
                       cap_c: int, interpret: bool):
    """Batched merge: grid ``(n_members, n_buckets)``, one output row of
    ``(n_members, cap_c)`` per member."""
    kernel = functools.partial(_batched_merge_kernel, cap_c=cap_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_members, n_buckets),
        in_specs=[_bbucket(bucket_cap)],
        out_specs=_bfull(cap_c),
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_members, cap_c), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    ))
