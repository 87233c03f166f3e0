"""Pallas TPU kernel: SpMM  y = A @ X  (CSR x dense tall-skinny).

The square x tall-skinny use case of paper section 5.5 (multi-source BFS /
betweenness frontiers).  Grid = equal-flop row bins (C1); each program walks
its rows, gathering rows of X -- the *stanza* access pattern of section 3.3:
each gather reads a contiguous (1, k) panel, which is exactly the access
shape the MCDRAM/HBM study says is bandwidth-friendly once k is lane-wide.

Memory plumbing: A's arrays stay in HBM and are read through SMEM windows
(``kernels._smem``); X and Y stay in HBM as ``(rows, 1, k)`` so that one
row is one whole tile, DMA'd into and out of a ``(1, k)`` VMEM buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._smem import Window

#: SMEM window size (words) over indptr and the A entries.
WIN = 2048


def _spmm_kernel(offsets_ref, ip_hbm, idx_hbm, val_hbm, x_hbm, y_hbm,
                 ip_w, idx_w, val_w, wbase, xrow, acc, sems, *, l_ip, l_a):
    b = pl.program_id(0)
    ipw = Window([ip_hbm], [ip_w], wbase, 0, sems, 0, WIN)
    aw = Window([idx_hbm, val_hbm], [idx_w, val_w], wbase, 1, sems, 1, WIN)
    ipw.reset()
    aw.reset()

    def do_row(i, _):
        acc[...] = jnp.zeros_like(acc)
        (p0,) = ipw.read(i, l_ip - WIN)
        (p1,) = ipw.read(i + 1, l_ip - WIN)

        def do_nz(j, _):
            col, av = aw.read(j, l_a - WIN)
            cp = pltpu.make_async_copy(x_hbm.at[col], xrow, sems.at[3])
            cp.start()
            cp.wait()
            acc[...] = acc[...] + av * xrow[...].astype(jnp.float32)
            return 0

        jax.lax.fori_loop(p0, p1, do_nz, 0)
        xrow[...] = acc[...].astype(xrow.dtype)
        cp = pltpu.make_async_copy(xrow, y_hbm.at[i], sems.at[3])
        cp.start()
        cp.wait()
        return 0

    jax.lax.fori_loop(offsets_ref[b], offsets_ref[b + 1], do_row, 0)


@functools.lru_cache(maxsize=128)
def spmm_call(n_bins: int, m: int, n: int, k: int, l_ip: int, l_a: int,
              dtype, interpret: bool):
    """Cached builder.  Operands: ``offsets`` (scalar prefetch), then in
    HBM ``indptr_a`` (padded to ``l_ip``), ``a_idx``/``a_val`` (padded to
    ``l_a``) and ``x`` as ``(n, 1, k)``; returns ``y`` as ``(m, 1, k)``."""
    kernel = functools.partial(_spmm_kernel, l_ip=l_ip, l_a=l_a)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,    # offsets
        grid=(n_bins,),
        in_specs=[any_spec] * 4,
        out_specs=any_spec,
        scratch_shapes=[pltpu.SMEM((WIN,), jnp.int32),
                        pltpu.SMEM((WIN,), jnp.int32),
                        pltpu.SMEM((WIN,), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.VMEM((1, k), dtype),
                        pltpu.VMEM((1, k), jnp.float32),
                        pltpu.SemaphoreType.DMA((4,))],
    )
    return jax.jit(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, k), dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="spmm",
    ))
