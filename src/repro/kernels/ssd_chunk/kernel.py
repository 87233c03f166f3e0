"""Pallas TPU kernel: Mamba-2 SSD chunk scan [arXiv:2405.21060].

Identified as the next kernel target by the roofline (EXPERIMENTS.md: the
mamba2 cells' remaining traffic is the (nc, nh, Q, Q) decay tensor the XLA
path materializes).  This kernel keeps the whole chunk-local working set --
decay matrix L, C.B^T panel, and the (n, hp) running state -- in VMEM and
feeds the MXU three (Q x Q)/(Q x n)-class matmuls per chunk:

  grid = (batch, heads, chunks); chunks is the innermost "arbitrary" dim
  carrying the inter-chunk state in scratch (the lax.scan of the XLA path
  becomes grid-carried VMEM state -- same trick as flash attention's kv
  loop).

Per (b, h, c):
  cum   = cumsum(log_a_c)                           # (Q,)
  L     = tril(exp(cum_i - cum_j))                  # (Q, Q)   VPU
  y     = ((C_c B_c^T) * L) @ xd_c                  # MXU
        + exp(cum) * (C_c @ state)                  # MXU (inter-chunk)
  state = exp(cum_Q) * state + B_c^T (exp(cum_Q - cum) xd_c)   # MXU
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(xd_ref, la_ref, b_ref, c_ref, y_ref, hT_ref, state_scr, *,
                q: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xd = xd_ref[0, :, 0, :].astype(jnp.float32)          # (Q, hp)
    la = la_ref[0, :, 0].astype(jnp.float32)             # (Q,)
    B = b_ref[0, :, 0, :].astype(jnp.float32)            # (Q, n)
    C = c_ref[0, :, 0, :].astype(jnp.float32)            # (Q, n)

    cum = jnp.cumsum(la)                                 # (Q,)
    seg = cum[:, None] - cum[None, :]                    # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)

    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y_intra = jax.lax.dot_general(CB * L, xd, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state = state_scr[...]                               # (n, hp)
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        C, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    dec_end = jnp.exp(cum[-1] - cum)                     # (Q,)
    new_state = jnp.exp(cum[-1]) * state + jax.lax.dot_general(
        B, dec_end[:, None] * xd, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (n, hp)
    state_scr[...] = new_state

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hT_ref[0, 0] = new_state.astype(hT_ref.dtype)


@functools.lru_cache(maxsize=64)
def ssd_call(batch: int, seq: int, nh: int, hp: int, g: int, n: int,
             chunk: int, dtype, interpret: bool):
    assert seq % chunk == 0 and nh % g == 0
    n_chunks = seq // chunk
    rep = nh // g
    kernel = functools.partial(_ssd_kernel, q=chunk, n_chunks=n_chunks)
    grid = (batch, nh, n_chunks)
    xd_spec = pl.BlockSpec((1, chunk, 1, hp),
                           lambda b, h, c: (b, c, h, 0))
    la_spec = pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h))
    bc_spec = pl.BlockSpec((1, chunk, 1, n),
                           lambda b, h, c: (b, c, h // rep, 0))
    return jax.jit(pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[xd_spec, la_spec, bc_spec, bc_spec],
        out_specs=[xd_spec,
                   pl.BlockSpec((1, 1, n, hp), lambda b, h, c: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((batch, seq, nh, hp), dtype),
                   jax.ShapeDtypeStruct((batch, nh, n, hp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, hp), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    ))
