"""Pallas TPU kernel: causal flash attention (online-softmax), GQA-aware.

Used by the LM stack for prefill (the 32k cells would otherwise materialize
S^2 score panels: 32768^2 * 2B = 2 GiB per head).  Standard two-level
structure: grid = (batch, q_head, q_block, kv_block) with the kv dimension
innermost ("arbitrary" semantics) carrying (m, l, acc) scratch across
iterations; output is emitted on the last *needed* kv block.

TPU notes:
  * q/k/v blocks are (bq, d) / (bkv, d) VMEM tiles; d is the lane dim
    (128/256 -> MXU-aligned);
  * fully-causally-masked kv blocks are skipped with ``pl.when`` -- the
    paper's C1 lesson (do no work you can statically avoid) applied to the
    attention grid;
  * GQA: the kv head index is ``q_head // group`` in the BlockSpec index
    map, so KV tiles are fetched once per group on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, bq, bkv, n_kv_blocks):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal block skip: block is needed iff its first kv index is <= the
    # last q index of this q block.
    if causal:
        needed = ki * bkv <= qi * bq + bq - 1
        last_needed = jnp.minimum(jnp.int32(n_kv_blocks - 1),
                                  (qi * bq + bq - 1) // bkv)
    else:
        needed = None
        last_needed = n_kv_blocks - 1

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            cols = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    if causal:
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ki == last_needed)
    def _emit():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def fwd_call(batch: int, n_heads: int, n_kv_heads: int, seq_q: int,
             seq_kv: int, d: int, *, scale: float, causal: bool,
             bq: int, bkv: int, dtype, interpret: bool):
    group = n_heads // n_kv_heads
    nq, nkv = seq_q // bq, seq_kv // bkv
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bkv=bkv, n_kv_blocks=nkv)
    grid = (batch, n_heads, nq, nkv)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, d),
                           lambda b, h, qi, ki: (b, h // group, ki, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((batch, n_heads, seq_q, d), dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )
