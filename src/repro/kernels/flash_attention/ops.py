"""Jit'd wrapper for the flash attention kernel, plus the pure-XLA chunked
fallback the dry-run lowers on non-TPU backends.

``flash_attention``      -- Pallas kernel (TPU target; interpret elsewhere).
``chunked_attention``    -- lax.scan online-softmax with O(S * bkv) memory;
                            identical math, lowers on any backend.  This is
                            what the LM stack uses under the dry-run so
                            compile-time memory stays bounded at 32k/500k.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import _compat
from . import kernel as K


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, H, Sq, D)."""
    interpret = _compat.resolve_interpret(interpret)
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    call = K.fwd_call(b, h, hkv, sq, skv, d, scale=scale, causal=causal,
                      bq=bq, bkv=bkv, dtype=q.dtype, interpret=interpret)
    return call(q, k, v)


# ---------------------------------------------------------------------------
# Flash-style chunked attention with a custom VJP (the XLA fallback path).
#
# Differentiating *through* the forward scan makes XLA save every chunk's
# probability panel -- O(S^2) residuals, exactly what flash attention
# exists to avoid (measured: +GBs of temp per device in the baseline
# dry-run; EXPERIMENTS.md Perf iteration 2).  The custom VJP stores only
# (q, k, v, out, lse) and the backward rescans kv chunks recomputing p,
# accumulating dq and emitting per-chunk dk/dv -- the standard flash
# backward, in pure XLA.
# ---------------------------------------------------------------------------

def _mask_scores(s, q_pos, k_pos, causal, window):
    if causal:
        m = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            m &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(m[None, None], s, K.NEG_INF)
    return s


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_attn_core(q, k, v, causal, window, bkv, shard_q, shard_kv):
    out, _ = _chunked_attn_fwd_impl(q, k, v, causal, window, bkv, shard_q,
                                    shard_kv)
    return out


def _chunked_attn_fwd_impl(q, k, v, causal, window, bkv, shard_q, shard_kv):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    n_chunks = skv // bkv
    scale = 1.0 / (d ** 0.5)
    qf = shard_q(q.astype(jnp.float32))
    # keep kv in model dtype through the scan xs (the SP gather then moves
    # bf16); upcast per-chunk inside the step.
    ks = jnp.moveaxis(k.reshape(b, h, n_chunks, bkv, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, h, n_chunks, bkv, d), 2, 0)
    q_pos = (skv - sq) + jnp.arange(sq)

    def step(carry, xs):
        m, l, acc = carry
        ci, kc, vc = xs
        kcr = shard_kv(kc)
        vcr = shard_kv(vc)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       kcr.astype(jnp.float32)) * scale
        s = _mask_scores(s, q_pos, ci * bkv + jnp.arange(bkv), causal,
                         window)
        m_new = shard_q(jnp.maximum(m, jnp.max(s, axis=-1)))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = shard_q(l * alpha + jnp.sum(p, axis=-1))
        # PV contraction in the *input* dtype: for bf16 models this halves
        # the dominant attention HBM traffic and feeds the MXU its native
        # dtype (Perf iter 7); softmax statistics stay f32; f32 inputs keep
        # full precision.
        acc_new = shard_q(acc * alpha[..., None] +
                          jnp.einsum("bhqk,bhkd->bhqd",
                                     p.astype(vcr.dtype), vcr
                                     ).astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = shard_q(jnp.full((b, h, sq), K.NEG_INF, jnp.float32))
    l0 = shard_q(jnp.zeros((b, h, sq), jnp.float32))
    acc0 = shard_q(jnp.zeros((b, h, sq, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0),
                                  (jnp.arange(n_chunks), ks, vs))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out, lse


def _chunked_attn_vjp_fwd(q, k, v, causal, window, bkv, shard_q, shard_kv):
    out, lse = _chunked_attn_fwd_impl(q, k, v, causal, window, bkv, shard_q,
                                      shard_kv)
    return out, (q, k, v, out, lse)


def _chunked_attn_vjp_bwd(causal, window, bkv, shard_q, shard_kv, res, dout):
    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    skv = k.shape[2]
    n_chunks = skv // bkv
    scale = 1.0 / (d ** 0.5)
    qf = shard_q(q.astype(jnp.float32))
    do = shard_q(dout.astype(jnp.float32))
    Drow = shard_q(jnp.sum(do * out.astype(jnp.float32), axis=-1))  # (B,H,S)
    ks = jnp.moveaxis(k.reshape(b, h, n_chunks, bkv, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, h, n_chunks, bkv, d), 2, 0)
    q_pos = (skv - sq) + jnp.arange(sq)

    def step(dq, xs):
        ci, kc, vc = xs
        kcr = shard_kv(kc)
        vcr = shard_kv(vc)
        lp = kcr.dtype   # low-precision contraction dtype = input dtype
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       kcr.astype(jnp.float32)) * scale
        s = _mask_scores(s, q_pos, ci * bkv + jnp.arange(bkv), causal,
                         window)
        p = jnp.exp(s - lse[..., None])                    # recomputed
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p.astype(lp),
                          do.astype(lp)).astype(jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vcr.astype(jnp.float32))
        ds = p * (dp - Drow[..., None])
        dq = shard_q(dq + jnp.einsum("bhqk,bhkd->bhqd", ds.astype(lp),
                                     kcr).astype(jnp.float32) * scale)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(lp),
                          qf.astype(lp)).astype(jnp.float32) * scale
        return dq, (dk_c, dv_c)

    dq0 = shard_q(jnp.zeros((b, h, sq, d), jnp.float32))
    dq, (dks, dvs) = jax.lax.scan(step, dq0,
                                  (jnp.arange(n_chunks), ks, vs))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, skv, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, skv, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_chunked_attn_core.defvjp(_chunked_attn_vjp_fwd, _chunked_attn_vjp_bwd)


def chunked_attention(q, k, v, shard=None, shard_kv=None, *,
                      causal: bool = True, window: int | None = None,
                      bkv: int = 512):
    # NOTE: deliberately not jit-wrapped -- always called inside the outer
    # jitted step, and `shard` closures would defeat the jit cache.
    """Online-softmax attention as a lax.scan over kv chunks (flash math).

    Peak live intermediate is (B, H, Sq, bkv) instead of (B, H, Sq, Skv).
    GQA KV heads are repeated up-front so every tensor keeps the clean
    (batch->DP, heads->TP) layout -- folding heads into (hkv, group) splits
    one mesh axis across two tensor dims, which SPMD cannot express as a
    sharding and resolves by replicating scan carries (the "involuntary
    full rematerialization" found in the baseline dry-run; EXPERIMENTS.md
    section Perf iteration 1).

    ``shard``: optional callable(array) -> array applying the caller's
    sharding constraint; it is applied to q/k/v and to every scan carry so
    both the forward and the transposed (backward) scan stay head-sharded.

    q positions are assumed to be the *last* Sq positions of the kv stream
    (prefill: Sq == Skv; decode: Sq == 1).  ``window`` adds sliding-window
    masking (recurrentgemma local attention).
    """
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = h // hkv
    bkv = min(bkv, skv)
    assert skv % bkv == 0
    ident = lambda x: x
    shard = shard or ident
    shard_kv = shard_kv or ident
    # constrain (=> gather, under SP) the *un-repeated* GQA heads, then
    # repeat locally: the all-gather moves n_kv_heads, not n_heads.
    kf = jnp.repeat(shard_kv(k), group, axis=1)
    vf = jnp.repeat(shard_kv(v), group, axis=1)
    return _chunked_attn_core(q, kf, vf, causal, window, bkv, shard,
                              shard_kv)
