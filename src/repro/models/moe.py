"""Mixture-of-Experts layer: top-k routing + expert-parallel dispatch.

This is where the paper's technique is a first-class LM feature
(DESIGN.md section 5): the token->expert dispatch matrix is a sparse matrix
in CSR-by-expert layout, dispatch is an SpMM, and the paper's C8 finding
(skip the sort when order doesn't matter) maps to the *unstable* dispatch
sort -- tokens within an expert have no required order, so
``stable_dispatch_sort=False`` (default) uses the cheaper unstable sort and
benchmarks the difference (bench `moe_dispatch`).

Two implementations:
  * ``dense``     -- single-device reference (smoke tests, examples);
  * ``shard_map`` -- production expert parallelism: tokens sharded
    (batch->DP, seq->SP), experts sharded E->TP ("model"); the dispatch
    buffer (E, C, d) is exchanged with ``lax.all_to_all`` over "model",
    expert FFN weights are fe-sharded over FSDP axes and all-gathered
    per layer (ZeRO-3), and the combine reverses the all_to_all.

Both share `_route` / `_dispatch` / `_combine`, so the reference IS the
oracle for the distributed path (tested in tests/test_moe.py).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.parallel.sharding import ParallelCtx, safe_pspec
from . import layers as L


def init(key, cfg):
    m = cfg.moe
    d, E, fe = cfg.d_model, m.n_experts, m.d_expert
    ks = jax.random.split(key, 4)
    return {
        "router": L.dense_init(ks[0], d, E, scale=d ** -0.5),
        "we_gate": jax.random.normal(ks[1], (E, d, fe), jnp.float32) * d ** -0.5,
        "we_in":   jax.random.normal(ks[2], (E, d, fe), jnp.float32) * d ** -0.5,
        "we_out":  jax.random.normal(ks[3], (E, fe, d), jnp.float32) * fe ** -0.5,
    }


# ---------------------------------------------------------------------------
# Routing + sparse dispatch (shared by both impls)
# ---------------------------------------------------------------------------

def _route(params, x2, cfg):
    """x2: (T, d) -> (top_p (T,k), top_i (T,k), aux_loss scalar)."""
    m = cfg.moe
    logits = (x2.astype(jnp.float32) @ params["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_i[:, 0], m.n_experts), axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    return top_p.astype(x2.dtype), top_i, aux


def _dispatch(x2, top_i, n_experts: int, capacity: int, stable: bool):
    """Build the (E*C, d) expert input buffer -- an SpMM with the
    CSR-by-expert dispatch matrix.

    Returns (buffer, slot_of_assignment (T, k) with -1 for dropped)."""
    T, k = top_i.shape
    d = x2.shape[1]
    flat_e = top_i.reshape(-1)                                    # (T*k,)
    # C8: unstable sort -- order within an expert is irrelevant.
    order = jnp.argsort(flat_e, stable=stable)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(T * k, dtype=jnp.int32) - first              # rank in expert
    keep = pos < capacity
    dest = jnp.where(keep, sorted_e * capacity + pos, n_experts * capacity)
    src_tok = order // k
    buf = jnp.zeros((n_experts * capacity, d), x2.dtype)
    buf = buf.at[dest].set(x2[src_tok], mode="drop")
    slot = jnp.full((T * k,), -1, jnp.int32).at[order].set(
        jnp.where(keep, dest, -1))
    return buf, slot.reshape(T, k)


def _combine(ybuf, slot, top_p):
    """Inverse dispatch: gather expert outputs back and mix by gate probs."""
    T, k = slot.shape
    safe = jnp.maximum(slot, 0)
    y = ybuf[safe.reshape(-1)].reshape(T, k, -1)
    y = jnp.where((slot >= 0)[..., None], y, 0)
    return jnp.einsum("tkd,tk->td", y, top_p.astype(y.dtype))


def _expert_ffn(xb, wg, wi, wo):
    """xb: (E, C, d); weights (E, d, fe)/(E, fe, d). Grouped SwiGLU."""
    dt = xb.dtype
    g = jnp.einsum("ecd,edf->ecf", xb, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xb, wi.astype(dt))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))


# ---------------------------------------------------------------------------
# Reference (single device / no mesh)
# ---------------------------------------------------------------------------

def apply_dense(params, x, cfg):
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    cap = max(1, int(T * m.top_k / m.n_experts * m.capacity_factor))
    top_p, top_i, aux = _route(params, x2, cfg)
    buf, slot = _dispatch(x2, top_i, m.n_experts, cap,
                          m.stable_dispatch_sort)
    xb = buf.reshape(m.n_experts, cap, d)
    yb = _expert_ffn(xb, params["we_gate"], params["we_in"], params["we_out"])
    y = _combine(yb.reshape(m.n_experts * cap, d), slot, top_p)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map implementation
# ---------------------------------------------------------------------------

def apply_ep(params, x, cfg, pctx: ParallelCtx):
    m = cfg.moe
    mesh = pctx.mesh
    B, S, d = x.shape
    tp = pctx.tp_axis
    ep = mesh.shape[tp]
    assert m.n_experts % ep == 0, (m.n_experts, ep)

    x_spec = safe_pspec(mesh, x.shape, (pctx.batch_axes, pctx.tp_axis, None))
    r_spec = P(None, None)
    fsdp = pctx.fsdp if pctx.fsdp else None
    wg_spec = safe_pspec(mesh, params["we_gate"].shape, (tp, None, fsdp))
    wo_spec = safe_pspec(mesh, params["we_out"].shape, (tp, fsdp, None))
    out_spec = x_spec

    # local token count (static)
    def _shards(spec, shape):
        n = 1
        for dim, s in zip(shape, spec):
            if s is None:
                continue
            for a in ((s,) if isinstance(s, str) else s):
                n *= mesh.shape[a]
        return n

    t_loc = (B * S) // _shards(x_spec, x.shape)
    cap = max(1, int(t_loc * m.top_k / m.n_experts * m.capacity_factor))
    fe_gather_axes = tuple(a for a in (pctx.fsdp or ())
                           if a in mesh.shape and
                           wg_spec[2] is not None and
                           (a == wg_spec[2] or (isinstance(wg_spec[2], tuple)
                                                and a in wg_spec[2])))

    def local(x_l, router, wg_l, wi_l, wo_l):
        bl, sl, _ = x_l.shape
        x2 = x_l.reshape(bl * sl, d)
        top_p, top_i, aux = _route({"router": router}, x2, cfg)
        buf, slot = _dispatch(x2, top_i, m.n_experts, cap,
                              m.stable_dispatch_sort)
        xb = buf.reshape(m.n_experts, cap, d)
        # exchange tokens for experts over the TP/EP axis
        xb = jax.lax.all_to_all(xb, tp, split_axis=0, concat_axis=1,
                                tiled=True)          # (E/ep, ep*cap, d)
        # ZeRO-3: regather fe-sharded expert weights for this layer.
        # Cast to the compute dtype BEFORE the gather so the collective
        # moves bf16, not f32 master bytes (Perf iteration 8).
        cdt = x_l.dtype
        if fe_gather_axes:
            wg = jax.lax.all_gather(wg_l.astype(cdt), fe_gather_axes,
                                    axis=2, tiled=True)
            wi = jax.lax.all_gather(wi_l.astype(cdt), fe_gather_axes,
                                    axis=2, tiled=True)
            wo = jax.lax.all_gather(wo_l.astype(cdt), fe_gather_axes,
                                    axis=1, tiled=True)
        else:
            wg, wi, wo = wg_l.astype(cdt), wi_l.astype(cdt), wo_l.astype(cdt)
        yb = _expert_ffn(xb, wg, wi, wo)             # (E/ep, ep*cap, d)
        yb = jax.lax.all_to_all(yb, tp, split_axis=1, concat_axis=0,
                                tiled=True)          # (E, cap, d)
        y = _combine(yb.reshape(m.n_experts * cap, d), slot, top_p)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y.reshape(bl, sl, d), aux

    fn = shard_map(local, mesh=mesh,
                   in_specs=(x_spec, r_spec, wg_spec, wg_spec, wo_spec),
                   out_specs=(out_spec, P()),
                   check_vma=False)
    return fn(x, params["router"], params["we_gate"], params["we_in"],
              params["we_out"])


def apply(params, x, cfg, pctx: ParallelCtx):
    if pctx.mesh is None or pctx.moe_impl == "dense":
        return apply_dense(params, x, cfg)
    return apply_ep(params, x, cfg, pctx)
