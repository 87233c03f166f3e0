"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU; the kernels target TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import CSR, BCSR
from repro.data.rmat import rmat_csr
from repro.kernels.spgemm_hash.ops import spgemm_hash, spgemm_hash_symbolic
from repro.kernels.spgemm_hash.ref import numeric_ref, symbolic_ref
from repro.kernels.spgemm_bcsr.ops import spgemm_bcsr
from repro.kernels.spmm.ops import spmm_pallas
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.flash_attention.ops import flash_attention, chunked_attention
from repro.kernels.flash_attention.ref import attention_ref


# ---------------- hash SpGEMM ------------------------------------------------

@pytest.mark.parametrize("vector", [False, True])
@pytest.mark.parametrize("scale,ef,preset", [
    (4, 2, "ER"), (5, 3, "G500"), (5, 4, "ER"), (6, 2, "G500"),
    (7, 8, "G500")])     # 128 columns: a two-row (256-slot) table
def test_hash_spgemm_sweep(vector, scale, ef, preset):
    a = rmat_csr(scale, ef, preset, seed=scale + ef)
    b = rmat_csr(scale, ef, "ER", seed=scale + ef + 1)
    cd = np.asarray(numeric_ref(a, b))
    cap = int((cd != 0).sum()) + 16
    c = spgemm_hash(a, b, cap, vector=vector, n_bins=4)
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3)
    rn = spgemm_hash_symbolic(a, b, vector=vector, n_bins=4)
    assert np.array_equal(np.asarray(rn), np.asarray(symbolic_ref(a, b)))


@pytest.mark.parametrize("table_size", [8, 16, 64])
def test_hash_spgemm_small_table_collisions(table_size):
    """Small power-of-two tables force heavy probing (collision factor c
    in Eq. 2) -- results must stay exact."""
    a = rmat_csr(4, 3, "G500", seed=9)
    b = rmat_csr(4, 3, "G500", seed=10)
    cd = np.asarray(numeric_ref(a, b))
    # table must still be >= max distinct cols per row + 1
    from repro.core.schedule import flops_per_row
    need = int(jnp.max(flops_per_row(a, b))) + 1
    if table_size < need:
        pytest.skip("table smaller than row bound")
    c = spgemm_hash(a, b, int((cd != 0).sum()) + 8, table_size=table_size,
                    n_bins=2)
    assert np.allclose(np.asarray(c.to_dense()), cd, atol=1e-3)


def test_hash_unsorted_flag_and_sort_epilogue():
    a = rmat_csr(5, 3, "G500", seed=1)
    b = rmat_csr(5, 3, "ER", seed=2)
    cd = np.asarray(numeric_ref(a, b))
    c = spgemm_hash(a, b, int((cd != 0).sum()) + 8, n_bins=4)
    assert not c.sorted_cols                    # C8: unsorted by default
    s = c.sort_rows()
    cols, ip = np.asarray(s.indices), np.asarray(s.indptr)
    for i in range(s.n_rows):
        assert np.all(np.diff(cols[ip[i]:ip[i + 1]]) > 0)


def test_hash_empty_matrix():
    z = CSR.from_dense(jnp.zeros((8, 8), jnp.float32), cap=4)
    c = spgemm_hash(z, z, cap_c=4, n_bins=2, table_size=8)
    assert int(c.nnz) == 0


@pytest.mark.parametrize("vector", [False, True])
def test_hash_undersized_cap_c_truncates(vector):
    """``cap_c`` < nnz(C) keeps exactly the first ``cap_c`` entries of the
    full product: the output stream runs past its one-window buffer and
    no window past the buffer may be written."""
    from repro.kernels.spgemm_hash.kernel import WIN_OUT
    a = rmat_csr(7, 8, "G500", seed=3)
    b = rmat_csr(7, 8, "ER", seed=4)
    full = spgemm_hash(a, b, 128 * 128, vector=vector, n_bins=4)
    nnz = int(full.nnz)
    cap = 100
    assert nnz > WIN_OUT >= cap
    c = spgemm_hash(a, b, cap, vector=vector, n_bins=4)
    assert np.array_equal(np.asarray(c.indptr), np.asarray(full.indptr))
    assert np.array_equal(np.asarray(c.indices),
                          np.asarray(full.indices)[:cap])
    assert np.array_equal(np.asarray(c.data), np.asarray(full.data)[:cap])


# ---------------- BCSR SpGEMM ------------------------------------------------

@pytest.mark.parametrize("vector", [False, True])
@pytest.mark.parametrize("blocks", [((4, 4), (4, 4)), ((8, 16), (16, 8)),
                                    ((2, 8), (8, 4))])
def test_bcsr_spgemm_sweep(vector, blocks, rng):
    (bm, bk), (bk2, bn) = blocks
    m, k, n = bm * 6, bk * 5, bn * 7
    def blocky(mm, nn, tb, p):
        occ = rng.random((mm // tb[0], nn // tb[1])) < p
        x = rng.uniform(0.5, 1.5, (mm, nn)).astype(np.float32)
        return np.where(np.kron(occ, np.ones(tb)) > 0, x, 0.0)
    ad = blocky(m, k, (bm, bk), 0.4)
    bd = blocky(k, n, (bk, bn), 0.4)
    a = BCSR.from_dense(jnp.asarray(ad), (bm, bk))
    b = BCSR.from_dense(jnp.asarray(bd), (bk, bn))
    c = spgemm_bcsr(a, b, bcap_c=(m // bm) * (n // bn), vector=vector,
                    n_bins=3)
    assert np.allclose(np.asarray(c.to_dense()), ad @ bd, atol=1e-2)


# ---------------- SpMM -------------------------------------------------------

@pytest.mark.parametrize("k", [1, 8, 32])
@pytest.mark.parametrize("preset", ["ER", "G500"])
def test_spmm_sweep(k, preset, rng):
    a = rmat_csr(5, 3, preset, seed=k)
    x = jnp.asarray(rng.normal(size=(32, k)).astype(np.float32))
    y = spmm_pallas(a, x, n_bins=4)
    assert np.allclose(np.asarray(y), np.asarray(spmm_ref(a, x)), atol=1e-3)


# ---------------- flash attention --------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,hkv,d", [(4, 4, 32), (4, 2, 64), (8, 1, 32)])
def test_flash_attention_sweep(causal, h, hkv, d, rng):
    B, S = 2, 128
    q = jnp.asarray(rng.normal(size=(B, h, S, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, hkv, S, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, hkv, S, d)).astype(np.float32))
    ref = attention_ref(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, bq=64, bkv=64)
    assert float(jnp.abs(out - ref).max()) < 2e-5


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol, rng):
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(B, H, S, D))).astype(dtype)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    out = flash_attention(q, k, v, causal=True, bq=32, bkv=32)
    assert float(jnp.abs(out.astype(jnp.float32) - ref).max()) < tol


@pytest.mark.parametrize("sq,skv", [(64, 64), (1, 128), (32, 128)])
def test_chunked_attention_decode_shapes(sq, skv, rng):
    B, H, HKV, D = 2, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, H, sq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, HKV, skv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, HKV, skv, D)).astype(np.float32))
    ref = attention_ref(q, k, v, causal=True)
    out = chunked_attention(q, k, v, causal=True, bkv=32)
    assert float(jnp.abs(out - ref).max()) < 2e-5


# ---------------- where kernels run -----------------------------------------

@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_resolve_interpret_by_backend(backend, want, monkeypatch):
    """Interpret on the CPU, compile on a TPU, and refuse anything else
    rather than silently interpreting; an explicit choice always wins."""
    import jax
    from repro.kernels import _compat
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _compat.resolve_interpret(True) is True
    assert _compat.resolve_interpret(False) is False
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            _compat.resolve_interpret()
    else:
        assert _compat.resolve_interpret() is want


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_use_compile_cache_honours_env(env, monkeypatch, tmp_path):
    import jax
    from repro.kernels import _compat
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = _compat.use_compile_cache(str(tmp_path))
        assert got == (env or str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == \
            (prev if env else str(tmp_path))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
