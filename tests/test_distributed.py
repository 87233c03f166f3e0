"""Multi-device behaviour via subprocess (XLA host-device-count must be set
before jax initializes, so these run as child processes)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_dev: int = 4, timeout=600):
    env = dict(os.environ)
    # replace (not append to) any inherited device-count flag: the CI
    # multi-device job exports one globally and XLA rejects duplicates
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_dev}"])
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# Host-side sharding (no mesh needed -- runs in-process)
# ---------------------------------------------------------------------------

def test_shard_csr_rows_sparse_native_equal_flop_roundtrip():
    """shard_csr_rows must never densify, must cut equal-flop boundaries,
    and must round-trip exactly through unshard_rows."""
    import numpy as np
    from repro.core import CSR
    from repro.core.distributed import shard_csr_rows, unshard_rows
    from repro.core.schedule import flops_per_row
    from repro.data.rmat import rmat_csr

    a = rmat_csr(5, 4, "G500", seed=0)
    b = rmat_csr(5, 4, "ER", seed=1)
    calls = {"n": 0}
    orig = CSR.to_dense

    def spy(self):
        calls["n"] += 1
        return orig(self)

    CSR.to_dense = spy
    try:
        sh = shard_csr_rows(a, 4, b=b)
    finally:
        CSR.to_dense = orig
    assert calls["n"] == 0, "shard_csr_rows must stay sparse-native"

    rt = unshard_rows(sh)
    assert rt.shape == a.shape and rt.sorted_cols == a.sorted_cols
    assert int(rt.nnz) == int(a.nnz)
    assert np.array_equal(np.asarray(rt.to_dense()), np.asarray(a.to_dense()))

    # equal-flop invariant: every shard <= ceil(total/S) + max row flop
    flop = np.asarray(flops_per_row(a, b)).astype(np.int64)
    total, S = int(flop.sum()), 4
    starts = sh.row_starts
    assert starts[0] == 0 and starts[-1] == a.n_rows
    for s in range(S):
        part = int(flop[starts[s]:starts[s + 1]].sum())
        assert part <= -(-total // S) + int(flop.max()), (s, part)


def test_summa_panel_bounds_pins_panel_count():
    """k_panels is honored, never silently ignored (dead-arg regression)."""
    import pytest
    from repro.core.distributed import summa_panel_bounds

    bounds = summa_panel_bounds(64, 8, 16)
    assert len(bounds) == 16
    assert bounds[0] == (0, 4) and bounds[-1] == (60, 64)
    assert summa_panel_bounds(64, 8) == summa_panel_bounds(64, 8, 8)
    with pytest.raises(ValueError, match="multiple of the mesh axis"):
        summa_panel_bounds(64, 8, 12)
    with pytest.raises(ValueError, match="exceeds the contraction dim"):
        summa_panel_bounds(64, 8, 128)


def test_equal_weight_partition_degenerates_rebalance():
    """All-zero weights must split rows evenly, not pile every cut at n
    (the old zero-total prefix handed part 0 all rows and left every
    other part empty); with more parts than rows the empties spread."""
    import numpy as np
    from repro.core.schedule import equal_weight_partition

    starts = np.asarray(equal_weight_partition(np.zeros(8, np.int64), 4))
    assert starts[0] == 0 and starts[-1] == 8
    assert np.diff(starts).max() == 2, starts  # fails pre-fix: [8, 0, 0, 0]

    starts = np.asarray(equal_weight_partition(np.zeros(3, np.int64), 8))
    assert starts[0] == 0 and starts[-1] == 3
    assert np.all(np.diff(starts) >= 0)
    assert np.diff(starts).max() == 1, starts  # empties spread, not piled


def test_summa_panel_bounds_ragged_tail():
    """K need not divide evenly: a prime K schedules with a short final
    panel (the old code raised 'must divide' here)."""
    from repro.core.distributed import summa_panel_bounds

    bounds = summa_panel_bounds(13, 2)
    assert bounds == ((0, 7), (7, 13))
    # invariants every executor relies on: contiguous cover of [0, K),
    # first panel widest (buffers are sized off it), monotone bounds
    for k_dim, s, kp in ((13, 2, 2), (97, 4, 8), (10, 2, 8), (31, 1, 16)):
        b = summa_panel_bounds(k_dim, s, kp)
        assert len(b) == kp
        assert b[0][0] == 0 and b[-1][1] == k_dim
        widths = [hi - lo for lo, hi in b]
        assert all(w >= 0 for w in widths) and max(widths) == widths[0]
        for (_, hi), (lo2, _) in zip(b, b[1:]):
            assert hi == lo2


# ---------------------------------------------------------------------------
# Mesh equivalence (8-way host-device mesh, subprocess)
# ---------------------------------------------------------------------------

def test_distributed_1d_matches_single_node_planned():
    """1D products bit-match the single-node planned spgemm() per
    algorithm, repeat products hit the plan cache, and SpMM/BFS are
    rectangular-safe."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import CSR, plan_spgemm, plan_cache_stats
from repro.core.distributed import (shard_csr_rows, plan_spgemm_1d,
                                    spgemm_1d, spmm_1d, unshard_rows,
                                    multi_source_bfs)
from repro.data.rmat import rmat_csr
assert len(jax.devices()) == 8
mesh = Mesh(np.array(jax.devices()), ("data",))
a = rmat_csr(6, 4, "G500", seed=0)
b = rmat_csr(6, 4, "ER", seed=1)
a_sh = shard_csr_rows(a, 8, b=b)       # equal-flop boundaries
assert a_sh.row_starts[0] == 0 and a_sh.row_starts[-1] == 64

# bit-match per algorithm: each planned local product now runs the same
# kernel the single-node planned path runs -- including the Pallas hash
# kernel, which traces inside the shard_map body
for algo in ("esc", "heap", "hash"):
    ref = plan_spgemm(a, b, algorithm=algo).execute(a, b)
    dp = plan_spgemm_1d(a_sh, b, algorithm=algo)
    c = unshard_rows(dp.execute(mesh, a_sh, b))
    assert np.array_equal(np.asarray(c.to_dense()),
                          np.asarray(ref.to_dense())), algo
# the jnp twin stays the reference oracle: same accumulation order, but
# it rounds every product where the kernel fuses multiply-add (~1 ulp)
ref_twin = plan_spgemm(a, b, algorithm="hash_jnp").execute(a, b)
c_hash = unshard_rows(plan_spgemm_1d(a_sh, b, algorithm="hash")
                      .execute(mesh, a_sh, b))
assert np.allclose(np.asarray(c_hash.to_dense()),
                   np.asarray(ref_twin.to_dense()), atol=1e-5)

# masked boolean product bit-matches too
mask = rmat_csr(6, 3, "ER", seed=7)
refm = plan_spgemm(a, b, semiring="boolean", mask=mask,
                   algorithm="hash_jnp").execute(a, b)
dpm = plan_spgemm_1d(a_sh, b, semiring="boolean", mask=mask,
                     algorithm="hash")
cm = unshard_rows(dpm.execute(mesh, a_sh, b))
assert np.array_equal(np.asarray(cm.to_dense()),
                      np.asarray(refm.to_dense()))

# repeat products replan nothing (distributed plan-cache hit)
before = plan_cache_stats()
dp2 = plan_spgemm_1d(a_sh, b, algorithm="esc")
dp3 = plan_spgemm_1d(a_sh, b, algorithm="esc")
after = plan_cache_stats()
assert dp2 is dp3
assert after["misses"] == before["misses"], "repeat replanned something"
assert after["hits"] >= before["hits"] + 2

# planless entry dispatches through spgemm() with explicit algorithm
c_pl = unshard_rows(spgemm_1d(mesh, a_sh, b, cap_c=dp2.cap_c,
                              flop_cap=dp2.flop_cap, algorithm="esc"))
ref_esc = plan_spgemm(a, b, algorithm="esc").execute(a, b)
assert np.array_equal(np.asarray(c_pl.to_dense()),
                      np.asarray(ref_esc.to_dense()))

# rectangular SpMM regression: A (48, 32) with unequal nnz shards --
# the old code reshaped assuming square A and would mis-assemble here
rng = np.random.default_rng(0)
ar = CSR.from_numpy_coo(rng.integers(0, 48, 200),
                        rng.integers(0, 32, 200),
                        rng.normal(size=200).astype(np.float32), (48, 32))
ar_sh = shard_csr_rows(ar, 8)
x = rng.normal(size=(32, 5)).astype(np.float32)
y = spmm_1d(mesh, ar_sh, jnp.asarray(x))
assert y.shape == (48, 5)
assert np.allclose(np.asarray(y), np.asarray(ar.to_dense()) @ x, atol=1e-4)

# BFS on the (square) graph agrees with a host-side reference
sq = rmat_csr(6, 4, "G500", seed=2)
sq_sh = shard_csr_rows(sq, 8)
sources = [0, 3, 7]
dist = np.asarray(multi_source_bfs(mesh, sq_sh, jnp.array(sources), 64, 4))
adj = np.asarray(sq.to_dense()) != 0
ref_d = np.full((64, len(sources)), -1, np.int32)
for j, s in enumerate(sources):
    front = np.zeros(64, bool); front[s] = True; ref_d[s, j] = 0
    for hop in range(1, 5):
        front = (adj @ front) & (ref_d[:, j] < 0)   # nxt = A @ frontier
        ref_d[front, j] = hop
assert np.array_equal(dist, ref_d)
print("OK")
""", n_dev=8)


def test_plan_1d_compiles_esc_once_and_keeps_exact_shard_plans():
    """``plan_spgemm_1d`` plans every shard with one static ESC expansion
    bound, the largest shard's flop, so ESC ``symbolic`` compiles once for
    four shards where each shard's own exact bound compiled it four times.
    Every shard's schedule and capacities, and the mesh product, are
    bitwise those of plans made with each shard's own exact bound, and
    the product equals scipy's."""
    _run("""
import collections
import numpy as np, jax, scipy.sparse as sp
from jax.sharding import Mesh
from repro.core import plan_spgemm
from repro.core.distributed import (shard_csr_rows, plan_spgemm_1d,
                                    unshard_rows)
from repro.data.rmat import rmat_csr
compiles = collections.Counter()
def listen(event, secs, fun_name=None, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles[fun_name] += 1
jax.monitoring.register_event_duration_secs_listener(listen)
assert len(jax.devices()) == 4
mesh = Mesh(np.array(jax.devices()), ("data",))
a = rmat_csr(9, 16, "G500", seed=0)
a_sh = shard_csr_rows(a, 4, b=a)
dp = plan_spgemm_1d(a_sh, a, algorithm="hash", cache=False)
assert compiles["jit(symbolic)"] == 1, compiles
flops = [p.total_flop for p in dp.plans]
assert len(set(flops)) == 4, flops
assert {p.flop_cap for p in dp.plans} == {dp.flop_cap} == {max(flops)}

exact = [plan_spgemm(a_sh.local(s), a, algorithm="hash", use_case="dist",
                     cache=False) for s in range(4)]
# a program per exact bound: the largest shard's is the common one
assert compiles["jit(symbolic)"] == 4, compiles
def same(x, y):
    return np.asarray(x).tobytes() == np.asarray(y).tobytes()
for p, q in zip(dp.plans, exact):
    assert q.flop_cap == q.total_flop == p.total_flop
    for f in ("offsets", "bin_tsize", "indptr_c", "row_nnz_c"):
        assert same(getattr(p, f), getattr(q, f)), f
    assert (p.cap_c, p.nnz_c, p.table_size) == (q.cap_c, q.nnz_c,
                                                 q.table_size)
for x, f in zip(dp.hash_sched, ("offsets", "bin_tsize", "indptr_c")):
    assert same(x, np.stack([np.asarray(getattr(q, f)) for q in exact])), f

c_sh = dp.execute(mesh, a_sh, a)
for s, q in enumerate(exact):
    want, got = q.execute(a_sh.local(s), a), c_sh.local(s)
    n = int(want.nnz)
    assert int(got.nnz) == n
    assert same(got.indptr, want.indptr)
    assert same(got.indices[:n], want.indices[:n])
    assert same(got.data[:n], want.data[:n])
c = unshard_rows(c_sh)
ref = sp.csr_matrix(np.asarray(a.to_dense(), np.float64))
ref = (ref @ ref).toarray()
got = np.asarray(c.to_dense())
assert np.array_equal(got != 0, ref != 0)
assert np.allclose(got, ref, rtol=1e-5, atol=0)
print("OK")
""", n_dev=4)


def test_distributed_summa_matches_single_node_and_honors_k_panels():
    _run("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import CSR, plan_spgemm, plan_cache_stats
from repro.core.distributed import spgemm_summa, plan_spgemm_summa, \
    unshard_rows
def int_csr(m, n, nnz, seed):
    r = np.random.default_rng(seed)
    return CSR.from_numpy_coo(r.integers(0, m, nnz), r.integers(0, n, nnz),
                              r.integers(1, 5, nnz).astype(np.float32),
                              (m, n))
# integer values: fp32 panel-sum reassociation is exact, so the SUMMA
# merge must bit-match the single-node product
a = int_csr(64, 64, 300, 1)
b = int_csr(64, 48, 300, 2)
mesh = Mesh(np.array(jax.devices()), ("data",))
refd = np.asarray(plan_spgemm(a, b, algorithm="esc").execute(a, b)
                  .to_dense())
for kp in (8, 16):
    c = unshard_rows(spgemm_summa(mesh, a, b, k_panels=kp,
                                  algorithm="esc"))
    assert np.array_equal(np.asarray(c.to_dense()), refd), kp
# boolean semiring via the post-scatter threshold
refb = np.asarray(plan_spgemm(a, b, algorithm="esc", semiring="boolean")
                  .execute(a, b).to_dense())
cb = unshard_rows(spgemm_summa(mesh, a, b, semiring="boolean",
                               algorithm="esc"))
assert np.array_equal(np.asarray(cb.to_dense()), refb)
# min_plus has no dense add-identity: refuse instead of corrupting
try:
    spgemm_summa(mesh, a, b, semiring="min_plus")
except NotImplementedError:
    pass
else:
    raise AssertionError("min_plus SUMMA must raise")
# invalid panel counts fail loudly (dead-arg regression)
for bad in (3, 7, 128):
    try:
        spgemm_summa(mesh, a, b, k_panels=bad)
    except ValueError:
        pass
    else:
        raise AssertionError(f"k_panels={bad} must raise")
# repeat product hits the summa plan cache
before = plan_cache_stats()
c2 = unshard_rows(spgemm_summa(mesh, a, b, k_panels=8, algorithm="esc"))
after = plan_cache_stats()
assert after["misses"] == before["misses"]
assert np.array_equal(np.asarray(c2.to_dense()), refd)
# values stay out of the frozen panel structure: a reweighted operand
# reuses the cached plan and execute re-gathers the new values
import dataclasses as dc
a3 = dc.replace(a, data=a.data * 3.0)
c3 = unshard_rows(spgemm_summa(mesh, a3, b, k_panels=8, algorithm="esc"))
assert np.array_equal(np.asarray(c3.to_dense()), 3.0 * refd)
assert plan_cache_stats()["misses"] == after["misses"]
print("OK")
""", n_dev=8)


def test_unshard_rows_roundtrip_is_bitwise_with_cap():
    """shard -> unshard with an explicit ``cap=`` must reproduce the
    operand bitwise -- same arrays, same structure key -- so plan reuse
    after a round trip matches the single-node path (the old code shrank
    capacity to nnz, making every round trip a new structure)."""
    import numpy as np
    from repro.core.distributed import shard_csr_rows, unshard_rows
    from repro.core.plan import structure_key
    from _fuzz import csr_of, rand_dense

    a = csr_of(rand_dense(16, 12, 0.3, seed=3), cap=96)   # deliberate slack
    assert int(a.nnz) < a.cap == 96
    rt = unshard_rows(shard_csr_rows(a, 4), cap=a.cap)
    assert rt.cap == a.cap                      # fails pre-fix: cap == nnz
    for f in ("indptr", "indices", "data"):
        assert np.array_equal(np.asarray(getattr(rt, f)),
                              np.asarray(getattr(a, f))), f
    assert int(rt.nnz) == int(a.nnz) and rt.shape == a.shape
    assert structure_key(rt) == structure_key(a)
    # default preserves the sharded slack instead of shrinking to nnz
    sh = shard_csr_rows(a, 4)
    assert unshard_rows(sh).cap == 4 * sh.cap_per


def test_distributed_summa_ragged_prime_k():
    """SUMMA on a prime contraction dim (regression: the old panel
    schedule raised 'must divide' unless k_panels | K)."""
    _run("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import CSR, plan_spgemm
from repro.core.distributed import spgemm_summa, unshard_rows
def int_csr(m, n, nnz, seed):
    r = np.random.default_rng(seed)
    return CSR.from_numpy_coo(r.integers(0, m, nnz), r.integers(0, n, nnz),
                              r.integers(1, 5, nnz).astype(np.float32),
                              (m, n))
a = int_csr(8, 13, 40, 1)     # K = 13 is prime
b = int_csr(13, 6, 30, 2)
mesh = Mesh(np.array(jax.devices()), ("data",))
refd = np.asarray(plan_spgemm(a, b, algorithm="esc").execute(a, b)
                  .to_dense())
for kp in (2, 4):             # ragged final panel: (12, 13) when kp=4
    c = unshard_rows(spgemm_summa(mesh, a, b, k_panels=kp,
                                  algorithm="esc"))
    assert np.array_equal(np.asarray(c.to_dense()), refd), kp
print("OK")
""", n_dev=2)


def test_distributed_1d_pb_sched_numeric_only():
    """The 1D plan's frozen PB geometry: shard_map executes run the
    scatter/merge Pallas pair with zero re-inspection, bit-match the
    mesh-free host twin, and general semirings fall back to esc."""
    _run("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import CSR, plan_spgemm
from repro.core.distributed import shard_csr_rows, plan_spgemm_1d, \\
    unshard_rows
from repro.kernels.spgemm_pb import ops as pb_ops
def int_csr(m, n, nnz, seed):
    r = np.random.default_rng(seed)
    return CSR.from_numpy_coo(r.integers(0, m, nnz), r.integers(0, n, nnz),
                              r.integers(1, 5, nnz).astype(np.float32),
                              (m, n))
a = int_csr(32, 24, 120, 1)
b = int_csr(24, 28, 100, 2)
a_sh = shard_csr_rows(a, 4)
plan = plan_spgemm_1d(a_sh, b, algorithm="pb", sorted_output=True)
assert plan.pb_sched is not None and len(plan.pb_sched) == 6
mesh = Mesh(np.array(jax.devices()), ("data",))
pb_ops.reset_kernel_calls()
c = unshard_rows(plan.execute(mesh, a_sh, b))
cnt = pb_ops.kernel_call_counts()
assert cnt["inspect"] == 0 and cnt["scatter"] >= 1 and cnt["merge"] >= 1
refd = np.asarray(plan_spgemm(a, b, algorithm="esc", sorted_output=True)
                  .execute(a, b).to_dense())
assert np.array_equal(np.asarray(c.to_dense()), refd)
# mesh-free twin is bitwise the mesh result
host = plan.execute_shards_host(a_sh, b)
mesh_out = plan.execute(mesh, a_sh, b)
for f in ("indptr", "indices", "data", "nnz"):
    assert np.array_equal(np.asarray(getattr(host.parts, f)),
                          np.asarray(getattr(mesh_out.parts, f))), f
# a general semiring keeps pb_sched=None (esc substitution in-trace)
pg = plan_spgemm_1d(a_sh, b, algorithm="pb", semiring="min_plus")
assert pg.pb_sched is None
cg = unshard_rows(pg.execute(mesh, a_sh, b))
refm = np.asarray(plan_spgemm(a, b, algorithm="esc", semiring="min_plus")
                  .execute(a, b).to_dense())
assert np.array_equal(np.asarray(cg.to_dense()), refm)
print("OK")
""", n_dev=4)


def test_distributed_pb_summa_matches_classic_merge():
    """PB-SUMMA's all_to_all bucket exchange must reproduce the classic
    dense reduce-scatter merge bitwise (integer values), reuse the frozen
    structure on reweighted operands, and never re-inspect on repeat
    executes."""
    _run("""
import numpy as np, jax, dataclasses as dc
from jax.sharding import Mesh
from repro.core import CSR, plan_cache_stats
from repro.core.distributed import spgemm_summa, spgemm_pb_summa, \\
    plan_spgemm_pb_summa, unshard_rows
from repro.kernels.spgemm_pb import ops as pb_ops
def int_csr(m, n, nnz, seed):
    r = np.random.default_rng(seed)
    return CSR.from_numpy_coo(r.integers(0, m, nnz), r.integers(0, n, nnz),
                              r.integers(1, 5, nnz).astype(np.float32),
                              (m, n))
a = int_csr(32, 24, 150, 1)
b = int_csr(24, 28, 120, 2)
mesh = Mesh(np.array(jax.devices()), ("data",))
ref = unshard_rows(spgemm_summa(mesh, a, b, algorithm="esc"))
plan = plan_spgemm_pb_summa(a, b, 4)
pb_ops.reset_kernel_calls()
c = plan.execute(mesh, a, b)
assert pb_ops.kernel_call_counts()["inspect"] == 0
assert np.array_equal(np.asarray(unshard_rows(c).to_dense()),
                      np.asarray(ref.to_dense()))
# output is sorted CSR with the exact planned structure
assert bool(np.all(np.asarray(c.parts.nnz)
                   == np.asarray(plan.out_nnz)))
# repeat product hits the plan cache; reweighted values re-gather only
before = plan_cache_stats()["misses"]
c2 = spgemm_pb_summa(mesh, dc.replace(a, data=a.data * 2.0), b)
assert plan_cache_stats()["misses"] == before
assert np.array_equal(np.asarray(unshard_rows(c2).to_dense()),
                      2.0 * np.asarray(ref.to_dense()))
# multiple K-panels per chip stream through the same exchange
c3 = spgemm_pb_summa(mesh, a, b, k_panels=8)
assert np.array_equal(np.asarray(unshard_rows(c3).to_dense()),
                      np.asarray(ref.to_dense()))
print("OK")
""", n_dev=4)


def test_distributed_1d_empty_shards_execute():
    """The shard_map executor must handle empty shards: all-zero
    partition weights (empty operand) and more shards than rows."""
    _run("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import CSR, plan_spgemm
from repro.core.distributed import (shard_csr_rows, plan_spgemm_1d,
                                    unshard_rows)
mesh = Mesh(np.array(jax.devices()), ("data",))

# all-zero weights: an empty operand has no flop anywhere -- the old
# partition handed shard 0 every row and trailing shards zero rows;
# either way the executor must survive and produce the empty product
empty = CSR.from_numpy_coo(np.zeros(0, np.int64), np.zeros(0, np.int64),
                           np.zeros(0, np.float32), (16, 8))
b = CSR.from_numpy_coo(np.array([0, 3, 5]), np.array([1, 2, 0]),
                       np.ones(3, np.float32), (8, 6))
e_sh = shard_csr_rows(empty, 8, b=b)
starts = np.asarray(e_sh.row_starts)
assert np.diff(starts).max() <= 2, starts   # rebalanced, not piled
ce = unshard_rows(plan_spgemm_1d(e_sh, b, algorithm="esc")
                  .execute(mesh, e_sh, b))
assert int(ce.nnz) == 0 and ce.shape == (16, 6)

# more shards than rows: some shards are necessarily empty
small = CSR.from_numpy_coo(np.array([0, 1, 2, 3]), np.array([0, 1, 2, 3]),
                           np.arange(1, 5, dtype=np.float32), (4, 8))
s_sh = shard_csr_rows(small, 8, b=b)
ref = np.asarray(plan_spgemm(small, b, algorithm="esc")
                 .execute(small, b).to_dense())
cs = unshard_rows(plan_spgemm_1d(s_sh, b, algorithm="esc")
                  .execute(mesh, s_sh, b))
assert np.array_equal(np.asarray(cs.to_dense()), ref)
print("OK")
""", n_dev=8)


def test_moe_ep_matches_dense():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import ARCHS, reduced
from repro.models import moe
from repro.parallel.sharding import ParallelCtx
cfg = reduced(ARCHS["qwen3-moe-30b-a3b"], d_model=64)
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), fsdp_axes=("data",))
params = moe.init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
y_ref, _ = moe.apply_dense(params, x, cfg)
y_ep, _ = jax.jit(lambda p, x: moe.apply_ep(p, x, cfg, pctx))(params, x)
assert float(jnp.abs(y_ref - y_ep).max()) < 1e-4
print("OK")
""", n_dev=8)


def test_sharded_train_step_matches_single_device():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import ARCHS, reduced
from repro.parallel.sharding import ParallelCtx, single_device_ctx
from repro.train import optimizer as opt, step as step_lib
from repro.data.lm_synthetic import DataPipeline
cfg = reduced(ARCHS["qwen3-0.6b"], d_model=64, vocab=64)
ocfg = opt.AdamWConfig(lr=1e-2)
data = DataPipeline(cfg, 4, 32)
batch = data.batch(0)
key = jax.random.PRNGKey(0)
# single device
p0 = single_device_ctx(remat=False, attn_impl="full")
s0 = step_lib.init_state(key, cfg, ocfg)
s0b, m0 = jax.jit(step_lib.make_train_step(cfg, p0, ocfg))(s0, batch)
# sharded 2x2 mesh
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
p1 = ParallelCtx(mesh=mesh, batch_axes=("data",), fsdp_axes=("data",),
                 remat=False, attn_impl="full", moe_impl="dense")
s1 = step_lib.init_state(key, cfg, ocfg)
from repro.parallel.sharding import mesh_context
with mesh_context(mesh):
    s1b, m1 = jax.jit(step_lib.make_train_step(cfg, p1, ocfg))(s1, batch)
assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4, (m0["loss"], m1["loss"])
d = max(float(jnp.abs(a - b).max()) for a, b in
        zip(jax.tree.leaves(s0b.params), jax.tree.leaves(s1b.params)))
assert d < 1e-3, d
print("OK")
""")


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto a 2-device mesh."""
    _run("""
import os, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint import Checkpointer
state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
mesh4 = Mesh(np.array(jax.devices()).reshape(4,), ("data",))
sh4 = {"w": NamedSharding(mesh4, P("data", None))}
state4 = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh4)
d = tempfile.mkdtemp()
ck = Checkpointer(d)
ck.save(1, state4, blocking=True)
mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2,), ("data",))
sh2 = {"w": NamedSharding(mesh2, P(None, "data"))}
restored = ck.restore(1, state, sh2)
assert np.array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
assert restored["w"].sharding == sh2["w"]
print("OK")
""")
