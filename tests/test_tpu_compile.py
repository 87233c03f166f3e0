"""Compile the main-path kernels for a TPU v5e at real sizes, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology.  That catches what interpret mode
cannot -- scalar loads or stores that touch VMEM, unaligned DMA slices,
SMEM or VMEM over budget -- at no chip time.  Nothing here runs a kernel.

Shapes are those of ``chip_smoke.py``: A x A at edge factor 16, seed 0,
for a Graph500 R-MAT matrix at scale 16 (nnz(A) = 955,780, nnz(C) =
163,577,005, static hash table 131,072 = lowest_p2(n_cols + 1)), for an
Erdos-Renyi one at scale 18 (nnz(A) = 4,194,176, nnz(C) = 67,073,501,
table 1,024 = lowest_p2(max row flop + 1)), and the G500 product split
over the 2x2 mesh of its ``--mesh 4`` phase.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.formats import CSR

M = 1 << 16                 # G500 scale 16: rows = cols
NNZ_A = 955_780
NNZ_C = 163_577_005
TABLE = 1 << 17             # lowest_p2(n_cols + 1)
N_BINS = 8
HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep these compiles out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, n, dtype=jnp.int32):
    shape = n if isinstance(n, tuple) else (n,)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total <= HBM_BYTES, mem
    return total


def _hash_args(sharding, numeric, m, cap_a):
    from repro.kernels._smem import padded_len
    from repro.kernels.spgemm_hash import kernel as K
    lens = (padded_len(m + 1, K.WIN_A), padded_len(cap_a, K.WIN_A),
            padded_len(cap_a, K.WIN_B))
    args = [_sds(sharding, N_BINS + 1), _sds(sharding, N_BINS),
            _sds(sharding, lens[0]), _sds(sharding, lens[1]),
            _sds(sharding, lens[1])]
    if numeric:
        args.append(_sds(sharding, lens[1], jnp.float32))
    args.append(_sds(sharding, lens[2]))
    if numeric:
        args.append(_sds(sharding, lens[2], jnp.float32))
    return lens, args


@pytest.mark.parametrize("vector", (False, True), ids=("hash", "hash_vector"))
@pytest.mark.parametrize("numeric", (False, True),
                         ids=("symbolic", "numeric"))
@pytest.mark.parametrize("m,nnz_a,nnz_c,table", [
    (M, NNZ_A, NNZ_C, TABLE), (1 << 18, 4_194_176, 67_073_501, 1 << 10)],
    ids=("g500_s16", "er_s18"))
def test_hash_kernel_compiles_at_smoke_sizes(one_chip, m, nnz_a, nnz_c,
                                             table, numeric, vector):
    from repro.kernels.spgemm_hash import kernel as K
    lens, args = _hash_args(one_chip, numeric, m, nnz_a)
    out_len = K.stream_len(nnz_c if numeric else m)
    build = K.numeric_call if numeric else K.symbolic_call
    call = build(1, N_BINS, m, lens, out_len, (0, 0, 0, 0, out_len), table,
                 vector, False)
    _check(call.lower(*args).compile())


@pytest.mark.parametrize("vector", (False, True), ids=("hash", "hash_vector"))
@pytest.mark.parametrize("m,nnz_a,nnz_c,table", [
    (M, NNZ_A, NNZ_C, TABLE), (1 << 18, 4_194_176, 67_073_501, 1 << 10)],
    ids=("g500_s16", "er_s18"))
def test_counted_hash_numeric_compiles_at_smoke_sizes(one_chip, m, nnz_a,
                                                      nnz_c, table, vector):
    """``counters=True``: the counter scratch in SMEM and its extra output,
    DMA'd out whole at each member's last bin."""
    from repro.kernels.spgemm_hash import kernel as K
    lens, args = _hash_args(one_chip, True, m, nnz_a)
    out_len = K.stream_len(nnz_c)
    call = K.numeric_call(1, N_BINS, m, lens, out_len,
                          (0, 0, 0, 0, out_len), table, vector, False,
                          counters=True)
    compiled = call.lower(*args).compile()
    _check(compiled)
    assert compiled.out_info[-1].shape == (K.CWIN,)


def test_batched_grid_hash_numeric_compiles(one_chip):
    """The ``custom_vmap`` rule's form: four row shards of the scale-16
    product over a ``(4, n_bins)`` grid, with B shared (stride 0)."""
    from repro.kernels._smem import padded_len
    from repro.kernels.spgemm_hash import kernel as K
    m, cap_a, cap_c = M // 4, NNZ_A // 4, NNZ_C // 4
    lens = (padded_len(m + 1, K.WIN_A), padded_len(cap_a, K.WIN_A),
            padded_len(NNZ_A, K.WIN_B))
    out_len = K.stream_len(cap_c)
    args = [_sds(one_chip, 4 * (N_BINS + 1)), _sds(one_chip, 4 * N_BINS),
            _sds(one_chip, 4 * lens[0]), _sds(one_chip, 4 * lens[1]),
            _sds(one_chip, 4 * lens[1]),
            _sds(one_chip, 4 * lens[1], jnp.float32),
            _sds(one_chip, lens[2]), _sds(one_chip, lens[2], jnp.float32)]
    call = K.numeric_call(4, N_BINS, m, lens, out_len,
                          (1, lens[0], lens[1], 0, out_len), TABLE, False,
                          False)
    _check(call.lower(*args).compile())


def test_planned_hash_execute_compiles_at_g500_scale16(one_chip):
    """The whole numeric program ``plan.execute`` stages for the scale-16
    product: operand padding, the kernel, and the CSR epilogue."""
    from repro.kernels.spgemm_hash import ops as hash_ops

    def csr(n_rows, cap):
        return CSR(_sds(one_chip, n_rows + 1), _sds(one_chip, cap),
                   _sds(one_chip, cap, jnp.float32), _sds(one_chip, ()),
                   (M, M))

    def execute(a, b, offsets, bin_tsize, indptr_c):
        return hash_ops.spgemm_hash(
            a, b, NNZ_C, table_size=TABLE, schedule=(offsets, bin_tsize),
            indptr_c=indptr_c, interpret=False)

    a = csr(M, NNZ_A)
    lowered = jax.jit(execute).lower(
        a, a, _sds(one_chip, N_BINS + 1), _sds(one_chip, N_BINS),
        _sds(one_chip, M + 1))
    total = _check(lowered.compile())
    assert total > 2 * 4 * NNZ_C          # the output really is in HBM


def test_1d_hash_executor_compiles_on_four_chips(topo, monkeypatch):
    """The ``plan_spgemm_1d(..., algorithm="hash")`` executor of
    ``chip_smoke.py --mesh 4``: one shard_map program over the 2x2 mesh,
    each chip running the numeric kernel on its equal-flop row shard.
    Shard shapes are those ``shard_csr_rows(a, 4, b=a)`` gives at G500
    scale 16 (widest shard 32,544 rows, 286,904 entries); ``cap_c`` is the
    widest shard's ``sum(min(flop_i, n))`` bound on its nnz(C)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import distributed as D
    from repro.kernels import _compat

    # the executor resolves ``interpret`` from the default backend, which
    # is the CPU here; compile the kernel the chip would run
    monkeypatch.setattr(_compat, "resolve_interpret", lambda i=None: False)
    rows_cap, cap_per, cap_c = 32_544, 286_904, 93_206_120
    mesh = Mesh(np.array(topo.devices), ("data",))
    sh, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    parts = CSR(_sds(sh, (4, rows_cap + 1)), _sds(sh, (4, cap_per)),
                _sds(sh, (4, cap_per), jnp.float32), _sds(sh, 4),
                (rows_cap, M))
    b = CSR(_sds(rep, M + 1), _sds(rep, NNZ_A),
            _sds(rep, NNZ_A, jnp.float32), _sds(rep, ()), (M, M))
    sched = (_sds(sh, (4, N_BINS + 1)), _sds(sh, (4, N_BINS)),
             _sds(sh, (4, rows_cap + 1)))
    statics = dict(algorithm="hash", semiring="plus_times",
                   complement_mask=False, sorted_output=False, cap_c=cap_c,
                   flop_cap=1, row_cap=1, k_width=1, table_size=TABLE)
    fn = D._build_1d_fn(mesh, "data", False, statics, with_sched=True)
    total = _check(jax.jit(fn).lower(parts, b, *sched).compile())
    assert total > 2 * 4 * cap_c          # each chip's output is in HBM


def test_spmm_kernel_compiles(one_chip):
    """SpMM of the scale-16 matrix against a lane-wide (n, 128) panel."""
    from repro.kernels._smem import padded_len
    from repro.kernels.spmm import kernel as S
    k = 128
    l_ip, l_a = padded_len(M + 1, S.WIN), padded_len(NNZ_A, S.WIN)
    call = S.spmm_call(N_BINS, M, M, k, l_ip, l_a, jnp.float32, False)
    args = [_sds(one_chip, N_BINS + 1), _sds(one_chip, l_ip),
            _sds(one_chip, l_a), _sds(one_chip, l_a, jnp.float32),
            _sds(one_chip, (M, 1, k), jnp.float32)]
    _check(call.lower(*args).compile())
