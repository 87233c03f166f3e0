"""The hash kernel's run-time counters (``counters=True``).

A counted run must give the same outputs, bit for bit, as an uncounted
one, and each counter must equal a plain-Python walk of the kernel's
algorithm: the same multiply hash, the same row-granular probe order
(linear or chunked), the same ``ALIGN``-tile window refills clamped to
the operand's end, the same output staging windows, and the same table
rows holding an entry, which the numeric flush writes.  Inputs are the
planned schedules of R-MAT graphs of both of the benchmark's families;
of one product made to collide: a B row of 200 even columns, whose keys
all hash to one row of a 256-slot chunked table (the hash keeps a key's
parity), so probes overflow into the next row; and of one made to
outgrow the two-row insert: 300 keys of one slot, in two rows of A,
which the kernel redoes by the probe loop (``redo_rows``: the walk
counts a row whose probes take more than two steps, and reads its
entries again).  A row-sharded plan counts per shard, each shard as the
mesh executor runs it.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import CSR
from repro.core.plan import plan_spgemm
from repro.data.rmat import rmat_csr
from repro.kernels._smem import ALIGN, padded_len
from repro.kernels.spgemm_hash import kernel as K
from repro.kernels.spgemm_hash import ops as hash_ops

#: R-MAT (scale, edge factor, family) of A in A x A
GRAPHS = {"g500_s8": (8, 16, "G500"), "er_s8": (8, 8, "ER")}
PRODUCTS = sorted(GRAPHS) + ["even_cols", "one_slot"]
#: a stride above every table size of ``one_slot``: keys ``7 + STRIDE * j``
#: share their slot (and their chunk) in each
STRIDE = 1 << 12


def _one_slot():
    """Three rows of A over four B rows: 300 keys of one slot (row 0, and
    again after 600 spread keys in row 2), and between them two B rows of
    700 that overlap; 2,300 B entries, so the windows refill, also while
    a row is redone."""
    same = 7 + STRIDE * np.arange(300)
    b_rows = [same, np.arange(700) * 3, np.arange(350, 1050) * 3,
              np.arange(600) * 5 + 1]
    b = CSR.from_numpy_coo(
        np.repeat(np.arange(4), [len(r) for r in b_rows]),
        np.concatenate(b_rows),
        np.linspace(0.5, 1.5, sum(map(len, b_rows)), dtype=np.float32),
        (4, STRIDE * 300))
    a = CSR.from_numpy_coo(np.array([0, 1, 1, 2, 2]),
                           np.array([0, 1, 2, 3, 0]),
                           np.float32([1, 0.5, 2, 1.5, 0.75]), (3, 4))
    return a, b


def _operands(name):
    if name in GRAPHS:
        a = rmat_csr(*GRAPHS[name], seed=0)
        return a, a
    if name == "one_slot":
        return _one_slot()
    cols = np.arange(0, 400, 2)
    a = CSR.from_numpy_coo(np.zeros(1, np.int64), np.zeros(1, np.int64),
                           np.ones(1, np.float32), (1, 1))
    b = CSR.from_numpy_coo(np.zeros(cols.size, np.int64), cols,
                           np.linspace(0.5, 1.5, cols.size, dtype=np.float32),
                           (1, 512))
    return a, b


@pytest.fixture(scope="module")
def planned():
    """Per product: A, B and the ``hash`` and ``hash_vector`` plans."""
    out = {}
    for name in PRODUCTS:
        a, b = _operands(name)
        out[name] = (a, b, {algo: plan_spgemm(a, b, algorithm=algo,
                                              cache=False)
                            for algo in ("hash", "hash_vector")})
    return out


class _Window:
    """``_smem.Window.read``'s refill rule, counting refills."""

    def __init__(self, win, length):
        self.win, self.last, self.loads = win, length - win, 0
        self.reset()

    def reset(self):
        self.base = -2 * self.win

    def read(self, g):
        if not self.base <= g < self.base + self.win:
            self.base = min(g & -ALIGN, self.last)
            self.loads += 1


class _Stream:
    """``_smem.Stream``'s staging windows, counting windows written."""

    def __init__(self, end):
        self.end, self.fill, self.pos, self.flushes = end, 0, 0, 0

    def flush(self):
        if self.pos < self.end:
            self.flushes += 1

    def push(self, n=1):
        for _ in range(n):
            if self.fill == K.WIN_OUT:
                self.flush()
                self.fill, self.pos = 0, self.pos + K.WIN_OUT
            self.fill += 1


def _probe(table, key, tsz, vector):
    """Insert ``key`` into ``table`` (rows of ``LANES`` slots) as
    ``kernel._probe`` does; returns the probe loop's iterations."""
    live = min(tsz, K.LANES)
    rmask = max(tsz >> 7, 1) - 1
    if vector:
        r, first = (key * K.HASH_CONST) & rmask, 0
    else:
        h = (key * K.HASH_CONST) & (tsz - 1)
        r, first = h >> 7, h & (K.LANES - 1)
    steps = 0
    while True:
        row, lo = table[r], (first if steps == 0 else 0)
        steps += 1
        if key in row[lo:live]:
            return steps
        if K.EMPTY in row[lo:live]:
            row[row.index(K.EMPTY, lo, live)] = key
            return steps
        r = (r + 1) & rmask


def walk(a, b, plan, numeric, vector, cap_c=None):
    """The kernel's counters for one member, by a walk in Python."""
    ip_a, idx_a = np.asarray(a.indptr), np.asarray(a.indices)
    ip_b, idx_b = np.asarray(b.indptr), np.asarray(b.indices)
    offsets = np.asarray(plan.offsets)
    bin_tsize = np.asarray(plan.bin_tsize)
    m = a.n_rows
    ipw = _Window(K.WIN_A, padded_len(m + 1, K.WIN_A))
    aw = _Window(K.WIN_A, padded_len(a.cap, K.WIN_A))
    bw = _Window(K.WIN_B, padded_len(b.cap, K.WIN_B))
    cap_c = plan.cap_c if cap_c is None else cap_c
    out = _Stream(K.stream_len(cap_c if numeric else m))
    steps = rows = flush_rows = redo_rows = 0
    for lo, hi, tsize in zip(offsets[:-1], offsets[1:], bin_tsize):
        for w in (ipw, aw, bw):
            w.reset()
        tsz = min(int(tsize), plan.table_size)
        n_rows = max(tsz >> 7, 1)
        for i in range(lo, hi):
            table = [[K.EMPTY] * K.LANES for _ in range(n_rows)]
            rows += n_rows
            ipw.read(i)
            ipw.read(i + 1)
            row_steps = []
            for j in range(ip_a[i], ip_a[i + 1]):
                aw.read(j)
                col = idx_a[j]
                for t in range(ip_b[col], ip_b[col + 1]):
                    bw.read(t)
                    row_steps.append(_probe(table, int(idx_b[t]), tsz,
                                            vector))
            steps += sum(row_steps)
            if any(n > 2 for n in row_steps):
                # the two-row insert missed: the row is read again and
                # redone by the probe loop
                redo_rows += 1
                for j in range(ip_a[i], ip_a[i + 1]):
                    aw.read(j)
                    col = idx_a[j]
                    for t in range(ip_b[col], ip_b[col + 1]):
                        bw.read(t)
            if numeric:
                filled = [sum(k != K.EMPTY for k in row) for row in table]
                flush_rows += sum(n > 0 for n in filled)
                out.push(sum(filled))
            else:
                out.push()
    if not numeric:
        out.push(m - int(offsets[-1]))        # rows past the last bin
    out.flush()
    return {"probe_steps": steps, "table_rows": rows,
            "ip_loads": ipw.loads, "a_loads": aw.loads, "b_loads": bw.loads,
            "out_flushes": out.flushes, "flush_rows": flush_rows,
            "redo_rows": redo_rows}


def _uncounted(numeric, a, b, plan, vector, cap_c):
    """The raw outputs of the same kernel with counters off."""
    bs, bl = hash_ops._a_entries(a, b)
    args = [plan.offsets, plan.bin_tsize, a.indptr.astype(jnp.int32), bs, bl]
    if numeric:
        args.append(a.data.astype(jnp.float32))
    args.append(b.indices)
    if numeric:
        args.append(b.data.astype(jnp.float32))
    call = hash_ops._entry(numeric, plan.n_bins, a.n_rows, a.cap, b.cap,
                           cap_c if numeric else 0, plan.table_size,
                           vector, True)
    outs = call(*args)
    return list(outs) if numeric else [outs]


def _assert_same_bits(outs, plain):
    assert len(outs) == len(plain)
    for got, want in zip(outs, plain):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("numeric", (False, True),
                         ids=("symbolic", "numeric"))
@pytest.mark.parametrize("vector", (False, True),
                         ids=("hash", "hash_vector"))
@pytest.mark.parametrize("product", PRODUCTS)
def test_counters_match_walk_and_leave_outputs_alone(planned, product,
                                                     vector, numeric):
    a, b, plans = planned[product]
    plan = plans["hash_vector" if vector else "hash"]
    outs, counts = hash_ops._counted(
        numeric, a, b, plan.cap_c, (plan.offsets, plan.bin_tsize),
        plan.table_size, vector, True)
    _assert_same_bits(outs, _uncounted(numeric, a, b, plan, vector,
                                       plan.cap_c))
    assert counts == walk(a, b, plan, numeric, vector)
    assert counts["probe_steps"] >= plan.total_flop
    if product == "even_cols" and vector:
        assert counts["probe_steps"] == 128 + 2 * 72
    # only the one-slot rows outgrow the two-row insert
    assert counts["redo_rows"] == (2 if product == "one_slot" else 0)


def test_truncated_stream_counts_only_windows_written(planned):
    """With ``cap_c`` short of nnz(C) the stream stops at its end: windows
    past it are neither written nor counted."""
    a, b, plans = planned["g500_s8"]
    plan, cap_c = plans["hash"], K.WIN_OUT
    assert plan.nnz_c > 4 * cap_c
    outs, counts = hash_ops._counted(
        True, a, b, cap_c, (plan.offsets, plan.bin_tsize), plan.table_size,
        False, True)
    _assert_same_bits(outs, _uncounted(True, a, b, plan, False, cap_c))
    assert counts == walk(a, b, plan, True, False, cap_c=cap_c)
    assert counts["out_flushes"] == 1


def test_plan_kernel_counts_is_the_counted_numeric_run(planned):
    a, b, plans = planned["er_s8"]
    plan = plans["hash"]
    assert plan.kernel_counts(a, b) == walk(a, b, plan, True, False)


def test_kernel_counts_refuses_plans_without_the_kernel(planned):
    a, _, plans = planned["er_s8"]
    esc = plan_spgemm(a, a, algorithm="esc", cache=False)
    with pytest.raises(ValueError, match="does not run the hash kernel"):
        esc.kernel_counts(a, a)
    big = dataclasses.replace(plans["hash"], total_flop=2 ** 31)
    with pytest.raises(ValueError, match="int32"):
        big.kernel_counts(a, a)


def test_distributed_plan_counts_each_shard_as_its_chip_runs_it():
    """``DistributedPlan.kernel_counts`` gives one dict per row shard: the
    walk of that shard's rows under the schedule and uniform capacities
    the mesh executor hands its chip, which is also what the shard's own
    plan counts.  A plan without the hash kernel is refused."""
    from types import SimpleNamespace
    from repro.core.distributed import plan_spgemm_1d, shard_csr_rows
    a = rmat_csr(9, 16, "G500", seed=0)
    a_sh = shard_csr_rows(a, 4, b=a)
    dp = plan_spgemm_1d(a_sh, a, algorithm="hash", cache=False)
    counts = dp.kernel_counts(a_sh, a)
    assert len(counts) == 4
    offsets, bin_tsize, _ = dp.hash_sched
    for s, got in enumerate(counts):
        a_loc = a_sh.local(s)
        chip = SimpleNamespace(offsets=offsets[s], bin_tsize=bin_tsize[s],
                               table_size=dp.table_size, cap_c=dp.cap_c)
        assert got == walk(a_loc, a, chip, True, False)
        assert got == dp.plans[s].kernel_counts(a_loc, a)
        assert got["probe_steps"] == dp.plans[s].total_flop
    esc = plan_spgemm_1d(a_sh, a, algorithm="esc", cache=False)
    with pytest.raises(ValueError, match="does not run the hash kernel"):
        esc.kernel_counts(a_sh, a)
