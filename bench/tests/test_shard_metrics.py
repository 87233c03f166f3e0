"""The readers of the row-sharded cell: ``shard_kernel_imbalance`` on
hand-made per-chip op lists, ``shard_table_rows_imbalance`` on a fake
workload, and ``plan_1d_s`` on the program's span table."""
import importlib.util
import os
import sys

import pytest

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
KERNEL = "_spgemm_hash_numeric.1_s32"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _TraceRun:
    """Per-chip op lists ``[(name, t0, t1)]`` in a window of 0..1000 ns."""

    def __init__(self, per_dev):
        self.per_dev = per_dev

    def device_ops(self):
        return self.per_dev

    def trace_window_ns(self):
        return 0, 1000


def test_kernel_imbalance_is_the_slowest_chip_over_the_mean():
    read = _reader("shard_kernel_imbalance")
    # kernel time 100 and 300 ns (other ops and time past the window
    # left out): 300 / 200
    uneven = [[(KERNEL, 0, 100), ("fusion", 100, 400)],
              [(KERNEL, 0, 250), (KERNEL, 950, 1200), ("fusion", 0, 5)]]
    assert read(_TraceRun(uneven)) == pytest.approx(1.5)
    even = [[(KERNEL, 0, 200)], [(KERNEL, 500, 700)]]
    assert read(_TraceRun(even)) == pytest.approx(1.0)


def test_kernel_imbalance_reads_nothing_on_one_chip_or_no_kernel():
    read = _reader("shard_kernel_imbalance")
    assert read(_TraceRun([[(KERNEL, 0, 100)]])) is None
    assert read(_TraceRun([[("fusion", 0, 9)], [("fusion", 0, 9)]])) is None
    assert read(_TraceRun(None)) is None


class _Run:
    def __init__(self, wl):
        self.workload = wl


class _Plan:
    def __init__(self, rows):
        self.rows, self.calls = rows, []

    def kernel_counts(self, a_sh, b):
        self.calls.append((a_sh, b))
        return [{"table_rows": r, "probe_steps": 1} for r in self.rows]


class _Workload:
    def __init__(self, plan, shards):
        self.plan, self.shards = plan, shards
        self.mesh_inputs = [("a_sh0", "b0"), ("a_sh1", "b1")]


def test_table_rows_imbalance_is_the_heaviest_shard_over_the_mean():
    read = _reader("shard_table_rows_imbalance")
    plan = _Plan([100, 300, 200, 200])
    assert read(_Run(_Workload(plan, 4))) == pytest.approx(1.5)
    assert plan.calls == [("a_sh0", "b0")]
    assert read(_Run(_Workload(_Plan([7, 7]), 2))) == pytest.approx(1.0)


def test_table_rows_imbalance_reads_nothing_without_shard_counts():
    from repro.core import plan_spgemm
    from repro.data.rmat import rmat_csr
    read = _reader("shard_table_rows_imbalance")
    a = rmat_csr(6, 8, "G500", seed=0)
    one_chip = plan_spgemm(a, a, algorithm="hash", cache=False)
    assert read(_Run(_Workload(one_chip, 1))) is None

    class NoHash(_Plan):
        def kernel_counts(self, a_sh, b):
            raise ValueError("does not run the hash kernel")
    assert read(_Run(_Workload(NoHash([]), 4))) is None
    assert read(_Run(_Workload(object(), 4))) is None   # no per-shard counts


def test_plan_1d_reads_the_span_per_call():
    from repro import tracing
    read = _reader("plan_1d_s")
    tracing.reset_spans()
    assert read(None) is None
    for _ in range(2):
        with tracing.span("repro.plan_1d"):
            pass
    t = tracing.span_totals()["repro.plan_1d"]
    assert read(None) == pytest.approx(t.seconds / 2)
    tracing.reset_spans()


def test_plan_1d_reads_nothing_without_spans(monkeypatch):
    """As in a program that has no ``repro.tracing``."""
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _reader("plan_1d_s")(None) is None
