"""The readers of what the program records about itself
(``bench/program.py``): kernel counters and planner spans."""
import importlib.util
import os
import sys

import pytest

import program

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
KERNEL = ("hash_probe_steps", "hash_table_rows", "hash_window_dmas")
SPANS = ("plan_profile_s", "plan_symbolic_s", "plan_self_s")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Run:
    def __init__(self, wl):
        self.workload = wl


class _Workload:
    def __init__(self, plan, inputs):
        self.plan, self.inputs = plan, inputs


@pytest.fixture(scope="module")
def planned():
    from repro import tracing
    from repro.core import plan_spgemm
    from repro.data.rmat import rmat_csr
    tracing.reset_spans()
    a = rmat_csr(7, 8, "ER", seed=0)
    return a, plan_spgemm(a, a, algorithm="hash", cache=False)


def test_kernel_readers_share_one_counted_product(planned, monkeypatch):
    a, plan = planned
    calls = []
    counted = type(plan).kernel_counts

    def spy(self, x, y):
        calls.append(1)
        return counted(self, x, y)
    monkeypatch.setattr(type(plan), "kernel_counts", spy)
    run = _Run(_Workload(plan, [a]))
    got = {m: _reader(m)(run) for m in KERNEL}
    assert len(calls) == 1
    counts = counted(plan, a, a)
    per = {k: v / plan.total_flop for k, v in counts.items()}
    assert got["hash_probe_steps"] == per["probe_steps"] >= 1.0
    assert got["hash_table_rows"] == per["table_rows"] > 0
    assert got["hash_window_dmas"] == pytest.approx(
        sum(per[k] for k in ("ip_loads", "a_loads", "b_loads",
                             "out_flushes")))


def test_kernel_readers_read_nothing_without_a_hash_plan(planned):
    from repro.core import plan_spgemm
    a, _ = planned
    esc = plan_spgemm(a, a, algorithm="esc", cache=False)
    for wl in (_Workload(esc, [a]), _Workload(object(), [a]),
               object()):            # no plan: products plan in the window
        for m in KERNEL:
            assert _reader(m)(_Run(wl)) is None


def test_span_readers_split_the_plan(planned):
    from repro import tracing
    totals = tracing.span_totals()
    assert totals["repro.plan"].calls >= 1
    got = {m: _reader(m)(None) for m in SPANS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    plan = totals["repro.plan"]
    assert got["plan_self_s"] == pytest.approx(
        (plan.seconds - totals["repro.plan.profile"].seconds
         - totals["repro.plan.symbolic"].seconds) / plan.calls)


def test_span_readers_read_nothing_without_spans(monkeypatch):
    """As in a program that has no ``repro.tracing``."""
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    for m in SPANS:
        assert _reader(m)(None) is None
