"""The ``hash_flush_rows`` reader (``bench/metrics/hash_flush_rows.py``):
the numeric kernel's ``flush_rows`` counter per partial product, and
nothing from a kernel that does not count it."""
from test_program_metrics import _Run, _Workload, _reader, planned  # noqa: F401


def test_flush_rows_reads_the_counted_product(planned):
    a, plan = planned
    counts = plan.kernel_counts(a, a)
    got = _reader("hash_flush_rows")(_Run(_Workload(plan, [a])))
    assert got == counts["flush_rows"] / plan.total_flop
    assert 0 < counts["flush_rows"] <= counts["table_rows"]


def test_flush_rows_reads_nothing_from_a_kernel_without_it(planned,
                                                           monkeypatch):
    """As in a program whose kernel counts no ``flush_rows``."""
    a, plan = planned
    counted = type(plan).kernel_counts

    def older(self, x, y):
        counts = counted(self, x, y)
        counts.pop("flush_rows")
        return counts
    monkeypatch.setattr(type(plan), "kernel_counts", older)
    run = _Run(_Workload(plan, [a]))
    assert _reader("hash_flush_rows")(run) is None
    assert _reader("hash_table_rows")(run) > 0


def test_flush_rows_reads_nothing_without_a_hash_plan(planned):
    from repro.core import plan_spgemm
    a, _ = planned
    esc = plan_spgemm(a, a, algorithm="esc", cache=False)
    for wl in (_Workload(esc, [a]), _Workload(object(), [a]), object()):
        assert _reader("hash_flush_rows")(_Run(wl)) is None
