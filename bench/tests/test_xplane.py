"""The trace reduction and the metric readers: by hand on made-up
intervals, and on a small trace recorded on one v5e chip
(``data/g500_s9.xplane.pb``, made by ``record_trace.py``)."""
import os

import xplane

EVENTS = [("a", 0, 10), ("b", 5, 20), ("spgemm_hash_numeric.1", 30, 40),
          ("c", 45, 50)]


def test_busy_union_and_gaps_by_hand():
    # ops cover [0, 20], [30, 40], [45, 50] of the window [0, 60]
    assert xplane.busy_ns(EVENTS, 0, 60) == 35
    assert xplane.idle_gaps(EVENTS, 0, 60) == [(20, 30), (40, 45), (50, 60)]
    # cut to a window: [8, 35] holds [8, 20] and [30, 35]
    assert xplane.busy_ns(EVENTS, 8, 35) == 17
    assert xplane.op_time_ns(EVENTS, 0, 60, "spgemm_hash_numeric") == 10
    assert xplane.op_time_ns(EVENTS, 0, 60)["b"] == 15


def test_span_at_picks_the_innermost():
    spans = [("bench.window", 0, 100), ("bench.execute", 10, 50),
             ("bench.plan", 20, 30)]
    assert xplane.span_at(spans, 25) == "bench.plan"
    assert xplane.span_at(spans, 40) == "bench.execute"
    assert xplane.span_at(spans, 70) == "outside any span"


def _metric(name):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Workload:
    def device_work(self, rec):
        return [(1000, 819_000)]          # 1 ms of traffic at 819 MB/s


def _run():
    import run
    tr = xplane.Trace(
        ops={0: [("spgemm_hash_numeric.1", 0, 1e9),
                 ("fusion.3", 1.5e9, 1.6e9)]},
        spans=[("bench.window", 0, 2e9), ("bench.execute", 0, 2e9)])
    r = run.RunData(_Workload(), [{"flop": 1000}], 0, tr,
                    {"flops_per_s": 1e9, "hbm_bytes_per_s": 819e6}, [0])
    return r


def test_kernel_metrics_by_hand():
    # the kernel runs 1 s of a 2 s window; one product whose least time
    # is max(2000 / 1e9, 819e3 / 819e6) = 1 ms
    r = _run()
    assert abs(_metric("hash_numeric_roofline")(r) - 0.1) < 1e-12
    assert abs(_metric("hash_numeric_share")(r) - 50.0) < 1e-12
    assert abs(_metric("device_idle")(r) - 45.0) < 1e-12
    assert _metric("window_compiles")(r) == 0


def test_kernel_metrics_read_nothing_without_the_kernel():
    r = _run()
    r.trace.ops = {0: [("fusion.3", 1.5e9, 1.6e9)]}
    assert _metric("hash_numeric_roofline")(r) is None
    assert _metric("hash_numeric_share")(r) is None
    r.trace = None
    assert _metric("device_idle")(r) is None


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "g500_s9.xplane.pb")


def test_reduction_of_a_trace_recorded_on_the_chip():
    # two planned executes of a G500 scale-9 square on one v5e chip
    # (record_trace.py): one spgemm_hash_numeric event each
    tr = xplane.load(DATA)
    w0, w1 = tr.window()
    assert [s[0] for s in tr.spans].count("bench.execute") == 2
    evs = xplane.clip(tr.ops[0], w0, w1)
    kernels = [e for e in evs if "spgemm_hash_numeric" in e[0]]
    assert len(kernels) == 2
    busy = xplane.busy_ns(evs, w0, w1)
    assert 0 < xplane.op_time_ns(evs, w0, w1, "spgemm_hash_numeric") <= busy
    assert busy <= w1 - w0
