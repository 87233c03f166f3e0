"""The program's host spans (``repro.tracing``) around ``plan_spgemm`` and
``plan_spgemm_1d``."""
import glob
import os

import jax
import pytest

from repro import tracing
from repro.core.plan import clear_plan_cache, plan_spgemm
from repro.data.rmat import rmat_csr

SPANS = ("repro.plan", "repro.plan.profile", "repro.plan.symbolic")


@pytest.fixture(scope="module")
def a():
    return rmat_csr(6, 8, "G500", seed=0)


def _calls():
    totals = tracing.span_totals()
    return {n: totals[n].calls if n in totals else 0 for n in SPANS}


def test_span_adds_seconds_and_calls():
    tracing.reset_spans()
    with tracing.span("test.outer", k=1):
        with tracing.span("test.inner"):
            pass
    with tracing.span("test.inner"):
        pass
    totals = tracing.span_totals()
    assert totals["test.outer"].calls == 1
    assert totals["test.inner"].calls == 2
    assert 0 < totals["test.inner"].seconds
    tracing.reset_spans()
    assert tracing.span_totals() == {}


def test_uncached_plan_adds_one_call_to_each_span(a):
    before = _calls()
    plan_spgemm(a, a, algorithm="hash", cache=False)
    after = _calls()
    assert {n: after[n] - before[n] for n in SPANS} == dict.fromkeys(SPANS, 1)
    totals = tracing.span_totals()
    assert (totals["repro.plan"].seconds
            >= totals["repro.plan.profile"].seconds
            + totals["repro.plan.symbolic"].seconds)


def test_cache_hit_adds_one_call_to_the_plan_span_only(a):
    clear_plan_cache()
    plan_spgemm(a, a, algorithm="hash")
    before = _calls()
    plan_spgemm(a, a, algorithm="hash")
    after = _calls()
    assert {n: after[n] - before[n] for n in SPANS} == {
        "repro.plan": 1, "repro.plan.profile": 0, "repro.plan.symbolic": 0}


def test_spans_land_on_the_profilers_host_plane(a, tmp_path):
    """The spans are host events of a profiler trace, on its clock: the
    stages nest in ``repro.plan``, and the ESC program's execution, on
    other threads of the host plane, falls inside ``repro.plan.symbolic``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        plan_spgemm(a, a, algorithm="hash", cache=False)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans, others = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                t = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name in SPANS:
                    spans[ev.name] = t
                else:
                    others.append((line.name, ev.name, t))
    assert set(spans) == set(SPANS)
    p0, p1 = spans["repro.plan"]
    for stage in SPANS[1:]:
        assert p0 <= spans[stage][0] <= spans[stage][1] <= p1
    s0, s1 = spans["repro.plan.symbolic"]
    assert any(name == "PjitFunction(symbolic)" and s0 <= t0 <= t1 <= s1
               for _, name, (t0, t1) in others)
    assert any(ln.startswith("tf_XLA") and s0 <= t0 <= t1 <= s1
               for ln, _, (t0, t1) in others)


def test_plan_1d_span_nests_its_stages_and_the_shard_plans(a, tmp_path):
    """``repro.plan_1d`` holds ``repro.plan_1d.shards``, which holds the
    four shards' ``repro.plan`` spans, and then ``repro.plan_1d.freeze``;
    one call each, on the profiler's host plane as in the table."""
    from jax.profiler import ProfileData
    from repro.core.distributed import plan_spgemm_1d, shard_csr_rows
    a_sh = shard_csr_rows(a, 4, b=a)
    names = ("repro.plan_1d", "repro.plan_1d.shards", "repro.plan_1d.freeze",
             "repro.plan")
    tracing.reset_spans()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        plan_spgemm_1d(a_sh, a, algorithm="hash", cache=False)
    finally:
        jax.profiler.stop_trace()
    totals = tracing.span_totals()
    assert {n: totals[n].calls for n in names} == {
        "repro.plan_1d": 1, "repro.plan_1d.shards": 1,
        "repro.plan_1d.freeze": 1, "repro.plan": 4}
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert {n: len(v) for n, v in spans.items()} == {
        "repro.plan_1d": 1, "repro.plan_1d.shards": 1,
        "repro.plan_1d.freeze": 1, "repro.plan": 4}

    def inside(inner, outer):
        return outer[0] <= inner[0] <= inner[1] <= outer[1]
    (whole,), (shards,), (freeze,) = (spans[n] for n in names[:3])
    assert inside(shards, whole) and inside(freeze, whole)
    assert shards[1] <= freeze[0]
    assert all(inside(p, shards) for p in spans["repro.plan"])
