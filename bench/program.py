"""What the program records about itself, for the per-layer metrics:
the hash kernel's run-time counters and the planner's host spans.

A program that records neither (one older than ``repro.tracing`` and
``SpGEMMPlan.kernel_counts``) reads None here, as does a run whose plan
does not run the hash kernel on one chip: a mesh plan, or products that
plan in the window.
"""
from __future__ import annotations

PLAN = "repro.plan"
PLAN_STAGES = ("repro.plan.profile", "repro.plan.symbolic")


def kernel_counts(run):
    """``{counter: count per partial product}`` of one counted product of
    the run's plan on its first input, made once per run after the window
    and the check.  The counts depend on the structure only, so in a
    fixed-structure cell they hold for every product of the window."""
    if not hasattr(run, "_kernel_counts"):
        run._kernel_counts = _count(run.workload)
    return run._kernel_counts


def _count(wl):
    plan = getattr(wl, "plan", None)
    if not hasattr(plan, "kernel_counts"):
        return None
    a = wl.inputs[0]
    try:
        counts = plan.kernel_counts(a, a)
    except ValueError:                   # the plan runs no hash kernel
        return None
    return {k: v / plan.total_flop for k, v in counts.items()}


def span_totals():
    """The program's ``{name: (seconds, calls)}`` span table, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing.span_totals()


def span_per_call(name: str):
    """Mean seconds of span ``name`` per call, or None."""
    t = (span_totals() or {}).get(name)
    return t.seconds / t.calls if t else None


def plan_self_per_call():
    """Seconds of ``repro.plan`` outside its two stages, per call."""
    totals = span_totals() or {}
    plan = totals.get(PLAN)
    if not plan:
        return None
    stages = sum(totals[n].seconds for n in PLAN_STAGES if n in totals)
    return (plan.seconds - stages) / plan.calls
