"""Seconds per ``plan_spgemm`` call of its ``repro.plan.profile`` span:
flop profile, equal-flop bins and table sizes, with their host syncs."""
import program


def read(run):
    return program.span_per_call("repro.plan.profile")
