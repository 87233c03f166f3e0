"""Seconds per ``plan_spgemm_1d`` call of its ``repro.plan_1d`` span: the
row-sharded plan, every shard's ``plan_spgemm`` and the stacked schedules
included."""
import program


def read(run):
    return program.span_per_call("repro.plan_1d")
