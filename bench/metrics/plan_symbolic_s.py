"""Seconds per ``plan_spgemm`` call of its ``repro.plan.symbolic`` span:
the ESC symbolic phase through the nnz(C) sync, device time included."""
import program


def read(run):
    return program.span_per_call("repro.plan.symbolic")
