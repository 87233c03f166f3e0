"""Seconds per ``plan_spgemm`` call outside its profile and symbolic
stages: the ``repro.plan`` span (structure key, cache lookup, recipe,
the plan's own syncs) less its two children."""
import program


def read(run):
    return program.plan_self_per_call()
