"""Probe-loop steps of the numeric hash kernel per partial product, from
the kernel's ``probe_steps`` counter (1 where every probe finds its key
or an empty slot in the first table row it loads)."""
import program


def read(run):
    c = program.kernel_counts(run)
    return c["probe_steps"] if c else None
