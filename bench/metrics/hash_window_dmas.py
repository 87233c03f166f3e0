"""SMEM-window DMAs of the numeric hash kernel per partial product: the
refills of its indptr, A-entry and B-row windows (``ip_loads``,
``a_loads``, ``b_loads``) and the output windows it writes
(``out_flushes``).  Each is a synchronous DMA the scalar unit waits on."""
import program

DMAS = ("ip_loads", "a_loads", "b_loads", "out_flushes")


def read(run):
    c = program.kernel_counts(run)
    return sum(c[k] for k in DMAS) if c else None
