"""128-slot table rows the numeric hash kernel clears, and its flush
scans, per partial product: the kernel's ``table_rows`` counter."""
import program


def read(run):
    c = program.kernel_counts(run)
    return c["table_rows"] if c else None
