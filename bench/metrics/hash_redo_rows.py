"""Rows of A the numeric hash kernel redoes by its probe loop, because an
insert outgrew the two table rows it tries first, per partial product:
the kernel's ``redo_rows`` counter.  None where the kernel has no such
counter."""
import program


def read(run):
    c = program.kernel_counts(run)
    return c.get("redo_rows") if c else None
