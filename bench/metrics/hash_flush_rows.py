"""128-slot table rows the numeric hash kernel's flush compacts and
writes (those holding an entry), per partial product: the kernel's
``flush_rows`` counter.  None where the kernel has no such counter."""
import program


def read(run):
    c = program.kernel_counts(run)
    return c.get("flush_rows") if c else None
