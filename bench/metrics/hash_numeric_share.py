"""Device time of the ``spgemm_hash_numeric`` kernel over the traced
window, in %, averaged over the cell's chips."""
import xplane

KERNEL = "spgemm_hash_numeric"


def read(run):
    per_dev = run.device_ops()
    if per_dev is None:
        return None
    w0, w1 = run.trace_window_ns()
    ns = sum(xplane.op_time_ns(evs, w0, w1, KERNEL)
             for evs in per_dev) / len(per_dev)
    return 100.0 * ns / (w1 - w0) if ns > 0 else None
