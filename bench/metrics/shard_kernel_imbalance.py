"""Device time of ``spgemm_hash_numeric`` in the window on the slowest
chip over the mean chip's: the pace the slowest row shard sets.  Only
where the cell runs on more than one chip."""
import xplane

KERNEL = "spgemm_hash_numeric"


def read(run):
    per_dev = run.device_ops()
    if per_dev is None or len(per_dev) < 2:
        return None
    w0, w1 = run.trace_window_ns()
    ns = [xplane.op_time_ns(evs, w0, w1, KERNEL) for evs in per_dev]
    mean = sum(ns) / len(ns)
    return max(ns) / mean if mean > 0 else None
