"""Share of its roofline that the numeric hash kernel reaches, in %.

The least time of each product on each chip is the larger of ``2 * flop``
over the chip's peak FLOP/s and the compulsory bytes over its HBM
bandwidth (``work.py``); their sum over the window's products and chips,
over the summed device time of the ``spgemm_hash_numeric`` events there.
"""
import work
import xplane

KERNEL = "spgemm_hash_numeric"


def read(run):
    per_dev = run.device_ops()
    if per_dev is None or run.peaks is None:
        return None
    w0, w1 = run.trace_window_ns()
    kernel_ns = sum(xplane.op_time_ns(evs, w0, w1, KERNEL)
                    for evs in per_dev)
    if kernel_ns <= 0:
        return None
    least = 0.0
    for rec in run.records:
        for flop, nbytes in run.workload.device_work(rec):
            least += work.roofline_seconds(
                2 * flop, nbytes, run.peaks["flops_per_s"],
                run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns * 1e-9)
