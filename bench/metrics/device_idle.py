"""Share of the traced window in which no operation ran on the chip, in
%: one minus the union of the device op intervals over the window,
averaged over the cell's chips."""
import xplane


def read(run):
    per_dev = run.device_ops()
    if per_dev is None:
        return None
    w0, w1 = run.trace_window_ns()
    busy = sum(xplane.busy_ns(evs, w0, w1) for evs in per_dev) / len(per_dev)
    return 100.0 * (1.0 - busy / (w1 - w0))
