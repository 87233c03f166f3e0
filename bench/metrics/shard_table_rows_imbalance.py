"""The hash kernel's ``table_rows`` counter (128-slot table rows cleared
and scanned, its non-flop work) on the heaviest row shard over the mean
shard's, from one counted run per shard of the set-up plan
(``DistributedPlan.kernel_counts``), made after the window.  The
equal-flop partition does not weigh this work.  None on one chip, and in
a program whose mesh plan has no per-shard counters."""


def read(run):
    wl = run.workload
    plan = getattr(wl, "plan", None)
    if getattr(wl, "shards", 1) < 2 or not hasattr(plan, "kernel_counts"):
        return None
    a_sh, b = wl.mesh_inputs[0]
    try:
        counts = plan.kernel_counts(a_sh, b)
    except ValueError:                   # the plan runs no hash kernel
        return None
    rows = [c["table_rows"] for c in counts]
    mean = sum(rows) / len(rows)
    return max(rows) / mean if mean > 0 else None
