"""Mean seconds of ``plan_spgemm`` per product, by the benchmark's host
span around it (ended when the plan's ``indptr_c`` is ready).  Only where
the products plan in the window."""


def read(run):
    spans = [r["plan_s"] for r in run.records if "plan_s" in r]
    return sum(spans) / len(spans) if spans else None
