"""Partial products of the heaviest row shard over the mean shard's,
from the plan's shard boundaries and the benchmark's own per-row flop
count.  Only on a row-sharded product."""


def read(run):
    flops = run.workload.shard_flops()
    if not flops:
        return None
    return max(flops) / (sum(flops) / len(flops))
