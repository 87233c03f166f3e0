#!/usr/bin/env python3
"""Drive one run of a cell on the CPU at a small scale, optionally with the
timed path broken underneath, and print the result line.

    python3 bench/tests/drive.py <cell> <scale> <fault> [<value dtype>]

``fault`` is ``none`` or one of :data:`FAULTS`: each replaces what the
program's executor returns with a broken answer, so a test can see that
``correct`` then comes out false.  The harness's look for a chip is
skipped (``run_cell(rehearse_scale=...)``); everything else is a run.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

FAULTS = ("stale", "half", "altered", "exchange")


def _empty_rows_from(c, r):
    """``c`` with every row from ``r`` on left out."""
    import dataclasses
    import jax.numpy as jnp
    cut = c.indptr[r]
    return dataclasses.replace(c, indptr=jnp.minimum(c.indptr, cut),
                               nnz=cut)


def _alter_first(c):
    """``c`` with its first value off by a relative 1e-3."""
    import dataclasses
    return dataclasses.replace(c, data=c.data.at[0].multiply(1.001))


def _on_parts(fn):
    """Apply ``fn(parts)`` to a row-sharded result."""
    import dataclasses

    def wrap(out):
        return dataclasses.replace(out, parts=fn(out.parts))
    return wrap


def _drop_shards_from(s0):
    """Shards from ``s0`` on come back empty: their rows left out."""
    import dataclasses

    def fn(parts):
        ip = parts.indptr.at[s0:].set(0)
        return dataclasses.replace(parts, indptr=ip,
                                   nnz=parts.nnz.at[s0:].set(0))
    return fn


def _alter_first_sharded(parts):
    import dataclasses
    return dataclasses.replace(parts,
                               data=parts.data.at[0, 0].multiply(1.001))


def install(fault: str) -> None:
    """Patch the program's executors so that every product returns the
    broken answer ``fault`` names."""
    from repro.core.distributed import DistributedPlan
    from repro.core.plan import SpGEMMPlan
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    for cls in (SpGEMMPlan, DistributedPlan):
        orig = cls.execute
        sharded = cls is DistributedPlan
        last = []

        def execute(self, *args, _orig=orig, _sharded=sharded, _last=last,
                    **kw):
            out = _orig(self, *args, **kw)
            if fault == "stale":        # the previous product's answer
                prev = _last[0] if _last else out
                _last[:] = [out]
                return prev
            if _sharded:
                n = out.parts.indptr.shape[0]
                fn = {"half": _drop_shards_from(n // 2),
                      "exchange": _drop_shards_from(1),
                      "altered": _alter_first_sharded}[fault]
                return _on_parts(fn)(out)
            if fault == "half":
                return _empty_rows_from(out, out.shape[0] // 2)
            if fault == "altered":
                return _alter_first(out)
            raise ValueError(f"{fault} does not apply to one chip")
        cls.execute = execute


def spec(cell: str) -> dict:
    """``run.load_cell``'s spec of ``<config>.<traffic>``, also for a cell
    that ``BENCHMARK.json`` does not list yet (its files are read
    directly)."""
    try:
        return run.load_cell(cell)
    except run.CellError:
        config, traffic = cell.split(".")
        cfg = run.load_json(os.path.join(run.HERE, "configs",
                                         config + ".json"))
        return {"cell": {"name": cell, "config": config,
                         "traffic": traffic, "chips": cfg["chips"]},
                "cfg": cfg,
                "traffic": run.load_json(os.path.join(
                    run.HERE, "traffic", traffic + ".json")),
                "end_to_end": [], "per_layer": []}


def main(argv) -> int:
    cell, scale, fault = argv[0], int(argv[1]), argv[2]
    dtype = argv[3] if len(argv) > 3 else None
    if fault != "none":
        install(fault)
    result = run.run_cell(spec(cell), seed=20261017, seconds=4.0,
                          trace=False, rehearse_scale=scale,
                          value_dtype=dtype)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
