"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs the larger sizes;
the default quick mode fits the single-core container (see
benchmarks/common.py for the interpret-mode caveat).

``--json PATH`` additionally writes the run as a machine-readable perf
trajectory (``BENCH_spgemm.json`` by convention): every emitted row plus
environment provenance, one file per run -- CI produces and uploads it on
every push so regressions are diffable across commits.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import sys
import time
import traceback

from . import common
from . import bench_spgemm_figs as figs
from . import bench_graph as graph
from . import bench_micro as micro
from . import bench_moe_dispatch as moe_bench
from . import bench_plan as plan_bench
from . import bench_distributed as dist_bench
from . import bench_chain as chain_bench
from . import bench_batch as batch_bench
from . import bench_verify as verify_bench
from . import bench_autotune as autotune_bench
from . import bench_bcsr as bcsr_bench
from . import bench_pb as pb_bench


SUITES = [
    ("fig2_scheduling", lambda q: micro.fig2_scheduling(q)),
    ("fig4_alloc", lambda q: micro.fig4_alloc(q)),
    ("fig5_stanza", lambda q: micro.fig5_stanza(q)),
    ("fig9_balanced_vs_naive", lambda q: figs.fig9_balanced_vs_naive()),
    ("fig11_density", lambda q: figs.fig11_density(q)),
    ("fig12_size", lambda q: figs.fig12_size(q)),
    ("fig13_scaling", lambda q: figs.fig13_scaling(q)),
    ("fig14_compression", lambda q: figs.fig14_compression(q)),
    ("fig15_profiles", lambda q: figs.fig15_profiles(q)),
    ("fig16_tall_skinny", lambda q: figs.fig16_tall_skinny(q)),
    ("fig17_triangle", lambda q: figs.fig17_triangle(q)),
    ("table4_recipe", lambda q: figs.table4_recipe(q)),
    ("graph", lambda q: graph.run(q)),
    ("moe_dispatch", lambda q: moe_bench.run(q)),
    ("plan", lambda q: plan_bench.run(q)),
    ("distributed", lambda q: dist_bench.run(q)),
    ("chain", lambda q: chain_bench.run(q)),
    ("batch", lambda q: batch_bench.run(q)),
    ("verify", lambda q: verify_bench.run(q)),
    ("autotune", lambda q: autotune_bench.run(q)),
    ("bcsr", lambda q: bcsr_bench.run(q)),
    ("pb", lambda q: pb_bench.run(q)),
]


def _git_sha() -> str:
    """Current commit (best effort; benchmarks also run from tarballs)."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _jaxlib_version() -> str:
    try:
        import jaxlib
        return getattr(jaxlib, "__version__", "unknown")
    except ImportError:
        return "unknown"


def _row_doc(row) -> dict:
    """One trajectory row.  Rows that attached a work model via
    ``common.emit(..., flops=, bytes_moved=)`` carry roofline columns
    (bound / roof_fraction / achieved rates)."""
    name, us, derived, extras = row
    doc = {"name": name, "us_per_call": round(us, 3), "derived": derived}
    roof = extras.get("roofline")
    if roof is not None:
        doc["flops"] = extras["flops"]
        doc["bytes_moved"] = extras["bytes_moved"]
        doc["roofline_bound"] = roof["bound"]
        doc["roof_fraction"] = round(roof["roof_fraction"], 6)
        doc["achieved_gflops"] = round(roof["achieved_gflops"], 4)
        doc["achieved_gbps"] = round(roof["achieved_gbps"], 4)
    return doc


def write_json(path: str, suites_run, failures: int) -> None:
    """Serialize ``common.ROWS`` + provenance as the perf trajectory."""
    import jax
    doc = {
        "schema": 1,
        "unix_time": int(time.time()),
        "git_sha": _git_sha(),
        "jax": jax.__version__,
        "jaxlib": _jaxlib_version(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "machine": platform.machine(),
        "suites": list(suites_run),
        "failures": failures,
        "rows": [_row_doc(row) for row in common.ROWS],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {path}: {len(doc['rows'])} rows", file=sys.stderr)
    _feed_db(doc)


def _feed_db(doc: dict) -> None:
    """Best-effort: mirror the trajectory rows into the autotune PerfDB
    (``bench|`` namespace, aged by this run's git sha) so the perf history
    CI gates on is queryable next to the tuner's winners."""
    try:
        from repro.autotune import feed_bench_rows
        n = feed_bench_rows(doc)
        print(f"fed {n} rows into the autotune DB", file=sys.stderr)
    except Exception as exc:  # noqa: BLE001 - ingestion never fails a run
        print(f"autotune DB feed skipped ({type(exc).__name__}: {exc})",
              file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write results as a machine-readable perf "
                         "trajectory (e.g. BENCH_spgemm.json)")
    args = ap.parse_args(argv)
    from repro.kernels._compat import use_compile_cache
    use_compile_cache(str(pathlib.Path(__file__).resolve().parents[1]
                          / ".jax_cache"))
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failures = 0
    suites_run = []
    for name, fn in SUITES:
        if only and name not in only:
            continue
        suites_run.append(name)
        try:
            fn(not args.full)
        except Exception:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"{name},ERROR,", flush=True)
            traceback.print_exc()
    if args.json:
        write_json(args.json, suites_run, failures)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
