#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 11 12 ... \\
        --control-seeds 21 22 23 [--products 2]

In one process (set-up is long, the programs compile once), for each
seed: set the cell up as a run does, drive ``--products`` products through
the timed path and compare each with the float64 reference.  The program
runs as the configuration states (float32 values); the control is the same
path handed bfloat16 values (the kernel then rounds its output to
bfloat16 too), the precision a later change might be tempted to use.  One
JSON line per seed, then a summary: the lower reading is the largest
program error, the upper reading the smallest control error.

Not part of a benchmark run.  ``--rehearse SCALE`` allows the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts src/ on the path)


def readings(cell: str, seed: int, dtype: str | None, products: int,
             devices, scale, warm: bool) -> dict:
    import workload
    spec = run.load_cell(cell)
    wl = workload.Workload(spec["cfg"], spec["traffic"], seed, devices,
                           scale=scale, value_dtype=dtype)
    wl.setup(warm=warm)
    t0 = time.perf_counter()
    got = [wl.compare(*wl.product(i)) for i in range(products)]
    return {"seed": seed, "values": wl.value_dtype,
            "rows_off": max(g["rows_off"] for g in got),
            "value_rel_err": max(g["value_rel_err"] for g in got),
            "products": products,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--products", type=int, default=2)
    ap.add_argument("--rehearse", type=int, metavar="SCALE", default=None)
    args = ap.parse_args(argv)
    run.use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and args.rehearse is None:
        print(f"readings: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    lines = []
    for dtype, seeds in ((None, args.seeds),
                         ("bfloat16", args.control_seeds)):
        for n, seed in enumerate(seeds):
            line = readings(args.workload, seed, dtype, args.products,
                            devices, args.rehearse, warm=(n == 0))
            lines.append(line)
            print(json.dumps(line), flush=True)
    prog = [ln for ln in lines if ln["values"] != "bfloat16"]
    ctrl = [ln for ln in lines if ln["values"] == "bfloat16"]
    summary = {"workload": args.workload,
               "device": devices[0].device_kind,
               "program_seeds": len(prog), "control_seeds": len(ctrl)}
    if prog:
        summary["lower_value_rel_err"] = max(p["value_rel_err"]
                                             for p in prog)
        summary["program_rows_off"] = max(p["rows_off"] for p in prog)
    if ctrl:
        summary["upper_value_rel_err"] = min(c["value_rel_err"]
                                             for c in ctrl)
        summary["control_rows_off"] = max(c["rows_off"] for c in ctrl)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
