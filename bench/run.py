#!/usr/bin/env python3
"""Benchmark of planned sparse products on the chip: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  The run loads
the cell's configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``), sets up (inputs, the plan where the
structure is fixed, one warm-up product that compiles every program the
window runs), then runs whole products until ``--seconds`` have passed,
checks a sample of them against the float64 reference, and prints one
JSON line last on standard output.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace of the window
and reports its per-layer metrics, each read by ``bench/metrics/<name>.py``.

The run refuses to start (exit 1, no result) without a TPU, or with fewer
chips than the cell asks for.  ``--rehearse SCALE`` lifts that for a
rehearsal on the CPU at R-MAT scale ``SCALE`` (kernels interpreted); its
numbers are not device numbers.  For the four-chip cell the CPU needs
four devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
else to ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

#: products whose output is kept and checked against the reference,
#: drawn uniformly from the window's products by reservoir sampling
SAMPLE = 2
#: backend compiles (or loads from the persistent cache), as JAX reports
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CellError(Exception):
    """The cell cannot run here: a refusal, not a result."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's ``BENCHMARK.json`` entries, configuration and traffic."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    def reports(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def device_peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def use_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # cache every program, the planner's small eager ones too, so that a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            self.n += 1


class RunData:
    """What the per-layer metric readers get."""

    def __init__(self, wl, records, compiles, trace, peaks, device_ids):
        self.workload = wl
        self.records = records          # one dict per product
        self.compiles_in_window = compiles
        self.trace = trace              # xplane.Trace or None
        self.peaks = peaks              # peaks.json entry or None
        self.device_ids = device_ids    # trace planes of the cell's chips

    def device_ops(self):
        """``[events]`` per chip of the cell, cut to the window; None
        without a device trace."""
        if self.trace is None or self.trace.window() is None:
            return None
        ids = [d for d in self.device_ids if d in self.trace.ops]
        if not ids:
            return None
        import xplane
        w0, w1 = self.trace.window()
        return [xplane.clip(self.trace.ops[d], w0, w1) for d in ids]

    def trace_window_ns(self):
        return self.trace.window() if self.trace is not None else None


def read_metric(name: str, run: RunData):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def breakdown(run: RunData) -> dict | None:
    import xplane
    per_dev = run.device_ops()
    if per_dev is None:
        return None
    w0, w1 = run.trace.window()
    totals: dict = {}
    gaps = []
    for evs in per_dev:
        for name, ns in xplane.op_time_ns(evs, w0, w1).items():
            totals[name] = totals.get(name, 0) + ns / len(per_dev)
        for a, b in xplane.idle_gaps(evs, w0, w1):
            gaps.append((xplane.span_at(run.trace.spans, (a + b) / 2),
                         (b - a) * 1e-9))
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:10]]}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             rehearse_scale: int | None = None, value_dtype=None) -> dict:
    """One run of the cell :func:`load_cell` gave; returns the result
    line as a dict."""
    cell, cfg = spec["cell"], spec["cfg"]
    name = cell["name"]
    use_compile_cache()
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if rehearse_scale is None:
        if devices[0].platform != "tpu":
            raise CellError(f"no TPU: JAX found {devices[0].platform!r}")
        peaks = device_peaks(kind)
    else:
        peaks = None
    if len(devices) < cell["chips"]:
        raise CellError(f"{name} needs {cell['chips']} chips, JAX found "
                        f"{len(devices)}")
    devices = devices[:cell["chips"]]
    counter = CompileCounter()

    import numpy as np
    import workload
    wl = workload.Workload(cfg, spec["traffic"], seed, devices,
                           scale=rehearse_scale, value_dtype=value_dtype)
    wl.setup()
    setup_s = time.perf_counter() - T_PROCESS
    print(f"bench: {name} set up in {setup_s:.1f} s", file=sys.stderr,
          flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        import xplane
        xplane.start(trace_dir)
    rng = np.random.default_rng(workload.derive_seed(seed, 4))
    kept, records, failed, errors = [], [], 0, []
    compiles0 = counter.n
    t0 = time.perf_counter()
    t_end = t0
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            i = len(records)
            try:
                out, rec = wl.product(i)
            except Exception as e:            # a product that fails counts
                failed += 1
                errors.append(f"product {i}: {type(e).__name__}: {e}")
                t_end = time.perf_counter()
                break
            t_end = time.perf_counter()
            records.append(rec)
            if len(kept) < SAMPLE:
                kept.append((i, out, rec))
            else:
                j = int(rng.integers(0, i + 1))
                if j < SAMPLE:
                    kept[j] = (i, out, rec)
            del out
            if t_end - t0 >= seconds:
                break
    window_s = t_end - t0
    compiles = counter.n - compiles0
    for rec in records:
        rec["flop"] = wl.flop(rec)
    trace_data = None
    if trace:
        jax.profiler.stop_trace()
        import xplane
        try:
            trace_data = xplane.load(xplane.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    # the check, after the window and after the memory peak was read
    rows_off, rel_err = 0, 0.0
    for i, out, rec in kept:
        got = wl.compare(out, rec)
        if got["rows_off"] > 0 or got["value_rel_err"] > cfg["limits"][
                "value_rel_err"]:
            failed += 1
        rows_off = max(rows_off, got["rows_off"])
        rel_err = max(rel_err, got["value_rel_err"])
    del kept
    limits = {"rows_off": 0, "value_rel_err": cfg["limits"]["value_rel_err"]}
    correct = (failed == 0 and len(records) > 0 and rows_off <= 0
               and rel_err <= limits["value_rel_err"])

    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    metrics = {}
    result = {"correct": correct, "attempted": len(records) + len(errors),
              "failed": failed, "metrics": metrics, "device": dev}
    if not trace:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = {"setup_s": setup_s, "peak_hbm": peak_bytes / 1e9}
        if window_s > 0 and records:
            values["flop_rate"] = (2 * sum(r["flop"] for r in records)
                                   / window_s / 1e6)
        for m, unit in units.items():
            if m in values:
                metrics[m] = {"value": values[m], "unit": unit}
    else:
        run = RunData(wl, records, compiles, trace_data, peaks,
                      [d.id for d in devices])
        for m in spec["per_layer"]:
            v = read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        per_dev = run.device_ops()
        if per_dev is not None:
            import xplane
            w0, w1 = trace_data.window()
            dev["busy_s"] = sum(xplane.busy_ns(e, w0, w1)
                                for e in per_dev) / len(per_dev) * 1e-9
            dev["window_s"] = (w1 - w0) * 1e-9
            result["breakdown"] = breakdown(run)
    for e in errors:
        print(f"bench: {e}", file=sys.stderr)
    # strict JSON has no infinity: a comparison that found no number
    # (malformed output, NaN) reads as the largest float
    rel_err = min(rel_err, sys.float_info.max)
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in (("rows_off", rows_off),
                                     ("value_rel_err", rel_err))}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, metavar="SCALE", default=None,
                    help="allow the CPU, at R-MAT scale SCALE")
    args = ap.parse_args(argv)
    try:
        result = run_cell(load_cell(args.workload), args.seed, args.seconds,
                          bool(args.trace), rehearse_scale=args.rehearse)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
