"""Reduction of a JAX profiler trace (``.xplane.pb``) to device intervals.

Reads the trace with ``jax.profiler.ProfileData`` only.  A device plane is
one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation that ran on that chip (a Pallas kernel is one event, named after
the kernel).  Host planes carry the benchmark's own spans, named
``bench.<phase>``, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    """Device op events per chip and the benchmark's host spans, in ns
    on the trace's clock."""
    ops: dict = field(default_factory=dict)    # device id -> [(name, t0, t1)]
    spans: list = field(default_factory=list)  # [(name, t0, t1)]

    def window(self):
        """``(t0, t1)`` of the benchmark's ``bench.window`` span; None if
        absent."""
        for n, t0, t1 in self.spans:
            if n == "bench.window":
                return t0, t1
        return None


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off (it would record every
    Python call of the planner) and host spans at the user level, which
    keeps the benchmark's ``bench.*`` spans."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m:
                tr.spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in tr.ops.values():
        evs.sort(key=lambda e: e[1])
    tr.spans.sort(key=lambda s: s[1])
    return tr


def clip(events, t0: float, t1: float):
    """Events cut to ``[t0, t1]``; those wholly outside dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def busy_intervals(events, t0: float, t1: float):
    """Union of the events' intervals inside ``[t0, t1]``, merged."""
    merged = []
    for _, a, b in clip(events, t0, t1):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, t0, t1))


def idle_gaps(events, t0: float, t1: float):
    """``[(start, end)]`` of the stretches of ``[t0, t1]`` with no op."""
    gaps, at = [], t0
    for a, b in busy_intervals(events, t0, t1):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def op_time_ns(events, t0: float, t1: float, pattern: str | None = None):
    """``{name: summed ns}`` of the events inside the window, or, with
    ``pattern``, the summed ns of the events whose name contains it."""
    if pattern is not None:
        return sum(b - a for n, a, b in clip(events, t0, t1) if pattern in n)
    out: dict = {}
    for n, a, b in clip(events, t0, t1):
        out[n] = out.get(n, 0) + (b - a)
    return out


def span_at(spans, t: float, default: str = "outside any span") -> str:
    """Innermost benchmark span (latest start) covering time ``t``."""
    best = None
    for n, a, b in spans:
        if a <= t < b and n != "bench.window" and (best is None
                                                   or a >= best[1]):
            best = (n, a)
    return best[0] if best else default
