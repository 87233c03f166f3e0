#!/usr/bin/env python3
"""Record a small device trace, to check the trace reduction against.

Run on one chip from the root of the checkout::

    python3 bench/tests/record_trace.py g500_s9.xplane.pb

It plans a G500 scale-9 square, then traces two executes inside a
``bench.window`` span (each in a ``bench.execute`` span), copies the
``.xplane.pb`` to the path given, and prints the trace's planes, lines
and most frequent event names, so a reader can see how the chip names
its work.
"""
import collections
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(out: str) -> int:
    import jax
    import xplane
    from run import load_cell
    from workload import Workload
    spec = load_cell("g500_s13.repeat")
    wl = Workload(spec["cfg"], spec["traffic"], 1, jax.devices(), scale=9)
    wl.setup()
    d = tempfile.mkdtemp(prefix="bench_trace_")
    xplane.start(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            wl.product(i)
    jax.profiler.stop_trace()
    src = xplane.find_xplane(d)
    shutil.copyfile(src, out)
    shutil.rmtree(d, ignore_errors=True)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print("  line", repr(line.name), sum(names.values()),
                  names.most_common(8))
    tr = xplane.load(out)
    print("window", tr.window(), "devices", sorted(tr.ops),
          "spans", tr.spans[:6])
    print("bytes", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
