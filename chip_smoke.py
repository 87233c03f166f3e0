#!/usr/bin/env python3
"""Smoke test of the planned hash SpGEMM path on a TPU, checked against scipy.

Runs the system's main path -- ``plan_spgemm(a, b)`` then
``plan.execute(a, b)``, which on R-MAT A x A routes to the Pallas hash
kernel -- on matrices generated from a seed, and compares every product
with ``scipy.sparse`` (row pointers and per-row column sets exact, values
to ``rtol=1e-5``).  Each phase prints one JSON line; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Usage::

    python chip_smoke.py              # one chip, two phases:
                                      #  G500 scale 16, algorithm "auto"
                                      #  ER scale 18, algorithm "hash_vector"
    python chip_smoke.py --mesh 4     # four chips: 1-D row-sharded plan
                                      #  vs the single-chip product
                                      #  (run on chips at scales 15, 12)

The script refuses to run (exit 1, no result line) when JAX finds no TPU.
``--rehearse`` lifts that for a CPU rehearsal at small scales (kernels
interpreted), e.g.::

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --g500-scale 9 \\
        --er-scale 9
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse --mesh 4 --g500-scale 9

The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
else to ``.jax_cache/`` next to this file.  Times printed are wall-clock
seconds of single calls, each ended by ``block_until_ready``: set-up and
first-call figures, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: R-MAT edge factor (nnz per row) of every input, as in the paper's
#: section 5.1 matrix families.
EDGE_FACTOR = 16
sys.path.insert(0, os.path.join(ROOT, "src"))


def _emit(doc: dict) -> None:
    print(json.dumps(doc, sort_keys=True), flush=True)


def _note(msg: str) -> None:
    """Progress on stderr, so a run cut by its time limit shows how far
    it got."""
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _device(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _host_csr(c):
    """``(indptr, indices, data)`` of a CSR's live prefix, on the host."""
    import numpy as np
    nnz = int(c.nnz)
    return (np.asarray(c.indptr), np.asarray(c.indices)[:nnz],
            np.asarray(c.data)[:nnz])


def _row_sorted(c):
    """Host arrays of ``c`` with each row's entries sorted by column."""
    import numpy as np
    ip, idx, val = _host_csr(c)
    rows = np.repeat(np.arange(ip.size - 1, dtype=np.int64), np.diff(ip))
    order = np.argsort(rows * c.shape[1] + idx, kind="stable")
    return ip, idx[order], val[order]


def _scipy_square(a):
    """The reference: A @ A in float64 by ``scipy.sparse``, rows sorted."""
    import numpy as np
    import scipy.sparse as sp
    ip, idx, val = _host_csr(a)
    sa = sp.csr_matrix((val.astype(np.float64), idx, ip), shape=a.shape)
    ref = (sa @ sa).tocsr()
    ref.sort_indices()
    return ref


def _check_vs_scipy(row_sorted, ref) -> None:
    """``row_sorted`` is :func:`_row_sorted` of the product."""
    import numpy as np
    ip, idx, val = row_sorted
    _require(np.array_equal(ip, ref.indptr), "indptr differs from scipy")
    _require(np.array_equal(idx, ref.indices), "row column sets differ")
    np.testing.assert_allclose(val, ref.data, rtol=1e-5, atol=0)


def _require(ok, msg: str) -> None:
    """A check that ``python -O`` keeps (unlike ``assert``)."""
    if not ok:
        raise RuntimeError(msg)


def _block(x):
    import jax
    return jax.block_until_ready(x)


def _matrix(preset: str, scale: int, seed: int):
    from repro.data.rmat import rmat_csr
    return rmat_csr(scale, EDGE_FACTOR, preset, seed=seed)


def run_phase(name, preset, scale, seed, algorithm, expect, devs) -> None:
    """Plan once, execute twice, check against scipy, print one line."""
    from repro.core import plan_spgemm
    from repro.kernels import _compat
    from repro.kernels.spgemm_hash import ops as hash_ops

    a = _matrix(preset, scale, seed)
    before = hash_ops.kernel_call_counts()
    t0 = time.perf_counter()
    plan = plan_spgemm(a, a, algorithm=algorithm)
    _block(plan.indptr_c)
    t1 = time.perf_counter()
    _note(f"{name}: planned {plan.algorithm} in {t1 - t0:.1f} s")
    c = _block(plan.execute(a, a))
    t2 = time.perf_counter()
    _note(f"{name}: first execute in {t2 - t1:.1f} s")
    c = _block(plan.execute(a, a))
    t3 = time.perf_counter()
    after = hash_ops.kernel_call_counts()
    calls = {k: after[k] - before[k] for k in after}
    _require(plan.algorithm == expect,
             f"{name}: plan chose {plan.algorithm!r}, expected {expect!r}")
    _require(calls["numeric"] > 0, f"{name}: numeric kernel never ran")
    ref = _scipy_square(a)
    _check_vs_scipy(_row_sorted(c), ref)
    _emit({"phase": name, "matrix": preset, "scale": scale,
           "edge_factor": EDGE_FACTOR, "seed": seed,
           "algorithm": algorithm, "plan_algorithm": plan.algorithm,
           "interpret": _compat.resolve_interpret(), "kernel_calls": calls,
           "nnz_a": int(a.nnz), "total_flop": plan.total_flop,
           "nnz_c": int(c.nnz), "plan_s": t1 - t0,
           "execute_first_s": t2 - t1, "execute_second_s": t3 - t2,
           "peak_bytes": _peak_bytes(devs[0]), "scipy_match": True,
           "device": _device(devs)})


def run_mesh(n, scale, seed, devs) -> None:
    """1-D row-sharded plan on an ``n``-device mesh vs the single-device
    planned product (bitwise after row sorting) and vs scipy."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import (plan_spgemm, plan_spgemm_1d, shard_csr_rows,
                            unshard_rows)
    from repro.kernels import _compat

    _require(len(devs) >= n,
             f"--mesh {n} needs {n} devices, found {len(devs)}")
    a = _matrix("G500", scale, seed)
    single = _block(plan_spgemm(a, a, algorithm="hash").execute(a, a))
    _note("single-device product done")
    mesh = Mesh(np.array(devs[:n]), ("data",))
    a_sh = shard_csr_rows(a, n, b=a)
    t0 = time.perf_counter()
    plan = plan_spgemm_1d(a_sh, a, algorithm="hash")
    t1 = time.perf_counter()
    _note(f"planned {n} shards in {t1 - t0:.1f} s")
    out = _block(plan.execute(mesh, a_sh, a))   # once: a second costs n chips
    t2 = time.perf_counter()
    homes = {s.device for s in out.parts.indices.addressable_shards}
    _require(len(homes) == n, f"output shards on {len(homes)} devices")
    c = unshard_rows(out)
    got, want = _row_sorted(c), _row_sorted(single)
    for g, w, what in zip(got, want, ("indptr", "indices", "data")):
        _require(np.array_equal(g.view(np.int32), w.view(np.int32)),
                 f"mesh {what} differs bitwise from the single-device product")
    _check_vs_scipy(got, _scipy_square(a))
    _emit({"phase": f"mesh{n}_g500", "matrix": "G500", "scale": scale,
           "edge_factor": EDGE_FACTOR, "seed": seed,
           "plan_algorithm": plan.algorithm, "row_starts": plan.row_starts,
           "interpret": _compat.resolve_interpret(), "nnz_c": int(c.nnz),
           "plan_s": t1 - t0, "execute_s": t2 - t1,
           "output_devices": sorted(d.id for d in homes),
           "peak_bytes_per_device": [_peak_bytes(d) for d in devs[:n]],
           "bitwise_vs_single": True, "scipy_match": True,
           "device": _device(devs[:n])})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the N-device row-sharded phase")
    ap.add_argument("--g500-scale", type=int, default=16)
    ap.add_argument("--er-scale", type=int, default=18)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU backend (kernels interpreted)")
    args = ap.parse_args(argv)

    import jax
    from repro.kernels._compat import use_compile_cache
    use_compile_cache(os.path.join(ROOT, ".jax_cache"))
    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if args.mesh:
        run_mesh(args.mesh, args.g500_scale, args.seed, devs)
        devs = devs[:args.mesh]
    else:
        run_phase(f"g500_s{args.g500_scale}_auto", "G500", args.g500_scale,
                  args.seed, "auto", "hash", devs)
        run_phase(f"er_s{args.er_scale}_hash_vector", "ER", args.er_scale,
                  args.seed, "hash_vector", "hash_vector", devs)
    _emit({"ok": True, "device": _device(devs)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
