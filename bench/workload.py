"""The one general generator: a configuration and a traffic mix in, a
stream of timed products out.

A configuration (``configs/<name>.json``) fixes the matrix: its R-MAT
family and parameters, scale, edge factor, value type, the algorithm the
planner is asked for (and the one it must resolve to), sortedness and the
number of row shards.  A traffic mix (``traffic/<name>.json``) fixes what
a product is:

* ``"structure": "fixed"`` -- one graph, from ``graph_seed``, planned once
  in set-up; each product re-weights it with the next of ``value_pool``
  value arrays drawn from ``--seed`` and runs ``execute`` only.
* ``"structure": "fresh"`` -- ``graph_pool`` graphs drawn from ``--seed``;
  each product plans its graph anew (``plan_spgemm(cache=False,
  bucket_caps=...)``) and executes it, in pool order.

Inputs are made here and kept here: the pattern on the host with
``repro.data.rmat.rmat_edges`` (duplicates merged by this module), the
values on the device in one jitted call from the seed.  The reference in
``check.py`` is handed this module's host copy, never the program's.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import check
import work

#: value range of every input entry, as ``repro.data.rmat.rmat_csr`` draws
VALUE_RANGE = (0.5, 1.5)


def derive_seed(seed: int, *path: int) -> int:
    """A 63-bit integer seed for stream ``path`` of ``seed`` (any whole
    number; negative ones are taken modulo 2**64)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), *path])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


@dataclass
class Graph:
    """A pattern on the host: sorted unique entries, row-major."""
    indptr: np.ndarray
    indices: np.ndarray      # (nnz,)
    n: int

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def make_graph(cfg: dict, seed: int, scale: int) -> Graph:
    from repro.data.rmat import PRESETS, rmat_edges
    mat = cfg["matrix"]
    family = mat["family"]
    if tuple(PRESETS[family]) != tuple(mat["rmat"]):
        raise ValueError(f"R-MAT parameters of {family} in the program "
                         f"({PRESETS[family]}) differ from the "
                         f"configuration's {mat['rmat']}")
    rows, cols = rmat_edges(scale, mat["edge_factor"], family, seed)
    n = 1 << scale
    keys = np.unique(rows * n + cols)
    r, c = keys // n, keys % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=n), out=indptr[1:])
    return Graph(indptr, c.astype(np.int32), n)


def _block(x):
    import jax
    return jax.block_until_ready(x)


class Workload:
    """Set-up, the timed product and the check of one cell.

    ``value_dtype`` is the type the program is handed; the reference
    always gets the float32 values as drawn.  ``scale`` overrides the
    configuration's (CPU rehearsals only).
    """

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices,
                 scale: int | None = None, value_dtype: str | None = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.scale = scale if scale is not None else cfg["matrix"]["scale"]
        self.n = 1 << self.scale
        self.cap = self.n * cfg["matrix"]["edge_factor"]
        self.value_dtype = value_dtype or cfg["value_dtype"]
        self.shards = cfg["shards"]
        self.devices = list(devices)[:cfg["chips"]]
        self.fresh = traffic["structure"] == "fresh"
        self._nnz_c = {}

    # ---- inputs ------------------------------------------------------
    def _values(self, nnzs, stream: int):
        """One jitted call: a ``(len(nnzs), cap)`` float32 pool on the
        device, uniform in :data:`VALUE_RANGE`, zero past each nnz."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def draw(key, nnz):
            u = jax.random.uniform(key, (nnz.shape[0], self.cap),
                                   jnp.float32, *VALUE_RANGE)
            live = jnp.arange(self.cap)[None, :] < nnz[:, None]
            return jnp.where(live, u, 0.0)

        key = jax.random.key(derive_seed(self.seed, stream) & 0x7FFFFFFF)
        return _block(draw(key, jnp.asarray(nnzs, jnp.int32)))

    def _csr(self, g: Graph, vals):
        import jax.numpy as jnp
        from repro.core import CSR
        idx = np.zeros(self.cap, np.int32)
        idx[:g.nnz] = g.indices
        return CSR(jnp.asarray(g.indptr.astype(np.int32)), jnp.asarray(idx),
                   vals.astype(self.value_dtype),
                   jnp.asarray(g.nnz, jnp.int32), (g.n, g.n),
                   sorted_cols=True)

    # ---- set-up ------------------------------------------------------
    def setup(self, warm: bool = True) -> None:
        """Inputs, the plan where the structure is fixed, and (``warm``)
        one product outside the window that compiles what it runs."""
        if self.fresh:
            self._setup_fresh(warm)
        else:
            self._setup_fixed(warm)

    def _check_algorithm(self, algorithm: str) -> None:
        want = self.cfg["expect_algorithm"]
        if algorithm != want:
            raise RuntimeError(f"planner resolved {algorithm!r}, the "
                               f"configuration runs {want!r}")

    def _setup_fixed(self, warm: bool) -> None:
        self.graphs = [make_graph(self.cfg, self.traffic["graph_seed"],
                                  self.scale)]
        pool = self.traffic["value_pool"]
        self.vals = self._values([self.graphs[0].nnz] * pool, 1)
        self.inputs = [self._csr(self.graphs[0], self.vals[k])
                       for k in range(pool)]
        if self.shards > 1:
            self._setup_mesh()
        else:
            from repro.core import plan_spgemm
            a = self.inputs[0]
            self.plan = plan_spgemm(a, a, algorithm=self.cfg["algorithm"],
                                    sorted_output=self.cfg["sorted_output"])
            _block(self.plan.indptr_c)
            self._check_algorithm(self.plan.algorithm)
        if warm:
            self.product(0)

    def _setup_mesh(self) -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import plan_spgemm_1d, shard_csr_rows
        if len(self.devices) < self.shards:
            raise RuntimeError(f"{self.shards} shards need {self.shards} "
                               f"devices, found {len(self.devices)}")
        self.mesh = Mesh(np.array(self.devices[:self.shards]), ("data",))
        a = self.inputs[0]
        a_sh = shard_csr_rows(a, self.shards, b=a)
        self.plan = plan_spgemm_1d(a_sh, a, algorithm=self.cfg["algorithm"],
                                   sorted_output=self.cfg["sorted_output"])
        self._check_algorithm(self.plan.algorithm)
        self.row_starts = tuple(self.plan.row_starts)
        row_sh = NamedSharding(self.mesh, P("data"))
        rep = NamedSharding(self.mesh, P())
        self.mesh_inputs = []
        for b in self.inputs:
            sh = shard_csr_rows(b, self.shards, row_starts=self.row_starts)
            sh = jax.device_put(sh, jax.tree.map(lambda _: row_sh, sh))
            b = jax.device_put(b, jax.tree.map(lambda _: rep, b))
            self.mesh_inputs.append((sh, b))

    def _setup_fresh(self, warm: bool) -> None:
        if self.shards > 1:
            raise NotImplementedError(
                "fresh structure on a mesh: plan_spgemm_1d has no "
                "bucket_caps, so every product would compile anew")
        pool = self.traffic["graph_pool"]
        self.graphs = [make_graph(self.cfg, derive_seed(self.seed, 2, j),
                                  self.scale) for j in range(pool)]
        warm_g = make_graph(self.cfg, derive_seed(self.seed, 3), self.scale)
        self.vals = self._values([g.nnz for g in self.graphs] + [warm_g.nnz],
                                 1)
        self.inputs = [self._csr(g, self.vals[j])
                       for j, g in enumerate(self.graphs)]
        if warm:
            self._plan_and_execute(self._csr(warm_g, self.vals[pool]))

    # ---- the timed product ------------------------------------------
    def _plan_and_execute(self, a):
        from repro.core import plan_spgemm
        t0 = time.perf_counter()
        plan = plan_spgemm(a, a, algorithm=self.cfg["algorithm"],
                           sorted_output=self.cfg["sorted_output"],
                           cache=False,
                           bucket_caps=self.traffic["bucket_caps"])
        _block(plan.indptr_c)
        plan_s = time.perf_counter() - t0
        self._check_algorithm(plan.algorithm)
        return _block(plan.execute(a, a)), plan_s

    def product(self, i: int):
        """Product ``i`` of the stream, finished on the device.  Returns
        ``(output, record)``; the record names the inputs it used."""
        import jax
        if self.fresh:
            j = i % len(self.graphs)
            with jax.profiler.TraceAnnotation("bench.plan_execute"):
                out, plan_s = self._plan_and_execute(self.inputs[j])
            return out, {"graph": j, "values": j, "plan_s": plan_s}
        k = i % len(self.inputs)
        with jax.profiler.TraceAnnotation("bench.execute"):
            if self.shards > 1:
                a_sh, b = self.mesh_inputs[k]
                out = self.plan.execute(self.mesh, a_sh, b)
                _block(out.parts)
            else:
                a = self.inputs[k]
                out = _block(self.plan.execute(a, a))
        return out, {"graph": 0, "values": k}

    # ---- work and the check -----------------------------------------
    def flop(self, rec) -> int:
        g = self.graphs[rec["graph"]]
        return work.flop(g.indptr, g.indices, g.indptr)

    def _ref_structure(self, j: int):
        """nnz of each row of C for graph ``j`` (boolean product, host)."""
        if j not in self._nnz_c:
            g = self.graphs[j]
            ref = check.reference(g.indptr, g.indices,
                                  np.ones(g.nnz), g.n)
            self._nnz_c[j] = np.diff(ref.indptr)
        return self._nnz_c[j]

    def device_work(self, rec):
        """``[(flop, compulsory bytes)]`` of the product, one per device
        it runs on: per row shard on a mesh."""
        g = self.graphs[rec["graph"]]
        row_c = self._ref_structure(rec["graph"])
        if self.shards == 1:
            return [(work.flop(g.indptr, g.indices, g.indptr),
                     work.compulsory_bytes(g.indptr, g.indices, g.indptr,
                                           int(row_c.sum()), a_row0=0))]
        out = []
        for s in range(self.shards):
            r0, r1 = self.row_starts[s], self.row_starts[s + 1]
            ip = g.indptr[r0:r1 + 1] - g.indptr[r0]
            idx = g.indices[g.indptr[r0]:g.indptr[r1]]
            out.append((work.flop(ip, idx, g.indptr),
                        work.compulsory_bytes(ip, idx, g.indptr,
                                              int(row_c[r0:r1].sum()),
                                              a_row0=r0)))
        return out

    def shard_flops(self):
        """Partial products of each row shard, or None on one chip."""
        if self.shards == 1:
            return None
        g = self.graphs[0]
        rf = work.row_flop(g.indptr, g.indices, g.indptr)
        return [int(rf[self.row_starts[s]:self.row_starts[s + 1]].sum())
                for s in range(self.shards)]

    def _host_output(self, out):
        """``(indptr, indices, values)`` of the product on the host; a
        row-sharded product is put together here from its shards."""
        if self.shards == 1:
            return (np.asarray(out.indptr), np.asarray(out.indices),
                    np.asarray(out.data).astype(np.float32))
        ip = np.asarray(out.parts.indptr).astype(np.int64)
        ind = np.asarray(out.parts.indices)
        dat = np.asarray(out.parts.data).astype(np.float32)
        counts, idx, val = [], [], []
        for s in range(self.shards):
            m_s = self.row_starts[s + 1] - self.row_starts[s]
            live = ip[s, m_s]
            counts.append(np.diff(ip[s, :m_s + 1]))
            idx.append(ind[s, :live])
            val.append(dat[s, :live])
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.concatenate(counts), out=indptr[1:])
        return indptr, np.concatenate(idx), np.concatenate(val)

    def compare(self, out, rec) -> dict:
        """The check of one product against the float64 reference."""
        g = self.graphs[rec["graph"]]
        vals = np.asarray(self.vals[rec["values"]])[:g.nnz]
        ref = check.reference(g.indptr, g.indices, vals, g.n)
        return check.compare(self._host_output(out), ref)
