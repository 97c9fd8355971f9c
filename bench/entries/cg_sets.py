"""Sets of CG iterations through ``repro.numerics.solvers.cg_solve`` on a
mesh of chips: the operator row-sharded over a ``(chips, 1)`` ``("data",
"model")`` mesh, jitted under ``use_level(O3, mesh)``, so the registry
runs the mesh CG (``solver_spmv`` -> ``mesh_dia``).  Each set runs exactly
``traffic["max_iters"]`` iterations from x0 = 0 (tolerance 0), as HPCG's
timed CG sets do.

Work of one set, per chip (the peaks are one chip's), from shapes, per
iteration: one DIA SpMV over the chip's rows, (ndiags + 2) * 4 B per row;
the minimum vector passes, 9 * 4 B per row; 2 FLOPs per stored non-zero
(the chip's share) plus 10 per row.  The halo's 2 * max|offset| rows are
left out: under 0.6 % of x at the configuration's size.

The right-hand sides are b = A x*, ``traffic["pool"]`` of them, cycled
through by the window.  The check compares each sampled set's x with the
generator's textbook CG (f32, the same b and iteration count):
max|x - x_ref| / max|x_ref|, as the CG cell's ``x_err``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness import ROOT, Work, load_module

cg = load_module(ROOT, "entries", "cg")

#: the program's gauge of the bytes of x one shard receives per SpMV
EXCHANGE_GAUGE = "distributed.mesh_dia.exchange_bytes_per_iter"


def program(offsets, n: int, max_iters: int):
    """The timed path: ``cg_solve`` on a DIA operator for ``max_iters``
    iterations; returns (x, iterations).  Jit it and call it under
    :func:`level`."""
    def solve(diags, b):
        from repro.core import unwrap
        from repro.numerics.solvers import cg_solve
        from repro.numerics.sparse import DIA

        a = DIA(diags=diags, offsets=tuple(offsets), shape=(n, n))
        res = cg_solve(a, b, stop=0.0, max_iters=max_iters)
        return unwrap(res.x), res.iterations
    return solve


def level(mesh):
    """The mesh, at O3."""
    from repro.core import ExecLevel, use_level

    return use_level(ExecLevel.O3, mesh)


def work(op, iterations: float) -> Work:
    """Work of one set of ``iterations``, on one chip."""
    return Work(
        flops=iterations * (2 * op.nnz / op.shards
                            + cg.VECTOR_FLOPS_PER_ROW * op.n_local),
        hbm_bytes=iterations * (op.spmv_bytes
                                + cg.VECTOR_BYTES_PER_ROW * op.n_local))


class Cell(cg.Cell):
    def __init__(self, config, traffic, seed, devices, *, root=ROOT,
                 control=False):
        self.gen = gen = load_module(root, "generators", config["generator"])
        self.op = op = gen.Operator(config, seed, devices)
        self.traffic = traffic
        self.control = control
        max_iters = int(traffic["max_iters"])
        self.bs = op.rhs(seed, int(traffic["pool"]))
        self.reference = jax.jit(functools.partial(op.cg,
                                                   max_iters=max_iters))
        if control:
            self.program = jax.jit(functools.partial(
                op.cg, max_iters=max_iters,
                dtype=gen.DTYPES[traffic["control_dtype"]]))
        else:
            self.program = jax.jit(program(op.offsets, op.n, max_iters))
        jax.block_until_ready((op.diags, self.bs))

    def _solve(self, b):
        if self.control:
            x, k = self.program(self.op.diags, b=b)
            return x.astype(jnp.float32), k
        with level(self.op.mesh):
            return self.program(self.op.diags, b)

    def warm(self):
        """As the CG cell's, after clearing JAX's trace caches: the
        registry counts a dispatch when it traces one, and the mesh CG's
        inner executables are cached per mesh and shape, so a set traced
        earlier in this process would hide this cell's dispatches."""
        jax.clear_caches()
        super().warm()

    def variants(self) -> dict:
        from repro.core import registry, wrap
        from repro.numerics.sparse import DIA
        from repro.obs import metrics

        ran = {k: v["value"] for k, v in
               metrics.METRICS.snapshot("dispatch.").items()}
        a = DIA(diags=self.op.diags, offsets=self.op.offsets,
                shape=(self.op.n, self.op.n))
        with level(self.op.mesh):
            selected = registry.select("solver_spmv", a,
                                       wrap(self.bs[0])).name
        if not self.control:
            want = self.traffic.get("expect", {})
            if "solver_spmv" in want and selected != want["solver_spmv"]:
                raise AssertionError(f"solver_spmv selected {selected!r}, "
                                     f"expected {want['solver_spmv']!r}")
            for op, variant in want.items():
                if op == "solver_spmv":
                    continue
                names = {k for k in ran if k.startswith(f"dispatch.{op}.")}
                if names != {f"dispatch.{op}.{variant}"}:
                    raise AssertionError(f"{op}: expected {variant}, ran "
                                         f"{ran}")
        return {"counters": ran, "solver_spmv": selected}

    def stats(self, outs) -> dict:
        from repro.obs import metrics

        out = super().stats(outs)
        gauge = metrics.METRICS.snapshot(EXCHANGE_GAUGE).get(EXCHANGE_GAUGE)
        if gauge is not None and not self.control:
            out["exchange_bytes_per_iter"] = gauge["value"]
        return out

    def work(self, stats) -> Work:
        return work(self.op, stats["iterations"])


def build(config, traffic, seed, devices, **kw):
    return Cell(config, traffic, seed, devices, **kw)
