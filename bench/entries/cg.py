"""Conjugate gradients through ``repro.numerics.solvers.cg_solve``, jitted,
on the configuration's operator, from x0 = 0 to a tolerance on the
recursive residual.

Work of one solve, from shapes: per iteration, the least HBM traffic of
textbook CG on the chip (``bench.peaks`` says which vectors it holds):

- the stored diagonals, ndiags * 4 B per row, read once by the SpMV;
- 9 accesses of 4 B per row to the n-vectors: x, p and Ap are each
  written once and read once; r is written once and read twice, as its new
  value gates b through r.r and then feeds p = r + b p.  The SpMV's p and
  Ap are among them: x += a p with p = r + b p can ride in the next SpMV,
  which streams p anyway;
- less the accesses of the vectors the chip keeps in VMEM, most accessed
  first: on a v5e, 3/4 of 128 MiB holds one 64 MiB vector of 256^3 rows,
  so r's 3 accesses go;
- 2 FLOPs per stored non-zero plus 10 per row (two dots, three axpys).

So 64 B/row from HBM for the 7-point operator, 52 on a v5e.

The right-hand sides are 1 + seeded N(0, 1), ``traffic["pool"]`` of them,
cycled through by the window.  The constant's large smooth part fixes how
many iterations a solve takes, so every seed gets the same work; with
white noise alone one right-hand side in several takes 10-20 % more or
fewer iterations than the rest.

The check compares each sampled solve's x with the plain textbook CG
(f32, the same b and tolerance) from ``generators``:
max|x - x_ref| / max|x_ref|.  Not the residual |b - A x| / |b|: for this
b, x is about 10^4 times larger than b, so f32's rounding of x alone puts
that ratio near 4e-3, and a solve stopped far short of its tolerance
reads the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import ROOT, Work, load_module

#: accesses of each n-vector in one iteration of textbook CG, at the least
VECTOR_ACCESSES = {"r": 3, "x": 2, "p": 2, "Ap": 2}
#: FLOPs per row of the vector work of one CG iteration
VECTOR_FLOPS_PER_ROW = 10


def program(offsets, n: int, rtol: float, max_iters: int):
    """The timed path: ``cg_solve`` on a DIA operator, stopping at
    ``rtol * |b|`` (recursive residual) or ``max_iters``; returns
    (x, iterations).  Jit it and call it under :func:`level`."""
    def solve(diags, b):
        from repro.core import unwrap
        from repro.numerics.solvers import cg_solve
        from repro.numerics.sparse import DIA

        a = DIA(diags=diags, offsets=tuple(offsets), shape=(n, n))
        stop = (rtol * rtol) * jnp.vdot(b, b)
        res = cg_solve(a, b, stop=stop, max_iters=max_iters)
        return unwrap(res.x), res.iterations
    return solve


def level():
    """One chip, the registry's default selection."""
    from repro.core import ExecLevel, use_level

    return use_level(ExecLevel.O2)


def iterations_work(rows: int, nnz: float, ndiags: int,
                    iterations: float) -> Work:
    """Work of ``iterations`` CG iterations over ``rows`` rows of a DIA
    operator of ``ndiags`` diagonals holding ``nnz`` non-zeros."""
    vector = 4 * rows
    accesses = VECTOR_ACCESSES.values()
    return Work(
        flops=iterations * (2 * nnz + VECTOR_FLOPS_PER_ROW * rows),
        hbm_bytes=iterations * (ndiags + sum(accesses)) * vector,
        vectors=tuple((vector, iterations * k) for k in accesses))


def work(op, iterations: float) -> Work:
    """Work of one solve of ``iterations``."""
    return iterations_work(op.n, op.nnz, len(op.offsets), iterations)


def x_err(x, x_ref):
    """max|x - x_ref| / max|x_ref|."""
    return jnp.max(jnp.abs(x - x_ref)) / jnp.max(jnp.abs(x_ref))


class Cell:
    def __init__(self, config, traffic, seed, devices, *, root=ROOT,
                 control=False):
        self.gen = gen = load_module(root, "generators", config["generator"])
        self.op = op = gen.Operator(config, seed, devices)
        self.traffic = traffic
        self.control = control
        rtol, max_iters = float(traffic["rtol"]), int(traffic["max_iters"])
        self.bs = [1.0 + v for v in op.vectors(seed, int(traffic["pool"]))]
        self.reference = jax.jit(functools.partial(
            gen.cg, spmv=op.spmv, rtol=rtol, max_iters=max_iters))
        if control:
            self.program = jax.jit(functools.partial(
                gen.cg, spmv=op.spmv, rtol=rtol, max_iters=max_iters,
                dtype=gen.DTYPES[traffic["control_dtype"]]))
        else:
            self.program = jax.jit(program(op.offsets, op.n, rtol,
                                           max_iters))
        jax.block_until_ready((op.diags, self.bs))

    def _solve(self, b):
        if self.control:
            x, k = self.program(self.op.diags, b=b)
            return x.astype(jnp.float32), k
        with level():
            return self.program(self.op.diags, b)

    def call(self, i):
        return self._solve(self.bs[i % len(self.bs)])

    def warm(self):
        """One call of the timed program on b = 0, which stops before its
        first iteration: the same program, compiled or loaded, at no cost
        of a solve."""
        from repro.obs import metrics

        metrics.METRICS.reset("dispatch.")
        jax.block_until_ready(self._solve(jnp.zeros_like(self.bs[0])))

    def variants(self) -> dict:
        from repro.core import registry, wrap
        from repro.numerics.sparse import DIA
        from repro.obs import metrics

        ran = {k: v["value"] for k, v in
               metrics.METRICS.snapshot("dispatch.").items()}
        a = DIA(diags=self.op.diags, offsets=self.op.offsets,
                shape=(self.op.n, self.op.n))
        with level():
            selected = registry.select("solver_spmv", a,
                                       wrap(self.bs[0])).name
        want = self.traffic.get("expect", {})
        if not self.control:
            if "solver_spmv" in want and selected != want["solver_spmv"]:
                raise AssertionError(f"solver_spmv selected {selected!r}, "
                                     f"expected {want['solver_spmv']!r}")
            for op, variant in want.items():
                names = {k for k in ran if k.startswith(f"dispatch.{op}.")}
                if names != {f"dispatch.{op}.{variant}"}:
                    raise AssertionError(f"{op}: expected {variant}, ran "
                                         f"{ran}")
        return {"counters": ran, "solver_spmv": selected}

    @staticmethod
    def stat_of(out):
        return out[1]

    @staticmethod
    def answer(out):
        return out[0]

    def stats(self, outs) -> dict:
        iters = [int(k) for k in outs]
        return {"iterations": float(np.mean(iters)),
                "iterations_each": iters, "spmv_bytes": self.op.spmv_bytes}

    def work(self, stats) -> Work:
        return work(self.op, stats["iterations"])

    def free_program(self):
        self.program = None

    def check(self, kept) -> dict:
        refs, errs = {}, []
        for i, x in kept:
            j = i % len(self.bs)
            if j not in refs:
                refs[j] = self.reference(self.op.diags, b=self.bs[j])[0]
            errs.append(float(x_err(x, refs[j])))
        return {"x_err": errs}


def build(config, traffic, seed, devices, **kw):
    return Cell(config, traffic, seed, devices, **kw)
