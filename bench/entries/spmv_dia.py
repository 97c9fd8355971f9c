"""The paper's mod2as mat-vec: ``repro.kernels.ops.spmv_dia`` through the
registry, one call per output, on the configuration's operator.

Work of one call, from shapes: (ndiags + 2) * 4 B per row (every stored
diagonal entry, x and y) and 2 FLOPs per stored non-zero.  The check
compares every sampled output with the plain shifted-FMA SpMV in f32:
max|y - y_ref| / max|y_ref|.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import ROOT, Work, load_module


def work(op) -> Work:
    """Work of one SpMV of ``op``."""
    return Work(flops=2 * op.nnz, hbm_bytes=op.spmv_bytes)


class Cell:
    def __init__(self, config, traffic, seed, devices, *, root=ROOT,
                 control=False):
        self.gen = load_module(root, "generators", config["generator"])
        self.op = self.gen.Operator(config, seed, devices)
        self.traffic = traffic
        self.control = control
        self.xs = self.op.vectors(seed, int(traffic["pool"]))
        self.reference = jax.jit(self.op.spmv)
        jax.block_until_ready((self.op.diags, self.xs))
        if control:
            low = self.gen.DTYPES[traffic["control_dtype"]]
            self.low = jax.jit(lambda d, x: self.op.spmv(
                d.astype(low), x.astype(low)).astype(jnp.float32))
        else:
            from repro.kernels import ops
            self.program = ops.spmv_dia

    def call(self, i):
        x = self.xs[i % len(self.xs)]
        if self.control:
            return self.low(self.op.diags, x)
        return self.program(self.op.diags, self.op.offsets, x)

    def warm(self):
        from repro.obs import metrics

        metrics.METRICS.reset("dispatch.")
        jax.block_until_ready(self.call(0))

    def variants(self) -> dict:
        from repro.obs import metrics

        ran = {k: v["value"] for k, v in
               metrics.METRICS.snapshot("dispatch.").items()}
        want = self.traffic.get("expect", {})
        if not self.control:
            for op, variant in want.items():
                if set(k for k in ran if k.startswith(f"dispatch.{op}.")) \
                        != {f"dispatch.{op}.{variant}"}:
                    raise AssertionError(f"{op}: expected {variant}, ran "
                                         f"{ran}")
        return ran

    @staticmethod
    def stat_of(out):
        return None

    @staticmethod
    def answer(out):
        return out

    def stats(self, outs) -> dict:
        return {"spmv_bytes": self.op.spmv_bytes}

    def work(self, stats) -> Work:
        return work(self.op)

    def free_program(self):
        self.program = None

    def check(self, kept) -> dict:
        errs = []
        for i, y in kept:
            want = self.reference(self.op.diags, self.xs[i % len(self.xs)])
            errs.append(float(jnp.max(jnp.abs(y - want))
                              / jnp.max(jnp.abs(want))))
        return {"rel_err": errs}


def build(config, traffic, seed, devices, **kw):
    return Cell(config, traffic, seed, devices, **kw)
