"""``--scaling-sweep`` — the paper's speedup-vs-cores tables, as
speedup-vs-mesh-shapes.

The paper's headline artefact is one program text re-run under O2 and O3
with ``ARBB_NUM_CORES`` sweeping the core count (Figs. 1-7: speedup columns
per thread count).  This module replays that for the mesh ladder — and,
past PR 2's device-count sweep, for mesh *shapes*: each of the four paper
kernels (mod2am matmul, mod2as SpMV, mod2f FFT, §3.4 CG) is timed at

    O2      1 device, the chip baseline
    8x1     (data=8, model=1)        — the flat O3 mesh
    4x2     (data=4, model=2)        — O3 with a real model axis: mod2am
                                       retargets to the 2-D (data, model)
                                       ``mesh_psum_2d`` tiling
    2x2x2   (pod=2, data=2, model=2) — O4: hierarchical reduction plans
                                       (reduce-scatter intra-pod,
                                       all-reduce inter-pod)

under ``use_level`` — the registry's scope dimension and the collectives
plane retarget every call, the program text never changing.

On the CPU container the fake host-platform devices share the same silicon,
so absolute speedups are not the claim (exactly as the paper's GFlop/s were
Westmere-specific); the artefact is the *trajectory*: per-mesh-shape
timings, the variant each shape selected, and the axis roles, persisted via
``--json-out`` so scaling regressions show up across PRs.

    PYTHONPATH=src python -m benchmarks.run --scaling-sweep
    PYTHONPATH=src python -m benchmarks.run --scaling-sweep --json-out s.json
"""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np

from benchmarks.common import print_table, time_fn

#: mesh shapes swept: label -> ((axis, size), ...); None = the O2 chip
#: baseline.  Shapes needing more devices than the platform has are skipped.
MESH_SHAPES = (
    ("O2", None),
    ("8x1", (("data", 8), ("model", 1))),
    ("4x2", (("data", 4), ("model", 2))),
    ("2x2x2", (("pod", 2), ("data", 2), ("model", 2))),
)


def _problems():
    """kernel name -> (timed_fn(), selected_variant_fn, sparse_format) on
    fixed inputs sized so every MESH_SHAPES entry divides them.
    ``sparse_format`` is the storage format of the sparse operand ('-' for
    the dense kernels) — recorded per row so ``--json-out`` trajectories
    show which format the statistics selected (DESIGN.md §9)."""
    import jax.numpy as jnp

    import repro.core as C
    from repro.core import registry
    from repro import sparse as S
    from repro.kernels import ops
    from repro.numerics import solvers, sparse

    rng = np.random.default_rng(42)
    problems = {}

    n = 256
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    problems["mod2am"] = (lambda: ops.matmul(a, b),
                          lambda: registry.select("matmul", a, b).name, "-")

    spd = sparse.banded_spd(2048, 31, seed=1)
    ell = sparse.ell_from_csr(sparse.csr_from_dense(spd))
    x = C.bind(rng.standard_normal(2048).astype(np.float32))
    problems["mod2as"] = (
        lambda: registry.dispatch("solver_spmv", ell, x),
        lambda: registry.select("solver_spmv", ell, x).name,
        S.format_of(ell))

    z = jnp.asarray(rng.standard_normal(4096) + 1j * rng.standard_normal(4096),
                    jnp.complex64)
    problems["mod2f"] = (lambda: ops.fft(z),
                         lambda: registry.select("fft", z).name, "-")

    cg_a = sparse.dia_from_dense(sparse.banded_spd(1024, 31, seed=2))
    cg_bv = C.unwrap(C.bind(rng.standard_normal(1024).astype(np.float32)))
    # cg_jit (the call() closure) so chip and mesh both time a cached
    # compiled solve, not per-call retracing
    problems["cg"] = (
        lambda: solvers.cg_jit(cg_a, cg_bv, 1e-10, 2048, None)[0],
        lambda: solvers._selected_spmv(cg_a, cg_bv, None).name,
        S.format_of(cg_a))

    sp_m = S.matrix(sparse.banded_spd(2048, 31, seed=3).astype(np.float32))
    sp_x = C.bind(rng.standard_normal((2048, 8)).astype(np.float32))
    problems["spmm"] = (
        lambda: S.spmm(sp_m, sp_x),
        lambda: registry.select("spmm", sp_m, sp_x).name,
        S.format_of(sp_m))

    # SpGEMM (DESIGN.md §15): clustered BSR × BSR, n = 1024 so the 128
    # block-rows divide every swept row partition (8 / 4 / 4); the mesh
    # shapes retarget to the Cannon-style pair-partitioned variant
    gn, gbs = 1024, 8
    gnb = gn // gbs
    gocc = rng.random((gnb, gnb)) < 0.08
    gd = rng.standard_normal((gn, gn)).astype(np.float32)
    gA = np.where(np.kron(gocc, np.ones((gbs, gbs), bool)), gd, 0.0) \
        .astype(np.float32)
    ga = S.bsr_from_dense(gA, block=gbs)
    problems["spgemm"] = (
        lambda: S.spgemm(ga, ga),
        lambda: registry.select("spgemm", ga, ga).name,
        "bsr")

    # causal GQA attention: L = 256 splits into 2*ring half-blocks on every
    # swept shape (ring = 8 / 4 / 4), so the sequence-parallel ring variant
    # (DESIGN.md §10) selects wherever a mesh is ambient
    qa = jnp.asarray(rng.standard_normal((2, 4, 256, 64)), jnp.float32)
    ka = jnp.asarray(rng.standard_normal((2, 2, 256, 64)), jnp.float32)
    va = jnp.asarray(rng.standard_normal((2, 2, 256, 64)), jnp.float32)
    problems["attention"] = (
        lambda: ops.flash_attention(qa, ka, va, causal=True),
        lambda: registry.select("flash_attention", qa, ka, va,
                                causal=True).name,
        "-")

    return problems


def _roles_label(mesh) -> str:
    from repro.core import topology_of

    topo = topology_of(mesh)
    if topo is None:
        return "-"
    # ';' separator: the table prints as CSV, so the field must stay atomic
    return ";".join(f"{n}={r}" for n, r in zip(topo.axis_names, topo.roles))


def _ring_label(mesh) -> int:
    from repro.distributed.collectives import ring_plan

    return ring_plan(mesh).size if mesh is not None else 1


def main(mesh_shapes: Iterable = MESH_SHAPES,
         only: Optional[str] = None) -> list[dict]:
    import jax

    from repro.core import ExecLevel, use_level

    avail = jax.device_count()
    shapes = [(label, spec) for label, spec in mesh_shapes
              if spec is None or int(np.prod([s for _, s in spec])) <= avail]
    dropped = [label for label, spec in mesh_shapes
               if (label, spec) not in shapes]
    if dropped:
        print(f"scaling sweep: only {avail} device(s) visible; "
              f"skipping shapes {dropped} (run via benchmarks.run, which "
              f"forces 8 host-platform devices before jax init)")

    problems = _problems()
    if only:
        problems = {k: v for k, v in problems.items() if k == only}

    rows: list[dict] = []
    base: dict[str, float] = {}
    for label, spec in shapes:
        if spec is None:
            ctx = use_level(ExecLevel.O2)          # the chip baseline
            mesh, devices = None, 1
        else:
            axes = tuple(a for a, _ in spec)
            sizes = tuple(s for _, s in spec)
            devices = int(np.prod(sizes))
            mesh = jax.make_mesh(sizes, axes,
                                 (jax.sharding.AxisType.Auto,) * len(axes),
                                 devices=jax.devices()[:devices])
            level = ExecLevel.O4 if "pod" in axes else ExecLevel.O3
            ctx = use_level(level, mesh)
        with ctx:
            ring = _ring_label(mesh)
            for kernel, (fn, selected, fmt) in problems.items():
                t = time_fn(lambda: fn(), warmup=1, iters=3)
                base.setdefault(kernel, t)
                rows.append({
                    "kernel": kernel, "devices": devices, "mesh": label,
                    "roles": _roles_label(mesh), "sparse_format": fmt,
                    # the sequence-ring width the attention problem shards
                    # over on this shape ('-' for the non-attention kernels)
                    "ring": ring if kernel == "attention" else "-",
                    "variant": selected(), "seconds": round(t, 6),
                    "speedup": round(base[kernel] / t, 3),
                })
    print_table("scaling sweep (speedup vs mesh shape; paper's "
                "ARBB_NUM_CORES tables, O2 -> O3 -> O4 meshes)", rows,
                ["kernel", "devices", "mesh", "roles", "variant",
                 "sparse_format", "ring", "seconds", "speedup"])
    return rows


if __name__ == "__main__":
    main()
