"""SpGEMM suite — two-phase BSR×BSR across densities, chip vs mesh.

Beyond the paper: mod2am stops at dense matmul and the blocked-sparse
plane at SpMM (sparse × dense panel).  The sparse-output workload is
SpGEMM — sparse × sparse with the product's pattern unknown until the
symbolic phase runs (DESIGN.md §15).  This suite times ``sparse.spgemm``
on the two block-structured classes the format selector routes to BSR
(clustered blocks, banded) over a density sweep, at O2 (chip: the
Gustavson pair kernel) and — when enough devices are visible — under the
8x1 and 2x2x2 meshes, where the Cannon-style ``mesh_spgemm`` variant
partitions the pair list and returns the product block-row-sharded.

GFLOP/s uses the *Gustavson* flop count — ``2 · npairs · bs³``, the block
products the symbolic phase scheduled — not the dense ``2n³``, so the
number reports useful work and chip/mesh rows divide through the same
denominator (speedup column = chip seconds / mesh seconds per case).

    PYTHONPATH=src python -m benchmarks.run --only spgemm
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only spgemm --json-out o.json
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import print_table, time_fn

N = 2048
BLOCK = 8

#: (pattern label, density knob values) — clustered sweeps block fill
#: fraction, banded sweeps bandwidth.
CLUSTERED_FRACS = (0.02, 0.08, 0.2)
BANDED_BWS = (31, 127)

#: mesh shapes the mesh variant is timed under (skipped when the platform
#: has fewer devices; benchmarks.run forces 8 for the sweep modes only).
MESH_SHAPES = (
    ("8x1", (("data", 8), ("model", 1))),
    ("2x2x2", (("pod", 2), ("data", 2), ("model", 2))),
)


def _clustered(n, frac, seed):
    rng = np.random.default_rng(seed)
    nb = n // BLOCK
    occ = rng.random((nb, nb)) < frac
    d = rng.standard_normal((n, n)).astype(np.float32)
    return np.where(np.kron(occ, np.ones((BLOCK, BLOCK), bool)), d, 0.0) \
        .astype(np.float32)


def _banded(n, bw, seed):
    from repro.numerics.sparse import banded_spd
    return banded_spd(n, bw, seed=seed).astype(np.float32)


def _cases(n):
    for frac in CLUSTERED_FRACS:
        yield (f"clustered_f{frac}", _clustered(n, frac, 1),
               _clustered(n, frac, 2))
    for bw in BANDED_BWS:
        yield (f"banded_bw{bw}", _banded(n, bw, 3), _banded(n, bw, 4))


def run(full: bool = False) -> list[dict]:
    import jax

    from repro import sparse as S
    from repro.core import ExecLevel, registry, use_level
    from repro.sparse.spgemm import spgemm_symbolic

    n = N if full else N // 2
    avail = jax.device_count()
    shapes = [(label, spec) for label, spec in MESH_SHAPES
              if int(np.prod([s for _, s in spec])) <= avail]
    if len(shapes) < len(MESH_SHAPES):
        print(f"spgemm: only {avail} device(s) visible; mesh rows limited "
              f"(set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              f"before jax init for the chip-vs-mesh comparison)")

    rows: list[dict] = []
    for case, A, B in _cases(n):
        a, b = S.bsr_from_dense(A, block=BLOCK), S.bsr_from_dense(B,
                                                                  block=BLOCK)
        sym = spgemm_symbolic(a, b)
        flops = 2.0 * sym.npairs * BLOCK ** 3       # Gustavson, not dense
        density = a.nblocks / (n // BLOCK) ** 2

        ref = A @ B
        scale = max(1.0, float(np.abs(ref).max()))   # relative error: banded
        # products reach O(100) magnitudes under f32 accumulation

        # chip baseline: O2, whatever the registry ranks first on this plane
        with use_level(ExecLevel.O2):
            variant = registry.select("spgemm", a, b).name
            C = S.spgemm(a, b)
            err = float(np.abs(C.todense() - ref).max()) / scale
            t_chip = time_fn(lambda: S.spgemm(a, b), warmup=1, iters=3)
        rows.append({"kernel": "spgemm", "case": case, "mesh": "O2",
                     "devices": 1, "variant": variant,
                     "n": n, "density": round(density, 4),
                     "npairs": sym.npairs, "nnzb_out": sym.nc,
                     "max_err": f"{err:.1e}", "seconds": round(t_chip, 6),
                     "gflops": round(flops / t_chip / 1e9, 4),
                     "speedup_vs_chip": 1.0})

        for label, spec in shapes:
            axes = tuple(x for x, _ in spec)
            sizes = tuple(s for _, s in spec)
            devices = int(np.prod(sizes))
            mesh = jax.make_mesh(sizes, axes,
                                 (jax.sharding.AxisType.Auto,) * len(axes),
                                 devices=jax.devices()[:devices])
            level = ExecLevel.O4 if "pod" in axes else ExecLevel.O3
            with use_level(level, mesh):
                variant = registry.select("spgemm", a, b).name
                C = S.spgemm(a, b)
                err = float(np.abs(C.todense() - ref).max()) / scale
                sharded = C.out_sharding is not None \
                    and C.values.sharding == C.out_sharding
                t = time_fn(lambda: S.spgemm(a, b), warmup=1, iters=3)
            rows.append({"kernel": "spgemm", "case": case, "mesh": label,
                         "devices": devices, "variant": variant,
                         "n": n, "density": round(density, 4),
                         "npairs": sym.npairs, "nnzb_out": sym.nc,
                         "max_err": f"{err:.1e}", "seconds": round(t, 6),
                         "gflops": round(flops / t / 1e9, 4),
                         "speedup_vs_chip": round(t_chip / t, 3),
                         "out_sharded": sharded})
    return rows


def validate(rows: list[dict]) -> dict:
    mesh_rows = [r for r in rows if r["mesh"] != "O2"]
    best = {}
    for r in mesh_rows:
        if r["devices"] >= 4:
            best[r["case"]] = max(best.get(r["case"], 0.0),
                                  r["speedup_vs_chip"])
    checks = {
        "spgemm_matches_oracle": all(float(r["max_err"]) < 1e-3
                                     for r in rows),
        "mesh_variant_selected": all(r["variant"] == "mesh_spgemm"
                                     for r in mesh_rows),
        "mesh_product_sharded": all(r.get("out_sharded") for r in mesh_rows),
        # the perf claim: on the block-structured classes, some ≥4-device
        # shape beats the chip baseline (vacuously true when no mesh rows
        # ran — the single-device CI leg)
        "mesh_beats_chip_at_4plus": (not best
                                     or any(s > 1.0 for s in best.values())),
    }
    return {"best_mesh_speedup": best, "checks": checks}


def main(full: bool = False):
    rows = run(full)
    print_table("spgemm (two-phase BSR×BSR: chip Gustavson vs Cannon-style "
                "mesh, Gustavson GFLOP/s)", rows,
                ["kernel", "case", "mesh", "devices", "variant", "n",
                 "density", "npairs", "nnzb_out", "max_err", "seconds",
                 "gflops", "speedup_vs_chip", "out_sharded"])
    print("validation:", validate(rows)["checks"])
    return rows


if __name__ == "__main__":
    main()
