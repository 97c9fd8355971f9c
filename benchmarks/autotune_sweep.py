"""``--autotune-sweep`` — the offline calibration sweep behind the measured
cost model (DESIGN.md §11).

The paper's Figs. 1-7 are measured GFLOP/s per kernel per runtime; this
sweep produces the same table for our own dispatch plane and *feeds it
back*: for each mesh shape (O2 chip baseline, 8x1, 4x2, 2x2x2) and each op
(matmul, solver_spmv, spmm, fft, flash_attention) it times **every
admissible registered variant end-to-end through ``registry.dispatch``** —
shard_map and collective overhead included, exactly what a caller pays —
and writes the measurements into

  * the cost model (``results/costmodel.json``): measured seconds, derived
    GFLOP/s, and the roofline-predicted seconds per variant, keyed
    ``op|signature|dtype|scope|mesh`` — what :meth:`OperatorRegistry.select`
    consults before the static ``cost=`` priors, and
  * the block autotune cache: mesh-scoped dispatches resolve their block
    sizes under shard_map *tracing*, where measurement is impossible — the
    resolve default-marks those entries, and this sweep's eager
    ``premeasure`` pass re-synthesises arrays of the recorded per-shard
    dims and measures the candidates for real (the "measurement skipped
    under a trace" hole, closed).

Interpret-plane variants are skipped by default: the interpret plane is the
test harness, never auto-selected, and measuring it would only slow the
sweep (``include_interpret=True`` reinstates them).

    REPRO_AUTOTUNE=1 PYTHONPATH=src python -m benchmarks.run --autotune-sweep
    ... --autotune-sweep --tiny --json-out bench.json      # CI smoke sizes
"""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np

from benchmarks.common import print_table, time_fn
from benchmarks.scaling_sweep import MESH_SHAPES


def _cases(tiny: bool) -> dict[str, list[tuple]]:
    """op -> [(case label, args, kwargs, flops)], sized so every MESH_SHAPES
    entry divides them (tiny: CI smoke sizes)."""
    import jax.numpy as jnp

    import repro.core as C
    from repro import sparse as S
    from repro.numerics import sparse

    rng = np.random.default_rng(42)
    cases: dict[str, list[tuple]] = {}

    n = 64 if tiny else 256
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    cases["matmul"] = [(f"{n}x{n}", (a, b), {}, 2.0 * n ** 3)]

    sn, bw = (512, 15) if tiny else (2048, 31)
    spd = sparse.banded_spd(sn, bw, seed=1)
    csr = sparse.csr_from_dense(spd)
    ell = sparse.ell_from_csr(csr)
    x = C.bind(rng.standard_normal(sn).astype(np.float32))
    nnz = float(np.count_nonzero(spd))
    cases["solver_spmv"] = [
        (f"ell_n{sn}bw{bw}", (ell, x), {}, 2.0 * nnz),
        # the CSR pair is the paper's own measured ranking (spmv2's
        # contiguity rewrite vs the naive spmv1 port) landing in the model
        (f"csr_n{sn}bw{bw}", (csr, x), {}, 2.0 * nnz),
    ]

    sp_m = S.matrix(spd.astype(np.float32))
    k = 8
    sp_x = C.bind(rng.standard_normal((sn, k)).astype(np.float32))
    cases["spmm"] = [(f"{S.format_of(sp_m)}_n{sn}k{k}", (sp_m, sp_x), {},
                      2.0 * nnz * k)]

    # SpGEMM (DESIGN.md §15): BSR×BSR clustered blocks.  FLOPs are the
    # Gustavson count (2·npairs·bs³) from the symbolic phase — the BSR
    # ``cost_dims()`` fingerprint (block, nnzb) keys the calibration per
    # density, so the measured chip↔mesh crossover is density-specific.
    from repro.sparse.spgemm import spgemm_symbolic
    gn, bs = (256, 8) if tiny else (1024, 8)
    gnb = gn // bs
    gocc = rng.random((gnb, gnb)) < 0.08
    gd = rng.standard_normal((gn, gn)).astype(np.float32)
    gA = np.where(np.kron(gocc, np.ones((bs, bs), bool)), gd, 0.0) \
        .astype(np.float32)
    gB = np.where(np.kron(gocc.T, np.ones((bs, bs), bool)), gd.T, 0.0) \
        .astype(np.float32)
    ga, gb = S.bsr_from_dense(gA, block=bs), S.bsr_from_dense(gB, block=bs)
    gsym = spgemm_symbolic(ga, gb)
    cases["spgemm"] = [(f"bsr_n{gn}b{bs}", (ga, gb), {},
                        2.0 * gsym.npairs * bs ** 3)]

    fn = 1024 if tiny else 4096
    z = jnp.asarray(rng.standard_normal(fn) + 1j * rng.standard_normal(fn),
                    jnp.complex64)
    cases["fft"] = [(f"n{fn}", (z,), {},
                     5.0 * fn * int(np.log2(fn)))]

    bq, hq, hkv, lq, d = (1, 2, 2, 128, 32) if tiny else (2, 4, 2, 256, 64)
    q = jnp.asarray(rng.standard_normal((bq, hq, lq, d)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((bq, hkv, lq, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bq, hkv, lq, d)), jnp.float32)
    cases["flash_attention"] = [(f"b{bq}h{hq}l{lq}d{d}", (q, kk, v),
                                 {"causal": True},
                                 4.0 * bq * hq * lq * lq * d)]

    # masked cases: the MaskSpec's cost_dims() fingerprint keys these
    # separately from the plain-causal case, so the dense <-> block-sparse
    # crossover calibrates per mask structure (DESIGN.md §12).  FLOPs are
    # the mask's useful work (dense flops x fill), making the per-variant
    # GFLOP/s comparable: a dense kernel burning the masked-out work shows
    # a proportionally worse roofline position.
    from repro.sparse.maskcompiler import MaskSpec, dense_mask
    win = MaskSpec(causal=True, window=lq // 4)
    nt = lq // 16
    pat = (np.random.default_rng(7).random((nt, nt)) < 0.15) \
        | np.eye(nt, dtype=bool)
    blk = MaskSpec.from_block_mask(pat, 16)
    for tag, spec in (("win", win), ("blk", blk)):
        fill = float(dense_mask(spec, lq, lq).mean())
        cases["flash_attention"].append(
            (f"b{bq}h{hq}l{lq}d{d}_{tag}", (q, kk, v),
             {"causal": True, "mask": spec},
             4.0 * bq * hq * lq * lq * d * fill))
    return cases


# ---------------------------------------------------------------------------
# eager premeasure: upgrade the default-marked block entries a traced
# shard_map dispatch left behind (per-shard dims recorded at trace time)
# ---------------------------------------------------------------------------

def _synthesize(op: str, dims: dict, dtype: str):
    """Concrete arrays of the recorded dims for a blocked() op, or None."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    if op == "matmul":
        return (jnp.asarray(rng.standard_normal((dims["m"], dims["k"])),
                            dtype),
                jnp.asarray(rng.standard_normal((dims["k"], dims["n"])),
                            dtype))
    if op == "spmm_ell":
        rows, width = dims["rows"], dims["width"]
        vals = jnp.asarray(rng.standard_normal((rows, width)), dtype)
        cols = jnp.asarray(rng.integers(0, rows, (rows, width)), jnp.int32)
        xv = jnp.asarray(rng.standard_normal((rows, dims["rhs"])), dtype)
        return (vals, cols, xv)
    return None


def _premeasure_pending(interpret: bool) -> list[dict]:
    """Walk the block cache's default-marked entries for the *ambient*
    scope/mesh and measure them eagerly with synthesised arrays of the
    recorded dims.  Must run inside the same ``use_level`` context that
    traced them (the ambient scope is part of the key)."""
    from repro.core import blocking

    cache = blocking.get_cache()
    scope, mesh = blocking.ambient_scope_key()
    rows = []
    for key in cache.pending_defaults():
        op, dims, dtype, kscope, kmesh = blocking.AutotuneCache.parse_key(key)
        if (kscope, kmesh) != (scope, mesh) or op not in blocking.PREMEASURE:
            continue
        args = _synthesize(op, dims, dtype)
        if args is None:
            continue
        blocks = blocking.premeasure(op, *args, interpret=interpret)
        entry = cache.entry(key) or {}
        rows.append({"op": op, "case": f"premeasure:{key}", "mesh": mesh,
                     "scope": scope, "variant": "-", "plane": "-",
                     "seconds": entry.get("_seconds", ""),
                     "gflops": "", "predicted": "",
                     "note": f"blocks upgraded to {blocks}"})
    return rows


def main(mesh_shapes: Iterable = MESH_SHAPES, only: Optional[str] = None,
         tiny: bool = False, include_interpret: bool = False) -> list[dict]:
    import jax

    from repro.core import ExecLevel, costmodel, registry, use_level
    from repro.core import blocking

    avail = jax.device_count()
    shapes = [(label, spec) for label, spec in mesh_shapes
              if spec is None or int(np.prod([s for _, s in spec])) <= avail]
    dropped = [label for label, _ in mesh_shapes
               if label not in {l for l, _ in shapes}]
    if dropped:
        print(f"autotune sweep: only {avail} device(s) visible; skipping "
              f"shapes {dropped} (run via benchmarks.run, which forces 8 "
              f"host-platform devices before jax init)")
    if not blocking.autotune_enabled():
        print("autotune sweep: REPRO_AUTOTUNE is not set — the cost model "
              "still calibrates, but block-cache entries are not written")

    model = costmodel.get_model()
    cases = _cases(tiny)
    if only:
        cases = {k: v for k, v in cases.items() if k == only}
    # the kernel plane whose blocks are tuned is the registry's, never a
    # silent substitute: 'pallas' on TPU, 'interpret' only when requested
    kernel_plane = registry.resolve_backend()

    rows: list[dict] = []
    for label, spec in shapes:
        if spec is None:
            ctx_mgr = use_level(ExecLevel.O2)
        else:
            axes = tuple(a for a, _ in spec)
            sizes = tuple(s for _, s in spec)
            mesh = jax.make_mesh(sizes, axes,
                                 (jax.sharding.AxisType.Auto,) * len(axes),
                                 devices=jax.devices()[:int(np.prod(sizes))])
            level = ExecLevel.O4 if "pod" in axes else ExecLevel.O3
            ctx_mgr = use_level(level, mesh)
        with ctx_mgr:
            ctx = registry.select_context()
            scope, mesh_desc = blocking.ambient_scope_key()
            for op, op_cases in cases.items():
                for case_label, args, kwargs, flops in op_cases:
                    for v in registry.variants(op):
                        if v.plane == "interpret" and not include_interpret:
                            continue
                        if not (v.is_available(ctx)
                                and v.matches(*args, **kwargs)):
                            continue
                        t = time_fn(lambda: registry.dispatch(
                            op, *args, variant=v.name, **kwargs),
                            warmup=1, iters=3)
                        rec = model.record(
                            op, v.name, seconds=t, args=args, kwargs=kwargs,
                            scope=scope, mesh=mesh_desc, flops=flops,
                            bytes_moved=costmodel.arg_bytes(args))
                        rows.append({
                            "op": op, "case": case_label, "mesh": label,
                            "scope": scope, "variant": v.name,
                            "plane": v.plane or "-",
                            "seconds": round(t, 6),
                            "gflops": rec.get("gflops", ""),
                            "predicted": rec.get("predicted_seconds", ""),
                            "note": ""})
            if spec is not None and blocking.autotune_enabled() \
                    and kernel_plane != "xla" and "matmul" in cases:
                # drive the blocked chip kernel through the mesh variant
                # once so the traced per-shard resolve default-marks its
                # mesh-scoped key, then upgrade all pending entries eagerly
                # — the §11 hole-fix, end to end
                (_, (ma, mb), _, _) = cases["matmul"][0]
                with registry.use_backend(kernel_plane):
                    try:
                        registry.dispatch("matmul", ma, mb,
                                          variant="mesh_psum")
                    except Exception as e:
                        print(f"autotune sweep: mesh_psum {kernel_plane} "
                              f"trace skipped ({type(e).__name__}: {e})")
                rows.extend(
                    _premeasure_pending(interpret=kernel_plane != "pallas"))

    print_table("autotune sweep (whole-dispatched-call seconds per variant "
                "per mesh shape -> results/costmodel.json)", rows,
                ["op", "case", "mesh", "scope", "variant", "plane",
                 "seconds", "gflops", "predicted", "note"])
    print(f"cost model: {model.path} ({len(model)} keys)")
    return rows


if __name__ == "__main__":
    main()
