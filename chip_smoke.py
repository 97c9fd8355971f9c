#!/usr/bin/env python3
"""On-chip smoke test: the library's main path on a TPU, through the entry
points a user calls, at sizes a user would call real.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # the O3 mesh variants on a (4, 1)
                                      # data x model mesh vs one chip

One-chip phases (each checks an independent reference and asserts which
registry variant ran, from the ``dispatch.{op}.{variant}`` counters):

    mod2am  ops.matmul, 8192^2 bf16 (f32 accumulation) and 4096^2 f32
    mod2as  ops.spmv_ell and ops.spmv_dia, 7-point operator on 128^3 rows;
            ops.spmv_ell on the paper's Table-1 random input (n 2000, 7.5 %)
    mod2f   ops.fft, complex64, n = 2^22
    cg      numerics.solvers.cg_solve on that operator, DIA backend
    serve   serve.ContinuousEngine, qwen3-1.7b at published widths, bf16,
            seeded weights: 8 slots, chunk 128, 16 requests of 128-512
            prompt tokens and 32 new tokens each; every served token is
            teacher-forced through the XLA-plane forward of its prefix

Exits non-zero, without the final line, when JAX finds no TPU or any phase
fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Per-phase
records also go to ``chiprun_out/chip_smoke.json``.  All data comes from
``--seed``.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: bf16 serve checks (qwen3-1.7b, 28 layers, seeded weights), each set
#: between the sound runs' readings and the planted faults' on a v5e
#: (PERF.md §2).  The largest |logits(Pallas) - logits(XLA)| of one
#: forward: sound 0.0625, one future key leaking into causal attention 6.05.
SERVE_LOGIT_ATOL = 0.25
#: The largest teacher-forced gap (row max minus the served token's
#: logit): sound 0.031 and 0.047, the newest key dropped (kv_len - 1) 0.125,
#: the page table rolled by one 1.84.
SERVE_GAP_TOL = 0.09


def _timed(fn, *args):
    """(result, first-call seconds, second-call seconds): the first call
    compiles (or hits the cache), the second is the steady run."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def _variants(op: str) -> dict[str, float]:
    """Variant -> dispatch count for ``op`` since the last reset."""
    from repro.obs import metrics

    pre = f"dispatch.{op}."
    return {k[len(pre):]: v["value"]
            for k, v in metrics.METRICS.snapshot(pre).items()}


def _expect(op: str, *variants: str) -> None:
    """The op dispatched to exactly these variants (a mesh variant also
    dispatches its per-shard chip kernel)."""
    got = _variants(op)
    if set(got) != set(variants):
        raise AssertionError(f"{op}: expected {variants}, ran {got}")


def _reset() -> None:
    from repro.obs import metrics

    metrics.METRICS.reset("dispatch.")


def _rel_max(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _check(name: str, value: float, limit: float) -> None:
    if not value <= limit:
        raise AssertionError(f"{name} = {value!r} exceeds {limit!r}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_mod2am(rng, plane: str, sizes=((8192, "bfloat16", 1e-2),
                                         (4096, "float32", 1e-4))):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    out = {}
    for n, dtype, tol in sizes:
        a = jnp.asarray(rng.standard_normal((n, n)), dtype)
        b = jnp.asarray(rng.standard_normal((n, n)), dtype)
        _reset()
        c, t_first, t_run = _timed(ops.matmul, a, b)
        _expect("matmul", plane)
        want = jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        err = _rel_max(c, want)
        _check(f"matmul {n} {dtype} max|err|/max|ref|", err, tol)
        out[f"{n}_{dtype}"] = {"err": err, "tol": tol, "first_s": t_first,
                               "run_s": t_run,
                               "tflops": 2 * n ** 3 / t_run / 1e12}
    return out


def _csr_spmv(csr, x):
    """Independent reference: XLA gather plus segment-sum over CSR."""
    import jax
    import jax.numpy as jnp

    rows = jnp.repeat(jnp.arange(csr.shape[0]), jnp.diff(csr.rowp),
                      total_repeat_length=csr.nnz)
    return jax.ops.segment_sum(csr.matvals * x[csr.indx], rows,
                               num_segments=csr.shape[0])


#: paper Table 1's largest mod2as input: n, fill percent
TABLE1_LARGEST = (2000, 7.5)


def phase_mod2as(rng, plane: str, op, tol=1e-5):
    """The stencil in ELL and DIA form, and the paper's own unstructured
    input (uniform random columns, the ELL kernel's worst case) in ELL."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.numerics import sparse

    n, fill = TABLE1_LARGEST
    csr_r = sparse.csr_from_dense(sparse.random_sparse(n, fill, seed=n),
                                  dtype="float32")
    ell_r = sparse.ell_from_csr(csr_r)
    x = jnp.asarray(rng.standard_normal(op.csr.shape[1]), jnp.float32)
    xr = jnp.asarray(rng.standard_normal(n), jnp.float32)
    out = {}
    for name, kernel, fn, args, csr in (
            ("spmv_ell", "spmv_ell", ops.spmv_ell,
             (op.ell.values, op.ell.cols, x), op.csr),
            ("spmv_dia", "spmv_dia",
             lambda d, v: ops.spmv_dia(d, op.dia.offsets, v),
             (op.dia.diags, x), op.csr),
            ("spmv_ell_table1", "spmv_ell", ops.spmv_ell,
             (ell_r.values, ell_r.cols, xr), csr_r)):
        want = jax.jit(_csr_spmv)(csr, args[-1])
        _reset()
        y, t_first, t_run = _timed(fn, *args)
        _expect(kernel, plane)
        err = _rel_max(y, want)
        _check(f"{name} max|err|/max|ref|", err, tol)
        out[name] = {"rows": csr.shape[0], "nnz": csr.nnz, "err": err,
                     "tol": tol, "first_s": t_first, "run_s": t_run}
    return out


def phase_mod2f(rng, plane: str, n=1 << 22, tol=1e-4):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    z = jnp.asarray(rng.standard_normal(n) + 1j * rng.standard_normal(n),
                    jnp.complex64)
    _reset()
    y, t_first, t_run = _timed(ops.fft, z)
    _expect("fft", plane)
    want = np.asarray(jnp.fft.fft(z), np.complex128)
    got = np.asarray(y, np.complex128)
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    _check("fft ||err||/||ref||", err, tol)
    return {"n": n, "err": err, "tol": tol, "first_s": t_first,
            "run_s": t_run}


#: CG stops once ||r|| <= CG_STOP * ||b|| (recursive residual), or at the
#: fixed ``max_iters``
CG_STOP = 1e-6


def _cg(a, b, max_iters, backend=None):
    import jax.numpy as jnp

    from repro.core import unwrap
    from repro.numerics import solvers

    stop = CG_STOP ** 2 * jnp.sum(b * b)
    res = solvers.cg_solve(a, b, stop=stop, max_iters=max_iters,
                           backend=backend)
    return unwrap(res.x), res.residual_sq, res.iterations


def phase_cg(rng, plane: str, op, max_iters=1000, tol=1e-4):
    import jax
    import jax.numpy as jnp

    b = jnp.asarray(rng.standard_normal(op.dia.shape[0]), jnp.float32)
    _reset()
    (x, r2, k), t_first, t_run = _timed(
        jax.jit(lambda diags, b: _cg(type(op.dia)(diags, op.dia.offsets,
                                                  op.dia.shape), b,
                                     max_iters, backend="dia")),
        op.dia.diags, b)
    _expect("solver_spmv", "dia")
    _expect("spmv_dia", plane)
    rel = float(jnp.linalg.norm(b - jax.jit(_csr_spmv)(op.csr, x))
                / jnp.linalg.norm(b))
    _check("cg ||b - Ax||/||b||", rel, tol)
    return {"rows": op.dia.shape[0], "iterations": int(k),
            "max_iters": max_iters, "rel_residual": rel, "tol": tol,
            "first_s": t_first, "run_s": t_run}


def _teacher_gaps(lm, params, reqs, outs, width: int, batch: int = 4):
    """Teacher-force every request's prompt + served tokens through the
    XLA-plane forward: per served token, its row's maximum logit minus the
    token's own (0 where the engine chose the reference argmax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import registry

    def gaps(params, seq, start, got):
        logits = lm.forward(params, seq)[0]
        pos = start[:, None] + jnp.arange(got.shape[1])[None]
        rows = logits[jnp.arange(seq.shape[0])[:, None], pos]
        rows = rows.astype(jnp.float32)                       # (b, new, V)
        chosen = jnp.take_along_axis(rows, got[..., None], axis=-1)[..., 0]
        return rows.max(axis=-1) - chosen

    got = np.asarray(outs, np.int32)
    seqs = np.zeros((len(reqs), width), np.int32)       # right-padded
    for i, (prompt, _) in enumerate(reqs):
        seq = np.concatenate([prompt, got[i, :-1]])
        seqs[i, :len(seq)] = seq
    start = np.asarray([len(prompt) - 1 for prompt, _ in reqs], np.int32)
    with registry.use_backend("xla"):        # read while tracing: fresh jit
        fn = jax.jit(gaps)
        return np.concatenate([
            np.asarray(fn(params, seqs[i:i + batch], start[i:i + batch],
                          got[i:i + batch]))
            for i in range(0, len(reqs), batch)])


def phase_serve(rng, plane: str, cfg, *, slots=8, chunk=128, requests=16,
                prompt=(128, 512), new=32, max_len=640, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import registry
    from repro.models.lm import LM
    from repro.serve import ContinuousEngine, SamplingParams

    lm = LM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(lm.init)(jax.random.PRNGKey(seed)))
    t_init = time.perf_counter() - t0
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(*prompt)))
             .astype(np.int32), new) for _ in range(requests)]

    _reset()
    eng = ContinuousEngine(lm, params, num_slots=slots, max_len=max_len,
                           chunk_size=chunk,
                           sampling=SamplingParams(greedy=True))
    t0 = time.perf_counter()
    eng.serve(reqs[:1])                                  # compile both steps
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = eng.serve(reqs)
    t_run = time.perf_counter() - t0
    for op, variant in (("chunk_attention", "merge"),
                        ("paged_attention", "gather"),
                        ("flash_attention_state", plane)):
        _expect(op, variant)
    short = [i for i, o in enumerate(outs) if len(o) != new]
    if short:
        raise AssertionError(f"requests {short} returned fewer than {new}")

    # every served token against the XLA-plane forward of its own prefix
    gap = _teacher_gaps(lm, params, reqs, outs, max_len)

    # LM.forward of two prompts, kernel plane vs XLA plane (fresh jits:
    # the plane is read while tracing), cut to a multiple of the 128-token
    # flash block so the kernel plane accepts the forward
    plen = min(len(reqs[0][0]), len(reqs[1][0]))
    plen -= plen % 128
    toks = jnp.asarray(np.stack([reqs[0][0][:plen], reqs[1][0][:plen]]))
    _reset()
    lg_k = jax.jit(lambda p, t: lm.forward(p, t)[0])(params, toks)
    kernels = {plane, "blocksparse" + ("" if plane == "pallas"
                                       else "_" + plane)}
    ran = set(_variants("flash_attention"))
    if not ran or not ran <= kernels:
        raise AssertionError(f"forward attention ran {ran}, not {kernels}")
    with registry.use_backend("xla"):
        lg_x = jax.jit(lambda p, t: lm.forward(p, t)[0])(params, toks)
    diff = float(np.abs(np.asarray(lg_k, np.float32)
                        - np.asarray(lg_x, np.float32)).max())

    n_tok = sum(len(o) for o in outs)
    rec = {"model": cfg.name, "layers": cfg.num_layers, "requests": requests,
           "tokens": n_tok, "init_s": t_init, "first_s": t_first,
           "run_s": t_run, "tokens_per_s": n_tok / t_run,
           "logit_max_diff": diff, "logit_atol": SERVE_LOGIT_ATOL,
           "gap_max": float(gap.max()),
           "gap_p99": float(np.quantile(gap, 0.99)),
           "argmax_share": float(np.mean(gap == 0)),
           "gap_tol": SERVE_GAP_TOL}
    _check("forward max|logits(kernel) - logits(xla)|", diff,
           SERVE_LOGIT_ATOL)
    _check("served tokens' teacher-forced max gap", rec["gap_max"],
           SERVE_GAP_TOL)
    return rec


# ---------------------------------------------------------------------------
# four chips: the O3 mesh variants against one chip, same inputs
# ---------------------------------------------------------------------------

def _per_device_bytes(fn, *args) -> tuple[object, int]:
    """Compile ``fn`` for these (possibly sharded) arguments: the compiled
    callable and its per-device argument + output bytes."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    return compiled, int(ma.argument_size_in_bytes + ma.output_size_in_bytes)


def phase_mesh(rng, plane: str, op, n=8192, cg_iters=1000,
               cg_tol=1e-4, mm_tol=1e-2):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ExecLevel, registry, use_level, wrap
    from repro.kernels import ops

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    out = {}

    a = jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16)
    with use_level(ExecLevel.O2):
        _reset()
        one, one_bytes = _per_device_bytes(ops.matmul, a, b)
        c1 = jax.block_until_ready(one(a, b))
        _expect("matmul", plane)
    a_s = jax.device_put(a, NamedSharding(mesh, P(None, "data")))
    b_s = jax.device_put(b, NamedSharding(mesh, P("data", None)))
    with use_level(ExecLevel.O3, mesh):
        _reset()
        many, many_bytes = _per_device_bytes(ops.matmul, a_s, b_s)
        _expect("matmul", "mesh_psum", plane)
    t0 = time.perf_counter()
    cm = jax.block_until_ready(many(a_s, b_s))
    t_mesh = time.perf_counter() - t0
    err = _rel_max(cm, c1)
    _check("mesh vs chip matmul max|err|/max|ref|", err, mm_tol)
    share = many_bytes / one_bytes
    _check("matmul per-device bytes share", abs(share - 1 / ndev), 0.05)
    out["matmul"] = {"n": n, "err_vs_chip": err, "tol": mm_tol,
                     "bytes_share": share, "run_s": t_mesh}

    bvec = jnp.asarray(rng.standard_normal(op.dia.shape[0]), jnp.float32)
    dia_t = type(op.dia)

    def cg(diags, bv):
        return _cg(dia_t(diags, op.dia.offsets, op.dia.shape), bv, cg_iters)

    with use_level(ExecLevel.O2):
        _reset()
        one, one_bytes = _per_device_bytes(cg, op.dia.diags, bvec)
        x1, _, k1 = jax.block_until_ready(one(op.dia.diags, bvec))
        _expect("spmv_dia", plane)
    d_s = jax.device_put(op.dia.diags, NamedSharding(mesh, P(None, "data")))
    b_s = jax.device_put(bvec, NamedSharding(mesh, P("data")))
    with use_level(ExecLevel.O3, mesh):
        won = registry.select("solver_spmv", op.dia, wrap(bvec)).name
        if won != "mesh_dia":
            raise AssertionError(f"cg under O3 selected {won!r}")
        many, many_bytes = _per_device_bytes(cg, d_s, b_s)
    t0 = time.perf_counter()
    xm, _, km = jax.block_until_ready(many(d_s, b_s))
    t_mesh = time.perf_counter() - t0
    ref = jax.jit(_csr_spmv)
    res = {}
    for name, x in (("chip", x1), ("mesh", xm)):
        x = jax.device_put(x, jax.devices()[0])
        res[name] = float(jnp.linalg.norm(bvec - ref(op.csr, x))
                          / jnp.linalg.norm(bvec))
        _check(f"cg {name} ||b - Ax||/||b||", res[name], cg_tol)
    share = many_bytes / one_bytes
    _check("cg per-device bytes share", abs(share - 1 / ndev), 0.05)
    out["cg"] = {"rows": op.dia.shape[0], "iterations_chip": int(k1),
                 "iterations_mesh": int(km), "rel_residual": res,
                 "tol": cg_tol, "bytes_share": share, "run_s": t_mesh}
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh phases on a (4, 1) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.numerics.sparse import stencil_3d
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device_kind={device['kind']} count={device['count']} "
          f"jax={jax.__version__} cache={cache}", flush=True)

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    op = stencil_3d(128, seed=args.seed)
    print(f"stencil 128^3 built in {time.perf_counter() - t0:.2f}s",
          flush=True)
    if args.chips == 4:
        phases = {"mesh": lambda: phase_mesh(rng, "pallas", op)}
    else:
        phases = {
            "mod2am": lambda: phase_mod2am(rng, "pallas"),
            "mod2as": lambda: phase_mod2as(rng, "pallas", op),
            "mod2f": lambda: phase_mod2f(rng, "pallas"),
            "cg": lambda: phase_cg(rng, "pallas", op),
            "serve": lambda: phase_serve(rng, "pallas",
                                         get_config("qwen3-1.7b"),
                                         seed=args.seed),
        }
    records, failed = {}, []
    for name, run in phases.items():
        t0 = time.perf_counter()
        try:
            rec = {"ok": True, **run()}
        except Exception as e:           # report every phase, then fail
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        rec["wall_s"] = time.perf_counter() - t0
        records[name] = rec
        print(f"phase {name}: " + json.dumps(rec, default=float), flush=True)

    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "jax": jax.__version__,
                   "phases": records}, f, indent=1, default=float)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
