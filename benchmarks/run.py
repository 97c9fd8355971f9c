"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # all, short inputs
    PYTHONPATH=src python -m benchmarks.run --full    # paper's full sweeps
    PYTHONPATH=src python -m benchmarks.run --only mod2am
    PYTHONPATH=src python -m benchmarks.run --only mod2am --backend-sweep
    PYTHONPATH=src python -m benchmarks.run --scaling-sweep

``--backend-sweep`` benchmarks every *registered registry variant* per op
instead of the paper-figure suites — the ArBB-vs-OpenMP-vs-MKL comparison,
reproduced for our own retargeting plane.

``--scaling-sweep`` replays the paper's speedup-vs-cores tables as
speedup-vs-mesh-shapes: the four paper kernels on 8 forced host-platform
devices arranged as O2 / 8x1 / 4x2 / 2x2x2 meshes (the device count is
forced before jax init), chip variants at O2, the mesh-scoped shard_map
variants — including the 2-D matmul tiling and the O4 hierarchical
reduction plans — beyond.

``--autotune-sweep`` is the offline calibration pass (DESIGN.md §11): every
registered variant of matmul / spmv / spmm / fft / flash_attention timed
end-to-end through dispatch per mesh shape, writing the measured cost model
(``results/costmodel.json``) plus — under ``REPRO_AUTOTUNE=1`` — the block
autotune cache, including the eager upgrade of mesh-scoped block entries a
shard_map trace could only default-mark.  ``--tiny`` shrinks the inputs to
CI-smoke sizes.

The ``--json-out`` payload records, per suite, the row data, wall time,
status, the kernel plane the registry resolved while it ran, and the
device count / mesh shapes / axis roles it saw, so ``BENCH_*.json``
trajectories stay comparable across PRs and machines — and scaling
regressions are visible.

Observability plane (DESIGN.md §14):

``--trace-out PATH`` enables the span tracer for the whole run and writes
a Chrome-trace JSON (load in Perfetto / chrome://tracing) covering every
``dispatch:*`` selection, ``blocked.*`` pad/resolve, collective-plan
event, and — for the serve suite — the continuous engine's
admit/prefill/decode/demux phases.

``--drift`` times every dispatched call against the measured cost model's
stored seconds and reports entries whose live timing diverges beyond
``REPRO_DRIFT_RATIO`` (default 4x) — the stale-calibration alarm.  The
report lands in the ``--json-out`` payload under ``"drift"`` and stale
rows print as warnings.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="the paper's full input sweeps (slower)")
    ap.add_argument("--only", "--suite", default=None,
                    choices=["mod2am", "mod2as", "mod2f", "cg", "spmm",
                             "spgemm", "attention", "serve", "roofline"])
    ap.add_argument("--backend-sweep", action="store_true",
                    help="benchmark every registered registry variant per op "
                         "and print a per-variant comparison table")
    ap.add_argument("--scaling-sweep", action="store_true",
                    help="time the four paper kernels at 1/2/4/8 devices "
                         "(speedup-vs-devices; forces 8 fake host devices)")
    ap.add_argument("--autotune-sweep", action="store_true",
                    help="calibrate the measured cost model: time every "
                         "registered variant per op per mesh shape and "
                         "write results/costmodel.json (+ the block cache "
                         "under REPRO_AUTOTUNE=1)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke input sizes for --autotune-sweep")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="enable the span tracer and write a Chrome-trace "
                         "JSON (Perfetto-loadable) for the whole run")
    ap.add_argument("--drift", action="store_true",
                    help="time dispatched calls against the measured cost "
                         "model and flag stale calibrations (report under "
                         "'drift' in --json-out)")
    args = ap.parse_args(argv)

    # stdlib-only — safe before the first jax import
    from repro.obs import drift as obs_drift
    from repro.obs import trace as obs_trace
    if args.trace_out:
        obs_trace.TRACER.enable(capacity=1_000_000)

    drift_scope = obs_drift.collect() if args.drift else None
    if drift_scope is not None:
        drift_scope.__enter__()

    def finish(payload):
        """Attach the obs artifacts every exit path shares: the drift
        report into the payload, the trace ring onto disk."""
        if drift_scope is not None:       # stop timing before reporting
            drift_scope.__exit__(None, None, None)
        rows = obs_drift.DETECTOR.report()
        if args.drift or rows or obs_drift.DETECTOR.unmatched:
            stale = [r for r in rows if r["stale"]]
            payload["drift"] = {"enabled": args.drift,
                                "threshold": obs_drift.threshold(),
                                "unmatched": obs_drift.DETECTOR.unmatched,
                                "rows": rows, "num_stale": len(stale)}
            for r in stale:
                print(f"WARNING: stale calibration {r['op']}/{r['variant']} "
                      f"[{r['key']}]: observed {r['observed_seconds']:.3e}s "
                      f"vs stored {r['stored_seconds']:.3e}s "
                      f"({r['ratio']:.1f}x > {obs_drift.threshold():.1f}x)")
        if args.trace_out:
            payload.setdefault("meta", {})["trace_out"] = args.trace_out
            payload["meta"]["trace_events"] = len(obs_trace.TRACER)
            obs_trace.TRACER.save(args.trace_out)
            print(f"trace: {len(obs_trace.TRACER)} events -> "
                  f"{args.trace_out}")
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(payload, f, default=str)

    if args.scaling_sweep or args.autotune_sweep or args.only == "spgemm":
        # Must precede the first jax import — jax locks the device count at
        # init (the spgemm suite's chip-vs-mesh rows need the devices too).
        # An explicit caller-provided count wins.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    from repro.core import registry
    from repro.utils import roofline
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctx = registry.select_context()
    kind = jax.devices()[0].device_kind
    meta = {"platform": jax.default_backend(), "jax": jax.__version__,
            "device_kind": kind,
            # the published peaks every rate on this device is read against
            # (an unknown TPU kind raises rather than borrowing another's)
            "peaks": (dataclasses.asdict(roofline.peaks(kind))
                      if jax.default_backend() == "tpu" else None),
            "backend": registry.resolve_backend(),
            "device_count": jax.device_count(),
            # the ambient mesh (usually none at the CLI) and its axis roles,
            # so payloads from mesh-scoped runs are distinguishable
            "mesh": ctx.topology.describe() if ctx.topology else None,
            "axis_roles": dict(zip(ctx.topology.axis_names,
                                   ctx.topology.roles))
            if ctx.topology else {}}

    if args.autotune_sweep:
        from benchmarks import autotune_sweep
        from repro.core import costmodel
        # --only speaks suite names; translate to the registry op swept
        op_of = {"mod2am": "matmul", "mod2as": "solver_spmv", "mod2f": "fft",
                 "spmm": "spmm", "spgemm": "spgemm",
                 "attention": "flash_attention"}
        t0 = time.time()
        try:
            rows = autotune_sweep.main(only=op_of.get(args.only),
                                       tiny=args.tiny)
            model = costmodel.get_model()
            entry = {"status": "ok", "rows": rows,
                     "costmodel_path": model.path,
                     "costmodel_keys": len(model),
                     "meshes": sorted({r["mesh"] for r in rows}),
                     "autotune_enabled":
                         os.environ.get("REPRO_AUTOTUNE", "") != ""}
        except Exception as e:
            print(f"[autotune_sweep] FAILED: {type(e).__name__}: {e}")
            entry = {"status": "error", "error": f"{type(e).__name__}: {e}"}
        entry["seconds"] = round(time.time() - t0, 3)
        entry["backend"] = registry.resolve_backend()
        payload = {"meta": meta, "suites": {"autotune_sweep": entry}}
        finish(payload)
        print("\nautotune sweep complete")
        return 1 if entry["status"] == "error" else 0

    if args.scaling_sweep:
        from benchmarks import scaling_sweep
        t0 = time.time()
        try:
            rows = scaling_sweep.main(only=args.only)
            entry = {"status": "ok", "rows": rows,
                     "device_counts": sorted({r["devices"] for r in rows}),
                     "meshes": sorted({r["mesh"] for r in rows}),
                     "axis_roles": sorted({r["roles"] for r in rows
                                           if r["roles"] != "-"}),
                     # which storage format the statistics selected for the
                     # sparse operands (DESIGN.md §9)
                     "sparse_formats": sorted({r["sparse_format"]
                                               for r in rows
                                               if r["sparse_format"] != "-"}),
                     # the sequence-ring widths the attention problem
                     # sharded over (DESIGN.md §10)
                     "ring_widths": sorted({r["ring"] for r in rows
                                            if r["ring"] != "-"})}
        except Exception as e:
            print(f"[scaling_sweep] FAILED: {type(e).__name__}: {e}")
            entry = {"status": "error", "error": f"{type(e).__name__}: {e}"}
        entry["seconds"] = round(time.time() - t0, 3)
        entry["backend"] = registry.resolve_backend()
        payload = {"meta": meta, "suites": {"scaling_sweep": entry}}
        finish(payload)
        print("\nscaling sweep complete")
        return 1 if entry["status"] == "error" else 0

    if args.backend_sweep:
        from benchmarks import backend_sweep
        if args.full:
            print("note: --full has no effect on --backend-sweep "
                  "(canonical inputs only)")
        t0 = time.time()
        try:
            rows = backend_sweep.main(only=args.only)
            entry = {"status": "ok", "rows": rows}
        except Exception as e:
            print(f"[backend_sweep] FAILED: {type(e).__name__}: {e}")
            entry = {"status": "error", "error": f"{type(e).__name__}: {e}"}
        entry["seconds"] = round(time.time() - t0, 3)
        entry["backend"] = registry.resolve_backend()
        payload = {"meta": meta, "suites": {"backend_sweep": entry}}
        finish(payload)
        print("\nbackend sweep complete")
        return 1 if entry["status"] == "error" else 0

    from benchmarks import (mod2am, mod2as, mod2f, cg, spmm, spgemm,
                            attention, serve, roofline_table)

    suites = {
        "mod2am": lambda: mod2am.main(args.full),
        "mod2as": lambda: mod2as.main(args.full),
        "mod2f": lambda: mod2f.main(args.full),
        "cg": lambda: cg.main(args.full),
        "spmm": lambda: spmm.main(args.full),
        "spgemm": lambda: spgemm.main(args.full),
        "attention": lambda: attention.main(args.full),
        "serve": lambda: serve.main(args.full),
        "roofline": roofline_table.main,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    payload = {"meta": meta, "suites": {}}
    failed = []
    for name, fn in suites.items():
        t0 = time.time()
        backend = registry.resolve_backend()
        try:
            rows = fn()
            entry = {"status": "ok", "rows": rows}
        except Exception as e:                       # keep the run alive:
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            entry = {"status": "error",
                     "error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        entry["seconds"] = round(time.time() - t0, 3)
        entry["backend"] = backend
        payload["suites"][name] = entry
        print(f"[{name}] done in {entry['seconds']:.1f}s "
              f"(backend={backend}, status={entry['status']})")

    finish(payload)
    print("\nbenchmarks complete" + (f" ({len(failed)} suite(s) failed: "
                                     f"{', '.join(failed)})" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
