#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's data is made on the device
from ``--seed``; its shapes are warmed up (set-up), then whole calls are
measured back to back for ``--seconds``.  With ``--trace 1`` the run also
traces a short window with ``jax.profiler`` and reports the cell's
per-layer metrics instead of its end-to-end ones.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared with its limit); the last lines of
standard error repeat the checks.  Exits 2, with no result line, where JAX
finds no accelerator or fewer chips than the cell asks for.  The compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the script's own directory would shadow modules such as ``trace``
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
