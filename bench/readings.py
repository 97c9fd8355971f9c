#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1-12 \
        --control-seeds 13-15 --fault early_stop:16-18 --seconds 3

Runs the cell's timed path on each of ``--seeds``, the control (the plain
reference in the precision below the configuration's, in the program's
place) on each of ``--control-seeds``, and the timed path with a fault of
``bench/faults.py`` planted on each seed of ``--fault <name>:<seeds>``,
each with a short window, and prints one JSON line per run with the
numbers compared.  The limit of each number lies between the largest
sound reading and the smallest control reading, below every planted
fault's (PERF.md, section 2).  The benchmark's own runs never run the
control or a fault.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault", action="append", default=[],
                    help="<fault>:<seeds>, a fault of bench/faults.py")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax
    from bench import faults, harness

    runs = ([(s, False, None) for s in args.seeds]
            + [(s, True, None) for s in args.control_seeds])
    for item in args.fault:
        name, _, text = item.partition(":")
        runs += [(s, False, name) for s in seeds(text)]
    for seed, control, fault in runs:
        lines: list[str] = []
        if fault:
            jax.clear_caches()          # no program traced before the fault
        with faults.planted(fault) if fault else contextlib.nullcontext():
            out = harness.run(args.workload, seed, args.seconds, False,
                              control=control, log=lines.append)
        if fault:
            jax.clear_caches()
        logged = {tag: json.loads(line[len(tag) + 2:]) for line in lines
                  for tag in ("stats", "setup")
                  if line.startswith(tag + ": ")}
        print(json.dumps({"seed": seed, "control": control, "fault": fault,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"],
                          "per_call_ms": [v["value"] for k, v in
                                          out["metrics"].items()
                                          if k != "setup_s"][0],
                          "setup": logged.get("setup"),
                          "stats": logged.get("stats")}),
              flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
