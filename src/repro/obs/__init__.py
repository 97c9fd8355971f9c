"""repro.obs — the runtime observability plane (DESIGN.md §14).

Three instruments plus one dispatch introspection API:

    trace     span/event tracer, Chrome-trace/Perfetto export, and its
              bridge to the ``jax.profiler`` timeline
    metrics   counters / gauges / log2 histograms, dict snapshot
    drift     live dispatch timings vs the §11 cost model's calibration
    explain   the ranked dispatch table — every candidate with its
              accept/reject reason, without executing anything

``explain`` answers the question dispatch never had to: *why this
variant*.  It evaluates the same ranking and the same predicates
``registry.select`` uses, so the winner it reports is the variant
``dispatch`` would run.
"""
from repro.obs import drift, metrics, trace
from repro.obs.metrics import METRICS
from repro.obs.trace import TRACER

__all__ = ["trace", "metrics", "drift", "TRACER", "METRICS", "explain",
           "explain_str"]


def explain(op, *args, variant=None, **kwargs):
    """The ranked candidate table for one dispatch, without executing:
    one row per registered variant in selection order, each carrying
    ``selected`` and a ``reason`` (``selected`` / ``plane-unavailable`` /
    ``scope-mismatch`` / ``available-predicate`` / ``accepts-predicate``
    / ``outranked-by-calibration`` / ``outranked``).  Evaluated under the
    ambient level/mesh/plane, exactly like ``dispatch``."""
    from repro.core import registry
    return registry.REGISTRY.explain(op, *args, variant=variant, **kwargs)


def explain_str(rows) -> str:
    """Human-readable rendering of an :func:`explain` table.  When the
    selected variant decides an output layout (``out_sharding`` — e.g. the
    Cannon-style mesh SpGEMM, DESIGN.md §15), a trailing line names it."""
    if not rows:
        return "(no candidates)"
    head = f"{'#':>2} {'variant':<22} {'plane':<9} {'scope':<5} " \
           f"{'cost':>8} {'measured':>11}  reason"
    lines = [head, "-" * len(head)]
    decided = None
    for row in rows:
        meas = row.get("calibrated_seconds")
        lines.append(
            f"{row['rank']:>2} "
            f"{('* ' if row['selected'] else '  ') + row['variant']:<22} "
            f"{str(row['plane']):<9} {row['scope']:<5} "
            f"{row['cost']:>8.3g} "
            f"{(f'{meas:.3e}' if meas is not None else '-'):>11}  "
            f"{row['reason']}")
        if row.get("selected") and row.get("out_sharding"):
            decided = row["out_sharding"]
    if decided:
        lines.append(f"decided out_sharding: {decided}")
    return "\n".join(lines)
