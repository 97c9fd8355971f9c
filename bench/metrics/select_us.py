"""Mean host time of the registry's selection for one SpMV: the program's
``dispatch.select:spmv_dia`` spans (``repro.core.registry._select``:
context, ranking, cost-model lookup, predicates) in the traced window."""
from bench import spans

SPAN = "dispatch.select:spmv_dia"


def read(rec):
    return spans.mean_us(rec.trace, SPAN)
