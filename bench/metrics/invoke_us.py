"""Mean host time of invoking the selected SpMV variant: the program's
``dispatch.invoke:spmv_dia`` spans (the jitted call, its launch through
PJRT, the output-layout attachment) in the traced window."""
from bench import spans

SPAN = "dispatch.invoke:spmv_dia"


def read(rec):
    return spans.mean_us(rec.trace, SPAN)
