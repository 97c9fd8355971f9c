"""Mean CG iterations per solve in the measured window, as ``cg_solve``
returns them."""


def read(rec):
    return rec.stats.get("iterations")
