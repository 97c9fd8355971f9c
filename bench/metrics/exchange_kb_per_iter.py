"""kB (1000 B) of x one chip receives from its neighbours per SpMV of the
mesh CG: the program's gauge ``distributed.mesh_dia.exchange_bytes_per_iter``
(2 * max|offset| * 4 B, set when the halo exchange is traced), as the
entry's stats carry it.  None where the program has no such gauge."""


def read(rec):
    value = rec.stats.get("exchange_bytes_per_iter")
    return None if value is None else value / 1e3
