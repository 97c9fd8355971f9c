"""Mean host time of one call from entering the entry point to its return,
before the result is waited for: the benchmark's own span around the call
(``perf_counter_ns``), over every call of the measured window."""


def read(rec):
    if not rec.dispatch_ns:
        return None
    return sum(rec.dispatch_ns) / len(rec.dispatch_ns) / 1e3
