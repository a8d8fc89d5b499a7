"""Collectives: the part of the collective intervals during which no other
operation runs on that device, over the traced window, mean over devices."""


def read(trace, spans, counters, cell):
    if not trace:
        return None
    return 100.0 * trace.collective_exposed_s / trace.window_s
