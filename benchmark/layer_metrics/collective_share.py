"""Collectives: device time inside all-reduce / reduce-scatter /
all-gather / all-to-all / collective-permute intervals over the traced
window, mean over devices."""


def read(trace, spans, counters, cell):
    if not trace:
        return None
    return 100.0 * trace.collective_s / trace.window_s
