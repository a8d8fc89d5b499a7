"""Device: 1 - (union of the operation intervals / traced window), mean
over devices."""


def read(trace, spans, counters, cell):
    if not trace:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
