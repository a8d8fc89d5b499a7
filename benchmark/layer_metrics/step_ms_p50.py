"""Train driver: median time of one step on the host clock.  Scanned: the
time between the completions of successive dispatches over the steps in
one; host-fed: the time between successive steps leaving the in-flight
window."""

import statistics


def read(trace, spans, counters, cell):
    samples = cell["step_ms"]
    if not samples:
        return None
    cell["say"]("step_ms_p50: %d samples, min %.4f max %.4f"
                % (len(samples), min(samples), max(samples)))
    return statistics.median(samples)
