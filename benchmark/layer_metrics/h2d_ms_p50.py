"""Feed path: median time of one batch's upload, the benchmark's span
around ``device_put`` (waited for) in its own ``convert``, over the
measured window."""

import statistics


def read(trace, spans, counters, cell):
    ms = spans.durations_ms("bench.convert", since=cell["t0"],
                            until=cell["t1"])
    if not ms:
        return None
    cell["say"]("h2d_ms_p50: %d samples, max %.4f" % (len(ms), max(ms)))
    return statistics.median(ms)
