"""Feed path: the share of the measured window that the training thread
spent waiting on the pipe, from the program's own
``monitor.pipe.feed_stall_ms`` (its sum over the window / the window),
cross-checked on an earlier line by the benchmark's span around
``next(pipe)``."""


def read(trace, spans, counters, cell):
    if "feed_stall_ms" not in counters:
        return None
    own = sum(spans.durations_ms("bench.feed_wait", since=cell["t0"],
                                 until=cell["t1"]))
    cell["say"]("feed_stall_share: program counter %.4f ms, benchmark span "
                "%.4f ms, over %.4f s" % (counters["feed_stall_ms"], own,
                                          cell["window_s"]))
    return 100.0 * counters["feed_stall_ms"] / 1e3 / cell["window_s"]
