"""Train driver: of the seconds the measured window lost (``window_s`` less
units / ``train_throughput``, which is ``window_lost_share`` of the window),
the share that is left after the start (the stretch before the first
completion less one median dispatch) and after the compile, collection and
stall seconds that the program's records hold AFTER the first completion, as
far as the window lost anything after its start (those before it are inside
the start, and the log says how much of it they are; a host that stalls
behind a dispatch in flight loses the device nothing): the instrument's own
coverage, signed, as ``setup_unattributed_share`` is.  Nothing where the
window lost under 50 ms."""

from ..harness import window_time

def read(trace, spans, counters, cell):
    got = window_time.unattributed(cell)
    if got is None:
        return None
    ms, parts = window_time.ms, window_time.PARTS
    cell["say"](
        "window, two views: the benchmark's marks lost %.3f ms of %.3f s | "
        "the start %.3f ms (%.3f ms to the first completion less a median "
        "dispatch; compile %.3f + gc %.3f + stall %.3f ms of the program's "
        "records lie inside it) + after it compile %.3f + gc %.3f + stall "
        "%.3f ms, of which %.3f ms can be of the %.3f ms lost after the "
        "start (the rest passed behind a dispatch in flight) + unattributed "
        "%.3f ms"
        % ((ms(got["lost_s"]), cell["window_s"], ms(got["start_s"]),
            ms(got["first_s"]))
           + tuple(ms(got["start"][k]) for k in parts)
           + tuple(ms(got["later"][k]) for k in parts)
           + (ms(got["explained_s"]), ms(got["after_s"]),
              ms(got["left_s"]))))
    if abs(got["lost_s"]) < window_time.FLOOR_S:
        cell["say"]("window_lost_unattributed_share: the window lost under "
                    "%.0f ms, nothing to attribute"
                    % ms(window_time.FLOOR_S))
        return None
    return 100.0 * got["left_s"] / got["lost_s"]
