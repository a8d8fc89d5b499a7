"""Train driver: the longest ``StepTrainer.step`` / ``run_steps`` of the
measured window, by the program's own ``call`` records.  The log names it
and sets the CPU the process burnt beside its wall time (a call as long as
its CPU was Python's own work, tracing for one; one with none was blocked),
gives the window's longest turn the same way, and lays the program's records
of the traced dispatches under the device's longest idle gaps, on the
trace's clock."""

from ..harness import window_time


def read(trace, spans, counters, cell):
    got = window_time.account(cell)
    if got is None or not got["calls"]:
        return None
    say, top = cell["say"], got["longest"]["call"][0]
    say("dispatch_ms_max: " + window_time.describe(top, cell["t0"]))
    turn = got["turn"]
    if turn:
        say("  the longest turn (a call's return to the next one's) ends in "
            "%s: +%.3f ms, wall %.3f ms, process CPU %.3f ms, thread CPU "
            "%.3f ms" % (turn["name"],
                         window_time.ms(turn["t0"] - cell["t0"]),
                         window_time.ms(turn["wall_s"]),
                         window_time.ms(turn["cpu_s"]),
                         window_time.ms(turn["thread_cpu_s"])))
    _say_gaps(trace, spans, cell)
    return window_time.ms(got["call_max_s"])


def _say_gaps(trace, spans, cell):
    """The traced part: which record of the program lies under each of the
    device's longest idle gaps."""
    found = trace and window_time.under_gaps(trace, spans)
    if not found:
        return
    (zero, pairs, spread_s), gaps = found
    if int(cell["traffic"].get("trace_host_level", 1)) == 0:
        cell["say"]("  the trace holds no annotation (host tracer level 0): "
                    "no pair of spans, the zero is tracing.anchored's")
    else:
        cell["say"]("  the trace's clock by %d spans on both clocks: its "
                    "zero at %.6f s of the host's, the pairs within %.3f us"
                    % (pairs, zero, spread_s * 1e6))
    for secs, lo, span, record in gaps:
        cell["say"]("  idle %.3f us at +%.3f ms of the trace, under %s: %s"
                    % (secs * 1e6, lo / 1e6, span,
                       window_time.describe(record, zero) if record
                       else "no record of the program"))
