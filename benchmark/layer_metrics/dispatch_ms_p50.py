"""Train driver: median host time inside one ``StepTrainer.step`` /
``run_steps`` of the measured window, by the program's own ``call`` records
(``harness/window_time.py``): what the host pays to hand the device a
dispatch.  ``step_ms_p50`` times the same layer from outside, between
completions; this is the part of it the host is busy for."""

from ..harness import window_time


def read(trace, spans, counters, cell):
    got = window_time.account(cell)
    if got is None or not got["calls"]:
        return None
    cell["say"]("dispatch_ms_p50: %d calls of the program in the window, "
                "median %.4f ms, longest %.4f ms; the longest:"
                % (got["calls"], window_time.ms(got["call_p50_s"]),
                   window_time.ms(got["call_max_s"])))
    window_time.say_longest(cell, got, "call")
    return window_time.ms(got["call_p50_s"])
