"""Train driver: milliseconds of the measured window inside a collection of
Python's collector, by the program's ``gc`` records (``gc.callbacks``).  A
collection keeps the interpreter's lock: no thread of the process
dispatches, feeds or converts while one runs."""

from ..harness import window_time


def read(trace, spans, counters, cell):
    got = window_time.account(cell)
    if got is None:
        return None
    cell["say"]("window_gc_ms: %.3f ms; the longest collections:"
                % window_time.ms(got["gc_s"]))
    window_time.say_longest(cell, got, "gc")
    return window_time.ms(got["gc_s"])
