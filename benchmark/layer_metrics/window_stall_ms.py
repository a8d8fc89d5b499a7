"""Train driver: milliseconds of the measured window in which the program's
watch thread, which sleeps 10 ms a turn, woke more than 50 ms late (its
``stall`` records), outside any collection or compile record: the process
was kept off the CPU (a shared host, a quota) or a thread kept the
interpreter's lock.  The log sets the CPU seconds, the involuntary context
switches and the cgroup's throttled time beside each."""

from ..harness import window_time


def read(trace, spans, counters, cell):
    got = window_time.account(cell)
    if got is None:
        return None
    cell["say"]("window_stall_ms: %.3f ms outside collections and compiles; "
                "the longest late beats:" % window_time.ms(got["stall_s"]))
    window_time.say_longest(cell, got, "stall")
    return window_time.ms(got["stall_s"])
