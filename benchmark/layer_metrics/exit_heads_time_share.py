"""Model code: device time under the program's scopes ``exit_gate`` (a looped
stack's exit gate at the end of every pass, the distribution over the exits,
its entropy and the weighted sum of the exits' losses) and ``lm_head`` (the
head over every exit's rows: four heads a step where a plain stack has one),
all phases, over the device's busy time.  ``loop_scan_time_share``'s rules:
a program without ``exit_gate`` reads nothing."""

from .loop_scan_time_share import read_scopes

SCOPES = ("exit_gate", "lm_head")


def read(trace, spans, counters, cell):
    return read_scopes(trace, spans, counters, cell, "exit_heads_time_share",
                       SCOPES)
