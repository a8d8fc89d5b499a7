"""Device: operation time whose instruction is in no registered program's
map or carries no scope of the program's vocabulary, over the device's busy
time: what the scope shares cannot see.  Where it is over 5 %, do not
believe them."""

from ..harness import scope_time


def read(trace, spans, counters, cell):
    return scope_time.share(trace, cell, lambda phase, scope: scope is None)
