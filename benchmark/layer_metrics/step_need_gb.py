"""Device / memory: what one chip must hold to run the window's program
once, by the compiler's count: argument + output - alias + temp + generated
code (``memscope.need_bytes``; donation is why alias comes off).  The
occupancy a step requires, whatever else the process put beside it."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    return None if got is None else got["need_bytes"] / memory_account.GB
