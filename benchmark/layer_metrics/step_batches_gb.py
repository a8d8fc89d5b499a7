"""Train driver / memory: what the batches staged on the fullest chip hold
after the window, by the program's owners ``staged_batches`` (what
``run_steps`` scans over) and ``feed_pipe`` (what the pipe has uploaded
ahead of the step)."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    if got is None:
        return None
    return sum(got["owners"].get(o, 0)
               for o in memory_account.BATCHES) / memory_account.GB
