"""Train driver / memory: what the trainer's state holds on the fullest
chip after the window, by the program's owners ``params``, ``opt_state``
and ``running`` (``harness/memory_account.py``): the model's size, the
optimizer's choice and its sharding."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    if got is None:
        return None
    return sum(got["owners"].get(o, 0)
               for o in memory_account.STATE) / memory_account.GB
