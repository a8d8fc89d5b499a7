"""Model code: device time in the phase ``optimizer`` (the ``opt_update``
call of the train step), over the device's busy time.  Phase of each
instruction: ``monitor.devscope``."""

from ..harness import scope_time


def read(trace, spans, counters, cell):
    return scope_time.share(trace, cell,
                            lambda phase, scope: phase == "optimizer")
