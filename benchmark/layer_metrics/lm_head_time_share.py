"""Model code: device time under the program's scope ``lm_head`` (final
layer norm excepted, which is ``layer_norm``), forward and backward, over
the device's busy time.  Scope of each instruction: ``monitor.devscope``."""

from ..harness import scope_time


def read(trace, spans, counters, cell):
    return scope_time.share(trace, cell,
                            lambda phase, scope: scope == "lm_head")
