"""Model code: device time under the program's scope ``bn`` (batch-norm
statistics, the normalisation pass with the ReLU and residual add XLA fuses
into it, and their backward), over the device's busy time.  Batch-norm work
that XLA fuses INTO a convolution's instruction is counted with ``conv``.
Scope of each instruction: ``monitor.devscope``."""

from ..harness import scope_time


def read(trace, spans, counters, cell):
    return scope_time.share(trace, cell, lambda phase, scope: scope == "bn")
