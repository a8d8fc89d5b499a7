"""Model code: device time in the phases ``backward`` and ``recompute``
(the forward that ``jax.checkpoint`` runs again), whatever the scope, over
the device's busy time.  Phase of each instruction: ``monitor.devscope``."""

from ..harness import scope_time


def read(trace, spans, counters, cell):
    return scope_time.share(
        trace, cell, lambda phase, scope: phase in ("backward", "recompute"))
