"""Model code / memory: the temporaries of the program the window ran, by
the compiler's buffer assignment (``memscope.temp_held_bytes``: on the TPU
the assignment's total less arguments and outputs, since
``memory_analysis()``'s own ``temp_size_in_bytes`` counts what the loops
carry of the arguments again): the activations kept for the backward pass,
the stacked gradients, every temporary.  What the remat policy, the row
blocks and the all-pairs tier set."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    return None if got is None else got["temp_bytes"] / memory_account.GB
