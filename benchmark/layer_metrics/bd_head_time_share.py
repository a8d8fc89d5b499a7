"""Model code: device time under the program's scope ``lm_head``, forward and
backward, over the device's busy time, where the head computes the MASKED
rows of the noised copy alone (whole blocks of 512: ``bd_head_rows_share``
says how many of the S).  ``lm_head_time_share``'s reading under a name of
its own: an existing entry may not take a cell."""

from . import bd_attn_time_share


def read(trace, spans, counters, cell):
    return bd_attn_time_share.scope_share(
        trace, spans, counters, cell, "bd_head_time_share", "lm_head")
