"""Model code: device time under the program's scope ``noise`` (the mask
from a batch's noise, the mask token put in, the noised copy put over the
clean one, the noised rows cut out again in front of the head), all phases,
over the device's busy time: what the doubling costs OUTSIDE the layers.  A
program without the scope (the parent commit's) reads 0 of nothing: None."""

from . import bd_attn_time_share


def read(trace, spans, counters, cell):
    share = bd_attn_time_share.scope_share(
        trace, spans, counters, cell, "bd_noise_time_share", "noise")
    return share or None
