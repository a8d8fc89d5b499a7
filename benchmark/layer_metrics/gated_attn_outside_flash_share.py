"""Model code: the device time under the program's scopes ``attention`` +
``attn_gate`` that is NOT in the flash kernels (the five projections, the
gate's sigmoid and product, the rotation, ``wo``; forward, recomputed and
backward), over the device's busy time: what a flash call whose last step
applied the gate itself would be judged by.  Every flash call of this stack
runs under ``attention``, so the kernels' time by name is taken off the
scopes'.  ``moe_time_share``'s rule on unattributed time; a program whose
vocabulary has no ``attn_gate`` reads nothing."""

from . import gated_attn_time_share
from .mla_time_share import attributed
from .swa_flash_time_share import FULL, WINDOWED


def read(trace, spans, counters, cell):
    under = gated_attn_time_share.seconds(trace, cell)
    if under is None or not attributed(trace, spans, counters, cell,
                                       "gated_attn_outside_flash_share"):
        return None
    flash = trace.seconds_of_kernels(FULL + WINDOWED)
    cell["say"]("gated_attn_outside_flash_share: %.6f s under attention + "
                "attn_gate, %.6f s of it in the flash kernels"
                % (under, flash))
    return 100.0 * max(under - flash, 0.0) / trace.busy_s
