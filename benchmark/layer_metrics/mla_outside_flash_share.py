"""Model code: the device time under the program's scope
``latent_attention`` that is NOT in the flash kernels (the two low-rank
chains, the latents' norms, the rotation and the query scale, assembling a
key for every head out of the latent and the one rotary key, the output
projection; forward, recomputed and backward), over the device's busy time:
what building keys and values out of the latent costs here, and what a
flash call that read the latent and the shared rotary key itself would be
judged by.  Every flash call of this stack runs under that scope, so the
kernels' time by name is taken off the scope's.  ``moe_time_share``'s rule
on unattributed time; a program without the scope reads nothing."""

from . import mla_time_share
from .swa_flash_time_share import FULL


def read(trace, spans, counters, cell):
    under = mla_time_share.seconds(trace, cell)
    if under is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "mla_outside_flash_share"):
        return None
    flash = trace.seconds_of_kernels(FULL)
    cell["say"]("mla_outside_flash_share: %.6f s under latent_attention, "
                "%.6f s of it in the flash kernels" % (under, flash))
    return 100.0 * max(under - flash, 0.0) / trace.busy_s
