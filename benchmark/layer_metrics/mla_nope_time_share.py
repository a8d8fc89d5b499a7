"""Model code: device time under the program's scope ``latent_attention`` in
a stack whose latent layers carry NO positions and stand inside a pattern
(one query matrix, the shared key added unrotated, the flash kernels'
value-width mode, the output projection), all phases, over the device's
busy time: ``mla_time_share``'s own reading and rule, under a name of its
own so that the two latent forms' cells stay apart."""

from . import mla_time_share


def read(trace, spans, counters, cell):
    took = mla_time_share.seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "mla_nope_time_share"):
        return None
    cell["say"]("mla_nope_time_share: %.6f s under latent_attention" % took)
    return 100.0 * took / trace.busy_s
