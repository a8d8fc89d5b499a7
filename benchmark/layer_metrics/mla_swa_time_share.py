"""Model code: device time under the program's scope ``mla_swa``, all
phases, over the device's busy time: the SLIDING layers' latent attention
(both low-rank chains at their own ranks, the rotation at its own theta, the
flash calls under the window, the output projection).
``moe_time_share``'s rule on unattributed time; a program without the scope
reads nothing."""

from . import dsa_time_share


def read(trace, spans, counters, cell):
    return dsa_time_share.share(trace, spans, counters, cell,
                                "mla_swa_time_share", ("mla_swa",))
