"""Model code: device time under the program's scope ``shared_expert`` (the
dense gated FFN every token meets beside its routed experts), all phases,
over the device's busy time.  ``moe_time_share``'s rule on unattributed
time; a program without the scope reads nothing."""

from . import mla_time_share

SCOPE = "shared_expert"


def read(trace, spans, counters, cell):
    took = mla_time_share.seconds(trace, cell, SCOPE)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "shared_expert_time_share"):
        return None
    cell["say"]("shared_expert_time_share: %.6f s under shared_expert" % took)
    return 100.0 * took / trace.busy_s
