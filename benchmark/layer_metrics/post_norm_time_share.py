"""Model code: device time under the program's scope ``post_norm`` (the RMS
norms on the branches' OUTPUTS, attention's and the FFN's, of a stack with
sandwich norms), all phases, over the device's busy time.
``moe_time_share``'s rule on unattributed time; a program without the scope
reads nothing."""

from . import mla_time_share

SCOPE = "post_norm"


def read(trace, spans, counters, cell):
    took = mla_time_share.seconds(trace, cell, SCOPE)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "post_norm_time_share"):
        return None
    cell["say"]("post_norm_time_share: %.6f s under post_norm" % took)
    return 100.0 * took / trace.busy_s
