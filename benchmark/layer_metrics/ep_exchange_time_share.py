"""Model code: device time under the program's scope ``exchange`` (expert
parallelism outside the matmuls: the pack of a chip's rows by destination,
the ``all_to_all`` out and back, the sort by local expert on arrival and its
inverse, forward, recomputed and backward), over the device's busy time,
mean over the devices.  ``moe_time_share``'s rule: where more than 5 % of
the busy time carries no scope it says so and reads nothing.  A program
without the scope reads nothing."""

from . import mla_time_share

SCOPE = "exchange"


def read(trace, spans, counters, cell):
    took = mla_time_share.seconds(trace, cell, SCOPE)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "ep_exchange_time_share"):
        return None
    cell["say"]("ep_exchange_time_share: %.6f s under exchange" % took)
    return 100.0 * took / trace.busy_s
