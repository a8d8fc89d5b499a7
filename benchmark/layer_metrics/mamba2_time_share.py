"""Model code: device time under the program's scopes ``mamba2`` (the
Mamba-2 mixer as a layer's one branch: both projections, the causal filter's
kernels, the step sizes, the gated group norm) and ``ssd_scan`` (the chunked
dual form's kernels, inside it), all phases, over the device's busy time.
The layer's input norm carries ``layer_norm`` and is not in it.
``moe_time_share``'s rule on unattributed time (``mla_time_share.
attributed``); a program without the scope (the parent commit's) reads
nothing."""

from ..harness import scope_time
from . import mla_time_share

SCOPES = ("mamba2", "ssd_scan")
FORWARD, BACKWARD = "ssd_scan_fwd", "ssd_scan_bwd"


def seconds(trace, cell):
    """Device seconds under the two scopes, or None without them."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, at), s in table.items() if at in SCOPES) or None


def kernel_seconds(trace):
    """Device seconds in the two kernels, by name."""
    return trace.seconds_of_kernels((FORWARD, BACKWARD))


def said_kernels(trace):
    """``<kernel> <seconds> s in <n> calls`` of each of the two."""
    return ", ".join("%s %.6f s in %g calls" % (
        name, trace.seconds_of_kernels((name,)),
        trace.count_of_kernels((name,))) for name in (FORWARD, BACKWARD))


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "mamba2_time_share"):
        return None
    cell["say"]("mamba2_time_share: %.6f s under mamba2 + ssd_scan, %.6f s "
                "of it in the scan's kernels" % (took, kernel_seconds(trace)))
    return 100.0 * took / trace.busy_s
