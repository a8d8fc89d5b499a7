"""Model code: device time under the program's scopes ``mamba`` (the Mamba-1
mixer where attention stands: both projections, the causal filter, the step
sizes' chain) and ``selective_scan`` (the scan's kernels, inside it), all
phases, over the device's busy time.  The layer's input norm carries
``layer_norm`` and is not in it.  ``moe_time_share``'s rule on unattributed
time (``mla_time_share.attributed``); a program without the scope (the
parent commit's) reads nothing."""

from ..harness import scope_time
from . import mla_time_share

SCOPES = ("mamba", "selective_scan")
FORWARD, BACKWARD = "selective_scan_fwd", "selective_scan_bwd"


def seconds(trace, cell):
    """Device seconds under the two scopes, or None without them."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, at), s in table.items() if at in SCOPES) or None


def kernel_seconds(trace):
    """Device seconds in the two kernels, by name."""
    return trace.seconds_of_kernels((FORWARD, BACKWARD))


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "mamba_time_share"):
        return None
    cell["say"]("mamba_time_share: %.6f s under mamba + selective_scan, "
                "%.6f s of it in the scan's kernels"
                % (took, kernel_seconds(trace)))
    return 100.0 * took / trace.busy_s
