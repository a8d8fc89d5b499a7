"""Model code: device time under the program's scope ``retention`` (power
retention where attention stands: the projections, q/k norm, rotary
positions, the gate, the chunked-scan kernels, the output projection), all
phases, over the device's busy time.  ``moe_time_share``'s rule: where more
than 5 % of the busy time carries no scope it says so and reads nothing.  A
program without the scope reads nothing."""

from ..harness import scope_time
from . import scope_unattributed_share
from .moe_time_share import UNATTRIBUTED_LIMIT

SCOPE = "retention"


def seconds(trace, cell):
    """Device seconds under the scope, or None without it."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, scope), s in table.items() if scope == SCOPE) or None


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None:
        return None
    lost = scope_unattributed_share.read(trace, spans, counters, cell)
    if lost > UNATTRIBUTED_LIMIT:
        cell["say"]("retention_time_share: %.3f %% of the busy time carries "
                    "no scope (limit %.1f %%): not read"
                    % (lost, UNATTRIBUTED_LIMIT))
        return None
    cell["say"]("retention_time_share: %.6f s under retention; %.3f %% of "
                "the busy time carries no scope" % (took, lost))
    return 100.0 * took / trace.busy_s
