"""Model code: device time under the program's scopes ``moe`` (dispatch,
grouped matmuls, combine) and ``router`` (logits, softmax, top-k, auxiliary
losses), all phases, over the device's busy time.  Scope of each
instruction: ``monitor.devscope``; a program without those scopes reads
nothing.

It is only as good as the scopes' coverage, and this cell lists itself
under no ``scope_unattributed_share`` to say so (ROADMAP.md Design 9): with
XLA's own ragged-dot calls, which lose the program's path, a third of the
step carried no scope and this share read 18.5 % for 46 % (PERF.md section
6, PR 27).  So where more than UNATTRIBUTED_LIMIT per cent of the busy time
carries no scope (the limit ``scope_unattributed_share`` gives for
believing any scope share) it says so and reads nothing, and the traced run
lacks the metric: a missing number where a wrong one would pass."""

from ..harness import scope_time
from . import scope_unattributed_share

SCOPES = ("moe", "router")
UNATTRIBUTED_LIMIT = 5.0


def seconds(trace, cell):
    """Device seconds under the two scopes, or None without them."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    took = sum(s for (_, scope), s in table.items() if scope in SCOPES)
    return took or None


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None:
        return None
    lost = scope_unattributed_share.read(trace, spans, counters, cell)
    if lost > UNATTRIBUTED_LIMIT:
        cell["say"]("moe_time_share: %.3f %% of the busy time carries no "
                    "scope (limit %.1f %%): not read" % (lost,
                                                          UNATTRIBUTED_LIMIT))
        return None
    return 100.0 * took / trace.busy_s
