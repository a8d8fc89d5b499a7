"""Model code: device time under the program's scope ``latent_attention``
(latent attention where attention stands: both low-rank chains and their
latents' norms, the rotation, the query scale and the assembly of the heads,
the flash kernels, the output projection), all phases, over the device's
busy time.  ``moe_time_share``'s rule: where more than 5 % of the busy time
carries no scope it says so and reads nothing.  A program without the scope
reads nothing."""

from ..harness import scope_time
from . import scope_unattributed_share
from .moe_time_share import UNATTRIBUTED_LIMIT

SCOPE = "latent_attention"


def seconds(trace, cell, scope=SCOPE):
    """Device seconds under ``scope``, or None without it."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, at), s in table.items() if at == scope) or None


def attributed(trace, spans, counters, cell, name):
    """Whether the scopes cover the busy time well enough to believe a
    share of it; says so under ``name`` where they do not."""
    lost = scope_unattributed_share.read(trace, spans, counters, cell)
    if lost > UNATTRIBUTED_LIMIT:
        cell["say"]("%s: %.3f %% of the busy time carries no scope (limit "
                    "%.1f %%): not read" % (name, lost, UNATTRIBUTED_LIMIT))
        return False
    return True


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None or not attributed(trace, spans, counters, cell,
                                      "mla_time_share"):
        return None
    cell["say"]("mla_time_share: %.6f s under latent_attention" % took)
    return 100.0 * took / trace.busy_s
