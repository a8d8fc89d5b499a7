"""Model code: device time under the program's scope ``attention``
(projections, the q/k norm and rotation, the flash kernels under the
block-diffusion rule, ``wo``), all phases, over the device's busy time, in a
stack that runs a noised and a clean copy of each sequence side by side: what
attending 2 S rows under the three-part mask costs.  ``moe_time_share``'s
rule on unattributed time; a program without the scopes reads nothing."""

from ..harness import scope_time
from . import scope_unattributed_share
from .moe_time_share import UNATTRIBUTED_LIMIT


def scope_share(trace, spans, counters, cell, name, scope):
    """Per cent of the busy time under ``scope``, all phases; nothing where
    more than the limit carries no scope, and said under ``name``."""
    share = scope_time.share(trace, cell, lambda phase, s: s == scope)
    if share is None:
        return None
    lost = scope_unattributed_share.read(trace, spans, counters, cell)
    if lost > UNATTRIBUTED_LIMIT:
        cell["say"]("%s: %.3f %% of the busy time carries no scope (limit "
                    "%.1f %%): not read" % (name, lost, UNATTRIBUTED_LIMIT))
        return None
    cell["say"]("%s: %.3f %% under %s; %.3f %% of the busy time carries no "
                "scope" % (name, share, scope, lost))
    return share


def read(trace, spans, counters, cell):
    return scope_share(trace, spans, counters, cell, "bd_attn_time_share",
                       "attention")
