"""Model code: device time of the FULL layers' latent attention without
their indexer, all phases, over the device's busy time: the program's scope
``mla_dsa`` (both low-rank chains and their norms, the rescale, the rotation
and the assembly of the heads' lanes, the output projection) and, inside it,
``sparse_attn`` (the flash calls under the selection's mask and the pass
with the statistic known).  The gate (``attn_gate``) is both kinds' and in
neither share.  ``moe_time_share``'s rule on unattributed time; a program
without the scope (the parent commit's) reads nothing."""

from . import dsa_time_share

SCOPES = ("mla_dsa", "sparse_attn")


def read(trace, spans, counters, cell):
    if dsa_time_share.seconds(trace, cell, SCOPES[:1]) is None:
        return None
    return dsa_time_share.share(trace, spans, counters, cell,
                                "mla_dsa_time_share", SCOPES)
