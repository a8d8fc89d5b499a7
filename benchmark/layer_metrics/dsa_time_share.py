"""Model code: device time under the program's four scopes of learned-sparse
attention, all phases, over the device's busy time: ``indexer`` (its
projections, the key's norm, rotation, the scores' kernels),
``indexer_select`` (the k-th largest score a row), ``sparse_attn`` (the
flash calls under the selection's mask) and ``indexer_kl`` (the indexer's
own loss term: the target from the main attention, the KL, its gradient).
The main q/k/v/o projections carry ``attention`` and are not in it.
``moe_time_share``'s rule on unattributed time
(``mla_time_share.attributed``); a program without the scopes (the parent
commit's) reads nothing."""

from ..harness import scope_time
from . import mla_time_share

SELECT, ATTEND = "indexer_select", "sparse_attn"
INDEXER = ("indexer", SELECT, "indexer_kl")
SCOPES = INDEXER + (ATTEND,)


def seconds(trace, cell, scopes=SCOPES):
    """Device seconds under ``scopes``, or None without them."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, at), s in table.items() if at in scopes) or None


def share(trace, spans, counters, cell, name, scopes):
    took = seconds(trace, cell, scopes)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, name):
        return None
    cell["say"]("%s: %.6f s under %s" % (name, took, " + ".join(scopes)))
    return 100.0 * took / trace.busy_s


def read(trace, spans, counters, cell):
    return share(trace, spans, counters, cell, "dsa_time_share", SCOPES)
