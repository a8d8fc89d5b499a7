"""Model code: device time under ``indexer`` + ``indexer_select`` +
``indexer_kl``, all phases, over the device's busy time: what SELECTING
costs, beside attending (``dsa_time_share`` less this is the masked flash
calls).  A program without the scopes reads nothing."""

from . import dsa_time_share


def read(trace, spans, counters, cell):
    return dsa_time_share.share(trace, spans, counters, cell,
                                "indexer_time_share", dsa_time_share.INDEXER)
