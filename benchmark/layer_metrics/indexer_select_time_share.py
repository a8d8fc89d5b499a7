"""Model code: device time under ``indexer_select`` (the k-th largest score
of every row: 32 counting passes over the scores), all phases, over the
device's busy time.  A program without the scope reads nothing."""

from . import dsa_time_share


def read(trace, spans, counters, cell):
    return dsa_time_share.share(trace, spans, counters, cell,
                                "indexer_select_time_share",
                                (dsa_time_share.SELECT,))
