"""Model code: device time under ``indexer`` + ``indexer_select`` +
``indexer_kl``, all phases, over the device's busy time, in a stack whose
full layers alone have an indexer (64 heads of 128 off the query latent):
what SELECTING costs, beside attending (``mla_dsa_time_share``).  Read only
where the program has the full layers' own scope ``mla_dsa``: a program
without it (the parent commit's) reads nothing."""

from . import dsa_time_share


def read(trace, spans, counters, cell):
    if dsa_time_share.seconds(trace, cell, ("mla_dsa",)) is None:
        return None
    return dsa_time_share.share(trace, spans, counters, cell,
                                "indexer64_time_share", dsa_time_share.INDEXER)
