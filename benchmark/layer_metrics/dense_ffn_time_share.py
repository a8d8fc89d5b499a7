"""Model code: device time under the program's scope ``mlp``, all phases,
over the device's busy time, in a sparse stack whose LEADING layer alone
carries a dense gated FFN (13,824 wide: 28 % of the cell's required FLOPs in
one layer).  Read only where the program has the full layers' own scope
``mla_dsa``: a program without it (the parent commit's) reads nothing."""

from . import dsa_time_share


def read(trace, spans, counters, cell):
    if dsa_time_share.seconds(trace, cell, ("mla_dsa",)) is None:
        return None
    return dsa_time_share.share(trace, spans, counters, cell,
                                "dense_ffn_time_share", ("mlp",))
