"""Model code: device time under the program's scope ``mlp`` in a LOOPED
stack (the dense gated FFN of every layer application: passes x layers of
them a step), all phases, over the device's busy time.
``loop_scan_time_share``'s rules: it reads where the program has the scope
``loop_scan`` beside ``mlp``, and nothing on a plain stack's program."""

from .loop_scan_time_share import read_scopes, seconds

SCOPES = ("mlp",)


def read(trace, spans, counters, cell):
    if seconds(trace, cell, ("loop_scan",)) is None:
        return None
    return read_scopes(trace, spans, counters, cell, "loop_mlp_time_share",
                       SCOPES)
