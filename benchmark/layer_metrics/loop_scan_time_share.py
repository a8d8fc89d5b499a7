"""Model code: device time under the program's scopes ``loop_scan`` (a
looped stack's passes themselves: the stream carried from pass to pass, the
exits kept, the running sum of the stacked gradients over the passes) and
``layer_scan`` (the scan over the stacked layers: its slices of each layer's
leaves, the activations it keeps for the backward pass, the gradients it
stacks), all phases, over the device's busy time: what running the layers
several times over one set of leaves costs beyond the layers, which keep
their own scopes further in.  ``moe_time_share``'s rule on unattributed time
(``mla_time_share.attributed``); a program without ``loop_scan`` (the parent
commit's, any plain stack's) reads nothing."""

from . import mla_time_share

SCOPES = ("loop_scan", "layer_scan")


def seconds(trace, cell, scopes):
    """Device seconds under each of ``scopes``, or None without a table or
    where the FIRST of them is not in it."""
    each = [mla_time_share.seconds(trace, cell, scope) or 0.0
            for scope in scopes]
    return each if each[0] else None


def read_scopes(trace, spans, counters, cell, name, scopes):
    """Per cent of the busy time under ``scopes``, said under ``name``."""
    each = seconds(trace, cell, scopes)
    if each is None or not mla_time_share.attributed(
            trace, spans, counters, cell, name):
        return None
    cell["say"]("%s: %s" % (name, ", ".join(
        "%.6f s under %s" % (s, scope) for s, scope in zip(each, scopes))))
    return 100.0 * sum(each) / trace.busy_s


def read(trace, spans, counters, cell):
    return read_scopes(trace, spans, counters, cell, "loop_scan_time_share",
                       SCOPES)
