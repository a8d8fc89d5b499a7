"""Model code: device time under the program's scopes ``moe`` (the held
experts' dispatch, grouped matmuls and sum back) and ``router`` (the
pre-attention logits, top-k and softmax), all phases, over the device's
busy time, for a layer that holds a share of its experts.
``moe_time_share``'s reading and its rule: where more than 5 % of the busy
time carries no scope it says so and reads nothing.  The line printed here
gives the unattributed share beside it."""

from . import moe_time_share, scope_unattributed_share


def read(trace, spans, counters, cell):
    share = moe_time_share.read(trace, spans, counters, cell)
    if share is not None:
        cell["say"]("moe_held_time_share: %.3f %% under moe + router; %.3f "
                    "%% of the busy time carries no scope"
                    % (share, scope_unattributed_share.read(
                        trace, spans, counters, cell)))
    return share
