"""Collectives: ``collective_share``'s reading (device time inside
collective intervals over the traced window, mean over devices) for a cell
whose layers exchange rows: the gradients' ``all-reduce`` AND the
expert-parallel ``all_to_all``, which the harness's pattern does not know by
the name the chip's trace gives it (``ep_collective_exposed_share`` says why
and makes the reduction this reads).  Beside ``ep_all_to_all_share`` (the
exchange's transfers alone) it says what the gradients' all-reduce over the
replicated leaves costs, hidden or not."""

from . import ep_collective_exposed_share


def read(trace, spans, counters, cell):
    wide = ep_collective_exposed_share.again(trace, cell)
    if not wide or wide.collective_s <= 0:
        return None
    return 100.0 * wide.collective_s / wide.window_s
