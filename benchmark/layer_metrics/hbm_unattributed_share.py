"""Device / memory: of ``bytes_in_use`` on the fullest chip after the
window, the share that no owner of the program holds: the instrument's own
coverage.  Over 5 %, do not believe ``step_state_gb`` and
``step_batches_gb``."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    if got is None or not got["in_use"]:
        return None
    return 100.0 * (got["in_use"] - got["owned"]) / got["in_use"]
