"""Device / memory: the run's ``peak_hbm_gb`` reading (the allocator's two
peaks on the fullest chip, added) less ``step_need_gb``: how much of the
guarded number is NOT the step.  The two peaks never stood together, and
the benchmark's own checks raise the first one.  Nothing where the backend
keeps no peaks."""

from ..harness import memory_account


def read(trace, spans, counters, cell):
    got = memory_account.account(spans, cell)
    if got is None or got["estimated"]:
        return None
    return (got["peak_bytes"] - got["need_bytes"]) / memory_account.GB
