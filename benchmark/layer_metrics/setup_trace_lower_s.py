"""Train driver / set-up: seconds the program spent tracing Python and
lowering to MLIR, from ``jax.monitoring``'s events through the program's
compile ledger, as a union (trace events nest).  The part of set-up that no
compile cache serves.  From the start of ``bench.build`` to the window, the
benchmark's own checks (``harness/setup_time.CHECKS``) left out."""

from ..harness import setup_time


def read(trace, spans, counters, cell):
    got = setup_time.split(spans, cell)
    return None if got is None else got["trace_lower_s"]
