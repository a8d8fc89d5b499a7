"""Train driver / set-up: programs the XLA backend COMPILED (the persistent
cache did not serve them) from the start of ``bench.build`` to the window,
the benchmark's own checks (``harness/setup_time.CHECKS``) left out.  0 in a
cached run: the one number that says a ``setup_s`` was a first set-up."""

from ..harness import setup_time


def read(trace, spans, counters, cell):
    got = setup_time.split(spans, cell)
    return None if got is None else float(got["compiled"])
