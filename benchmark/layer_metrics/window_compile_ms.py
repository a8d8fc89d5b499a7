"""Train driver / compile: milliseconds of the measured window inside a
``trace``, ``lower`` or ``backend`` record of the program's compile ledger
(their union): whatever the program traced, lowered, compiled or loaded
while the window ran.  ``recompiles_in_window`` hears backend compiles only;
a trainer whose ``run_steps`` is traced again on the state a step returned
(it finds its executable, so no backend event) shows here."""

from ..harness import window_time


def read(trace, spans, counters, cell):
    got = window_time.account(cell)
    if got is None:
        return None
    records = window_time.compile_records(cell)
    cell["say"]("window_compile_ms: %.3f ms as one union, %d records "
                "(%s); the longest:"
                % (window_time.ms(got["compile_s"]), len(records),
                   ", ".join("%d %s" % (sum(1 for r in records
                                            if r["kind"] == k), k)
                             for k in window_time.COMPILE_KINDS)))
    for r in records[:window_time.LONGEST]:
        cell["say"]("  " + window_time.describe(r, cell["t0"]))
    return window_time.ms(got["compile_s"])
