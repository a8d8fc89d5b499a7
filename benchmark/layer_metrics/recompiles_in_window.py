"""Train driver / compile: XLA backend compiles (or cache loads) between
the first and the last instant of the measured window.  Must be 0; any
other count also makes the run ``correct: false``."""


def read(trace, spans, counters, cell):
    return float(cell["recompiles"])
