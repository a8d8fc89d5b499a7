"""Train driver / set-up: seconds inside XLA backend events (a compile, or
a load from the persistent cache and its deserialisation), from
``jax.monitoring`` through the program's compile ledger, as a union.  From
the start of ``bench.build`` to the window, the benchmark's own checks
(``harness/setup_time.CHECKS``) left out."""

from ..harness import setup_time

ROWS = 10


def read(trace, spans, counters, cell):
    got = setup_time.split(spans, cell)
    if got is None:
        return None
    say = cell["say"]
    say("compile ledger: %d records in set-up (%d events heard in the "
        "process so far); %d programs compiled, %d loaded from the cache, "
        "which saved %.3f s of compiling"
        % (len(got["records"]), got["heard"], got["compiled"], got["loaded"],
           got["saved_s"]))
    say("  %-28s %-14s %4s %9s %9s %9s  compiled/loaded"
        % ("program", "phase", "n", "trace", "lower", "backend"))
    for row in setup_time.ledger().table(got["records"])[:ROWS]:
        say("  %-28s %-14s %4d %9.4f %9.4f %9.4f  %d/%d" % (
            (row["name"] or "?")[:28], row["parent"] or "-", row["n"],
            row["trace_s"], row["lower_s"], row["backend_s"],
            row["compiled"], row["loaded"]))
    return got["backend_s"]
