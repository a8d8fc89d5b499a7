"""Collectives: device time inside ``all-to-all`` operations (the
expert-parallel exchange's, by the instruction's name in the trace) over the
traced window, mean over devices.  ``collective_share``'s reading for one
kind of collective; a trace without one reads nothing.

The chip's trace names the instruction after the JAX primitive it came from,
``all_to_all.<n>`` (my four-chip runs, PR 73), not after its opcode
``all-to-all``: both spellings are read.  (The harness's own ``COLLECTIVE``
pattern knows the opcode's alone, so ``collective_share`` and
``collective_exposed_share`` would not see these; PERF.md section 7.)"""

import re

ALL_TO_ALL = re.compile(r"all[-_]to[-_]all")


def seconds(trace):
    """``(seconds, events)`` inside all-to-all operations, mean over
    devices."""
    n = len(trace.devices)
    took = sum(ns for d in trace.devices for name, ns in d["by_name"].items()
               if ALL_TO_ALL.search(name)) / n / 1e9
    events = sum(c for d in trace.devices for name, c in d["count"].items()
                 if ALL_TO_ALL.search(name)) / n
    return took, events


def read(trace, spans, counters, cell):
    if not trace:
        return None
    took, events = seconds(trace)
    if took <= 0:
        return None
    cell["say"]("ep_all_to_all_share: %.6f s in %g all-to-all operations a "
                "device of a window of %.6f s" % (took, events,
                                                  trace.window_s))
    return 100.0 * took / trace.window_s
