"""Collectives: ``collective_exposed_share``'s reading (the part of the
collective intervals during which no other operation runs on that device,
over the traced window, mean over devices) for a cell whose layers exchange
rows, with the EXCHANGE AMONG the collectives: the gradients' ``all-reduce``
and the expert-parallel ``all_to_all`` out and back.

The chip's trace names the exchange's instructions after the JAX primitive
they came from, ``all_to_all.<n>`` (my four-chip runs, PR 73), and the
harness's pattern (``trace_reduce.COLLECTIVE``) knows the opcode's spelling
alone: read through it, the exchange's transfers count as compute that an
all-reduce may hide behind.  So this reader takes the run's trace file once
more, spells those instructions as their opcode (``all-to-all.<n>``) and
hands it to the harness's own reduction: the intervals, the joining of a
``-start`` with its ``-done`` and the subtraction are ``trace_reduce``'s,
not a second copy.  ``ep_collective_share`` reads the same reduction."""

import glob
import os
import re

from ..harness import trace_reduce

SPELLED = re.compile(r"all_to_all")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_kept = {}              # the trace file -> its reduction, respelled


def respelled(trace):
    """``trace_reduce.Reduced`` of the neutral form ``trace``, the
    exchange's instructions under their opcode's name."""
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                event[0] = SPELLED.sub("all-to-all", event[0])
    return trace_reduce.Reduced(trace)


def newest_trace(cell, bench=BENCH):
    """The trace file of the run that is being reduced: the newest under
    ``<benchmark>/out/<configuration>.*/trace`` (``cellrun`` writes a cell's
    there before it calls the readers)."""
    found = [trace_reduce.find_xplane(d) for d in glob.glob(os.path.join(
        bench, "out", cell["config"]["name"] + ".*", "trace"))]
    return max(filter(None, found), key=os.path.getmtime, default=None)


def again(trace, cell):
    """The run's trace file reduced once more with the exchange among the
    collectives (kept for the second reader that asks), or None where there
    is no trace to read."""
    path = newest_trace(cell) if trace else None
    if not path:
        return None
    if path not in _kept:
        _kept.clear()
        _kept[path] = respelled(trace_reduce.load_xplane(path))
    return _kept[path] or None


def read(trace, spans, counters, cell):
    wide = again(trace, cell)
    if not wide or wide.collective_s <= 0:
        return None
    cell["say"]("ep_collective_exposed_share: %.6f s of the %.6f s inside "
                "collectives (all-reduce and all-to-all) with nothing else "
                "running, of a window of %.6f s; by the harness's pattern "
                "%.6f s of %.6f s"
                % (wide.collective_exposed_s, wide.collective_s,
                   wide.window_s, trace.collective_exposed_s,
                   trace.collective_s))
    return 100.0 * wide.collective_exposed_s / wide.window_s
