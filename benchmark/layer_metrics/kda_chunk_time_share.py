"""Kernels: device time under the program's scope ``kda_chunk`` (the delta
rule itself: the chunks' own parts, the solve, the scan that carries the
state; forward, recomputed and backward, whatever implements it, Pallas or
XLA), over the device's busy time.  A program without the scope reads
nothing."""

from . import kda_time_share


def read(trace, spans, counters, cell):
    took = kda_time_share.seconds(trace, cell, (kda_time_share.CHUNK,))
    if took is None:
        return None
    cell["say"]("kda_chunk_time_share: %.6f s under kda_chunk" % took)
    return 100.0 * took / trace.busy_s
