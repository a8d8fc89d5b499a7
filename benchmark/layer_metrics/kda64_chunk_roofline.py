"""Kernels: the least time the chip could take for the delta rule the job
requires at 64 heads of 128 (``benchmark/flops/solar_open2_train.py:
delta_rule`` a KDA layer and step: q, k, v, g, beta in and o out, forward
and backward, a kept state a chunk; counted the same whatever implements it
and whatever the strengths' range) over the device seconds under the
program's scope ``kda_chunk``.  That time holds the forward that remat runs
a second time, the chunked form's solve and every copy an implementation
makes, none of which is in the requirement; the decays are the vector and
exponent units', for which ``harness/peaks.py`` has no peak and none is
invented: a low reading is the truth.  The steps in the traced stretch are
counted from the trace (``kda64_time_share.steps_traced``)."""

from ..flops import solar_open2_train
from ..harness import flops
from . import kda64_time_share, kda_time_share


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = kda_time_share.seconds(trace, cell, (kda_time_share.CHUNK,))
    if took is None:
        return None                 # a program without the scope
    layers, _, steps, step_tokens = kda64_time_share.steps_traced(trace, cell)
    if steps <= 0:
        return None
    need = solar_open2_train.delta_rule(cell["config"]["model"], step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    cell["say"]("kda64_chunk_roofline: least %.6f s (%.6f s a layer and "
                "step, %s binds, %d layers, %.3f steps traced) of %.6f s "
                "under kda_chunk" % (least, per_layer, binds, layers, steps,
                                     took))
    return 100.0 * least / took
