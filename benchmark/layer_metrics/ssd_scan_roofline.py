"""Kernels: the least time the chip could take for the chunked scans the job
requires (``benchmark/flops/nemotron_h_train.py:ssd_scan`` a layer and step:
the four products at the published chunk against the MXU's peak, or the
operands' bytes at their stored widths against HBM's where they bind) over
the two kernels' OWN device seconds, by name.  The kernels' time holds the
forward that remat runs a second time and the states the forward keeps for
the backward, neither of which is in the requirement; the decays' block (an
exponential a pair of tokens and head) is the vector and exponent units',
for which ``harness/peaks.py`` has no peak and none is invented: a low
reading is the truth, and the line it says gives the kernels' seconds beside
the scope's.  The steps in the traced stretch are counted from the trace:
``ssd_scan_bwd`` runs once a Mamba-2 layer and step."""

from ..flops import nemotron_h_train
from ..harness import build, flops
from . import mamba2_time_share
from .mamba2_time_share import BACKWARD


def layer_steps(trace, cell):
    """(Mamba-2 layers, steps in the traced stretch, tokens a step and
    chip): ``ssd_scan_bwd`` runs once a Mamba-2 layer and step."""
    layers = nemotron_h_train.layer_counts(cell["config"]["model"])[0]
    return (layers, trace.count_of_kernels((BACKWARD,)) / layers,
            build.units_per_step(cell["config"], cell["dims"])
            / cell["chips"])


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = mamba2_time_share.kernel_seconds(trace)
    if took <= 0:
        return None                 # a program without the kernels
    layers, steps, step_tokens = layer_steps(trace, cell)
    if steps <= 0:
        return None
    need = nemotron_h_train.ssd_scan(cell["config"]["model"], step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    cell["say"]("ssd_scan_roofline: least %.6f s (%.6f s a layer and step, "
                "%s binds, %d layers, %.3f steps traced) of %.6f s in the "
                "kernels: %s; %.6f s under mamba2 + ssd_scan"
                % (least, per_layer, binds, layers, steps, took,
                   mamba2_time_share.said_kernels(trace),
                   mamba2_time_share.seconds(trace, cell) or 0.0))
    return 100.0 * least / took
