"""Kernels: the least time the chip could take for the selective scans the
job requires (``benchmark/flops/jamba_train.py:selective_scan`` a layer and
step: the bytes that must move against HBM's peak, or the FLOPs against the
MXU's where they bind) over the two kernels' OWN device seconds, by name.
The kernels' time holds the forward that remat runs a second time, which is
not in the requirement.  The work is the vector unit's and the exponent
unit's, for which ``harness/peaks.py`` has no peak and none is invented: a
low reading is the truth, and the line it says gives the kernels' seconds
beside the scope's.  The steps in the traced stretch are counted from the
trace: ``selective_scan_bwd`` runs once a Mamba layer and step."""

from ..flops import jamba_train
from ..harness import build, flops
from . import mamba_time_share
from .mamba_time_share import BACKWARD, FORWARD


def layer_steps(trace, cell):
    """(Mamba layers, steps in the traced stretch, tokens a step and chip):
    ``selective_scan_bwd`` runs once a Mamba layer and step."""
    layers = jamba_train.layer_counts(cell["config"]["model"])[0]
    return (layers, trace.count_of_kernels((BACKWARD,)) / layers,
            build.units_per_step(cell["config"], cell["dims"])
            / cell["chips"])


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = mamba_time_share.kernel_seconds(trace)
    layers, steps, step_tokens = layer_steps(trace, cell)
    if took <= 0 or steps <= 0:
        return None
    need = jamba_train.selective_scan(cell["config"]["model"], step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    cell["say"]("selective_scan_roofline: least %.6f s (%.6f s a layer and "
                "step, %s binds, %d layers, %.3f steps traced) of %.6f s in "
                "the kernels: %s %.6f s in %g calls, %s %.6f s in %g calls; "
                "%.6f s under mamba + selective_scan"
                % (least, per_layer, binds, layers, steps, took, FORWARD,
                   trace.seconds_of_kernels((FORWARD,)),
                   trace.count_of_kernels((FORWARD,)), BACKWARD,
                   trace.seconds_of_kernels((BACKWARD,)),
                   trace.count_of_kernels((BACKWARD,)),
                   mamba_time_share.seconds(trace, cell) or 0.0))
    return 100.0 * least / took
