"""Kernels: the least time the chip could take for the power retention the
job requires (``benchmark/flops/brumby_train.py:retention`` a layer and
step: the cheaper exact form's FLOPs against the MXU's peak, or the bytes
that must move where they bind) over the device time under the program's
scope ``retention``, all phases.  The scope also holds the projections
around the operator and the forward that remat runs a second time, which
are in the time and not in the requirement: the line it says gives the
kernels' own seconds by name beside the scope's, so that what the operator
takes and what surrounds it can be told apart.  The steps in the traced
stretch are counted from the trace: ``power_retention_bwd`` runs once a
layer and step."""

from ..flops import brumby_train
from ..harness import build, flops
from . import retention_time_share

FORWARD, BACKWARD = "power_retention_fwd", "power_retention_bwd"


def steps_traced(trace, model):
    return trace.count_of_kernels((BACKWARD,)) / model["num_hidden_layers"]


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = retention_time_share.seconds(trace, cell)
    model, config = cell["config"]["model"], cell["config"]
    steps = steps_traced(trace, model)
    if not took or steps <= 0:
        return None
    step_tokens = build.units_per_step(config, cell["dims"]) / cell["chips"]
    need = brumby_train.retention(model, step_tokens, cell["dims"]["S"])
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * model["num_hidden_layers"] * steps
    cell["say"]("retention_roofline: least %.6f s (%.6f s a layer and step, "
                "%s binds, %d layers, %.3f steps traced) of %.6f s under "
                "scope retention; kernels: %s %.6f s in %g calls, %s %.6f s "
                "in %g calls"
                % (least, per_layer, binds, model["num_hidden_layers"], steps,
                   took, FORWARD, trace.seconds_of_kernels((FORWARD,)),
                   trace.count_of_kernels((FORWARD,)), BACKWARD,
                   trace.seconds_of_kernels((BACKWARD,)),
                   trace.count_of_kernels((BACKWARD,))))
    return 100.0 * least / took
