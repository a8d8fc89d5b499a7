"""Kernels: the least time the chip could take for the short convolutions
the job requires (``benchmark/flops/lfm2_train.py:short_conv`` per layer and
step: the two projections' FLOPs against the MXU's peak, or the bytes that
must move where they bind) over the device time under the program's scope
``short_conv``, all phases.  The forward that remat runs a second time is in
the time and not in the requirement, so the share cannot pass 3/4 by much;
what XLA's fusion of the gates and taps costs between the matmuls shows
here as the rest.  The steps in the traced stretch come from the trace, as
``moe_held_roofline`` counts them: an expert layer's backward runs ``tgmm``
twice a step."""

from ..flops import lfm2_train
from ..harness import build, flops
from . import short_conv_time_share
from .moe_roofline import TGMM_PER_LAYER_AND_STEP


def steps_traced(trace, model):
    return (trace.count_of_kernels(("tgmm",)) / (TGMM_PER_LAYER_AND_STEP * (
        model["num_hidden_layers"] - model["num_dense_layers"])))


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = short_conv_time_share.seconds(trace, cell)
    model, config = cell["config"]["model"], cell["config"]
    steps = steps_traced(trace, model)
    if not took or steps <= 0:
        return None
    layers = lfm2_train.layer_types(model).count("conv")
    step_tokens = build.units_per_step(config, cell["dims"]) / cell["chips"]
    need = lfm2_train.short_conv(model, step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    cell["say"]("short_conv_roofline: least %.6f s (%.6f s a layer and step, "
                "%s binds, %d layers, %.3f steps traced) of %.6f s under "
                "scope short_conv" % (least, per_layer, binds, layers, steps,
                                      took))
    return 100.0 * least / took
