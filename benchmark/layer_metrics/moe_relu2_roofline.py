"""Kernels: the least time the chip could take for the ROUTED ungated expert
matmuls over the rows that meet a HELD expert, over the time the
grouped-matmul kernels took (``gmm.<n>`` / ``tgmm.<n>`` in the trace, as
``moe_held_roofline`` reads them).  Required:
``benchmark/flops/nemotron_h_train.py:expert_matmuls`` per sparse layer and
step, two matmuls a row at the published width 1,856 over the rows uniform
routing brings 16 of 128 experts (T * k * 16 / 128 = 12,288, 768 an expert),
whatever static number of rows the program's step gathered and whatever its
tiles pad the width to.  The steps in the traced stretch come from the
trace: a sparse layer's backward runs ``tgmm`` twice a step."""

from ..flops import nemotron_h_train
from ..harness import build, flops
from . import moe_time_share
from .moe_roofline import KERNELS, TGMM_PER_LAYER_AND_STEP


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(KERNELS)
    model, config = cell["config"]["model"], cell["config"]
    if took <= 0 or "hybrid_override_pattern" not in model:
        return None                 # no such kernels, or another's cell
    layers = nemotron_h_train.layer_counts(model)[1]
    steps = (trace.count_of_kernels(("tgmm",))
             / (TGMM_PER_LAYER_AND_STEP * layers))
    if steps <= 0:
        return None
    step_tokens = build.units_per_step(config, cell["dims"]) / cell["chips"]
    need = nemotron_h_train.expert_matmuls(model, step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    scoped = moe_time_share.seconds(trace, cell)
    cell["say"]("moe_relu2_roofline: least %.6f s (%.6f s a layer and step, "
                "%s binds, %.3f steps traced, %g gmm and %g tgmm calls) of "
                "%.6f s in gmm / tgmm; %s s under scopes moe + router"
                % (least, per_layer, binds, steps,
                   trace.count_of_kernels(("gmm",)),
                   trace.count_of_kernels(("tgmm",)), took,
                   "no" if scoped is None else "%.6f" % scoped))
    return 100.0 * least / took
