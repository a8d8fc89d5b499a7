"""Kernels: the least time the chip could take for the ROUTED expert
matmuls over the rows that meet a HELD expert, over the time the
grouped-matmul kernels took (``gmm.<n>`` / ``tgmm.<n>`` in the trace, as
``moe_held_roofline`` reads them).  Required:
``benchmark/flops/sdar_train.py:expert_matmuls`` per layer and step, the rows
uniform routing brings 32 of 128 experts from BOTH copies of the step's
tokens (2 T * k * 32 / 128 = 32,768, 1,024 an expert), whatever static
number of rows the program's step gathered.  The steps in the traced stretch
come from the trace: a layer's backward runs ``tgmm`` twice a step."""

from ..flops import sdar_train
from ..harness import build, flops
from . import moe_time_share
from .moe_roofline import KERNELS, TGMM_PER_LAYER_AND_STEP


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(KERNELS)
    model, config = cell["config"]["model"], cell["config"]
    layers = model["num_hidden_layers"]
    steps = (trace.count_of_kernels(("tgmm",))
             / (TGMM_PER_LAYER_AND_STEP * layers))
    if took <= 0 or steps <= 0:
        return None
    step_tokens = build.units_per_step(config, cell["dims"]) / cell["chips"]
    need = sdar_train.expert_matmuls(model, step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    scoped = moe_time_share.seconds(trace, cell)
    cell["say"]("moe_held32of128_roofline: least %.6f s (%.6f s a layer and "
                "step, %s binds, %.3f steps traced, %g gmm and %g tgmm "
                "calls) of %.6f s in gmm / tgmm; %s s under scopes moe + "
                "router"
                % (least, per_layer, binds, steps,
                   trace.count_of_kernels(("gmm",)),
                   trace.count_of_kernels(("tgmm",)), took,
                   "no" if scoped is None else "%.6f" % scoped))
    return 100.0 * least / took
