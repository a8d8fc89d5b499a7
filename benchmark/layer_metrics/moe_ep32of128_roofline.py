"""Kernels: the least time the chip could take for the ROUTED expert matmuls
of its 32 of 128 experts over the rows the FOUR chips' tokens bring them,
over the time the grouped-matmul kernels took (``gmm.<n>`` / ``tgmm.<n>`` in
the trace, as ``moe_held32of128_roofline`` reads them).  Required:
``benchmark/flops/kanana2_train.py:expert_matmuls`` per sparse layer and
step: 49,152 rows under uniform routing (T * k: each chip sends a quarter of
its own), 1,536 an expert, whatever static number of rows a round's buffers
hold.  The steps in the traced stretch come from the trace: a sparse
layer's backward runs ``tgmm`` twice a step."""

from ..flops import kanana2_train
from ..harness import build, flops
from . import moe_time_share
from .moe_roofline import KERNELS, TGMM_PER_LAYER_AND_STEP


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(KERNELS)
    model, config = cell["config"]["model"], cell["config"]
    sparse = model["num_hidden_layers"] - model["first_k_dense_replace"]
    steps = (trace.count_of_kernels(("tgmm",))
             / (TGMM_PER_LAYER_AND_STEP * sparse))
    if took <= 0 or steps <= 0:
        return None
    step_tokens = build.units_per_step(config, cell["dims"]) / cell["chips"]
    need = kanana2_train.expert_matmuls(model, step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * sparse * steps
    scoped = moe_time_share.seconds(trace, cell)
    cell["say"]("moe_ep32of128_roofline: least %.6f s (%.6f s a layer and "
                "step, %s binds, %.3f steps traced, %g gmm and %g tgmm "
                "calls) of %.6f s in gmm / tgmm; %s s under scopes moe + "
                "router"
                % (least, per_layer, binds, steps,
                   trace.count_of_kernels(("gmm",)),
                   trace.count_of_kernels(("tgmm",)), took,
                   "no" if scoped is None else "%.6f" % scoped))
    return 100.0 * least / took
