"""Kernels: the least time the chip could take for the expert matmuls the
job requires over the time the grouped-matmul kernels took.

The expert matmuls are JAX's own Pallas ``megablox`` kernels, which the
trace names ``gmm.<n>`` (rows times their group's weights: the forward, the
forward recomputed under remat, and the backward's dX) and ``tgmm.<n>`` (the
backward's dW), so they are read by name, as ``flash_roofline`` reads its
kernels; a program whose grouped matmul is another primitive reads nothing.
Required: ``benchmark/flops/moe_decoder_train.py:expert_matmuls`` per layer
and step (routed rows only).  The steps in the traced stretch come from the
trace too: a layer's backward runs ``tgmm`` twice a step (gate/up and down),
and the recomputed forward runs none.  A lowering that multiplied every row
by every expert would read under 2 %.

What the mechanism around the kernels costs (router, sort, gathers,
combine) is ``moe_time_share``'s to show; the line printed here puts the
kernels' seconds beside the seconds under the scopes ``moe`` + ``router``."""

from ..flops import moe_decoder_train
from ..harness import build, flops
from . import moe_time_share

KERNELS = ("gmm", "tgmm")
TGMM_PER_LAYER_AND_STEP = 2


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
    need = moe_decoder_train.expert_matmuls(model, step_tokens)
    per_layer, binds = flops.least_seconds(need["flops"], need["bytes"],
                                           cell["peaks"])
    least = per_layer * layers * steps
    scoped = moe_time_share.seconds(trace, cell)
    cell["say"]("moe_roofline: least %.6f s (%.6f s a layer and step, %s "
                "binds, %.3f steps traced) of %.6f s in gmm / tgmm; %s s "
                "under scopes moe + router"
                % (least, per_layer, binds, steps, took,
                   "no" if scoped is None else "%.6f" % scoped))
    return 100.0 * least / took
