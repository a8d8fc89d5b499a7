"""Collectives: the least time a chip's interconnect could take to send the
rows the expert-parallel exchange REQUIRES
(``benchmark/flops/kanana2_train.py:exchange_bytes``: a row a (token,
expert) pair that meets another chip's expert under uniform routing, out and
back, forward and backward, whatever number of slots the program's buffers
have) at the chip's published ``ici_bits_per_s``, over the time inside
``all-to-all`` operations.  The steps in the traced stretch come from the
trace (``tgmm`` runs twice a sparse layer and step).  A recomputed forward's
exchange, the unused slots of a round and latency all read as distance from
100 %."""

from ..flops import kanana2_train
from ..harness import build
from . import ep_all_to_all_share
from .moe_roofline import TGMM_PER_LAYER_AND_STEP


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took, events = ep_all_to_all_share.seconds(trace)
    model, config = cell["config"]["model"], cell["config"]
    sparse = model["num_hidden_layers"] - model["first_k_dense_replace"]
    steps = (trace.count_of_kernels(("tgmm",))
             / (TGMM_PER_LAYER_AND_STEP * sparse))
    if took <= 0 or steps <= 0:
        return None
    chips = cell["chips"]
    step_tokens = build.units_per_step(config, cell["dims"]) / chips
    need = kanana2_train.exchange_bytes(model, step_tokens, chips)
    per_layer = need * 8.0 / cell["peaks"]["ici_bits_per_s"]
    least = per_layer * sparse * steps
    cell["say"]("ep_all_to_all_roofline: least %.6f s (%.3f MB and %.6f s a "
                "layer and step, %.3f steps traced) of %.6f s in %g "
                "all-to-all operations a device"
                % (least, need / 1e6, per_layer, steps, took, events))
    return 100.0 * least / took
