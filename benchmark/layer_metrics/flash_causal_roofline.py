"""Kernels: ``flash_roofline``'s arithmetic for CAUSAL attention: the least
time the chip could take for the attention the shapes require (half the
FLOPs of the full square, ``benchmark/flops/flash_attention.py``) over the
time the flash kernels took.  Each ``flash_fwd`` event is one layer's
forward over the chip's share of the batch (under remat the backward pass
runs it a second time, and each run counts), each ``flash_bwd_fused`` or
``flash_bwd_dq`` one layer's backward."""

from ..flops import flash_attention
from ..harness import flops
from .flash_time_share import KERNELS


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(KERNELS)
    if took <= 0:
        return None
    model, dims = cell["config"]["model"], cell["dims"]
    need = flash_attention.required(
        dims["B"] // cell["traffic"]["mesh"].get("dp", 1), dims["S"],
        model["hidden_size"], causal=True)
    least, binds = 0.0, {}
    for part, kernels in (("fwd", ("flash_fwd",)),
                          ("bwd", ("flash_bwd_fused", "flash_bwd_dq"))):
        sec, binds[part] = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        least += sec * trace.count_of_kernels(kernels)
    cell["say"]("flash_causal_roofline: least %.6f s of %.6f s taken; "
                "binds: %s" % (least, took, binds))
    return 100.0 * least / took
