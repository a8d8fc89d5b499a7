"""Kernels: the least time the chip could take for the attention the
block-diffusion rule requires (``benchmark/flops/sdar_train.py:
blockdiff_attention``: S (S + Bd) pairs a QUERY head, q and o of both copies'
rows at the query heads, k and v at the key/value heads, each once) over the
time the flash kernels under the rule took (``flash_bd_*`` by their Pallas
``name=``).  Each ``flash_bd_fwd`` event is the layer's forward over the
chip's batch (under remat the backward pass runs it a second time, and each
run counts), each ``flash_bd_bwd_fused`` or ``flash_bd_bwd_dq`` its backward
(a ``flash_bd_bwd_dkv`` is in the time), counted by call.  Required pairs
only: the noised diagonal tiles, 0.8 % live, and every other masked-out pair
a tile computes show as time over the least.  A program without the kernels
(the parent commit's) reads nothing."""

from ..flops import sdar_train
from ..harness import flops

FORWARD = ("flash_bd_fwd",)
BACKWARD = ("flash_bd_bwd_fused", "flash_bd_bwd_dq")
ALL = FORWARD + BACKWARD + ("flash_bd_bwd_dkv",)


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(ALL)
    if took <= 0:
        return None
    dims = cell["dims"]
    need = sdar_train.blockdiff_attention(
        cell["config"]["model"],
        dims["B"] // cell["traffic"]["mesh"].get("dp", 1), dims["S"])
    least, said = 0.0, []
    for part, kernels in (("fwd", FORWARD), ("bwd", BACKWARD)):
        sec, binds = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        calls = trace.count_of_kernels(kernels)
        least += sec * calls
        said.append("%s %g calls x %.6f s (%s)" % (part, calls, sec, binds))
    cell["say"]("flash_blockdiff_roofline: least %.6f s of %.6f s taken; %s"
                % (least, took, "; ".join(said)))
    return 100.0 * least / took
