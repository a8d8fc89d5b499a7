"""Kernels: the least time the chip could take for the causal grouped-query
attention the shapes require at 64 query heads of 128 on 8 key/value heads
(a group of 8: ``benchmark/flops/solar_open2_train.py:flash_gqa``, the pairs
the causal mask lets through a QUERY head, q and o at the query heads, k
and v at the key/value heads: a K or V repeated in HBM would not be counted,
and would show) over the time the flash kernels took.  Each ``flash_fwd``
event is the layer's forward over the chip's batch (under remat the
backward pass runs it a second time, and each run counts), each
``flash_bwd_fused`` or ``flash_bwd_dq`` its backward (a ``flash_bwd_dkv``
is in the time), counted by call as ``flash_gqa64_roofline`` counts
them."""

from ..flops import solar_open2_train
from ..harness import flops
from .swa_flash_time_share import FULL


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(FULL)
    if took <= 0:
        return None
    dims = cell["dims"]
    need = solar_open2_train.flash_gqa(
        cell["config"]["model"],
        dims["B"] // cell["traffic"]["mesh"].get("dp", 1), dims["S"])
    least, said = 0.0, []
    for part, kernels in (("fwd", ("flash_fwd",)),
                          ("bwd", ("flash_bwd_fused", "flash_bwd_dq"))):
        sec, binds = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        calls = trace.count_of_kernels(kernels)
        least += sec * calls
        said.append("%s %g calls x %.6f s (%s)" % (part, calls, sec, binds))
    cell["say"]("flash_gqa64q8_roofline: least %.6f s of %.6f s taken; %s"
                % (least, took, "; ".join(said)))
    return 100.0 * least / took
