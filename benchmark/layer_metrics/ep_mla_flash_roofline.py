"""Kernels: the least time the chip could take for the causal attention the
PUBLISHED widths require (32 heads whose q and k are 192 wide and whose v
and o are 128: ``benchmark/flops/kanana2_train.py:latent_attention``, the
pairs the causal mask lets through at 192 + 128, q, k, v, o and their
gradients at those widths; the 64 zero lanes a q or k head is carried with
are not in it) over the time the flash kernels took, on ONE chip's
sequences (the batch over ``dp``).  Each ``flash_fwd`` event is one layer's
forward over the chip's batch (under remat the backward pass runs it a
second time, and each run counts), each ``flash_bwd_fused`` or
``flash_bwd_dq`` one layer's backward (a ``flash_bwd_dkv`` is in the time),
counted by call as ``flash_qk192_v128_roofline`` counts them."""

from ..flops import kanana2_train
from ..harness import flops
from .swa_flash_time_share import FULL


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(FULL)
    if took <= 0:
        return None
    dims = cell["dims"]
    need = kanana2_train.latent_attention(
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
    cell["say"]("ep_mla_flash_roofline: least %.6f s of %.6f s taken; %s"
                % (least, took, "; ".join(said)))
    return 100.0 * least / took
