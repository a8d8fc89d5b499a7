"""Kernels: the least time the chip could take for the causal grouped-query
attention the shapes require at a head width of 64 (two heads a lane block,
both on one key/value head) over the time the flash kernels took.  Each
``flash_fwd`` event is one attention layer's forward over the chip's batch
(under remat the backward pass runs it a second time, and each run counts),
each ``flash_bwd_dq`` one layer's backward (its ``flash_bwd_dkv`` is in the
time). ``benchmark/flops/flash_attention_gqa.py`` gives the FLOPs (the pairs
the causal mask lets through) and bytes (q, o at the query heads, k, v at
the key/value heads: a K or V repeated in HBM would not be counted, and
would show)."""

from ..flops import flash_attention_gqa
from ..harness import flops
from .swa_flash_time_share import FULL


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(FULL)
    if took <= 0:
        return None
    model, dims = cell["config"]["model"], cell["dims"]
    heads = model["num_attention_heads"]
    need = flash_attention_gqa.required(
        dims["B"] // cell["traffic"]["mesh"].get("dp", 1), dims["S"], heads,
        model["num_key_value_heads"], model["hidden_size"] // heads)
    least, said = 0.0, []
    for part, kernels in (("fwd", ("flash_fwd",)),
                          ("bwd", ("flash_bwd_fused", "flash_bwd_dq"))):
        sec, binds = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        calls = trace.count_of_kernels(kernels)
        least += sec * calls
        said.append("%s %g calls x %.6f s (%s)" % (part, calls, sec, binds))
    cell["say"]("flash_gqa64_roofline: least %.6f s of %.6f s taken; %s"
                % (least, took, "; ".join(said)))
    return 100.0 * least / took
