"""Kernels: the least time the chip could take for the causal attention the
shapes require after the latent is expanded (32 heads of 128 on 32
key/value heads: every head has a key and a value of its own in HBM) over
the time the flash kernels took.  Each ``flash_fwd`` event is one layer's
forward over the chip's batch (under remat the backward pass runs it a
second time, and each run counts), each ``flash_bwd_fused`` or
``flash_bwd_dq`` one layer's backward (a ``flash_bwd_dkv`` is in the time),
counted by call as ``flash_gqa64_roofline`` counts them.
``benchmark/flops/flash_attention_gqa.py`` gives the FLOPs (the pairs the
causal mask lets through) and bytes (q, o, k and v at all 32 heads: the
expanded keys and values are what these kernels must read; what a call that
read the 320-wide latent instead would save shows in
``mla_outside_flash_share`` and the bytes here, not in this share)."""

from ..flops import flash_attention_gqa, mistral4_train
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
        model["num_key_value_heads"], mistral4_train.head_dim(model))
    least, said = 0.0, []
    for part, kernels in (("fwd", ("flash_fwd",)),
                          ("bwd", ("flash_bwd_fused", "flash_bwd_dq"))):
        sec, binds = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        calls = trace.count_of_kernels(kernels)
        least += sec * calls
        said.append("%s %g calls x %.6f s (%s)" % (part, calls, sec, binds))
    cell["say"]("mla_flash_roofline: least %.6f s of %.6f s taken; %s"
                % (least, took, "; ".join(said)))
    return 100.0 * least / took
