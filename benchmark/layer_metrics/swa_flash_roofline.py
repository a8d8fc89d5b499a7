"""Kernels: the least time the chip could take for the attention the shapes
require, full and windowed layers apart, over the time the flash kernels
took.

Each ``flash_fwd`` event is one FULL layer's forward over the chip's batch
(under remat the backward pass runs it a second time, and each run counts),
each ``flash_bwd_dq`` (or ``flash_bwd_fused``) one full layer's backward;
the ``flash_swa_*`` events are the windowed layers' (a windowed call carries
a name of its own so that it can be counted here).
``benchmark/flops/flash_attention_gqa.py`` gives each kind's FLOPs (the
pairs its mask lets through) and bytes (q, o at the query heads, k, v at
the key/value heads)."""

from ..flops import flash_attention_gqa
from ..harness import flops
from .swa_flash_time_share import FULL, WINDOWED


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    took = trace.seconds_of_kernels(FULL + WINDOWED)
    if took <= 0:
        return None
    model, dims = cell["config"]["model"], cell["dims"]
    batch = dims["B"] // cell["traffic"]["mesh"].get("dp", 1)
    least, said = 0.0, []
    for kind, prefix, window in (("full", "flash_", None),
                                 ("windowed", "flash_swa_",
                                  model["sliding_window_size"])):
        need = flash_attention_gqa.required(
            batch, dims["S"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"], window)
        for part, kernels in (("fwd", ("fwd",)),
                              ("bwd", ("bwd_fused", "bwd_dq"))):
            sec, binds = flops.least_seconds(
                need[part]["flops"], need[part]["bytes"], cell["peaks"])
            calls = trace.count_of_kernels([prefix + k for k in kernels])
            least += sec * calls
            said.append("%s %s %g calls x %.6f s (%s)"
                        % (kind, part, calls, sec, binds))
    cell["say"]("swa_flash_roofline: least %.6f s of %.6f s taken (%.6f s "
                "in the windowed kernels); %s"
                % (least, took, trace.seconds_of_kernels(WINDOWED),
                   "; ".join(said)))
    return 100.0 * least / took
