"""Kernels: the least time the chip could take for a sliding layer's
attention over the pairs inside its window of 513 at the PUBLISHED widths (16
held heads, q and k 256, v and o 128:
``benchmark/flops/dots3_train.py:flash``) over the time the windowed flash
kernels took (``flash_swa_*``: a windowed call carries a name of its own).
A window of one block and a key makes a q block sweep two kv blocks (31
steps of 512 x 512 where the band holds 4.07 M pairs): at most half of what
the kernels compute is required, and a low reading is the truth."""

from ..flops import dots3_train
from . import indexer_scores_roofline

KERNELS = {"fwd": ("flash_swa_fwd",),
           "bwd": ("flash_swa_bwd_fused", "flash_swa_bwd_dq",
                   "flash_swa_bwd_dkv")}
CALLS = {"fwd": KERNELS["fwd"],
         "bwd": ("flash_swa_bwd_fused", "flash_swa_bwd_dq")}


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    return indexer_scores_roofline.roofline(
        trace, cell, "mla_swa_flash_roofline", KERNELS,
        dots3_train.flash(cell["config"]["model"],
                          *indexer_scores_roofline.shapes(cell),
                          sliding=True), CALLS)
