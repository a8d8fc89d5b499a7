"""Kernels: the least time the chip could take for a full layer's attention
over the SELECTED pairs at the PUBLISHED widths (32 held heads, q and k 192,
v and o 128: ``benchmark/flops/dots3_train.py:flash``; the 64 zero lanes a
head of q or k is carried with and the pairs a kernel computes and drops are
not in it) over the time its kernels took: ``flash_dsa_fwd`` (the masked
online forward that gives the statistic) and ``dsa_attend_kl_fwd`` (the pass
with the statistic known, which makes the layer's output: once in the
forward, once more under remat), each a forward's worth, and the masked
backward ``flash_dsa_bwd_*``.  A kernel that sweeps the whole causal
triangle under a mask reads at most the selected share of a full sweep's
(43.7 % at S = 8,192 and 2,048): a low reading is the truth."""

from ..flops import dots3_train
from . import indexer_scores_roofline

KERNELS = {"fwd": ("flash_dsa_fwd", "dsa_attend_kl_fwd"),
           "bwd": ("flash_dsa_bwd_fused", "flash_dsa_bwd_dq",
                   "flash_dsa_bwd_dkv")}
# a two-sweep backward is two events a layer: count the dq sweep's
CALLS = {"fwd": KERNELS["fwd"],
         "bwd": ("flash_dsa_bwd_fused", "flash_dsa_bwd_dq")}


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    return indexer_scores_roofline.roofline(
        trace, cell, "mla_dsa_flash_roofline", KERNELS,
        dots3_train.flash(cell["config"]["model"],
                          *indexer_scores_roofline.shapes(cell),
                          sliding=False), CALLS)
