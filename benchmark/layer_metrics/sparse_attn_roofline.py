"""Kernels: the least time the chip could take for attention over the
SELECTED pairs (``benchmark/flops/keye_vl2_train.py:sparse_attention`` a
layer: ``topk`` keys a query past the first ``topk``, whatever a kernel
computes and drops) over the time the ``flash_dsa_*`` kernels took.  A
kernel that sweeps the whole causal triangle under a mask reads at most the
selected share of what a full layer's kernel reads (23.4 % at S = 16,384 and
2,048): a low reading is the truth."""

from ..flops import keye_vl2_train
from . import indexer_scores_roofline

KERNELS = {"fwd": ("flash_dsa_fwd",),
           "bwd": ("flash_dsa_bwd_fused", "flash_dsa_bwd_dq",
                   "flash_dsa_bwd_dkv")}
# a two-sweep backward is two events a layer: count the dq sweep's
CALLS = {"fwd": KERNELS["fwd"],
         "bwd": ("flash_dsa_bwd_fused", "flash_dsa_bwd_dq")}


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    return indexer_scores_roofline.roofline(
        trace, cell, "sparse_attn_roofline", KERNELS,
        keye_vl2_train.sparse_attention(
            cell["config"]["model"], *indexer_scores_roofline.shapes(cell)),
        CALLS)
