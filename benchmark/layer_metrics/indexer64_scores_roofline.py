"""Kernels: the least time the chip could take for the indexer's scores at
64 heads of 128 (``benchmark/flops/dots3_train.py:indexer_scores`` a layer:
every causal pair, every indexer head, the scores written once in float32)
over the time the ``indexer_scores_fwd`` / ``indexer_scores_bwd`` kernels
took, as ``indexer_scores_roofline`` reads Keye's 16 heads of 64.  A head of
128 fills the MXU's depth; the kernels compute whole tiles along the
diagonal where the requirement is the triangle."""

from ..flops import dots3_train
from . import indexer_scores_roofline


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    return indexer_scores_roofline.roofline(
        trace, cell, "indexer64_scores_roofline",
        indexer_scores_roofline.KERNELS,
        dots3_train.indexer_scores(cell["config"]["model"],
                                   *indexer_scores_roofline.shapes(cell)))
