"""Kernels: the least time the chip could take for the indexer's scores the
shapes require (``benchmark/flops/keye_vl2_train.py:indexer_scores`` a layer:
every causal pair, every indexer head, the scores written once in float32)
over the time the ``indexer_scores_fwd`` / ``indexer_scores_bwd`` kernels
took.  Each forward event is one layer's (under remat the backward pass runs
it a second time, and each run counts), each backward event one layer's.  A
head of 64 fills half the MXU's depth: a low reading is the truth."""

from ..flops import keye_vl2_train
from ..harness import flops

KERNELS = {"fwd": ("indexer_scores_fwd",), "bwd": ("indexer_scores_bwd",)}


def roofline(trace, cell, name, kernels, need, calls_of=None):
    """100 x the least seconds of ``need`` ({"fwd", "bwd"}: FLOPs and bytes
    a call) over the seconds of ``kernels`` ({"fwd", "bwd"}: names);
    ``calls_of``: the kernels whose events count a part's calls, where not
    all of ``kernels``."""
    took = trace.seconds_of_kernels(kernels["fwd"] + kernels["bwd"])
    if took <= 0:
        return None
    least, said = 0.0, []
    for part in ("fwd", "bwd"):
        sec, binds = flops.least_seconds(
            need[part]["flops"], need[part]["bytes"], cell["peaks"])
        calls = trace.count_of_kernels((calls_of or kernels)[part])
        least += sec * calls
        said.append("%s %g calls x %.6f s (%s)" % (part, calls, sec, binds))
    cell["say"]("%s: least %.6f s of %.6f s taken; %s"
                % (name, least, took, "; ".join(said)))
    return 100.0 * least / took


def shapes(cell):
    dims = cell["dims"]
    return dims["B"] // cell["traffic"]["mesh"].get("dp", 1), dims["S"]


def read(trace, spans, counters, cell):
    if not trace or not cell.get("peaks"):
        return None
    return roofline(trace, cell, "indexer_scores_roofline", KERNELS,
                    keye_vl2_train.indexer_scores(cell["config"]["model"],
                                                  *shapes(cell)))
