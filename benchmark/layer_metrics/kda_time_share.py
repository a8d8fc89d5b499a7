"""Model code: device time under the program's scopes ``kda`` (Kimi Delta
Attention where attention stands: the three projections and their filters'
kernels, the L2 norms, the decay's and the output's gates, the head-wise
norm, the output projection) and ``kda_chunk`` (the delta rule itself,
inside it), all phases, over the device's busy time.  The layer's input norm
carries ``layer_norm`` and is not in it.  ``moe_time_share``'s rule on
unattributed time (``mla_time_share.attributed``); a program without the
scope (the parent commit's) reads nothing."""

from ..harness import scope_time
from . import mla_time_share

SCOPES = ("kda", "kda_chunk")
CHUNK = "kda_chunk"
# one latent layer a step: its backward's first kernel counts the steps
STEP_KERNELS = ("flash_bwd_fused", "flash_bwd_dq")


def seconds(trace, cell, scopes=SCOPES):
    """Device seconds under ``scopes``, or None without them."""
    table = scope_time.seconds(trace, cell)
    if table is None:
        return None
    return sum(s for (_, at), s in table.items() if at in scopes) or None


def steps_traced(trace, cell):
    """(KDA layers, latent layers, steps in the traced stretch, tokens a
    step and chip): the latent layers' flash backward runs once a layer and
    step."""
    from ..flops import kimi_linear_train
    from ..harness import build

    kda, full = kimi_linear_train.layer_counts(cell["config"]["model"])[:2]
    return (kda, full, trace.count_of_kernels(STEP_KERNELS) / max(full, 1),
            build.units_per_step(cell["config"], cell["dims"])
            / cell["chips"])


def read(trace, spans, counters, cell):
    took = seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "kda_time_share"):
        return None
    cell["say"]("kda_time_share: %.6f s under kda + kda_chunk, %.6f s of it "
                "under kda_chunk" % (took, seconds(trace, cell, (CHUNK,))
                                     or 0.0))
    return 100.0 * took / trace.busy_s
