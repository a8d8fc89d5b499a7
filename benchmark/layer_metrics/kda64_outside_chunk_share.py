"""Model code: the device time under the program's scope ``kda`` that is NOT
under ``kda_chunk`` (the three projections to 8,192 columns and their
filters' kernels, the L2 norms, both low-rank gates, the head-wise norm and
its gate, the output projection; forward, recomputed and backward), over the
device's busy time: what stands around the delta rule at 64 heads.  The line
it says gives the projections' least seconds (their required FLOPs, three
passes, against the MXU's peak) beside it.  ``moe_time_share``'s rule on
unattributed time; a program without the scope reads nothing."""

from ..flops import solar_open2_train
from . import kda64_time_share, kda_time_share, mla_time_share


def read(trace, spans, counters, cell):
    outside = kda_time_share.seconds(trace, cell, ("kda",))
    if outside is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "kda64_outside_chunk_share"):
        return None
    said = ""
    if cell.get("peaks"):
        layers, _, steps, tokens = kda64_time_share.steps_traced(trace, cell)
        projections = 3.0 * solar_open2_train.kda_projection_flops_per_token(
            cell["config"]["model"])
        said = "; the projections' least %.6f s (%.3f steps traced)" % (
            projections * tokens * layers * steps
            / cell["peaks"]["bf16_flops"], steps)
    cell["say"]("kda64_outside_chunk_share: %.6f s under kda and not under "
                "kda_chunk%s" % (outside, said))
    return 100.0 * outside / trace.busy_s
