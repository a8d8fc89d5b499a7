"""Model code: the device time under the program's scopes ``mamba2`` +
``ssd_scan`` that is NOT in the scan's kernels (both projections, the causal
filter's kernels, the step sizes, the gated group norm, the gradients'
assembly at the scan's door; forward, recomputed and backward), over the
device's busy time: what stands around the kernels.  Every scan call of this
stack runs under the scope, so the kernels' time by name is taken off the
scopes'.  The line it says gives the projections' least seconds (their
required FLOPs, three passes, against the MXU's peak) beside it.
``moe_time_share``'s rule on unattributed time; a program without the scope
reads nothing."""

from ..flops import nemotron_h_train
from . import mamba2_time_share, mla_time_share
from .ssd_scan_roofline import layer_steps


def read(trace, spans, counters, cell):
    under = mamba2_time_share.seconds(trace, cell)
    if under is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "mamba2_outside_scan_share"):
        return None
    kernels = mamba2_time_share.kernel_seconds(trace)
    said = ""
    if cell.get("peaks"):
        model = cell["config"]["model"]
        layers, steps, tokens = layer_steps(trace, cell)
        projections = 3.0 * (nemotron_h_train.mixer_flops_per_token(model)
                             - nemotron_h_train.scan_flops_per_token(model))
        said = "; the projections' least %.6f s (%.3f steps traced)" % (
            projections * tokens * layers * steps
            / cell["peaks"]["bf16_flops"], steps)
    cell["say"]("mamba2_outside_scan_share: %.6f s under mamba2 + ssd_scan, "
                "%.6f s of it in the scan's kernels%s"
                % (under, kernels, said))
    return 100.0 * max(under - kernels, 0.0) / trace.busy_s
