"""Model code: ``kda_time_share``'s reading (device time under the program's
scopes ``kda`` + ``kda_chunk``, all phases, over the device's busy time;
``moe_time_share``'s rule on unattributed time) under a name of its own for
a stack of 64 KDA heads whose keys and values are twice the stream's width
and whose write strengths reach 2: an existing entry may not take a cell.
A program without the scope (the parent commit's) reads nothing."""

from . import kda_time_share, mla_time_share

# the grouped-query layer a step: its backward's first kernel counts the
# steps
STEP_KERNELS = ("flash_bwd_fused", "flash_bwd_dq")


def steps_traced(trace, cell):
    """(KDA layers, grouped-query layers, steps in the traced stretch,
    tokens a step and chip): the grouped-query layers' flash backward runs
    once a layer and step."""
    from ..flops import solar_open2_train
    from ..harness import build

    kda, full = solar_open2_train.layer_counts(cell["config"]["model"])
    return (kda, full, trace.count_of_kernels(STEP_KERNELS) / max(full, 1),
            build.units_per_step(cell["config"], cell["dims"])
            / cell["chips"])


def read(trace, spans, counters, cell):
    took = kda_time_share.seconds(trace, cell)
    if took is None or not mla_time_share.attributed(
            trace, spans, counters, cell, "kda64_time_share"):
        return None
    cell["say"]("kda64_time_share: %.6f s under kda + kda_chunk, %.6f s of "
                "it under kda_chunk"
                % (took, kda_time_share.seconds(
                    trace, cell, (kda_time_share.CHUNK,)) or 0.0))
    return 100.0 * took / trace.busy_s
