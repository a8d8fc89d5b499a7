"""Model code: the run's units per second times the FLOPs the job REQUIRES
per unit (``benchmark/flops/<name>.py``, the file the configuration names,
from shapes) over chips times the chip's published bf16 peak.
``train_throughput`` times a constant."""

from ..harness import flops


def read(trace, spans, counters, cell):
    if not cell.get("peaks"):
        return None
    per_unit = flops.per_unit(cell["config"], cell["dims"])
    if per_unit is None:
        return None
    cell["say"]("model_mfu: %.6g required FLOPs per %s"
                % (per_unit, cell["config"]["unit_of_work"]))
    return (100.0 * cell["throughput"] * per_unit
            / (cell["chips"] * cell["peaks"]["bf16_flops"]))
