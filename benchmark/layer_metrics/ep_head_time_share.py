"""Model code: ``lm_head_time_share``'s reading (device time under the
program's scope ``lm_head``, forward and backward, over the device's busy
time) under a name of its own for a whole 128,256-row head replicated on
every chip of an expert-parallel group under a five-layer stack (33 % of the
required FLOPs there; 5 % in the 48-layer model): an existing entry may not
take a cell."""

from .lm_head_time_share import read  # noqa: F401
