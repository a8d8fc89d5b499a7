"""Model code: ``shared_expert_time_share``'s reading (device time under
the program's scope ``shared_expert``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a shared expert of width 3,072 whose sum with the routed part
goes through an output norm: an existing entry may not take a cell."""

from .shared_expert_time_share import read  # noqa: F401
