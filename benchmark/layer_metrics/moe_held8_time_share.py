"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a layer that holds 8 of 128 routed experts beside a shared one:
an existing entry may not take a cell.  The shared expert is not in it
(``shared_expert_time_share``)."""

from .moe_time_share import read  # noqa: F401
