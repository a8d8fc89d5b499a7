"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a layer that holds 32 of 128 routed experts, a quarter of the
pairs, on both copies' rows of a block-diffusion step: an existing entry may
not take a cell."""

from .moe_time_share import read  # noqa: F401
