"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a layer that holds 10 of 320 routed experts (1/32 of the pairs;
a router of two and a half lane tiles, a share that is no multiple of 8)
beside a shared one: an existing entry may not take a cell.  The shared
expert carries ``shared_expert`` and is not in it."""

from .moe_time_share import read  # noqa: F401
