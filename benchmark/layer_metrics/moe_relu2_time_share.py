"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a sparse feed-forward LAYER of ungated ``relu^2`` experts that
holds 16 of 128, 1/8 of the pairs, beside a shared one: an existing entry
may not take a cell.  The shared expert carries ``shared_expert`` and is not
in it."""

from .moe_time_share import read  # noqa: F401
