"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for expert layers that select by a bias: an existing entry may not
take a cell.  ``router`` here holds the logits, the sigmoid, the biased
top-k and the load count that moves the bias."""

from .moe_time_share import read  # noqa: F401
