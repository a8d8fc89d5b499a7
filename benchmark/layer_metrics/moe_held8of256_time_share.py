"""Model code: ``moe_time_share``'s reading (device time under the
program's scopes ``moe`` + ``router``, all phases, over the device's busy
time; nothing where more than 5 % of it carries no scope) under a name of
its own for a layer that holds 8 of 256 routed experts, 1/32 of the pairs,
beside a shared one: an existing entry may not take a cell.  The shared
expert (``shared_expert_w3072_time_share``) and the FFN's output norm
(``post_norm_time_share``) are not in it."""

from .moe_time_share import read  # noqa: F401
