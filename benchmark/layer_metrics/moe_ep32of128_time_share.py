"""Model code: ``moe_time_share``'s reading (device time under the program's
scopes ``moe`` + ``router``, all phases, over the device's busy time; nothing
where more than 5 % of it carries no scope) under a name of its own for a
layer whose 128 routed experts ride four chips, 32 a chip, each chip's
grouped matmuls over the rows ALL four send it (the exchange itself is scope
``exchange``, ``ep_exchange_time_share``): an existing entry may not take a
cell."""

from .moe_time_share import read  # noqa: F401
