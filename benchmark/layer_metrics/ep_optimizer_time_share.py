"""Model code: ``optimizer_time_share``'s reading (device time in the phase
``optimizer``, the ``opt_update`` call of the train step, over the device's
busy time) under a name of its own for an expert-parallel group, where a chip
updates its OWN 32 experts of every layer beside the replicated leaves (the
guide's pairing for experts spread over chips names "the optimizer's
share"): an existing entry may not take a cell."""

from .optimizer_time_share import read  # noqa: F401
