"""Model code: ``mla_time_share``'s reading (device time under the program's
scope ``latent_attention``: the query projection and the key/value chain
with its latent's norm, the rotation and the assembly of the heads, the
flash kernels, the output projection; all phases, over the device's busy
time) under a name of its own for latent attention at FULL-RANK queries on
every chip of an expert-parallel group: an existing entry may not take a
cell."""

from .mla_time_share import read  # noqa: F401
