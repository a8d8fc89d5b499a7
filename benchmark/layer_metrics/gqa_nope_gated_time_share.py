"""Model code: ``gated_attn_time_share``'s reading (device time under the
program's scopes ``attention`` + ``attn_gate``: the five projections, the
flash kernels, the gate's sigmoid and product, ``wo``; all phases, over the
device's busy time; the line it says bears that reader's name) under a name
of its own for ONE grouped-query layer of 64 query heads on 8 key/value heads
WITHOUT positions beside three KDA layers: an existing entry may not take a
cell.  ``moe_time_share``'s rule on unattributed time; a program without the
scopes reads nothing."""

from .gated_attn_time_share import read  # noqa: F401
