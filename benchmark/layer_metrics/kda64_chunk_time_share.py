"""Kernels: ``kda_chunk_time_share``'s reading (device time under the
program's scope ``kda_chunk``: the delta rule itself, forward, recomputed
and backward, over the device's busy time; the line it says bears that
reader's name) under a name of its own for 64 heads at write strengths in (0,
2), where the solve is by doubling: an existing entry may not take a cell.  A
program without the scope reads nothing."""

from .kda_chunk_time_share import read  # noqa: F401
