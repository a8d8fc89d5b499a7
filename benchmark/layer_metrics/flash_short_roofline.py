"""Kernels: ``flash_roofline``'s reading in the cell whose whole sequence is
one short block (S=128): there HBM binds, not the MXU, and a grid step of
the kernels holds several batch rows and head-blocks."""

from .flash_roofline import read  # noqa: F401
