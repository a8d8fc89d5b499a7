"""Shared Pallas kernel plumbing: the on-TPU probe every kernel module uses
to select interpret mode.  One copy, so a platform-probe fix lands
everywhere at once.
"""

import jax
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams


def on_tpu():
    """False only when the default backend is positively ``cpu`` (kernels
    then run in Pallas interpret mode); any accelerator compiles through
    Mosaic.  A backend that cannot be probed raises — it must not read as
    "no accelerator" and silently interpret."""
    return jax.devices()[0].platform != "cpu"
