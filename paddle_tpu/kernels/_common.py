"""Shared Pallas kernel plumbing, one copy each so a fix lands everywhere at
once: the on-TPU probe every kernel module uses to select interpret mode,
the vector register's geometry and the two helpers the row kernels share,
and the one place a kernel's engagement is counted.
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from .. import monitor

CompilerParams = pltpu.CompilerParams

LANES = 128
SUBLANES = 8


def on_tpu():
    """False only when the default backend is positively ``cpu`` (kernels
    then run in Pallas interpret mode); any accelerator compiles through
    Mosaic.  A backend that cannot be probed raises — it must not read as
    "no accelerator" and silently interpret."""
    return jax.devices()[0].platform != "cpu"


def sublane_tile(itemsize):
    """Rows of a sublane tile of the element type: 8 of 32 bits, 16 of 16."""
    return SUBLANES * 4 // itemsize


def sublane_sums(v):
    """``v`` [rows, lanes] summed into eight sublanes: elementwise adds of
    its 8-row tiles, no cross-sublane reduce."""
    return jnp.sum(v.reshape(-1, SUBLANES, v.shape[-1]), axis=0)


def count_call(kernel, /, **labels):
    """Under a monitor session, one count in
    ``monitor.kernels.<kernel>_calls{labels}`` of a call site as a program
    is TRACED (this runs when the call is traced, not when it runs): what
    the trace did — which branch a ``supported(shape)`` took, how many calls
    a program holds — which no formula says.  Off a session nothing."""
    mon = monitor.active()
    if mon is not None:
        mon.registry.counter("monitor.kernels.%s_calls" % kernel,
                             **labels).incr()
