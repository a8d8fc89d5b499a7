"""Named-axis collective wrappers used inside shard_map bodies.

The TPU-native replacement for the reference's collective op kernels
(operators/collective/c_allreduce_op.h:58-108 pattern: look up NCCL comm by
ring_id, launch ncclAllReduce on a stream) and op-handles
(details/all_reduce_op_handle.cc:113, broadcast_op_handle, reduce_op_handle,
details/sparse_all_reduce_op_handle.h).  Ring ids map to mesh axis names;
streams/sync (c_sync_calc_stream / c_sync_comm_stream) have no equivalent —
XLA schedules collectives into the single program.

Every wrapper is a no-op when the axis is absent or has size 1, so the same
model code runs on any mesh degeneration (single chip included).
"""

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "axis_present",
    "psum",
    "psum_forward",
    "pmean",
    "pmax",
    "all_gather",
    "reduce_scatter",
    "ppermute_shift",
    "all_to_all",
    "axis_index",
    "axis_size_in",
]


def _in_scope(axis):
    """True if `axis` is bound as a manual mesh axis in the current trace."""
    try:
        lax.axis_size(axis)
        return True
    except (NameError, KeyError, ValueError, AssertionError):
        return False


def axis_present(axis):
    return axis is not None and _in_scope(axis)


def axis_size_in(axis):
    return lax.axis_size(axis) if axis_present(axis) else 1


def axis_index(axis):
    return lax.axis_index(axis) if axis_present(axis) else jnp.int32(0)


def psum(x, axis):
    """All-reduce sum (parity: c_allreduce_sum, all_reduce_op_handle.cc:48)."""
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.psum(x, axis)


def psum_forward(x, axis):
    """The all-reduce sum of a LOSS TERM: the value is ``psum(x)``, the
    cotangent that reaches ``x`` is the result's own.  Inside a ``shard_map``
    without the replication check ``psum`` transposes to ``psum``: every
    device differentiates its own copy of the replicated loss, so a plain
    ``psum`` in the loss hands each device's local terms the cotangents of
    ALL the copies, the axis' size times too much, in every leaf alike
    (Adam's update does not see a common factor; SGD's, a clip's or a
    reference's gradient does).  ``global_mean_loss`` is this over a
    count."""
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return x + lax.stop_gradient(lax.psum(x, axis) - x)


def pmean(x, axis):
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.pmean(x, axis)


def global_mean_loss(local_sum, global_count, axis):
    """Globally-reduced mean loss whose GRADIENT is exact for axis-sharded
    leaves: normalize the local sum by the GLOBAL count, then add the other
    shards' contributions under stop_gradient (value = global mean; the
    cotangent reaching local compute stays exactly 1/global_count).

    Why not lax.pmean(local_mean): psum's transpose is psum, so a replicated
    cotangent picks up an extra axis-size factor on sharded leaves (the
    ScaleLossGradOp 1/N placement problem, details/scale_loss_grad_op_handle —
    solved here by construction instead of a scale op).
    """
    local = local_sum / global_count
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return local
    return lax.stop_gradient(lax.psum(local, axis) - local) + local


def pmax(x, axis):
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.pmax(x, axis)


def all_gather(x, axis, dim=0):
    """Concat shards along `dim` (parity: c_allgather op)."""
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.all_gather(x, axis, axis=dim, tiled=True)


def reduce_scatter(x, axis, dim=0):
    """Sum then keep this rank's shard of `dim` (parity: c_reducescatter)."""
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


def ppermute_shift(x, axis, shift=1):
    """Rotate shards around the axis ring (the ICI-neighbor primitive behind
    pipeline stage hand-off and ring attention)."""
    if not axis_present(axis):
        return x
    n = axis_size_in(axis)
    if n == 1:
        return x
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def all_to_all(x, axis, split_dim, concat_dim):
    """Exchange shards (expert-parallel dispatch/combine primitive)."""
    if not axis_present(axis) or axis_size_in(axis) == 1:
        return x
    return lax.all_to_all(x, axis, split_dim, concat_dim, tiled=True)
