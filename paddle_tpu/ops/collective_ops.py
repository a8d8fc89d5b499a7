"""Collective communication ops.

Reference parity: operators/collective/ — c_allreduce_{sum,max,min,prod},
c_allgather, c_reducescatter, c_broadcast, c_comm_init*, c_gen_nccl_id,
c_sync_*_stream (c_allreduce_op.h:58-108).

Design translation (SURVEY.md §5 "Distributed communication backend"): NCCL
rings keyed by ring_id are replaced by named mesh axes; each op lowers to the
XLA collective (psum / all_gather / psum_scatter / ppermute) over the axis
that the ring_id maps to (ctx.axis_env, set by the parallel runtime when the
program runs under shard_map).  Outside any mesh axis they are identities —
the single-process behavior of an uninitialized ring.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..registry import register_op
from .common import x, out


def _axis(ctx, attrs):
    ring = int(attrs.get("ring_id", 0))
    return ctx.axis_env.get(ring) if ctx.axis_env else None


@register_op("c_allreduce_sum")
def _c_allreduce_sum(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    return out(Out=lax.psum(v, ax) if ax else v)


@register_op("c_allreduce_max")
def _c_allreduce_max(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    return out(Out=lax.pmax(v, ax) if ax else v)


@register_op("c_allreduce_min")
def _c_allreduce_min(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    return out(Out=lax.pmin(v, ax) if ax else v)


@register_op("c_allreduce_prod")
def _c_allreduce_prod(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    if not ax:
        return out(Out=v)
    return out(Out=jnp.exp(lax.psum(jnp.log(v), ax)))


@register_op("c_allgather")
def _c_allgather(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    if not ax:
        return out(Out=v)
    g = lax.all_gather(v, ax)  # [nranks, ...]
    return out(Out=g.reshape((-1,) + v.shape[1:]))


@register_op("c_reducescatter")
def _c_reducescatter(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    if not ax:
        return out(Out=v)
    return out(Out=lax.psum_scatter(v, ax, scatter_dimension=0, tiled=True))


@register_op("c_broadcast")
def _c_broadcast(ins, attrs, ctx):
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    if not ax:
        return out(Out=v)
    root = int(attrs.get("root", 0))
    idx = lax.axis_index(ax)
    masked = jnp.where(idx == root, v, jnp.zeros_like(v))
    return out(Out=lax.psum(masked, ax))


@register_op("c_ppermute")
def _c_ppermute(ins, attrs, ctx):
    """Ring shift (net-new building block for ring attention / pipeline)."""
    v = x(ins, "X")
    ax = _axis(ctx, attrs)
    if not ax:
        return out(Out=v)
    n = lax.axis_size(ax)
    shift = int(attrs.get("shift", 1))
    perm = [(i, (i + shift) % n) for i in range(n)]
    return out(Out=lax.ppermute(v, ax, perm))


@register_op("c_sync_calc_stream")
def _c_sync_calc(ins, attrs, ctx):
    # stream sync is meaningless under XLA's single-module schedule
    return out(Out=x(ins, "X"))


@register_op("c_sync_comm_stream")
def _c_sync_comm(ins, attrs, ctx):
    return out(Out=x(ins, "X"))


@register_op("c_comm_init")
def _c_comm_init(ins, attrs, ctx):
    # ring bootstrap maps to jax.distributed.initialize (parallel/env.py);
    # inside a program this is a no-op marker.
    return {}


@register_op("c_comm_init_all")
def _c_comm_init_all(ins, attrs, ctx):
    return {}


@register_op("c_gen_nccl_id")
def _c_gen_nccl_id(ins, attrs, ctx):
    # parity marker: unique-id exchange is handled by the jax.distributed
    # coordinator (reference: c_gen_nccl_id_op.cc TCP bootstrap)
    return {}


@register_op("allreduce")
def _allreduce(ins, attrs, ctx):
    return _c_allreduce_sum(ins, attrs, ctx)


@register_op("broadcast")
def _broadcast(ins, attrs, ctx):
    return _c_broadcast(ins, attrs, ctx)
