"""Mixture-of-Experts FFNs.  Two layers, for two layouts:

- ``dropless_moe_ffn``: top-k routing with NO capacity and no dropped token,
  every expert on this device.  The T*k (token, expert) pairs are sorted by
  expert, the rows gathered in that order, and two GROUPED matmuls run over
  the sorted rows (the Pallas ``megablox`` kernels that ship with JAX: row i
  meets the weights of its own group only, so nothing is computed for a
  pair that was not routed), then the sort is undone and each token's k
  rows are summed.  The FFN of a
  ``TransformerConfig`` with ``n_experts > 0`` (models/olmoe.py).
- ``switch_moe_ffn``: top-1 (Switch) routing with a capacity limit that
  DROPS the overflow, experts sharded over a mesh axis (by default ``dp``,
  "EP rides DP") and exchanged with ``lax.all_to_all`` over ICI.  Net-new
  against the reference (SURVEY.md section 2.9: it has no expert
  parallelism; its sparse story is the PSLib parameter server,
  fleet/fleet_wrapper.h:55).  The multichip dry run is its one caller; the
  two fold into one when expert parallelism gets a model (ROADMAP.md).

Per-device code for use inside shard_map bodies (parallel/train.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from . import collectives as col
from .mesh import DP
from ..kernels._common import on_tpu
from ..monitor import devscope

__all__ = ["init_moe_params", "switch_moe_ffn", "init_dropless_moe_params",
           "dropless_moe_ffn", "route_top_k"]


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def init_moe_params(key, n_experts, hidden, ffn_hidden, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": _normal(k1, (hidden, n_experts), hidden, jnp.float32),
        "w1": _normal(k2, (n_experts, hidden, ffn_hidden), hidden, dtype),
        "w2": _normal(k3, (n_experts, ffn_hidden, hidden), ffn_hidden, dtype),
    }


def init_dropless_moe_params(key, n_experts, hidden, ffn_hidden,
                             dtype=jnp.float32):
    """``router`` [E, n] float32; ``we_gate_up`` [n, E, 2F], the gate
    projection in columns [0, F) and the up projection in [F, 2F);
    ``we_down`` [n, F, E]."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": _normal(k1, (hidden, n_experts), hidden, jnp.float32),
        "we_gate_up": _normal(k2, (n_experts, hidden, 2 * ffn_hidden), hidden,
                              dtype),
        "we_down": _normal(k3, (n_experts, ffn_hidden, hidden), ffn_hidden,
                           dtype),
    }


def moe_param_specs(ep_axis=DP):
    """Derived from the rule tree (parallel/rules.py moe_rules)."""
    from . import rules as shard_rules

    leaf = shard_rules.SkeletonLeaf
    return shard_rules.match_partition_rules(
        shard_rules.moe_rules(ep_axis),
        {"router": leaf(), "w1": leaf(), "w2": leaf()})


@devscope.scoped(devscope.ROUTER)
def route_top_k(router, x, k):
    """Router of a dropless layer on the tokens ``x`` [T, E]: the k largest
    of ``softmax(x @ router)`` (float32, over ALL experts; as they are, not
    renormalised to sum to one) and their experts, [T, k] each, and the
    layer's auxiliary values over the dp-global batch:

    - ``load_balance`` = n * sum_e f_e * P_e, ``f_e`` the share of the T*k
      assignments that went to expert e (a count: no gradient), ``P_e`` the
      mean of p_e over tokens; 1 at a uniform router;
    - ``router_z`` = mean_t logsumexp(logits_t)^2;
    - ``load_max_over_mean``: the busiest expert's assignments over the mean.
    """
    n = router.shape[-1]
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_e = jax.lax.top_k(probs, k)
    counts = col.psum(jnp.bincount(top_e.reshape(-1), length=n), DP)
    tokens = x.shape[0] * col.axis_size_in(DP)
    share = counts.astype(jnp.float32) / (tokens * k)
    mean_p = col.psum(jnp.sum(probs, axis=0), DP) / tokens
    aux = {"load_balance": n * jnp.sum(share * mean_p),
           "router_z": col.psum(jnp.sum(jnp.square(lse)), DP) / tokens,
           "load_max_over_mean": jnp.max(share) * n}
    return top_p, top_e, aux


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Row i of the result is token ``order[i] // k``: x [T, E] gathered
    into the sorted order of its T*k assignments.  The transpose of that
    gather is a scatter-add; ``order`` is a permutation with inverse
    ``inv``, so the backward is a gather too, and a sum over each token's
    k rows."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


@devscope.scoped(devscope.MOE)
def _dispatch_bwd(k, inv, g):
    dx = jnp.sum(g[inv].reshape(-1, k, g.shape[-1]).astype(jnp.float32),
                 axis=1).astype(g.dtype)
    return dx, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(rows, order, inv):
    """``rows[inv]``: the sorted rows back in assignment order (token-major).
    Backward ``g[order]``, a gather where autodiff would scatter."""
    return rows[inv]


def _unsort_fwd(rows, order, inv):
    return rows[inv], order


@devscope.scoped(devscope.MOE)
def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _tiling(m, k, n):
    """Tiles (rows, contraction, columns) of the megablox kernels: 512 x
    1024 x 1024 at training sizes (the expert FFN of one OLMoE layer,
    forward and backward, took 36.4 ms with it, 39.4 ms at 512 x 512 x 1024
    and 452 ms at the kernel's default 128^3; PERF.md section 6, PR 27),
    the whole dimension where that is smaller."""
    return min(m, 512), min(k, 1024), min(n, 1024)


def _whole_row_tiles(rows, tm):
    """Rows padded to whole tiles; the kernels skip rows past the groups."""
    return jnp.pad(rows, ((0, -rows.shape[0] % tm), (0, 0)))


def _gmm(rows, weights, group_sizes, transpose_rhs=False):
    m, k = rows.shape
    tiling = _tiling(m, k, weights.shape[1 if transpose_rhs else 2])
    out = gmm(_whole_row_tiles(rows, tiling[0]), weights, group_sizes,
              rows.dtype, tiling, transpose_rhs=transpose_rhs,
              interpret=not on_tpu())
    return out[:m]


@jax.custom_vjp
def _grouped_matmul(rows, weights, group_sizes):
    """rows [M, K] sorted by group, weights [G, K, N]: row i times the
    weights of its own group, nothing for a pair that was not routed.  The
    Pallas grouped matmul that ships with JAX (``megablox``): a quarter
    faster here than XLA's lowering of ``jax.lax.ragged_dot`` (36.4 against
    48.2 ms), and its instructions keep the program's scope in their
    ``op_name``, which XLA's own ragged-dot calls do not."""
    return _gmm(rows, weights, group_sizes)


def _grouped_matmul_fwd(rows, weights, group_sizes):
    return _gmm(rows, weights, group_sizes), (rows, weights, group_sizes)


# a custom_vjp backward is traced on its own, in the backward pass: it names
# its scope itself
@devscope.scoped(devscope.MOE)
def _grouped_matmul_bwd(res, g):
    rows, weights, group_sizes = res
    m = rows.shape[0]
    tiling = _tiling(m, rows.shape[1], g.shape[1])
    d_weights = tgmm(
        _whole_row_tiles(rows, tiling[0]).swapaxes(0, 1),
        _whole_row_tiles(g, tiling[0]), group_sizes, weights.dtype, tiling,
        num_actual_groups=weights.shape[0], interpret=not on_tpu())
    return _gmm(g, weights, group_sizes, transpose_rhs=True), d_weights, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@devscope.scoped(devscope.MOE)
def dropless_moe_ffn(params, x, k):
    """Top-k dropless expert FFN.  x [T, E] (flatten batch and sequence
    before the call); returns ``(y [T, E], aux)`` with
    ``y_t = sum_{e in top k} p_te * down_e(silu(gate_e x_t) * up_e x_t)``
    and ``aux`` as ``route_top_k`` gives it."""
    T = x.shape[0]
    n = params["router"].shape[-1]
    top_p, top_e, aux = route_top_k(params["router"], x, k)

    expert = top_e.reshape(-1)                                   # [T*k]
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.bincount(expert, length=n).astype(jnp.int32)

    rows = _dispatch(x, order, inv, k)                           # [T*k, E]
    gate, up = jnp.split(
        _grouped_matmul(rows, params["we_gate_up"], group_sizes), 2, axis=-1)
    out = _grouped_matmul(jax.nn.silu(gate) * up, params["we_down"],
                          group_sizes)                           # [T*k, E]
    out = _unsort(out, order, inv).reshape(T, k, -1).astype(jnp.float32)
    return jnp.sum(out * top_p[..., None], axis=1).astype(x.dtype), aux


def switch_moe_ffn(params, x, ep_axis=DP, capacity_factor=1.25):
    """Switch-routed expert FFN.  x: [tokens_local, E] (flatten batch*seq
    before calling).  Experts sharded over `ep_axis`; router replicated
    (its gradient must be psum'd over ep_axis — spec it accordingly)."""
    T, E = x.shape
    n_local = params["w1"].shape[0]          # experts on this rank
    ep = col.axis_size_in(ep_axis)
    n_experts = n_local * ep

    logits = (x.astype(jnp.float32) @ params["router"])          # [T, nE]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                               # [T]
    expert = jnp.argmax(probs, axis=-1)                          # [T]

    cap = int(max(1, round(T * capacity_factor / n_experts)))
    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)  # [T, nE]
    pos = jnp.cumsum(onehot, axis=0) * onehot                    # 1-based
    pos_in_expert = jnp.sum(pos, axis=-1) - 1                    # [T]
    keep = (pos_in_expert >= 0) & (pos_in_expert < cap)

    # scatter tokens into [nE, cap, E] send buffer
    buf = jnp.zeros((n_experts, cap, E), x.dtype)
    tok_idx = jnp.where(keep, expert * cap + jnp.clip(pos_in_expert, 0, cap - 1), 0)
    buf = buf.reshape(n_experts * cap, E).at[tok_idx].add(
        jnp.where(keep[:, None], x, 0), mode="drop"
    ).reshape(n_experts, cap, E)

    # exchange: [nE, cap, E] -> [n_local, ep*cap, E] (tokens from every rank)
    if ep > 1:
        buf = col.all_to_all(buf, ep_axis, split_dim=0, concat_dim=1)

    # run local experts
    h = jnp.einsum("gce,gef->gcf", buf.astype(params["w1"].dtype), params["w1"])
    h = jax.nn.gelu(h)
    out = jnp.einsum("gcf,gfe->gce", h, params["w2"])

    # route back
    if ep > 1:
        out = col.all_to_all(out, ep_axis, split_dim=1, concat_dim=0)
    out = out.reshape(n_experts * cap, E)

    # gather each token's result, weight by its gate prob
    y = out[tok_idx] * keep[:, None].astype(out.dtype)
    return (y.astype(jnp.float32) * gate[:, None]).astype(x.dtype)
