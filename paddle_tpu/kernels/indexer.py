"""The indexer of a learned-sparse attention layer: which keys a query reads.

Four pieces, each a (q block, k block) tile at a time, so that nothing
[heads, S, S] ever stands (16 heads at S = 16,384 would be 17 GB):

- ``indexer_scores`` (Pallas, forward and backward): ``I[t, s] = sum_j
  w[t, j] * relu(q[t, j] . k[s])`` for ``s <= t``, float32, ``-inf`` where
  ``s > t``; q [B, S, Hi * Di] packed, ONE key head k [B, S, Di], w [B, S,
  Hi] float32.  The heads are looped inside the tile.  The backward
  (``indexer_scores_bwd``) walks the causal triangle once: a tile recomputes
  each head's products and gives dq and dw into the q block's scratch and dk
  into a float32 accumulator that holds the WHOLE sequence (it is one head
  of Di lanes: 4 MiB at S = 16,384) and leaves at the batch row's last step.
- ``kth_largest`` (blocked ``jnp``): the k-th largest of each row of I,
  exact, without a sort: 32 counting passes over the ordered-integer image
  of float32 (``_ordered``: an unsigned integer whose order is the float's)
  fix the answer a bit a pass.  A row with fewer than k finite entries
  answers ``-inf``: every causal key is selected.
- ``dsa_attend_kl`` (Pallas, forward; its backward is the masked sweep
  ``flash_dsa_bwd_fused`` and the scores' backward): the attention's output
  under the selection AND the mean over rows of ``KL(p_t || softmax over
  S_t of I[t, .])``, ``S_t = {s <= t : I[t, s] >= tau_t}``, ``p[t, s] = mean
  over the heads of the main attention's probabilities``, in ONE sweep with
  the statistic known: a (tile, key/value head) pair a grid step, its
  group's query heads looped inside, each head's tile ``exp(q k^T - lse)``
  made once and used twice, through ``p v`` into the head's accumulator
  (``o``) and into the heads' sum in scratch (``p``).  The same pass writes
  ``G = softmax_S(I) - p`` (0 off ``S_t``), which IS ``dKL/dI``: the
  backward hands it to ``indexer_scores_bwd`` with the cotangent's scalar as
  that kernel's gain, so no other [S, S] array is kept or made.  p carries a
  stop-gradient: the KL term reaches the scores' operands alone.  ``lse``
  is the masked online sweep's (``dsa_lse``: ``flash_dsa_fwd``), which
  under a layer's remat runs ONCE: the statistic is kept, the recompute is
  this pass alone.
- the two masked sweeps (Pallas): ``flash_dsa_fwd``, the online softmax's
  statistic ALONE (running max and denominator a head, no ``P V``, no
  accumulator, no ``o``), and ``flash_dsa_bwd_fused``, the flash backward's
  one sweep (five matmuls and one ``exp`` a (tile, head); dk and dv of the
  whole sequence in VMEM) under the selection.  In both a grid row is a
  (batch row, key/value head) pair and a grid step a tile of the causal
  triangle: the tile of I, the thresholds and the selection are built once
  a step and the group's query heads are looped inside it, k and v fetched
  once for all of them, dk and dv summed over them before ONE
  read-modify-write of the accumulators.

interpret=None auto-selects the Pallas interpreter off-TPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor import devscope
from ._common import (LANES, CompilerParams as _CompilerParams,
                      count_call as _count_call, on_tpu as _on_tpu)
from .flash_attention import (FIRST, LAST, NEG_INF as MASKED, SWEEP_VMEM,
                              _Geom, _delta, _lanes_to, heads_a_step,
                              past_scoped as _past_scoped, step_table)

__all__ = ["indexer_scores", "kth_largest", "selected", "selected_lse",
           "dsa_lse", "dsa_attend_kl"]

NEG_INF = float("-inf")
SELECT_ROWS = 256       # rows of I a counting pass of ``kth_largest`` reads


def _blocks(S, block_q, block_k, interpret):
    """(q block, k block) clamped to S, and whether to interpret."""
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    return bq, bk, bool(not _on_tpu() if interpret is None else interpret)


def _causal(shape, q0, k0):
    return q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
        >= k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _head_products(q, k, heads):
    """``q[:, j] . k`` of each indexer head j: [bq, bk] float32 each."""
    di = k.shape[-1]
    for j in range(heads):
        yield j, jax.lax.dot_general(
            q[:, j * di:(j + 1) * di], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, heads, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j * bk > i * bq + bq - 1)
    def _above():
        o_ref[0] = jnp.full((bq, bk), NEG_INF, jnp.float32)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _tile():
        w = w_ref[0]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h, s in _head_products(q_ref[0], k_ref[0], heads):
            acc += w[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = jnp.where(_causal((bq, bk), i * bq, j * bk), acc, NEG_INF)


def _last_visible(i, bq, bk):
    """The last k block a q block's rows see."""
    return (i * bq + bq - 1) // bk


def _scores_fwd_call(q, k, w, bq, bk, interpret):
    B, S, _ = q.shape
    heads = w.shape[-1]
    # a block above the diagonal is filled, not computed: its operands are
    # the diagonal's, which are there already
    kmap = lambda b, i, j: (b, jnp.minimum(j, _last_visible(i, bq, bk)), 0)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, bq=bq, bk=bk),
        grid=(B, S // bq, S // bk),
        in_specs=[pl.BlockSpec((1, bq, q.shape[-1]), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, k.shape[-1]), kmap),
                  pl.BlockSpec((1, bq, heads), lambda b, i, j: (b, i, 0))],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            **_past_scoped(scores_vmem_bytes(bq, bk, q.shape[-1], heads,
                                             q.dtype.itemsize))),
        interpret=interpret, name="indexer_scores_fwd",
    )(q, k, w)


def scores_vmem_bytes(bq, bk, width, heads, itemsize, backward=False, S=0):
    """What the scores' kernels hold in VMEM at a q block of ``bq`` rows of
    ``width`` = heads x Di lanes: the q block twice (a pipelined operand),
    the k block, the weights' and the [bq, bk] tile twice each and six
    float32 tiles of a step's own; the backward also dq's block twice, its
    float32 accumulator and dk's of all ``S`` keys with its output block."""
    di = width // heads
    tile = bq * bk * 4
    rows = bq * max(heads, LANES) * 4
    need = 2 * bq * width * itemsize + 2 * bk * di * itemsize + 2 * rows \
        + 8 * tile
    if backward:
        # and as much again of the step's own values as the forward's: the
        # compiled kernel took 71.4 MiB at 64 x 128 where those were left out
        need += 2 * bq * width * itemsize + bq * width * 4 + 3 * rows \
            + S * di * (4 + 2 * itemsize) + 8 * tile
    return need + 2 * 2 ** 20


def _scores_bwd_kernel(q_of, kv_of, head_of, flags, gain_ref, q_ref, k_ref,
                       w_ref, g_ref, dq_ref, dk_ref, dw_ref, dq_scr, dw_scr,
                       dk_acc, *, heads, bq, bk):
    t = pl.program_id(1)
    di = k_ref.shape[-1]
    rows = pl.ds(pl.multiple_of(kv_of[t] * bk, bk), bk)

    @pl.when(t == 0)
    def _open():
        dk_acc[:] = jnp.zeros_like(dk_acc)

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    q, k, w = q_ref[0], k_ref[0], w_ref[0]
    g = jnp.where(_causal((bq, bk), q_of[t] * bq, kv_of[t] * bk),
                  g_ref[0] * gain_ref[0], 0.0)
    dk = jnp.zeros((bk, di), jnp.float32)
    dws = []
    for h, s in _head_products(q, k, heads):
        dws.append(jnp.sum(g * jnp.maximum(s, 0.0), axis=1, keepdims=True))
        ds = jnp.where(s > 0.0, g * w[:, h:h + 1], 0.0).astype(q.dtype)
        dq_scr[:, h * di:(h + 1) * di] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk += jax.lax.dot_general(
            ds, q[:, h * di:(h + 1) * di], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    dw_scr[:] += jnp.concatenate(dws, axis=1)
    dk_acc[rows, :] += dk

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        dw_ref[0] = dw_scr[:]

    @pl.when(t == pl.num_programs(1) - 1)
    def _close():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)


def _triangle_call(kernel, name, table, B, operands, in_specs, out_specs,
                   out_shape, scratch_shapes, interpret, extra_axes=(),
                   row_axes=(), **params):
    """A sweep over the causal triangle's (q block, k block) pairs, the
    flash kernels' ``step_table`` as scalar-prefetch operands; ``row_axes``
    outer grid axes in front of the step (a sweep each), ``extra_axes``
    inner ones behind it."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(B,) + tuple(row_axes) + (table.shape[1],)
            + tuple(extra_axes),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",) * (1 + len(row_axes))
            + ("arbitrary",) * (1 + len(extra_axes)), **params),
        interpret=interpret, name=name,
    )(*(jnp.asarray(column) for column in table), *operands)


def _scores_bwd_call(q, k, w, g, bq, bk, interpret, gain=1.0):
    """(dq, dk, dw) of the cotangent ``gain * g``, ``gain`` a scalar the
    tile is multiplied by as it is read: a cotangent that is a kept [S, S]
    array times a scalar (``dsa_attend_kl``'s) is never made."""
    B, S, W = q.shape
    heads, di = w.shape[-1], k.shape[-1]
    table = step_table(S, S, bq, bk, True)
    qrow = lambda b, t, q_of, kv_of, head_of, flags: (b, q_of[t], 0)
    krow = lambda b, t, q_of, kv_of, head_of, flags: (b, kv_of[t], 0)
    tile = lambda b, t, q_of, kv_of, head_of, flags: (b, q_of[t], kv_of[t])
    whole = lambda b, t, *table: (b, 0, 0)
    return _triangle_call(
        functools.partial(_scores_bwd_kernel, heads=heads, bq=bq, bk=bk),
        "indexer_scores_bwd", table, B,
        (jnp.reshape(gain, (1,)).astype(jnp.float32), q, k, w, g),
        [pl.BlockSpec(memory_space=pltpu.SMEM),
         pl.BlockSpec((1, bq, W), qrow), pl.BlockSpec((1, bk, di), krow),
         pl.BlockSpec((1, bq, heads), qrow), pl.BlockSpec((1, bq, bk), tile)],
        [pl.BlockSpec((1, bq, W), qrow), pl.BlockSpec((1, S, di), whole),
         pl.BlockSpec((1, bq, heads), qrow)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        [pltpu.VMEM((bq, W), jnp.float32),
         pltpu.VMEM((bq, heads), jnp.float32),
         pltpu.VMEM((S, di), jnp.float32)],
        interpret,
        vmem_limit_bytes=max(48 * 2 ** 20, scores_vmem_bytes(
            bq, bk, W, heads, q.dtype.itemsize, backward=True, S=S)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _scores(q, k, w, bq, bk, interpret):
    return _scores_fwd_call(q, k, w, bq, bk, interpret)


def _scores_fwd(q, k, w, bq, bk, interpret):
    return _scores_fwd_call(q, k, w, bq, bk, interpret), (q, k, w)


def _scores_bwd(bq, bk, interpret, res, g):
    q, k, w = res
    dq, dk, dw = _scores_bwd_call(q, k, w, g, bq, bk, interpret)
    return dq, dk, dw.astype(w.dtype)


_scores.defvjp(_scores_fwd, _scores_bwd)


def indexer_scores(q, k, w, block_q=512, block_k=512, interpret=None):
    """``I`` [B, S, S] float32 of q [B, S, Hi * Di], k [B, S, Di] and w [B,
    S, Hi] (float32): ``sum_j w[t, j] relu(q[t, j] . k[s])`` where ``s <=
    t``, ``-inf`` above the diagonal.  The gradient reads ``dI`` under the
    diagonal alone."""
    B, S, W = q.shape
    heads = w.shape[-1]
    assert W == heads * k.shape[-1] and k.shape[:2] == (B, S) \
        and w.shape[:2] == (B, S), (q.shape, k.shape, w.shape)
    bq, bk, interpret = _blocks(S, block_q, block_k, interpret)
    return _scores(q, k, w.astype(jnp.float32), bq, bk, interpret)


# ---------------------------------------------------------------------------
# the k-th largest of a row
# ---------------------------------------------------------------------------

def _ordered(x):
    """float32 -> uint32 whose unsigned order is the float's (``-inf``
    lowest; -0.0 below +0.0)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def _unordered(u):
    return jax.lax.bitcast_convert_type(
        jnp.where(u >> 31 == 1, u & jnp.uint32((1 << 31) - 1), ~u),
        jnp.float32)


def _by_rows(block, B, S, rows):
    """``block(i)`` [B, rows] of each block of ``rows`` rows in turn, [B, S]."""
    out = jax.lax.map(block, jnp.arange(S // rows))        # [blocks, B, rows]
    return jnp.moveaxis(out, 0, 1).reshape(B, S)


def kth_largest(scores, k, rows=SELECT_ROWS):
    """The ``k``-th largest entry of each row of ``scores`` [B, S, N]
    float32, [B, S] float32, exact: the largest value v with at least k
    entries >= v, found a bit a pass from the top over the ordered-integer
    image, ``rows`` rows of every batch row a block.  ``k`` > N counts as N
    (the row's least entry)."""
    B, S, N = scores.shape
    k = min(int(k), N)
    rows = min(rows, S)
    assert S % rows == 0, (S, rows)

    def block(i):
        u = _ordered(jax.lax.dynamic_slice_in_dim(scores, i * rows, rows, 1))

        def bit(n, prefix):
            cand = prefix | (jnp.uint32(1 << 31) >> n.astype(jnp.uint32))
            count = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
            return jnp.where(count >= k, cand, prefix)

        return _unordered(jax.lax.fori_loop(
            0, 32, bit, jnp.zeros((B, rows), jnp.uint32)))

    return _by_rows(block, B, S, rows)


def selected(scores, tau):
    """[B, S, S] bool: ``S_t``, the causal keys at or above the row's
    threshold (``scores`` is ``-inf`` above the diagonal, where nothing is
    selected even at ``tau = -inf``)."""
    S = scores.shape[1]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None]
    return causal & (scores >= tau[..., None])


# ---------------------------------------------------------------------------
# attention under the selection with the statistic known, and the indexer's
# own loss term, in one pass
# ---------------------------------------------------------------------------

def _attend_kl_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref,
                      lse_ref, i_ref, tau_ref, lsei_ref, o_ref, kl_ref, g_ref,
                      off_scr, p_scr, acc_scr, *, scale, heads, group, bq, bk):
    t, kh = pl.program_id(1), pl.program_id(2)
    first = (flags[t] & FIRST) != 0
    d, dv = k_ref.shape[-1], v_ref.shape[-1]

    @pl.when(kh == 0)
    def _open():
        # the tile's selection, once for its heads: 0 on S_t, -inf off it
        keep = _causal((bq, bk), q_of[t] * bq, kv_of[t] * bk) \
            & (i_ref[0] >= tau_ref[0])
        off_scr[:] = jnp.where(keep, 0.0, NEG_INF)
        p_scr[:] = jnp.zeros_like(p_scr)

    k, v = k_ref[0], v_ref[0]
    for h in range(group):      # the query heads of this key/value head
        s = jax.lax.dot_general(q_ref[0, :, h * d:(h + 1) * d], k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # normalised already: no running max, no denominator
        p = jnp.exp(s - lse_ref[0, h] + off_scr[:])
        p_scr[:] += p
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        n = kh * group + h
        acc_scr[n] = jnp.where(first, pv, acc_scr[n] + pv)

    @pl.when(kh == heads // group - 1)
    def _tile():
        keep = off_scr[:] == 0.0
        p = p_scr[:] * (1.0 / heads)
        log_r = jnp.where(keep, i_ref[0] - lsei_ref[0], 0.0)
        g_ref[0] = jnp.where(keep, jnp.exp(log_r), 0.0) - p
        part = jnp.sum(jnp.where(p > 0.0, p * (jnp.log(
            jnp.where(p > 0.0, p, 1.0)) - log_r), 0.0), axis=1, keepdims=True)

        @pl.when(first)
        def _first():
            kl_ref[0] = part

        @pl.when(jnp.logical_not(first))
        def _later():
            kl_ref[0] += part

        @pl.when((flags[t] & LAST) != 0)
        def _out():
            for h in range(heads):
                o_ref[0, :, h * dv:(h + 1) * dv] = acc_scr[h].astype(
                    o_ref.dtype)


def attend_kl_vmem_bytes(n_heads, head_dim, itemsize, group, bq=512,
                         bk=512, v_head_dim=None):
    """What ``dsa_attend_kl_fwd`` asks of VMEM: the q block's ``o`` of every
    head twice (an output block) and once more in float32 (the accumulator),
    the tiles of I and G twice each, the heads' sum and the selection, the
    operands' blocks (q and ``lse`` of a group's heads, k, v and three
    [bq, 1] columns, each padded to a lane tile) twice, and as much again
    as a step's [bq, bk] float32 values (the products, the probabilities,
    their rounded copy)."""
    dv = head_dim if v_head_dim is None else v_head_dim
    width = n_heads * dv
    tile = bq * bk * 4
    rows = bq * LANES * 4               # a [bq, 1] float32 block, padded
    return (bq * width * (2 * itemsize + 4) + 6 * tile
            + 2 * ((group * bq + bk) * head_dim + bk * dv) * itemsize
            + 2 * (group + 3) * rows + 6 * tile)


def _attend_kl_call(q, k, v, scores, tau, lse, lse_i, n_heads, n_kv_heads,
                    scale, bq, bk, interpret):
    B, S, W = q.shape
    D = W // n_heads
    Dv = v.shape[-1] // n_kv_heads      # a value's own width, or D
    group = n_heads // n_kv_heads
    table = step_table(S, S, bq, bk, True)
    # a grid step is a (tile, key/value head) pair: the group's query heads
    # are looped inside it
    qrow = lambda b, t, n, q_of, kv_of, head_of, flags: (b, q_of[t], n)
    krow = lambda b, t, n, q_of, kv_of, head_of, flags: (b, kv_of[t], n)
    stat = lambda b, t, n, q_of, kv_of, head_of, flags: (b, n, q_of[t], 0)
    row = lambda b, t, n, q_of, kv_of, head_of, flags: (b, q_of[t], 0)
    tile = lambda b, t, n, q_of, kv_of, head_of, flags: (
        b, q_of[t], kv_of[t])
    return _triangle_call(
        functools.partial(_attend_kl_kernel, scale=scale, heads=n_heads,
                          group=group, bq=bq, bk=bk),
        "dsa_attend_kl_fwd", table, B, (q, k, v, lse, scores, tau, lse_i),
        [pl.BlockSpec((1, bq, group * D), qrow),
         pl.BlockSpec((1, bk, D), krow), pl.BlockSpec((1, bk, Dv), krow),
         pl.BlockSpec((1, group, bq, 1), stat),
         pl.BlockSpec((1, bq, bk), tile), pl.BlockSpec((1, bq, 1), row),
         pl.BlockSpec((1, bq, 1), row)],
        [pl.BlockSpec((1, bq, n_heads * Dv), row),
         pl.BlockSpec((1, bq, 1), row), pl.BlockSpec((1, bq, bk), tile)],
        [jax.ShapeDtypeStruct((B, S, n_heads * Dv), q.dtype),
         jax.ShapeDtypeStruct((B, S, 1), jnp.float32),
         jax.ShapeDtypeStruct((B, S, S), jnp.float32)],
        [pltpu.VMEM((bq, bk), jnp.float32), pltpu.VMEM((bq, bk), jnp.float32),
         pltpu.VMEM((n_heads, bq, Dv), jnp.float32)], interpret,
        extra_axes=(n_kv_heads,),
        vmem_limit_bytes=attend_kl_vmem_bytes(n_heads, D, q.dtype.itemsize,
                                              group, bq, bk, Dv))


# ---------------------------------------------------------------------------
# the two masked sweeps: the online softmax's statistic alone, and the flash
# backward's one sweep, a (tile, key/value head) a grid step
# ---------------------------------------------------------------------------

def _selection_off(i_ref, tau_ref, q0, k0, bq, bk):
    """[bq, bk] float32 of a step's tile of I and its rows' thresholds: 0 on
    ``S_t``, ``MASKED`` off it (the flash sweeps' finite minus infinity: a
    scaled score plus it IS it, so a head's masked scores are ``s + off``)."""
    keep = _causal((bq, bk), q0, k0) & (i_ref[0] >= tau_ref[0])
    return jnp.where(keep, 0.0, MASKED)


def _lse_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, i_ref, tau_ref,
                lse_ref, m_scr, l_scr, *, scale, heads, bq, bk):
    """``flash_dsa_fwd``: the running max and denominator of ``heads`` query
    heads of one key/value head over a q block's sweep, the recurrence of
    ``flash_attention._fwd_sweep_kernel`` a head; the statistic leaves at
    the sweep's last step and nothing else is made."""
    t = pl.program_id(2)
    d = k_ref.shape[-1]

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)

    off = _selection_off(i_ref, tau_ref, q_of[t] * bq, kv_of[t] * bk, bq, bk)
    k = k_ref[0]
    for h in range(heads):
        s = jax.lax.dot_general(q_ref[0, :, h * d:(h + 1) * d], k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + off
        m_prev = m_scr[h]                                  # [bq, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _lanes_to(m_new, bk))
        l_scr[h] = l_scr[h] * jnp.exp(m_prev - m_new) \
            + jnp.sum(p, axis=1)[:, None]
        m_scr[h] = m_new

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        for h in range(heads):
            lse_ref[0, h] = m_scr[h][:, :1] + jnp.log(
                jnp.maximum(l_scr[h][:, :1], 1e-30))


def _dsa_bwd_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, i_ref, tau_ref, dq_ref, dk_ref,
                    dv_ref, dq_scr, dk_acc, dv_acc, *, scale, heads, bq, bk):
    """``flash_dsa_bwd_fused``: a grid row is a (batch row, key/value head)
    pair, a step a tile of the triangle with ``heads`` query heads looped
    inside: of each the five products and ONE ``exp`` of
    ``flash_attention._bwd_sweep_kernel``'s tile, dq into the q sweep's
    scratch (which leaves at its last step), dk and dv summed over the
    heads, then ONE read-modify-write of rows ``kv block`` of the two
    float32 accumulators that hold the whole sequence and are the key/value
    head's own; both leave once, at the grid row's last step."""
    t = pl.program_id(2)
    d, dv_w = k_ref.shape[-1], v_ref.shape[-1]

    def rows_of(kv_block):
        return pl.ds(pl.multiple_of(kv_block * bk, bk), bk)

    def kv_blocks_of_the_sequence(block):
        def step(n, carry):
            block(rows_of(n))
            return carry
        jax.lax.fori_loop(0, dk_acc.shape[0] // bk, step, 0)

    @pl.when(t == 0)
    def _open():
        def zero(at):
            dk_acc[at, :] = jnp.zeros((bk, d), jnp.float32)
            dv_acc[at, :] = jnp.zeros((bk, dv_w), jnp.float32)
        kv_blocks_of_the_sequence(zero)

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    off = _selection_off(i_ref, tau_ref, q_of[t] * bq, kv_of[t] * bk, bq, bk)
    k, v = k_ref[0], v_ref[0]
    dk = dv = None
    for h in range(heads):
        q = q_ref[0, :, h * d:(h + 1) * d]
        do = do_ref[0, :, h * dv_w:(h + 1) * dv_w]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s + off - lse_ref[0, h])       # [bq, bk] - the ONE exp
        dv_h = jax.lax.dot_general(                # p^T dO
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = (p * (dov - delta_ref[0, h]) * scale).astype(q.dtype)
        dq_scr[:, h * d:(h + 1) * d] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_h = jax.lax.dot_general(                # ds^T q
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk, dv = (dk_h, dv_h) if dk is None else (dk + dk_h, dv + dv_h)
    rows = rows_of(kv_of[t])
    dk_acc[rows, :] += dk
    dv_acc[rows, :] += dv

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _close():
        def leave(at):
            dk_ref[0, at, :] = dk_acc[at, :].astype(dk_ref.dtype)
            dv_ref[0, at, :] = dv_acc[at, :].astype(dv_ref.dtype)
        kv_blocks_of_the_sequence(leave)


def dsa_fwd_vmem_bytes(heads, head_dim, itemsize, bq=512, bk=512):
    """What ``flash_dsa_fwd`` asks of VMEM at ``heads`` query heads a step:
    the q block of those heads, the k block, the tile of I and the
    thresholds' column (padded to a lane tile) twice each, the heads'
    statistic twice (an output block, a column a head padded to a lane
    tile), their running max and denominator, and six [bq, bk] float32
    values of a step's own."""
    tile, rows = bq * bk * 4, bq * LANES * 4
    return (2 * (heads * bq + bk) * head_dim * itemsize + 2 * tile
            + 2 * rows + 4 * heads * rows + 6 * tile)


def dsa_bwd_vmem_bytes(S, heads, head_dim, v_head_dim, itemsize, bq=512,
                       bk=512):
    """What ``flash_dsa_bwd_fused`` asks of VMEM at ``heads`` query heads a
    step and S keys: the two float32 accumulators of dk and dv of the whole
    sequence and their output blocks (one buffer each: they leave once a
    grid row); the blocks of q and dq (at q's width) and do (at the
    values') of the step's heads twice each and dq's float32 scratch; the
    heads' ``lse`` and ``delta``, a column each padded to a lane tile, twice;
    the tile of I, the thresholds' column, k and v twice; and eight [bq, bk]
    float32 values of a step's own (the selection, a head's scores,
    probabilities, ``dP``, ``dS`` and their rounded copies, the next head's
    beside them)."""
    tile, rows = bq * bk * 4, bq * LANES * 4
    width = head_dim + v_head_dim
    return (S * width * (4 + itemsize)
            + heads * bq * (2 * (2 * head_dim + v_head_dim) * itemsize
                            + 4 * head_dim)
            + 4 * heads * rows + 2 * (tile + rows + bk * width * itemsize)
            + 8 * tile)


def _sweep_maps(chunks):
    """Index maps of a masked sweep's grid (batch row, key/value head, step
    of the table): (q rows of the step's heads, k rows, the heads' row
    statistics, the tile of I, a [.., S, 1] column's rows, a key/value
    head's whole sequence).  ``chunks`` sweeps a grid row: the step's heads
    are the ``head_of[t]``-th of them."""
    def at(pick):
        return lambda b, n, t, q_of, kv_of, head_of, flags: pick(
            b, n, n * chunks + head_of[t], q_of[t], kv_of[t])
    return (at(lambda b, n, c, i, j: (b, i, c)),
            at(lambda b, n, c, i, j: (b, j, n)),
            at(lambda b, n, c, i, j: (b, c, i, 0)),
            at(lambda b, n, c, i, j: (b, i, j)),
            at(lambda b, n, c, i, j: (b, i, 0)),
            at(lambda b, n, c, i, j: (b, 0, n)))


def _lse_call(q, k, scores, tau, n_heads, n_kv_heads, scale, bq, bk,
              interpret):
    """[B, H, S, 1] float32 of q [B, S, H * D], k [B, S, Hkv * D], the
    scores [B, S, S] and the thresholds [B, S, 1]."""
    B, S, W = q.shape
    D, group = W // n_heads, n_heads // n_kv_heads
    need = functools.partial(dsa_fwd_vmem_bytes, head_dim=D,
                             itemsize=q.dtype.itemsize, bq=bq, bk=bk)
    heads = heads_a_step(group, need)
    _count_call("flash_dsa", part="fwd", group=group, heads_in_step=heads,
                statistic_only=1)
    qrow, krow, stat, tile, row, _ = _sweep_maps(group // heads)
    return _triangle_call(
        functools.partial(_lse_kernel, scale=scale, heads=heads, bq=bq,
                          bk=bk),
        "flash_dsa_fwd", step_table(S, S, bq, bk, True, group=group // heads),
        B, (q, k, scores, tau),
        [pl.BlockSpec((1, bq, heads * D), qrow),
         pl.BlockSpec((1, bk, D), krow), pl.BlockSpec((1, bq, bk), tile),
         pl.BlockSpec((1, bq, 1), row)],
        pl.BlockSpec((1, heads, bq, 1), stat),
        jax.ShapeDtypeStruct((B, n_heads, S, 1), jnp.float32),
        [pltpu.VMEM((heads, bq, LANES), jnp.float32)] * 2, interpret,
        row_axes=(n_kv_heads,), **_past_scoped(need(heads)))


def _dsa_bwd_call(q, k, v, do, lse, delta, scores, tau, n_heads, n_kv_heads,
                  scale, bq, bk, interpret):
    """(dq, dk, dv) of ``o``'s cotangent ``do`` under the selection, ``lse``
    and ``delta`` [B, H, S, 1] float32."""
    B, S, W = q.shape
    D, Dv = W // n_heads, v.shape[-1] // n_kv_heads
    group = n_heads // n_kv_heads
    need = functools.partial(dsa_bwd_vmem_bytes, S, head_dim=D,
                             v_head_dim=Dv, itemsize=q.dtype.itemsize, bq=bq,
                             bk=bk)
    heads = heads_a_step(group, need)
    assert need(heads) <= SWEEP_VMEM, \
        "dk and dv of the whole sequence do not fit VMEM"
    _count_call("flash_dsa", part="bwd", group=group, heads_in_step=heads,
                statistic_only=0)
    qrow, krow, stat, tile, row, whole = _sweep_maps(group // heads)
    held = lambda lanes: pl.BlockSpec((1, S, lanes), whole,
                                      pipeline_mode=pl.Buffered(1))
    stats = pl.BlockSpec((1, heads, bq, 1), stat)
    return _triangle_call(
        functools.partial(_dsa_bwd_kernel, scale=scale, heads=heads, bq=bq,
                          bk=bk),
        "flash_dsa_bwd_fused",
        step_table(S, S, bq, bk, True, group=group // heads), B,
        (q, k, v, do, lse, delta, scores, tau),
        [pl.BlockSpec((1, bq, heads * D), qrow),
         pl.BlockSpec((1, bk, D), krow), pl.BlockSpec((1, bk, Dv), krow),
         pl.BlockSpec((1, bq, heads * Dv), qrow), stats, stats,
         pl.BlockSpec((1, bq, bk), tile), pl.BlockSpec((1, bq, 1), row)],
        [pl.BlockSpec((1, bq, heads * D), qrow), held(D), held(Dv)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((bq, heads * D), jnp.float32),
         pltpu.VMEM((S, D), jnp.float32), pltpu.VMEM((S, Dv), jnp.float32)],
        interpret, row_axes=(n_kv_heads,), vmem_limit_bytes=need(heads))


def selected_lse(scores, tau, rows=SELECT_ROWS):
    """``log sum over S_t of exp(I[t, s])`` [B, S]: the selected keys'
    normaliser, ``rows`` rows a block."""
    B, S, _ = scores.shape
    rows = min(rows, S)
    at = jnp.arange(S)

    def block(i):
        x = jax.lax.dynamic_slice_in_dim(scores, i * rows, rows, 1)
        th = jax.lax.dynamic_slice_in_dim(tau, i * rows, rows, 1)
        keep = (at[None] <= (i * rows + jnp.arange(rows))[:, None]) \
            & (x >= th[..., None])
        return jax.nn.logsumexp(jnp.where(keep, x, NEG_INF), axis=-1)

    return _by_rows(block, B, S, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _attend_kl(q, k, v, indexer, scores, tau, lse, lse_i, heads, scale, bq,
               bk, interpret):
    """``heads`` = (H, Hkv); ``indexer`` = (q, k, w) of ``scores``; tau and
    lse_i [B, S, 1], lse [B, H, S, 1]."""
    return _attend_kl_fwd(q, k, v, indexer, scores, tau, lse, lse_i, heads,
                          scale, bq, bk, interpret)[0]


def _attend_kl_fwd(q, k, v, indexer, scores, tau, lse, lse_i, heads, scale,
                   bq, bk, interpret):
    B, S, _ = scores.shape
    o, kl, g = _attend_kl_call(q, k, v, scores, tau, lse, lse_i, *heads,
                               scale, bq, bk, interpret)
    # the pass attends (the caller's scope); the scalar is the loss term's
    with jax.named_scope(devscope.INDEXER_KL):
        kl = jnp.sum(kl) / (B * S)
    return (o, kl), (q, k, v, o, lse, indexer, scores, tau, lse_i, g)


def _attend_kl_bwd(heads, scale, bq, bk, interpret, res, cts):
    q, k, v, o, lse, indexer, scores, tau, lse_i, g = res
    do, ct = cts
    B, S, _ = g.shape
    # the cross entropy reaches q, k and v through o, the statistic a
    # constant (the flash backward's ``delta`` is its account of it); p is a
    # constant of the KL term, whose dI = G ct / (B S) goes straight to the
    # scores' operands: the scalar rides the scores' backward, and no third
    # [S, S] array stands beside I and G.  The selection passes no gradient
    H, Hkv = heads
    geom = _Geom(q, k, H, bq, bk, Hkv, Dv=v.shape[-1] // Hkv)
    d_qkv = tuple(_dsa_bwd_call(
        q, k, v, do, lse, _delta(o, do, geom, True, interpret), scores, tau,
        H, Hkv, scale, bq, bk, interpret))
    with jax.named_scope(devscope.INDEXER):
        dqi, dki, dw = _scores_bwd_call(*indexer, g, bq, bk, interpret,
                                        gain=ct / (B * S))
    return d_qkv + ((dqi, dki, dw),) + tuple(
        jnp.zeros_like(x) for x in (scores, tau, lse, lse_i))


_attend_kl.defvjp(_attend_kl_fwd, _attend_kl_bwd)


def dsa_lse(q, k, scores, tau, n_heads, n_kv_heads=None, scale=None,
            block_q=512, block_k=512, interpret=None):
    """[B, H, S] float32: each head's log-sum-exp over the keys its row
    selects, ``log sum over S_t of exp(scale q_t . k_s)``, by the online
    softmax's running max and denominator alone (kernel ``flash_dsa_fwd``:
    no ``P V`` and no output but the statistic), a constant: nothing
    differentiates through it.  Every row must select a key."""
    B, S, E = q.shape
    H, Hkv = int(n_heads), int(n_kv_heads or n_heads)
    D = E // H
    assert D % LANES == 0 and H % Hkv == 0 and k.shape == (B, S, Hkv * D), (
        q.shape, k.shape, H, Hkv)
    assert scores.shape == (B, S, S) and tau.shape == (B, S), (
        scores.shape, tau.shape)
    bq, bk, interpret = _blocks(S, block_q, block_k, interpret)
    stop = jax.lax.stop_gradient
    return _lse_call(stop(q), stop(k), stop(scores), stop(tau)[..., None], H,
                     Hkv, float(D ** -0.5 if scale is None else scale), bq,
                     bk, interpret)[..., 0]


def dsa_attend_kl(q, k, v, indexer, scores, tau, lse, lse_i, n_heads,
                  n_kv_heads=None, scale=None, block_q=512, block_k=512,
                  interpret=None, v_head_dim=None):
    """``(o, kl)`` of a learned-sparse layer with the statistic KNOWN: the
    attention's output under the selection, ``o[t] = sum over S_t of exp(s -
    lse) v`` [B, S, H * D], and the mean over batch rows and tokens of
    ``KL(p_t || softmax over S_t of I[t, .])``, ``S_t = {s <= t : I[t, s] >=
    tau_t}``, ``p`` the mean over the heads of those same probabilities.
    ONE sweep of the causal triangle (kernel ``dsa_attend_kl_fwd``): a grid
    step is a (tile, key/value head) pair, the heads innermost and a group's
    query heads looped inside the step (k and v are fetched once a group),
    so the tile of I, the thresholds and the selection are the tile's and
    not a head's; each head's normalised tile goes through ``p v`` into its
    float32 accumulator and into the heads' sum; the tile's last step
    writes the KL part and ``G = softmax_S(I) - p`` (0 off ``S_t``), which
    IS ``dKL/dI``; a q block's last step writes its ``o`` for every head.

    q [B, S, H * D], k and v [B, S, Hkv * D], D whole lane blocks (v and
    ``o`` at ``v_head_dim`` a head where given: whole lane blocks too, no
    grouping; give ``scale`` where D holds lanes that are not the head's);
    ``scores`` [B, S, S] = ``indexer_scores(*indexer)``, ``indexer`` its
    (q, k, w); ``tau`` [B, S]; ``lse`` [B, H, S] (``dsa_lse``) and ``lse_i``
    [B, S] (``selected_lse``), both constants.  Gradients: o's to q, k and
    v by the masked backward sweep (``flash_dsa_bwd_fused``, which re-makes
    p from ``lse`` as this pass does); kl's to ``indexer`` alone, through
    ``indexer_scores_bwd`` on ``G`` with the cotangent's scalar as its
    gain (``scores`` itself gets zeros: its only way on is here)."""
    B, S, E = q.shape
    H, Hkv = int(n_heads), int(n_kv_heads or n_heads)
    D = E // H
    Dv = D if v_head_dim is None else int(v_head_dim)
    assert D % LANES == 0 and Dv % LANES == 0, "a head is whole lane blocks"
    assert Dv == D or H == Hkv, "a value width of its own: no grouping"
    assert k.shape == (B, S, Hkv * D) and v.shape == (B, S, Hkv * Dv), (
        k.shape, v.shape)
    assert lse.shape == (B, H, S) and lse_i.shape == tau.shape == (B, S), (
        lse.shape, lse_i.shape, tau.shape)
    bq, bk, interpret = _blocks(S, block_q, block_k, interpret)
    qi, ki, w = indexer
    assert scores.shape == (B, S, S) and qi.shape[:2] == (B, S), (
        scores.shape, qi.shape)
    return _attend_kl(q, k, v, (qi, ki, w.astype(jnp.float32)),
                      jax.lax.stop_gradient(scores), tau[..., None],
                      lse[..., None], lse_i[..., None], (H, Hkv),
                      float(D ** -0.5 if scale is None else scale), bq, bk,
                      interpret)
