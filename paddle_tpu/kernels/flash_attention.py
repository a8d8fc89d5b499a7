"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

Parity target: the reference's fused attention CUDA op
(operators/fused/multihead_matmul_op.cu, surfaced by
ir/multihead_matmul_fuse_pass.cc) — but trained-path capable: blockwise
streaming softmax never materializes the [S, S] score matrix in HBM, so both
memory and HBM traffic drop from O(S^2) to O(S * block).

Layout: q, k, v are [BH, S, D] (batch*heads flattened).  Grid is
(BH, q_blocks, kv_blocks) with the kv axis innermost; the running max (m),
denominator (l) and output accumulator live in VMEM scratch across the kv
sweep (the standard TPU flash schedule).  The backward pass recomputes
probabilities blockwise from the saved row logsumexp L (two kernels: a dq
sweep and a dk/dv sweep), per the FlashAttention-2 formulation.

All matmuls feed the MXU in the input dtype with f32 accumulation.
interpret=True (CPU tests) is selected automatically off-TPU.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["flash_attention", "flash_attention_packed"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

LANES = 128   # running row stats ride full-lane [bq, 128] layouts: a lane-1
              # layout forces Mosaic relayouts on every broadcast against the
              # [bq, bk] score tile (the single biggest cost in the r2 kernel)


def _lanes_to(x, n):
    """Broadcast a [rows, LANES] lane-replicated stat to n lanes."""
    if n >= LANES:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n]


def packed_layout_supported(n_heads, head_dim):
    """True when the packed [B, S, H*D] entry can address this head shape
    (Mosaic lane-tiling rule; see _heads_per_block)."""
    hpb = max(1, LANES // head_dim)
    return (head_dim * hpb) % LANES == 0 and n_heads % hpb == 0


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, bq, bk, hpb=1):
    """hpb = heads per block.  The packed [B, S, H*D] layout needs 128-wide
    lane blocks (Mosaic tiling rule), so for D=64 each kernel instance
    processes 2 adjacent heads: the block's columns are per-head slices and
    every head keeps independent running stats.  hpb=1 is the [BH, S, D]
    layout.  Heads never mix: each dot contracts only its own D columns."""
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    D = q_ref.shape[-1] // hpb

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    i = pl.program_id(1)
    run = True
    if causal:
        # whole kv block strictly in the future -> skip
        run = (j * bk) <= (i * bq + bq - 1)

    @pl.when(run if causal else (j >= 0))
    def _body():
        for hh in range(hpb):
            cs = slice(hh * D, (hh + 1) * D)
            ls = slice(hh * LANES, (hh + 1) * LANES)
            q = q_ref[0][:, cs]                            # [bq, D]
            k = k_ref[0][:, cs]                            # [bk, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                      # [bq, bk]
            if causal:
                qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)

            m_prev = m_scr[:, ls]                          # [bq, LANES]
            m_cur = jnp.max(s, axis=1)[:, None]            # [bq, 1]
            m_new = jnp.maximum(m_prev, m_cur)             # [bq, LANES]
            p = jnp.exp(s - _lanes_to(m_new, bk))          # [bq, bk] f32
            alpha = jnp.exp(m_prev - m_new)                # [bq, LANES]
            l_scr[:, ls] = l_scr[:, ls] * alpha + jnp.sum(p, axis=1)[:, None]
            acc_scr[:, cs] = acc_scr[:, cs] * _lanes_to(alpha, D) \
                + jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0][:, cs],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            m_scr[:, ls] = m_new

    @pl.when(j == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[:], 1e-30)
        alpha_cols = jnp.concatenate(
            [_lanes_to(l[:, hh * LANES:(hh + 1) * LANES], D)
             for hh in range(hpb)], axis=1) if hpb > 1 else _lanes_to(l, D)
        o_ref[0] = (acc_scr[:] / alpha_cols).astype(o_ref.dtype)
        # lse rides a [bq, hpb] lane-narrow block: the DMA transfers only the
        # valid lanes, and no in-kernel transpose is needed (a lane-replicated
        # [bq, 128] output costs ~150MB/layer of HBM traffic at bench shapes;
        # a lane-oriented [1, bq] output costs a Mosaic relayout per block —
        # both measured slower than this form)
        lse_ref[0, 0] = jnp.concatenate(
            [m_scr[:, hh * LANES:hh * LANES + 1]
             + jnp.log(l[:, hh * LANES:hh * LANES + 1]) for hh in range(hpb)],
            axis=1)


def _heads_per_block(D):
    """Packed layout: Mosaic requires the last block dim be a multiple of 128
    (or the full array dim), so D=64 heads pair up 2-per-block; D>=128 heads
    stand alone."""
    return max(1, LANES // D)


class _Geom:
    """Grid/block geometry for the two layouts.  H=None: [BH, S, D]
    separate-heads.  H=int: packed [B, S, H*D] — per-head column slices are
    addressed by the BlockSpec index maps, so the model never materializes a
    [B, H, S, D] transpose (the r2 wrapper's main HBM cost)."""

    def __init__(self, q, k, H):
        if H is None:
            self.BH, self.S, self.D = q.shape
            self.hpb = 1
            self.qw = self.D          # block width (lane dim)
            self.o_shape = q.shape
            # stats are 4-D so the block's last dim equals the array's
            # (Mosaic tiling rule): [outer, head-block, S, heads-per-block]
            self.stat_shape = (self.BH, 1, self.S, 1)
            self.dkv_shape = k.shape
            self.grid_b = self.BH
            self.Hb = None
        else:
            B, self.S, E = q.shape
            self.D = E // H
            self.hpb = _heads_per_block(self.D)
            assert H % self.hpb == 0 and (self.D * self.hpb) % LANES == 0, (H, self.D)
            self.qw = self.D * self.hpb
            self.o_shape = q.shape
            self.Hb = H // self.hpb   # head-blocks per batch
            self.stat_shape = (B, self.Hb, self.S, self.hpb)
            self.dkv_shape = k.shape
            self.grid_b = B * self.Hb
        self.Sk = k.shape[1]

    # index maps: 3-arg (b, i, j) with i indexing q rows, j kv rows
    def qmap(self):
        Hb = self.Hb
        if Hb is None:
            return lambda b, i, j=0: (b, i, 0)
        return lambda b, i, j=0: (b // Hb, i, b % Hb)

    def kmap(self):
        Hb = self.Hb
        if Hb is None:
            return lambda b, i, j=0: (b, j, 0)
        return lambda b, i, j=0: (b // Hb, j, b % Hb)

    def smap(self):
        Hb = self.Hb
        if Hb is None:
            return lambda b, i, j=0: (b, 0, i, 0)
        return lambda b, i, j=0: (b // Hb, b % Hb, i, 0)

    def q_spec(self, bq):
        return pl.BlockSpec((1, bq, self.qw), self.qmap())

    def kv_spec(self, bk):
        return pl.BlockSpec((1, bk, self.qw), self.kmap())

    def stat_spec(self, bq):
        return pl.BlockSpec((1, 1, bq, self.hpb), self.smap())


def _fwd(q, k, v, scale, causal, bq, bk, interpret, H=None):
    """H=None: q/k/v are [BH, S, D].  H=int: q/k/v are [B, S, H*D]."""
    g = _Geom(q, k, H)
    nq, nk = g.S // bq, g.Sk // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, hpb=g.hpb)
    o, lse = pl.pallas_call(
        kernel,
        grid=(g.grid_b, nq, nk),
        in_specs=[
            g.q_spec(bq),
            g.kv_spec(bk),
            g.kv_spec(bk),
        ],
        out_specs=[
            g.q_spec(bq),
            # row stats as narrow-lane blocks (see _final)
            g.stat_spec(bq),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(g.o_shape, q.dtype),
            jax.ShapeDtypeStruct(g.stat_shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, g.hpb * LANES), jnp.float32),
            pltpu.VMEM((bq, g.hpb * LANES), jnp.float32),
            pltpu.VMEM((bq, g.qw), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# fused backward (single kernel) for the single-kv-block case: when all of
# K/V fits one block (Sk == bk), dq/dk/dv share ONE recomputed probability
# matrix — one exp pass and 5 matmuls instead of the two-sweep schedule's
# two exp passes and 7 matmuls.  This is the hot path for the bench shapes
# (S=512, block 512).
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      scale, causal, bq, bk, hpb=1):
    i = pl.program_id(1)
    nq = pl.num_programs(1)
    D = q_ref.shape[-1] // hpb

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dq_cols = []
    for hh in range(hpb):
        cs = slice(hh * D, (hh + 1) * D)
        q = q_ref[0][:, cs]
        k = k_ref[0][:, cs]
        v = v_ref[0][:, cs]
        do = do_ref[0][:, cs]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, hh:hh + 1])       # [bq, bk] — the ONE exp
        pv = p.astype(do.dtype)
        dv_scr[:, cs] += jax.lax.dot_general(pv, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0][:, cs].astype(jnp.float32),
                        axis=1)[:, None]                # [bq, 1]
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = (p * (dov - delta) * scale).astype(q.dtype)  # [bq, bk]
        dq_cols.append(jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dk_scr[:, cs] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
    dq_ref[0] = (jnp.concatenate(dq_cols, axis=1) if hpb > 1
                 else dq_cols[0]).astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused(scale, causal, bq, bk, interpret, res, do, H=None):
    q, k, v, o, lse = res
    g = _Geom(q, k, H)
    nq = g.S // bq
    # 2-arg index maps (grid has no kv axis): kv lives at block 0
    qm, km, sm = g.qmap(), g.kmap(), g.smap()
    qb = lambda b, i: qm(b, i, 0)
    kb = lambda b, i: km(b, i, 0)
    sb = lambda b, i: sm(b, i, 0)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, hpb=g.hpb),
        grid=(g.grid_b, nq),
        in_specs=[
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, bk, g.qw), kb),
            pl.BlockSpec((1, bk, g.qw), kb),
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, 1, bq, g.hpb), sb),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, bk, g.qw), kb),
            pl.BlockSpec((1, bk, g.qw), kb),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(g.dkv_shape, k.dtype),
            jax.ShapeDtypeStruct(g.dkv_shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, g.qw), jnp.float32),
            pltpu.VMEM((bk, g.qw), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_fused",
    )(q, k, v, o, do, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# backward: dq sweep (grid kv-innermost) and dk/dv sweep (grid q-innermost)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, bq, bk, hpb=1):
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    i = pl.program_id(1)
    D = q_ref.shape[-1] // hpb

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)

    @pl.when(run if causal else (j >= 0))
    def _body():
        for hh in range(hpb):
            cs = slice(hh * D, (hh + 1) * D)
            q = q_ref[0][:, cs]
            k = k_ref[0][:, cs]
            v = v_ref[0][:, cs]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, 0][:, hh:hh + 1])   # [bq, bk]
            dov = jax.lax.dot_general(do_ref[0][:, cs], v,
                                      (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            ds = p * (dov - delta_ref[0, 0][:, hh:hh + 1]) * scale  # [bq, bk] f32
            acc_scr[:, cs] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, bq, bk,
                    hpb=1):
    i = pl.program_id(2)           # q blocks innermost here
    nq = pl.num_programs(2)
    j = pl.program_id(1)
    D = q_ref.shape[-1] // hpb

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = (j * bk) <= (i * bq + bq - 1)

    @pl.when(run if causal else (i >= 0))
    def _body():
        for hh in range(hpb):
            cs = slice(hh * D, (hh + 1) * D)
            q = q_ref[0][:, cs]
            k = k_ref[0][:, cs]
            v = v_ref[0][:, cs]
            do = do_ref[0][:, cs]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, 0][:, hh:hh + 1])   # [bq, bk]
            # dv_j += p^T dO
            dv_scr[:, cs] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            ds = p * (dov - delta_ref[0, 0][:, hh:hh + 1]) * scale
            # dk_j += ds^T q
            dk_scr[:, cs] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, bq, bk, interpret, res, do, H=None):
    q, k, v, o, lse = res
    g = _Geom(q, k, H)
    nq, nk = g.S // bq, g.Sk // bk
    if nk == 1:
        return _bwd_fused(scale, causal, bq, bk, interpret, res, do, H=H)
    if H is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True).reshape(g.stat_shape)
    else:
        B = q.shape[0]
        delta = jnp.sum(
            (do.astype(jnp.float32) * o.astype(jnp.float32))
            .reshape(B, g.S, g.Hb, g.hpb, g.D), axis=-1
        ).transpose(0, 2, 1, 3)                           # [B, Hb, S, hpb]
    qb, kb, sb = g.qmap(), g.kmap(), g.smap()

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, hpb=g.hpb),
        grid=(g.grid_b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, bk, g.qw), kb),
            pl.BlockSpec((1, bk, g.qw), kb),
            pl.BlockSpec((1, bq, g.qw), qb),
            pl.BlockSpec((1, 1, bq, g.hpb), sb),
            pl.BlockSpec((1, 1, bq, g.hpb), sb),
        ],
        out_specs=pl.BlockSpec((1, bq, g.qw), qb),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, g.qw), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dkv sweep: grid is (b, kv, q) — the index-map roles swap
    qb2 = (lambda b, j, i: qb(b, i, j))
    kb2 = (lambda b, j, i: kb(b, i, j))
    sb2 = (lambda b, j, i: sb(b, i, j))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, hpb=g.hpb),
        grid=(g.grid_b, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, g.qw), qb2),
            pl.BlockSpec((1, bk, g.qw), kb2),
            pl.BlockSpec((1, bk, g.qw), kb2),
            pl.BlockSpec((1, bq, g.qw), qb2),
            pl.BlockSpec((1, 1, bq, g.hpb), sb2),
            pl.BlockSpec((1, 1, bq, g.hpb), sb2),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, g.qw), kb2),
            pl.BlockSpec((1, bk, g.qw), kb2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(g.dkv_shape, k.dtype),
            jax.ShapeDtypeStruct(g.dkv_shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, g.qw), jnp.float32),
            pltpu.VMEM((bk, g.qw), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, bq, bk, interpret):
    o, _ = _fwd(q, k, v, scale, causal, bq, bk, interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, bq, bk, interpret):
    o, lse = _fwd(q, k, v, scale, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, bq, bk, interpret, res, do):
    return _bwd(scale, causal, bq, bk, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_packed(q, k, v, H, scale, causal, bq, bk, interpret):
    o, _ = _fwd(q, k, v, scale, causal, bq, bk, interpret, H=H)
    return o


def _flash_packed_fwd(q, k, v, H, scale, causal, bq, bk, interpret):
    o, lse = _fwd(q, k, v, scale, causal, bq, bk, interpret, H=H)
    return o, (q, k, v, o, lse)


def _flash_packed_bwd(H, scale, causal, bq, bk, interpret, res, do):
    return _bwd(scale, causal, bq, bk, interpret, res, do, H=H)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=256, interpret=None):
    """q, k, v: [B, S, H, D] (model layout).  Returns [B, S, H, D].

    Falls back gracefully: callers should gate on shape divisibility (see
    parallel/transformer.py attention dispatch).
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)

    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), float(scale), bool(causal),
               bq, bk, bool(interpret))
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_packed(q, k, v, n_heads, causal=False, scale=None,
                           block_q=256, block_k=256, interpret=None):
    """Packed-layout flash attention: q, k, v are [B, S, H*D] exactly as the
    qkv projections produce them; returns [B, S, H*D] ready for the output
    projection.  The per-head D-wide column slices are addressed by the
    Pallas BlockSpec index maps, so no [B, H, S, D] transpose or reshape ever
    touches HBM (~8 layout copies/layer saved vs the bshd entry at bench
    shapes)."""
    B, S, E = q.shape
    H = n_heads
    assert E % H == 0, (E, H)
    D = E // H
    if not packed_layout_supported(H, D):
        raise ValueError(
            "packed layout cannot tile H=%d heads of D=%d (needs D*hpb a "
            "multiple of %d lanes with hpb dividing H); use flash_attention "
            "on [B, S, H, D]" % (H, D, LANES))
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)
    return _flash_packed(q, k, v, H, float(scale), bool(causal),
                         bq, bk, bool(interpret))
