"""What stands AROUND the delta rule in the KDA mixer, as Pallas TPU row
kernels (fwd + custom-VJP bwd) on the FLAT arrays ``[b, S, heads * 128]``
that the filters write and ``kda_chunk`` reads: ``l2_heads``, ``log_decay``
and ``norm_gate``.  A head is ONE lane tile, so a head's statistic is one
lane reduce of a tile where it lies: no ``[b, S, heads, d]`` view of a
sequence-sized array is formed.  Each is what these lines of
``parallel/transformer.py:kda_mixer`` give (the ``*_reference`` functions,
the tests' second opinion and the mixer's path where ``supported`` says no),
in float32 and rounded ONCE:

    l2_heads    x / sqrt(sum_head(x^2) + 1e-6) * scale          x's type
    log_decay   -exp(a_log[head]) * softplus(pre + dt_bias)     float32
    norm_gate   o * rsqrt(mean_head(o^2) + eps) * o_norm * sigmoid(gate_pre)
                the norm THEN the gate (``gated_norm`` gates first)  o's type

The write strength is none of these passes: ``beta`` [b, S, heads] is a
matmul's epilogue in the mixer (``transformer.kda_write_strength``: a sigmoid,
times 2 where ``kda_beta_scale`` is 2), in (0, 1) or (0, 2); the three passes
neither read it nor depend on its range (what a strength past 1 does to an
error the state carries is ``kernels/kda_chunk.py``'s to say).  At 64 heads
(``solar_open2_250b.s4096_scan``: [1, 4096, 8192], sixteen lane blocks of
four heads where Kimi-Linear's [1, 16384, 4096] has eight) the geometry and
``vmem_bytes`` are the same, (1024, 128, 512) and 18.1 MiB for the widest
backward: a grid step is a block, whatever the array's width.

Why kernels (PERF.md section 6, PR 60): the lines reduce over a RESHAPED
minor dimension, and on this chip a ``reshape`` between ``[16384, 4096]`` and
``[16384, 32, 128]`` is a copy: one KDA layer's recompute + backward at the
Kimi cell's ``[1, 16384, 4096]`` moved 20.4 GB outside its matmuls and
kernels (nine float32 copies, six float32 reshapes, six broadcasts of a
head's statistic back at full width) where the work below needs 3.6.

- a grid step holds ``[block rows, block lanes]`` of every sequence-sized
  operand (``BLOCK_LANES``: several heads side by side, so that a row of a
  block is a long run of HBM) and WALKS it ``walk`` rows at a time
  (``gated_norm``'s ``block_rows`` / ``walk_rows``), a head's lane tile
  after another inside a turn, everything of a turn in float32;
- the backwards read what the forwards read and the cotangent and make the
  statistic again: nothing float32 of size [S, P] is kept.  With r the
  statistic, n = o r, s = sigmoid(gate_pre), x = pre + dt_bias:

      l2_heads    dx = scale r (dy - x r^2 sum_head(dy x))
      log_decay   d pre = dg (-exp(a_log)) sigmoid(x)
                  d dt_bias = sum_rows(d pre)    d a_log = sum_rows,head(dg g)
      norm_gate   d gate_pre = dout n o_norm s (1 - s)
                  do = r (dn - n mean_head(dn n)),  dn = dout o_norm s
                  d o_norm = sum_rows,heads(dout n s)

  the three parameters' gradients summed in float32 over the row blocks in a
  revisited output block (rows the grid's innermost, sequential axis), eight
  sublanes a lane: no cross-sublane reduce in a kernel; the batch rows, the
  sublanes and (``a_log``, ``o_norm``) the lanes are summed outside;
- ``log_decay``'s FORWARD is no kernel: with the rate one a lane (``a_log``
  repeated 128 times, 16 KB) the lines are pointwise on the flat array, and
  XLA makes them the epilogue of the low-rank matmul that makes ``pre``
  (0.41 ms a call in the step; as a kernel behind that matmul it read 0.80
  more, PERF.md section 6, PR 60).  Its backward kernel reads g, which the
  delta rule keeps anyway, and not ``pre``: ``softplus(x) = g / rate`` and
  ``sigmoid(x) = 1 - exp(-softplus(x))``, so ``pre`` is never in HBM and no
  ``exp(+large)`` is formed.

The geometry, by device trace at the cell's [1, 16384, 4096] bf16
(``scripts/kda_rows_receipt.py``, PERF.md section 6, PR 60; forward /
backward us a call of ``l2_heads``, ``norm_gate``; their bytes need 328 /
492 and 656 / 1,147; the ``jnp`` lines took 3,779 / 7,545 forward / both
and 4,157 / 9,141): **blocks of 1,024 rows x 512 lanes walked 128 rows a
turn 403 / 601, 783 / 1,395 (shipped)**; 512 rows 425 / 635, 792 / 1,396;
2,048 rows 404 / 591, 795 / 1,402; walked 64 rows 417 / 641, 788 / 1,389;
256 rows 402 / 593, 785 / 1,394; 256 lanes x 2,048 rows 461 / 681, 790 /
1,388; ONE head a block (128 lanes x 2,048 rows) 505 / 718, 849 / 1,439;
1,024 lanes x 512 rows 410 / 606, 776 / 1,402; whole rows (4,096 lanes x
128 rows walked 16) 411 / 601, 771 / 1,410.  1.2 times the bytes' least
whatever the blocks but for the narrow ones.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import gated_norm as _gn
from ._common import (LANES, SUBLANES, CompilerParams as _CompilerParams,
                      on_tpu as _on_tpu, sublane_sums as _sublane_sums)

__all__ = ["l2_heads", "l2_heads_reference", "log_decay",
           "log_decay_reference", "norm_gate", "norm_gate_reference",
           "supported", "geometry", "vmem_bytes", "PARTS"]

L2_EPS = 1e-6               # under the root of a head's L2 norm
BLOCK_LANES = (512, 256, 128)   # of a grid step's block: whole heads
F32 = jnp.float32
PARTS = ("l2_heads", "log_decay", "norm_gate")
# bytes an element of a backward's blocks takes, by the operands' itemsize:
# x, dy, dx; g, dg, d pre; o, dout, do beside gate_pre and its gradient
_BWD_BYTES = {"l2_heads": lambda i: 3 * i, "log_decay": lambda i: 12,
              "norm_gate": lambda i: 3 * i + 8}


def geometry(S, P, itemsize):
    """(block rows, walk rows, block lanes) of the kernels on ``[b, S, P]``
    whose narrowest element has ``itemsize`` bytes: the widest of
    BLOCK_LANES that divides P, ``gated_norm``'s rows for it; None where S
    is no whole sublane tiles of the element or P no whole heads."""
    lanes = next((n for n in BLOCK_LANES if P % n == 0), None)
    bs = lanes and _gn.block_rows(S, lanes, itemsize)
    return bs and (bs, _gn.walk_rows(bs, lanes, itemsize), lanes)


def vmem_bytes(part, bs, lanes, itemsize):
    """What ``part``'s backward, the larger of its two calls, asks Mosaic
    for: its pipelined blocks, two copies each, the sums' blocks and the
    lanes' parameters, and room for what the compiler keeps of a turn."""
    return (2 * _BWD_BYTES[part](itemsize) * bs * lanes
            + 8 * (SUBLANES + 1) * lanes * 4 + (4 << 20))


def supported(shape, head_dim, itemsize):
    """Whether the kernels take ``[b, S, heads * head_dim]`` arrays whose
    narrowest element has ``itemsize`` bytes: a head ONE lane tile, S in
    whole sublane tiles of that element (a block is within VMEM by
    ``gated_norm.BLOCK_ELEMENTS``: ``vmem_bytes`` at most 19 MB)."""
    _, S, P = shape
    return head_dim == LANES and P % LANES == 0 \
        and geometry(S, P, itemsize) is not None


# -- the mixer's own lines ------------------------------------------------

def l2_heads_reference(x, heads, scale):
    """x [b, S, heads * d] as heads [b, S, heads, d], each ``x / |x|_2 *
    scale`` (eps 1e-6 under the root), float32."""
    x = x.reshape(x.shape[:2] + (heads, -1)).astype(F32)
    return (x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                               + L2_EPS) * scale))


def log_decay_reference(pre, dt_bias, a_log):
    """``-exp(a_log) * softplus(pre + dt_bias)`` as [b, S, heads, d] float32:
    pre [b, S, heads * d] float32, dt_bias [heads * d], a_log [heads]."""
    step = jax.nn.softplus(pre + dt_bias)
    return -jnp.exp(a_log)[:, None] * step.reshape(
        pre.shape[:2] + (a_log.shape[0], -1))


def norm_gate_reference(o, gate_pre, o_norm, eps):
    """o [b, S, heads, d] RMS-normed a head by the one scale ``o_norm`` [d],
    THEN gated by ``sigmoid(gate_pre)`` (gate_pre [b, S, heads * d]
    float32); float32 [b, S, heads, d]."""
    gate = jax.nn.sigmoid(gate_pre)
    o = o.astype(F32)
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                             + eps) * o_norm * gate.reshape(o.shape)


# -- the kernels ----------------------------------------------------------

def _walk(ref, walk, turn):
    """``turn(rows, head)`` over a block like ``ref``'s [rows, lanes]:
    ``walk`` rows at a time in one traced loop, inside it a head's lane tile
    after another."""
    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * walk, walk), walk)
        for at in range(0, ref.shape[1], LANES):
            turn(rows, slice(at, at + LANES))
        return carry

    jax.lax.fori_loop(0, ref.shape[0] // walk, body, 0)


def _zero_at_first_row_block(*refs):
    @pl.when(pl.program_id(2) == 0)
    def _():
        for ref in refs:
            ref[...] = jnp.zeros(ref.shape, F32)


def _l2_statistic(x):
    return jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _l2_fwd_kernel(x_ref, o_ref, *, scale, walk):
    def turn(rows, head):
        x = x_ref[rows, head].astype(F32)
        o_ref[rows, head] = (x * (_l2_statistic(x) * scale)).astype(
            o_ref.dtype)

    _walk(x_ref, walk, turn)


def _l2_bwd_kernel(x_ref, dy_ref, dx_ref, *, scale, walk):
    def turn(rows, head):
        x = x_ref[rows, head].astype(F32)
        dy = dy_ref[rows, head].astype(F32)
        r = _l2_statistic(x)
        along = jnp.sum(dy * x, axis=-1, keepdims=True)
        dx_ref[rows, head] = ((r * scale) * (dy - x * (r * r * along))
                              ).astype(dx_ref.dtype)

    _walk(x_ref, walk, turn)


def _decay_bwd_kernel(g_ref, dg_ref, rate_ref, inv_ref, dpre_ref, dbias_ref,
                      drate_ref, *, walk):
    """``rate_ref``: ``-exp(a_log)`` of a lane's head, ``inv_ref`` one over
    it, [1, lanes].  The step ``softplus(x) = g / rate`` gives ``sigmoid(x)
    = 1 - exp(-step)`` (its series where the step is small: no
    cancellation).  Grid (b, lane blocks, row blocks), the rows innermost
    and in order: ``dbias_ref`` and ``drate_ref`` [8, lanes] sum over
    them."""
    _zero_at_first_row_block(dbias_ref, drate_ref)

    def turn(rows, head):
        g, dg = g_ref[rows, head], dg_ref[rows, head]
        step = g * inv_ref[:, head]
        sig = jnp.where(step < 1e-2,
                        step * (1.0 - step * (0.5 - step * (1.0 / 6.0))),
                        1.0 - jnp.exp(-step))
        dpre = dg * rate_ref[:, head] * sig
        dpre_ref[rows, head] = dpre
        dbias_ref[:, head] += _sublane_sums(dpre)
        drate_ref[:, head] += _sublane_sums(dg * g)

    _walk(g_ref, walk, turn)


def _rms_statistic(o, eps):
    return jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)


def _gate_fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps, walk):
    def turn(rows, head):
        o = o_ref[rows, head].astype(F32)
        y_ref[rows, head] = (o * _rms_statistic(o, eps) * w_ref[:, head]
                             * jax.nn.sigmoid(z_ref[rows, head])).astype(
                                 y_ref.dtype)

    _walk(o_ref, walk, turn)


def _gate_bwd_kernel(o_ref, z_ref, dy_ref, w_ref, do_ref, dz_ref, dw_ref, *,
                     eps, walk):
    """Grid as ``_decay_bwd_kernel``'s; ``dw_ref`` [8, lanes] sums ``d
    o_norm`` a lane (the heads share the scale: summed outside)."""
    _zero_at_first_row_block(dw_ref)

    def turn(rows, head):
        o = o_ref[rows, head].astype(F32)
        dy = dy_ref[rows, head].astype(F32)
        s = jax.nn.sigmoid(z_ref[rows, head])
        r = _rms_statistic(o, eps)
        n = o * r
        dns = dy * n                    # d (w s) of the product n (w s)
        dz_ref[rows, head] = (dns * w_ref[:, head] * (s * (1.0 - s))).astype(
            dz_ref.dtype)
        dn = dy * w_ref[:, head] * s
        do_ref[rows, head] = (r * (dn - n * jnp.mean(
            dn * n, axis=-1, keepdims=True))).astype(do_ref.dtype)
        dw_ref[:, head] += _sublane_sums(dns * s)

    _walk(o_ref, walk, turn)


def _call(kernel, name, part, rows, lanes_in, outs, sums, interpret):
    """One of the six calls: ``rows`` the sequence-sized operands [b, S, P],
    ``lanes_in`` the parameters a lane [1, P] float32, ``outs`` the element
    types of the sequence-sized results and ``sums`` how many [b, 8, P]
    float32 blocks of sums behind them.  Grid (b, lane blocks, row blocks)."""
    b, S, P = rows[0].shape
    itemsize = min(a.dtype.itemsize for a in rows)
    bs, walk, lanes = geometry(S, P, itemsize)
    block = pl.BlockSpec((None, bs, lanes), lambda bi, li, ri: (bi, ri, li))
    a_lane = pl.BlockSpec((1, lanes), lambda bi, li, ri: (0, li))
    summed = pl.BlockSpec((None, SUBLANES, lanes),
                          lambda bi, li, ri: (bi, 0, li))
    return pl.pallas_call(
        functools.partial(kernel, walk=walk),
        grid=(b, P // lanes, S // bs),
        in_specs=[block] * len(rows) + [a_lane] * len(lanes_in),
        out_specs=[block] * len(outs) + [summed] * sums,
        out_shape=[jax.ShapeDtypeStruct((b, S, P), t) for t in outs]
        + [jax.ShapeDtypeStruct((b, SUBLANES, P), F32)] * sums,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if sums else "parallel"),
            vmem_limit_bytes=vmem_bytes(part, bs, lanes, itemsize)),
        interpret=interpret, name=name,
    )(*rows, *lanes_in)


def _a_lane(v):
    """A parameter one a lane [P] as the [1, P] float32 block the kernels
    read: 16 KB at 32 heads."""
    return v.astype(F32).reshape(1, -1)


def _rates(a_log, sign=1.0):
    """``-exp(sign * a_log)`` [heads] as one a lane, [heads * 128]."""
    return jnp.repeat(-jnp.exp(sign * a_log.astype(F32)), LANES)


def _lane_sums(sums, like, over=None):
    """[b, 8, P] partial sums as the gradient of ``like``: one a lane [P],
    or ``over`` = 1: a head's lanes summed [heads], 0: the heads summed
    [128]."""
    total = jnp.sum(sums, axis=(0, 1))
    if over is not None:
        total = jnp.sum(total.reshape(-1, LANES), axis=over)
    return total.astype(like.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _l2(x, scale, interpret):
    return _call(functools.partial(_l2_fwd_kernel, scale=scale),
                 "kda_l2_heads_fwd", "l2_heads", [x], [], [x.dtype], 0,
                 interpret)[0]


def _l2_fwd(x, scale, interpret):
    return _l2(x, scale, interpret), x


def _l2_bwd(scale, interpret, x, dy):
    return tuple(_call(functools.partial(_l2_bwd_kernel, scale=scale),
                       "kda_l2_heads_bwd", "l2_heads",
                       [x, dy.astype(x.dtype)], [], [x.dtype], 0, interpret))


_l2.defvjp(_l2_fwd, _l2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decay(pre, dt_bias, a_log, interpret):
    # the lines themselves on the flat array: pointwise once the rate is one
    # a lane, so XLA makes them the epilogue of the matmul that makes ``pre``
    return _rates(a_log) * jax.nn.softplus(pre + dt_bias)


def _decay_fwd(pre, dt_bias, a_log, interpret):
    g = _decay(pre, dt_bias, a_log, interpret)
    return g, (g, dt_bias, a_log)       # g: ``kda_chunk`` keeps it anyway


def _decay_bwd(interpret, res, dg):
    g, dt_bias, a_log = res
    dpre, dbias, drate = _call(
        _decay_bwd_kernel, "kda_log_decay_bwd", "log_decay",
        [g, dg.astype(F32)],
        [_a_lane(_rates(a_log)), _a_lane(_rates(a_log, -1.0))], [F32], 2,
        interpret)
    return dpre, _lane_sums(dbias, dt_bias), _lane_sums(drate, a_log, 1)


_decay.defvjp(_decay_fwd, _decay_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gate(o, gate_pre, o_norm, eps, interpret):
    return _call(functools.partial(_gate_fwd_kernel, eps=eps),
                 "kda_norm_gate_fwd", "norm_gate", [o, gate_pre],
                 [_a_lane(jnp.tile(o_norm, o.shape[-1] // LANES))],
                 [o.dtype], 0, interpret)[0]


def _gate_fwd(o, gate_pre, o_norm, eps, interpret):
    return _gate(o, gate_pre, o_norm, eps, interpret), (o, gate_pre, o_norm)


def _gate_bwd(eps, interpret, res, dy):
    o, gate_pre, o_norm = res
    do, dz, dw = _call(
        functools.partial(_gate_bwd_kernel, eps=eps), "kda_norm_gate_bwd",
        "norm_gate", [o, gate_pre, dy.astype(o.dtype)],
        [_a_lane(jnp.tile(o_norm, o.shape[-1] // LANES))],
        [o.dtype, gate_pre.dtype], 1, interpret)
    return do, dz, _lane_sums(dw, o_norm, 0)


_gate.defvjp(_gate_fwd, _gate_bwd)


def _checked(what, arrays, itemsize):
    shape = arrays[0].shape
    if any(a.shape != shape for a in arrays) or not supported(
            shape, LANES, itemsize):
        raise ValueError("%s: %s is not supported" % (what, ", ".join(
            "%s %s" % (a.shape, a.dtype) for a in arrays)))


def _interpret(interpret):
    return bool(not _on_tpu() if interpret is None else interpret)


def l2_heads(x, *, scale, interpret=None):
    """Each head (a lane tile) of ``x`` [b, S, heads * 128] as ``x / |x|_2 *
    scale``: ``l2_heads_reference`` on the flat array, float32 inside,
    rounded once to ``x.dtype``; differentiable in x."""
    _checked("l2_heads", [x], x.dtype.itemsize)
    return _l2(x, float(scale), _interpret(interpret))


def log_decay(pre, dt_bias, a_log, *, interpret=None):
    """``-exp(a_log[head]) * softplus(pre + dt_bias)`` [b, S, heads * 128]
    float32, as ``kda_chunk`` reads it: pre float32, dt_bias [heads * 128],
    a_log [heads]; differentiable in all three.  The forward is XLA's (the
    epilogue of whatever makes ``pre``), the backward a kernel."""
    if pre.dtype != F32 or dt_bias.shape != pre.shape[-1:] \
            or a_log.shape[0] * LANES != pre.shape[-1]:
        raise ValueError("log_decay: pre %s %s, dt_bias %s, a_log %s" % (
            pre.shape, pre.dtype, dt_bias.shape, a_log.shape))
    _checked("log_decay", [pre], 4)
    return _decay(pre, dt_bias, a_log, _interpret(interpret))


def norm_gate(o, gate_pre, o_norm, *, eps, interpret=None):
    """Each head (a lane tile) of ``o`` [b, S, heads * 128] RMS-normed by the
    ONE scale ``o_norm`` [128], THEN gated by ``sigmoid(gate_pre)``
    (gate_pre [b, S, heads * 128] float32): ``norm_gate_reference`` on the
    flat arrays, float32 inside, rounded once to ``o.dtype``; differentiable
    in all three."""
    if o_norm.shape != (LANES,):
        raise ValueError("norm_gate: o_norm %s" % (o_norm.shape,))
    _checked("norm_gate", [o, gate_pre], o.dtype.itemsize)
    return _gate(o, gate_pre, o_norm, float(eps), _interpret(interpret))
