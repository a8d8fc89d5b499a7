"""The Mamba mixer's causal depthwise filter, its bias and ``silu`` in ONE
pass each way, as Pallas TPU row kernels (fwd + custom-VJP bwd):
``mamba_filter``.

``x`` [b, S, W] holds the filter's input in its first ``d`` lanes (W = d, or
``in_proj``'s packed output ``[x | z]``, W = 2 d, read IN PLACE: the blocks'
index map visits lane blocks 0 .. d / block - 1 and never the rest).  The
result is what these lines of ``parallel/transformer.py: mamba_operands``
give (the tests' reference), in float32 and rounded ONCE:

    pre_t = conv_b + sum_j conv_w[j] * x[t - (taps - 1) + j]     x[< 0] = the
    out_t = silu(pre_t)                                          rows ``before``

Why a kernel (PERF.md section 6, PR 51): XLA splits the packed projection
into z and a FLOAT32 copy of x ``[S, d]`` in HBM for the shifts, the filter
writes x in bf16 AND the float32 pre-activation for its backward, and the
backward walks all of them again: 2.3 GB a layer and step at the jamba
cell's shape where the work is 84 MB in and 84 MB out a pass.  Here a block
of rows comes in and the same block goes out:

- a grid step holds ``[rows, lanes]`` of x (``block_rows`` x
  ``block_lanes``) and WALKS it ``walk_rows`` rows at a time in ONE traced
  loop body: the rows widened to float32 in registers, the walk's last 8
  rows carried to the next turn as its halo, a tap ``back`` rows behind one
  sublane rotation of the window ``[8 + walk, lanes]``;
- the halo of a block's first rows is the sublane tile BEFORE the block (a
  second ``BlockSpec`` on the same array); at the sequence's first block it
  is ``before`` (zeros, or the rows ``mamba_operands`` projects again for a
  block of positions past the first);
- the backward reads x again (the residual: nothing float32 of size ``[S,
  d]`` is kept), makes the pre-activation again in registers, and
  ``g = dout * silu'(pre)`` for its rows AND the 8 after them into VMEM
  scratch, since the transposed filter reaches FORWARD: ``dx_t = sum_j
  conv_w[j] * g[t + (taps - 1) - j]``, g zero past the sequence's end.
  ``d conv_w`` and ``d conv_b`` are summed in float32 over the row blocks in
  a revisited output block (rows the grid's innermost, sequential axis),
  eight sublanes a tap: no cross-sublane reduce in the kernel; the batch
  rows and the sublanes are summed outside.

The geometry, by device trace at the cell's [1, 8192, 5120 of 10240] bf16
(PERF.md section 6, PR 51; forward / backward us a call, HBM's bytes need
205 / 307): blocks of 2,048 x 512 walked 32 rows a turn 281 / 542; 512 x 512
334 / 562; 512 x 128 568 / 840; turns of 16 or 64 rows are slower.  ``silu``
by ``0.5 tanh(p / 2) + 0.5`` reads 30 / 80 us less and is NOT taken: the
chip's ``tanh`` is 7e-6 from the float32 lines where ``exp`` and a divide
are 8e-8.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (SUBLANES, CompilerParams as _CompilerParams,
                      on_tpu as _on_tpu, sublane_sums as _sublane_sums,
                      sublane_tile as _tile)

__all__ = ["mamba_filter", "mamba_filter_reference", "supported",
           "block_rows", "block_lanes", "walk_rows", "vmem_bytes"]

MAX_TAPS = SUBLANES     # the halo a walk carries: taps reach at most 7 back
ROW_BLOCKS = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
LANE_BLOCKS = (512, 256, 128)
WALKS = (32, 16, 8)
F32 = jnp.float32


def block_rows(S, itemsize):
    """Rows of a grid step's block: the tallest of ROW_BLOCKS in whole
    sublane tiles of the element type that divides S; None where none."""
    return next((bs for bs in ROW_BLOCKS
                 if bs % _tile(itemsize) == 0 and S % bs == 0), None)


def block_lanes(d):
    """Lanes of a grid step's block: the widest of LANE_BLOCKS that divides
    the channels; None where they are no whole lane blocks."""
    return next((lb for lb in LANE_BLOCKS if d % lb == 0), None)


def walk_rows(bs, itemsize):
    """Rows a turn of the walk inside a block takes: whole tiles of the
    element type (a turn's rows are a dynamic slice of the block)."""
    return next(w for w in WALKS if w % _tile(itemsize) == 0 and bs % w == 0)


def supported(shape, taps, itemsize):
    """Whether ``mamba_filter`` takes x's ``[b, S, d]`` channels at ``taps``
    taps: d in whole lane blocks, S in whole sublane tiles of the element
    type, at most MAX_TAPS taps (the halo is one float32 tile)."""
    _, S, d = shape
    return (1 <= taps <= MAX_TAPS and block_lanes(d) is not None
            and block_rows(S, itemsize) is not None)


def vmem_bytes(bs, lb, itemsize):
    """What the backward, the larger of the two calls, asks Mosaic for: its
    pipelined blocks (x, dout in, dx out, two copies each), the float32
    scratch of g, the tiles before and after, the small operands, and room
    for what the compiler keeps of a turn's windows."""
    return (6 * bs * lb * itemsize + (bs + SUBLANES) * lb * 4
            + 8 * 2 * SUBLANES * lb * 4 + (4 << 20))


def mamba_filter_reference(x, conv_w, conv_b, before=None):
    """``mamba_operands``' own lines: x [b, S, d], conv_w [taps, d], conv_b
    [d], ``before`` [b, taps - 1, d] the rows ahead of x (None: zeros)."""
    taps = conv_w.astype(F32)
    halo, n = taps.shape[0] - 1, x.shape[1]
    if before is None:
        before = jnp.zeros(x.shape[:1] + (halo, x.shape[2]), F32)
    padded = jnp.concatenate([before.astype(F32), x.astype(F32)], axis=1)
    conv = conv_b.astype(F32) + sum(
        taps[j] * padded[:, j:j + n] for j in range(halo + 1))
    return jax.nn.silu(conv).astype(x.dtype)


def _behind(win, back):
    """``win`` [8 + rows, lanes], a walk's window behind its 8 halo rows:
    the rows ``back`` behind each of its own, ``[rows, lanes]``."""
    if back == 0:
        return win[SUBLANES:]
    return pltpu.roll(win, back, 0)[SUBLANES:]


def _pre(shifted, w_ref, b_ref, taps):
    """The pre-activation of a turn's rows: the bias and the taps on the
    windows ``shifted[back]``, float32."""
    pre = b_ref[...] + w_ref[taps - 1:taps, :] * shifted[0]
    for back in range(1, taps):
        pre = pre + w_ref[taps - 1 - back:taps - back, :] * shifted[back]
    return pre


def _first_halo(before_ref, halo_ref, first):
    """The 8 float32 rows ahead of a block: ``before`` at the sequence's
    first block, else the tail of the tile before it."""
    tile = halo_ref.shape[0]
    return jnp.where(first, before_ref[...],
                     halo_ref[...].astype(F32)[tile - SUBLANES:])


def _fwd_kernel(x_ref, halo_ref, before_ref, w_ref, b_ref, o_ref, *, taps,
                walk):
    """One block [rows, lanes] of one sequence; grid (b, row blocks, lane
    blocks)."""
    def turn(i, prev):
        rows = pl.ds(pl.multiple_of(i * walk, walk), walk)
        cur = x_ref[rows, :].astype(F32)
        win = jnp.concatenate([prev, cur], axis=0)
        pre = _pre([_behind(win, back) for back in range(taps)],
                   w_ref, b_ref, taps)
        o_ref[rows, :] = (pre * jax.nn.sigmoid(pre)).astype(o_ref.dtype)
        return cur[walk - SUBLANES:]

    jax.lax.fori_loop(0, x_ref.shape[0] // walk, turn, _first_halo(
        before_ref, halo_ref, pl.program_id(1) == 0))


def _bwd_kernel(x_ref, halo_ref, after_ref, before_ref, g_ref, g_after_ref,
                w_ref, b_ref, dx_ref, dw_ref, gs_ref, *, taps, walk):
    """One block [rows, lanes] of one sequence; grid (b, lane blocks, row
    blocks), the rows innermost and in order: ``dw_ref`` [(taps + 1) * 8,
    lanes] sums over them.  ``gs_ref`` [rows + 8, lanes] float32."""
    at, blocks = pl.program_id(2), pl.num_programs(2)
    bs = x_ref.shape[0]

    @pl.when(at == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    def grad_pre(prev, cur, dout):
        """``dout * silu'(pre)`` of the rows ``cur`` behind the halo
        ``prev``, and the windows the taps read."""
        win = jnp.concatenate([prev, cur], axis=0)
        shifted = [_behind(win, back) for back in range(taps)]
        pre = _pre(shifted, w_ref, b_ref, taps)
        sig = jax.nn.sigmoid(pre)
        # d silu(p) = sigmoid(p) (1 + p (1 - sigmoid(p)))
        return dout.astype(F32) * sig * (1.0 + pre * (1.0 - sig)), shifted

    def turn(i, prev):
        rows = pl.ds(pl.multiple_of(i * walk, walk), walk)
        cur = x_ref[rows, :].astype(F32)
        g, shifted = grad_pre(prev, cur, g_ref[rows, :])
        gs_ref[rows, :] = g
        for back in range(taps):
            j = taps - 1 - back
            dw_ref[j * SUBLANES:(j + 1) * SUBLANES, :] += _sublane_sums(
                g * shifted[back])
        dw_ref[taps * SUBLANES:, :] += _sublane_sums(g)
        return cur[walk - SUBLANES:]

    last = jax.lax.fori_loop(0, bs // walk, turn, _first_halo(
        before_ref, halo_ref, at == 0))
    # the 8 rows after the block: the transposed taps reach into them; past
    # the sequence's end there is nothing
    g_after, _ = grad_pre(last, after_ref[...].astype(F32)[:SUBLANES],
                          g_after_ref[...][:SUBLANES])
    gs_ref[bs:, :] = jnp.where(at == blocks - 1, 0.0, g_after)

    def turn_back(i, carry):
        start = pl.multiple_of(i * walk, walk)
        win = gs_ref[pl.ds(start, walk + SUBLANES), :]
        dx = w_ref[taps - 1:taps, :] * win[:walk]
        for back in range(1, taps):
            ahead = pltpu.roll(win, walk + SUBLANES - back, 0)[:walk]
            dx = dx + w_ref[taps - 1 - back:taps - back, :] * ahead
        dx_ref[pl.ds(start, walk), :] = dx.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bs // walk, turn_back, 0)


def _geometry(x, d):
    b, S, _ = x.shape
    itemsize = x.dtype.itemsize
    bs, lb = block_rows(S, itemsize), block_lanes(d)
    return b, S, bs, lb, _tile(itemsize), walk_rows(bs, itemsize)


def _small(conv_w, conv_b, before, b, d):
    """The small operands as the kernels read them: the taps in 8 sublanes,
    the bias a row, the rows before position 0 as the LAST of 8."""
    taps = conv_w.shape[0]
    w8 = jnp.zeros((MAX_TAPS, d), F32).at[:taps].set(conv_w.astype(F32))
    before8 = jnp.zeros((b, SUBLANES, d), F32)
    if before is not None:
        before8 = before8.at[:, SUBLANES - before.shape[1]:].set(
            before.astype(F32))
    return w8, conv_b.astype(F32).reshape(1, d), before8


def _fwd_call(x, w8, bias, before8, d, taps, interpret):
    b, S, bs, lb, tile, walk = _geometry(x, d)
    per = bs // tile
    block = pl.BlockSpec((None, bs, lb), lambda bi, ri, li: (bi, ri, li))
    small = lambda rows: pl.BlockSpec((rows, lb), lambda bi, ri, li: (0, li))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, walk=walk),
        grid=(b, S // bs, d // lb),
        in_specs=[block,
                  pl.BlockSpec((None, tile, lb), lambda bi, ri, li: (
                      bi, jnp.maximum(ri * per - 1, 0), li)),
                  pl.BlockSpec((None, SUBLANES, lb),
                               lambda bi, ri, li: (bi, 0, li)),
                  small(MAX_TAPS), small(1)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, S, d), x.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=vmem_bytes(bs, lb, x.dtype.itemsize)),
        interpret=interpret, name="mamba_filter_fwd",
    )(x, x, before8, w8, bias)


def _bwd_call(x, dout, w8, bias, before8, d, taps, interpret):
    """``dx`` [b, S, d] and the partial sums [b, (taps + 1) * 8, d] float32
    of ``d conv_w`` (a tap's 8 sublanes) and ``d conv_b`` (the last 8)."""
    b, S, bs, lb, tile, walk = _geometry(x, d)
    per, tiles = bs // tile, S // tile
    block = pl.BlockSpec((None, bs, lb), lambda bi, li, ri: (bi, ri, li))
    before_tile = pl.BlockSpec((None, tile, lb), lambda bi, li, ri: (
        bi, jnp.maximum(ri * per - 1, 0), li))
    after_tile = pl.BlockSpec((None, tile, lb), lambda bi, li, ri: (
        bi, jnp.minimum((ri + 1) * per, tiles - 1), li))
    small = lambda rows: pl.BlockSpec((rows, lb), lambda bi, li, ri: (0, li))
    sums = (taps + 1) * SUBLANES
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, walk=walk),
        grid=(b, d // lb, S // bs),
        in_specs=[block, before_tile, after_tile,
                  pl.BlockSpec((None, SUBLANES, lb),
                               lambda bi, li, ri: (bi, 0, li)),
                  block, after_tile, small(MAX_TAPS), small(1)],
        out_specs=[block, pl.BlockSpec((None, sums, lb),
                                       lambda bi, li, ri: (bi, 0, li))],
        out_shape=[jax.ShapeDtypeStruct((b, S, d), x.dtype),
                   jax.ShapeDtypeStruct((b, sums, d), F32)],
        scratch_shapes=[pltpu.VMEM((bs + SUBLANES, lb), F32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(bs, lb, x.dtype.itemsize)),
        interpret=interpret, name="mamba_filter_bwd",
    )(x, x, x, before8, dout, dout, w8, bias)


def _head_grad(x, dout, conv_w, conv_b, before):
    """The gradient of the rows ``before`` position 0: they reach the first
    ``taps - 1`` rows alone, so it is the reference's own, on those rows."""
    halo = before.shape[1]
    _, vjp = jax.vjp(
        lambda rows: mamba_filter_reference(x[:, :halo], conv_w, conv_b,
                                            rows), before)
    return vjp(dout[:, :halo])[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _filter(x, conv_w, conv_b, before, d, interpret):
    w8, bias, before8 = _small(conv_w, conv_b, before, x.shape[0], d)
    return _fwd_call(x, w8, bias, before8, d, conv_w.shape[0], interpret)


def _filter_fwd(x, conv_w, conv_b, before, d, interpret):
    # the projection it read is the residual: nothing float32 of its size
    return (_filter(x, conv_w, conv_b, before, d, interpret),
            (x, conv_w, conv_b, before))


def _filter_bwd(d, interpret, res, dout):
    x, conv_w, conv_b, before = res
    taps = conv_w.shape[0]
    w8, bias, before8 = _small(conv_w, conv_b, before, x.shape[0], d)
    dx, sums = _bwd_call(x, dout, w8, bias, before8, d, taps, interpret)
    sums = jnp.sum(sums.reshape(x.shape[0], taps + 1, SUBLANES, d),
                   axis=(0, 2))
    # the lanes it did not read: zeros, a pad that XLA fuses into whatever
    # reads the packed gradient (``in_proj``'s backward matmuls)
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[-1] - d)))
    return (dx, sums[:taps].astype(conv_w.dtype),
            sums[taps].astype(conv_b.dtype),
            None if before is None else _head_grad(
                x[..., :d], dout, conv_w, conv_b, before))


_filter.defvjp(_filter_fwd, _filter_bwd)


def mamba_filter(x, conv_w, conv_b, before=None, *, width=None,
                 interpret=None):
    """``silu(conv(x) + conv_b)`` [b, S, d] of the first ``width`` = d lanes
    of ``x`` [b, S, W] (default: all; the packed projection ``[x | z]`` is
    read in place): conv_w [taps, d], conv_b [d], ``before`` [b, taps - 1,
    d] float32 the filter's input ahead of x's first row (None: zeros).
    ``supported((b, S, d), taps, itemsize)`` must hold.  Float32 inside,
    rounded once to ``x.dtype``; differentiable in all four (x's gradient
    zero in the lanes past d)."""
    d = x.shape[-1] if width is None else int(width)
    if not supported(x.shape[:2] + (d,), conv_w.shape[0], x.dtype.itemsize) \
            or x.shape[-1] % block_lanes(d):
        raise ValueError("mamba_filter: %s at width %d and %d taps is not "
                         "supported" % (x.shape, d, conv_w.shape[0]))
    if interpret is None:
        interpret = not _on_tpu()
    return _filter(x, conv_w, conv_b, before, d, bool(interpret))
