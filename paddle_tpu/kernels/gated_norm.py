"""The Mamba-2 mixer's gate and grouped RMS norm in ONE pass each way, as
Pallas TPU row kernels (fwd + custom-VJP bwd): ``gated_norm``.

``y`` [b, S, d] is the scan's output; ``z`` [b, S, P] holds the gate in its
LAST ``d`` lanes (P = d, or ``in_proj``'s packed output ``[xBC | z]`` read IN
PLACE: the blocks' index map visits lane blocks (P - d) / block .. and never
the rest).  The result is what these lines of ``parallel/transformer.py:
mamba2_mixer`` gave (``gated_norm_reference``, the tests' second opinion and
the fallback), in float32 and rounded ONCE:

    u   = y * silu(z)                                  silu(z) = z sigmoid(z)
    out = u * rsqrt(mean_group(u^2) + eps) * gate_norm

over each of ``groups`` runs of d / groups channels.

Why a kernel (PERF.md section 6, PR 53): the norm's reduction runs over a
RESHAPED minor dimension, which XLA fuses into neither the scan's kernel nor
``w_out``'s matmul: at the nemotron cell's [2, 8192, 4096] in 8 groups of
512 it wrote float32 [2, 8192, 4096] twice as a ``reshape`` and twice as a
``copy`` to ``[2048, 8, 8, 512]`` tiles, reduced, and broadcast the
statistics back at full width, 4.6 GB a layer's recompute + backward where
the work is 0.40 GB a forward and 0.67 GB a backward.  Here a group is a
LANE BLOCK (four lane tiles at 512): its sum of squares is a sum of tiles
and one lane reduce, no relayout.

- a grid step holds ``[rows, group]`` of y, z and out (``block_rows`` x d /
  groups) and WALKS it ``walk_rows`` rows at a time in one traced loop body,
  everything of a turn in float32;
- the backward reads y, z and ``dout``, makes u and the statistic again
  (the residuals are y, z and ``gate_norm``: nothing float32 of size [S, d]
  is kept or made), writes ``dy`` and ``dz``, and sums ``d gate_norm`` in
  float32 over the row blocks in a revisited output block (rows the grid's
  innermost, sequential axis), eight sublanes a group: no cross-sublane
  reduce in the kernel; the batch rows and the sublanes are summed outside.
  With g = dout * gate_norm, r the statistic:

      du = r * (g - u * r^2 * mean_group(g * u))
      dy = du * silu(z)        dz = du * y * silu'(z)
      d gate_norm = sum_rows(dout * u * r)

The geometry, by device trace at the cell's y [2, 8192, 4096] bf16 with z in
[2, 8192, 10240] (PERF.md section 6, PR 53; forward / backward us a call,
HBM's bytes need 492 / 819, the ``jnp`` lines took 8,205 for both): blocks
of 1,024 rows walked 16 rows a turn 1,166 / 1,244 (a turn is one chain of
loads, an ``exp``, a lane reduce, an ``rsqrt`` and stores: the next turn's
work does not overlap it); 32 rows 694 / 1,036; 64 rows 663 / 1,001; **128
rows 624 / 1,001 (shipped)**; blocks of 512 rows walked 128: 665 / 1,024, 256:
645 / 1,024; blocks of 256: 710 / 1,054; blocks of 2,048 walked 128: 605 /
998 for 24 MB of VMEM; a whole block a turn runs out of VMEM.  The backward
stands at 1.0 ms whatever the blocks: about 33 vector operations a float32
tile bind it, not HBM.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import (LANES, SUBLANES, CompilerParams as _CompilerParams,
                      on_tpu as _on_tpu, sublane_sums as _sublane_sums,
                      sublane_tile as _tile)

__all__ = ["gated_norm", "gated_norm_reference", "supported", "block_rows",
           "walk_rows", "vmem_bytes"]

MAX_GROUP_LANES = 1024      # the widest group tried through Mosaic
BLOCK_ELEMENTS = 1 << 19    # of one grid step's block of one array
WALK_ELEMENTS = 1 << 16     # of one turn of the walk (the geometry below)
ROW_BLOCKS = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
WALKS = (512, 256, 128, 64, 32, 16, 8)
F32 = jnp.float32


def block_rows(S, lanes, itemsize):
    """Rows of a grid step's block of ``lanes`` channels: the tallest of
    ROW_BLOCKS in whole sublane tiles of the element type that divides S and
    keeps the block within BLOCK_ELEMENTS; None where none."""
    return next((bs for bs in ROW_BLOCKS
                 if bs % _tile(itemsize) == 0 and S % bs == 0
                 and bs * lanes <= BLOCK_ELEMENTS), None)


def walk_rows(bs, lanes, itemsize):
    """Rows a turn of the walk inside a block takes: whole tiles of the
    element type (a turn's rows are a dynamic slice of the block), within
    WALK_ELEMENTS where a tile is."""
    tile = _tile(itemsize)
    return next((w for w in WALKS if w % tile == 0 and bs % w == 0
                 and w * lanes <= WALK_ELEMENTS), tile)


def supported(shape, groups, packed_width, itemsize):
    """Whether ``gated_norm`` takes y's ``[b, S, d]`` in ``groups`` groups
    with the gate the last d lanes of ``packed_width``: a group whole lane
    tiles (at most MAX_GROUP_LANES), the gate's first lane on a group's
    edge, S in whole sublane tiles of the element type."""
    _, S, d = shape
    if groups < 1 or d % groups:
        return False
    lanes = d // groups
    return (lanes % LANES == 0 and lanes <= MAX_GROUP_LANES
            and packed_width >= d and (packed_width - d) % lanes == 0
            and block_rows(S, lanes, itemsize) is not None)


def vmem_bytes(bs, lanes, itemsize):
    """What the backward, the larger of the two calls, asks Mosaic for: its
    pipelined blocks (y, z, dout in, dy, dz out, two copies each), the
    sums' block and the scale's, and room for what the compiler keeps of a
    turn."""
    return (10 * bs * lanes * itemsize + 4 * (SUBLANES + 1) * lanes * 4
            + (4 << 20))


def gated_norm_reference(y, z, gate_norm, groups, eps):
    """``mamba2_mixer``'s own lines: y [b, S, d], z [b, S, d] the gate,
    gate_norm [d]; the result in y's type."""
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = gated.reshape(gated.shape[:-1] + (groups, -1))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(ms + eps)).reshape(gated.shape)
    return (normed * gate_norm).astype(y.dtype)


def _gated(y, z):
    """(u, silu(z), sigmoid(z), y) of a turn's rows, float32."""
    y, z = y.astype(F32), z.astype(F32)
    sig = jax.nn.sigmoid(z)
    gate = z * sig
    return y * gate, gate, sig, y


def _statistic(u, eps):
    """``rsqrt(mean(u^2) + eps)`` of each row of a group, [rows, 1]."""
    return jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)


def _fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, eps, walk):
    """One block [rows, group] of one sequence; grid (b, row blocks,
    groups)."""
    def turn(i, carry):
        rows = pl.ds(pl.multiple_of(i * walk, walk), walk)
        u = _gated(y_ref[rows, :], z_ref[rows, :])[0]
        o_ref[rows, :] = (u * _statistic(u, eps) * w_ref[...]).astype(
            o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // walk, turn, 0)


def _bwd_kernel(y_ref, z_ref, g_ref, w_ref, dy_ref, dz_ref, dw_ref, *, eps,
                walk):
    """One block [rows, group] of one sequence; grid (b, groups, row
    blocks), the rows innermost and in order: ``dw_ref`` [8, group] sums
    over them."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)

    def turn(i, carry):
        rows = pl.ds(pl.multiple_of(i * walk, walk), walk)
        u, gate, sig, y = _gated(y_ref[rows, :], z_ref[rows, :])
        dout = g_ref[rows, :].astype(F32)
        r = _statistic(u, eps)
        g = dout * w_ref[...]
        du = r * (g - u * (r * r * jnp.mean(g * u, axis=-1, keepdims=True)))
        dy_ref[rows, :] = (du * gate).astype(dy_ref.dtype)
        # d silu(z) = sigmoid(z) (1 + z (1 - sigmoid(z)))
        #           = sigmoid(z) + silu(z) (1 - sigmoid(z))
        dz_ref[rows, :] = (du * y * (sig + gate * (1.0 - sig))).astype(
            dz_ref.dtype)
        dw_ref[...] += _sublane_sums(dout * u * r)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0] // walk, turn, 0)


def _geometry(y, z, groups):
    """(b, S, group lanes, block rows, walk rows, the gate's first lane
    block of z)."""
    b, S, d = y.shape
    lanes, itemsize = d // groups, y.dtype.itemsize
    bs = block_rows(S, lanes, itemsize)
    return (b, S, lanes, bs, walk_rows(bs, lanes, itemsize),
            (z.shape[-1] - d) // lanes)


def _fwd_call(y, z, w, groups, eps, interpret):
    b, S, lanes, bs, walk, z_at = _geometry(y, z, groups)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, walk=walk),
        grid=(b, S // bs, groups),
        in_specs=[
            pl.BlockSpec((None, bs, lanes), lambda bi, ri, gi: (bi, ri, gi)),
            pl.BlockSpec((None, bs, lanes),
                         lambda bi, ri, gi: (bi, ri, z_at + gi)),
            pl.BlockSpec((1, lanes), lambda bi, ri, gi: (0, gi))],
        out_specs=pl.BlockSpec((None, bs, lanes),
                               lambda bi, ri, gi: (bi, ri, gi)),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=vmem_bytes(bs, lanes, y.dtype.itemsize)),
        interpret=interpret, name="gated_norm_fwd",
    )(y, z, w)


def _bwd_call(y, z, dout, w, groups, eps, interpret):
    """``dy``, ``dz`` [b, S, d] and the partial sums [b, 8, d] float32 of
    ``d gate_norm``."""
    b, S, lanes, bs, walk, z_at = _geometry(y, z, groups)
    block = pl.BlockSpec((None, bs, lanes), lambda bi, gi, ri: (bi, ri, gi))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, walk=walk),
        grid=(b, groups, S // bs),
        in_specs=[
            block,
            pl.BlockSpec((None, bs, lanes),
                         lambda bi, gi, ri: (bi, ri, z_at + gi)),
            block,
            pl.BlockSpec((1, lanes), lambda bi, gi, ri: (0, gi))],
        out_specs=[block, block,
                   pl.BlockSpec((None, SUBLANES, lanes),
                                lambda bi, gi, ri: (bi, 0, gi))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((b, SUBLANES, y.shape[-1]), F32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(bs, lanes, y.dtype.itemsize)),
        interpret=interpret, name="gated_norm_bwd",
    )(y, z, dout, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_norm(y, z, gate_norm, groups, eps, interpret):
    return _fwd_call(y, z, gate_norm.astype(F32).reshape(1, -1), groups, eps,
                     interpret)


def _gated_norm_fwd(y, z, gate_norm, groups, eps, interpret):
    # what it read is the residual: nothing float32 of y's size
    return (_gated_norm(y, z, gate_norm, groups, eps, interpret),
            (y, z, gate_norm))


def _gated_norm_bwd(groups, eps, interpret, res, dout):
    y, z, gate_norm = res
    dy, dz, sums = _bwd_call(y, z, dout, gate_norm.astype(F32).reshape(1, -1),
                             groups, eps, interpret)
    # the lanes of a wider z it did not read: zeros, a pad that XLA fuses
    # into whatever reads the packed gradient (``in_proj``'s backward matmuls)
    dz = jnp.pad(dz, ((0, 0), (0, 0), (z.shape[-1] - y.shape[-1], 0)))
    return dy, dz, jnp.sum(sums, axis=(0, 1)).astype(gate_norm.dtype)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_norm(y, z, gate_norm, *, groups, eps, interpret=None):
    """``y * silu(z)`` RMS-normed over each of ``groups`` groups of channels
    and scaled by ``gate_norm`` [d]: y [b, S, d]; the gate the LAST d lanes
    of ``z`` [b, S, P] (the packed projection ``[xBC | z]`` is read in
    place).  ``supported(y.shape, groups, P, itemsize)`` must hold.  Float32
    inside, rounded once to ``y.dtype``; differentiable in all three (z's
    gradient zero in the lanes before the gate)."""
    if y.dtype != z.dtype or y.shape[:2] != z.shape[:2] or not supported(
            y.shape, groups, z.shape[-1], y.dtype.itemsize):
        raise ValueError("gated_norm: y %s %s, z %s %s in %d groups is not "
                         "supported" % (y.shape, y.dtype, z.shape, z.dtype,
                                        groups))
    if interpret is None:
        interpret = not _on_tpu()
    return _gated_norm(y, z, gate_norm, int(groups), float(eps),
                       bool(interpret))
