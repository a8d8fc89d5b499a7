"""The flash backward's ``delta = sum_d(o * do)`` a head in ONE pass, as a
Pallas TPU row kernel: ``flash_delta``.

``o`` and ``do`` [B, S, W] are the packed attention output and its cotangent,
W = heads * head_dim, as ``flash_attention_packed``'s backward holds them.
The result is the row statistic the several-block backward kernels read, in
THEIR array (``flash_attention._Geom.stat_shape`` with one (row, head-block)
pair a grid step: float32 ``[B, W / 128, S, heads a lane block]``), what
these lines of ``flash_attention._bwd`` give (``flash_delta_reference``, the
tests' reference and the fallback):

    (do.f32 * o.f32).reshape(B, S, Hb, hpb, D).sum(-1).transpose(0, 2, 1, 3)

Why a kernel (PERF.md section 6, PR 55): XLA writes the float32 product at
full width (as a second output of ``wo``'s dX matmul), relayouts it, reduces
it over the reshaped minor dimension and relayouts the result: four
instructions over 0.9 GB a layer at Trinity's [1, 6144, 48 x 128], 1.3 GB at
Mistral-Small-4's, in front of every several-block backward.  Here a block
of rows of ``o`` and ``do`` comes in as it lies, each lane block (one head of
128, or ``128 / head_dim`` whole heads) is multiplied in float32 and
lane-reduced (masked to a head's lanes where a block holds several), and the
[rows, heads a block] column goes straight into the statistic's block: no
float32 ``[tokens, W]`` array reaches HBM.

Grid (row blocks, batch); the lane blocks in a ``fori_loop`` that traces
ONE.  interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU
tests run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import (LANES, CompilerParams as _CompilerParams,
                      on_tpu as _on_tpu, sublane_tile as _tile)

__all__ = ["flash_delta", "flash_delta_reference", "supported", "block_rows",
           "vmem_bytes"]

ROW_BLOCKS = (512, 256, 128, 64, 32, 16, 8)
# what a grid step's pipelined blocks may take of VMEM: o and do, and the
# statistic's block, whose [rows, heads a block] columns pad to 128 lanes
# (512 bytes a number, as they lie in HBM); two copies each
BLOCK_VMEM = 24 * 2 ** 20
F32 = jnp.float32


def _block_bytes(bs, W, itemsize):
    return 2 * (2 * bs * W * itemsize + W // LANES * bs * LANES * 4)


def block_rows(S, W, itemsize):
    """Rows of a grid step's block of ``[B, S, W]``, from the shapes alone:
    the tallest of ROW_BLOCKS in whole tiles of the element type (8 rows of
    32 bits, 16 of 16) that divides S and keeps the step's blocks within
    BLOCK_VMEM; None where there is none."""
    return next((bs for bs in ROW_BLOCKS
                 if bs % _tile(itemsize) == 0 and S % bs == 0
                 and _block_bytes(bs, W, itemsize) <= BLOCK_VMEM), None)


def vmem_bytes(bs, W, itemsize):
    """What a call asks Mosaic for: the pipelined blocks, a lane block's
    float32 temporaries (both operands widened, the product, a masked copy a
    head), and room."""
    return _block_bytes(bs, W, itemsize) + 8 * bs * LANES * 4 + 2 * 2 ** 20


def supported(shape, head_dim, itemsize):
    """Whether ``flash_delta`` takes ``o`` and ``do`` of this shape: W whole
    lane blocks of whole heads (``head_dim`` 128 or a divisor of it, the
    widths the packed flash kernels take a lane block at a time), S in whole
    sublane tiles, and a block of rows within BLOCK_VMEM."""
    _, S, W = shape
    return (W % LANES == 0 and 0 < head_dim <= LANES
            and LANES % head_dim == 0
            and block_rows(S, W, itemsize) is not None)


def flash_delta_reference(o, do, head_dim):
    """``flash_attention._bwd``'s own lines: float32 [B, Hb, S, hpb]."""
    B, S, W = o.shape
    hpb = max(1, LANES // head_dim)
    return jnp.sum(
        (do.astype(F32) * o.astype(F32))
        .reshape(B, S, W // (hpb * head_dim), hpb, head_dim), axis=-1
    ).transpose(0, 2, 1, 3)


def _kernel(o_ref, do_ref, delta_ref, *, dh):
    """One block [rows, W] of one batch row; ``delta_ref`` [W / 128, rows,
    heads a lane block]."""
    hpb = LANES // dh
    rows = o_ref.shape[0]

    def block(h, carry):
        sl = pl.ds(pl.multiple_of(h * LANES, LANES), LANES)
        prod = o_ref[:, sl].astype(F32) * do_ref[:, sl].astype(F32)
        if hpb == 1:
            delta_ref[h] = jnp.sum(prod, axis=1, keepdims=True)
            return carry
        head = jax.lax.broadcasted_iota(jnp.int32, prod.shape, 1) // dh
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, hpb), 1)
        sums = [jnp.sum(jnp.where(head == i, prod, 0.0), axis=1,
                        keepdims=True) for i in range(hpb)]
        out = jnp.broadcast_to(sums[0], (rows, hpb))
        for i in range(1, hpb):
            out = jnp.where(col == i, sums[i], out)
        delta_ref[h] = out
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // LANES, block, 0)


def flash_delta(o, do, *, head_dim, interpret=None):
    """``sum_d(o * do)`` of every head of the packed ``o`` and ``do`` [B, S,
    W], float32 inside, as float32 ``[B, W / 128, S, 128 / head_dim]`` (a
    head of 128: ``[B, heads, S, 1]``).  ``o`` and ``do`` of one shape and
    type, and ``supported(o.shape, head_dim, itemsize)`` must hold."""
    itemsize = o.dtype.itemsize
    if (o.shape, o.dtype) != (do.shape, do.dtype) or not supported(
            o.shape, head_dim, itemsize):
        raise ValueError("flash_delta: o %s %s, do %s %s at head_dim %d is "
                         "not supported" % (o.shape, o.dtype, do.shape,
                                            do.dtype, head_dim))
    if interpret is None:
        interpret = not _on_tpu()
    B, S, W = o.shape
    bs = block_rows(S, W, itemsize)
    rows = pl.BlockSpec((None, bs, W), lambda si, bi: (bi, si, 0))
    hpb = LANES // head_dim
    return pl.pallas_call(
        functools.partial(_kernel, dh=head_dim),
        grid=(S // bs, B), in_specs=[rows, rows],
        out_specs=pl.BlockSpec((None, W // LANES, bs, hpb),
                               lambda si, bi: (bi, 0, si, 0)),
        out_shape=jax.ShapeDtypeStruct((B, W // LANES, S, hpb), F32),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_bytes(bs, W, itemsize)),
        interpret=bool(interpret), name="flash_delta",
    )(o, do)
