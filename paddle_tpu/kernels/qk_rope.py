"""The q/k norm and the rotary positions of a packed projection in ONE pass,
as a Pallas TPU row kernel (fwd + custom-VJP bwd): ``qk_rope``.

``x`` [b, S, W] is a packed projection, W = heads * head_dim.  The result is
what ``rope(rms_norm(x))`` of ``parallel/transformer.py`` gives (the tests'
reference), computed in float32 and rounded ONCE:

    n = x * rsqrt(mean(x^2) + eps) * weight     per head with one [dh] weight
                                                ("head"), over the whole
                                                projection with a [W] weight
                                                ("whole"), or n = x (None)
    y = n * cos + rotate_half(n) * sin          per head, at the angles
                                                ``pos * theta^(-2i / dh)``
                                                (or y = n: no positions)

With ``pairs`` (static) the rotation is the ADJACENT-pair convention's
(``transformer.rope_pairs``, the latent form's): the partner of lane 2j is
lane 2j + 1 and back, and everything else a head does to its lanes is in the
tables (``pair_tables``): the sign by lane, cosine 1 and sine 0 in a head's
lanes that carry no position, and any scale by position multiplied in.  With
``shared`` [b, S, 128], ONE lane block a row that every head takes, the
rotated ``n + shared``: a key ``[k_nope_i | 0] + [0 | kr]`` is assembled and
rotated in the pass that reads it.

A head may also be n WHOLE lane blocks (``head_dim`` 256: the latent form
whose value is narrower than its head, ``transformer._latent_qkv_lanes``),
with ``pairs`` or with no tables, and no norm: the tables are then ``[S, n *
128]`` and ``shared`` ``[b, S, n * 128]``, a lane block of the HEAD each, and
lane block i of a row takes block ``i % n`` of them (a pair's partner never
leaves its lane block, so a block's body is the one-block head's).  Such a
call is ``_call_touched``: x is ALIASED to the result and a grid step is ONE
lane block, so the first ``plain_blocks`` lane blocks of every head, which
carry no position and none of ``shared`` (``[q_nope 128 | ...]``), are not
visited and never leave HBM (a full dots3 layer's q pass 270 µs so where the
whole rows copied took 412: PERF.md section 6, PR 66).

Why a kernel (PERF.md section 6, PR 47): the compiled text of one rotary
layer at Trinity's shape moves 9.0 GB outside its matmuls and flash kernels
where this work needs 0.45: XLA broadcasts ``cos`` and ``sin`` to float32
``[S, H, dh]`` in HBM, makes ``rotate_half``'s halves in a transposed layout
and copies them back, and a reduction over a reshaped minor dimension fuses
into no matmul.  Here a block of rows comes in, the same block goes out, and
nothing float32 reaches HBM but the angles' tables:

- a lane block (128 lanes) holds one head of 128 or ``128 / dh`` whole heads;
  a head's sum of squares is a lane reduce (masked to the head's lanes where
  a block holds several), ``rotate_half`` one lane rotation by ``dh / 2``
  (a select between two where a block holds several heads) with the sign in
  the sine's table; a pair's partner two lane rotations by one and a select
  by lane parity;
- the angles are two float32 tables ``[S, 128]`` (``angle_tables``: cosine,
  and sine with ``rotate_half``'s sign), made by XLA from ``first``, which
  may be traced, by ``rope``'s own formula: 3 to 8 MB a layer, read once a
  block of rows whatever the batch (the batch is the grid's inner axis);
- the backward reads ``dy`` and the saved RAW projection (saved only where
  a norm reads it: the rotation alone is linear, and ``shared``'s gradient
  is the lane blocks' sum of dx), recomputes the statistics
  (no lane-narrow block of them is saved), and writes ``dx`` over ``dy``;
  the weight's gradient leaves as per-block partial sums ``[blocks * 8,
  lanes]`` float32 (eight sublanes a block: no cross-sublane reduce in the
  kernel), summed outside.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (LANES, SUBLANES, CompilerParams as _CompilerParams,
                      on_tpu as _on_tpu, sublane_sums as _sublane_sums,
                      sublane_tile as _tile)

__all__ = ["qk_rope", "angle_tables", "pair_tables", "pair_streams",
           "stream_angles", "supported", "head_blocks",
           "block_rows", "vmem_bytes"]

ROW_BLOCKS = (256, 128, 64, 32, 16, 8)
# what the backward's six pipelined blocks (x, dy, dx; two copies each) may
# take of VMEM; the forward's four are under it
BLOCK_VMEM = 12 * 2 ** 20


def block_rows(S, W, itemsize):
    """Rows of a grid step's block of a ``[b, S, W]`` projection, from the
    shapes alone: the tallest of ROW_BLOCKS in whole tiles of the element
    type (8 rows of 32 bits, 16 of 16) that divides S and keeps the
    backward's six blocks within BLOCK_VMEM; None where there is none."""
    for bs in ROW_BLOCKS:
        if (bs % _tile(itemsize) == 0 and S % bs == 0
                and 6 * bs * W * itemsize <= BLOCK_VMEM):
            return bs
    return None


def vmem_bytes(bs, W, itemsize, shared=False):
    """What a call asks Mosaic for: the backward's six pipelined blocks of
    the projection, the angles', the weight's and the partial sums' blocks
    twice each, thirty-two float32 temporaries of a lane block's rows
    (Mosaic's stack does not reuse every one; the compiled kernels take 1 to
    7 MiB of the 8 to 17 asked, ``tests/test_chip_compile_rows.py``), and
    room; with ``shared`` its lane block and its gradient's, twice each."""
    return (6 * bs * W * itemsize + (4 + 32) * bs * LANES * 4
            + 2 * (SUBLANES + 1) * W * 4 + 2 * 2 ** 20
            + (4 * bs * LANES * itemsize if shared else 0))


def head_blocks(head_dim):
    """The lane blocks a head stands in: 1 for a head that divides one."""
    return max(1, head_dim // LANES)


def supported(shape, head_dim, itemsize):
    """Whether ``qk_rope`` takes a packed projection of this shape: W whole
    lane blocks of whole heads (``head_dim`` a divisor of 128, or whole lane
    blocks), S in whole sublane tiles, and a block of rows within
    BLOCK_VMEM."""
    _, S, W = shape
    if head_dim > LANES:
        return (head_dim % LANES == 0 and W % head_dim == 0
                and touched_rows(S, itemsize) is not None)
    return (W % LANES == 0 and LANES % head_dim == 0 and head_dim % 2 == 0
            and block_rows(S, W, itemsize) is not None)


def pair_streams(sections, half):
    """Which position stream each of a head's ``half`` frequency pairs takes
    its angle from, int [half]: ``sections`` pairs from each stream in turn;
    none: the first stream for every pair."""
    if not sections:
        return _np.zeros((half,), _np.int32)
    assert sum(sections) == half, (sections, half)
    return _np.repeat(_np.arange(len(sections), dtype=_np.int32), sections)


def stream_angles(positions, half, theta, sections=()):
    """[b, S, half] float32: pair i's angle ``positions[stream of i] *
    theta^(-i / half)`` of position streams ``positions`` [streams, b, S]."""
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)[pair_streams(sections, half)]
    return jnp.moveaxis(pos, 0, -1) * inv_freq


def angle_tables(S, head_dim, theta, first=0, positions=None, sections=(),
                 period=None):
    """(cos, signed sin) [S, 128] float32 of positions ``first``..``first``
    + S - 1 (``first`` may be traced), each head's ``head_dim`` lanes
    ``rope``'s own ``tile(cos(pos * theta^(-i / half)), 2)``, the sine with
    ``rotate_half``'s sign (minus on a head's first half).  ``positions``
    [streams, b, S]: the positions as DATA, pair i from the stream
    ``sections`` gives it (``stream_angles``); the tables are then [b * S,
    128], a row a (batch row, position).  ``period``: row r's position is
    ``r mod period`` (``transformer.rope``)."""
    half = head_dim // 2
    if positions is not None:
        ang = stream_angles(positions, half, theta, sections).reshape(
            -1, half)
    else:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        pos = jnp.arange(S, dtype=jnp.float32)
        if not (isinstance(first, int) and first == 0):
            pos = pos + first
        if period:
            pos = pos % period
        ang = pos[:, None] * inv_freq[None]
    heads = LANES // head_dim
    return (jnp.tile(jnp.cos(ang), (1, 2 * heads)),
            jnp.tile(jnp.concatenate([-jnp.sin(ang), jnp.sin(ang)], axis=1),
                     (1, heads)))


def pair_tables(S, freqs, head_dim, first=0, factor=1.0, scale=None, tail=0):
    """(cos, signed sin) float32 of the adjacent-pair convention at positions
    ``first``.. (``first`` may be traced), [S, 128] or, for a head of whole
    lane blocks, [S, head_dim]: ``2 * len(freqs)`` lanes of a head, its last
    but for ``tail`` lanes behind them, are the pairs (2j, 2j + 1), turned
    by ``pos * freqs[j]``, cosine and sine times ``factor``, the sine minus
    on a pair's first lane (``transformer.rope_pairs``' sign); its lanes
    before and behind them carry no position, cosine 1 and sine 0.
    ``scale`` [S] float32 multiplies a position's row of both tables."""
    pos = jnp.arange(S, dtype=jnp.float32) + first
    ang = jnp.repeat(pos[:, None] * jnp.asarray(freqs, jnp.float32)[None], 2,
                     axis=1)
    plain = head_dim - ang.shape[1] - tail
    sign = jnp.where(jnp.arange(ang.shape[1]) % 2 == 0, -1.0, 1.0)
    behind = lambda fill: [jnp.full((S, tail), fill, jnp.float32)] \
        if tail else []
    cos = jnp.concatenate(
        [jnp.ones((S, plain), jnp.float32), factor * jnp.cos(ang)]
        + behind(1.0), axis=1)
    sin = jnp.concatenate(
        [jnp.zeros((S, plain), jnp.float32), factor * sign * jnp.sin(ang)]
        + behind(0.0), axis=1)
    if scale is not None:
        cos, sin = cos * scale[:, None], sin * scale[:, None]
    heads = LANES // head_dim or 1
    return jnp.tile(cos, (1, heads)), jnp.tile(sin, (1, heads))


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _partner(y, dh, pairs=False):
    """``y[..., j ^ (dh / 2)]`` along the 128 lanes: the other half of lane
    j's head, ``rotate_half`` without its sign (its own transpose); with
    ``pairs`` ``y[..., j ^ 1]``, the other lane of lane j's pair."""
    if pairs:
        return jnp.where((_lane(y.shape) & 1) != 0, pltpu.roll(y, 1, 1),
                         pltpu.roll(y, LANES - 1, 1))
    half = dh // 2
    if dh == LANES:
        return pltpu.roll(y, half, 1)
    return jnp.where((_lane(y.shape) & half) != 0, pltpu.roll(y, half, 1),
                     pltpu.roll(y, LANES - half, 1))


def _head_sum(v, dh):
    """The sum of ``v`` [rows, 128] over each head's lanes, at every lane of
    that head (``[rows, 1]`` where the block is one head)."""
    if dh == LANES:
        return jnp.sum(v, axis=1, keepdims=True)
    head = _lane(v.shape) // dh
    out = jnp.zeros_like(v)
    for i in range(LANES // dh):
        mine = head == i
        out = jnp.where(mine, jnp.sum(jnp.where(mine, v, 0.0), axis=1,
                                      keepdims=True), out)
    return out


def _rotate(y, cos, sin, dh, pairs):
    return y * cos + _partner(y, dh, pairs) * sin


def _over_blocks(ref, body, carry=None):
    """``carry = body(sl, carry)`` for every lane block ``sl`` of a ``[rows,
    W]`` ref, in a ``fori_loop``: ONE block's operations are traced and
    lowered whatever the heads (48 unrolled blocks a kernel cost Trinity's
    set-up 8 s of tracing and lowering)."""
    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * LANES, LANES), LANES), carry)
    return jax.lax.fori_loop(0, ref.shape[-1] // LANES, step, carry)


def _row_rstd(x_ref, eps):
    """``rsqrt(mean(x^2) + eps)`` [rows, 1] over the whole projection: the
    lane blocks' squares summed elementwise, ONE lane reduce."""
    def squares(sl, acc):
        xf = x_ref[:, sl].astype(jnp.float32)
        return acc + xf * xf
    acc = _over_blocks(x_ref, squares,
                       jnp.zeros((x_ref.shape[0], LANES), jnp.float32))
    return jax.lax.rsqrt(
        jnp.sum(acc, axis=1, keepdims=True) / x_ref.shape[-1] + eps)


def _fwd_kernel(*refs, dh, norm, rotary, eps, pairs, shared):
    x_ref, refs = refs[0], refs[1:]
    if shared:
        s_ref, refs = refs[0], refs[1:]
    if norm:
        w_ref, refs = refs[0], refs[1:]
    if rotary:
        cos_ref, sin_ref = refs[0], refs[1]
    o_ref = refs[-1]
    rstd = _row_rstd(x_ref, eps) if norm == "whole" else None

    def block(sl, _):
        y = x_ref[:, sl].astype(jnp.float32)
        if norm == "head":
            y = y * jax.lax.rsqrt(_head_sum(y * y, dh) / dh + eps) \
                * w_ref[...]
        elif norm:
            y = y * rstd * w_ref[:, sl]
        if shared:
            y = y + s_ref[...].astype(jnp.float32)
        if rotary:
            y = _rotate(y, cos_ref[...], sin_ref[...], dh, pairs)
        o_ref[:, sl] = y.astype(o_ref.dtype)

    _over_blocks(x_ref, block)


def _bwd_kernel(*refs, dh, norm, rotary, eps, pairs, shared):
    if norm:
        x_ref, refs = refs[0], refs[1:]
    g_ref, refs = refs[0], refs[1:]
    if norm:
        w_ref, refs = refs[0], refs[1:]
    if rotary:
        cos_ref, sin_ref = refs[0], refs[1]
    # the results: dx, then the weight's gradient or ``shared``'s (a norm
    # and ``shared`` never meet)
    dx_ref = refs[-2] if norm or shared else refs[-1]
    dw_ref = refs[-1] if norm else None
    ds_ref = refs[-1] if shared else None

    def d_normed(sl):
        g = g_ref[:, sl].astype(jnp.float32)
        # the rotation's transpose: its partner map is its own inverse
        return g * cos_ref[...] + _partner(g * sin_ref[...], dh, pairs) \
            if rotary else g

    if not norm:        # the rotation alone is linear: no x
        def block(sl, ds):
            d = d_normed(sl)
            dx_ref[:, sl] = d.astype(dx_ref.dtype)
            return ds + d if shared else ds
        ds = _over_blocks(g_ref, block, jnp.zeros(
            (g_ref.shape[0], LANES), jnp.float32) if shared else None)
        if shared:      # every lane block took it: the blocks' sum
            ds_ref[...] = ds.astype(ds_ref.dtype)
        return
    rows, W = x_ref.shape
    rstd = proj = None
    if norm == "whole":
        # two trips over the block: the row's sum of dn * w * x, then dx
        rstd = _row_rstd(x_ref, eps)
        acc = _over_blocks(
            x_ref, lambda sl, acc: acc + d_normed(sl) * w_ref[:, sl]
            * x_ref[:, sl].astype(jnp.float32),
            jnp.zeros((rows, LANES), jnp.float32))
        proj = jnp.sum(acc, axis=1, keepdims=True) * (rstd * rstd / W)

    def block(sl, dw):
        xf = x_ref[:, sl].astype(jnp.float32)
        dn = d_normed(sl)
        if norm == "head":
            r = jax.lax.rsqrt(_head_sum(xf * xf, dh) / dh + eps)
            w = w_ref[...]
            mean = _head_sum(dn * w * xf, dh) * (r * r / dh)
        else:
            r, w, mean = rstd, w_ref[:, sl], proj
        # n = x * rstd; dx = rstd * (dn * w - n * mean(dn * w * n))
        dx_ref[:, sl] = (r * (dn * w - xf * mean)).astype(dx_ref.dtype)
        part = _sublane_sums(dn * xf * r)
        if norm == "head":      # one weight for every head: the blocks' sum
            return dw + part
        dw_ref[:, sl] = part
        return dw

    dw = _over_blocks(x_ref, block,
                      jnp.zeros((SUBLANES, LANES), jnp.float32))
    if norm == "head":
        dw_ref[...] = dw


def _call(kernel, name, rows, weight, tables, dh, norm, eps, interpret,
          pairs, shared, backward=False):
    """One pallas_call over grid (S / bs, b), the batch innermost so that a
    block of the angles' tables is fetched once for all b.  ``rows``: the
    operands of the projection's shape (x; in the backward x, where there
    is a norm, then dy, over which dx is written: nothing reads dy after
    it).  ``shared``: the forward's [b, S, 128] operand or None; in the
    backward anything but None makes its gradient a result."""
    b, S, W = rows[0].shape
    dtype = rows[0].dtype
    bs = block_rows(S, W, dtype.itemsize)
    block = pl.BlockSpec((None, bs, W), lambda si, bi: (bi, si, 0))
    lane_block = pl.BlockSpec((None, bs, LANES), lambda si, bi: (bi, si, 0))
    operands, specs = list(rows), [block] * len(rows)
    has_shared = shared is not None
    if has_shared and not backward:
        operands.append(shared)
        specs.append(lane_block)
    wl = LANES if norm == "head" else W
    if norm:
        operands.append(weight.reshape(1, wl))
        specs.append(pl.BlockSpec((1, wl), lambda si, bi: (0, 0)))
    if tables is not None:
        operands += list(tables)
        specs += [pl.BlockSpec((bs, LANES), lambda si, bi: (si, 0))] * 2
    out_shape = [jax.ShapeDtypeStruct((b, S, W), dtype)]
    out_specs = [block]
    if backward and norm:       # the weight's gradient, eight sublanes a block
        out_shape.append(jax.ShapeDtypeStruct(
            (S // bs * b * SUBLANES, wl), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (SUBLANES, wl), lambda si, bi: (si * b + bi, 0)))
    if backward and has_shared:
        out_shape.append(jax.ShapeDtypeStruct((b, S, LANES), dtype))
        out_specs.append(lane_block)
    return pl.pallas_call(
        functools.partial(kernel, dh=dh, norm=norm,
                          rotary=tables is not None, eps=eps, pairs=pairs,
                          shared=has_shared),
        grid=(S // bs, b), in_specs=specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_bytes(bs, W, dtype.itemsize, has_shared)),
        input_output_aliases={len(rows) - 1: 0} if backward else {},
        interpret=interpret, name=name,
    )(*operands)


# rows of a grid step's ONE lane block at a head of several: 256 KiB a block
# in bf16, the step's overhead under its DMA
TOUCHED_ROWS = (1024, 512) + ROW_BLOCKS


def touched_rows(S, itemsize):
    """Rows of a step's lane block at a head of several lane blocks."""
    return next((bs for bs in TOUCHED_ROWS
                 if bs % _tile(itemsize) == 0 and S % bs == 0), None)


def touched_vmem_bytes(bs, itemsize):
    """What such a call asks Mosaic for: the backward's blocks of dy, dx and
    ``shared``'s gradient and the tables' two twice each, the float32 block
    the gradient is summed in, six float32 temporaries, and room (the
    compiled kernels take 1.8 to 4.6 MiB of the 9 asked at 1,024 rows of
    bf16, ``tests/test_chip_compile_rows.py``)."""
    return 6 * bs * LANES * itemsize + (4 + 1 + 6) * bs * LANES * 4 \
        + 2 * 2 ** 20


def _touched_fwd(*refs, rotary, shared, pairs):
    """One lane block: x, ``shared``'s block, the tables' blocks, the
    result."""
    (x_ref, *refs), o_ref = refs[:-1], refs[-1]
    y = x_ref[...].astype(jnp.float32)
    if shared:
        y = y + refs.pop(0)[...].astype(jnp.float32)
    if rotary:
        y = _rotate(y, refs[0][...], refs[1][...], LANES, pairs)
    o_ref[...] = y.astype(o_ref.dtype)


def _touched_bwd(*refs, rotary, shared, pairs):
    """One lane block: dy, the tables' blocks; dx and, with ``shared``, its
    gradient's block and the float32 scratch block it is summed in."""
    d = refs[0][...].astype(jnp.float32)
    if rotary:      # the rotation's transpose, as ``_bwd_kernel``'s
        d = d * refs[1][...] + _partner(d * refs[2][...], LANES, pairs)
    if not shared:
        refs[-1][...] = d.astype(refs[-1].dtype)
        return
    dx_ref, ds_ref, acc_ref = refs[-3:]
    dx_ref[...] = d.astype(dx_ref.dtype)
    head = pl.program_id(3)     # block j of every head took block j of it

    @pl.when(head == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += d

    @pl.when(head == pl.num_programs(3) - 1)
    def _():
        ds_ref[...] = acc_ref[...].astype(ds_ref.dtype)


def _call_touched(name, x, tables, dh, interpret, pairs, shared, plain,
                  backward=False):
    """A head of n lane blocks whose first ``plain`` pass as they are: x is
    ALIASED to the result and the grid visits the other blocks alone, (rows,
    batch, touched block of a head, head) with ONE lane block a step, so the
    plain blocks never leave HBM.  The head is the inner axis: a block of
    the tables and of ``shared`` is fetched once for all heads, and the
    backward sums ``shared``'s gradient (a result [b, S, (n - plain) * 128],
    float32 in a scratch block) over it."""
    b, S, W = x.shape
    n = head_blocks(dh)
    bs = touched_rows(S, x.dtype.itemsize)
    has_shared = shared is not None
    column = pl.BlockSpec((None, bs, LANES),
                          lambda si, bi, j, h: (bi, si, h * n + plain + j))
    operands, specs = [x], [column]
    if has_shared and not backward:
        operands.append(shared)
        specs.append(pl.BlockSpec((None, bs, LANES),
                                  lambda si, bi, j, h: (bi, si, plain + j)))
    if tables is not None:
        operands += list(tables)
        specs += [pl.BlockSpec((bs, LANES),
                               lambda si, bi, j, h: (si, plain + j))] * 2
    out_shape, out_specs = [jax.ShapeDtypeStruct(x.shape, x.dtype)], [column]
    summed = backward and has_shared
    if summed:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, S, (n - plain) * LANES), x.dtype))
        out_specs.append(pl.BlockSpec((None, bs, LANES),
                                      lambda si, bi, j, h: (bi, si, j)))
    return pl.pallas_call(
        functools.partial(_touched_bwd if backward else _touched_fwd,
                          rotary=tables is not None, shared=has_shared,
                          pairs=pairs),
        grid=(S // bs, b, n - plain, W // (n * LANES)), in_specs=specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bs, LANES), jnp.float32)] if summed
        else [],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",) * 3 + (
                "arbitrary" if summed else "parallel",),
            vmem_limit_bytes=touched_vmem_bytes(bs, x.dtype.itemsize)),
        input_output_aliases={0: 0}, interpret=interpret, name=name,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _qk_rope(x, weight, tables, shared, dh, norm, eps, interpret, pairs,
             plain):
    if head_blocks(dh) > 1:
        return _call_touched("qk_rope_fwd", x, tables, dh, interpret, pairs,
                             shared, plain)[0]
    return _call(_fwd_kernel, "qk_rope_fwd", [x], weight, tables, dh, norm,
                 eps, interpret, pairs, shared)[0]


def _qk_rope_fwd(x, weight, tables, shared, dh, norm, eps, interpret, pairs,
                 plain):
    # the RAW projection is the residual, and only where a norm reads it; of
    # ``shared`` its (empty) place in the tree says whether it was there
    return (_qk_rope(x, weight, tables, shared, dh, norm, eps, interpret,
                     pairs, plain),
            (x if norm else None, weight, tables,
             None if shared is None else ()))


def _qk_rope_bwd(dh, norm, eps, interpret, pairs, plain, res, dy):
    x, weight, tables, shared = res
    if head_blocks(dh) > 1:
        out = _call_touched("qk_rope_bwd", dy, tables, dh, interpret, pairs,
                            shared, plain, backward=True)
        if shared is not None and plain:    # nothing of it in those blocks
            out = (out[0], jnp.pad(out[1], (
                (0, 0), (0, 0), (plain * LANES, 0))))
    else:
        out = _call(_bwd_kernel, "qk_rope_bwd", [x, dy] if norm else [dy],
                    weight, tables, dh, norm, eps, interpret, pairs, shared,
                    backward=True)
    # the angles' tables hang on positions alone: no cotangent
    return (out[0], jnp.sum(out[1], axis=0) if norm else None,
            jax.tree.map(jnp.zeros_like, tables),
            None if shared is None else out[1])


_qk_rope.defvjp(_qk_rope_fwd, _qk_rope_bwd)


def qk_rope(x, weight=None, tables=None, *, head_dim, norm=None, eps=1e-5,
            pairs=False, shared=None, plain_blocks=0, interpret=None):
    """``x`` [b, S, W] packed heads of ``head_dim``; ``norm`` "head" (RMS
    norm of each head, ``weight`` [head_dim]), "whole" (of the projection,
    ``weight`` [W]) or None; ``tables`` = ``angle_tables(S, head_dim, theta,
    first)`` for rotary positions, or None; with ``pairs`` the rotation is of
    adjacent pairs and ``tables`` = ``pair_tables(...)``; ``shared`` [b, S,
    128] is added to every lane block before the rotation (no norm with it).
    A head of n whole lane blocks (``pairs`` or no tables, no norm): tables
    [S, n * 128], ``shared`` [b, S, n * 128], and the first ``plain_blocks``
    lane blocks of every head, where the tables turn nothing and ``shared``
    holds nothing, stay where they are (x aliased to the result).
    ``supported(x.shape, head_dim, itemsize)`` must hold.  Float32 inside,
    rounded once to ``x.dtype``."""
    if not supported(x.shape, head_dim, x.dtype.itemsize):
        raise ValueError("qk_rope: shape %s at head_dim %d is not supported"
                         % (x.shape, head_dim))
    if shared is not None and norm:
        raise ValueError("qk_rope: a shared lane block goes with no norm")
    if head_dim > LANES and (norm or not (pairs or tables is None)):
        raise ValueError("qk_rope: a head of several lane blocks goes with "
                         "no norm and the pairs' rotation")
    if not 0 <= plain_blocks < head_blocks(head_dim):
        raise ValueError("qk_rope: %d plain blocks of a head's %d"
                         % (plain_blocks, head_blocks(head_dim)))
    if interpret is None:
        interpret = not _on_tpu()
    if norm == "head":          # one weight for every head of a lane block
        weight = jnp.tile(weight.astype(jnp.float32), LANES // head_dim)
    elif norm:
        weight = weight.astype(jnp.float32)
    if shared is not None:
        shared = shared.astype(x.dtype)
    return _qk_rope(x, weight, tables, shared, head_dim, norm or None,
                    float(eps), bool(interpret), bool(pairs),
                    int(plain_blocks))
