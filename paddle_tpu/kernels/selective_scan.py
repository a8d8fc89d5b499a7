"""The selective scan of a Mamba-1 layer (Gu & Dao, arXiv:2312.00752) and
the gate behind it as Pallas TPU kernels, forward and a custom-VJP backward:
``selective_scan``.

For every channel c of ``d`` and state cell n of ``N``, along one sequence:

    h_t[c, n] = exp(dt_t[c] * A[c, n]) * h_{t-1}[c, n] + dt_t[c] * B_t[n] * x_t[c]
    y_t[c]    = sum_n C_t[n] * h_t[c, n] + D[c] * x_t[c]          h_{-1} = 0
    out_t[c]  = y_t[c] * silu(z_t[c])

The decay differs for every cell and token, so there is no chunked-matmul
form (``power_retention.py`` has one scalar decay a head): the recurrence is
walked a token at a time on the vector unit, in float32.  What a kernel is
for is the state: ``[S, d, N]`` float32 is 2.7 GB a layer at S = 8,192 and
d = 5,120, and here it never reaches HBM.

Layout.  ``d`` lies on lanes AND sublanes: a token's 1,024 channels are one
whole float32 tile ``[8, 128]`` (eight lane tiles of its row, one a sublane)
and a state cell n of those channels is one tile too.  A token's step is
then sixteen tile recurrences whose ``B_t[n]`` and ``C_t[n]`` are SCALARS,
read from SMEM and splat: no value crosses a lane or a sublane in the
forward.  The sequence is walked in chunks of ``chunk`` tokens (the grid's
inner, sequential axis); the state ``[N, d / 128, 128]`` stays in VMEM
scratch from chunk to chunk, and inside a chunk each BAND of 8 sublane rows
(1,024 channels; a loop over the bands, one body) keeps its sixteen state
tiles in registers while it walks the chunk's tokens.

The door.  ``[b, S, d]`` and ``[b, S, d / 128, 128]`` are the same bytes in
row-major order and NOT on the chip: HBM holds ``[S, d]`` in tiles of (8,
128) over (tokens, channels), and the second shape's tiles lie over (channel
rows, channels), so XLA answers that reshape with a relayout copy of every
operand and gradient, each way, at half of HBM's rate: eight copies, 3.8 ms
a layer and step at the jamba cell's shape beside 10.3 in the kernels (PR
48 did just that).  The kernels therefore address the projections' OWN
tiling.  Tiled (8, 128), float32 ``[S, d]`` is byte for byte the row-major
array ``[S / 8, d / 16, 128]`` whose row 8 r + s of group g is lane tile r
of token 8 g + s (``_tiles``: a reshape, a transpose and a reshape that XLA
compiles to a bitcast).  There the tile of a token and a band is eight
sublanes a fixed stride apart, ``ref[g, pl.ds(64 band + s, 8, stride=8)]``:
ONE strided load or store (``_tokens``; an index ``[g, rows, s, :]`` into
``[S / 8, d / 128, 8, 128]`` says the same and Mosaic makes of it eight
one-sublane loads, seven rotates and seven selects).  The float32 per-token
arrays, ``dt`` in and ``ddt`` out, cross as that view; every whole-chunk
float32 scratch has its shape.  x, z and the output's gradient (in) and the
output, ``dx`` and ``dz`` (out), bfloat16 in the cell, whose tiles hold 16
tokens and have no such view at 8, cross as plain ``[chunk, d]`` blocks of
the 2-D array: the cast to or from float32 that each had anyway goes a lane
tile at a time, ``[chunk, 128]`` read as ``[chunk / 8, 8, 128]`` (whole
float32 tiles renumbered, nothing moved) into or out of the scratch.
Nothing re-tiles an operand or a gradient in HBM.

Backward.  The forward (under ``jax.vjp``) also writes the state as each
chunk FOUND it: ``[b, S / chunk, N, d]`` float32, 21 MB a layer at chunks of
128.  The backward walks the chunks from the last to the first: it makes a
chunk's states again from that edge (kept in VMEM: chunk + 1 tiles a cell),
then walks the tokens backwards with ``dh`` carried in registers and from
chunk to chunk in scratch.  ``dB_t[n]`` and ``dC_t[n]`` are sums over ALL
channels: they are gathered a tile a (token, cell) in VMEM over the bands
of rows and reduced once a chunk (strided sublane loads, a lane reduce),
which is the one place a value crosses lanes.  ``dA`` and ``dD`` are
accumulated in their output blocks, a batch row each, summed outside.

No ``[S, d, N]`` and no ``[chunk, d, N]`` tensor is written to HBM in either
direction.

``selective_scan_reference`` is the same in plain ``jnp``, a ``lax.scan`` a
token: what the tests hold the kernels to, and what shapes the kernels do
not take (``supported``) run on.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom), but for ONE hint: the
eight tokens of a group are unrolled on the chip and stay a loop under the
interpreter, whose XLA compile of eight copies of every body was two of the
CPU tests' minutes (``tests/test_chip_compile_scans.py`` compiles the
unrolled bodies for a described v5e).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["selective_scan", "selective_scan_reference", "supported",
           "group_rows", "vmem_bytes"]

LANES = 128
SUBLANES = 8
F32 = jnp.float32


def group_rows(d):
    """Sublane rows of ``[d / 128, 128]`` that one walk of a chunk's tokens
    takes: 8, a whole float32 tile a state cell, where they divide the
    rows; else all of them (a narrow test shape)."""
    rows = d // LANES
    return SUBLANES if rows % SUBLANES == 0 else rows


def supported(shape, n_state, chunk):
    """Whether the kernels take x ``[b, S, d]`` at ``n_state`` cells and
    chunks of ``chunk`` tokens: whole lane blocks of channels, whole chunks,
    chunks of whole sublane tiles, so that S is groups of 8 tokens too
    (``_tiles``)."""
    _, S, d = shape
    return d % LANES == 0 and S % chunk == 0 and chunk % SUBLANES == 0 \
        and S % SUBLANES == 0


def vmem_bytes(chunk, d, n_state, itemsize):
    """What the backward, the larger of the two calls, asks Mosaic for: its
    pipelined blocks (x, z, dout in, dx, dz out at ``itemsize``; dt in, ddt
    out float32; two copies each), four float32 copies of a chunk, the
    chunk's states and the two gathers of a group of rows, the carried
    state's, A's and dA's blocks; and half as much again for what the
    compiler keeps."""
    rows = group_rows(d)
    block = chunk * d
    pipelined = 2 * block * (5 * itemsize + 2 * 4)
    scratch = 4 * block * 4
    states = (3 * chunk + 1) * n_state * rows * LANES * 4
    cells = 6 * n_state * d * 4
    return int(1.5 * (pipelined + scratch + states + cells)) + (4 << 20)


def selective_scan_reference(x, dt, bmat, cmat, z, a, dskip):
    """The recurrence a token at a time in float32 ``jnp``: x, dt, z [b, S,
    d], bmat, cmat [b, S, N], a [d, N] (negative), dskip [d]; out [b, S, d]
    in x's dtype."""
    xf, zf, dtf = (t.astype(F32) for t in (x, z, dt))

    def step(h, turn):
        x_t, dt_t, b_t, c_t = turn                  # [b, d], [b, d], [b, N]
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros(x.shape[:1] + a.shape, F32)
    _, y = jax.lax.scan(step, h0, tuple(
        t.swapaxes(0, 1) for t in (xf, dtf, bmat.astype(F32),
                                   cmat.astype(F32))))
    y = y.swapaxes(0, 1) + dskip.astype(F32) * xf
    return (y * jax.nn.silu(zf)).astype(x.dtype)


def _silu(zf):
    return zf * jax.nn.sigmoid(zf)


def _band(g, d):
    """The ``g``-th walk's rows of ``[d / 128, 128]``, ``group_rows`` of
    them."""
    rows = group_rows(d)
    return pl.ds(pl.multiple_of(g * rows, rows), rows)


def _lanes(r):
    return slice(r * LANES, (r + 1) * LANES)


def _rows(r):
    return slice(r * SUBLANES, (r + 1) * SUBLANES)


def _in_tiles(ref, r):
    """Lane tile ``r`` of a ``[chunk, d]`` block in float32, as the scratch
    holds it: ``[chunk / 8, 8, 128]``."""
    return ref[:, _lanes(r)].astype(F32).reshape(-1, SUBLANES, LANES)


def _out_of_tiles(ref, r, tiles):
    """``tiles`` [chunk / 8, 8, 128] float32 as lane tile ``r`` of the
    ``[chunk, d]`` block ``ref``, rounded to its type."""
    ref[:, _lanes(r)] = tiles.reshape(-1, LANES).astype(ref.dtype)


def _tokens(chunk, rows, step, carry, unroll, back=False):
    """``step(t, at, carry)`` for every token ``t`` of a chunk in order
    (``back``: from the last to the first), ``at`` where a ``[chunk / 8, 8
    R, 128]`` ref keeps the lane tiles ``rows`` of the token's channels: one
    sublane of each tile, 8 apart, ONE strided load or store.  A loop over
    the chunk's groups of 8 tokens; ``unroll``: a group's 8 tokens unrolled,
    their sublanes static, so that the compiler schedules a token's loads
    and ``exp`` under the token before it (PERF.md section 6, PR 49)."""
    groups = chunk // SUBLANES

    def group_step(k, carry):
        group = groups - 1 - k if back else k

        def token(j, carry):
            s = SUBLANES - 1 - j if back else j
            return step(group * SUBLANES + s,
                        (group, pl.ds(rows.start * SUBLANES + s, rows.size,
                                      stride=SUBLANES)), carry)

        return jax.lax.fori_loop(0, SUBLANES, token, carry, unroll=unroll)

    return jax.lax.fori_loop(0, groups, group_step, carry)


def _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows, h, chunk,
          n_state, unroll, hist_ref=None):
    """The recurrence over a chunk's tokens for one band of ``rows``, from
    the state tiles ``h`` (a tuple, a cell each): ``y`` before its gate into
    ``ys_ref``, each token's state into ``hist_ref[t + 1]`` where given;
    the state the chunk leaves."""
    N = n_state
    a = [a_ref[n, rows, :] for n in range(N)]
    skip = d_ref[rows, :]

    def step(t, at, h):
        dt, x = dt_ref[at], xf_ref[at]
        dtx, y, new = dt * x, skip * x, []
        for n in range(N):
            hn = jnp.exp(dt * a[n]) * h[n] + dtx * b_ref[0, t * N + n]
            y = y + hn * c_ref[0, t * N + n]
            if hist_ref is not None:
                hist_ref[t + 1, n] = hn
            new.append(hn)
        ys_ref[at] = y
        return tuple(new)

    return _tokens(chunk, rows, step, h, unroll)


def _fwd_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, *rest,
                chunk, n_state, save, unroll):
    """One chunk of one sequence.  x, z [chunk, d]; dt [chunk / 8, 8 R,
    128] float32; b, c SMEM [1, chunk * N]; a [N, R, 128]; d [R, 128]."""
    if save:
        y_ref, edge_ref, h_ref, xf_ref, ys_ref = rest
    else:
        y_ref, h_ref, xf_ref, ys_ref = rest
    N = n_state
    R = a_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, F32)

    if save:
        edge_ref[...] = h_ref[...]
    for r in range(R):
        xf_ref[:, _rows(r)] = _in_tiles(x_ref, r)

    def walk_band(g, carry):
        rows = _band(g, R * LANES)
        h = _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows,
                  tuple(h_ref[n, rows, :] for n in range(N)), chunk, N,
                  unroll)
        for n in range(N):
            h_ref[n, rows, :] = h[n]
        return carry

    jax.lax.fori_loop(0, R // group_rows(R * LANES), walk_band, 0)
    for r in range(R):
        _out_of_tiles(y_ref, r,
                      ys_ref[:, _rows(r)] * _silu(_in_tiles(z_ref, r)))


def _sum_tiles(acc_ref, chunk, n_state, rows):
    """``[chunk, N]``: every (token, cell) tile of ``acc_ref`` [chunk * N *
    rows, 128] summed over its rows and lanes."""
    N = n_state
    cell = jax.lax.broadcasted_iota(jnp.int32, (chunk, N), 1)
    out = jnp.zeros((chunk, N), F32)
    for n in range(N):
        part = acc_ref[pl.ds(n * rows, chunk, stride=N * rows), :]
        for s in range(1, rows):
            part = part + acc_ref[pl.ds(n * rows + s, chunk,
                                        stride=N * rows), :]
        out = jnp.where(cell == n, jnp.sum(part, axis=-1, keepdims=True), out)
    return out


def _bwd_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, edge_ref,
                do_ref, dx_ref, ddt_ref, dz_ref, db_ref, dc_ref, da_ref,
                dd_ref, dh_ref, xf_ref, g_ref, ys_ref, dxf_ref, hist_ref,
                accb_ref, accc_ref, *, chunk, n_state, unroll):
    """One chunk of one sequence, the chunks from the last to the first.
    ``edge_ref`` [N, R, 128] is the state the chunk found; ``dh_ref`` holds
    what the later chunk's first token hands back: ``exp(dt A) dh``."""
    N = n_state
    R = a_ref.shape[1]
    rows_n = group_rows(R * LANES)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[...] = jnp.zeros(dh_ref.shape, F32)
        da_ref[...] = jnp.zeros(da_ref.shape, F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, F32)

    for r in range(R):
        xf_ref[:, _rows(r)] = _in_tiles(x_ref, r)
        zf = _in_tiles(z_ref, r)
        # the gradient of y
        g_ref[:, _rows(r)] = _in_tiles(do_ref, r) * zf * jax.nn.sigmoid(zf)

    def band(g, carry):
        rows, first = _band(g, R * LANES), g == 0
        a = [a_ref[n, rows, :] for n in range(N)]
        skip = d_ref[rows, :]
        for n in range(N):
            hist_ref[0, n] = edge_ref[n, rows, :]
        # the chunk's states again, from the state it found
        _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows,
              tuple(edge_ref[n, rows, :] for n in range(N)), chunk, N,
              unroll, hist_ref)

        def backward(t, at, later):
            dt, x, gy = dt_ref[at], xf_ref[at], g_ref[at]
            dtx = dt * x
            ddt, dx, new = jnp.zeros_like(dt), jnp.zeros_like(dt), []
            for n in range(N):
                b_tn, c_tn = b_ref[0, t * N + n], c_ref[0, t * N + n]
                at_tile = pl.multiple_of((t * N + n) * rows_n, rows_n)
                tile = pl.ds(at_tile, rows_n)
                dh = gy * c_tn + later[n]
                decay = jnp.exp(dt * a[n])
                q = dh * hist_ref[t, n] * decay
                ddt = ddt + q * a[n] + dh * (x * b_tn)
                dx = dx + dh * (dt * b_tn)
                da_ref[n, rows, :] += q * dt
                # the first band's sums start the (token, cell) tiles
                accc_ref[tile, :] = jnp.where(
                    first, 0.0, accc_ref[tile, :]) + gy * hist_ref[t + 1, n]
                accb_ref[tile, :] = jnp.where(
                    first, 0.0, accb_ref[tile, :]) + dh * dtx
                new.append(decay * dh)
            ddt_ref[at] = ddt
            dxf_ref[at] = dx + skip * gy
            return tuple(new)

        earlier = _tokens(chunk, rows, backward,
                          tuple(dh_ref[n, rows, :] for n in range(N)), unroll,
                          back=True)
        for n in range(N):
            dh_ref[n, rows, :] = earlier[n]
        return carry

    jax.lax.fori_loop(0, R // rows_n, band, 0)
    for r in range(R):
        _out_of_tiles(dx_ref, r, dxf_ref[:, _rows(r)])
        zf = _in_tiles(z_ref, r)
        sig = jax.nn.sigmoid(zf)
        # d silu(z) = sigmoid(z) (1 + z (1 - sigmoid(z)))
        _out_of_tiles(dz_ref, r, _in_tiles(do_ref, r) * ys_ref[:, _rows(r)]
                      * sig * (1.0 + zf * (1.0 - sig)))
    # summed over the token groups here, over a group's tokens outside
    dd_ref[...] += jnp.sum(g_ref[...] * xf_ref[...], axis=0)
    db_ref[...] = _sum_tiles(accb_ref, chunk, N, rows_n)
    dc_ref[...] = _sum_tiles(accc_ref, chunk, N, rows_n)


def _params(chunk, d, n_state, itemsize):
    return _CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes(chunk, d, n_state, itemsize))


def _tiles(t):
    """Float32 ``[b, S, d]`` as ``[b, S / 8, d / 16, 128]``, row 8 r + s of
    group g the lane tile r of token 8 g + s: the array's own (8, 128) tiles
    in the order HBM holds them, a bitcast on the chip."""
    b, S, d = t.shape
    return t.reshape(b, S // SUBLANES, SUBLANES, d // LANES, LANES) \
        .transpose(0, 1, 3, 2, 4) \
        .reshape(b, S // SUBLANES, SUBLANES * (d // LANES), LANES)


def _of_tiles(t):
    """``_tiles`` back: ``[b, S, d]``."""
    b, groups, rows, _ = t.shape
    return t.reshape(b, groups, rows // SUBLANES, SUBLANES, LANES) \
        .transpose(0, 1, 3, 2, 4) \
        .reshape(b, groups * SUBLANES, rows // SUBLANES * LANES)


def _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, save, z_at=0):
    """``out`` [b, S, d] and, where ``save``, the state each chunk found
    [b, S / chunk, N, d / 128, 128].  ``a_t`` [N, d] float32; z's block the
    ``z_at``-th of d lanes of a wider array."""
    b, S, d = x.shape
    N, R, nc, G = a_t.shape[0], d // LANES, S // chunk, chunk // SUBLANES
    rows = pl.BlockSpec((None, chunk, d), lambda i, j: (i, j, 0))
    z_rows = pl.BlockSpec((None, chunk, d), lambda i, j: (i, j, z_at))
    tiles = pl.BlockSpec((None, G, SUBLANES * R, LANES),
                         lambda i, j: (i, j, 0, 0))
    scalars = pl.BlockSpec((1, chunk * N), lambda i, j: (i, j),
                           memory_space=pltpu.SMEM)
    cells = pl.BlockSpec((N, R, LANES), lambda i, j: (0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, S, d), x.dtype)]
    out_specs = [rows]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, N, R, LANES), F32))
        out_specs.append(pl.BlockSpec((None, None, N, R, LANES),
                                      lambda i, j: (i, j, 0, 0, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=N, save=save,
                          unroll=not interpret),
        grid=(b, nc),
        in_specs=[rows, tiles, z_rows, scalars, scalars, cells,
                  pl.BlockSpec((R, LANES), lambda i, j: (0, 0))],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, R, LANES), F32)]
        + [pltpu.VMEM((G, SUBLANES * R, LANES), F32)] * 2,
        compiler_params=_params(chunk, d, N, x.dtype.itemsize),
        interpret=interpret, name="selective_scan_fwd",
    )(x, _tiles(dt), z, bmat.reshape(b, S * N), cmat.reshape(b, S * N),
      a_t.reshape(N, R, LANES), dskip.reshape(R, LANES))


def _bwd(chunk, interpret, z_at, res, dout):
    x, dt, bmat, cmat, z, a_t, dskip, edges = res
    b, S, d = x.shape
    N, R, nc, G = a_t.shape[0], d // LANES, S // chunk, chunk // SUBLANES
    gr = group_rows(d)

    def back(i, j):         # the chunks from the last to the first
        return nc - 1 - j

    rows = pl.BlockSpec((None, chunk, d), lambda i, j: (i, back(i, j), 0))
    z_rows = pl.BlockSpec((None, chunk, d),
                          lambda i, j: (i, back(i, j), z_at))
    tiles = pl.BlockSpec((None, G, SUBLANES * R, LANES),
                         lambda i, j: (i, back(i, j), 0, 0))
    scalars = pl.BlockSpec((1, chunk * N), lambda i, j: (i, back(i, j)),
                           memory_space=pltpu.SMEM)
    cells = pl.BlockSpec((N, R, LANES), lambda i, j: (0, 0, 0))
    per_token = pl.BlockSpec((None, chunk, N), lambda i, j: (i, back(i, j), 0))
    like_x = jax.ShapeDtypeStruct((b, S, d), x.dtype)
    dx, ddt, dz, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=N,
                          unroll=not interpret),
        grid=(b, nc),
        in_specs=[rows, tiles, z_rows, scalars, scalars, cells,
                  pl.BlockSpec((R, LANES), lambda i, j: (0, 0)),
                  pl.BlockSpec((None, None, N, R, LANES),
                               lambda i, j: (i, back(i, j), 0, 0, 0)),
                  rows],
        out_specs=[rows, tiles, rows, per_token, per_token,
                   pl.BlockSpec((None, N, R, LANES),
                                lambda i, j: (i, 0, 0, 0)),
                   pl.BlockSpec((None, SUBLANES * R, LANES),
                                lambda i, j: (i, 0, 0))],
        out_shape=[like_x,
                   jax.ShapeDtypeStruct((b, S // SUBLANES, SUBLANES * R,
                                         LANES), F32),
                   like_x, jax.ShapeDtypeStruct((b, S, N), F32),
                   jax.ShapeDtypeStruct((b, S, N), F32),
                   jax.ShapeDtypeStruct((b, N, R, LANES), F32),
                   jax.ShapeDtypeStruct((b, SUBLANES * R, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((N, R, LANES), F32)]
        + [pltpu.VMEM((G, SUBLANES * R, LANES), F32)] * 4
        + [pltpu.VMEM((chunk + 1, N, gr, LANES), F32)]
        + [pltpu.VMEM((chunk * N * gr, LANES), F32)] * 2,
        compiler_params=_params(chunk, d, N, x.dtype.itemsize),
        interpret=interpret, name="selective_scan_bwd",
    )(x, _tiles(dt), z, bmat.reshape(b, S * N), cmat.reshape(b, S * N),
      a_t.reshape(N, R, LANES), dskip.reshape(R, LANES), edges, dout)
    # the lanes of a wider z it did not read: zeros, a pad that XLA fuses
    # into whatever reads that gradient
    if z.shape[-1] != d:
        dz = jnp.pad(dz, ((0, 0), (0, 0),
                          (z_at * d, z.shape[-1] - (z_at + 1) * d)))
    return (dx, _of_tiles(ddt), db, dc, dz,
            jnp.sum(da, axis=0).reshape(N, d),
            jnp.sum(dd.reshape(b, R, SUBLANES, LANES), axis=(0, 2))
            .reshape(d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, z_at):
    return _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, False,
                z_at)[0]


def _scan_fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, z_at):
    out, edges = _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret,
                      True, z_at)
    return out, (x, dt, bmat, cmat, z, a_t, dskip, edges)


_scan.defvjp(_scan_fwd, _bwd)


def selective_scan(x, dt, bmat, cmat, z, a, dskip, chunk=128, interpret=None,
                   z_at=0):
    """``out`` [b, S, d] of the module's three lines: x, z [b, S, d] (any
    float type; ``out``, ``dx`` and ``dz`` have x's), dt [b, S, d] the step
    sizes AFTER their softplus, bmat and cmat [b, S, N], a [d, N] the
    NEGATIVE rates (``-exp(a_log)``), dskip [d]; the recurrence, the state
    and every sum in float32.  Differentiable in all seven.  ``chunk``
    tokens between two kept states (clamp it to S); the result does not
    depend on it beyond the rounding of the sums ``dB`` and ``dC``.  z may
    be a wider array [b, S, k d] read IN PLACE (``in_proj``'s packed ``[x |
    z]``): its lanes ``z_at`` d .. are the gate, and its gradient is zero
    in the others."""
    assert supported(x.shape, a.shape[1], chunk), (x.shape, a.shape, chunk)
    assert z.shape[-1] % x.shape[-1] == 0 \
        and z_at < z.shape[-1] // x.shape[-1], (z.shape, x.shape, z_at)
    if interpret is None:
        interpret = not _on_tpu()
    dt, bmat, cmat = (t.astype(F32) for t in (dt, bmat, cmat))
    return _scan(x, dt, bmat, cmat, z, a.astype(F32).T, dskip.astype(F32),
                 int(chunk), bool(interpret), int(z_at))
