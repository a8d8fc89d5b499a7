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

Layout.  ``d`` lies on lanes AND sublanes: ``[b, S, d]`` is read as ``[b, S,
d / 128, 128]`` (the same bytes), so that a token's 1,024 channels are one
whole float32 tile ``[8, 128]`` and a state cell n of those channels is one
tile too.  A token's step is then sixteen tile recurrences whose ``B_t[n]``
and ``C_t[n]`` are SCALARS, read from SMEM and splat: no value crosses a
lane or a sublane in the forward.  The sequence is walked in chunks of
``chunk`` tokens (the grid's inner, sequential axis); the state ``[N, d /
128, 128]`` stays in VMEM scratch from chunk to chunk, and inside a chunk
each group of 8 sublane rows keeps its sixteen state tiles in registers
while it walks the chunk's tokens.

Backward.  The forward (under ``jax.vjp``) also writes the state as each
chunk FOUND it: ``[b, S / chunk, N, d]`` float32, 21 MB a layer at chunks of
128.  The backward walks the chunks from the last to the first: it makes a
chunk's states again from that edge (kept in VMEM: chunk + 1 tiles a cell),
then walks the tokens backwards with ``dh`` carried in registers and from
chunk to chunk in scratch.  ``dB_t[n]`` and ``dC_t[n]`` are sums over ALL
channels: they are gathered a tile a (token, cell) in VMEM over the groups
of rows and reduced once a chunk (strided sublane loads, a lane reduce),
which is the one place a value crosses lanes.  ``dA`` and ``dD`` are
accumulated in their output blocks, a batch row each, summed outside.

No ``[S, d, N]`` and no ``[chunk, d, N]`` tensor is written to HBM in either
direction.

``selective_scan_reference`` is the same in plain ``jnp``, a ``lax.scan`` a
token: what the tests hold the kernels to, and what shapes the kernels do
not take (``supported``) run on.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
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
    chunks of whole sublane tiles."""
    _, S, d = shape
    return d % LANES == 0 and S % chunk == 0 and chunk % SUBLANES == 0


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


def _groups(d):
    rows = group_rows(d)
    return [slice(g, g + rows) for g in range(0, d // LANES, rows)]


def _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows, h, chunk,
          n_state, hist_ref=None):
    """The recurrence over a chunk's tokens for one group of ``rows``, from
    the state tiles ``h`` (a tuple, a cell each): ``y`` before its gate into
    ``ys_ref``, each token's state into ``hist_ref[t + 1]`` where given;
    the state the chunk leaves."""
    N = n_state
    a = [a_ref[n, rows, :] for n in range(N)]
    skip = d_ref[rows, :]

    def step(t, h):
        dt, x = dt_ref[t, rows, :], xf_ref[t, rows, :]
        dtx, y, new = dt * x, skip * x, []
        for n in range(N):
            hn = jnp.exp(dt * a[n]) * h[n] + dtx * b_ref[0, t * N + n]
            y = y + hn * c_ref[0, t * N + n]
            if hist_ref is not None:
                hist_ref[t + 1, n] = hn
            new.append(hn)
        ys_ref[t, rows, :] = y
        return tuple(new)

    return jax.lax.fori_loop(0, chunk, step, h)


def _fwd_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, *rest,
                chunk, n_state, save):
    """One chunk of one sequence.  x, z [chunk, R, 128]; dt the same,
    float32; b, c SMEM [1, chunk * N]; a [N, R, 128]; d [R, 128]."""
    if save:
        y_ref, edge_ref, h_ref, xf_ref, ys_ref = rest
    else:
        y_ref, h_ref, xf_ref, ys_ref = rest
    N = n_state

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, F32)

    if save:
        edge_ref[...] = h_ref[...]
    xf_ref[...] = x_ref[...].astype(F32)
    for rows in _groups(xf_ref.shape[1] * LANES):
        h = _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows,
                  tuple(h_ref[n, rows, :] for n in range(N)), chunk, N)
        for n in range(N):
            h_ref[n, rows, :] = h[n]
    y_ref[...] = (ys_ref[...] * _silu(z_ref[...].astype(F32))).astype(
        y_ref.dtype)


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
                accb_ref, accc_ref, *, chunk, n_state):
    """One chunk of one sequence, the chunks from the last to the first.
    ``edge_ref`` [N, R, 128] is the state the chunk found; ``dh_ref`` holds
    what the later chunk's first token hands back: ``exp(dt A) dh``."""
    N = n_state
    R = xf_ref.shape[1]
    rows_n = group_rows(R * LANES)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[...] = jnp.zeros(dh_ref.shape, F32)
        da_ref[...] = jnp.zeros(da_ref.shape, F32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, F32)

    xf_ref[...] = x_ref[...].astype(F32)
    zf = z_ref[...].astype(F32)
    sig = jax.nn.sigmoid(zf)
    dof = do_ref[...].astype(F32)
    g_ref[...] = dof * zf * sig             # the gradient of y
    for at, rows in enumerate(_groups(R * LANES)):
        a = [a_ref[n, rows, :] for n in range(N)]
        skip = d_ref[rows, :]
        for n in range(N):
            hist_ref[0, n] = edge_ref[n, rows, :]
        # the chunk's states again, from the state it found
        _walk(dt_ref, xf_ref, b_ref, c_ref, a_ref, d_ref, ys_ref, rows,
              tuple(edge_ref[n, rows, :] for n in range(N)), chunk, N,
              hist_ref)

        def backward(i, carry):
            t = chunk - 1 - i
            dt, x, g = dt_ref[t, rows, :], xf_ref[t, rows, :], \
                g_ref[t, rows, :]
            dtx = dt * x
            ddt, dx, new = jnp.zeros_like(dt), jnp.zeros_like(dt), []
            for n in range(N):
                b_tn, c_tn = b_ref[0, t * N + n], c_ref[0, t * N + n]
                at_tile = pl.multiple_of((t * N + n) * rows_n, rows_n)
                tile = pl.ds(at_tile, rows_n)
                dh = g * c_tn + carry[n]
                decay = jnp.exp(dt * a[n])
                q = dh * hist_ref[t, n] * decay
                ddt = ddt + q * a[n] + dh * (x * b_tn)
                dx = dx + dh * (dt * b_tn)
                da_ref[n, rows, :] += q * dt
                if at == 0:
                    accc_ref[tile, :] = g * hist_ref[t + 1, n]
                    accb_ref[tile, :] = dh * dtx
                else:
                    accc_ref[tile, :] += g * hist_ref[t + 1, n]
                    accb_ref[tile, :] += dh * dtx
                new.append(decay * dh)
            ddt_ref[t, rows, :] = ddt
            dxf_ref[t, rows, :] = dx + skip * g
            return tuple(new)

        carry = jax.lax.fori_loop(
            0, chunk, backward, tuple(dh_ref[n, rows, :] for n in range(N)))
        for n in range(N):
            dh_ref[n, rows, :] = carry[n]
    dx_ref[...] = dxf_ref[...].astype(dx_ref.dtype)
    # d silu(z) = sigmoid(z) (1 + z (1 - sigmoid(z)))
    dz_ref[...] = (dof * ys_ref[...] * sig * (1.0 + zf * (1.0 - sig))).astype(
        dz_ref.dtype)
    dd_ref[...] += jnp.sum(g_ref[...] * xf_ref[...], axis=0)
    db_ref[...] = _sum_tiles(accb_ref, chunk, N, rows_n)
    dc_ref[...] = _sum_tiles(accc_ref, chunk, N, rows_n)


def _params(chunk, d, n_state, itemsize):
    return _CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes(chunk, d, n_state, itemsize))


def _tiled(t):
    """``[b, S, d]`` read as ``[b, S, d / 128, 128]``."""
    return t.reshape(t.shape[:2] + (t.shape[2] // LANES, LANES))


def _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, save):
    """``out`` [b, S, d] and, where ``save``, the state each chunk found
    [b, S / chunk, N, d / 128, 128].  ``a_t`` [N, d] float32."""
    b, S, d = x.shape
    N, R, nc = a_t.shape[0], d // LANES, S // chunk
    rows = pl.BlockSpec((None, chunk, R, LANES), lambda i, j: (i, j, 0, 0))
    scalars = pl.BlockSpec((1, chunk * N), lambda i, j: (i, j),
                           memory_space=pltpu.SMEM)
    cells = pl.BlockSpec((N, R, LANES), lambda i, j: (0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, S, R, LANES), x.dtype)]
    out_specs = [rows]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, N, R, LANES), F32))
        out_specs.append(pl.BlockSpec((None, None, N, R, LANES),
                                      lambda i, j: (i, j, 0, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=N, save=save),
        grid=(b, nc),
        in_specs=[rows, rows, rows, scalars, scalars, cells,
                  pl.BlockSpec((R, LANES), lambda i, j: (0, 0))],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, R, LANES), F32),
                        pltpu.VMEM((chunk, R, LANES), F32),
                        pltpu.VMEM((chunk, R, LANES), F32)],
        compiler_params=_params(chunk, d, N, x.dtype.itemsize),
        interpret=interpret, name="selective_scan_fwd",
    )(_tiled(x), _tiled(dt), _tiled(z), bmat.reshape(b, S * N),
      cmat.reshape(b, S * N), a_t.reshape(N, R, LANES),
      dskip.reshape(R, LANES))
    return (out[0].reshape(b, S, d),) + tuple(out[1:])


def _bwd(chunk, interpret, res, dout):
    x, dt, bmat, cmat, z, a_t, dskip, edges = res
    b, S, d = x.shape
    N, R, nc = a_t.shape[0], d // LANES, S // chunk
    gr = group_rows(d)

    def back(i, j):         # the chunks from the last to the first
        return nc - 1 - j

    rows = pl.BlockSpec((None, chunk, R, LANES),
                        lambda i, j: (i, back(i, j), 0, 0))
    scalars = pl.BlockSpec((1, chunk * N), lambda i, j: (i, back(i, j)),
                           memory_space=pltpu.SMEM)
    cells = pl.BlockSpec((N, R, LANES), lambda i, j: (0, 0, 0))
    per_token = pl.BlockSpec((None, chunk, N), lambda i, j: (i, back(i, j), 0))
    like_x = jax.ShapeDtypeStruct((b, S, R, LANES), x.dtype)
    dx, ddt, dz, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=N),
        grid=(b, nc),
        in_specs=[rows, rows, rows, scalars, scalars, cells,
                  pl.BlockSpec((R, LANES), lambda i, j: (0, 0)),
                  pl.BlockSpec((None, None, N, R, LANES),
                               lambda i, j: (i, back(i, j), 0, 0, 0)),
                  rows],
        out_specs=[rows, rows, rows, per_token, per_token,
                   pl.BlockSpec((None, N, R, LANES),
                                lambda i, j: (i, 0, 0, 0)),
                   pl.BlockSpec((None, R, LANES), lambda i, j: (i, 0, 0))],
        out_shape=[like_x, jax.ShapeDtypeStruct((b, S, R, LANES), F32),
                   like_x, jax.ShapeDtypeStruct((b, S, N), F32),
                   jax.ShapeDtypeStruct((b, S, N), F32),
                   jax.ShapeDtypeStruct((b, N, R, LANES), F32),
                   jax.ShapeDtypeStruct((b, R, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((N, R, LANES), F32)]
        + [pltpu.VMEM((chunk, R, LANES), F32)] * 4
        + [pltpu.VMEM((chunk + 1, N, gr, LANES), F32)]
        + [pltpu.VMEM((chunk * N * gr, LANES), F32)] * 2,
        compiler_params=_params(chunk, d, N, x.dtype.itemsize),
        interpret=interpret, name="selective_scan_bwd",
    )(_tiled(x), _tiled(dt), _tiled(z), bmat.reshape(b, S * N),
      cmat.reshape(b, S * N), a_t.reshape(N, R, LANES),
      dskip.reshape(R, LANES), edges, _tiled(dout))
    return (dx.reshape(b, S, d), ddt.reshape(b, S, d), db, dc,
            dz.reshape(b, S, d), jnp.sum(da, axis=0).reshape(N, d),
            jnp.sum(dd, axis=0).reshape(d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret):
    return _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret, False)[0]


def _scan_fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret):
    out, edges = _fwd(x, dt, bmat, cmat, z, a_t, dskip, chunk, interpret,
                      True)
    return out, (x, dt, bmat, cmat, z, a_t, dskip, edges)


_scan.defvjp(_scan_fwd, _bwd)


def selective_scan(x, dt, bmat, cmat, z, a, dskip, chunk=128, interpret=None):
    """``out`` [b, S, d] of the module's three lines: x, z [b, S, d] (any
    float type; ``out``, ``dx`` and ``dz`` have x's), dt [b, S, d] the step
    sizes AFTER their softplus, bmat and cmat [b, S, N], a [d, N] the
    NEGATIVE rates (``-exp(a_log)``), dskip [d]; the recurrence, the state
    and every sum in float32.  Differentiable in all seven.  ``chunk``
    tokens between two kept states (clamp it to S); the result does not
    depend on it beyond the rounding of the sums ``dB`` and ``dC``."""
    assert supported(x.shape, a.shape[1], chunk), (x.shape, a.shape, chunk)
    if interpret is None:
        interpret = not _on_tpu()
    dt, bmat, cmat = (t.astype(F32) for t in (dt, bmat, cmat))
    return _scan(x, dt, bmat, cmat, z, a.astype(F32).T, dskip.astype(F32),
                 int(chunk), bool(interpret))
