"""The Mamba-2 mixer's scan (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060) in its chunked state-space DUAL form, as Pallas TPU kernels
that carry the state from chunk to chunk in VMEM, forward and backward:
``ssd_scan``.

Head i (``P`` channels wide) of group g, at the rate ``A_i < 0``, with step
sizes ``dt_t > 0`` and the group's ``B_t``, ``C_t`` [N]:

    H_t = exp(dt_t A_i) H_{t-1} + dt_t x_t (x) B_t        H [P, N] float32
    y_t = H_t C_t + D_i x_t

In chunks of ``Q`` tokens, ``a`` the running sum of ``dt A_i`` inside the
chunk (its own token's included) and ``H0`` the state the chunk found:

    y_i  = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j       four matrix
           + exp(a_i) H0 C_i + D_i x_i                            products a
    H1   = exp(a_Q) H0 + sum_j exp(a_Q - a_j) dt_j x_j (x) B_j    chunk

Every exponent is a sum of ``dt A <= 0`` over a stretch of the chunk: none
overflows, and a decay of ``e^-1000`` a chunk is a zero, not a fault.

Grid (batch, group, chunk), the last sequential.  ONE grid step serves a
whole group: ``C B^T`` [Q, Q] is made once for its heads, the state's read
``C H0^T`` and the fold ``(e dt x)^T B`` are one product each over the
group's ``heads x P`` stacked state rows [heads * P, N], and only the
masked-decay block ``L`` and its product with ``dt x`` run a head at a time.
The operands cross the door as VIEWS: x, B and C are lane blocks of the
filter's ONE output [b, S, d + 2 G N] (the same array three times, three
index maps), the output is the mixer's [b, S, d].  The per-token scalars of
a head ride lane-narrow: a group's ``a`` and ``dt`` side by side, a column a
head ([b, G, S, 2 x its heads]), and ``a`` again as rows [b, chunks, heads,
Q] for the other side of ``a_i - a_j``; inside a step they are spread over
their head's channels a lane tile at a time (``_by_lane``), and everything
but a head's own [Q, Q] block runs at the group's whole width.

THE BACKWARD walks the chunks in REVERSE with the state's gradient in VMEM
and reads the states the forward kept, one a chunk and group ([b, chunks, G,
heads * P, N] in the operands' type: 134 MB a layer at [2, 8192] x 64 heads
of 64 x 128 cells in bf16; the forward's own read of the state is in that
type too, as the published kernels' is, and the recurrence cannot be run
backwards through a decay that underflows).  ``a``'s gradient leaves in the
two layouts it arrived in (a column a head, and rows), the caller adds them;
what lies between ``a``, ``dt`` and the mixer's leaves (the running sum, the
rates) is ``jnp`` around the kernels and differentiates itself.

Kernel names in a trace: ``ssd_scan_fwd``, ``ssd_scan_bwd``.
``ssd_scan_chunked`` is the same form in ``jnp`` (where ``supported`` is
false, and the CPU tests' second opinion beside the per-token recurrence).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["ssd_scan", "ssd_scan_chunked", "supported", "vmem_bytes",
           "kept_state_bytes", "LANES"]

LANES = 128
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def supported(shape, heads, groups, d_state, chunk):
    """Whether the kernels take the filter's output ``shape`` = [b, S, d + 2
    G N]: the sequence whole chunks of whole sublane tiles, a group's
    channels whole lane blocks, B and C a lane block each (N = 128) that the
    index maps can address (d a multiple of N), and a group's heads whole
    sublane tiles of the scalars' rows (or all the heads)."""
    _, S, W = shape
    d = W - 2 * groups * d_state
    if d <= 0 or heads % groups or d % heads:
        return False
    per = heads // groups
    return (chunk % 8 == 0 and S % chunk == 0 and d_state == LANES
            and d % groups == 0 and (d // groups) % LANES == 0
            and (per % 8 == 0 or per == heads))


def vmem_bytes(chunk, group_width, d_state, itemsize):
    """What a grid step of the BACKWARD (the larger of the two) asks Mosaic
    for: its pipelined blocks twice (x, dy, dx at the group's width; B, C,
    dB, dC; the state kept and the lane-narrow scalars), the state's
    gradient, and room for a step's values: the group's float32 stacks and a
    head's [Q, Q] blocks."""
    wide = chunk * group_width
    blocks = 2 * (3 * wide * itemsize + 4 * chunk * d_state * itemsize
                  + group_width * d_state * itemsize
                  + 2 * chunk * LANES * 4 + 2 * 8 * chunk * 4)
    values = 10 * wide * 4 + 12 * chunk * chunk * 4 \
        + 4 * group_width * d_state * 4
    return blocks + group_width * d_state * 4 + values + (4 << 20)


def kept_state_bytes(batch, seq, chunk, d_inner, d_state, itemsize):
    """Bytes of the states one call's forward keeps for its backward."""
    return batch * (seq // chunk) * d_inner * d_state * itemsize


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _decays(a_col, a_row):
    """``exp(a_i - a_j)`` for j <= i, else 0: [Q, Q]."""
    Q = a_col.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.where(j <= i, jnp.exp(jnp.minimum(a_col - a_row, 0.0)), 0.0)


def _by_lane(cols, P):
    """The heads' per-token columns ``cols`` (a [Q, 1] each) as wide as
    their channels, [Q, heads * P]: a lane tile at a time, a broadcast of
    its first head's column and a select for each further head it holds
    (one, at P = 64)."""
    Q = cols[0].shape[0]
    if P % LANES == 0:
        return _cat([jnp.broadcast_to(c, (Q, P)) for c in cols])
    per_tile = LANES // P
    head = jax.lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) // P
    tiles = []
    for first in range(0, len(cols), per_tile):
        tile = jnp.broadcast_to(cols[first], (Q, LANES))
        for i in range(1, per_tile):
            tile = jnp.where(head == i, cols[first + i], tile)
        tiles.append(tile)
    return _cat(tiles)


def _scalars(cols_ref, arow_ref, per):
    """Of the heads of this step's group: ``a`` and ``dt`` as columns [Q, 1]
    a head, ``a`` as a row [1, Q] a head, and ``a`` at the chunk's last
    token [1, 1] a head."""
    Q = arow_ref.shape[1]
    rows = [arow_ref[h:h + 1, :] for h in range(per)]
    # the last token's by a masked sum over the lanes: a reduction's result
    # broadcasts over a [P, N] block, a one-lane slice of a row does not
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    return ([cols_ref[:, h:h + 1] for h in range(per)],
            [cols_ref[:, per + h:per + h + 1] for h in range(per)], rows,
            [jnp.sum(jnp.where(last, row, 0.0), axis=1, keepdims=True)
             for row in rows])


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, arow_ref, skip_ref, y_ref,
                *rest, per, save):
    if save:
        kept_ref, h_ref = rest
    else:
        (h_ref,) = rest
    dt = x_ref.dtype
    P = x_ref.shape[1] // per

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    state = h_ref[...].astype(dt)                   # [per * P, N]
    if save:
        kept_ref[...] = state
    bm, cm = b_ref[...], c_ref[...]
    a_cols, dt_cols, a_rows, a_lasts = _scalars(cols_ref, arow_ref, per)
    a_wide = _by_lane(a_cols, P)                    # [Q, per * P]
    x = x_ref[...].astype(_F32)
    xd = _by_lane(dt_cols, P) * x
    xd_b = xd.astype(dt)
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=_F32)
    read = jax.lax.dot_general(cm, state, _NT, preferred_element_type=_F32)
    within = _cat([
        jnp.dot((cb * _decays(a_cols[h], a_rows[h])).astype(dt),
                xd_b[:, h * P:(h + 1) * P], preferred_element_type=_F32)
        for h in range(per)])
    y_ref[...] = (within + jnp.exp(a_wide) * read
                  + skip_ref[...] * x).astype(y_ref.dtype)
    a_last = a_wide[a_wide.shape[0] - 1:, :]                    # [1, per * P]
    fold = jax.lax.dot_general(
        (jnp.exp(a_last - a_wide) * xd).astype(dt), bm, _TN,
        preferred_element_type=_F32)                            # [per * P, N]
    for h in range(per):
        at = slice(h * P, (h + 1) * P)
        h_ref[at, :] = jnp.exp(a_lasts[h]) * h_ref[at, :] + fold[at, :]


def _bwd_kernel(x_ref, b_ref, c_ref, dy_ref, cols_ref, arow_ref, skip_ref,
                sums_ref, kept_ref, dx_ref, db_ref, dc_ref, dcols_ref,
                darow_ref, dskip_ref, dh_ref, *, per):
    dt = x_ref.dtype
    Q = x_ref.shape[0]
    P = x_ref.shape[1] // per

    @pl.when(pl.program_id(2) == 0)                 # the LAST chunk
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    bm, cm = b_ref[...], c_ref[...]
    state = kept_ref[...]                           # the state the chunk found
    dstate = dh_ref[...]                            # d (the state it left)
    dstate_b = dstate.astype(dt)
    a_cols, dt_cols, a_rows, a_lasts = _scalars(cols_ref, arow_ref, per)
    a_wide, dt_wide = _by_lane(a_cols, P), _by_lane(dt_cols, P)
    x, dy = x_ref[...].astype(_F32), dy_ref[...].astype(_F32)
    skip = skip_ref[...]
    xd = dt_wide * x
    xd_b, dy_b = xd.astype(dt), dy_ref[...]
    ea = jnp.exp(a_wide)
    e_fold = jnp.exp(a_wide[Q - 1:, :] - a_wide)
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=_F32)
    read = jax.lax.dot_general(cm, state, _NT, preferred_element_type=_F32)
    # B_j . d H1[p, :] for every stacked state row p: [Q, per * P]
    bdh = jax.lax.dot_general(bm, dstate_b, _NT, preferred_element_type=_F32)
    carried = dstate * state.astype(_F32)           # <d H1, H0>, by element
    pairs = jnp.zeros((Q, Q), _F32)                 # sum over heads of G o L
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, LANES), 1)
    moved_rows = jnp.zeros((Q, LANES), _F32)        # lane h: head h's
    kept_last = jnp.zeros((1, LANES), _F32)
    dxd = []
    for h in range(per):
        at = slice(h * P, (h + 1) * P)
        decays = _decays(a_cols[h], a_rows[h])
        # d S_ij = dy_i . (dt x)_j; S = (C B^T) o L
        g = jax.lax.dot_general(dy_b[:, at], xd_b[:, at], _NT,
                                preferred_element_type=_F32) * decays
        pairs = pairs + g
        moved = g * cb                              # d L o L
        moved_rows = jnp.where(lane == h, jnp.sum(moved, axis=1,
                                                  keepdims=True), moved_rows)
        darow_ref[h:h + 1, :] = -jnp.sum(moved, axis=0, keepdims=True)
        dxd.append(jax.lax.dot_general((cb * decays).astype(dt), dy_b[:, at],
                                       _TN, preferred_element_type=_F32))
        kept_last = jnp.where(
            lane[:1] == h, jnp.exp(a_lasts[h]) * jnp.sum(
                jnp.sum(carried[at, :], axis=1, keepdims=True), axis=0,
                keepdims=True), kept_last)
    dxd = _cat(dxd) + e_fold * bdh
    dx_ref[...] = (dt_wide * dxd + skip * dy).astype(dx_ref.dtype)
    dskip_ref[...] += jnp.sum(dy * x, axis=0, keepdims=True)
    # the sums over a head's channels, by the MXU: lane h (of 3 x per) of
    # ``sums`` adds up head h's lanes of the first, the second or the third
    # of the products set side by side
    folded_x = e_fold * xd
    summed = jnp.dot(_cat([ea * dy * read, dxd * x, folded_x * bdh])
                     .astype(dt), sums_ref[...],
                     preferred_element_type=_F32)               # [Q, 128]
    # lanes [0, per): a's gradient, a column a head; [per, 2 per): dt's
    folded = pltpu.roll(summed, LANES - 2 * per, 1)     # lanes [0, per)
    token = jax.lax.broadcasted_iota(jnp.int32, (Q, LANES), 0)
    last = jnp.sum(folded, axis=0, keepdims=True) + kept_last
    out = summed + jnp.where(
        lane < per, moved_rows - folded + jnp.where(token == Q - 1, last,
                                                    0.0), 0.0)
    dcols_ref[...] = out[:, :2 * per]
    edy, pairs_b = (ea * dy).astype(dt), pairs.astype(dt)
    dc_ref[...] = (jnp.dot(pairs_b, bm, preferred_element_type=_F32)
                   + jnp.dot(edy, state, preferred_element_type=_F32)
                   ).astype(dc_ref.dtype)
    db_ref[...] = (jax.lax.dot_general(pairs_b, cm, _TN,
                                       preferred_element_type=_F32)
                   + jnp.dot(folded_x.astype(dt), dstate_b,
                             preferred_element_type=_F32)
                   ).astype(db_ref.dtype)
    dfound = jax.lax.dot_general(edy, cm, _TN, preferred_element_type=_F32)
    for h in range(per):
        at = slice(h * P, (h + 1) * P)
        dh_ref[at, :] = jnp.exp(a_lasts[h]) * dstate[at, :] + dfound[at, :]


class _Geom:
    """The shapes of one call and its block specs; ``flip`` walks the chunks
    from the end."""

    def __init__(self, xbc, heads, groups, d_state, chunk, flip=False):
        self.B, self.S, W = xbc.shape
        assert supported(xbc.shape, heads, groups, d_state, chunk), \
            (xbc.shape, heads, groups, d_state, chunk)
        self.heads, self.G, self.N, self.Q = heads, groups, d_state, chunk
        self.d = d = W - 2 * groups * d_state
        self.per, self.wide, self.nc = heads // groups, d // groups, \
            self.S // chunk
        nc, N, G, Q, wide, per = self.nc, d_state, groups, chunk, self.wide, \
            self.per
        at = (lambda c: nc - 1 - c) if flip else (lambda c: c)
        self.x = pl.BlockSpec((None, Q, wide), lambda b, g, c: (b, at(c), g))
        self.bmat = pl.BlockSpec((None, Q, N),
                                 lambda b, g, c: (b, at(c), d // N + g))
        self.cmat = pl.BlockSpec((None, Q, N),
                                 lambda b, g, c: (b, at(c), d // N + G + g))
        self.group = pl.BlockSpec((None, Q, N), lambda b, g, c: (b, at(c), g))
        self.cols = pl.BlockSpec((None, None, Q, 2 * per),
                                 lambda b, g, c: (b, g, at(c), 0))
        self.arow = pl.BlockSpec((None, None, per, Q),
                                 lambda b, g, c: (b, at(c), g, 0))
        self.skip = pl.BlockSpec((1, wide), lambda b, g, c: (0, g))
        self.kept = pl.BlockSpec((None, None, None, wide, N),
                                 lambda b, g, c: (b, at(c), g, 0, 0))
        self.sums = pl.BlockSpec((3 * wide, LANES), lambda b, g, c: (0, 0))
        self.dskip = pl.BlockSpec((None, 1, wide), lambda b, g, c: (b, 0, g))
        self.grid = (self.B, G, nc)
        self.params = _CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(Q, wide, N, xbc.dtype.itemsize))

    def scalars(self, cols):
        """``cols`` [B, S, (a | dt) x heads] as the kernels read it: a
        group's columns side by side, [B, G, S, (a | dt) x its heads], and
        ``a`` again a row a head and chunk, [B, chunks, heads, Q]."""
        B, S, G, per = self.B, self.S, self.G, self.per
        return (cols.reshape(B, S, 2, G, per).transpose(0, 3, 1, 2, 4)
                .reshape(B, G, S, 2 * per),
                cols[..., :self.heads].reshape(
                    B, self.nc, self.Q, self.heads).swapaxes(2, 3))

    def sums_of_heads(self, dtype):
        """[3 x a group's channels, 128] of 0 and 1: lane ``i * heads a
        group + h`` adds up head h's channels of the i-th of three products
        set side by side."""
        row = jnp.arange(3 * self.wide)
        lane = row // self.wide * self.per \
            + row % self.wide // (self.wide // self.per)
        return (lane[:, None] == jnp.arange(LANES)[None]).astype(dtype)


def _fwd(xbc, cols, skip, static, save):
    heads, groups, d_state, chunk, interpret = static
    geom = _Geom(xbc, heads, groups, d_state, chunk)
    dt = xbc.dtype
    out_specs = [geom.x]
    out_shape = [jax.ShapeDtypeStruct((geom.B, geom.S, geom.d), dt)]
    if save:
        out_specs.append(geom.kept)
        out_shape.append(jax.ShapeDtypeStruct(
            (geom.B, geom.nc, geom.G, geom.wide, geom.N), dt))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=geom.per, save=save),
        grid=geom.grid,
        in_specs=[geom.x, geom.bmat, geom.cmat, geom.cols, geom.arow,
                  geom.skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((geom.wide, geom.N), _F32)],
        compiler_params=geom.params, interpret=interpret,
        name="ssd_scan_fwd",
    )(xbc, xbc, xbc, *geom.scalars(cols), skip)


def _bwd(static, res, dy):
    heads, groups, d_state, chunk, interpret = static
    xbc, cols, skip, kept = res
    geom = _Geom(xbc, heads, groups, d_state, chunk, flip=True)
    B, S, G, N, per = geom.B, geom.S, geom.G, geom.N, geom.per
    dt = xbc.dtype
    dx, db, dc, dcols, darow, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, per=per),
        grid=geom.grid,
        in_specs=[geom.x, geom.bmat, geom.cmat, geom.x, geom.cols, geom.arow,
                  geom.skip, geom.sums, geom.kept],
        out_specs=[geom.x, geom.group, geom.group, geom.cols, geom.arow,
                   geom.dskip],
        out_shape=[jax.ShapeDtypeStruct((B, S, geom.d), dt),
                   jax.ShapeDtypeStruct((B, S, G * N), dt),
                   jax.ShapeDtypeStruct((B, S, G * N), dt),
                   jax.ShapeDtypeStruct((B, G, S, 2 * per), _F32),
                   jax.ShapeDtypeStruct((B, geom.nc, heads, chunk), _F32),
                   jax.ShapeDtypeStruct((B, 1, geom.d), _F32)],
        scratch_shapes=[pltpu.VMEM((geom.wide, N), _F32)],
        compiler_params=geom.params, interpret=interpret,
        name="ssd_scan_bwd",
    )(xbc, xbc, xbc, dy, *geom.scalars(cols), skip, geom.sums_of_heads(dt),
      kept)
    # [B, G, S, (a's columns | dt's)] -> [B, S, heads] each; a's rows added
    dcols = dcols.reshape(B, G, S, 2, per).transpose(0, 2, 3, 1, 4).reshape(
        B, S, 2, heads)
    da = dcols[:, :, 0] + darow.swapaxes(2, 3).reshape(B, S, heads)
    dxbc = jnp.concatenate([dx, db, dc], axis=-1)
    return (jnp.pad(dxbc, ((0, 0), (0, 0), (0, xbc.shape[-1]
                                             - dxbc.shape[-1]))),
            jnp.concatenate([da, dcols[:, :, 1]], axis=-1),
            jnp.sum(dskip, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scan(xbc, cols, skip, static):
    return _fwd(xbc, cols, skip, static, False)[0]


def _scan_fwd(xbc, cols, skip, static):
    y, kept = _fwd(xbc, cols, skip, static, True)
    return y, (xbc, cols, skip, kept)


_scan.defvjp(_scan_fwd, _bwd)


def _running(dt, a, chunk):
    """``a``: the running sum of ``dt * A`` inside each chunk, its own
    token's included, [b, S, heads] float32."""
    b, S, heads = dt.shape
    return jnp.cumsum((dt * a).reshape(b, S // chunk, chunk, heads),
                      axis=2).reshape(b, S, heads)


def ssd_scan(xbc, dt, a, d_skip, *, heads, groups, d_state, chunk=128,
             interpret=None):
    """``y`` [b, S, d] of the filter's output ``xbc`` [b, S, d + 2 G N] (x in
    the first d = heads x P lanes, then each group's B [N], then each group's
    C), step sizes ``dt`` [b, S, heads] float32 (> 0), rates ``a`` [heads]
    float32 (< 0) and skips ``d_skip`` [heads], by the chunked dual form in
    chunks of ``chunk`` tokens.  ``supported`` must hold.  Differentiable in
    all four; the kernels' operands take xbc's dtype, the state is
    float32."""
    assert supported(xbc.shape, heads, groups, d_state, chunk), \
        (xbc.shape, heads, groups, d_state, chunk)
    if interpret is None:
        interpret = not _on_tpu()
    P = (xbc.shape[-1] - 2 * groups * d_state) // heads
    dt = dt.astype(_F32)
    cols = jnp.concatenate([_running(dt, a.astype(_F32), chunk), dt], axis=-1)
    skip = jnp.repeat(d_skip.astype(_F32), P)[None]              # [1, d]
    return _scan(xbc, cols, skip, (int(heads), int(groups), int(d_state),
                                   int(chunk), bool(interpret)))


def ssd_scan_chunked(xbc, dt, a, d_skip, *, heads, groups, d_state,
                     chunk=128):
    """The same in ``jnp``: the chunked dual form with a ``lax.scan`` over
    the chunks, float32 inside, rounded once to xbc's dtype."""
    b, S, W = xbc.shape
    G, N = groups, d_state
    d = W - 2 * G * N
    P, per, nc, Q = d // heads, heads // groups, S // chunk, chunk
    dt = dt.astype(_F32)
    x = xbc[..., :d].astype(_F32).reshape(b, nc, Q, heads, P)
    bm = xbc[..., d:d + G * N].astype(_F32).reshape(b, nc, Q, G, N)
    cm = xbc[..., d + G * N:].astype(_F32).reshape(b, nc, Q, G, N)
    acc = _running(dt, a.astype(_F32), chunk).reshape(b, nc, Q, heads)
    xd = dt.reshape(b, nc, Q, heads, 1) * x
    bh, ch = (jnp.repeat(t, per, axis=3) for t in (bm, cm))      # by head
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def one_chunk(state, turn):
        x_c, xd_c, b_c, c_c, a_c = turn             # [b, Q, heads, ...]
        decays = jnp.where(
            tri[None, :, :, None],
            jnp.exp(jnp.minimum(a_c[:, :, None] - a_c[:, None, :], 0.0)), 0.0)
        scores = jnp.einsum("bihn,bjhn->bijh", c_c, b_c) * decays
        y = jnp.einsum("bijh,bjhp->bihp", scores, xd_c) \
            + jnp.exp(a_c)[..., None] * jnp.einsum("bihn,bhpn->bihp", c_c,
                                                   state)
        last = a_c[:, -1]                           # [b, heads]
        fold = jnp.exp(last[:, None] - a_c)[..., None] * xd_c
        state = jnp.exp(last)[..., None, None] * state \
            + jnp.einsum("bjhp,bjhn->bhpn", fold, b_c)
        return state, y + d_skip.astype(_F32)[:, None] * x_c

    turns = tuple(t.swapaxes(0, 1) for t in (x, xd, bh, ch, acc))
    _, y = jax.lax.scan(one_chunk, jnp.zeros((b, heads, P, N), _F32), turns)
    return y.swapaxes(0, 1).reshape(b, S, d).astype(xbc.dtype)
