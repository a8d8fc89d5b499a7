"""Power retention of degree 2 (Buckman, Gelada et al., "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239) as Pallas TPU kernels that
carry a STATE from chunk to chunk, forward and backward.

With log-decays ``g_t <= 0`` and ``G_t = sum_{l <= t} g_l`` the operator is

    a_tj = (scale * q_t . k_j)^2 * exp(G_t - G_j)        for j <= t
    o_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

(no softmax, no running maximum: every weight is non-negative).  The same as
a recurrence, with ``phi(x)`` the products ``x_a x_b`` so that
``phi(q) . phi(k) = (q . k)^2``:

    S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T,   Z_t = e^{g_t} Z_{t-1} + k_t k_t^T
    o_t = phi(q_t)^T S_t / (q_t^T Z_t q_t + eps)

and, in chunks of ``c`` tokens, the first form on the chunk's own ``c x c``
block plus a read of the state as the chunk found it, decayed by the
chunk's running sum.  That is what runs here: nothing of size tokens x
phi's width reaches HBM, one [rows, 128] tile of phi lives in VMEM at a time.

THE STATE'S LAYOUT.  A head is 128 wide (one lane tile).  ``phi`` is laid
out by wrapped diagonals, 65 tiles of 128 lanes = 8,320 columns for the
8,256 distinct products: tile d holds ``x_b * x_{(b - d) mod 128}``, which is
``x * roll(x, d)``, one lane rotation and one multiply a tile.  Diagonal 0
is the squares; diagonals 1..63 hold every unordered pair once, so they
weigh 2 (the sqrt 2 of the symmetric-reduced expansion, squared and put on
the key side alone); diagonal 64 holds every pair ``{b, b + 64}`` twice and
weighs 1.  S is [65, 128, 128] float32 a key/value head (4.26 MB), its MXU
operand a bf16 copy.  The normaliser is kept unreduced, as the 128 x 128
second moment Z (``phi(q) . z = q^T Z q``): one small float32 matmul a
chunk, where the reduced ``z`` would cost a multiply-add per phi element.

Grid (batch, key/value head, chunk), the last sequential.  ONE grid step
serves a whole key/value group: its ``G`` query heads are stacked along
rows ([G * c, 128]; a row block of every head after another, so that the
in-chunk block's rows are contiguous), and every loop over the 65 tiles
runs once a chunk for all of them.  A forward step saves the state it
found, reads it for the stacked rows (5 tiles of phi side by side in one
product, so that their sum happens in the matmul: 13 loop iterations), adds
the chunk's own block (256 query rows of every head at a time against the
keys at or before them, ONE block of decays for the group), writes the
heads' outputs and normalisers, then folds the chunk's keys and values in
(5 tiles' phi(k) side by side against the one decayed v).  The backward
walks the chunks in REVERSE with the state's gradient in VMEM and reads
the states the forward saved ([B, Hkv, S/c, 65, 128, 128] float32:
recomputing them would need the same buffer, the recurrence cannot be run
backwards through a decay of 2^-1000 a chunk): a step takes the fold's
backward (5 tiles at a time), then the read's backward for the stacked
rows a tile at a time (one transposed product over G * c rows into the
tile's gradient), then the in-chunk block's.  What a step holds in VMEM
follows from the shapes (``_step_vmem_bytes``): a group whose stacked rows
would pass ``VMEM_LIMIT`` goes in the largest divisor of its heads that
fits, on a fourth grid axis: the first of a chunk's steps saves the state
or takes the fold's backward, the last folds or writes dk and dv
(``sweep_heads``; at the Brumby cell's shape five heads ride a step at
chunks of 1,024 and one at 2,048; ``state_sweeps`` is what a trainer's
gauge reports).

Kernel names in a trace: ``power_retention_fwd``, ``power_retention_bwd``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["power_retention", "supported", "sweep_heads", "state_sweeps",
           "LANES", "DIAGONALS", "STATE_COLUMNS", "EPS"]

LANES = 128                     # the head width the kernels are written for
DIAGONALS = LANES // 2 + 1      # tiles of phi
STATE_COLUMNS = DIAGONALS * LANES
EPS = 1e-6
ROW_BLOCK = 256                 # query rows of the in-chunk block at a time
# tiles of phi set side by side in ONE product where a sweep's sum over the
# tiles can happen in the matmul (the read: [rows, 5 * 128] x [5 * 128,
# 128]) or its few rows can share a latched operand (the fold and its
# backward: 5 * 128 rows where 128 stood): 13 loop iterations of 5
TILES = 5
assert DIAGONALS % TILES == 0
VMEM_LIMIT = 100 * 1024 * 1024
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def supported(head_dim, seq, chunk):
    """Whether the kernels take this shape: heads one lane tile wide, the
    sequence whole chunks of whole sublane tiles."""
    return head_dim == LANES and chunk % 8 == 0 and seq % chunk == 0


def _weight(d):
    """What diagonal ``d`` of phi weighs on the key side."""
    return jnp.where((d == 0) | (d == LANES // 2), 1.0, 2.0).astype(_F32)


def _unroll_back(x, d):
    """``roll(x, -d)`` along the lanes."""
    return pltpu.roll(x, jnp.where(d == 0, 0, LANES - d), 1)


def _column(x, at):
    """Column ``at`` (a traced index) of a lane-narrow block x [c, n], as
    [c, 1]: a per-token scalar rides HBM beside its head's others, because a
    trailing axis of 1 would be padded to a whole lane tile there."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == at, x, 0.0), axis=1, keepdims=True)


def _decays(bcol, brow_ref, r0, hi):
    """The masked decays ``exp(b_t - b_j)``, ``j <= t``, of rows [r0, hi) of
    the chunk against its keys [0, hi): one block for all the heads of a
    group, whose log-decay is their key/value head's."""
    t = r0 + jax.lax.broadcasted_iota(jnp.int32, (hi - r0, hi), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (hi - r0, hi), 1)
    return jnp.where(j <= t, jnp.exp(jnp.minimum(
        bcol[r0:hi] - brow_ref[:, :hi], 0.0)), 0.0)


def _head(g):
    """The lanes of query head ``g`` in a group's [c, G * 128] block."""
    return slice(g * LANES, (g + 1) * LANES)


def _stacked(parts):
    """Blocks one under another: the heads' rows of one row block."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _each_head(x, heads, f):
    """``f`` on each head's rows of a stacked block [heads * rows, n]."""
    rows = x.shape[0] // heads
    return _stacked([f(x[g * rows:(g + 1) * rows]) for g in range(heads)])


def _into_lanes(base, cols, at):
    """Per-token columns [rows, 1] into lanes ``at``, ``at + 1``, ... of a
    lane-narrow block ``base`` [rows, n]: how a group's scalars leave."""
    lane = jax.lax.broadcasted_iota(jnp.int32, base.shape, 1)
    for g, col in enumerate(cols):
        base = jnp.where(lane == at + g, col, base)
    return base


def _step(parts):
    """Where a grid step stands among the ``parts`` a group is swept in:
    (its index, whether it is the first, whether the last).  With the group
    whole the grid has no such axis and both hold."""
    if parts == 1:
        return 0, True, True
    sub = pl.program_id(3)
    return sub, sub == 0, sub == parts - 1


def _sweep(tiles):
    """``tiles(d0)`` for the 65 diagonals, ``TILES`` at a time from d0."""
    def body(j, carry):
        tiles(j * TILES)
        return carry

    jax.lax.fori_loop(0, DIAGONALS // TILES, body, 0)


def _beside(parts):
    """Tiles side by side along the lanes: one wider matmul operand."""
    return jnp.concatenate(parts, axis=1)


def _when(cond):
    """``pl.when`` that also takes a Python ``True``."""
    return (lambda f: f()) if cond is True else pl.when(cond)


def _fwd_kernel(q_ref, k_ref, v_ref, bseq_ref, brow_ref, o_ref, den_ref,
                *rest, scale, eps, heads, parts, rows, save):
    if save:
        s_out, z_out, s_ref, sb_ref, z_ref, xq_ref, acc_ref = rest
    else:
        s_ref, sb_ref, z_ref, xq_ref, acc_ref = rest
    n = pl.program_id(2)
    sub, first, last = _step(parts)
    c = k_ref.shape[0]
    dt = q_ref.dtype
    span = heads * rows             # the stacked rows of one row block
    bcol = _column(bseq_ref[...], pl.program_id(1))             # [c, 1]

    @_when(first)
    def _():
        @pl.when(n == 0)
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)
            sb_ref[...] = jnp.zeros_like(sb_ref)
            z_ref[...] = jnp.zeros_like(z_ref)

        if save:
            s_out[...] = s_ref[...]
            z_out[...] = z_ref[...]

    # the heads' scaled queries stacked along rows, one row block of every
    # head after another: block r is rows [r * span, (r + 1) * span)
    for r in range(c // rows):
        for g in range(heads):
            xq_ref[r * span + g * rows:r * span + (g + 1) * rows, :] = \
                q_ref[r * rows:(r + 1) * rows, _head(g)].astype(_F32) * scale

    # the state as the chunk found it, a tile of phi(q) at a time: ONE sweep
    # of the 65 tiles for all the stacked heads
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def read(d0):
        xq = xq_ref[...]
        phi = _beside([(xq * pltpu.roll(xq, d0 + i, 1)).astype(dt)
                       for i in range(TILES)])
        acc_ref[...] += jnp.dot(
            phi, sb_ref[pl.ds(d0, TILES)].reshape(TILES * LANES, LANES),
            preferred_element_type=_F32)

    _sweep(read)

    # the chunk's own block, a few hundred query rows of every head at a
    # time against the keys at or before them, and what leaves
    e = jnp.exp(bcol)
    for r in range(c // rows):
        r0, hi, blk = r * rows, (r + 1) * rows, slice(r * span, (r + 1) * span)
        qb = _stacked([q_ref[r0:hi, _head(g)] for g in range(heads)])
        sc = jax.lax.dot_general(qb, k_ref[:hi, :], _NT,
                                 preferred_element_type=_F32) * scale
        dec = _decays(bcol, brow_ref, r0, hi)
        w = _each_head(sc * sc, heads, lambda x: x * dec)
        num = jnp.dot(w.astype(dt), v_ref[:hi, :],
                      preferred_element_type=_F32)
        xq = xq_ref[blk, :]
        zq = jnp.dot(xq, z_ref[...], preferred_element_type=_F32,
                     precision=_HIGHEST)
        eb = _stacked([e[r0:hi]] * heads)
        den = jnp.sum(w, axis=1, keepdims=True) \
            + eb * jnp.sum(zq * xq, axis=1, keepdims=True) + eps
        o = ((num + eb * acc_ref[blk, :]) / den).astype(o_ref.dtype)
        for g in range(heads):
            o_ref[r0:hi, _head(g)] = o[g * rows:(g + 1) * rows]
        den_ref[r0:hi, :] = _into_lanes(
            jnp.zeros((rows, den_ref.shape[1]), _F32) if parts == 1
            else den_ref[r0:hi, :],
            [den[g * rows:(g + 1) * rows] for g in range(heads)],
            sub * heads)

    @_when(last)
    def _():
        kf = k_ref[...].astype(_F32)
        last_b = brow_ref[:, c - 1:c]                           # [1, 1]
        wk = jnp.exp(last_b - bcol)                             # [c, 1]
        decay = jnp.exp(last_b)
        vw = (wk * v_ref[...].astype(_F32)).astype(dt)

        def fold(d0):
            phi = _beside([(kf * pltpu.roll(kf, d0 + i, 1)).astype(dt)
                           for i in range(TILES)])
            u = jax.lax.dot_general(phi, vw, _TN,
                                    preferred_element_type=_F32)
            for i in range(TILES):
                d = d0 + i
                new = decay * s_ref[d] \
                    + _weight(d) * u[i * LANES:(i + 1) * LANES]
                s_ref[d] = new
                sb_ref[d] = new.astype(dt)

        _sweep(fold)
        z_ref[...] = decay * z_ref[...] + jax.lax.dot_general(
            kf, wk * kf, _TN, preferred_element_type=_F32,
            precision=_HIGHEST)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, bseq_ref, brow_ref, stats_ref,
                p_ref, zn_ref, dq_ref, dk_ref, dv_ref, dbrow_ref, dgam_ref,
                ds_ref, dz_ref, dk_acc, dv_acc, dvp_acc, dot_acc, dbrow_acc,
                xq_ref, dnb_ref, dneb_ref, dx_acc, qs_acc,
                *, scale, group, heads, parts, rows):
    n = pl.program_id(2)                            # n counts from the END
    sub, first, last = _step(parts)
    c = k_ref.shape[0]
    dt = q_ref.dtype
    span = heads * rows
    last_b = brow_ref[:, c - 1:c]                               # [1, 1]
    decay = jnp.exp(last_b)
    bcol = _column(bseq_ref[...], pl.program_id(1))             # [c, 1]
    e = jnp.exp(bcol)

    @_when(first)
    def _():
        @pl.when(n == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)
            dz_ref[...] = jnp.zeros_like(dz_ref)

        # the fold's backward: ds_ref is the gradient of the state the
        # chunk LEFT; the chunk's keys and values take theirs, then it
        # decays into the gradient of the state the chunk found
        kf = k_ref[...].astype(_F32)
        wk = jnp.exp(last_b - bcol)                             # [c, 1]
        vw = wk * v_ref[...].astype(_F32)
        vwb = vw.astype(dt)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dvp_acc[...] = jnp.zeros_like(dvp_acc)
        dot_acc[...] = jnp.zeros_like(dot_acc)

        def unfold(d0):
            krs = [pltpu.roll(kf, d0 + i, 1) for i in range(TILES)]
            dsds = [ds_ref[d0 + i] for i in range(TILES)]
            dsb = _stacked([(_weight(d0 + i) * dsd).astype(dt)
                            for i, dsd in enumerate(dsds)])
            dvp_acc[...] += jnp.dot(
                _beside([(kf * kr).astype(dt) for kr in krs]), dsb,
                preferred_element_type=_F32)
            m = jax.lax.dot_general(vwb, dsb, _NT,
                                    preferred_element_type=_F32)
            ms = [m[:, i * LANES:(i + 1) * LANES] for i in range(TILES)]
            dk_acc[...] += sum(mi * kr + _unroll_back(mi * kf, d0 + i)
                               for i, (mi, kr) in enumerate(zip(ms, krs)))
            dot_acc[...] += sum(dsd * p_ref[d0 + i]
                                for i, dsd in enumerate(dsds))
            for i, dsd in enumerate(dsds):
                ds_ref[d0 + i] = decay * dsd

        _sweep(unfold)
        dz = dz_ref[...]
        zk = jnp.dot(kf, dz, preferred_element_type=_F32, precision=_HIGHEST)
        dvp = dvp_acc[...]
        # d(term_j) / d(wk_j), times wk_j: the gate's gradient on the key
        # side, against b_j and for the chunk's last b; summed over the
        # lanes by the MXU, which hands it over as a ROW
        moved = jax.lax.dot_general(
            jnp.ones((8, LANES), _F32), dvp * vw + wk * zk * kf, _NT,
            preferred_element_type=_F32, precision=_HIGHEST)[:1]   # [1, c]
        dk_acc[...] += 2.0 * wk * zk
        dv_acc[...] = wk * dvp
        carried = jnp.sum(jnp.sum(dot_acc[...], axis=1, keepdims=True),
                          axis=0, keepdims=True) \
            + jnp.sum(jnp.sum(dz * zn_ref[...], axis=1, keepdims=True),
                      axis=0, keepdims=True)                    # [1, 1]
        dgam_ref[...] = jnp.broadcast_to(
            decay * carried + jnp.sum(moved, axis=1, keepdims=True),
            dgam_ref.shape)
        dz_ref[...] = decay * dz
        dbrow_acc[...] = -moved

    # the heads' rows stacked as the forward stacks them: the scaled
    # queries, d num and d num under the decay the state was read with
    stats = stats_ref[...]                          # 1 / den, then d den
    for g in range(heads):
        rden = _column(stats, sub * heads + g)                  # [c, 1]
        for r in range(c // rows):
            r0, hi = r * rows, (r + 1) * rows
            to = slice(r * span + g * rows, r * span + (g + 1) * rows)
            xq_ref[to, :] = q_ref[r0:hi, _head(g)].astype(_F32) * scale
            dn = do_ref[r0:hi, _head(g)].astype(_F32) * rden[r0:hi]
            dnb_ref[to, :] = dn.astype(dt)
            dneb_ref[to, :] = (e[r0:hi] * dn).astype(dt)
    dds = [_column(stats, group + sub * heads + g) for g in range(heads)]

    # the read's backward, ONE sweep of the tiles for the stacked heads:
    # phi(q)'s gradient back through the products, and the heads' part of
    # the state's gradient.
    # b's gradient, QUERY side: every weight of row t carries e^{b_t}, so in
    # exact arithmetic the row's terms add up to eps * (do . o) / den, next
    # to nothing; they are summed here all the same, product by product as
    # the key side subtracts them, because the log-decay's gradient is the
    # running sum of (query side - key side) over a chunk and only equal
    # roundings cancel in it (qs_acc's lanes add up to the row's sum)
    dx_acc[...] = jnp.zeros_like(dx_acc)
    qs_acc[...] = jnp.zeros_like(qs_acc)

    def unread(d, carry):
        xq = xq_ref[...]
        xr = pltpu.roll(xq, d, 1)
        phi = xq * xr
        dneb = dneb_ref[...]
        m = jax.lax.dot_general(dneb, p_ref[d].astype(dt), _NT,
                                preferred_element_type=_F32)
        dx_acc[...] += m * xr + _unroll_back(m * xq, d)
        qs_acc[...] += phi * m
        ds_ref[d] += jax.lax.dot_general(phi.astype(dt), dneb, _TN,
                                         preferred_element_type=_F32)
        return carry

    jax.lax.fori_loop(0, DIAGONALS, unread, 0)

    # the chunk's own block and what leaves, a row block of every head at
    # a time
    for r in range(c // rows):
        r0, hi, blk = r * rows, (r + 1) * rows, slice(r * span, (r + 1) * span)
        qb = _stacked([q_ref[r0:hi, _head(g)] for g in range(heads)])
        sc = jax.lax.dot_general(qb, k_ref[:hi, :], _NT,
                                 preferred_element_type=_F32) * scale
        dec = _decays(bcol, brow_ref, r0, hi)
        w = _each_head(sc * sc, heads, lambda x: x * dec)
        dnb = dnb_ref[blk, :]
        dd = _stacked([col[r0:hi] for col in dds])              # [span, 1]
        dw = jax.lax.dot_general(dnb, v_ref[:hi, :], _NT,
                                 preferred_element_type=_F32) + dd
        dww = dw * w
        dsc = _each_head(dw * (2.0 * scale) * sc, heads,
                         lambda x: x * dec).astype(dt)
        dq = jnp.dot(dsc, k_ref[:hi, :], preferred_element_type=_F32)
        dk_acc[:hi, :] += jax.lax.dot_general(
            dsc, qb, _TN, preferred_element_type=_F32)
        dv_acc[:hi, :] += jax.lax.dot_general(
            w.astype(dt), dnb, _TN, preferred_element_type=_F32)
        dbrow_acc[:, :hi] -= jnp.sum(dww, axis=0, keepdims=True)
        xq = xq_ref[blk, :]
        cz = dd * _stacked([e[r0:hi]] * heads)                  # [span, 1]
        zq = jnp.dot(xq, zn_ref[...], preferred_element_type=_F32,
                     precision=_HIGHEST)
        dz_ref[...] += jax.lax.dot_general(cz * xq, xq, _TN,
                                           preferred_element_type=_F32,
                                           precision=_HIGHEST)
        dq = dq + scale * (dx_acc[blk, :] + 2.0 * cz * zq)
        for g in range(heads):
            dq_ref[r0:hi, _head(g)] = dq[g * rows:(g + 1) * rows].astype(
                dq_ref.dtype)
        qs = qs_acc[blk, :] + jnp.sum(dww, axis=1, keepdims=True) \
            * (1.0 / LANES) + cz * zq * xq
        moved = jax.lax.dot_general(
            jnp.ones((8, LANES), _F32), qs, _NT,
            preferred_element_type=_F32, precision=_HIGHEST)[:1]  # [1, span]
        dbrow_acc[:, r0:hi] += sum(moved[:, g * rows:(g + 1) * rows]
                                   for g in range(heads))

    @_when(last)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dbrow_ref[...] = dbrow_acc[...]


def _step_vmem_bytes(heads, chunk, itemsize):
    """What a grid step of the BACKWARD (the larger of the two) holds in
    VMEM with ``heads`` query heads stacked: an estimate from the shapes
    that has to stay over the compiler's count wherever it lets a step
    through.  The state three times (its block twice, its gradient); for an
    element of the stacked rows [heads * chunk, 128] the q, do and dq blocks
    twice, five scratch arrays and ten float32 temporaries of the tile loop
    or of an in-chunk row block; for an element of one head's [chunk, 128]
    the k, v, dk, dv blocks twice, the lane-narrow scalars' blocks and
    their columns as values, four scratch arrays.

    Checked against the v5e compiler's own count (MiB, the backward's;
    ``tests/test_chip_compile_scans.py`` pins the first two, the Brumby
    cell's, the others were compiled once for a described v5e and are not
    pinned): bf16, group 5, chunks of 1,024: 49.8 (66.7 here); the same at
    2,048: five heads would take 142, over the limit here too (121.2), so
    the group goes a head at a time there, 33.9 (53.2 here); group 8 at
    1,024: 77.1 (92.2); group 3 at 2,048: 72.8 (87.2); group 5 at 1,536:
    82.1 (93.9); one head at 4,096: 76.7 (94.2); float32, group 5 at 1,024:
    60.4 (78.7); group 7 at 1,024, the nearest to the limit that a divisor
    reaches: 82.5 (99.7).  Other dtypes and chunks past 4,096 are not
    checked."""
    stacked = 6 * itemsize + (12 + 2 * itemsize) + 40
    one_head = 8 * itemsize + 80
    return 3 * DIAGONALS * LANES * LANES * 4 \
        + (heads * stacked + one_head) * chunk * LANES


def sweep_heads(group, chunk, itemsize=2):
    """How many of a group's ``group`` query heads ride one grid step,
    stacked along rows: the largest divisor of the group whose step fits
    ``VMEM_LIMIT`` (1 where none does: a sweep of the state's tiles a head
    and chunk)."""
    for heads in range(group, 1, -1):
        if group % heads == 0 and \
                _step_vmem_bytes(heads, chunk, itemsize) <= VMEM_LIMIT:
            return heads
    return 1


def state_sweeps(q_heads, kv_heads, seq, chunk, itemsize=2):
    """Sweeps of the state's 65 tiles a call's forward runs over one
    sequence: a grid step each, key/value heads x chunks x the parts a
    group goes in."""
    group = q_heads // kv_heads
    return kv_heads * (seq // chunk) * (
        group // sweep_heads(group, chunk, itemsize))


class _Geom:
    """The shapes of one call and its block specs.  q is [B, S, Hq * 128],
    k and v [B, S, Hkv * 128]; ``flip`` walks the chunks from the end."""

    def __init__(self, q, k, chunk, flip=False):
        self.B, self.S = q.shape[:2]
        self.Hq, self.Hkv = q.shape[2] // LANES, k.shape[2] // LANES
        assert self.Hq % self.Hkv == 0 and supported(LANES, self.S, chunk), \
            (q.shape, k.shape, chunk)
        self.G, self.c, self.N = self.Hq // self.Hkv, chunk, self.S // chunk
        self.heads = sweep_heads(self.G, chunk, q.dtype.itemsize)
        self.parts = parts = self.G // self.heads
        self.rows = min(ROW_BLOCK, chunk)
        assert chunk % self.rows == 0
        N = self.N
        at = (lambda n: N - 1 - n) if flip else (lambda n: n)
        c = chunk
        # the grid's last axis, the parts of a group, is there only where a
        # group does not go whole
        self.q = pl.BlockSpec(
            (None, c, self.heads * LANES),
            lambda b, h, n, *sub: (b, at(n), h * parts + sum(sub)))
        self.kv = pl.BlockSpec((None, c, LANES),
                               lambda b, h, n, *sub: (b, at(n), h))
        # per-token scalars: the running log-decay of every key/value head
        # [B, S, Hkv], and a group's query heads' side by side [B, Hkv, S, *]
        self.bseq = pl.BlockSpec((None, c, self.Hkv),
                                 lambda b, h, n, *sub: (b, at(n), 0))
        self.stats = lambda width: pl.BlockSpec(
            (None, None, c, width), lambda b, h, n, *sub: (b, h, at(n), 0))
        self.krow = pl.BlockSpec((None, None, None, 1, c),
                                 lambda b, h, n, *sub: (b, h, at(n), 0, 0))
        self.state = pl.BlockSpec(
            (None, None, None, DIAGONALS, LANES, LANES),
            lambda b, h, n, *sub: (b, h, at(n), 0, 0, 0))
        self.moment = pl.BlockSpec(
            (None, None, None, LANES, LANES),
            lambda b, h, n, *sub: (b, h, at(n), 0, 0))
        self.scalar = pl.BlockSpec((None, None, None, 1, LANES),
                                   lambda b, h, n, *sub: (b, h, at(n), 0, 0))
        self.grid = (self.B, self.Hkv, N) + ((parts,) if parts > 1 else ())

    def shape(self, *dims, dtype=_F32):
        return jax.ShapeDtypeStruct((self.B,) + dims, dtype)

    def stacked(self, dtype):
        """VMEM scratch for one array of the stacked heads' rows."""
        return pltpu.VMEM((self.heads * self.c, LANES), dtype)

    @property
    def params(self):
        return _CompilerParams(
            dimension_semantics=("parallel", "parallel")
            + ("arbitrary",) * (len(self.grid) - 2),
            vmem_limit_bytes=VMEM_LIMIT)


def _layouts(b, geom):
    """The within-chunk running log-decay [B, S, Hkv] as it is (a kernel
    picks its head's column) and as a row a chunk and head."""
    return b, b.transpose(0, 2, 1).reshape(geom.B, geom.Hkv, geom.N, 1,
                                           geom.c)


def _fwd(q, k, v, b, chunk, scale, eps, interpret, save):
    geom = _Geom(q, k, chunk)
    bseq, brow = _layouts(b, geom)
    dt = q.dtype
    out_specs = [geom.q, geom.stats(geom.G)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, dt),
                 geom.shape(geom.Hkv, geom.S, geom.G)]
    if save:
        out_specs += [geom.state, geom.moment]
        out_shape += [geom.shape(geom.Hkv, geom.N, DIAGONALS, LANES, LANES),
                      geom.shape(geom.Hkv, geom.N, LANES, LANES)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, eps=eps,
                          heads=geom.heads, parts=geom.parts, rows=geom.rows,
                          save=save),
        grid=geom.grid,
        in_specs=[geom.q, geom.kv, geom.kv, geom.bseq, geom.krow],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((DIAGONALS, LANES, LANES), _F32),
                        pltpu.VMEM((DIAGONALS, LANES, LANES), dt),
                        pltpu.VMEM((LANES, LANES), _F32),
                        geom.stacked(_F32),         # the scaled queries
                        geom.stacked(_F32)],        # what they read
        compiler_params=geom.params, interpret=interpret,
        name="power_retention_fwd",
    )(q, k, v, bseq, brow)


def _bwd(chunk, scale, eps, interpret, res, do):
    q, k, v, b, o, den, states, moments = res
    geom = _Geom(q, k, chunk, flip=True)
    B, S, Hq, Hkv, G, N = geom.B, geom.S, geom.Hq, geom.Hkv, geom.G, geom.N
    bseq, brow = _layouts(b, geom)
    # d num = do / den; d den = -(do . o) / den: [B, Hkv, S, G] each
    delta = jnp.sum((do.astype(_F32) * o.astype(_F32)).reshape(
        B, S, Hkv, G, LANES), axis=-1).transpose(0, 2, 1, 3)
    rden = 1.0 / den
    c = chunk
    dq, dk, dv, dbrow, dgam = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, group=G,
                          heads=geom.heads, parts=geom.parts, rows=geom.rows),
        grid=geom.grid,
        in_specs=[geom.q, geom.kv, geom.kv, geom.q, geom.bseq, geom.krow,
                  geom.stats(2 * G), geom.state, geom.moment],
        out_specs=[geom.q, geom.kv, geom.kv, geom.krow, geom.scalar],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   geom.shape(Hkv, N, 1, c), geom.shape(Hkv, N, 1, LANES)],
        scratch_shapes=[pltpu.VMEM((DIAGONALS, LANES, LANES), _F32),
                        pltpu.VMEM((LANES, LANES), _F32),
                        pltpu.VMEM((c, LANES), _F32),      # dk
                        pltpu.VMEM((c, LANES), _F32),      # dv
                        pltpu.VMEM((c, LANES), _F32),      # d (decayed v)
                        pltpu.VMEM((LANES, LANES), _F32),  # <dS, S> by lane
                        pltpu.VMEM((1, c), _F32),          # d b, as a row
                        geom.stacked(_F32),         # the scaled queries
                        geom.stacked(q.dtype),      # d num
                        geom.stacked(q.dtype),      # d num, decayed
                        geom.stacked(_F32),         # d (scaled q)
                        geom.stacked(_F32)],        # d b's query side
        compiler_params=geom.params, interpret=interpret,
        name="power_retention_bwd",
    )(q, k, v, do, bseq, brow,
      jnp.concatenate([rden, -delta * rden], axis=-1), states, moments)
    db = dbrow.reshape(B, Hkv, N, c).at[..., c - 1].add(dgam[..., 0, 0])
    return dq, dk, dv, db.reshape(B, Hkv, S).transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _retention(q, k, v, b, chunk, scale, eps, interpret):
    return _fwd(q, k, v, b, chunk, scale, eps, interpret, False)[0]


def _retention_fwd(q, k, v, b, chunk, scale, eps, interpret):
    o, den, states, moments = _fwd(q, k, v, b, chunk, scale, eps, interpret,
                                   True)
    return o, (q, k, v, b, o, den, states, moments)


_retention.defvjp(_retention_fwd, _bwd)


def power_retention(q, k, v, log_decay, chunk=1024, scale=None, eps=EPS,
                    interpret=None):
    """``o`` [B, S, Hq * 128] of packed projections q [B, S, Hq * 128] and
    k, v [B, S, Hkv * 128] (query head i reads key/value head ``i // (Hq //
    Hkv)``) under per-token log-decays ``log_decay`` [B, S, Hkv] (<= 0,
    float32), by the carried-state algorithm in chunks of ``chunk`` tokens.
    Differentiable in all four; the kernels' operands take q's dtype, state
    and normaliser are float32."""
    B, S, _ = q.shape
    Hkv = k.shape[2] // LANES
    assert supported(LANES, S, chunk) and log_decay.shape == (B, S, Hkv), \
        (q.shape, k.shape, log_decay.shape, chunk)
    if scale is None:
        scale = 1.0 / math.sqrt(LANES)
    if interpret is None:
        interpret = not _on_tpu()
    # the running log-decay inside each chunk, its own token's included
    b = jnp.cumsum(log_decay.astype(_F32).reshape(B, S // chunk, chunk, Hkv),
                   axis=2).reshape(B, S, Hkv)
    return _retention(q, k, v.astype(q.dtype), b, int(chunk), float(scale),
                      float(eps), bool(interpret))
