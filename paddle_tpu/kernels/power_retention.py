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

Grid (batch, key/value head, chunk, query head of the group), the last two
sequential: the ``G`` query heads of a group read the state the chunk
found, then the last of them folds the chunk's keys and values in.  The
backward walks the chunks in REVERSE with the state's gradient in VMEM and
reads the states the forward saved ([B, Hkv, S/c, 65, 128, 128] float32:
recomputing them would need the same buffer, the recurrence cannot be run
backwards through a decay of 2^-1000 a chunk).

Kernel names in a trace: ``power_retention_fwd``, ``power_retention_bwd``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["power_retention", "supported", "LANES",
           "DIAGONALS", "STATE_COLUMNS", "EPS"]

LANES = 128                     # the head width the kernels are written for
DIAGONALS = LANES // 2 + 1      # tiles of phi
STATE_COLUMNS = DIAGONALS * LANES
EPS = 1e-6
ROW_BLOCK = 256                 # query rows of the in-chunk block at a time
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


def _in_chunk(q_ref, k_ref, bcol, brow_ref, r0, hi, scale):
    """Rows [r0, hi) of the chunk against its keys [0, hi): the scaled
    scores and the masked decays ``exp(b_t - b_j)``, ``j <= t``."""
    sc = jax.lax.dot_general(q_ref[r0:hi, :], k_ref[:hi, :], _NT,
                             preferred_element_type=_F32) * scale
    t = r0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    dec = jnp.where(j <= t, jnp.exp(jnp.minimum(
        bcol[r0:hi] - brow_ref[:, :hi], 0.0)), 0.0)
    return sc, dec


def _fwd_kernel(q_ref, k_ref, v_ref, bseq_ref, brow_ref, o_ref, den_ref,
                *rest, scale, eps, group, rows, save):
    if save:
        s_out, z_out, s_ref, sb_ref, z_ref, acc_ref = rest
    else:
        s_ref, sb_ref, z_ref, acc_ref = rest
    n, g = pl.program_id(2), pl.program_id(3)
    c = q_ref.shape[0]
    dt = q_ref.dtype
    bcol = _column(bseq_ref[...], pl.program_id(1))             # [c, 1]

    @pl.when((n == 0) & (g == 0))
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        sb_ref[...] = jnp.zeros_like(sb_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    if save:
        @pl.when(g == 0)
        def _():
            s_out[...] = s_ref[...]
            z_out[...] = z_ref[...]

    # the chunk's own block, a few hundred query rows at a time and only
    # the keys at or before them
    nums, dens = [], []
    for r0 in range(0, c, rows):
        hi = r0 + rows
        sc, dec = _in_chunk(q_ref, k_ref, bcol, brow_ref, r0, hi, scale)
        w = sc * sc * dec
        nums.append(jnp.dot(w.astype(dt), v_ref[:hi, :],
                            preferred_element_type=_F32))
        dens.append(jnp.sum(w, axis=1, keepdims=True))
    num = nums[0] if len(nums) == 1 else jnp.concatenate(nums, axis=0)
    den = dens[0] if len(dens) == 1 else jnp.concatenate(dens, axis=0)

    # the state as the chunk found it, a tile of phi(q) at a time
    xq = q_ref[...].astype(_F32) * scale
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def read(d, carry):
        phi = (xq * pltpu.roll(xq, d, 1)).astype(dt)
        acc_ref[...] += jnp.dot(phi, sb_ref[d], preferred_element_type=_F32)
        return carry

    jax.lax.fori_loop(0, DIAGONALS, read, 0)
    zq = jnp.dot(xq, z_ref[...], preferred_element_type=_F32,
                 precision=_HIGHEST)
    e = jnp.exp(bcol)
    den = den + e * jnp.sum(zq * xq, axis=1, keepdims=True) + eps
    o_ref[...] = ((num + e * acc_ref[...]) / den).astype(o_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, den_ref.shape, 1)
    den_ref[...] = jnp.where(lane == g, den, den_ref[...])

    @pl.when(g == group - 1)
    def _():
        kf = k_ref[...].astype(_F32)
        last = brow_ref[:, c - 1:c]                             # [1, 1]
        wk = jnp.exp(last - bcol)                               # [c, 1]
        decay = jnp.exp(last)
        vw = (wk * v_ref[...].astype(_F32)).astype(dt)

        def fold(d, carry):
            phi = (kf * pltpu.roll(kf, d, 1)).astype(dt)
            u = jax.lax.dot_general(phi, vw, _TN,
                                    preferred_element_type=_F32)
            new = decay * s_ref[d] + _weight(d) * u
            s_ref[d] = new
            sb_ref[d] = new.astype(dt)
            return carry

        jax.lax.fori_loop(0, DIAGONALS, fold, 0)
        z_ref[...] = decay * z_ref[...] + jax.lax.dot_general(
            kf, wk * kf, _TN, preferred_element_type=_F32,
            precision=_HIGHEST)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, bseq_ref, brow_ref, stats_ref,
                p_ref, zn_ref, dq_ref, dk_ref, dv_ref, dbrow_ref, dgam_ref,
                ds_ref, dz_ref, dk_acc, dv_acc, dx_acc, dvp_acc, dot_acc,
                dbrow_acc, qs_acc, *, scale, group, rows):
    n, g = pl.program_id(2), pl.program_id(3)       # n counts from the END
    c = q_ref.shape[0]
    dt = q_ref.dtype
    last = brow_ref[:, c - 1:c]                                 # [1, 1]
    decay = jnp.exp(last)
    bcol = _column(bseq_ref[...], pl.program_id(1))             # [c, 1]

    @pl.when(g == 0)
    def _():
        @pl.when(n == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)
            dz_ref[...] = jnp.zeros_like(dz_ref)

        # the fold's backward: ds_ref is the gradient of the state the
        # chunk LEFT; the chunk's keys and values take theirs, then it
        # decays into the gradient of the state the chunk found
        kf = k_ref[...].astype(_F32)
        wk = jnp.exp(last - bcol)                               # [c, 1]
        vw = wk * v_ref[...].astype(_F32)
        vwb = vw.astype(dt)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dvp_acc[...] = jnp.zeros_like(dvp_acc)
        dot_acc[...] = jnp.zeros_like(dot_acc)

        def unfold(d, carry):
            kr = pltpu.roll(kf, d, 1)
            dsd = ds_ref[d]
            dsb = (_weight(d) * dsd).astype(dt)
            dvp_acc[...] += jnp.dot((kf * kr).astype(dt), dsb,
                                    preferred_element_type=_F32)
            m = jax.lax.dot_general(vwb, dsb, _NT,
                                    preferred_element_type=_F32)
            dk_acc[...] += m * kr + _unroll_back(m * kf, d)
            dot_acc[...] += dsd * p_ref[d]
            ds_ref[d] = decay * dsd
            return carry

        jax.lax.fori_loop(0, DIAGONALS, unfold, 0)
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

    # this query head's rows
    stats = stats_ref[...]                          # 1 / den, then d den
    dn = do_ref[...].astype(_F32) * _column(stats, g)           # d num
    dnb = dn.astype(dt)
    dd = _column(stats, group + g)                              # [c, 1]
    # b's gradient, QUERY side: every weight of row t carries e^{b_t}, so in
    # exact arithmetic the row's terms add up to eps * (do . o) / den, next
    # to nothing; they are summed here all the same, product by product as
    # the key side subtracts them, because the log-decay's gradient is the
    # running sum of (query side - key side) over a chunk and only equal
    # roundings cancel in it (qs_acc's lanes add up to the row's sum)
    qs_acc[...] = jnp.zeros_like(qs_acc)
    dqs = []
    for r0 in range(0, c, rows):
        hi = r0 + rows
        sc, dec = _in_chunk(q_ref, k_ref, bcol, brow_ref, r0, hi, scale)
        w = sc * sc * dec
        dw = jax.lax.dot_general(dnb[r0:hi], v_ref[:hi, :], _NT,
                                 preferred_element_type=_F32) + dd[r0:hi]
        qs_acc[r0:hi, :] += jnp.sum(dw * w, axis=1, keepdims=True) \
            * (1.0 / LANES)
        dsc = (dw * (2.0 * scale) * sc * dec).astype(dt)
        dqs.append(jnp.dot(dsc, k_ref[:hi, :], preferred_element_type=_F32))
        dk_acc[:hi, :] += jax.lax.dot_general(
            dsc, q_ref[r0:hi, :], _TN, preferred_element_type=_F32)
        dv_acc[:hi, :] += jax.lax.dot_general(
            w.astype(dt), dnb[r0:hi], _TN, preferred_element_type=_F32)
        dbrow_acc[:, :hi] -= jnp.sum(dw * w, axis=0, keepdims=True)
    dq = dqs[0] if len(dqs) == 1 else jnp.concatenate(dqs, axis=0)

    # the read's backward: phi(q)'s gradient back through the products,
    # and this head's part of the state's gradient
    xq = q_ref[...].astype(_F32) * scale
    e = jnp.exp(bcol)
    dneb = (e * dn).astype(dt)
    dx_acc[...] = jnp.zeros_like(dx_acc)

    def unread(d, carry):
        xr = pltpu.roll(xq, d, 1)
        phi = xq * xr
        m = jax.lax.dot_general(dneb, p_ref[d].astype(dt), _NT,
                                preferred_element_type=_F32)
        dx_acc[...] += m * xr + _unroll_back(m * xq, d)
        qs_acc[...] += phi * m
        ds_ref[d] += jax.lax.dot_general(phi.astype(dt), dneb, _TN,
                                         preferred_element_type=_F32)
        return carry

    jax.lax.fori_loop(0, DIAGONALS, unread, 0)
    cz = dd * e                                                 # [c, 1]
    zq = jnp.dot(xq, zn_ref[...], preferred_element_type=_F32,
                 precision=_HIGHEST)
    dz_ref[...] += jax.lax.dot_general(cz * xq, xq, _TN,
                                       preferred_element_type=_F32,
                                       precision=_HIGHEST)
    dq_ref[...] = (dq + scale * (dx_acc[...] + 2.0 * cz * zq)).astype(
        dq_ref.dtype)
    dbrow_acc[...] += jax.lax.dot_general(
        jnp.ones((8, LANES), _F32), qs_acc[...] + cz * zq * xq, _NT,
        preferred_element_type=_F32, precision=_HIGHEST)[:1]

    @pl.when(g == group - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dbrow_ref[...] = dbrow_acc[...]


class _Geom:
    """The shapes of one call and its block specs.  q is [B, S, Hq * 128],
    k and v [B, S, Hkv * 128]; ``flip`` walks the chunks from the end."""

    def __init__(self, q, k, chunk, flip=False):
        self.B, self.S = q.shape[:2]
        self.Hq, self.Hkv = q.shape[2] // LANES, k.shape[2] // LANES
        assert self.Hq % self.Hkv == 0 and supported(LANES, self.S, chunk), \
            (q.shape, k.shape, chunk)
        self.G, self.c, self.N = self.Hq // self.Hkv, chunk, self.S // chunk
        self.rows = min(ROW_BLOCK, chunk)
        assert chunk % self.rows == 0
        N, G = self.N, self.G
        at = (lambda n: N - 1 - n) if flip else (lambda n: n)
        c = chunk
        self.q = pl.BlockSpec((None, c, LANES),
                              lambda b, h, n, g: (b, at(n), h * G + g))
        self.kv = pl.BlockSpec((None, c, LANES),
                               lambda b, h, n, g: (b, at(n), h))
        # per-token scalars: the running log-decay of every key/value head
        # [B, S, Hkv], and a group's query heads' side by side [B, Hkv, S, *]
        self.bseq = pl.BlockSpec((None, c, self.Hkv),
                                 lambda b, h, n, g: (b, at(n), 0))
        self.stats = lambda width: pl.BlockSpec(
            (None, None, c, width), lambda b, h, n, g: (b, h, at(n), 0))
        self.krow = pl.BlockSpec((None, None, None, 1, c),
                                 lambda b, h, n, g: (b, h, at(n), 0, 0))
        self.state = pl.BlockSpec(
            (None, None, None, DIAGONALS, LANES, LANES),
            lambda b, h, n, g: (b, h, at(n), 0, 0, 0))
        self.moment = pl.BlockSpec(
            (None, None, None, LANES, LANES),
            lambda b, h, n, g: (b, h, at(n), 0, 0))
        self.scalar = pl.BlockSpec((None, None, None, 1, LANES),
                                   lambda b, h, n, g: (b, h, at(n), 0, 0))
        self.grid = (self.B, self.Hkv, N, G)

    def shape(self, *dims, dtype=_F32):
        return jax.ShapeDtypeStruct((self.B,) + dims, dtype)

    @property
    def params(self):
        return _CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
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
        functools.partial(_fwd_kernel, scale=scale, eps=eps, group=geom.G,
                          rows=geom.rows, save=save),
        grid=geom.grid,
        in_specs=[geom.q, geom.kv, geom.kv, geom.bseq, geom.krow],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((DIAGONALS, LANES, LANES), _F32),
                        pltpu.VMEM((DIAGONALS, LANES, LANES), dt),
                        pltpu.VMEM((LANES, LANES), _F32),
                        pltpu.VMEM((chunk, LANES), _F32)],
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
        functools.partial(_bwd_kernel, scale=scale, group=G, rows=geom.rows),
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
                        pltpu.VMEM((c, LANES), _F32),      # d (scaled q)
                        pltpu.VMEM((c, LANES), _F32),      # d (decayed v)
                        pltpu.VMEM((LANES, LANES), _F32),  # <dS, S> by lane
                        pltpu.VMEM((1, c), _F32),          # d b, as a row
                        pltpu.VMEM((c, LANES), _F32)],     # its query side
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
