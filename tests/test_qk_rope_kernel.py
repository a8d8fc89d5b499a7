"""``kernels/qk_rope.py`` (q/k norm and rotary positions in one pass) in
Pallas interpret mode against the lines it replaces, ``rope(rms_norm(...))``
of ``parallel/transformer.py``: outputs and every gradient; and ``_qkv``
taking the kernel where the shapes allow and those lines where not; its
``pairs`` convention and shared lane block (the latent form's q and k)
against ``rope_pairs``, the scale and the concatenate-and-broadcast lines of
``_latent_qkv``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.kernels import qk_rope as K
from paddle_tpu.parallel import transformer as T

EPS, THETA = 1e-5, 1e4


def reference(x, w, heads, dh, norm, rotary, first=0):
    """Today's lines of ``_qkv`` on one projection."""
    b, S, W = x.shape
    if norm == "head":
        x = T.rms_norm(x.reshape(b, S, heads, dh), w, EPS).reshape(b, S, W)
    elif norm:
        x = T.rms_norm(x, w, EPS)
    return T.rope(x, heads, THETA, first) if rotary else x


def kernel(x, w, heads, dh, norm, rotary, first=0):
    tables = K.angle_tables(x.shape[1], dh, THETA, first) if rotary else None
    return K.qk_rope(x, w, tables, head_dim=dh, norm=norm, eps=EPS)


def operands(b, S, heads, dh, norm, dtype=jnp.float32, seed=0):
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    W = heads * dh
    x = (2 * jax.random.normal(kx, (b, S, W))).astype(dtype)
    w = None if not norm else 1 + 0.3 * jax.random.normal(
        kw, (dh if norm == "head" else W,))
    return x, w, jax.random.normal(kg, (b, S, W))


def value_and_grads(fn, x, w, g, *static, first=0):
    """(output, dx, dw) of ``sum(fn(x, w) * g)``; no dw without a norm."""
    def loss(x, w):
        out = fn(x, w, *static, first)
        return jnp.sum(out.astype(jnp.float32) * g), out
    (_, out), grads = jax.value_and_grad(
        loss, (0, 1) if w is not None else (0,), has_aux=True)(x, w)
    return (out,) + tuple(grads)


# rows: 48 fill no taller block than 16 (three grid steps of positions), 8 are
# one block of one sublane tile; heads 3 on 1: grouped k widths beside q's
@pytest.mark.parametrize("norm,rotary", [
    ("head", True), ("head", False), ("whole", True), ("whole", False),
    (None, True)])      # neither: ``_qkv`` makes no call
@pytest.mark.parametrize("dh,heads,b,S", [(128, 3, 2, 48), (128, 1, 1, 8),
                                          (64, 4, 2, 48), (64, 2, 1, 24),
                                          (32, 4, 1, 16)])
def test_kernel_equals_the_lines_it_replaces(dh, heads, b, S, norm, rotary):
    x, w, g = operands(b, S, heads, dh, norm)
    static = (heads, dh, norm, rotary)
    got = value_and_grads(kernel, x, w, g, *static, first=5)
    want = value_and_grads(reference, x, w, g, *static, first=5)
    for name, a, r in zip(("out", "dx", "dw"), got, want):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        # float32 both ways; a sum over rows for dw
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("norm", ["head", "whole", None])
@pytest.mark.parametrize("dh,heads", [(128, 2), (64, 4)])
def test_bf16_is_rounded_once_and_no_further_from_float32(dh, heads, norm):
    """The replaced lines round after the norm and again after the rotation;
    the kernel rounds its float32 result once: the float32 lines' result
    rounded, and never further from them than today's path."""
    x, w, g = operands(2, 32, heads, dh, norm, jnp.bfloat16, seed=1)
    static = (heads, dh, norm, True)
    exact = value_and_grads(reference, x.astype(jnp.float32), w, g, *static)
    got = value_and_grads(kernel, x, w, g, *static)
    old = value_and_grads(reference, x, w, g, *static)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)
    # the float32 lines rounded once, but for a value in a thousand whose
    # float32 sums, in another order, lie either side of a rounding boundary
    once = f32(exact[0].astype(jnp.bfloat16))
    assert np.mean(f32(got[0]) != once) < 1e-3
    np.testing.assert_allclose(f32(got[0]), once, rtol=2 ** -7, atol=1e-6)
    for a, o, e in zip(got, old, exact):
        assert np.abs(f32(a) - f32(e)).max() \
            <= 1.001 * np.abs(f32(o) - f32(e)).max() + 1e-6


def test_a_traced_first_under_lax_map_is_a_block_of_a_longer_sequence():
    """Brumby's call: ``_by_row_blocks`` hands ``_qkv`` a block of rows and
    its first position, traced, under ``lax.map`` and ``jax.checkpoint``."""
    heads, dh, block = 2, 128, 16
    x, w, g = operands(1, 4 * block, heads, dh, "head", seed=2)

    def blocked(fn):
        def whole(x, w, *static_and_first):
            def rows(turn):
                return fn(turn[0], w, heads, dh, "head", True, turn[1])
            out = jax.lax.map(jax.checkpoint(rows), (
                x.reshape(1, -1, block, heads * dh).swapaxes(0, 1),
                jnp.arange(0, x.shape[1], block)))
            return out.swapaxes(0, 1).reshape(x.shape)
        return whole

    got = value_and_grads(blocked(kernel), x, w, g)
    want = value_and_grads(reference, x, w, g, heads, dh, "head", True)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5)


FREQS = {dr: 1e4 ** (-np.arange(dr // 2) / (dr // 2)) for dr in (32, 64, 128)}
FACTOR = 1.25


def _position_scale(S, first):
    """``_latent_qkv``'s: a softmax scale times the position's step."""
    pos = jnp.arange(S, dtype=jnp.float32) + first
    return 1.3 * (1.0 + 0.1 * jnp.log1p(jnp.floor(pos / 16)))


def pairs_reference(x, _, heads, dn, dr, scaled, first=0):
    """``_latent_qkv``'s lines on the query: ``rope_pairs`` on a head's last
    ``dr`` lanes, the whole head times the position's scale."""
    b, S, W = x.shape
    pos = jnp.arange(S, dtype=jnp.float32) + first
    ang = pos[:, None] * jnp.asarray(FREQS[dr], jnp.float32)[None]
    q = x.astype(jnp.float32).reshape(b, S, heads, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], T.rope_pairs(q[..., dn:], ang, FACTOR)], axis=-1)
    if scaled:
        q = q * _position_scale(S, first)[None, :, None, None]
    return q.astype(x.dtype).reshape(b, S, W)


def pairs_kernel(x, _, heads, dn, dr, scaled, first=0):
    S = x.shape[1]
    tables = K.pair_tables(S, FREQS[dr], dn + dr, first, FACTOR,
                           _position_scale(S, first) if scaled else None)
    return K.qk_rope(x, None, tables, head_dim=dn + dr, pairs=True)


@pytest.mark.parametrize("scaled", [True, False])
@pytest.mark.parametrize("dn,dr,heads,b,S", [
    (64, 64, 3, 2, 48), (96, 32, 2, 1, 16), (0, 128, 2, 1, 24),
    (32, 32, 4, 2, 16)])    # two heads of 32 + 32 a lane block
def test_pairs_equal_rope_pairs_and_the_scale_lines(dn, dr, heads, b, S,
                                                    scaled):
    x, _, g = operands(b, S, heads, dn + dr, None, seed=3)
    static = (heads, dn, dr, scaled)
    got = value_and_grads(pairs_kernel, x, None, g, *static, first=9)
    want = value_and_grads(pairs_reference, x, None, g, *static, first=9)
    for name, a, r in zip(("out", "dx"), got, want):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5, err_msg=name)
    # and NOT the rotate-half convention's at the same angles
    assert dr < 4 or np.abs(got[0] - K.qk_rope(
        x, None, K.pair_tables(S, FREQS[dr], dn + dr, 9, FACTOR),
        head_dim=dn + dr)).max() > 0.1


@pytest.mark.parametrize("dn,dr", [(64, 64), (96, 32)])
def test_pairs_in_bf16_are_rounded_once_and_no_further_from_float32(dn, dr):
    x, _, g = operands(2, 32, 2, 128, None, jnp.bfloat16, seed=4)
    static = (2, dn, dr, True)
    exact = value_and_grads(pairs_reference, x.astype(jnp.float32), None, g,
                            *static)
    got = value_and_grads(pairs_kernel, x, None, g, *static)
    old = value_and_grads(pairs_reference, x, None, g, *static)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)
    once = f32(exact[0].astype(jnp.bfloat16))
    assert np.mean(f32(got[0]) != once) < 1e-3
    np.testing.assert_allclose(f32(got[0]), once, rtol=2 ** -7, atol=1e-6)
    for a, o, e in zip(got, old, exact):
        assert np.abs(f32(a) - f32(e)).max() \
            <= 1.001 * np.abs(f32(o) - f32(e)).max() + 1e-6


def test_pairs_with_a_traced_first_under_lax_map_and_checkpoint():
    """Mistral's call: ``_by_row_blocks`` hands ``_latent_qkv`` a block of
    rows and its first position, traced."""
    heads, dn, dr, block = 2, 64, 64, 16
    x, _, g = operands(1, 4 * block, heads, 128, None, seed=5)

    def blocked(x, _, *static_and_first):
        def rows(turn):
            return pairs_kernel(turn[0], None, heads, dn, dr, True, turn[1])
        out = jax.lax.map(jax.checkpoint(rows), (
            x.reshape(1, -1, block, heads * 128).swapaxes(0, 1),
            jnp.arange(0, x.shape[1], block)))
        return out.swapaxes(0, 1).reshape(x.shape)

    got = value_and_grads(blocked, x, None, g)
    want = value_and_grads(pairs_reference, x, None, g, heads, dn, dr, True)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5)


def keys_reference(k_nope, kr, heads, dn, dr, first):
    """``_latent_qkv``'s lines: the ONE rotary key rotated, rounded and
    written behind every head's own ``dn`` lanes."""
    b, S, _ = k_nope.shape
    pos = jnp.arange(S, dtype=jnp.float32) + first
    ang = pos[:, None] * jnp.asarray(FREQS[dr], jnp.float32)[None]
    kr = T.rope_pairs(kr.astype(jnp.float32)[:, :, None, :], ang, FACTOR)
    return jnp.concatenate(
        [k_nope.reshape(b, S, heads, dn), jnp.broadcast_to(
            kr.astype(k_nope.dtype), (b, S, heads, dr))],
        axis=-1).reshape(b, S, -1)


def keys_kernel(k_nope, kr, heads, dn, dr, first):
    """The kernel's assembly: every head's ``[k_nope_i | 0]`` (what the
    matmul by ``_latent_columns``' padded columns gives) plus the shared
    ``[0 | kr]``, rotated in the same pass."""
    b, S, _ = k_nope.shape
    padded = jnp.pad(k_nope.reshape(b, S, heads, dn),
                     ((0, 0), (0, 0), (0, 0), (0, dr))).reshape(b, S, -1)
    shared = jnp.tile(jnp.pad(kr, ((0, 0), (0, 0), (dn, 0))),
                      128 // (dn + dr))
    return K.qk_rope(padded, None,
                     K.pair_tables(S, FREQS[dr], dn + dr, first, FACTOR),
                     head_dim=dn + dr, pairs=True, shared=shared)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dn,dr,heads,b,S", [
    (64, 64, 3, 2, 48), (96, 32, 4, 1, 16), (32, 32, 4, 2, 32)])
def test_the_keys_assembly_equals_concatenate_and_broadcast(dn, dr, heads, b,
                                                            S, dtype):
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    k_nope = jax.random.normal(keys[0], (b, S, heads * dn)).astype(dtype)
    kr = (2 * jax.random.normal(keys[1], (b, S, dr))).astype(dtype)
    g = jax.random.normal(keys[2], (b, S, heads * (dn + dr)))

    def run(fn):
        def loss(k_nope, kr):
            out = fn(k_nope, kr, heads, dn, dr, 7)
            return jnp.sum(out.astype(jnp.float32) * g), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            k_nope, kr)
        return (out,) + grads

    got, want = run(keys_kernel), run(keys_reference)
    for name, a, r in zip(("k", "dk_nope", "dkr"), got, want):
        assert a.shape == r.shape and a.dtype == r.dtype == dtype, name
    f32 = lambda a: np.asarray(a, np.float32)
    if dtype == jnp.float32:
        for name, a, r in zip(("k", "dk_nope", "dkr"), got, want):
            np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5,
                                       err_msg=name)
        return
    # a head's own lanes pass untouched, the rotated key is rounded once as
    # the lines round it; dkr is the heads' sum in float32, rounded once,
    # where the lines round every head's before and the sum after
    np.testing.assert_array_equal(f32(got[0]), f32(want[0]))
    np.testing.assert_array_equal(f32(got[1]), f32(want[1]))
    exact = run(lambda *a: keys_reference(
        a[0].astype(jnp.float32), a[1].astype(jnp.float32), *a[2:]))
    assert np.abs(f32(got[2]) - f32(exact[2])).max() \
        <= 1.001 * np.abs(f32(want[2]) - f32(exact[2])).max() + 1e-6


# a head of TWO lane blocks: (plain, rotated, tail) lanes of a head, dots3's
# full layer [q_nope 128 | q_rope 64 | zero 64] and its sliding one [192 | 64]
# (a head's first lane block never moved: the grid visits the second alone),
# and a head whose rotated lanes lie across both (no plain block: both visited,
# the shared key's gradient two lane blocks wide)
WIDE = [(128, 64, 64), (192, 64, 0), (96, 64, 96)]


def wide_reference(x, ks, heads, plain, dr, tail, rotary):
    """``_latent_qkv_lanes``' lines on q (``ks`` None) or on k: the shared
    key rotated apart and rounded, padded to the head's lanes and added
    through the [b, S, H, lanes] view; q turned on the flat array by angles
    that are zero off a head's rotated lanes."""
    b, S, W = x.shape
    lanes, f32 = plain + dr + tail, jnp.float32
    ang = (jnp.arange(S, dtype=f32) + 0)[:, None] \
        * jnp.asarray(FREQS[dr], f32)[None]
    if ks is None:
        return T.rope_pairs(x.astype(f32), jnp.pad(
            ang, ((0, 0), (plain // 2, tail // 2))), tiles=heads).astype(
                x.dtype)
    if rotary:
        ks = T.rope_pairs(ks.astype(f32), ang).astype(x.dtype)
    return (_padded(x, heads, lanes).reshape(b, S, heads, lanes) + jnp.pad(
        ks, ((0, 0), (0, 0), (plain, tail)))[:, :, None, :]).reshape(
            b, S, -1)


def _padded(k_nope, heads, lanes):
    """What the matmul by the keys' zero-padded columns gives: every head's
    own lanes, zeros behind them."""
    b, S, W = k_nope.shape
    return jnp.pad(k_nope.reshape(b, S, heads, -1), (
        (0, 0), (0, 0), (0, 0), (0, lanes - W // heads))).reshape(b, S, -1)


def wide_kernel(x, ks, heads, plain, dr, tail, rotary):
    S, lanes = x.shape[1], plain + dr + tail
    tables = K.pair_tables(S, FREQS[dr], lanes, tail=tail) if rotary else None
    rotate = functools.partial(K.qk_rope, head_dim=lanes, pairs=True,
                               plain_blocks=plain // 128)
    if ks is None:
        return rotate(x, None, tables)
    return rotate(_padded(x, heads, lanes), None, tables,
                  shared=jnp.pad(ks, ((0, 0), (0, 0), (plain, tail))))


def _wide_operands(b, S, heads, plain, dr, tail, dtype, shared):
    """q [b, S, heads * lanes] with zeros where the zero columns of ``wq``
    leave them, or the keys' own lanes [b, S, heads * plain]; the shared key
    and a cotangent [b, S, heads * lanes]."""
    lanes = plain + dr + tail
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = 2 * jax.random.normal(keys[0], (b, S, heads, lanes)) \
        * (jnp.arange(lanes) < plain + dr)
    x = (x[..., :plain] if shared else x).astype(dtype).reshape(b, S, -1)
    ks = (2 * jax.random.normal(keys[1], (b, S, dr))).astype(dtype)
    return x, ks, jax.random.normal(keys[2], (b, S, heads * lanes))


def _wide_run(fn, x, ks, g, *static):
    def loss(x, ks):
        out = fn(x, ks, *static)
        return jnp.sum(out.astype(jnp.float32) * g), out
    (_, out), grads = jax.value_and_grad(
        loss, (0, 1) if ks is not None else (0,), has_aux=True)(x, ks)
    return (out,) + tuple(grads)


# q rotated; k assembled and rotated; k assembled without positions (Kimi's)
@pytest.mark.parametrize("shared,rotary", [(False, True), (True, True),
                                           (True, False)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("plain,dr,tail", WIDE)
@pytest.mark.parametrize("heads,b,S", [(3, 2, 48), (2, 1, 16)])
def test_a_head_of_two_lane_blocks_equals_the_lanes_lines(
        heads, b, S, plain, dr, tail, dtype, shared, rotary):
    x, ks, g = _wide_operands(b, S, heads, plain, dr, tail, dtype, shared)
    static = (heads, plain, dr, tail, rotary)
    ks = ks if shared else None
    got = _wide_run(wide_kernel, x, ks, g, *static)
    want = _wide_run(wide_reference, x, ks, g, *static)
    names = ("out", "dx", "dks")
    for name, a, r in zip(names, got, want):
        assert a.shape == r.shape and a.dtype == r.dtype == dtype, name
    f32 = lambda a: np.asarray(a, np.float32)
    if dtype == jnp.float32:
        for name, a, r in zip(names, got, want):
            np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5,
                                       err_msg=name)
        return
    # float32 inside and one rounding, both ways: the lines' own bf16 result
    # but for a value in a thousand whose float32 sums, in another order (a
    # fused multiply-add here, none there), lie either side of a rounding
    # boundary (on the chip to the bit: ``scripts/dots3_kernels_receipt.py``)
    for name, a, r in zip(names[:2], got, want):
        assert np.mean(f32(a) != f32(r)) < 1e-3, name
        np.testing.assert_allclose(f32(a), f32(r), rtol=2 ** -7, atol=1e-6,
                                   err_msg=name)
    if shared:
        # the heads' sum in float32 rounded once, where the lines round the
        # sum and then its rotation back
        exact = _wide_run(wide_reference, x.astype(jnp.float32),
                          ks.astype(jnp.float32), g, *static)
        assert np.abs(f32(got[2]) - f32(exact[2])).max() \
            <= 1.001 * np.abs(f32(want[2]) - f32(exact[2])).max() + 1e-6


def test_what_a_head_of_several_lane_blocks_goes_with():
    x = jnp.zeros((1, 16, 512))
    tables = K.pair_tables(16, FREQS[64], 256, tail=64)
    assert tables[0].shape == tables[1].shape == (16, 256)
    with pytest.raises(ValueError, match="no norm and the pairs"):
        K.qk_rope(x, None, tables, head_dim=256)     # rotate-half
    with pytest.raises(ValueError, match="no norm and the pairs"):
        K.qk_rope(x, jnp.ones((256,)), tables, head_dim=256, norm="head",
                  pairs=True)
    with pytest.raises(ValueError, match="plain blocks"):
        K.qk_rope(x, None, tables, head_dim=256, pairs=True, plain_blocks=2)
    with pytest.raises(ValueError, match="plain blocks"):
        K.qk_rope(x, None, None, head_dim=128, plain_blocks=1)


def test_a_shared_lane_block_goes_with_no_norm():
    x = jnp.zeros((1, 16, 256))
    with pytest.raises(ValueError, match="no norm"):
        K.qk_rope(x, jnp.ones((128,)), None, head_dim=128, norm="head",
                  shared=jnp.zeros((1, 16, 128)))


@pytest.mark.parametrize("shape,dh,itemsize,rows", [
    ((1, 6144, 6144), 128, 2, 128),     # Trinity's q: six blocks in 12 MiB
    ((1, 6144, 1024), 128, 2, 256),     # its k
    ((4, 4096, 2048), 128, 2, 256),     # OLMoE
    ((2, 8192, 2048), 64, 2, 256),      # LFM2, two heads a lane block
    ((1, 16384, 3584), 128, 2, 256),    # SmallThinker
    ((1, 2048, 5120), 128, 2, 128),     # Brumby's row block
    ((1, 1024, 4096), 128, 2, 256),     # Mistral's row block, q and k
    ((2, 48, 384), 128, 4, 16),
    ((2, 24, 256), 64, 2, None),        # 24 rows are no whole bf16 tiles
    ((2, 32, 64), 16, 4, None),         # tiny OLMoE: half a lane block
    ((2, 32, 768), 96, 4, None),        # a head across lane blocks
    ((1, 8192, 8192), 256, 2, 1024),    # dots3's full layer: 32 heads of 256
    ((1, 8192, 4096), 256, 2, 1024),    # its sliding layer: 16
    ((1, 16384, 8192), 256, 2, 1024),   # Kimi-Linear's latent k
    ((2, 32, 768), 192, 4, None),       # 192: not whole lane blocks
    ((2, 32, 640), 256, 4, None),       # no whole heads of 256
    ((1, 16, 256 * 1024), 128, 4, None),  # no block within BLOCK_VMEM
])
def test_the_shapes_the_kernel_takes(shape, dh, itemsize, rows):
    assert K.supported(shape, dh, itemsize) == (rows is not None)
    if rows and dh > 128:       # ONE lane block a grid step
        assert K.touched_rows(shape[1], itemsize) == rows
        assert K.touched_vmem_bytes(rows, itemsize) < 12 * 2 ** 20
    elif rows:
        assert K.block_rows(shape[1], shape[2], itemsize) == rows
        assert 6 * rows * shape[2] * itemsize <= K.BLOCK_VMEM
        assert K.vmem_bytes(rows, shape[2], itemsize) < 24 * 2 ** 20
    else:
        x = jnp.zeros(shape, jnp.float32 if itemsize == 4 else jnp.bfloat16)
        with pytest.raises(ValueError, match="not supported"):
            K.qk_rope(x, None, None, head_dim=dh)


def _config(**kw):
    d = dict(vocab_size=64, hidden=32, n_layers=1, n_heads=2, n_kv_heads=1,
             head_width=128, ffn_hidden=64, max_seq=64, causal=True,
             norm="rms", positions="rotary", qk_norm="head", bias=False,
             dtype="float32")
    d.update(kw)
    return T.TransformerConfig(**d)


def _counted(tmp_path, trace):
    """{(dh, norm, rotary, convention, fused): calls} that ``trace()``
    counts in ``monitor.kernels.qk_rope_calls`` under a monitor session."""
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        trace()
        return {tuple(r["labels"][k] for k in (
            "dh", "norm", "rotary", "convention", "fused")): r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.qk_rope_calls"}
    finally:
        monitor.disable()


@pytest.mark.parametrize("S,fused", [(16, 1), (12, 0)])
def test_qkv_takes_the_kernel_where_the_shapes_allow(tmp_path, S, fused):
    """2 query heads on 1 key/value head of 128: the kernel takes q and k
    at 16 positions, and at 12 (no whole sublane tiles) ``_qkv`` keeps the
    ``rms_norm`` / ``rope`` lines and counts ``fused=0``; both ways the
    result is those lines'."""
    cfg = _config()
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    h = jax.random.normal(keys[0], (2, S, 32))
    pl = {"wq": jax.random.normal(keys[1], (32, 256)) / 6,
          "wk": jax.random.normal(keys[2], (32, 128)) / 6,
          "wv": jax.random.normal(keys[3], (32, 128)) / 6,
          "q_norm": 1 + 0.2 * jax.random.normal(keys[4], (128,)),
          "k_norm": 1 + 0.2 * jax.random.normal(keys[5], (128,))}
    T._qkv(pl, h, cfg, True, 3)             # off the monitor: nothing counts
    out = []
    assert _counted(tmp_path, lambda: out.extend(
        T._qkv(pl, h, cfg, True, 3))) == {(128, "head", 1, "half", fused): 2}
    q, k, v = out
    np.testing.assert_allclose(q, reference(
        h @ pl["wq"], pl["q_norm"], 2, 128, "head", True, 3),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(k, reference(
        h @ pl["wk"], pl["k_norm"], 1, 128, "head", True, 3),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(v, h @ pl["wv"])
    # a layer without positions and without a norm makes no call at all
    assert _counted(tmp_path, lambda: T._qkv(
        pl, h, _config(qk_norm=False), False)) == {}


@pytest.mark.parametrize("S,fused", [(16, 1), (12, 0)])
def test_latent_qkv_takes_the_kernel_where_the_shapes_allow(
        tmp_path, monkeypatch, S, fused):
    """Tiny Mistral-Small-4's layer (4 heads of 96 + 32, values of 128): the
    kernel takes q and k at 16 positions; at 12 (no whole sublane tiles)
    ``_latent_qkv`` runs the ``rope_pairs`` lines and counts ``fused=0``;
    both ways q, k and v are those lines'."""
    from paddle_tpu.models import mistral4

    cfg = mistral4.mistral4_tiny_config()
    params = T.init_transformer_params(jax.random.PRNGKey(4), cfg)
    pl = jax.tree.map(lambda a: a[0], params["params_layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, S, 64))
    out = []
    assert _counted(tmp_path, lambda: out.extend(
        T._latent_qkv(pl, h, cfg, 5))) == {(128, "none", 1, "pairs", fused): 2}
    monkeypatch.setattr(K, "supported", lambda *a: False)
    for a, r in zip(out, T._latent_qkv(pl, h, cfg, 5)):
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=2e-5)


# tiny model -> (sequence, the projections its forward counts): BERT (learned
# positions, no q/k norm) makes no call; Mistral-Small-4's latent q and k
# (``_latent_qkv``: heads of 96 + 32 = 128, the adjacent-pair convention)
# take the kernel; OLMoE's 4 heads of 16 are half a lane block, so it keeps
# the XLA lines; the others' widths are whole lane blocks and take the kernel
# (SmallThinker's and Trinity's layers without positions: no call, and the
# norm alone)
ENGAGED = {
    "bert": (32, set()),
    "mistral4": (64, {(128, "none", 1, "pairs", 1)}),
    "olmoe": (32, {(16, "whole", 1, "half", 0)}),
    "smallthinker": (64, {(128, "none", 1, "half", 1)}),
    "lfm2": (64, {(64, "head", 1, "half", 1)}),
    "brumby": (64, {(128, "head", 1, "half", 1)}),
    "trinity": (64, {(128, "head", 1, "half", 1), (128, "head", 0, "half", 1)}),
    # a head of TWO lane blocks (``_latent_qkv_lanes``): dots3's two shapes'
    # q and k, beside its indexer's queries and key at a head of one; of
    # Kimi-Linear's latent layer without positions the k alone
    "dots3": (64, {(256, "none", 1, "pairs", 1), (128, "none", 1, "half", 1)}),
    "kimi_linear": (64, {(256, "none", 0, "pairs", 1)}),
}


@pytest.mark.parametrize("model", list(ENGAGED))
def test_which_tiny_models_take_the_kernel(tmp_path, model):
    import importlib

    from paddle_tpu.parallel import decoder

    seq, want = ENGAGED[model]
    module = importlib.import_module("paddle_tpu.models." + model)
    cfg = getattr(module, model + "_tiny_config")(remat=True)
    params = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    got = _counted(tmp_path, lambda: jax.eval_shape(
        lambda p, i: decoder.forward(p, i, cfg)[0], params, ids))
    assert set(got) == want
    # q and k, every call (a q without positions or a norm makes none)
    assert all(n % 2 == 0 for n in got.values()) or model == "kimi_linear"


def test_project_is_the_matmul_and_its_gradients():
    """``_project`` (the weight's gradient made beside the input's, behind
    one ``optimization_barrier``) is ``h @ w`` to the bit forward, and both
    gradients to a float32 sum's order: the barrier orders, it computes
    nothing."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(keys[0], (2, 16, 32))
    w = jax.random.normal(keys[1], (32, 128)) / 6
    g = jax.random.normal(keys[2], (2, 16, 128))
    want = jax.value_and_grad(lambda h, w: jnp.sum((h @ w) * g), (0, 1))(h, w)
    got = jax.value_and_grad(
        lambda h, w: jnp.sum(T._project(h, w) * g), (0, 1))(h, w)
    np.testing.assert_array_equal(got[0], want[0])
    for a, r in zip(got[1], want[1]):
        assert a.dtype == r.dtype
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)
