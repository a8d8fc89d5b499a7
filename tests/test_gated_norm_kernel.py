"""``kernels/gated_norm.py`` (the Mamba-2 mixer's gate and grouped RMS norm
in one pass each way) in Pallas interpret mode against the lines it
replaces, ``mamba2_mixer``'s ``jnp`` gate and norm (``gated_norm_reference``):
the output and every gradient; and ``mamba2_mixer`` taking the kernel where
the shapes allow and those lines where not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.kernels import gated_norm as K
from paddle_tpu.models import nemotron_h
from paddle_tpu.parallel import transformer as T

NAMES = ("out", "dy", "dz", "dgate_norm")
EPS = 1e-5


def operands(b, S, d, P, dtype=jnp.float32, seed=0):
    """y [b, S, d], z [b, S, P] (the gate its last d lanes), the scale, and
    the cotangents of the output and of z's other lanes (the xBC that the
    filter reads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return ((jax.random.normal(ks[0], (b, S, d)).astype(dtype),
             jax.random.normal(ks[1], (b, S, P)).astype(dtype),
             1.0 + 0.2 * jax.random.normal(ks[2], (d,))),
            jax.random.normal(ks[3], (b, S, d)),
            jax.random.normal(ks[4], (b, S, P - d)))


def kernel(y, z, w, groups):
    return K.gated_norm(y, z, w, groups=groups, eps=EPS)


def reference(y, z, w, groups):
    """Today's fallback lines of ``mamba2_mixer`` behind its slice of z."""
    return K.gated_norm_reference(y, z[..., z.shape[-1] - y.shape[-1]:], w,
                                  groups, EPS)


def value_and_grads(fn, args, g, gx, groups):
    """(output, dy, dz, dgate_norm) of ``sum(fn(y, z, w) * g) + sum(z's
    other lanes * gx)``."""
    def loss(y, z, w):
        out = fn(y, z, w, groups)
        first = z.shape[-1] - y.shape[-1]
        return (jnp.sum(out.astype(jnp.float32) * g)
                + jnp.sum(z[..., :first].astype(jnp.float32) * gx), out)

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(*args)
    return (out,) + tuple(grads)


@pytest.fixture
def row_blocks(monkeypatch):
    """The kernels' blocks at most this many rows tall, so that a tiny
    sequence is several grid steps and the scale's gradient sums over
    them."""
    def cap(rows):
        monkeypatch.setattr(K, "ROW_BLOCKS", tuple(
            r for r in K.ROW_BLOCKS if r <= rows))
    return cap


def _close(name, a, r, dtype):
    assert a.shape == r.shape and a.dtype == r.dtype, name
    a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
    if dtype == jnp.bfloat16 and name != "dgate_norm":
        # one rounding each; a value in a thousand lies either side of a
        # rounding boundary by the order of a float32 sum
        assert np.mean(a != r) < 2e-3, name
        np.testing.assert_allclose(a, r, rtol=2 ** -7, atol=1e-6,
                                   err_msg=name)
    else:           # float32 both ways; the scale's sums over b * S rows
        np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5, err_msg=name)


# 96 rows: ROW_BLOCKS' default gives one block of 32 (the tallest that
# divides) and the cap blocks of 16 (six grid steps a group); 64 rows: one
# block whole, or four; P = d the gate alone, P = 3 d behind the filter's
# lanes in the packed projection
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "alone"])
@pytest.mark.parametrize("S,block", [(64, 64), (64, 16), (96, 32), (96, 16)])
@pytest.mark.parametrize("groups,lanes", [(1, 128), (2, 128), (8, 128),
                                          (1, 512), (2, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_equals_the_lines_it_replaces(row_blocks, dtype, groups, lanes,
                                             S, block, packed):
    b, d = 2, groups * lanes
    row_blocks(block)
    assert K.block_rows(S, lanes, jnp.dtype(dtype).itemsize) == block
    args, g, gx = operands(b, S, d, 3 * d if packed else d, dtype)
    got = value_and_grads(kernel, args, g, gx, groups)
    want = value_and_grads(reference, args, g, gx, groups)
    for name, a, r in zip(NAMES, got, want):
        _close(name, a, r, dtype)
    if packed:      # the filter's lanes of z: the other cotangent alone
        np.testing.assert_array_equal(
            np.asarray(got[2][..., :2 * d], np.float32),
            np.asarray(gx.astype(dtype), np.float32))


@pytest.mark.parametrize("groups,lanes", [(8, 128), (2, 512)])
def test_bf16_is_no_further_from_float32_than_the_lines(row_blocks, groups,
                                                        lanes):
    """The gate, the statistic and the scale in float32 and ONE rounding, as
    the replaced lines: never further from the float32 lines than they
    are."""
    row_blocks(16)
    d = groups * lanes
    args, g, gx = operands(2, 64, d, 2 * d, jnp.bfloat16, seed=1)
    exact = value_and_grads(
        reference, tuple(a.astype(jnp.float32) for a in args), g, gx, groups)
    got = value_and_grads(kernel, args, g, gx, groups)
    old = value_and_grads(reference, args, g, gx, groups)
    assert got[0].dtype == got[1].dtype == got[2].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)
    for name, a, o, e in zip(NAMES, got, old, exact):
        assert np.abs(f32(a) - f32(e)).max() \
            <= 1.01 * np.abs(f32(o) - f32(e)).max() + 1e-5, name


def test_a_group_s_statistic_is_its_own():
    """Rows of one group scaled a thousandfold leave the other group's
    output as it was (and their own, the norm's point, almost): the
    statistic never crosses a lane block."""
    (y, z, w), _, _ = operands(1, 16, 256, 256, seed=2)
    out = kernel(y, z, w, 2)
    loud = kernel(y.at[..., :128].multiply(1e3), z, w, 2)
    np.testing.assert_array_equal(out[..., 128:], loud[..., 128:])
    np.testing.assert_allclose(out[..., :128], loud[..., :128], rtol=1e-3,
                               atol=1e-4)
    # a row of zeros: eps keeps the statistic finite and the output zero
    still = kernel(y.at[0, 3].set(0.0), z, w, 2)
    assert float(jnp.abs(still[0, 3]).max()) == 0.0


@pytest.mark.parametrize("shape,groups,packed_width,itemsize,takes", [
    ((2, 8192, 4096), 8, 10240, 2, True),   # nemotron3_nano_30b_a3b.s8192_scan
    ((2, 64, 256), 2, 768, 4, True),        # the tiny configuration
    ((1, 64, 1024), 1, 1024, 2, True),      # one group, the gate alone
    ((1, 64, 2048), 1, 2048, 2, False),     # a group past MAX_GROUP_LANES
    ((1, 64, 192), 2, 192, 4, False),       # a group off a lane tile
    ((1, 64, 256), 3, 256, 4, False),       # channels off the groups
    ((1, 64, 1024), 2, 1024 + 256, 2, False),   # the gate off a group's edge
    ((1, 64, 256), 2, 128, 4, False),       # a packed array too narrow
    ((1, 60, 256), 2, 256, 4, False),       # positions off a sublane tile
    ((1, 24, 256), 2, 256, 2, False),       # bf16 tiles hold 16 rows
])
def test_supported_takes_whole_lane_tiles_on_a_group_s_edge(
        shape, groups, packed_width, itemsize, takes):
    assert K.supported(shape, groups, packed_width, itemsize) is takes
    if not takes and packed_width >= shape[-1]:
        dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
        with pytest.raises(ValueError):
            K.gated_norm(jnp.zeros(shape, dtype),
                         jnp.zeros(shape[:2] + (packed_width,), dtype),
                         jnp.ones((shape[-1],)), groups=groups, eps=EPS)


def test_the_cell_s_geometry():
    """nemotron3_nano_30b_a3b.s8192_scan: a group's 512 channels a lane
    block, 1,024 rows a grid step walked 128 rows a turn (PERF.md section 6,
    PR 53, has the geometries tried)."""
    assert (K.block_rows(8192, 512, 2), K.walk_rows(1024, 512, 2)) \
        == (1024, 128)
    assert K.vmem_bytes(1024, 512, 2) < 16 * 2 ** 20


def _counted(tmp_path, trace):
    """{fused: calls} that ``trace()`` counts in
    ``monitor.kernels.gated_norm_calls`` under a monitor session."""
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        trace()
        return {r["labels"]["fused"]: r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.gated_norm_calls"}
    finally:
        monitor.disable()


# configuration -> (batch, sequence, what one mixer's trace counts): the
# cell's shape; the tiny configuration (two groups of 128 channels behind
# 768 lanes of xBC, float32); groups of 64 channels and positions off a
# sublane tile keep the ``jnp`` lines
ENGAGED = {
    "nemotron3_nano_30b_a3b.s8192_scan": (dict(n_layers=9), 2, 8192, {1: 1}),
    "tiny": (None, 2, 64, {1: 1}),
    "tiny, groups of 64 channels": (dict(d_inner=128, ssm_heads=8), 2, 64,
                                    {0: 1}),
    "tiny, 60 positions": (dict(scan_chunk=12), 2, 60, {0: 1}),
}


def _mixer_leaves(cfg, shapes_only):
    """One Mamba-2 layer's leaves of ``cfg``: the period's first position
    that holds a mixer's (``p<i>``: [periods, ...])."""
    make = lambda: T._init_params(jax.random.PRNGKey(1), cfg)["params_layers"]
    layers = jax.eval_shape(make) if shapes_only else make()
    at = layers["p%d" % cfg.layer_kinds.index(T.MAMBA2)]
    if shapes_only:
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), at)
    return jax.tree.map(lambda a: a[0], at)


@pytest.mark.parametrize("what", list(ENGAGED))
def test_which_shapes_take_the_kernel(tmp_path, what):
    kw, b, S, want = ENGAGED[what]
    cfg = nemotron_h.nemotron3_nano_30b_a3b_config(**kw) if "." in what \
        else nemotron_h.nemotron_h_tiny_config(**(kw or {}))
    pl = _mixer_leaves(cfg, shapes_only=True)
    h = jax.ShapeDtypeStruct((b, S, cfg.hidden), cfg.jdtype)
    assert _counted(tmp_path, lambda: jax.eval_shape(
        lambda pl, h: T.mamba2_mixer(pl, h, cfg), pl, h)) == want
    # off the monitor: nothing counts
    jax.eval_shape(lambda pl, h: T.mamba2_mixer(pl, h, cfg), pl, h)


def test_mamba2_mixer_gives_the_lines_numbers_either_way(tmp_path,
                                                         monkeypatch):
    """The tiny mixer, output and the gradients of its input and of every
    leaf, with the kernel and with ``supported`` patched false: the same
    numbers, and the counter reads ``fused=1`` and ``fused=0``."""
    cfg = nemotron_h.nemotron_h_tiny_config()
    pl = _mixer_leaves(cfg, shapes_only=False)
    pl["gate_norm"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(3), pl["gate_norm"].shape)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.hidden))
    g = jax.random.normal(jax.random.PRNGKey(4), h.shape)

    def run():
        return jax.value_and_grad(lambda pl, h: jnp.sum(
            T.mamba2_mixer(pl, h, cfg) * g), (0, 1))(pl, h)

    out = []
    assert _counted(tmp_path, lambda: out.append(run())) == {1: 1}
    monkeypatch.setattr(K, "supported", lambda *a: False)
    assert _counted(tmp_path, lambda: out.append(run())) == {0: 1}
    (got, got_grads), (want, want_grads) = out
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_grads)
    for (path, w), a in zip(flat, jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(
            a, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_receipt_s_programs_agree_at_a_tiny_shape(monkeypatch):
    """``scripts/nemotron_kernels_receipt.py``'s norm programs (the kernels
    on the packed projection; the ``jnp`` lines behind its slice; the lines
    in float32) as the chip run builds them, at 64 x 256 of 768 in two
    groups: the same four results, and the one-group control apart."""
    import importlib
    import os

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    receipt = importlib.import_module("nemotron_kernels_receipt")
    args, g = receipt.norm_operands(3, s=64, d=256, packed=768)
    assert args[1].shape == (receipt.B, 64, 768) \
        and args[1].dtype == jnp.bfloat16
    programs = receipt.norm_programs(groups=2)
    got, old, want = (programs[k](*args, g)
                      for k in ("kernel", "jnp", "float32"))
    assert len(got) == len(old) == len(want) == len(receipt.NORM_NAMES)
    for name, a, o, w in zip(receipt.NORM_NAMES, got, old, want):
        assert a.shape == o.shape == w.shape and a.dtype == o.dtype, name
        assert receipt._rel(a, w) <= 1.02 * receipt._rel(o, w) + 1e-6 \
            < receipt.NORM_LIMIT, name
    assert receipt._rel(got[0], receipt.norm_programs(groups=1)["float32"](
        *args, g)[0]) > receipt.NORM_LIMIT
