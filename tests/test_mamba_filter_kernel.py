"""``kernels/mamba_filter.py`` (the Mamba mixer's causal filter, bias and
``silu`` in one pass each way) in Pallas interpret mode against the lines it
replaces, ``mamba_operands``' ``jnp`` filter (``mamba_filter_reference``):
the output and every gradient; and ``mamba_operands`` taking the kernel
where the shapes allow and those lines where not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.kernels import mamba_filter as K
from paddle_tpu.models import jamba
from paddle_tpu.parallel import transformer as T

NAMES = ("out", "dx", "dconv_w", "dconv_b", "dbefore")


def operands(b, S, d, W, taps, before, dtype=jnp.float32, seed=0):
    """x [b, S, W] (the filter's input its first d lanes), the taps, the
    bias, the rows before position 0 (or None), and the cotangents of the
    output and of x's other lanes (a z that something else reads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return ((jax.random.normal(ks[0], (b, S, W)).astype(dtype),
             jax.random.uniform(ks[1], (taps, d), minval=-0.5, maxval=0.5),
             0.1 * jax.random.normal(ks[2], (d,)),
             jax.random.normal(ks[3], (b, taps - 1, d)) if before else None),
            jax.random.normal(ks[4], (b, S, d)),
            jax.random.normal(ks[5], (b, S, W - d)))


def kernel(x, conv_w, conv_b, before, d):
    return K.mamba_filter(x, conv_w, conv_b, before, width=d)


def reference(x, conv_w, conv_b, before, d):
    """Today's lines of ``mamba_operands`` behind its split."""
    return K.mamba_filter_reference(x[..., :d], conv_w, conv_b, before)


def value_and_grads(fn, args, g, gz):
    """(output, dx, dconv_w, dconv_b, dbefore or None) of ``sum(fn(x, ...) *
    g) + sum(x's other lanes * gz)``."""
    d = g.shape[-1]

    def loss(*a):
        out = fn(*a, d)
        return (jnp.sum(out.astype(jnp.float32) * g)
                + jnp.sum(a[0][..., d:].astype(jnp.float32) * gz), out)

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
        *args)
    return (out,) + tuple(grads)


@pytest.fixture
def row_blocks(monkeypatch):
    """The kernels' blocks at most this many rows tall, so that a tiny
    sequence crosses block edges."""
    def cap(rows):
        monkeypatch.setattr(K, "ROW_BLOCKS", tuple(
            r for r in K.ROW_BLOCKS if r <= rows))
    return cap


# 64 rows in blocks of 16 (four: the halo crosses three edges behind and
# three ahead) and of 32 (two, each walked in turns of 16 or 32); W = 2 d is
# the packed projection read in place, W = d a projection of its own
@pytest.mark.parametrize("before", [False, True], ids=["zeros", "rows"])
@pytest.mark.parametrize("packed", [True, False], ids=["packed", "alone"])
@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_equals_the_lines_it_replaces(row_blocks, dtype, block, packed,
                                             before):
    b, S, d, taps = 2, 64, 256, 4
    row_blocks(block)
    assert K.block_rows(S, jnp.dtype(dtype).itemsize) == block
    args, g, gz = operands(b, S, d, 2 * d if packed else d, taps, before,
                           dtype)
    got = value_and_grads(kernel, args, g, gz)
    want = value_and_grads(reference, args, g, gz)
    assert got[4] is None if not before else got[4].shape == (b, taps - 1, d)
    for name, a, r in zip(NAMES, got, want):
        if r is None:
            continue
        assert a.shape == r.shape and a.dtype == r.dtype, name
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        if dtype == jnp.bfloat16 and name in ("out", "dx"):
            # one rounding each; a value in a thousand lies either side of
            # a rounding boundary by the order of a float32 sum
            assert np.mean(a != r) < 2e-3, name
            np.testing.assert_allclose(a, r, rtol=2 ** -7, atol=1e-6)
        else:       # float32 both ways; sums over b * S rows for the taps
            np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5,
                                       err_msg=name)


@pytest.mark.parametrize("before", [False, True], ids=["zeros", "rows"])
def test_bf16_is_no_further_from_float32_than_the_lines(row_blocks, before):
    """The filter, the bias and ``silu`` in float32 and ONE rounding, as the
    replaced lines: never further from the float32 lines than they are."""
    row_blocks(16)
    args, g, gz = operands(2, 64, 128, 256, 4, before, jnp.bfloat16, seed=1)
    exact = value_and_grads(
        reference, (args[0].astype(jnp.float32),) + args[1:], g, gz)
    got = value_and_grads(kernel, args, g, gz)
    old = value_and_grads(reference, args, g, gz)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)
    for name, a, o, e in zip(NAMES, got, old, exact):
        if e is not None:
            assert np.abs(f32(a) - f32(e)).max() \
                <= 1.01 * np.abs(f32(o) - f32(e)).max() + 1e-5, name


@pytest.mark.parametrize("taps,S,b", [(1, 8, 1), (2, 24, 1), (3, 40, 2),
                                      (8, 16, 1)])
def test_any_number_of_taps_up_to_a_tile(taps, S, b):
    """One tap (no halo at all) to eight (seven rows of it), sequences of
    one block of 8, 24, 40 rows (walked 8 at a time) and 16."""
    args, g, gz = operands(b, S, 128, 128, taps, taps > 1, seed=2)
    got = value_and_grads(kernel, args, g, gz)
    want = value_and_grads(reference, args, g, gz)
    for name, a, r in zip(NAMES, got, want):
        if r is not None and r.size:
            np.testing.assert_allclose(a, r, rtol=2e-5, atol=5e-5,
                                       err_msg=name)


def test_the_rows_ahead_and_behind_a_block_edge_reach_across_it(row_blocks):
    """A lone spike in the input's LAST row of a block shows in the next
    block's first ``taps - 1`` outputs (the halo behind), and a lone
    cotangent in a block's FIRST row reaches the block before it (the halo
    ahead): both exactly the taps."""
    row_blocks(16)
    d, taps, S = 128, 4, 48
    conv_w = jnp.arange(1, taps + 1, dtype=jnp.float32)[:, None] \
        * jnp.ones((taps, d))
    x = jnp.zeros((1, S, d)).at[0, 15].set(1.0)
    zero = jnp.zeros((d,))
    out, vjp = jax.vjp(lambda x: K.mamba_filter(x, conv_w, zero), x)
    pre = np.zeros(S)
    pre[15:19] = [4, 3, 2, 1]           # tap taps - 1 - back meets t - back
    np.testing.assert_allclose(out[0, :, 0], pre / (1 + np.exp(-pre)),
                               rtol=1e-6)
    # silu'(0) = 1/2 where the input is zero: the cotangent at row 32 comes
    # back over rows 29 .. 32 as the taps
    dx, = vjp(jnp.zeros((1, S, d)).at[0, 32].set(2.0))
    assert float(jnp.abs(jnp.delete(dx[0, :, 0], np.arange(29, 33))).max()) \
        == 0.0
    np.testing.assert_allclose(dx[0, 29:33, 0], [1, 2, 3, 4], rtol=1e-6)


@pytest.mark.parametrize("shape,taps,itemsize,takes", [
    ((1, 8192, 5120), 4, 2, True),          # jamba2_3b.s8192_scan
    ((2, 64, 128), 4, 4, True),             # the tiny configuration
    ((2, 8, 128), 4, 4, True),              # its row blocks of 8 positions
    ((1, 64, 96), 4, 4, False),             # channels off a lane block
    ((1, 64, 5120 + 64), 4, 2, False),
    ((1, 60, 128), 4, 4, False),            # positions off a sublane tile
    ((1, 24, 128), 4, 2, False),            # bf16 tiles hold 16 rows
    ((1, 64, 128), 9, 4, False),            # a halo past one float32 tile
])
def test_supported_takes_whole_lane_blocks_and_sublane_tiles(shape, taps,
                                                             itemsize, takes):
    assert K.supported(shape, taps, itemsize) is takes
    if not takes:
        with pytest.raises(ValueError):
            K.mamba_filter(
                jnp.zeros(shape, jnp.float32 if itemsize == 4
                          else jnp.bfloat16),
                jnp.zeros((taps, shape[-1])), jnp.zeros((shape[-1],)))


def test_the_cell_s_geometry():
    """jamba2_3b.s8192_scan: blocks of 2,048 rows x 512 lanes walked 32
    rows a turn (PERF.md section 6, PR 51, has the geometries tried)."""
    assert (K.block_rows(8192, 2), K.block_lanes(5120),
            K.walk_rows(2048, 2)) == (2048, 512, 32)
    assert K.vmem_bytes(2048, 512, 2) < 32 * 2 ** 20


def _counted(tmp_path, trace):
    """{(fused, halo): calls} that ``trace()`` counts in
    ``monitor.kernels.mamba_filter_calls`` under a monitor session."""
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        trace()
        return {(r["labels"]["fused"], r["labels"]["halo"]): r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.mamba_filter_calls"}
    finally:
        monitor.disable()


# configuration -> (batch, sequence, ROW_BLOCK_ELEMENTS or None, what one
# mixer's trace counts): the cell's shape whole (8,192 x 10,240 elements are
# under ROW_BLOCK_ELEMENTS: zeros before position 0); the tiny configuration
# (128 channels, float32) whole and in row blocks of 8 positions (the rows
# before a block projected again); channels off a lane block and positions
# off a sublane tile keep the ``jnp`` lines
ENGAGED = {
    "jamba2_3b.s8192_scan": (dict(n_layers=14), 1, 8192, None,
                             {(1, "zeros"): 1}),
    "tiny": (None, 2, 64, None, {(1, "zeros"): 1}),
    "tiny, row blocks": (None, 2, 64, 4 * 8 * 2 * 2 * 128, {(1, "rows"): 1}),
    "tiny, 96 channels": (dict(d_inner=96), 2, 64, None, {(0, "zeros"): 1}),
    "tiny, 60 positions": (dict(scan_chunk=12), 2, 60, None,
                           {(0, "zeros"): 1}),
}


@pytest.mark.parametrize("what", list(ENGAGED))
def test_which_shapes_take_the_kernel(tmp_path, monkeypatch, what):
    kw, b, S, elements, want = ENGAGED[what]
    cfg = jamba.jamba2_3b_config(**kw) if "." in what \
        else jamba.jamba_tiny_config(**(kw or {}))
    if elements:
        monkeypatch.setattr(T, "ROW_BLOCK_ELEMENTS", elements)
    pl = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype),
        jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
        ["params_layers"]["r0"])
    h = jax.ShapeDtypeStruct((b, S, cfg.hidden), cfg.jdtype)
    assert _counted(tmp_path, lambda: jax.eval_shape(
        lambda pl, h: T.mamba_mixer(pl, h, cfg), pl, h)) == want
    # off the monitor: nothing counts
    jax.eval_shape(lambda pl, h: T.mamba_mixer(pl, h, cfg), pl, h)


@pytest.mark.parametrize("d_inner,fused", [(128, 1), (96, 0)])
def test_mamba_operands_gives_the_lines_numbers_either_way(tmp_path, d_inner,
                                                           fused):
    """The mixer's operands with the kernel (128 channels) and without (96):
    x is the ``jnp`` lines' on the projection's x half; z its other half,
    or, behind the kernel, the whole projection for the scan to read z's
    lanes of."""
    cfg = jamba.jamba_tiny_config(d_inner=d_inner)
    params = T._init_params(jax.random.PRNGKey(1), cfg)
    pl = jax.tree.map(lambda a: a[0, 0], params["params_layers"]["r0"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.hidden))
    out = []
    assert _counted(tmp_path, lambda: out.extend(
        T.mamba_operands(pl, h, cfg, h, 0))) == {(fused, "zeros"): 1}
    x_raw, z = jnp.split(h @ pl["w_in"], 2, axis=-1)
    np.testing.assert_allclose(out[0], K.mamba_filter_reference(
        x_raw, pl["conv_w"], pl["conv_b"]), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(out[1], h @ pl["w_in"] if fused else z)


def test_the_receipt_s_two_programs_agree_at_a_tiny_shape(monkeypatch):
    """``scripts/jamba_kernels_receipt.py``'s filter programs (the kernels on
    the packed projection; the ``jnp`` lines behind the split) as the chip
    run builds them, at 64 x 256 of 512: the same five results."""
    import importlib
    import os

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    receipt = importlib.import_module("jamba_kernels_receipt")
    monkeypatch.setattr(receipt, "D", 256)
    args, g = receipt.filter_operands(3, S=64, before=True)
    assert args[0].shape == (1, 64, 512) and args[0].dtype == jnp.bfloat16
    programs = receipt._filter_programs()
    got, old = programs["kernel"](*args, g), programs["jnp"](*args, g)
    assert len(got) == len(old) == len(NAMES)
    for name, a, o in zip(NAMES, got, old):
        assert a.shape == o.shape and a.dtype == o.dtype, name
        assert receipt._rel(a, o) < 2e-3, name
