"""``kernels/kda_rows.py`` (what stands around the delta rule in the KDA
mixer: the L2 norm a head, the log-decays, the norm THEN the gate; one pass
each way on the flat arrays) in Pallas interpret mode against the lines it
replaces (``*_reference``): the output and every gradient, the parameters'
among them; and ``kda_mixer`` taking the flat path where a head is a lane
tile and those lines where not."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.kernels import gated_norm, kda_chunk, kda_rows as K
from paddle_tpu.models import kimi_linear, solar_open2
from paddle_tpu.parallel import transformer as T

EPS = 1e-5
F32 = jnp.float32


def operands(part, b, S, heads, dtype, seed=0):
    """(the operands of ``part`` as the mixer has them on [b, S, heads x
    128], the cotangent of its result)."""
    P = heads * 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = lambda k, scale=1.0: scale * jax.random.normal(k, (b, S, P))  # noqa
    if part == "l2_heads":
        return (rows(ks[0]).astype(dtype),), rows(ks[3]).astype(dtype)
    if part == "log_decay":
        step = jnp.exp(jax.random.uniform(
            ks[1], (P,), F32, math.log(1e-3), math.log(1e-1)))
        return ((rows(ks[0], 3.0), step + jnp.log(-jnp.expm1(-step)),
                 jnp.log(jax.random.uniform(ks[2], (heads,), F32, 1.0,
                                            16.0))), rows(ks[3]))
    return ((rows(ks[0]).astype(dtype), rows(ks[1], 2.0),
             1.0 + 0.2 * jax.random.normal(ks[2], (128,))),
            rows(ks[3]).astype(dtype))


def kernel(part):
    return {"l2_heads": lambda x: K.l2_heads(x, scale=0.3),
            "log_decay": K.log_decay,
            "norm_gate": lambda o, z, w: K.norm_gate(o, z, w, eps=EPS)}[part]


def lines(part):
    """The mixer's lines on the flat arrays, rounded where it rounds."""
    def flat(a, like):
        return a.reshape(like.shape)

    return {
        "l2_heads": lambda x: flat(K.l2_heads_reference(
            x, x.shape[-1] // 128, 0.3).astype(x.dtype), x),
        "log_decay": lambda pre, bias, a_log: flat(
            K.log_decay_reference(pre, bias, a_log), pre),
        "norm_gate": lambda o, z, w: flat(K.norm_gate_reference(
            o.reshape(o.shape[:2] + (-1, 128)), z, w, EPS).astype(o.dtype),
            o)}[part]


def value_and_grads(fn, args, g):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(g)


@pytest.fixture
def blocks(monkeypatch):
    """The kernels' blocks at most ``rows`` rows by ``lanes`` lanes, so that
    a tiny array is several grid steps each way and the parameters'
    gradients sum over them."""
    def cap(rows, lanes):
        monkeypatch.setattr(gated_norm, "ROW_BLOCKS", tuple(
            r for r in gated_norm.ROW_BLOCKS if r <= rows))
        monkeypatch.setattr(K, "BLOCK_LANES", tuple(
            n for n in K.BLOCK_LANES if n <= lanes))
    return cap


def _close(name, a, r, rounded):
    assert a.shape == r.shape and a.dtype == r.dtype, name
    a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
    if rounded:
        # one rounding each; a value in a thousand lies either side of a
        # rounding boundary by the order of a float32 sum
        assert np.mean(a != r) < 2e-3, name
        np.testing.assert_allclose(a, r, rtol=2 ** -7, atol=1e-6,
                                   err_msg=name)
    else:   # float32 both ways; the parameters' sums over b * S rows
        np.testing.assert_allclose(a, r, rtol=2e-5,
                                   atol=2e-5 * np.abs(r).max() + 1e-6,
                                   err_msg=name)


# 96 rows: the default gives one block of 32 (the tallest that divides) and
# the cap blocks of 16; 64 rows: one block whole, or four; four heads in one
# lane block, two, or one a grid step
@pytest.mark.parametrize("S,rows,lanes", [(64, 64, 512), (64, 16, 256),
                                          (96, 32, 128), (96, 16, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("part", K.PARTS)
def test_kernel_equals_the_lines_it_replaces(blocks, part, dtype, S, rows,
                                             lanes):
    blocks(rows, lanes)
    b, heads = 2, 4
    args, g = operands(part, b, S, heads, dtype)
    itemsize = min(a.dtype.itemsize for a in args if a.ndim == 3)
    assert K.geometry(S, heads * 128, itemsize)[::2] == (rows, lanes)
    got = value_and_grads(kernel(part), args, g)
    want = value_and_grads(lines(part), args, g)
    assert len(got) == len(want) == 1 + len(args)
    for i, (a, r) in enumerate(zip(got, want)):
        _close("%s[%d]" % (part, i), a, r,
               rounded=dtype == jnp.bfloat16 and a.dtype == jnp.bfloat16)


@pytest.mark.parametrize("part", ["l2_heads", "norm_gate"])
def test_bf16_is_no_further_from_float32_than_the_lines(blocks, part):
    """The statistic, the scale and the gate in float32 and ONE rounding,
    as the replaced lines: never further from the float32 lines than they
    are."""
    blocks(16, 256)
    args, g = operands(part, 2, 64, 4, jnp.bfloat16, seed=1)
    exact = value_and_grads(lines(part), tuple(a.astype(F32) for a in args),
                            g.astype(F32))
    got = value_and_grads(kernel(part), args, g)
    old = value_and_grads(lines(part), args, g)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
    for i, (a, o, e) in enumerate(zip(got, old, exact)):
        assert np.abs(f32(a) - f32(e)).max() \
            <= 1.01 * np.abs(f32(o) - f32(e)).max() \
            + 1e-6 * max(1.0, np.abs(f32(e)).max()), (part, i)


def test_the_decays_at_the_extremes_are_finite_and_never_positive(blocks):
    """A pre-activation of +-30 at the fastest rate (``a_log`` = ln 16): no
    ``exp(+large)`` is formed, g <= 0, and the three gradients are finite
    and the lines'."""
    blocks(16, 128)
    b, S, heads = 1, 32, 2
    pre = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(5), 0.5,
                                         (b, S, heads * 128)), 30.0, -30.0)
    args = (pre, jnp.zeros((heads * 128,)), jnp.full((heads,), math.log(16.)))
    g = jax.random.normal(jax.random.PRNGKey(6), pre.shape)
    got = value_and_grads(K.log_decay, args, g)
    want = value_and_grads(lines("log_decay"), args, g)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    assert float(got[0].max()) <= 0.0 and float(got[0].min()) < -479.0
    for i, (a, r) in enumerate(zip(got, want)):
        _close("log_decay[%d]" % i, a, r, rounded=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_head_s_statistic_is_its_own_and_eps_holds_a_zero_row(dtype):
    """Rows of one head scaled a thousandfold leave the other heads' output
    as it was: the statistic never crosses a lane tile.  A head's row of
    zeros: the eps under the root keeps the output zero and the gradient
    finite (``dy * scale / sqrt(eps)``)."""
    (x,), g = operands("l2_heads", 1, 32, 2, dtype, seed=2)
    run = kernel("l2_heads")
    loud = run(x.at[..., :128].multiply(1e3))
    np.testing.assert_array_equal(np.asarray(run(x)[..., 128:], np.float32),
                                  np.asarray(loud[..., 128:], np.float32))
    still = x.at[0, 3, :128].set(0.0)
    out, dx = value_and_grads(run, (still,), g)
    assert float(jnp.abs(out[0, 3, :128]).max()) == 0.0
    assert bool(jnp.all(jnp.isfinite(dx.astype(F32))))
    np.testing.assert_allclose(
        np.asarray(dx[0, 3, :128], np.float32),
        np.asarray(g[0, 3, :128], np.float32) * 0.3 * 1e3, rtol=2 ** -7)
    o, z, w = operands("norm_gate", 1, 32, 2, dtype, seed=2)[0]
    gate = kernel("norm_gate")
    np.testing.assert_array_equal(
        np.asarray(gate(o, z, w)[..., 128:], np.float32),
        np.asarray(gate(o.at[..., :128].multiply(1e3), z, w)[..., 128:],
                   np.float32))
    assert float(jnp.abs(gate(o.at[0, 3].set(0.0), z, w)[0, 3]).max()) == 0.0


@pytest.mark.parametrize("shape,head_dim,itemsize,takes", [
    ((1, 16384, 4096), 128, 2, True),   # kimi_linear_48b_a3b.s16384_scan
    ((1, 4096, 8192), 128, 2, True),    # solar_open2_250b.s4096_scan, 64 heads
    ((2, 64, 512), 128, 4, True),
    ((1, 128, 128), 128, 2, True),      # one head
    ((2, 64, 32), 16, 4, False),        # the tiny configuration's heads of 16
    ((1, 64, 256), 64, 2, False),       # two heads a lane tile
    ((1, 64, 512), 256, 2, False),      # a head two lane tiles
    ((1, 60, 256), 128, 4, False),      # positions off a sublane tile
    ((1, 24, 256), 128, 2, False),      # bf16 tiles hold 16 rows
])
def test_supported_takes_a_head_a_lane_tile(shape, head_dim, itemsize, takes):
    assert K.supported(shape, head_dim, itemsize) is takes
    if not takes and shape[-1] % 128 == 0 and head_dim == 128:
        dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
        with pytest.raises(ValueError):
            K.l2_heads(jnp.zeros(shape, dtype), scale=1.0)
        with pytest.raises(ValueError):
            K.norm_gate(jnp.zeros(shape, dtype), jnp.zeros(shape, F32),
                        jnp.ones((128,)), eps=EPS)


def test_the_cell_s_geometry():
    """kimi_linear_48b_a3b.s16384_scan: four heads a lane block, 1,024 rows
    a grid step walked 128 rows a turn (PERF.md section 6, PR 60, has the
    geometries tried); the widest backward's blocks within 20 MiB."""
    assert K.geometry(16384, 4096, 2) == (1024, 128, 512)
    # solar_open2_250b.s4096_scan: 64 heads are sixteen lane blocks of the
    # same four, four grid steps of rows: the same blocks, the same VMEM
    assert K.geometry(4096, 8192, 2) == (1024, 128, 512)
    assert max(K.vmem_bytes(part, 1024, 512, 2) for part in K.PARTS) \
        == K.vmem_bytes("norm_gate", 1024, 512, 2) < 20 * 2 ** 20


def _counted(trace):
    """{(part, fused): calls} that ``trace()`` counts in
    ``monitor.kernels.kda_rows_calls`` under a monitor session."""
    mon = monitor.enable()
    try:
        mon.registry.reset()        # the registry is the process's
        trace()
        return {(r["labels"]["part"], r["labels"]["fused"]): r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.kda_rows_calls"}
    finally:
        monitor.disable()


def _mixer_leaves(cfg, seed=None):
    """One KDA layer's leaves of ``cfg``: shapes alone, or seeded."""
    keys = jax.random.split(jax.random.PRNGKey(seed or 0), 1)
    stack = lambda fold, fan, shape: jax.vmap(          # noqa: E731
        lambda key: jax.random.normal(jax.random.fold_in(key, fold), shape,
                                      cfg.jdtype) * fan ** -0.5)(keys)
    make = lambda: {n: a[0] for n, a in                 # noqa: E731
                    T._kda_leaves(stack, keys, cfg).items()}
    return jax.eval_shape(make) if seed is None else make()


# configuration -> (batch, sequence, fused): the cell's widths; the tiny
# configuration's heads of 16; heads of 128 at 60 positions (no whole stack)
ENGAGED = {
    "kimi_linear_48b_a3b.s16384_scan": (None, 1, 16384, 1),
    "solar_open2_250b.s4096_scan": ("solar", 1, 4096, 1),
    "tiny": (dict(), 2, 64, 0),
    "tiny, a head 128 wide": (dict(kda_heads=2, kda_head_dim=128,
                                   kda_chunk=64, max_seq=128), 1, 128, 1),
    "tiny, a head 128 wide, 60 positions": (
        dict(kda_heads=2, kda_head_dim=128, kda_chunk=16), 1, 60, 0),
}


@pytest.mark.parametrize("what", list(ENGAGED))
def test_which_shapes_take_the_flat_path(what):
    kw, b, S, fused = ENGAGED[what]
    if kw == "solar":       # 64 heads of 128, strengths in (0, 2)
        cfg = solar_open2.solar_open2_250b_config(n_layers=4)
        assert (cfg.kda_heads, cfg.kda_beta_scale) == (64, 2.0)
    else:
        cfg = kimi_linear.kimi_linear_48b_a3b_config(n_layers=5) \
            if kw is None else kimi_linear.kimi_linear_tiny_config(**kw)
    pl = _mixer_leaves(cfg)
    h = jax.ShapeDtypeStruct((b, S, cfg.hidden), cfg.jdtype)
    trace = lambda: jax.eval_shape(                     # noqa: E731
        lambda pl, h: T.kda_mixer(pl, h, cfg), pl, h)
    assert _counted(trace) == {(part, fused): 1 for part in K.PARTS}
    assert trace().shape == h.shape     # off the monitor: nothing counts


@pytest.mark.parametrize("beta_scale", [1.0, 2.0],
                         ids=["strengths under 1", "strengths under 2"])
def test_kda_mixer_gives_the_lines_numbers_either_way(monkeypatch,
                                                      beta_scale):
    """The mixer at two heads of 128, output and the gradients of its input
    and of every leaf, on the flat path and with ``kda_rows.supported``
    patched false (the ``jnp`` lines around ``kda_chunked``): the same
    numbers, and the counter reads ``fused=1`` and ``fused=0``; at
    ``kda_beta_scale`` 2 (``w_beta`` steep enough that the strengths reach
    both ends of (0, 2)) as at 1."""
    cfg = kimi_linear.kimi_linear_tiny_config(
        kda_heads=2, kda_head_dim=128, kda_chunk=64, max_seq=128,
        kda_beta_scale=beta_scale)
    pl = _mixer_leaves(cfg, seed=11)
    if beta_scale > 1:
        pl["w_beta"] = 4.0 * pl["w_beta"]
    pl["o_norm"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(3), pl["o_norm"].shape)
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 128, cfg.hidden))
    g = jax.random.normal(jax.random.PRNGKey(13), h.shape)
    if beta_scale > 1:
        beta = T.kda_write_strength(pl, h, cfg)
        assert float(beta.min()) < 0.2 and float(beta.max()) > 1.8

    def run():
        return jax.value_and_grad(lambda pl, h: jnp.sum(
            T.kda_mixer(pl, h, cfg) * g), (0, 1))(pl, h)

    out = []
    assert _counted(lambda: out.append(run())) \
        == {(part, 1): 1 for part in K.PARTS}
    monkeypatch.setattr(K, "supported", lambda *a: False)
    assert _counted(lambda: out.append(run())) \
        == {(part, 0): 1 for part in K.PARTS}
    (got, got_grads), (want, want_grads) = out
    np.testing.assert_allclose(got, want, rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_grads)
    assert len(flat) == len(pl) + 1
    for (path, w), a in zip(flat, jax.tree.leaves(got_grads)):
        np.testing.assert_allclose(
            a, w, rtol=2e-3, atol=2e-4 * float(jnp.abs(w).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_flat_path_forms_no_view_by_heads():
    """The traced mixer at the cell's widths: between the filters and ``wo``
    no equation's result is a [b, S, heads, 128] array (the view that is a
    copy on the chip); the tiny configuration's lines form them."""
    def views(cfg, b, S):
        h = jax.ShapeDtypeStruct((b, S, cfg.hidden), cfg.jdtype)
        jaxpr = jax.make_jaxpr(lambda pl, h: T.kda_mixer(pl, h, cfg))(
            _mixer_leaves(cfg), h)
        return [v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars
                if v.aval.shape[:2] == (b, S) and len(v.aval.shape) == 4]

    assert not views(kimi_linear.kimi_linear_48b_a3b_config(n_layers=5), 1,
                     16384)
    assert views(kimi_linear.kimi_linear_tiny_config(), 2, 64)
    assert kda_chunk.supported((1, 16384, 32, 128), 128, 64, jnp.bfloat16)


def test_the_receipt_s_programs_agree_at_a_tiny_shape(monkeypatch):
    """``scripts/kda_rows_receipt.py``'s programs (the kernels; the lines on
    the flat arrays) as the chip run builds them, at 64 x 4 heads: the same
    results, and its bytes' least follows the element types."""
    import importlib
    import os

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    receipt = importlib.import_module("kda_rows_receipt")
    monkeypatch.setattr(receipt, "S", 64)
    monkeypatch.setattr(receipt, "H", 4)
    monkeypatch.setattr(receipt, "P", 512)
    made = receipt.parts(jnp.bfloat16)
    assert tuple(made) == K.PARTS
    for part, (fused, old, args, g) in made.items():
        assert args[0].shape == (receipt.B, 64, 512)
        got, want = receipt.both(fused)(args, g), receipt.both(old)(args, g)
        assert len(got) == len(want) == 1 + len(args)
        for a, w in zip(got, want):
            assert a.shape == w.shape and a.dtype == w.dtype, part
            assert receipt._rel(a, w) < 5e-3, part
        forward, backward = receipt.BYTES[part]
        sized = [a for a in args if a.ndim == 3]
        assert forward == sum(a.dtype.itemsize for a in sized) \
            + got[0].dtype.itemsize
        assert backward == sum(2 * a.dtype.itemsize for a in sized) \
            + got[0].dtype.itemsize
