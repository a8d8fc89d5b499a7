"""``kernels/flash_delta.py`` (the flash backward's ``delta = sum_d(o * do)``
in one pass) in Pallas interpret mode against the ``jnp`` lines it replaces in
``flash_attention._bwd``: the statistic, its shape and layout, the shapes
taken and refused, and ``flash_attention_packed``'s gradients with the
kernel against the same backward on the lines."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.kernels import flash_delta as K

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def operands(B, S, H, D, dtype, seed=0):
    ko, kd = jax.random.split(jax.random.PRNGKey(seed))
    return ((2 * jax.random.normal(ko, (B, S, H * D))).astype(dtype),
            jax.random.normal(kd, (B, S, H * D)).astype(dtype))


def exact(o, do, D):
    """The statistic in float64, on the host."""
    B, S, W = o.shape
    hpb = 128 // D
    prod = np.asarray(o, np.float64) * np.asarray(do, np.float64)
    return prod.reshape(B, S, W // 128, hpb, D).sum(-1).transpose(0, 2, 1, 3)


# heads: 28, 48 and 20 are the grouped cells' query heads (a group is the
# backward kernels' business: the statistic is a query head's), 7 and 3 odd
# counts of lane blocks; rows from one float32 tile to the tallest block
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("B,S,H,D", [
    (1, 8, 1, 128), (2, 48, 3, 128), (4, 16, 7, 128), (1, 512, 2, 128),
    (1, 256, 28, 128), (2, 128, 48, 128), (1, 64, 20, 128),
    (2, 48, 4, 64), (1, 16, 2, 64), (4, 32, 32, 64), (1, 512, 6, 64),
    (1, 16, 4, 32), (2, 80, 8, 32), (1, 1024, 4, 32)])
def test_kernel_equals_the_lines_it_replaces(B, S, H, D, dtype):
    if dtype == jnp.bfloat16 and S % 16:
        S *= 2                          # whole bfloat16 tiles of rows
    o, do = operands(B, S, H, D, dtype, seed=S + H)
    got = K.flash_delta(o, do, head_dim=D)
    want = K.flash_delta_reference(o, do, D)
    hpb = 128 // D
    assert got.shape == want.shape == (B, H // hpb, S, hpb)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # float32 inside: no further from the float64 sum than the lines are,
    # but for the order of a float32 sum of ``D`` products
    true = exact(o, do, D)
    assert np.abs(got - true).max() <= 2 * np.abs(want - true).max() + 1e-6


CELLS = {   # batch, positions, query heads, kv heads, head width, window
    "smallthinker_21b_a3b.s16384_scan": (1, 16384, 28, 4, 128, 4096),
    "trinity_large_preview.s6144_scan": (1, 6144, 48, 8, 128, 4096),
    "mistral_small_4_119b.s16384_scan": (1, 16384, 32, 32, 128, None),
    "olmoe_1b_7b.s4096_scan": (4, 4096, 16, 16, 128, None),
    "ouro_2_6b.s4096_scan": (2, 4096, 16, 16, 128, None),
    "lfm2_8b_a1b.s8192_scan": (2, 8192, 32, 8, 64, None),
    "jamba2_3b.s8192_scan": (1, 8192, 20, 1, 128, None),
    "nemotron3_nano_30b_a3b.s8192_scan": (2, 8192, 32, 2, 128, None),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_statistic_is_the_backward_kernels_array(cell):
    """At every decoder cell's geometry the kernel's result has the shape
    (and, a Pallas result, the default layout) of ``_Geom.stat_shape``,
    which ``stat_spec``'s blocks read; and the cell's shape is taken, in
    blocks of rows within the VMEM the call states."""
    B, S, H, Hkv, D, window = CELLS[cell]
    q = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, S, Hkv * D), jnp.bfloat16)
    g = fa._Geom(q, k, H, 512, 512, Hkv, window)
    assert not g.one_block and (g.G, g.Hg) == (1, 1)
    assert K.supported(q.shape, D, 2)
    out = jax.eval_shape(lambda o, do: K.flash_delta(o, do, head_dim=D), q, q)
    assert out.shape == g.stat_shape and out.dtype == jnp.float32
    rows = K.block_rows(S, H * D, 2)
    assert rows in (256, 512) and S % rows == 0
    assert K.vmem_bytes(rows, H * D, 2) <= 32 * 2 ** 20


@pytest.mark.parametrize("shape,D,itemsize,rows", [
    ((1, 16384, 3584), 128, 2, 256),    # SmallThinker
    ((1, 6144, 6144), 128, 2, 256),     # Trinity: 24 MiB of blocks
    ((4, 4096, 2048), 128, 2, 512),     # OLMoE
    ((2, 8192, 2048), 64, 2, 512),      # LFM2, two heads a lane block
    ((2, 48, 384), 128, 4, 16),
    ((1, 8, 128), 128, 4, 8),
    ((2, 64, 256), 16, 4, 64),          # eight heads a lane block
    ((2, 24, 256), 64, 2, None),        # 24 rows are no whole bf16 tiles
    ((2, 32, 64), 16, 4, None),         # half a lane block
    ((2, 32, 768), 96, 4, None),        # a head across lane blocks
    ((2, 32, 512), 256, 4, None),       # a head of two lane blocks
    ((1, 8, 1024 * 1024), 128, 4, None),  # no block within BLOCK_VMEM
])
def test_the_shapes_the_kernel_takes(shape, D, itemsize, rows):
    assert K.supported(shape, D, itemsize) == (rows is not None)
    if rows:
        assert K.block_rows(shape[1], shape[2], itemsize) == rows
        assert K.vmem_bytes(rows, shape[2], itemsize) < 32 * 2 ** 20
    else:
        x = jnp.zeros(shape, jnp.float32 if itemsize == 4 else jnp.bfloat16)
        with pytest.raises(ValueError, match="not supported"):
            K.flash_delta(x, x, head_dim=D)


def test_o_and_do_are_of_one_type():
    o, do = operands(1, 16, 1, 128, jnp.float32)
    with pytest.raises(ValueError, match="not supported"):
        K.flash_delta(o, do.astype(jnp.bfloat16), head_dim=128)


def _counted(tmp_path, trace):
    """{(fused, head_dim): calls} that ``trace()`` counts in
    ``monitor.kernels.flash_delta_calls`` under a monitor session."""
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        trace()
        return {(r["labels"]["fused"], r["labels"]["head_dim"]): r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.flash_delta_calls"}
    finally:
        monitor.disable()


def _gradients(B, S, H, Hkv, D, bq, bk, window, causal=True, entry="packed",
               seed=11):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.array((rng.randn(B, S, h * D) * 0.5).astype(np.float32))
    q, k, v, w = mk(H), mk(Hkv), mk(Hkv), mk(H)
    if entry == "bshd":
        q, k, v, w = (t.reshape(B, S, -1, D) for t in (q, k, v, w))
        attn = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk)
    else:
        attn = lambda q, k, v: fa.flash_attention_packed(
            q, k, v, H, causal=causal, block_q=bq, block_k=bk,
            n_kv_heads=Hkv, window=window)
    return jax.grad(lambda *a: jnp.sum(attn(*a) * w), argnums=(0, 1, 2))(
        q, k, v)


#   what                        B  S    H  Hkv D    bq   bk   window fused
BACKWARDS = [
    ("full, ungrouped",         1, 256, 2, 2, 128, 64,  64,  None, 1),
    ("full, group 3",           2, 256, 6, 2, 128, 64,  64,  None, 1),
    ("window, group 7",         1, 512, 7, 1, 128, 64,  64,  100,  1),
    ("two heads a lane block",  1, 256, 4, 4, 64,  64,  64,  None, 1),
    ("stacked, group 2, window", 1, 512, 4, 2, 64, 64,  64,  100,  1),
    ("group 3 at one block",    2, 128, 6, 2, 128, 128, 128, None, 1),
    # 36 rows of 12: no whole sublane tiles, the lines
    ("rows the kernel refuses", 1, 36,  2, 2, 128, 12,  12,  None, 0),
]


@pytest.mark.parametrize("what,B,S,H,Hkv,D,bq,bk,window,fused", BACKWARDS,
                         ids=[b[0] for b in BACKWARDS])
def test_the_backward_with_the_kernel_is_the_backward_on_the_lines(
        tmp_path, monkeypatch, what, B, S, H, Hkv, D, bq, bk, window, fused):
    """dq, dk and dv of ``flash_attention_packed`` (whose references
    ``tests/test_flash_attention.py`` holds them to, with the kernel in the
    path) against the same backward kernels fed the lines' ``delta``; a
    shape the kernel refuses runs the lines and says ``fused=0``."""
    args = (B, S, H, Hkv, D, bq, bk, window)
    got = []
    assert _counted(tmp_path, lambda: got.extend(_gradients(*args))) == {
        (fused, D): 1}
    monkeypatch.setattr(K, "supported", lambda *a: False)
    want = []
    assert _counted(tmp_path, lambda: want.extend(_gradients(*args))) == {
        (0, D): 1}
    for a, b, n in zip(got, want, "qkv"):
        if fused:   # a float32 sum of D products in another order
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6,
                                       err_msg="d%s of %s" % (n, what))
        else:
            np.testing.assert_array_equal(a, b)


def test_one_block_and_the_unpacked_layout_make_no_call(tmp_path):
    """BERT's backward (one kv block, ungrouped) makes ``delta`` inside
    ``flash_bwd_fused`` and counts nothing; [BH, S, D] over several blocks
    keeps the lines."""
    assert _counted(tmp_path, lambda: _gradients(
        2, 128, 2, 2, 64, 128, 128, None, causal=False)) == {}
    assert _counted(tmp_path, lambda: _gradients(
        2, 256, 2, 2, 64, 64, 64, None, entry="bshd")) == {(0, 64): 1}


# tiny model -> (sequence, what one traced forward + backward counts): BERT
# (one kv block) and Brumby (retention) make no several-block flash backward;
# OLMoE's heads of 16 and Ouro's are no packed layout, so no flash call at
# all; the others' layer kinds are a traced backward each (a scanned run
# traces once), all taken by the kernel
ENGAGED = {
    "bert": (32, {}),
    "olmoe": (32, {}),
    "brumby": (64, {}),
    "ouro": (64, {}),
    "mistral4": (64, {(1, 128): 1}),
    "smallthinker": (64, {(1, 128): 2}),
    "lfm2": (64, {(1, 64): 1}),
    "trinity": (64, {(1, 128): 3}),
    "jamba": (64, {(1, 128): 1}),
    "nemotron_h": (64, {(1, 128): 1}),
}


@pytest.mark.parametrize("model", list(ENGAGED))
def test_which_tiny_models_take_the_kernel(tmp_path, model):
    from paddle_tpu.parallel import decoder, transformer as T

    seq, want = ENGAGED[model]
    module = importlib.import_module("paddle_tpu.models." + model)
    cfg = getattr(module, model + "_tiny_config")(remat=True)
    params = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)

    def loss(p, i):
        return jnp.sum(decoder.forward(p, i, cfg)[0].astype(jnp.float32))

    assert _counted(tmp_path, lambda: jax.eval_shape(
        jax.grad(loss), params, ids)) == want


# cell -> the traced several-block backwards of its step at the PUBLISHED
# widths and the cell's batch and sequence (a layer kind each; shapes alone,
# nothing compiles): every one the kernel's, and none at all in BERT's one kv
# block and Brumby's retention
CELL_CALLS = {
    "smallthinker_21b_a3b.s16384_scan": {(1, 128): 2},
    "trinity_large_preview.s6144_scan": {(1, 128): 3},
    "mistral_small_4_119b.s16384_scan": {(1, 128): 1},
    "nemotron3_nano_30b_a3b.s8192_scan": {(1, 128): 1},
    "olmoe_1b_7b.s4096_scan": {(1, 128): 1},
    "ouro_2_6b.s4096_scan": {(1, 128): 1},
    "lfm2_8b_a1b.s8192_scan": {(1, 64): 1},
    "jamba2_3b.s8192_scan": {(1, 128): 1},
    "brumby_14b.s16384_scan": {},
    "bert_base.s512_scan": {},
    "bert_base.s128_scan": {},
}


@pytest.mark.parametrize("cell", list(CELL_CALLS))
def test_every_decoder_cell_s_backward_takes_the_kernel(tmp_path, cell):
    import os
    import sys

    from paddle_tpu.parallel import decoder, transformer as T

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    hlo = importlib.import_module("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config(cell, tiny=False)
    params = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss(p, i):
        return jnp.sum(decoder.forward(p, i, cfg)[0].astype(jnp.float32))

    def trace():
        with hlo.kernels_as_on_a_tpu():
            jax.eval_shape(jax.grad(loss), params, ids)

    assert _counted(tmp_path, trace) == CELL_CALLS[cell]
