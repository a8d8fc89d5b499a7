"""Flash-attention Pallas kernel: numerical parity + gradient checks against
the XLA blockwise reference, in interpret mode on CPU (the kernel itself is
identical code on TPU; only the Mosaic lowering differs)."""

import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.parallel.ring_attention import ring_attention


def _qkv(seed, B=2, S=256, H=4, D=64):
    rng = np.random.RandomState(seed)
    mk = lambda: (rng.randn(B, S, H, D) * 0.5).astype(np.float32)
    return jnp.array(mk()), jnp.array(mk()), jnp.array(mk())


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(0)
    ref = ring_attention(q, k, v, axis=None, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = _qkv(1)
    w = jnp.array(np.random.RandomState(2).randn(*q.shape).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) * w)

    def loss_ref(q, k, v):
        return jnp.sum(ring_attention(q, k, v, axis=None, causal=causal) * w)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4,
                                   err_msg="d%s mismatch" % n)


def test_uneven_blocks():
    """S divisible by block but nq != nk paths (rectangular grids)."""
    q, _, _ = _qkv(3, S=256)
    _, k, v = _qkv(4, S=512)
    ref = ring_attention(q, k, v, axis=None, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)


def test_dispatch_block_choice():
    """The transformer dispatch must never pick a block that does not divide
    S (regression: S=640 passed the old %128 gate then hit the 512-block
    assert)."""
    from paddle_tpu.parallel.transformer import (_local_attention_dispatch,
                                                 TransformerConfig)

    cfg = TransformerConfig(causal=False)
    rng = np.random.RandomState(5)
    for S in (128, 384, 640):
        x = jnp.array((rng.randn(1, S, 2, 64) * 0.5).astype(np.float32))
        out = _local_attention_dispatch(x, x, x, cfg)
        ref = ring_attention(x, x, x, axis=None, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=1e-5, err_msg="S=%d" % S)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S,bq,bk", [(256, 256, 256),   # fused single-kv-block bwd
                                     (256, 128, 128),   # two-sweep bwd
                                     (256, 128, 256)])  # fused, two q blocks:
                                                        # dk, dv through scratch
def test_packed_layout_matches_bshd(causal, S, bq, bk):
    """flash_attention_packed on [B,S,H*D] == flash_attention on [B,S,H,D],
    values and gradients (the head-column BlockSpec addressing)."""
    from paddle_tpu.kernels.flash_attention import flash_attention_packed

    B, H, D = 2, 4, 64
    q, k, v = _qkv(6, B=B, S=S, H=H, D=D)
    qp, kp, vp = (t.reshape(B, S, H * D) for t in (q, k, v))
    w = jnp.array(np.random.RandomState(7).randn(B, S, H * D).astype(np.float32))

    def loss_p(a, b, c):
        return jnp.sum(flash_attention_packed(a, b, c, H, causal=causal,
                                              block_q=bq, block_k=bk) * w)

    def loss_r(a, b, c):
        return jnp.sum(flash_attention(a, b, c, causal=causal,
                                       block_q=bq, block_k=bk)
                       .reshape(B, S, H * D) * w)

    np.testing.assert_allclose(
        np.asarray(flash_attention_packed(qp, kp, vp, H, causal=causal,
                                          block_q=bq, block_k=bk)),
        np.asarray(flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk).reshape(B, S, H * D)),
        atol=2e-6, rtol=1e-5)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(qp, kp, vp)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b).reshape(B, S, H * D),
                                   atol=5e-5, rtol=1e-4,
                                   err_msg="d%s mismatch" % n)


# ---------------------------------------------------------------------------
# one grid step carries a fixed amount of work (PR 28): where the sequence is
# one block a step holds G batch rows by Hg head-blocks, from the shapes
# ---------------------------------------------------------------------------

import importlib

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


#   entry      B  S    H  D    G, Hg the rule gives for float32 inputs
GROUPED = [
    ("packed", 8, 128, 4, 64, (2, 2)),
    ("packed", 3, 128, 2, 64, (3, 1)),     # one head-block: rows only
    ("packed", 1, 128, 4, 64, (1, 2)),     # B = 1: head-blocks only
    ("packed", 8, 256, 4, 64, (1, 2)),
    ("packed", 3, 256, 2, 64, (3, 1)),     # a prime B goes whole
    ("packed", 3, 384, 2, 128, (1, 2)),
    ("packed", 8, 128, 2, 128, (2, 2)),
    ("packed", 1, 384, 2, 64, (1, 1)),     # nothing to group: falls back to 1, 1
    ("packed", 4, 128, 8, 64, (2, 2)),     # width 64, every head its own k/v
    ("bshd", 3, 128, 4, 64, (4, 1)),       # G consecutive rows of [B*H, S, D]
    ("bshd", 8, 256, 2, 64, (2, 1)),
    ("bshd", 1, 384, 2, 128, (2, 1)),
    ("bshd", 1, 128, 1, 64, (1, 1)),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("entry,B,S,H,D,geom", GROUPED)
def test_grouped_step_matches_reference_and_one_pair_a_step(
        monkeypatch, entry, B, S, H, D, geom, causal):
    """Forward and dq, dk, dv of the grouped geometry against ring_attention,
    and bit for bit against the same call forced to G = Hg = 1: every pair
    is computed as a step of its own would compute it."""
    q, k, v = _qkv(11, B=B, S=S, H=H, D=D)
    w = jnp.array(np.random.RandomState(12).randn(B, S, H, D)
                  .astype(np.float32))
    if entry == "packed":
        assert fa.grid_geometry(B, S, S, H // max(1, 128 // D),
                                max(D, 128), 4, S, S)[:2] == geom
        flat = lambda t: t.reshape(B, S, H * D)
        attn = lambda a, b, c: fa.flash_attention_packed(
            flat(a), flat(b), flat(c), H, causal=causal, block_q=512,
            block_k=512).reshape(B, S, H, D)
    else:
        assert fa.grid_geometry(B * H, S, S, 1, D, 4, S, S)[:2] == geom
        attn = lambda a, b, c: fa.flash_attention(
            a, b, c, causal=causal, block_q=512, block_k=512)

    def both(f):
        o, vjp = jax.vjp(f, q, k, v)
        return (o,) + vjp(w)

    got = both(attn)
    want = both(lambda a, b, c: ring_attention(a, b, c, axis=None,
                                               causal=causal))
    for a, b, n in zip(got, want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=1e-4, err_msg=n)
    # the same call with every (row, head-block) pair a step of its own
    monkeypatch.setattr(fa, "step_geometry", lambda *a: (1, 1))
    for a, b, n in zip(got, both(attn), ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=n)


@pytest.mark.parametrize("what,args,want", [
    # B, S, head-blocks, lanes, itemsize
    ("bert_base.s128_scan", (256, 128, 6, 128, 2), (2, 3)),
    ("bert_base.s512_scan: the step as it was", (64, 512, 6, 128, 2), (1, 1)),
    ("olmoe, were S one block", (4, 512, 16, 128, 2), (1, 1)),
    ("fine-tuning, one block of 384", (32, 384, 6, 128, 2), (1, 2)),
    ("S=256", (128, 256, 6, 128, 2), (1, 2)),
    ("B = 1", (1, 128, 6, 128, 2), (1, 3)),
    ("a prime B goes whole or not at all", (7, 128, 6, 128, 2), (7, 3)),
    ("a prime B too large for the budget", (61, 128, 1, 128, 2), (1, 1)),
    ("tp=2: six local heads", (256, 128, 3, 128, 2), (2, 3)),
    ("tp=4: three local heads of 128", (256, 128, 3, 128, 2), (2, 3)),
    ("tp=3: four local heads", (256, 128, 2, 128, 2), (2, 2)),
    ("tp=6: one local head-block", (256, 128, 1, 128, 2), (4, 1)),
    ("16 head-blocks: 4 divides, 3 is the most", (8, 128, 16, 128, 2), (2, 2)),
    ("[BH, S, 64] float32", (96, 128, 1, 64, 4), (4, 1)),
])
def test_step_geometry_table(what, args, want):
    G, Hg = fa.step_geometry(*args)
    assert (G, Hg) == want, what
    assert args[0] % G == 0 and args[2] % Hg == 0
    assert Hg <= fa.STEP_HEAD_BLOCKS


@pytest.mark.parametrize("S", [128, 256, 384, 512])
def test_step_geometry_stays_under_the_vmem_budget(S):
    """Whatever the batch and the heads: G and Hg divide, and a step that
    holds more than one pair fits the budget (one pair is the floor the
    kernels always ran at)."""
    for B in (1, 2, 3, 5, 7, 8, 12, 61, 64, 96, 256, 1024):
        for Hb in (1, 2, 3, 4, 6, 8, 12, 16):
            for lanes, itemsize in ((128, 2), (128, 4), (256, 2), (64, 4)):
                G, Hg = fa.step_geometry(B, S, Hb, lanes, itemsize)
                assert B % G == 0 and Hb % Hg == 0, (B, S, Hb, lanes)
                if (G, Hg) != (1, 1):
                    assert fa.step_vmem_bytes(G, S, Hg, lanes, itemsize) \
                        <= fa.VMEM_BUDGET, (B, S, Hb, lanes, itemsize)
                    # no more rows than it takes to fill the step
                    smaller = [g for g in range(1, G) if B % g == 0]
                    assert not smaller or (smaller[-1] * Hg * S
                                           * max(lanes, 128)
                                           < fa.STEP_ROWS * 128)


@pytest.mark.parametrize("B,S,H,D,causal,want", [
    (256, 128, 12, 64, False, (6, 256)),  # bert_base.s128_scan: 1,536 before
    (64, 512, 12, 64, False, (1, 384)),   # bert_base.s512_scan: untouched
    # olmoe: the triangle of 8 x 8 blocks, 36 steps where 64, and since
    # PR 70 eight of the row's sixteen head-blocks a step
    (4, 4096, 16, 128, True, (8, 4 * 2 * 36)),
    (8, 640, 12, 64, False, (1, 8 * 6 * 25)),  # S = 640 in five blocks of 128
])
def test_packed_grid_of_the_cells(B, S, H, D, causal, want):
    bq = 512 if S % 512 == 0 or S < 512 else 128
    assert fa.packed_grid(B, S, H, D, bq, bq, causal=causal) == want


def test_several_blocks_are_one_pair_a_step(monkeypatch):
    """S above the block size never asks the rule: the several-block
    sweeps and the causal skip run the geometry they always had."""
    def never(*a):
        raise AssertionError("step_geometry asked for a multi-block grid")
    monkeypatch.setattr(fa, "step_geometry", never)
    assert fa.grid_geometry(4, 4096, 4096, 16, 128, 2, 512, 512) == (1, 1, 64)
    assert fa.grid_geometry(8, 256, 512, 2, 128, 4, 128, 512) == (1, 1, 16)
    q, k, v = _qkv(13, B=2, S=256, H=2, D=64)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = ring_attention(q, k, v, axis=None, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=1e-5)


def test_a_layer_at_s128_is_one_forward_and_one_backward_kernel():
    """The jaxpr of one transformer layer's forward and backward at the
    S=128 cell's shape holds exactly one ``flash_fwd`` and one
    ``flash_bwd_fused`` call: the metrics that find the kernels by name
    count one event as one layer's pass over the chip's batch."""
    import re

    from paddle_tpu.models.bert import bert_base_config
    from paddle_tpu.parallel import transformer as T

    cfg = bert_base_config()
    params = jax.eval_shape(
        lambda: T.init_transformer_params(jax.random.PRNGKey(0), cfg))
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         params["params_layers"])
    x = jax.ShapeDtypeStruct((256, 128, cfg.hidden), cfg.jdtype)

    def loss(pl, x):
        return jnp.sum(T.transformer_layer(pl, x, cfg)[0].astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(layer, x))
    calls = re.findall(r"name=(flash_\w+)", text)
    assert sorted(calls) == ["flash_bwd_fused", "flash_fwd"], calls
    # 2 rows by 3 head-blocks a step: 256 steps where there were 1,536
    steps = fa.packed_grid(256, 128, cfg.n_heads, cfg.head_dim, 512, 512)[1]
    assert steps == 256
    assert "grid=(%d, 1, 1)" % steps in text and "grid=(%d, 1)" % steps in text


# ---------------------------------------------------------------------------
# grouped queries and a sliding window (PR 31): modes of the same kernels
# ---------------------------------------------------------------------------

def _plain(q, k, v, causal, window=None):
    """Softmax attention on [B, S, H, D] against [B, Sk, Hkv, D], float32,
    no kernel."""
    S, Sk, H = q.shape[1], k.shape[1], q.shape[2]
    kh, vh = (jnp.repeat(t, H // t.shape[2], axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kh) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(S)[:, None], jnp.arange(Sk)[None, :]
    seen = (j <= i) if causal else jnp.ones((S, Sk), bool)
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh)


def _plain_gqa(q, k, v, H, Hkv, window=None, causal=True):
    """``_plain`` on the packed layout: query head h reads kv head
    h // (H // Hkv); query i sees keys j with i - window < j <= i."""
    B, S, _ = q.shape
    heads = lambda t, h: t.reshape(B, t.shape[1], h, -1)
    return _plain(heads(q, H), heads(k, Hkv), heads(v, Hkv), causal,
                  window).reshape(B, S, -1)


def _packed_qkv(seed, B, S, H, Hkv, D):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.array((rng.randn(B, S, h * D) * 0.5).astype(np.float32))
    return mk(H), mk(Hkv), mk(Hkv), mk(H)


#   what                          B  S    H   Hkv D    bq   bk   window causal
MODES = [
    ("group 7, causal",           1, 256, 7,  1, 128, 64,  64,  None, True),
    ("group 3, causal",           2, 256, 6,  2, 128, 64,  64,  None, True),
    ("group 3, bidirectional",    1, 256, 3,  1, 128, 128, 64,  None, False),
    ("group 3, one block",        2, 128, 6,  2, 128, 128, 128, None, True),
    ("window, whole blocks",      1, 512, 2,  2, 128, 64,  64,  128,  True),
    ("window 100 of blocks 64",   1, 512, 2,  2, 128, 64,  64,  100,  True),
    ("window, bq != bk",          1, 512, 2,  2, 128, 128, 64,  100,  True),
    ("window, bq < bk",           1, 512, 2,  2, 128, 64,  128, 200,  True),
    ("window, heads of 64",       1, 256, 4,  4, 64,  64,  64,  72,   True),
    ("window in one block",       2, 128, 2,  2, 128, 128, 128, 50,   True),
    ("window and group 7",        1, 512, 7,  1, 128, 64,  64,  100,  True),
    ("window and group 3",        2, 256, 6,  2, 128, 64,  64,  72,   True),
    ("window of S: causal",       1, 256, 3,  1, 128, 64,  64,  256,  True),
    # two heads a lane block, both on one key/value head (PR 33)
    ("group 4 at width 64",       2, 256, 8,  2, 64,  64,  64,  None, True),
    ("32 on 8 heads of 64",       1, 128, 32, 8, 64,  64,  64,  None, True),
    ("group 4 at 64, one block",  1, 128, 8,  2, 64,  128, 128, None, True),
    ("group 2 at 64, both ways",  1, 256, 4,  2, 64,  128, 64,  None, False),
    ("group 6 at 64, bq < bk",    1, 256, 12, 2, 64,  64,  128, None, True),
    ("window and group 4 at 64",  1, 512, 8,  2, 64,  64,  64,  100,  True),
    # the sweeps' step tables (PR 34): the triangle, bands of windows on and
    # off a block's edge, the rectangle
    ("group 1, eight kv blocks",  1, 512, 2,  2, 128, 64,  64,  None, True),
    ("window of one block",       1, 512, 7,  1, 128, 64,  64,  64,   True),
    ("window of a block and one", 1, 512, 3,  1, 128, 64,  64,  65,   True),
    ("window of 1",               1, 256, 2,  2, 128, 64,  64,  1,    True),
    ("group 3, bq > bk, causal",  1, 512, 3,  1, 128, 128, 64,  None, True),
    ("group 7, both ways, 4 x 4", 1, 256, 7,  1, 128, 64,  64,  None, False),
    ("halves, eight kv blocks",   1, 512, 8,  2, 64,  64,  64,  None, True),
    ("halves, window of a block", 1, 512, 8,  2, 64,  64,  64,  64,   True),
    ("halves, window 1, bq < bk", 1, 256, 4,  2, 64,  64,  128, 1,    True),
    # the two heads of a lane block stacked along rows (PR 41): a group of 2
    # is one query block a key/value head (block 0 reads half 0 of the
    # key/value block, block 1 half 1), a group of 4 two
    ("stacked, group 2, causal",  2, 256, 4,  2, 64,  64,  64,  None, True),
    ("stacked, group 2, window",  1, 512, 4,  2, 64,  64,  64,  100,  True),
    ("stacked, group 2, bq > bk", 1, 256, 4,  2, 64,  128, 64,  None, True),
    ("stacked, group 4, both ways", 1, 256, 8, 2, 64, 64,  128, None, False),
    ("stacked, group 4, window, bq > bk", 1, 512, 8, 2, 64, 128, 64, 72, True),
    ("stacked, 16 on 4 heads",    1, 256, 16, 4, 64,  64,  64,  None, True),
]


@pytest.mark.parametrize("what,B,S,H,Hkv,D,bq,bk,window,causal", MODES,
                         ids=[m[0] for m in MODES])
def test_grouped_and_windowed_modes_equal_plain_attention(
        what, B, S, H, Hkv, D, bq, bk, window, causal):
    """Forward and dq / dk / dv of ``flash_attention_packed`` with fewer
    key/value heads than query heads and with a window, against plain
    softmax attention; dk and dv are the sums over a group's query heads."""
    q, k, v, w = _packed_qkv(21, B, S, H, Hkv, D)

    def flash(q, k, v):
        return fa.flash_attention_packed(
            q, k, v, H, causal=causal, block_q=bq, block_k=bk,
            n_kv_heads=Hkv, window=window)

    def plain(q, k, v):
        return _plain_gqa(q, k, v, H, Hkv, window, causal)

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v),
                               atol=3e-6, rtol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(got, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4,
                                   err_msg="d%s of %s" % (n, what))


def _kernel_names(fn, *args):
    return sorted(re.findall(r"name=(flash_\w+)", str(jax.make_jaxpr(fn)(*args))))


def test_windowed_kernels_carry_names_of_their_own(monkeypatch):
    q, k, v, w = _packed_qkv(22, 1, 512, 2, 2, 128)

    def both(window):
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention_packed(
                q, k, v, 2, causal=True, block_q=64, block_k=64,
                window=window) * w)
        return _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)

    # ONE backward kernel a layer, of the one-block backward's name: the
    # benchmark's readers count an event of it as one layer's backward.  The
    # row kernel that makes its ``delta`` (``flash_delta``, PR 55) is no
    # flash kernel to them, windowed layer or not: they match whole names
    assert both(None) == ["flash_bwd_fused", "flash_delta", "flash_fwd"]
    assert both(100) == ["flash_delta", "flash_swa_bwd_fused",
                         "flash_swa_fwd"]
    assert both(512) == both(None)          # the window is the causal mask
    # a sequence whose dk and dv do not fit VMEM: the two sweeps
    monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    assert both(None) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_delta",
                          "flash_fwd"]
    assert both(100) == ["flash_delta", "flash_swa_bwd_dkv",
                         "flash_swa_bwd_dq", "flash_swa_fwd"]


# ---------------------------------------------------------------------------
# the several-block backward is one sweep (PR 36) wherever dk and dv of the
# whole sequence fit VMEM, and two past that
# ---------------------------------------------------------------------------

#   what                           entry     B  S    Sk   H  Hkv D    bq   bk   window causal
ONE_SWEEP = [
    ("triangle, ungrouped",        "packed", 1, 512, 512, 2, 2, 128, 64,  64,  None, True),
    ("triangle, group 2",          "packed", 2, 256, 256, 4, 2, 128, 64,  64,  None, True),
    ("triangle, group 7",          "packed", 1, 256, 256, 7, 1, 128, 64,  64,  None, True),
    ("band, group 7",              "packed", 1, 512, 512, 7, 1, 128, 64,  64,  100,  True),
    ("band, bq > bk",              "packed", 1, 512, 512, 2, 2, 128, 128, 64,  100,  True),
    ("triangle, bq < bk, group 2", "packed", 1, 512, 512, 4, 2, 128, 64,  128, None, True),
    ("rectangle, S != Sk, group 2", "packed", 1, 256, 512, 4, 2, 128, 64,  128, None, False),
    ("rectangle, group 7",         "packed", 1, 256, 256, 7, 1, 128, 64,  64,  None, False),
    ("two heads a block, no group", "packed", 1, 256, 256, 4, 4, 64,  64,  64,  None, True),
    ("halves, two query blocks",   "packed", 1, 512, 512, 8, 2, 64,  64,  64,  None, True),
    ("halves, band, bq < bk",      "packed", 1, 256, 256, 4, 2, 64,  64,  128, 72,   True),
    ("halves, rectangle",          "packed", 1, 256, 256, 8, 2, 64,  128, 64,  None, False),
    ("stacked, group 2, triangle", "packed", 2, 256, 256, 4, 2, 64,  64,  64,  None, True),
    ("stacked, group 2, band",     "packed", 1, 512, 512, 4, 2, 64,  64,  64,  100,  True),
    ("stacked, group 4, band, bq > bk", "packed", 1, 512, 512, 8, 2, 64, 128, 64, 72, True),
    ("stacked, group 2, S != Sk",  "packed", 1, 256, 512, 4, 2, 64,  64,  128, None, False),
    ("stacked, group 4 at one block", "packed", 1, 128, 128, 8, 2, 64, 128, 128, None, True),
    ("group 3 at one block",       "packed", 2, 128, 128, 6, 2, 128, 128, 128, None, True),
    ("[BH, S, 64], triangle",      "bshd",   2, 256, 256, 2, 2, 64,  64,  128, None, True),
    ("[BH, S, 128], rectangle",    "bshd",   1, 128, 256, 3, 3, 128, 64,  64,  None, False),
]


@pytest.mark.parametrize(
    "what,entry,B,S,Sk,H,Hkv,D,bq,bk,window,causal", ONE_SWEEP,
    ids=[m[0] for m in ONE_SWEEP])
def test_one_sweep_backward_equals_plain_attention_and_the_two_sweeps(
        monkeypatch, what, entry, B, S, Sk, H, Hkv, D, bq, bk, window,
        causal):
    """dq, dk, dv of ``flash_bwd_fused`` over several blocks against plain
    attention's, and BIT FOR BIT against ``flash_bwd_dq`` / ``flash_bwd_dkv``
    (the same call with no VMEM for the accumulators): one probability tile
    a step in place of two, the same sums in the same order.  Grouped
    queries (PR 68): a step holds ``heads_a_step`` of a group and sums their
    dk and dv before ONE add into the accumulators, so those two keep dq bit
    for bit and dk and dv to the last bits; at one head a step
    (``heads_a_step`` replaced) they are the two sweeps' bit for bit too."""
    rng = np.random.RandomState(31)
    mk = lambda s, h: jnp.array((rng.randn(B, s, h, D) * 0.5)
                                .astype(np.float32))
    q, k, v, w = mk(S, H), mk(Sk, Hkv), mk(Sk, Hkv), mk(S, H)
    if entry == "packed":
        flat = lambda t: t.reshape(t.shape[0], t.shape[1], -1)
        attn = lambda a, b, c: fa.flash_attention_packed(
            flat(a), flat(b), flat(c), H, causal=causal, block_q=bq,
            block_k=bk, n_kv_heads=Hkv, window=window).reshape(a.shape)
    else:
        attn = lambda a, b, c: fa.flash_attention(
            a, b, c, causal=causal, block_q=bq, block_k=bk)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * w),
                               argnums=(0, 1, 2))
    names = _kernel_names(grads(attn), q, k, v)
    assert [n for n in names if "bwd" in n] == [
        "flash_swa_bwd_fused" if window else "flash_bwd_fused"], what
    one = grads(attn)(q, k, v)
    want = grads(lambda a, b, c: _plain(a, b, c, causal, window))(q, k, v)
    for a, b, n in zip(one, want, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4,
                                   err_msg="d%s of %s" % (n, what))
    with monkeypatch.context() as m:
        m.setattr(fa, "SWEEP_VMEM", 0)
        assert len([n for n in _kernel_names(grads(attn), q, k, v)
                    if "bwd" in n]) == 2, what
        two = [np.asarray(x) for x in grads(attn)(q, k, v)]

    def held(got, exact):
        for a, b, n in zip(got, two, "qkv"):
            if exact or n == "q":
                np.testing.assert_array_equal(
                    np.asarray(a), b, err_msg="d%s of %s" % (n, what))
            else:
                assert np.abs(np.asarray(a) - b).max() \
                    <= 1e-6 * np.abs(b).max(), (n, what)

    grouped = entry == "packed" and H != Hkv
    held(one, exact=not grouped)
    if grouped:
        monkeypatch.setattr(fa, "heads_a_step", lambda group, need: 1)
        held(grads(attn)(q, k, v), exact=True)


@pytest.mark.parametrize("what,S,lanes,group,sweeps,mib", [
    ("olmoe_1b_7b.s4096_scan", 4096, 128, 1, 1, 22),
    ("lfm2_8b_a1b.s8192_scan", 8192, 128, 4, 1, 28),
    ("smallthinker_21b_a3b.s16384_scan", 16384, 128, 7, 1, 40),
    ("twice that", 32768, 128, 7, 1, 64),
    ("four times", 65536, 128, 7, 2, 112),
    ("Reach 8's sequence", 131072, 128, 8, 2, 208),
    ("[BH, S, 64] pads to a lane tile", 16384, 64, 1, 1, 40),
    ("heads of 256", 16384, 256, 1, 1, 64),
    ("heads of 256, twice the sequence", 32768, 256, 1, 2, 112),
])
def test_the_rule_of_the_one_sweep_backward(what, S, lanes, group, sweeps,
                                            mib):
    """From the shapes: the accumulators and output blocks of dk and dv over
    the whole sequence (float32 and bf16, [S, lanes] twice each) beside
    Mosaic's own 16 MiB; one sweep up to half a v5e core's VMEM."""
    need = fa.fused_sweep_vmem_bytes(S, lanes, 2)
    assert need == mib * 2 ** 20, what
    assert need == (2 * S * max(lanes, 128) * (4 + 2)) + fa.SCOPED_VMEM
    assert fa.bwd_sweeps(S, 512, lanes, 2, group) == sweeps, what
    assert (need <= fa.SWEEP_VMEM) == (sweeps == 1)
    assert fa.SWEEP_VMEM <= 64 * 2 ** 20     # of a v5e core's 128 MiB


def test_one_block_ungrouped_is_one_kernel_whatever_vmem_holds(monkeypatch):
    """Every BERT shape: ``_bwd_fused``, which never asks the rule's bytes."""
    monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    assert fa.bwd_sweeps(512, 512, 128, 2) == 1
    assert fa.bwd_sweeps(512, 512, 64 * fa._heads_per_block(64), 2) == 1
    assert fa.bwd_sweeps(512, 512, 128, 2, group=7) == 2
    assert fa.bwd_sweeps(4096, 512, 128, 2) == 2


@pytest.mark.parametrize("S,bq,bk,window,blocks", [
    (16384, 512, 512, 4096, 252),       # the cell's windowed layers: the
    (16384, 512, 512, None, 528),       # band; and its full one
    (8192, 512, 512, None, 136),        # lfm2's
    (4096, 512, 512, None, 36),         # olmoe's
    (512, 64, 64, 100, 21),
    (512, 128, 64, 100, 14),
])
def test_the_sweeps_visit_the_triangle_and_the_band(S, bq, bk, window, blocks):
    """A sweep's grid is its table: the blocks under the diagonal (and
    inside the band), none skipped; what the gauges say of a layer kind."""
    assert fa.kv_blocks(S, bq, bk, True, window) == blocks
    # (the row's four head-blocks ride one step since PR 70)
    assert fa.packed_grid(3, S, 4, 128, bq, bk, causal=True, window=window) \
        == (4, 3 * blocks)
    if S <= 4096:
        assert _tiles(S, S, bq, bk, True, window).sum() == blocks


def _tiles(S, Sk, bq, bk, causal, window):
    """[nq, nk] bool: the blocks that hold a pair the mask lets through, by
    ``_seen`` itself."""
    seen = np.asarray(fa._seen((S, Sk), 0, 0, window)) if causal \
        else np.ones((S, Sk), bool)
    return seen.reshape(S // bq, bq, Sk // bk, bk).any(axis=(1, 3))


#   S     Sk    bq   bk   causal window group
TABLES = [
    (2048, 2048, 128, 128, True,  None, 1),     # the triangle
    (2048, 2048, 128, 128, True,  1024, 7),     # W on a block's edge: 4096
    (2048, 2048, 128, 128, True,  1000, 3),     # and off it: 4000 of 512s,
    (2048, 2048, 128, 128, True,  129,  1),     # 513
    (2048, 2048, 128, 128, True,  1,    4),     # the diagonal alone
    (2048, 2048, 128, 128, True,  128,  1),
    (1024, 1024, 256, 128, True,  None, 2),     # bq != bk
    (1024, 1024, 128, 256, True,  300,  7),
    (1024, 1024, 64,  256, True,  1,    1),
    (512,  1024, 128, 128, False, None, 3),     # the rectangle
    (1024, 1024, 128, 128, False, None, 1),
]


@pytest.mark.parametrize("kv_major", [False, True],
                         ids=["q-major", "kv-major"])
@pytest.mark.parametrize("S,Sk,bq,bk,causal,window,group", TABLES)
def test_step_table(S, Sk, bq, bk, causal, window, group, kv_major):
    """Every block that holds a visible pair is a step exactly once a head
    of the group, no other block is; FIRST and LAST open and close each
    sweep once, in sweep order.  Both orders bring a kv block its
    (head, q block) pairs head first, then q block ascending: the order dk
    and dv are summed in, whichever backward runs."""
    visible = _tiles(S, Sk, bq, bk, causal, window)
    qs, ks, heads, flags = fa.step_table(S, Sk, bq, bk, causal, window,
                                         group, kv_major)
    assert qs.dtype == np.int32 and qs.ndim == 1
    steps = list(zip(qs.tolist(), ks.tolist(), heads.tolist()))
    assert len(set(steps)) == len(steps)
    want = {(i, j, h) for i, j in zip(*np.nonzero(visible))
            for h in range(group)}
    assert set(steps) == want
    assert not (flags & ~(fa.FIRST | fa.LAST)).any()
    # sweep order: one run of steps a kv block (a q block of a head),
    # ascending, each opened and closed once; inside a kv block's, head by
    # head
    owner, inner = (ks, qs) if kv_major else (heads * (S // bq) + qs, ks)
    assert (np.diff(owner) >= 0).all()
    first, last = (flags & fa.FIRST) != 0, (flags & fa.LAST) != 0
    edge = np.r_[True, np.diff(owner) != 0]
    np.testing.assert_array_equal(first, edge)
    np.testing.assert_array_equal(last, np.r_[edge[1:], True])
    for o in np.unique(owner):
        run = owner == o
        assert (np.diff(heads[run]) >= 0).all()
        for h in np.unique(heads[run]):
            assert (np.diff(inner[run & (heads == h)]) > 0).all()
    for j in np.unique(ks):
        met = [(h, i) for i, jj, h in steps if jj == j]
        assert met == sorted(met)
    # the forward's and the dq sweep's grids hold the group as an axis
    if not kv_major:
        one = fa.step_table(S, Sk, bq, bk, causal, window)
        assert not one[2].any() and one.shape[1] * group == len(steps)
        np.testing.assert_array_equal(
            np.tile(one[[0, 1, 3]], group), np.stack([qs, ks, flags]))


#   what                              H   Hkv D    S    block stacked
STACKED = [
    ("lfm2's heads, several blocks",   32, 8,  64,  256, 64,   2),
    ("a group of 2 at width 64",       4,  2,  64,  256, 64,   2),
    ("grouped at 64, one block",       8,  2,  64,  128, 128,  2),
    ("bert's heads, several blocks",   4,  4,  64,  256, 64,   1),
    ("bert's heads, one block",        4,  4,  64,  128, 128,  1),
    ("smallthinker's: a head a block", 7,  1,  128, 256, 64,   1),
    ("olmoe's: ungrouped at 128",      2,  2,  128, 256, 64,   1),
]


@pytest.mark.parametrize("what,H,Hkv,D,S,block,stacked", STACKED,
                         ids=[m[0] for m in STACKED])
def test_heads_ride_stacked_exactly_where_a_lane_block_reads_one_kv_head(
        monkeypatch, what, H, Hkv, D, S, block, stacked):
    """The score tiles of a traced forward and backward: [hpb * bq, 128]
    against the whole key/value lane block where ``_Geom.halves`` > 1 (two
    heads a lane block AND grouped queries), a head's own [bq, D] everywhere
    else, and ``_stack_heads`` met for q (forward) and for q and do
    (backward) or not at all.  One block and grouped: the forward is the one-block kernel,
    which stacks nothing, the backward the sweep."""
    tiles, stacks = set(), []
    scores, stack = fa._scores, fa._stack_heads
    monkeypatch.setattr(fa, "_scores", lambda q, k, *a: (
        tiles.add((q.shape, k.shape, a[5] if len(a) > 5 else None)),
        scores(q, k, *a))[1])
    monkeypatch.setattr(fa, "_stack_heads", lambda block, *a: (
        stacks.append(block.shape), stack(block, *a))[1])
    q, k, v, w = _packed_qkv(24, 1, S, H, Hkv, D)
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fa.flash_attention_packed(
        *a, H, causal=True, block_q=block, block_k=block, n_kv_heads=Hkv) * w),
        argnums=(0, 1, 2)))(q, k, v)
    g = fa._Geom(q, k, H, block, block, Hkv=Hkv)
    assert g.halves == stacked, what
    lanes = max(D, 128)
    if stacked == 1:
        assert not stacks and tiles == {((block, D), (block, D), None)}, what
        return
    several = {((stacked * block, lanes), (block, lanes), block)}
    # a step holds the group's query blocks, each stacked on its own
    heads = g.heads_in_step("bwd")[0]
    assert heads == g.heads_in_step("fwd")[0] == g.group
    if S == block:      # the one-block forward takes the half of k and v
        assert tiles == several | {((block, D), (block, D), None)}, what
        assert stacks == [(block, lanes)] * 2 * heads, what
    else:
        assert tiles == several, what
        assert stacks == [(block, lanes)] * 3 * heads, what


def test_a_block_nobody_sees_is_refused():
    """Keys past the last query under the causal mask: a dk/dv sweep of no
    step would leave its output block unwritten."""
    with pytest.raises(AssertionError, match="no query and key meet"):
        fa.step_table(256, 512, 128, 128, True, kv_major=True)


def test_grouped_queries_need_whole_head_blocks():
    assert fa.packed_layout_supported(28, 128, 4)
    assert fa.packed_layout_supported(12, 64)
    assert fa.packed_layout_supported(32, 64, 8)        # a group is 2 blocks
    assert fa.packed_layout_supported(4, 64, 2)         # a group is 1 block
    assert not fa.packed_layout_supported(12, 64, 4)    # a group of 3 heads
    # is a block and a half
    assert not fa.packed_layout_supported(6, 64, 3)     # 3 kv heads: 1.5 blocks
    assert not fa.packed_layout_supported(28, 128, 5)   # 5 does not divide 28
    q, k, v, _ = _packed_qkv(23, 1, 128, 6, 3, 64)
    with pytest.raises(ValueError, match="grouped"):
        fa.flash_attention_packed(q, k, v, 6, n_kv_heads=3)


@pytest.mark.parametrize("what,B,S,H,Hkv,D,want", [
    # several blocks: a group's head-blocks a step, steps = B x key/value
    # head-blocks x the triangle's blocks; one block: (row, head-block)
    # pairs a step, steps = B x head-blocks / pairs
    ("smallthinker_21b_a3b.s16384_scan", 1, 16384, 28, 4, 128, (7, 4 * 528)),
    ("lfm2_8b_a1b.s8192_scan", 2, 8192, 32, 8, 64, (4, 2 * 4 * 136)),
    ("trinity_large_preview.s6144_scan", 1, 6144, 48, 8, 128, (6, 8 * 78)),
    ("nemotron3_nano_30b_a3b.s8192_scan", 2, 8192, 32, 2, 128,
     (16, 2 * 2 * 136)),
    # ungrouped over several blocks (PR 70): SWEEP_HEAD_BLOCKS adjacent
    # head-blocks of the row a step
    ("olmoe_1b_7b.s4096_scan, ungrouped", 4, 4096, 16, None, 128,
     (8, 4 * 2 * 36)),
    ("mistral_small_4_119b.s16384_scan, ungrouped", 1, 16384, 32, None, 128,
     (8, 4 * 528)),
    ("bert_base.s128_scan, ungrouped", 256, 128, 12, 12, 64, (6, 256)),
    ("bert_base.s512_scan, ungrouped", 64, 512, 12, None, 64, (1, 384)),
])
def test_packed_grid_of_the_grouped_cells(what, B, S, H, Hkv, D, want):
    """The grids of the grouped modes are a (row, key/value head-block)
    pair's triangle of blocks, the group's query head-blocks riding each
    step (PR 68), of the ungrouped several-block mode a (row, eight
    head-blocks) step's (PR 70); BERT's ungrouped width-64 mode is what it
    was before grouped queries ran at two heads a lane block."""
    assert fa.packed_grid(B, S, H, D, 512, 512, n_kv_heads=Hkv,
                          causal=S > 512) == want


def test_a_query_block_reads_one_half_of_its_key_value_block():
    """32 query heads on 8 key/value heads of 64: query block b (heads 2b,
    2b + 1) reads key/value head b // 2, which is half (b // 2) % 2 of
    key/value block b // 4; the kv-major sweep walks a block's 4 query
    blocks, the first two into half 0."""
    q = jnp.zeros((1, 1024, 32 * 64), jnp.bfloat16)
    k = jnp.zeros((1, 1024, 8 * 64), jnp.bfloat16)
    g = fa._Geom(q, k, 32, 512, 512, Hkv=8)
    assert (g.hpb, g.Hb, g.group, g.halves, g.grid_b) == (2, 16, 4, 2, 16)
    table = fa.step_table(1024, 1024, 512, 512, True, None, g.group, True)
    assert table[2].tolist() == [0, 0, 1, 1, 2, 2, 3, 3, 0, 1, 2, 3]
    # grid (row, key/value block, query block of its 4, step): query block
    # b = 4 kh + g reads key/value block kh
    zero = np.zeros(1, np.int32)
    qm, km, sm = g.sweep_maps()
    for b in range(16):
        assert g.kmap()(b, 0, 0)[2] == b // 4
        at = lambda m: m(0, b // 4, b % 4, 0, zero, zero, zero, zero)
        assert (at(qm)[2], at(km)[2], at(sm)[1]) == (b, b // 4, b)
        assert g.kv_half(b) == (b // 2) % 2 == ((2 * b) // 4) % 2
    # the dk/dv sweep of key/value block 1: its table walks query blocks 4-7
    qm, km, sm = g.sweep_maps(walks_group=True)
    at = lambda m, t: m(0, 1, t, *table)
    assert [at(qm, t)[2] for t in range(0, 8, 2)] == [4, 5, 6, 7]
    assert [at(sm, t)[1] for t in range(0, 8, 2)] == [4, 5, 6, 7]
    assert [g.kv_half(at(qm, t)[2]) for t in range(0, 8, 2)] == [0, 0, 1, 1]
    assert at(km, 0) == (0, 0, 1) and at(km, 11) == (0, 1, 1)
    # a block that is one head, or heads that pair up one to one: no half
    wide = fa._Geom(jnp.zeros((1, 512, 28 * 128)), jnp.zeros((1, 512, 512)),
                    28, 512, 512, Hkv=4)
    bert = fa._Geom(jnp.zeros((1, 512, 768)), jnp.zeros((1, 512, 768)), 12,
                    512, 512)
    assert wide.kv_half(3) is None and bert.kv_half(3) is None
    assert bert.halves == 1


# ---------------------------------------------------------------------------
# a group's query heads INSIDE a grid step of the several-block sweeps
# (PR 68): one (q block, kv block) tile of a key/value head-block a step
# with ``heads_a_step`` of its group looped in it
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _swept(H, Hkv, D, Dv, heads, window, dtype, seed=68):
    """(o, lse, dq, dk, dv) of ``H`` query heads of ``D`` on ``Hkv``
    key/value heads (values ``Dv`` wide) over 128 positions in blocks of 32,
    ``heads`` head-blocks a grid step (``heads_a_step`` replaced), float32
    numpy; then the same five by plain ``jnp`` in float32 of the same
    (rounded) operands, and the grids."""
    S, block, scale = 128, 32, D ** -0.5
    rng = np.random.RandomState(seed)
    q, k, v, w = (jnp.array((rng.randn(1, S, h * d) * 0.5).astype(np.float32)
                            ).astype(dtype)
                  for h, d in ((H, D), (Hkv, D), (Hkv, Dv), (H, Dv)))
    more = (Dv,) if Dv != D else ()
    rule = fa.heads_a_step
    fa.heads_a_step = lambda g, need, most=None: heads
    try:
        def run(q, k, v, w):
            o, lse = fa._fwd(q, k, v, scale, True, block, block, True, H, Hkv,
                             window, *more)
            return (o, lse) + tuple(fa._bwd(
                scale, True, block, block, True, (q, k, v, o, lse), w, H, Hkv,
                window, *more))
        grids = re.findall(r"grid=\(([\d, ]*)\)", str(jax.make_jaxpr(run)(
            q, k, v, w)))
        got = run(q, k, v, w)
    finally:
        fa.heads_a_step = rule

    def plain(q, k, v):
        qh, kh, vh = (t.reshape(1, S, h, -1)
                      for t, h in ((q, H), (k, Hkv), (v, Hkv)))
        s = jnp.einsum("bqhd,bkhd->bhqk", qh,
                       jnp.repeat(kh, H // Hkv, 2)) * scale
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        seen = (j <= i) & (i - j < (window or S))
        lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
        # the statistic's layout: [row, head-block, S, heads of the block]
        hpb = max(1, 128 // D)
        return _plain(qh, kh, vh, True, window).reshape(1, S, -1), \
            lse.reshape(1, H // hpb, hpb, S).transpose(0, 1, 3, 2)

    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    (o, lse), vjp = jax.vjp(plain, *f32)
    want = (o, lse) + vjp((w.astype(jnp.float32), jnp.zeros_like(lse)))
    as_np = lambda ts: [np.asarray(t.astype(jnp.float32)) for t in ts]
    return as_np(got), as_np(want), grids


#   group, heads a step: one, a proper divisor, the whole group
HEADS_A_STEP = [(6, 1), (6, 3), (6, 6), (7, 1), (7, 7), (16, 1), (16, 4),
                (16, 16)]


# full in bfloat16 and a window that is no multiple of the block in float32
# at every geometry, the cross of mask and type at one
RIDES = [(g, h) + m for g, h in HEADS_A_STEP
         for m in ((None, "bfloat16"), (40, "float32"))] \
    + [(6, 3, None, "float32"), (6, 3, 40, "bfloat16")]


@pytest.mark.parametrize("group,heads,window,dtype", RIDES)
def test_a_group_s_heads_ride_one_grid_step(group, heads, window, dtype):
    """o, lse, dq, dk, dv against plain ``jnp`` attention; against the same
    call at ONE head a step (the step before PR 68) o, lse and dq bit for
    bit (a head's recurrence is unchanged) and dk and dv to the last bits
    (the step's heads are summed before the one add into the
    accumulators).  The grids: (row, key/value head-block, chunks of the
    group, tiles), the backward's table walking the chunks."""
    got, want, grids = _swept(group, 1, 128, 128, heads, window, dtype)
    one, _, _ = _swept(group, 1, 128, 128, 1, window, dtype)
    tol = 2e-5 if dtype == "float32" else 3e-2
    for a, b, n in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        assert a.shape == b.shape, n
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), n
    for a, b, n in zip(got, one, ("o", "lse", "dq")):
        np.testing.assert_array_equal(a, b, err_msg=n)
    ulp = 1e-6 if dtype == "float32" else 2.0 ** -7
    for a, b in zip(got[3:], one[3:]):
        assert np.abs(a - b).max() <= ulp * np.abs(b).max()
    tiles = fa.kv_blocks(128, 32, 32, True, window)
    assert grids[0] == "1, 1, %d, %d" % (group // heads, tiles)
    assert grids[-1] == "1, 1, %d" % (group // heads * tiles)


def test_two_heads_of_64_a_lane_block_join_a_step_stacked():
    """LFM2's shape: the four query lane blocks of a key/value lane block in
    ONE step, each stacked on its own [2 * bq, 128] rows (``_stack_heads``);
    against one block a step, o, lse and dq bit for bit."""
    four, one = _stacked_sweep(4), _stacked_sweep(1)
    for a, b in zip(four[:3], one[:3]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(four[3:], one[3:]):
        assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max()


def _stacked_sweep(heads):
    S, H, Hkv, D, block = 128, 8, 2, 64, 32
    q, k, v, w = _packed_qkv(69, 1, S, H, Hkv, D)
    rule, fa.heads_a_step = fa.heads_a_step, lambda g, need: heads
    try:
        o, lse = fa._fwd(q, k, v, D ** -0.5, True, block, block, True, H, Hkv)
        return [np.asarray(t) for t in (o, lse) + tuple(fa._bwd(
            D ** -0.5, True, block, block, True, (q, k, v, o, lse), w, H,
            Hkv))]
    finally:
        fa.heads_a_step = rule


@pytest.mark.parametrize("what,S,group,lanes,halves,fwd,bwd", [
    # a prime group that fits: all seven in a step, both ways
    ("smallthinker_21b_a3b.s16384_scan", 16384, 7, 128, 1, 7, 7),
    # and one that does not: dk and dv of 32,768 positions leave the
    # backward's step room for one head; the forward holds no sequence
    ("twice its sequence", 32768, 7, 128, 1, 7, 1),
    ("trinity_large_preview.s6144_scan", 6144, 6, 128, 1, 6, 6),
    ("nemotron3_nano_30b_a3b.s8192_scan", 8192, 16, 128, 1, 16, 16),
    ("solar_open2_250b.s4096_scan", 4096, 8, 128, 1, 8, 8),
    ("jamba2_3b.s8192_scan", 8192, 20, 128, 1, 20, 20),
    ("lfm2_8b_a1b.s8192_scan", 8192, 4, 128, 2, 4, 4),
    ("ungrouped", 16384, 1, 128, 1, 1, 1),
])
def test_the_heads_a_step_come_from_the_shapes(monkeypatch, what, S, group,
                                               lanes, halves, fwd, bwd):
    """``heads_a_step``: the largest divisor of the group whose step fits
    SWEEP_VMEM by ``fwd_sweep_vmem_bytes`` / ``fused_sweep_vmem_bytes``; no
    configuration field, flag or name.  One head a step asks what the
    parent's call asked (Mosaic's own scope for the step)."""
    need_f = lambda n: fa.fwd_sweep_vmem_bytes(n, lanes, 2, halves=halves)
    need_b = lambda n: fa.fused_sweep_vmem_bytes(S, lanes, 2, heads=n,
                                                 halves=halves)
    assert fa.heads_a_step(group, need_f) == fwd, what
    assert fa.heads_a_step(group, need_b) == bwd, what
    assert need_b(1) == 2 * S * lanes * 6 + fa.SCOPED_VMEM
    assert not fa.past_scoped(need_f(1))
    assert need_f(fwd) <= fa.SWEEP_VMEM and need_b(bwd) <= fa.SWEEP_VMEM
    g = fa._Geom(jax.ShapeDtypeStruct((1, S, group * halves * lanes),
                                      jnp.bfloat16),
                 jax.ShapeDtypeStruct((1, S, halves * lanes), jnp.bfloat16),
                 group * halves * lanes // (lanes // halves), 512, 512,
                 Hkv=halves * halves * lanes // lanes)
    assert (g.group, g.halves) == (group, halves), what
    assert g.heads_in_step("fwd") == (fwd, need_f(fwd))
    assert g.heads_in_step("bwd") == (bwd, need_b(bwd))
    # no VMEM for a sweep (the tests' way to the two sweeps): one head
    monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    assert fa.heads_a_step(group, need_f) == 1


# ---------------------------------------------------------------------------
# UNGROUPED queries: several adjacent head-blocks of a batch row INSIDE a
# grid step of the several-block sweeps, each with its own k and v (PR 70)
# ---------------------------------------------------------------------------

#   what, heads of the row, D, Dv, window, dtype
ROWS = [("128 / 128, causal", 4, 128, 128, None, "bfloat16"),
        ("128 / 128, causal, float32", 4, 128, 128, None, "float32"),
        ("the value-width mode, 256 / 128", 4, 256, 128, None, "bfloat16"),
        ("a window that is no multiple of the block", 4, 128, 128, 40,
         "float32"),
        ("the value-width mode under a window", 4, 256, 128, 40, "bfloat16"),
        # no cell runs it and the rule keeps it at one (``heads_in_step``):
        # the body's lines take it as they are
        ("two heads of 64 a lane block", 8, 64, 64, None, "bfloat16")]


@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("what,H,D,Dv,window,dtype", ROWS,
                         ids=[m[0] for m in ROWS])
def test_a_row_s_head_blocks_ride_one_grid_step(what, H, D, Dv, window, dtype,
                                                heads):
    """o, lse, dq, dk, dv against plain ``jnp`` attention, and ALL FIVE bit
    for bit against the same call at one head-block a step (the step before
    PR 70): a head's recurrence is unchanged, and its dk and dv add into
    columns of the accumulators that are its own, in the order they did (no
    sum over the step's heads: the order of every sum is unchanged, hence no
    tolerance).  The grids: (row, the row's head-blocks over ``heads``, 1,
    tiles), the backward's without the third axis."""
    got, want, grids = _swept(H, H, D, Dv, heads, window, dtype, 71)
    one, _, one_grids = _swept(H, H, D, Dv, 1, window, dtype, 71)
    tol = 2e-5 if dtype == "float32" else 3e-2
    for a, b, n in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        assert a.shape == b.shape, n
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), n
    for a, b, n in zip(got, one, ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(a, b, err_msg=n)
    tiles, blocks = fa.kv_blocks(128, 32, 32, True, window), H * D // max(D, 128)
    assert (grids[0], grids[-1]) == (
        "1, %d, 1, %d" % (blocks // heads, tiles),
        "1, %d, %d" % (blocks // heads, tiles))
    assert (one_grids[0], one_grids[-1]) == (
        "1, %d, 1, %d" % (blocks, tiles), "1, %d, %d" % (blocks, tiles))


@pytest.mark.parametrize("what,S,H,D,Dv,fwd,bwd,mib", [
    # the forward holds no sequence: its stop; the backward's accumulators
    # are a head's own, 24 MiB a head at 16,384 positions of 128 lanes, and
    # its stop is two
    ("mistral_small_4_119b.s16384_scan", 16384, 32, 128, 128, 8, 2,
     (30, 59)),
    ("ouro_2_6b.s4096_scan / olmoe_1b_7b.s4096_scan", 4096, 16, 128, 128, 8,
     2, (30, 23)),
    # q and k two lane blocks a head, v one: 36 MiB of accumulators a head
    ("kimi_linear_48b_a3b.s16384_scan", 16384, 32, 256, 128, 8, 1, (34, 52)),
    ("twelve head-blocks: the most under the stop that divides them", 4096,
     12, 128, 128, 6, 2, (23.5, 23)),
    ("a prime count of head-blocks past the stop", 4096, 11, 128, 128, 1, 1,
     (7.25, 22)),
    ("two heads of 64 a lane block stay one block a step", 4096, 16, 64, 64,
     1, 1, (7.25, 22)),
    ("[BH, S, D]: one head-block a row", 4096, None, 128, 128, 1, 1,
     (7.25, 22)),
])
def test_the_ungrouped_heads_a_step_come_from_the_shapes(monkeypatch, what, S,
                                                         H, D, Dv, fwd, bwd,
                                                         mib):
    """``_Geom.heads_in_step`` where the queries are not grouped: the most
    head-blocks of the row (a divisor of their count, SWEEP_HEAD_BLOCKS or
    fewer, SWEEP_BWD_HEAD_BLOCKS in the backward, whose scope is what XLA
    loses for arrays of its own) whose step fits SWEEP_VMEM by the two
    counts with the k and v
    blocks, the accumulators and their output blocks ``heads`` wide; no
    configuration field, flag or name."""
    assert (fa.SWEEP_HEAD_BLOCKS, fa.SWEEP_BWD_HEAD_BLOCKS) == (8, 2)
    q = jax.ShapeDtypeStruct((1, S, (H or 1) * D), jnp.bfloat16)
    g = fa._Geom(q, q, H, 512, 512, Dv=Dv)
    assert g.group == 1 and (g.kv_heads(3), g.chunks(3)) == (3, 1)
    got = g.heads_in_step("fwd"), g.heads_in_step("bwd")
    assert (got[0][0], got[1][0]) == (fwd, bwd), what
    assert (got[0][1], got[1][1]) == tuple(int(m * 2 ** 20) for m in mib)
    lanes, vw = max(D, 128), max(Dv, 128)
    assert got[0][1] == fa.fwd_sweep_vmem_bytes(fwd, lanes, 2, vw,
                                                kv_heads=fwd)
    assert got[1][1] == fa.fused_sweep_vmem_bytes(S, lanes, 2, vw, heads=bwd,
                                                  kv_heads=bwd)
    # a head more would not fit, or pass the stop, or not divide the row
    more = [n for n in range(bwd + 1, fa.SWEEP_BWD_HEAD_BLOCKS + 1)
            if g.hpb == 1 and g.Hb % n == 0]
    assert all(fa.fused_sweep_vmem_bytes(S, lanes, 2, vw, heads=n, kv_heads=n)
               > fa.SWEEP_VMEM for n in more), what
    # no VMEM for a sweep (the tests' way to the two sweeps): one head
    monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    assert g.heads_in_step("fwd")[0] == 1


def test_a_traced_sweep_counts_the_heads_in_its_step(tmp_path):
    """``monitor.kernels.flash_sweep_calls{part, group, heads_in_step}``: one
    count a traced several-block call (``kernels/_common.count_call``), the
    ungrouped and the two-sweep backward's forward too; the one-block
    kernels count nothing."""
    from paddle_tpu import monitor

    def traced(H, Hkv, S, block):
        q, k, v, w = _packed_qkv(70, 1, S, H, Hkv, 128)
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fa.flash_attention_packed(
            *a, H, causal=True, block_q=block, block_k=block,
            n_kv_heads=Hkv) * w), argnums=(0, 1, 2)))(q, k, v)

    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        traced(6, 1, 128, 32)
        traced(2, 2, 128, 32)
        traced(2, 2, 128, 128)
        got = {tuple(r["labels"][n] for n in (
            "part", "group", "heads_in_step")): r["value"]
            for r in mon.registry.snapshot()
            if r["name"] == "monitor.kernels.flash_sweep_calls"}
    finally:
        monitor.disable()
    # (the ungrouped call's two head-blocks ride one step since PR 70)
    assert got == {("fwd", 6, 6): 1, ("bwd", 6, 6): 1,
                   ("fwd", 1, 2): 1, ("bwd", 1, 2): 1}
