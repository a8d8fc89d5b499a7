"""The flash kernels under the BLOCK-DIFFUSION rule (``flash_attention_packed(
block_diffusion=Bd)``): the rows a noised copy of a sequence over its clean
copy; ``step_table``'s fourth table against the tiles a brute-force dense
mask touches; the kernels in interpret mode against a dense softmax under
that mask, forward and dq, dk, dv, grouped and ungrouped, one sweep and two;
and the three older tables bit for bit what the parent commit's builder
gave at the accepted cells' shapes."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
# the package exports the function of the same name over the module
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def dense_rule(half, bd):
    """[2 half, 2 half] bool, the rule as ISSUE 71 writes it, pair by pair."""
    r = np.arange(2 * half)
    noised, block = r < half, (r % half) // bd
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = block[:, None], block[None, :]
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


SHAPES = [(64, 16, 16, 4), (64, 16, 32, 4), (64, 32, 16, 8), (96, 16, 48, 2),
          (128, 64, 32, 1), (64, 64, 64, 4), (48, 8, 24, 8)]


@pytest.mark.parametrize("half,bq,bk,bd", SHAPES)
@pytest.mark.parametrize("kv_major", [False, True])
def test_the_rule_s_table_is_the_tiles_a_dense_mask_touches(half, bq, bk, bd,
                                                            kv_major):
    S = 2 * half
    mask = dense_rule(half, bd)
    live = mask.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3))
    table = fa.step_table(S, S, bq, bk, False, kv_major=kv_major, blocks=bd)
    tiles = list(zip(table[0].tolist(), table[1].tolist()))
    assert len(set(tiles)) == len(tiles) == int(live.sum())
    assert all(live[i, j] for i, j in tiles)            # no empty tile
    # a sweep's first and last steps open and close it, in order
    major = table[1] if kv_major else table[0]
    first = np.flatnonzero(table[3] & fa.FIRST)
    last = np.flatnonzero(table[3] & fa.LAST)
    assert len(first) == len(last) == len(set(major.tolist()))
    assert (np.diff(major) >= 0).all()
    if bq == bk:
        nq = half // bq
        assert len(tiles) == nq * (nq + 1) + nq
    assert fa.kv_blocks(S, bq, bk, False, blocks=bd) == len(tiles)
    tiles_, share = fa.blockdiff_live_share(S, bq, bk, bd)
    assert tiles_ == len(tiles)
    np.testing.assert_allclose(share, mask.sum() / (len(tiles) * bq * bk))
    assert mask.sum() == half * (half + bd)


def test_the_cell_s_table_and_what_a_tile_holds():
    """S = 8,192 clean tokens in 512-row tiles at blocks of 4: 288 of the
    square's 1,024 tiles, a diagonal noised tile 0.8 % live."""
    assert fa.kv_blocks(16384, 512, 512, False, blocks=4) == 288
    tiles, share = fa.blockdiff_live_share(16384, 512, 512, 4)
    np.testing.assert_allclose(share, 8192 * 8196 / (288 * 512 * 512))
    heads, steps = fa.packed_grid(1, 16384, 32, 128, 512, 512, n_kv_heads=4,
                                  blocks=4)
    assert 8 % heads == 0 and steps == 32 // heads * 288


def test_a_tile_must_hold_whole_blocks_and_a_copy_whole_tiles():
    for S, bq, bk, bd in [(128, 16, 16, 3), (96, 32, 16, 4), (128, 16, 24, 8)]:
        with pytest.raises(AssertionError):
            fa.step_table(S, S, bq, bk, False, blocks=bd)


# sha256[:16] of ``step_table(...)``'s bytes on the parent commit (16069be),
# at the accepted cells' shapes: (S, Sk, bq, bk, causal, window, group,
# kv_major)
PARENT_TABLES = {
    (16384, 16384, 512, 512, True, None, 1, False): "5dc68a2d56248a6e",
    (16384, 16384, 512, 512, True, 4096, 1, False): "dd9f3b9eff1c500f",
    (16384, 16384, 512, 512, True, None, 1, True): "89c9d208b5ef9e83",
    (16384, 16384, 512, 512, True, 4096, 7, True): "83b169408e6671ca",
    (6144, 6144, 512, 512, True, 4096, 1, False): "68aaf0ce02490bf5",
    (6144, 6144, 512, 512, True, None, 3, False): "f295085768c09d73",
    (4096, 4096, 512, 512, True, None, 1, False): "0122a4c827990b2e",
    (4096, 4096, 512, 512, True, None, 4, False): "d167b8e826e5f97f",
    (8192, 8192, 512, 512, True, None, 2, False): "98a61b6cc85ebc50",
    (8192, 8192, 512, 512, True, 2048, 1, False): "86abe552f767693e",
    (1024, 2048, 256, 512, False, None, 1, False): "5774db206f804b9e",
    (2048, 1024, 512, 256, False, None, 2, True): "ead0e12b359456c9",
}


@pytest.mark.parametrize("shape", list(PARENT_TABLES), ids=str)
def test_the_triangle_the_band_and_the_rectangle_are_the_parent_s(shape):
    table = fa.step_table(*shape)
    assert hashlib.sha256(table.tobytes()).hexdigest()[:16] == \
        PARENT_TABLES[shape]


def dense_attention(q, k, v, heads, kv_heads, mask):
    """Softmax attention a head under ``mask`` in float32 at ``highest``."""
    b, s, _ = q.shape
    d = q.shape[-1] // heads
    qh = q.reshape(b, s, kv_heads, heads // kv_heads, d)
    kh, vh = (x.reshape(b, s, kv_heads, -1) for x in (k, v))
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, kh) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vh).reshape(b, s, -1)


KERNEL_CASES = {            # heads, key/value heads, head width, blocks
    "group_of_8": (8, 1, 128, 4),
    "ungrouped": (2, 2, 128, 4),
    "two_heads_a_lane_block": (4, 2, 64, 8),
}


@pytest.mark.parametrize("sweeps", [1, 2])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernels_equal_a_dense_softmax_under_the_rule(case, sweeps,
                                                          monkeypatch):
    """Interpret mode, float32: o, dq, dk and dv of the rule's sweeps (the
    fused backward, and the two sweeps a longer sequence takes) against
    ``jax.grad`` of the dense form."""
    heads, kv_heads, d, bd = KERNEL_CASES[case]
    if sweeps == 2:     # no whole-sequence accumulator fits: two sweeps
        monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    half, tile = 64, 16
    r = np.random.RandomState(heads)
    q, k, v, do = (jnp.asarray(r.randn(2, 2 * half, n * d), jnp.float32)
                   for n in (heads, kv_heads, kv_heads, heads))
    mask = jnp.asarray(dense_rule(half, bd))

    def kernel(q, k, v):
        return fa.flash_attention_packed(
            q, k, v, heads, block_q=tile, block_k=tile, n_kv_heads=kv_heads,
            block_diffusion=bd)

    mon = monitor.enable()
    try:
        mon.registry.reset()
        o, pull = jax.vjp(kernel, q, k, v)
        got = (o,) + pull(do)
        calls = {(r["labels"]["part"], r["labels"].get("sweeps")): r["value"]
                 for r in mon.registry.snapshot()
                 if r["name"] == "monitor.kernels.flash_blockdiff_calls"
                 and r["labels"]["fused"] == 1}
    finally:
        monitor.disable()
    assert calls == {("fwd", None): 1, ("bwd", sweeps): 1}
    o, pull = jax.vjp(lambda q, k, v: dense_attention(
        q, k, v, heads, kv_heads, mask), q, k, v)
    want = (o,) + pull(do)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=2e-5 * float(
            jnp.abs(w).max()), err_msg=name)


def test_the_rule_s_kernels_carry_names_of_their_own():
    q = jnp.zeros((1, 128, 256), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        fa.flash_attention_packed(q, q[..., :128], q[..., :128], 2,
                                  block_q=16, block_k=16, n_kv_heads=1,
                                  block_diffusion=4))))(q))
    assert "flash_bd_fwd" in text and "flash_bd_bwd_fused" in text
    assert "name=flash_fwd" not in text


def test_a_noised_row_reads_its_block_and_a_clean_row_itself():
    """Every row has a key: no row of the output is the empty softmax's."""
    half, bd = 64, 4
    mask = dense_rule(half, bd)
    assert mask.any(axis=1).all()
    assert (mask[:half, :half].sum(1) == bd).all()
    assert not mask[half:, :half].any()
    # block 0 of the noised copy has no clean key at all
    assert not mask[:bd, half:].any() and mask[bd, half:half + bd].all()
