"""The expert layer's kernels compiled for a described v5e at the cells'
shapes: the grouped matmuls at the rule's tiles and the row sums
(``kernels/moe_rows.py``).  Nothing runs; no chip is needed
(``tests/tpu_compile.py``)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (_vmem, one_chip)  # noqa: F401


@pytest.mark.parametrize("what,slots,k,width,m", [
    ("smallthinker_21b_a3b.s16384_scan", 98304, 6, 2560, 30720),
    ("lfm2_8b_a1b.s8192_scan", 65536, 4, 2048, 20480),
    ("mistral_small_4_119b.s16384_scan", 65536, 4, 4096, 5120),
    ("olmoe_1b_7b.s4096_scan, every expert held", 131072, 8, 2048, 131072),
])
def test_the_moe_row_kernel_compiles_for_a_v5e(one_chip, what, slots, k,
                                               width, m):
    """``moe_rows_sum`` at one layer's shapes of the three cells that hold a
    share of their experts and of the one that holds them all (pair slots
    T*k, k, E, the first capacity's rows M; bf16): the row DMAs from an HBM array whose rows lie contiguous (the
    words ``moe_rows_words`` writes, handed over as they lie: a bitcast, no
    copy between the kernels), the strided reads of the fetched rows and the
    bf16 tiles of the result are what Mosaic has to take.  A grid step holds
    256 tokens: the buffer of fetched rows and the result's two blocks
    within the VMEM the call asks for, and in scalar memory that block's
    pairs alone (two lists of 256 * k, twice for the pipeline, and the
    blocks' counts), not the layer's."""
    mr = importlib.import_module("paddle_tpu.kernels.moe_rows")
    rows = jax.ShapeDtypeStruct((m, width), jnp.bfloat16, sharding=one_chip)
    inv = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda r, i: mr.moe_rows_sum(r, i, k, interpret=False)
                   ).lower(rows, inv).compile().as_text()
    tb, blocks = mr.token_block(k, width // 2), slots // k // 256
    assert tb == 256 and text.count("tpu_custom_call") == 2, what
    asked, took = _vmem(text, "moe_rows_sum")
    assert asked == mr.vmem_bytes(k, tb, width // 2) <= 18 * 2 ** 20, what
    assert (k * tb + 1) * width * 2 + 2 * tb * width * 2 <= took < asked, what
    call, = [l for l in text.splitlines() if " custom-call(" in l
             and re.search(r"%?moe_rows_sum[\w.\-]* = ", l)]
    lists = "s32[%d,1,%d]" % (blocks, tb * k)
    assert call.count(lists + "{2,1,0}") == 2, what
    assert "s32[%d]" % slots not in call, what
    assert 2 * 2 * tb * k * 4 + blocks * 4 < 64 * 2 ** 10, what
    assert re.search(r"u32\[%d,1,%d\]\S* bitcast\(\S*moe_rows_words"
                     % (m, width // 2), text), what


def test_the_moe_row_kernel_compiles_at_ten_held_of_a_router_of_320(one_chip):
    """``moe_rows_sum`` at one layer's shape of ``solar_open2_250b.
    s4096_scan`` (32,768 pair slots, k = 8, rows of 4,096 in bf16): 10 held
    of a router 320 wide (two and a half lane tiles; a share that is no
    multiple of 8) make a first capacity of 1,536 rows, 1.5 x the 1,024
    uniform routing brings; at eight rows of 2,048 words a token a grid step
    holds 128 tokens, within the VMEM the call asks for."""
    mr = importlib.import_module("paddle_tpu.kernels.moe_rows")
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    slots, k, width = 4096 * 8, 8, 4096
    m = moe._held_capacities(slots, 10, 320)[0]
    assert m == 1536
    rows = jax.ShapeDtypeStruct((m, width), jnp.bfloat16, sharding=one_chip)
    inv = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda r, i: mr.moe_rows_sum(r, i, k, interpret=False)
                   ).lower(rows, inv).compile().as_text()
    tb = mr.token_block(k, width // 2)
    assert tb == 128 and text.count("tpu_custom_call") == 2
    asked, took = _vmem(text, "moe_rows_sum")
    assert asked == mr.vmem_bytes(k, tb, width // 2) <= 18 * 2 ** 20
    assert took < asked
    assert re.search(r"u32\[%d,1,%d\]\S* bitcast\(\S*moe_rows_words"
                     % (m, width // 2), text)


MOE_CELLS = {      # rows at the first capacity, groups, E, F of one layer
    "olmoe_1b_7b.s4096_scan": (131072, 64, 2048, 1024),
    "lfm2_8b_a1b.s8192_scan": (20480, 8, 2048, 1792),
    "smallthinker_21b_a3b.s16384_scan": (30720, 16, 2560, 768),
    "mistral_small_4_119b.s16384_scan": (5120, 8, 4096, 2048),
    "trinity_large_preview.s6144_scan": (1024, 8, 3072, 3072),
}


@pytest.mark.parametrize("what", MOE_CELLS)
def test_the_grouped_matmuls_compile_for_a_v5e_at_the_rule_s_tiles(
        one_chip, monkeypatch, what):
    """``megablox``'s ``gmm``, ``gmm`` with the weights transposed and
    ``tgmm`` as ``parallel/moe.py`` calls them at one layer's shapes of the
    five sparse cells, bf16, at the tiles ``moe._tiling`` gives each call:
    ``megablox`` asks Mosaic for no VMEM of its own, so what a grid step
    holds has to fit the scope a v5e kernel has by default (16 MiB).  What
    the compiled kernel took is at most the rule's own count
    (``moe._vmem_bytes``, within VMEM_BUDGET) and 3 MiB of the kernels'
    temporaries (2.1 MiB read: the transposed weight block's copy at
    LFM2's 512 x 1792 x 512)."""
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    m, groups, E, F = MOE_CELLS[what]
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(moe, "on_tpu", lambda: True)   # compile, not interpret
    for k, n in ((E, 2 * F), (F, E)):
        fwd = jax.jit(moe._gmm).lower(
            S(m, k), S(groups, k, n), sizes).compile().as_text()
        dx = jax.jit(lambda g, w, s: moe._gmm(g, w, s, transpose_rhs=True)
                     ).lower(S(m, n), S(groups, k, n), sizes
                             ).compile().as_text()
        dw = jax.jit(lambda r, w, s, g: moe._grouped_matmul_bwd(
            (r, w, s), g)[1]).lower(
                S(m, k), S(groups, k, n), sizes, S(m, n)).compile().as_text()
        for text, kernel, (kk, nn), is_dw in (
                (fwd, "gmm", (k, n), False), (dx, "gmm", (n, k), False),
                (dw, "tgmm", (k, n), True)):
            asked, took = _vmem(text, kernel)
            count = moe._vmem_bytes(
                *moe._tiling(m, kk, nn, groups, 2, dw=is_dw), 2, is_dw)
            assert asked is None, (what, kernel)
            assert count // 2 < took <= count + 3 * 2 ** 20 < 16 * 2 ** 20, (
                what, kernel, kk, nn, count, took)


def test_the_moe_row_kernel_compiles_for_rows_of_an_odd_number_of_registers(
        one_chip):
    """``moe_rows_sum`` at one sparse layer's shape of
    ``nemotron3_nano_30b_a3b.s8192_scan`` (98,304 pair slots, k = 6, 15,360
    rows at the first capacity, bf16) whose rows are 2,688 = 21 x 128
    columns: 1,344 words would be ten registers and a half, which no row DMA
    may slice, so a row goes as ``_half`` = 1,408 words, the last register's
    high bits zero."""
    mr = importlib.import_module("paddle_tpu.kernels.moe_rows")
    slots, k, width, m = 98304, 6, 2688, 15360
    assert mr._half(width) == 1408 and mr._half(2560) == 1280
    rows = jax.ShapeDtypeStruct((m, width), jnp.bfloat16, sharding=one_chip)
    inv = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda r, i: mr.moe_rows_sum(r, i, k, interpret=False)
                   ).lower(rows, inv).compile().as_text()
    tb = mr.token_block(k, mr._half(width))
    assert tb == 256 and text.count("tpu_custom_call") == 2
    asked, took = _vmem(text, "moe_rows_sum")
    assert took < asked == mr.vmem_bytes(k, tb, 1408) <= 18 * 2 ** 20
    assert re.search(r"u32\[%d,1,1408\]\S* bitcast\(\S*moe_rows_words" % m,
                     text)


def test_the_ungated_grouped_matmuls_compile_at_a_width_off_the_lane_tile(
        one_chip, monkeypatch):
    """The six calls of one sparse layer of ``nemotron3_nano_30b_a3b.
    s8192_scan`` (15,360 rows at the first capacity, 16 groups, E = 2,688,
    UNGATED experts of width 1,856 = 29 x 64, no whole number of lane
    tiles): ``moe._tiling`` leaves 1,856 whole, as one column tile and as
    one contraction tile, and Mosaic takes a block that wide (a block's
    last dimension may be the array's own) within the default scope."""
    moe = importlib.import_module("paddle_tpu.parallel.moe")
    m, groups, E, F = 15360, 16, 2688, 1856
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    monkeypatch.setattr(moe, "on_tpu", lambda: True)   # compile, not interpret
    tiles = {}
    for k, n in ((E, F), (F, E)):
        fwd = jax.jit(moe._gmm).lower(
            S(m, k), S(groups, k, n), sizes).compile().as_text()
        dx = jax.jit(lambda g, w, s: moe._gmm(g, w, s, transpose_rhs=True)
                     ).lower(S(m, n), S(groups, k, n), sizes
                             ).compile().as_text()
        dw = jax.jit(lambda r, w, s, g: moe._grouped_matmul_bwd(
            (r, w, s), g)[1]).lower(
                S(m, k), S(groups, k, n), sizes, S(m, n)).compile().as_text()
        for text, kernel, (kk, nn), is_dw in (
                (fwd, "gmm", (k, n), False), (dx, "gmm", (n, k), False),
                (dw, "tgmm", (k, n), True)):
            asked, took = _vmem(text, kernel)
            tiling = moe._tiling(m, kk, nn, groups, 2, dw=is_dw)
            tiles[kernel, kk, nn] = tiling
            count = moe._vmem_bytes(*tiling, 2, is_dw)
            assert asked is None, kernel
            assert count // 2 < took <= count + 3 * 2 ** 20 < 16 * 2 ** 20, (
                kernel, kk, nn, count, took)
    assert tiles == {("gmm", E, F): (128, 896, F), ("gmm", F, E): (128, F, 896),
                     ("tgmm", E, F): (128, 384, F),
                     ("tgmm", F, E): (128, F, 384)}
