"""The flash kernels compiled for a described v5e, at the benchmark cells'
real shapes: what interpret mode cannot see (Mosaic's tiling rules, the
scoped VMEM a grid step may hold).  The several-block and one-block sweeps,
their Mosaic digests (``ONE_BLOCK_MOSAIC``, ``SWEEP_MOSAIC``) and the grouped
sweeps a cell's program traces.  Nothing runs; no chip is needed
(``tests/tpu_compile.py``)."""

import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (JAMBA, LFM2, MISTRAL4, NEMOTRON, SMALLTHINKER, SOLAR,
                         TRINITY, _compiled, _vmem, one_chip)  # noqa: F401

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.mark.parametrize("what,entry,B,S,H,D,causal,pairs", [
    ("bert_base.s128_scan", "packed", 256, 128, 12, 64, False, 6),
    ("bert_base.s512_scan", "packed", 64, 512, 12, 64, False, 1),
    ("fine-tuning at 384", "packed", 32, 384, 12, 64, False, 2),
    ("a prime batch, causal", "packed", 7, 128, 12, 64, True, 21),
    ("heads the packed layout cannot tile", "bshd", 32, 128, 3, 64, False, 4),
])
def test_forward_and_backward_compile_for_a_v5e(one_chip, what, entry, B, S,
                                                H, D, causal, pairs):
    if entry == "packed":
        assert fa.packed_grid(B, S, H, D, 512, 512)[0] == pairs
        x = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16,
                                 sharding=one_chip)
        attn = lambda q, k, v: fa.flash_attention_packed(
            q, k, v, H, causal=causal, block_q=512, block_k=512,
            interpret=False)
    else:
        assert fa.grid_geometry(B * H, S, S, 1, D, 2, S, S)[0] == pairs
        x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                 sharding=one_chip)
        attn = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=512, block_k=512,
            interpret=False)

    text, grids, mosaic = _compiled(attn, x, x, x, x)
    assert text.count("tpu_custom_call") >= 2, what
    # one block: the kernels PR 28 left, to the letter (the several-block
    # shapes: ``test_the_ungrouped_sweeps_compile_for_a_v5e``)
    assert set(grids) == {"flash_fwd", "flash_bwd_fused"}, what
    assert _vmem(text, "flash_bwd_fused")[0] is None, what
    assert mosaic == ONE_BLOCK_MOSAIC[what], what


DOTS3_SLIDING = (1, 8192, 16, 256, 128, 513)    # the share's 16 of 64 heads


@pytest.mark.parametrize("what,shape,steps,fwd,bwd,mib", [
    # four rows of sixteen heads of 128: eight head-blocks of a row a
    # forward step, two a backward one (dk and dv of 4,096 positions: 6 MiB
    # a head; four would fit and ask 40 MiB: ``SWEEP_BWD_HEAD_BLOCKS``)
    ("olmoe_1b_7b.s4096_scan", (4, 4096, 16, 128, 128, None), 36, 8, 2,
     (30, 23)),
    # the same heads and length at the looped stack's batch: 48 layer
    # applications a step call these
    ("ouro_2_6b.s4096_scan", (2, 4096, 16, 128, 128, None), 36, 8, 2,
     (30, 23)),
    # 24 MiB of accumulators and output blocks a head at 16,384 positions:
    # two heads a backward step, 54.7 MiB by Mosaic's count of the 59 asked
    ("mistral_small_4_119b.s16384_scan, the latent expanded to 32 heads",
     MISTRAL4[:3] + (128, 128, None), 528, 8, 2, (30, 59)),
    # 16 heads of 256 / 128 under a window of 513: the band's 31 tiles
    ("dots3_note_prev.s8192_scan, a sliding layer", DOTS3_SLIDING, 31, 8, 2,
     (34, 49)),
])
def test_the_ungrouped_sweeps_compile_for_a_v5e(one_chip, what, shape, steps,
                                                fwd, bwd, mib):
    """Every head its own key/value head over several blocks (PR 70): a grid
    step holds ``fwd`` (``bwd``) ADJACENT head-blocks of the batch row, each
    with k and v columns of its own in blocks that many head-blocks wide,
    unrolled in the body; the backward's two whole-sequence accumulators and
    their output blocks are ``bwd`` head-blocks wide.  The grids are the
    tables over (row, the row's head-blocks a step's worth at a time); the
    calls ask what ``_Geom.heads_in_step`` counts and Mosaic takes less."""
    B, S, H, D, Dv, window = shape
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xv = jax.ShapeDtypeStruct((B, S, H * Dv), jnp.bfloat16, sharding=one_chip)
    more = dict(v_head_dim=Dv, scale=192 ** -0.5) if Dv != D else {}
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, block_q=512, block_k=512, interpret=False,
        window=window, **more)
    names = ("flash_swa_fwd", "flash_swa_bwd_fused") if window else (
        "flash_fwd", "flash_bwd_fused")
    text, grids, mosaic = _compiled(attn, xq, xq, xv, xv)
    assert fa.kv_blocks(S, 512, 512, True, window) == steps
    assert grids == dict(zip(names, [(B, H // fwd, 1, steps),
                                     (B, H // bwd, steps)])), what
    assert fa.packed_grid(B, S, H, D, 512, 512, causal=True, window=window,
                          part="bwd") == (bwd, B * H // bwd * steps)
    if what in SWEEP_MOSAIC:
        assert mosaic == SWEEP_MOSAIC[what], what
    g = fa._Geom(xq, xq, H, 512, 512, window=window, Dv=Dv)
    (asked_f, took_f), (asked, took) = (_vmem(text, n) for n in names)
    assert (fwd, asked_f) == g.heads_in_step("fwd")
    assert (bwd, asked) == g.heads_in_step("bwd")
    assert (asked_f, asked) == tuple(int(m * 2 ** 20) for m in mib), what
    # the accumulators and the single-buffered output blocks of the step's
    # heads, and a step's own blocks and tiles beside them
    assert bwd * S * (D + Dv) * (4 + 2) < took < asked, what
    assert 6 * 2 ** 20 < took_f < asked_f, what


# The one-block kernels' Mosaic modules as the several-block backward's
# parent (6d15aec) lowers them, forward and backward: sha1 of each
# ``tpu_custom_call`` body's text without debug info (``_compiled``).  A PR
# that means to change these kernels replaces the digests; any other finds
# here that it changed what every BERT cell runs.
ONE_BLOCK_MOSAIC = {
    "bert_base.s128_scan": ["1d482125fe7a", "496403bedea7"],
    "bert_base.s512_scan": ["d6584da870c2", "a0feeacea941"],
    "fine-tuning at 384": ["a2edfc8f7477", "4de8b028195f"],
    "a prime batch, causal": ["f7cb281f682b", "40b5c4ca2615"],
    "heads the packed layout cannot tile": ["72dc7b47f49c", "85a570491d08"],
}


# The several-block kernels at a head a lane block (width 128), forward and
# backward.  Replaced like ``ONE_BLOCK_MOSAIC``.  PR 68 took SmallThinker's
# two entries anew ON PURPOSE (a group's seven query heads ride one grid
# step; they were 55126c1bd654 / 170f86a03873 full and 985173cce04a /
# 42134345da2e windowed).  PR 70 took the three UNGROUPED entries anew ON
# PURPOSE (eight adjacent head-blocks of the row ride a forward step, two a
# backward one, each with k and v of its own; as PR 41's parent c1b744a lowered them, one head-block a step,
# they were 05626943d473 / eba4a626459f (OLMoE), 8aec72d32a18 /
# afa8aab872da (Ouro) and e267681a781d / ad2a365dd0c8 (Mistral), and a call
# whose rule answers 1 lowers to those still): the GROUPED entries are
# PR 68's, byte for byte.
SWEEP_MOSAIC = {
    "olmoe_1b_7b.s4096_scan": ["b712f2862fca", "82bfd1492339"],
    "ouro_2_6b.s4096_scan": ["74c52422a398", "fd28d46cc1f8"],
    "smallthinker_21b_a3b.s16384_scan, a full layer":
        ["465f38b6b128", "1d46a9c9d9f7"],
    "smallthinker_21b_a3b.s16384_scan, a windowed layer":
        ["e3a26b4fe57d", "987d580b7182"],
    "mistral_small_4_119b.s16384_scan, the latent expanded to 32 heads":
        ["03bb65ba345d", "eaa2a244e9a1"],
}


@pytest.mark.parametrize("what,shape,window,names,steps,heads,mib", [
    ("smallthinker_21b_a3b.s16384_scan, a full layer", SMALLTHINKER, None,
     ("flash_fwd", "flash_bwd_fused"), 528, 7, (23.75, 44.5)),
    ("smallthinker_21b_a3b.s16384_scan, a windowed layer", SMALLTHINKER, 4096,
     ("flash_swa_fwd", "flash_swa_bwd_fused"), 252, 7, (23.75, 44.5)),
    ("lfm2_8b_a1b.s8192_scan, two heads a lane block", LFM2, None,
     ("flash_fwd", "flash_bwd_fused"), 136, 4, (27.5, 39.5)),
    ("trinity_large_preview.s6144_scan, a full layer", TRINITY, None,
     ("flash_fwd", "flash_bwd_fused"), 78, 6, (21, 27.5)),
    ("trinity_large_preview.s6144_scan, a windowed layer", TRINITY, 4096,
     ("flash_swa_fwd", "flash_swa_bwd_fused"), 72, 6, (21, 27.5)),
    ("nemotron3_nano_30b_a3b.s8192_scan", NEMOTRON, None,
     ("flash_fwd", "flash_bwd_fused"), 136, 16, (48.5, 50.5)),
    ("solar_open2_250b.s4096_scan", SOLAR, None,
     ("flash_fwd", "flash_bwd_fused"), 36, 8, (26.5, 28.5)),
    ("jamba2_3b.s8192_scan", JAMBA, None,
     ("flash_fwd", "flash_bwd_fused"), 136, 20, (59.5, 58.5)),
])
def test_grouped_and_windowed_kernels_compile_for_a_v5e(
        one_chip, what, shape, window, names, steps, heads, mib):
    """28 query heads on 4 key/value heads of 128 over 16,384 positions:
    the index maps' reads of the scalar-prefetched step table and the
    backward's one sweep over a group's heads, dk and dv of the whole
    sequence in two float32 accumulators (16 MiB of the 44.5 the call asks
    for), are what Mosaic has to take; at 32 on 8 heads of 64, the two heads
    of a lane block stacked along rows (PR 41): the lane rotation that moves
    a head to its key/value head's columns, the [1024, 512] tiles of a step
    and the stack's scratch.  A group's ``heads`` query head-blocks ride ONE
    grid step (PR 68: seven, Trinity's six, Solar's eight, Nemotron's
    sixteen, Jamba's twenty, LFM2's four stacked blocks), unrolled in the
    body; the calls ask the VMEM ``_Geom.heads_in_step`` counts (the
    forward too, past Mosaic's own 16 MiB) and Mosaic takes less.  The
    grids are the tables: (row, key/value head-block, chunk of
    ``heads`` of its group) by the blocks under the diagonal (in the band),
    the backward's (row, key/value head-block) by the chunks' times as
    many."""
    B, S, H, Hkv, D = shape
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xk = jax.ShapeDtypeStruct((B, S, Hkv * D), jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, block_q=512, block_k=512, interpret=False,
        n_kv_heads=Hkv, window=window)

    text, grids, mosaic = _compiled(attn, xq, xk, xk, xq)
    for name in names:
        assert name in text, (what, name)
    stacked = fa._heads_per_block(D) if Hkv != H else 1
    assert stacked == (2 if shape is LFM2 else 1)
    if what in SWEEP_MOSAIC:
        assert mosaic == SWEEP_MOSAIC[what], what
    kv_blocks, chunks = Hkv * D // 128, H // Hkv // heads
    assert fa.kv_blocks(S, 512, 512, True, window) == steps
    assert fa.packed_grid(B, S, H, D, 512, 512, n_kv_heads=Hkv, causal=True,
                          window=window) == (heads,
                                             B * kv_blocks * chunks * steps)
    assert grids == dict(zip(names, [(B, kv_blocks, chunks, steps),
                                     (B, kv_blocks, chunks * steps)])), what
    g = fa._Geom(xq, xk, H, 512, 512, Hkv, window)
    (asked_f, took_f), (asked, took) = (_vmem(text, n) for n in names)
    assert (heads, asked_f or fa.SCOPED_VMEM) == (
        g.heads_in_step("fwd")[0],
        max(g.heads_in_step("fwd")[1], fa.SCOPED_VMEM))
    assert (heads, asked) == g.heads_in_step("bwd")
    # (one head a step: the default scope, which the text spells out where
    # XLA keeps an array of its own in VMEM beside the call)
    assert (asked_f or fa.SCOPED_VMEM, asked) == tuple(
        int(m * 2 ** 20) for m in mib), what
    # the accumulators and the single-buffered output blocks, and a step's
    # own blocks and tiles beside them
    least = S * 128 * (4 + 2) * 2 + (stacked * heads - 1) * 2 ** 20
    assert least < took < asked, what
    assert took_f < (asked_f or fa.SCOPED_VMEM), what
    assert (took_f > 6 * 2 ** 20) == (stacked * heads > 1), what


def test_the_value_width_kernels_compile_for_a_v5e(one_chip):
    """kimi_linear_48b_a3b.s16384_scan's latent layer: 32 heads whose q and
    k stand in 256 lanes (192 and 64 zeros) and whose v, o, do and dv are
    128 wide, over 16,384 positions.  A query head-block is two lane blocks
    and a value's one; the backward is ONE sweep, dk of the whole sequence
    in a [16384, 256] float32 accumulator and dv in a [16384, 128] one (24
    MiB of the 52 the call asks for, where one width of 256 would ask for
    64), so a backward step has room for ONE head (36 MiB of accumulators
    and output blocks each); the forward holds no sequence and takes eight
    head-blocks of the row a step (PR 70: 34 MiB asked)."""
    B, S, H, D, Dv = 1, 16384, 32, 256, 128
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xv = jax.ShapeDtypeStruct((B, S, H * Dv), jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, scale=192 ** -0.5, block_q=512, block_k=512,
        interpret=False, v_head_dim=Dv)
    assert jax.eval_shape(attn, xq, xq, xv).shape == xv.shape
    text, grids, _ = _compiled(attn, xq, xq, xv, xv)
    steps = fa.kv_blocks(S, 512, 512, True)
    assert grids == {"flash_fwd": (B, H // 8, 1, steps),
                     "flash_bwd_fused": (B, H, steps)}
    asked_f, took_f = _vmem(text, "flash_fwd")
    assert asked_f == fa.fwd_sweep_vmem_bytes(
        8, D, 2, Dv, kv_heads=8) == 34 * 2 ** 20 and took_f < asked_f
    asked, took = _vmem(text, "flash_bwd_fused")
    assert asked == fa.fused_sweep_vmem_bytes(S, D, 2, Dv) == 52 * 2 ** 20
    assert fa.fused_sweep_vmem_bytes(S, D, 2) == 64 * 2 ** 20
    assert S * (D + Dv) * (4 + 2) < took < asked


def test_a_sequence_past_the_rule_compiles_as_two_sweeps(one_chip):
    """S = 65,536 at 128 lanes would ask for 112 MiB: ``flash_bwd_dq`` and
    ``flash_bwd_dkv`` in Mosaic's own scope, as every several-block shape
    ran before the one sweep."""
    B, S, H, Hkv, D = 1, 65536, 4, 2, 128
    assert fa.bwd_sweeps(S, 512, D, 2, H // Hkv) == 2
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xk = jax.ShapeDtypeStruct((B, S, Hkv * D), jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, block_q=512, block_k=512, interpret=False,
        n_kv_heads=Hkv, window=4096)
    text, grids, _ = _compiled(attn, xq, xk, xk, xq)
    steps = fa.kv_blocks(S, 512, 512, True, 4096)
    # the forward holds no sequence: its group's two heads ride one step;
    # the two backward sweeps stay a head-block a step
    assert grids == {"flash_swa_fwd": (B, 2, 1, steps),
                     "flash_swa_bwd_dq": (B, 2, 2, steps),
                     "flash_swa_bwd_dkv": (B, 2, 2 * steps)}
    # the default scope, which the text spells out where XLA keeps an array
    # of its own in VMEM beside the call (``_vmem``)
    for kernel in ("flash_swa_bwd_dq", "flash_swa_bwd_dkv"):
        assert _vmem(text, kernel)[0] in (None, fa.SCOPED_VMEM)


@pytest.mark.parametrize("cell,kind,kernels", [
    # LFM2's two heads a lane block
    ("lfm2_8b_a1b.s8192_scan", "(None, True)",
     {"qk_rope_fwd", "qk_rope_bwd", "flash_fwd", "flash_delta",
      "flash_bwd_fused"}),
    # Nemotron-H's Mamba-2 mixer: two groups of 128 channels, float32
    ("nemotron3_nano_30b_a3b.s8192_scan", "mamba2",
     {"mamba_filter_fwd", "mamba_filter_bwd", "ssd_scan_fwd", "ssd_scan_bwd",
      "gated_norm_fwd", "gated_norm_bwd"}),
    # Kimi-Linear's KDA mixer: the tiny heads of 16 (32 channels a filter)
    # keep the ``jnp`` lines around ``kda_chunked`` and the filters'
    ("kimi_linear_48b_a3b.s16384_scan", "kda", set()),
])
def test_attn_outside_hlo_smoke(one_chip, capsys, cell, kind, kernels):
    """The script end to end at a tiny configuration, the kernels compiled
    for the described chip."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    hlo = importlib.import_module("attn_outside_hlo")
    report = hlo.main([cell, "--tiny", "--top", "3"])
    assert report["kind"] == kind and report["seq"] == 256
    assert set(report["kernels_gb"]) == kernels
    assert 0 < report["gb"]["other"]
    if kind != "mamba2":        # a tiny mixer's matmuls are its least part
        assert report["gb"]["other"] < report["gb"]["matmul"]
    printed = capsys.readouterr().out.splitlines()
    assert json.loads(printed[-1]) == report
    # XLA's estimated cycles beside each listed instruction's bytes, and
    # their sum over "other" in milliseconds (PR 55)
    assert len(printed) == 4 and all(
        re.match(r"\s*[\d.]+ MB\s+\d+ cycles  %", l) for l in printed[:3])
    assert 0 < report["other_estimated_ms"] < 1
    # the gradients are the leaves' the branch reads (PR 55): a layer's
    # experts and norms of the other branch are none of them
    cfg, batch, seq = hlo.cell_config(cell, tiny=True)
    layer = hlo.default_kind(cfg)
    leaves, h = hlo.layer_shapes(cfg, batch, seq, layer)
    read = hlo.leaves_read(hlo.branch_of(cfg, layer), leaves, h)
    experts = {"ln1_scale", "ln2_scale", "router", "we_down", "we_gate_up"}
    assert set(leaves) - set(read) == {
        "mamba2": {"ln1_scale"},
        "kda": experts | {"ws_down", "ws_gate_up"}}.get(kind, experts)
    assert {"mamba2": {"w_in", "w_out"},
            "kda": {"a_log", "dt_bias", "o_norm", "w_fb", "w_gb", "wo"}}.get(
                kind, set(read)) <= set(read)
    assert kind in ("mamba2", "kda") or set(read) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm"}


# cell -> the several-block sweeps one traced forward + backward of its
# PUBLISHED configuration counts in ``monitor.kernels.flash_sweep_calls``,
# (part, group, heads in a step): SmallThinker's full and windowed layer
# kinds (a forward each and one recomputed under remat), a group's seven
# heads in every step; Nemotron's sixteen; LFM2's four stacked lane blocks;
# Mistral-Small-4's and Ouro's ungrouped heads, eight head-blocks of the row
SWEEPS = {
    "smallthinker": ("smallthinker_21b_a3b_config", (1, 16384),
                     {("fwd", 7, 7): 4, ("bwd", 7, 7): 2}),
    "nemotron_h": ("nemotron3_nano_30b_a3b_config", (2, 8192),
                   {("fwd", 16, 16): 2, ("bwd", 16, 16): 1}),
    "lfm2": ("lfm2_8b_a1b_config", (2, 8192),
             {("fwd", 4, 4): 2, ("bwd", 4, 4): 1}),
    # ungrouped (PR 70): eight head-blocks of the row a forward step, two a
    # backward one
    "mistral4": ("mistral_small_4_config", (1, 16384),
                 {("fwd", 1, 8): 2, ("bwd", 1, 2): 1}),
    "ouro": ("ouro_2_6b_config", (2, 4096),
             {("fwd", 1, 8): 2, ("bwd", 1, 2): 1}),
}


@pytest.mark.parametrize("model", list(SWEEPS))
def test_the_grouped_sweeps_a_cell_s_program_traces(tmp_path, model):
    """No chip and no compile: shapes alone through the cell's own
    configuration, so what the counter says here is what a trace of the
    cell says (``kernels/_common.count_call`` counts when a call is
    traced)."""
    from paddle_tpu import monitor
    from paddle_tpu.parallel import decoder, transformer as T

    config, batch, want = SWEEPS[model]
    module = importlib.import_module("paddle_tpu.models." + model)
    cfg = getattr(module, config)(remat=True)
    params = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    loss = lambda p, i: jnp.sum(decoder.forward(p, i, cfg)[0].astype(
        jnp.float32))
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        jax.eval_shape(jax.grad(loss), params,
                       jax.ShapeDtypeStruct(batch, jnp.int32))
        got = {tuple(r["labels"][n] for n in (
            "part", "group", "heads_in_step")): r["value"]
            for r in mon.registry.snapshot()
            if r["name"] == "monitor.kernels.flash_sweep_calls"}
    finally:
        monitor.disable()
    assert got == want


@pytest.mark.parametrize("what,half,heads,kv_heads,tiles,fwd,bwd", [
    # tier-1's case: one key/value head's group at four tiles a copy
    ("a group of 8 at four tiles a copy", 2048, 8, 1, 24, 8, 8),
    pytest.param("sdar_30b_a3b_chat.s8192_scan", 8192, 32, 4, 288, 8, 8,
                 marks=pytest.mark.slow),
])
def test_the_block_diffusion_rule_s_kernels_compile_for_a_v5e(
        one_chip, what, half, heads, kv_heads, tiles, fwd, bwd):
    """A noised copy over a clean one, ``half`` positions each in blocks of
    4, 512-row tiles, heads of 128 in groups of 8: the rule's table (``nq
    (nq + 1) + nq`` tiles a head: 288 of the square's 1,024 at S = 8,192)
    scalar-prefetched as the other three are, the in-tile mask from the
    tile's quadrant (scalar selects on the table's entries) and the rows'
    and columns' blocks, a group's eight heads looped inside a grid step
    (PR 68), forward and the ONE-sweep fused backward (dk and dv of all 2 S
    rows in VMEM: 48.8 MB asked at 16,384 rows).  Kernels of names of their
    own, no mask operand."""
    S, D = 2 * half, 128
    xq = jax.ShapeDtypeStruct((1, S, heads * D), jnp.bfloat16,
                              sharding=one_chip)
    xk = jax.ShapeDtypeStruct((1, S, kv_heads * D), jnp.bfloat16,
                              sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, heads, block_q=512, block_k=512, interpret=False,
        n_kv_heads=kv_heads, block_diffusion=4)
    text, grids, _ = _compiled(attn, xq, xk, xk, xq)
    nq = half // 512
    assert fa.kv_blocks(S, 512, 512, False, blocks=4) == tiles \
        == nq * (nq + 1) + nq
    g = fa._Geom(xq, xk, heads, 512, 512, kv_heads, blocks=4)
    assert (g.heads_in_step("fwd")[0], g.heads_in_step("bwd")[0],
            g.bwd_sweeps) == (fwd, bwd, 1)
    group = heads // kv_heads
    assert grids == {"flash_bd_fwd": (1, kv_heads, group // fwd, tiles),
                     "flash_bd_bwd_fused": (1, kv_heads,
                                            group // bwd * tiles)}, what
    assert fa.packed_grid(1, S, heads, D, 512, 512, n_kv_heads=kv_heads,
                          blocks=4) == (fwd, kv_heads * group // fwd * tiles)
    for name, part in (("flash_bd_fwd", "fwd"), ("flash_bd_bwd_fused", "bwd")):
        asked, took = _vmem(text, name)
        assert asked == g.heads_in_step(part)[1] and took < asked, what
    assert "flash_fwd" not in grids and "flash_bwd_fused" not in grids
