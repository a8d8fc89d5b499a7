"""The learned-sparse kernels (``kernels/indexer.py``) compiled for a
described v5e at Keye's and dots3's shapes, the masked sweeps a tiny program
traces and dots3's latent layers' text.  Nothing runs; no chip is needed
(``tests/tpu_compile.py``)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (_grids, _script, _vmem, one_chip)  # noqa: F401

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.mark.parametrize("layer", ["full", "sliding"])
def test_dots3_s_latent_layers_hold_no_float32_heads_outside_the_kernels(
        one_chip, layer):
    """dots3-note-prev's two attention shapes at the cell's size, recompute
    + backward, through ``scripts/attn_outside_hlo.py`` (which reads an
    ``AttentionShape`` position through ``cfg.position``, the learned-sparse
    branch where it has an indexer): both row kernels are in the text, and
    outside the matmuls and kernels no float32 array of q's size and no
    ``[.., H, 256]`` view of the keys is left (the parent's ``rope_pairs``
    lines and broadcast add moved 13.4 GB a full layer beside the counting
    select's 8.6 and 7.2 a sliding one by the same count; this tree 4.9 and
    2.6: the head-wise gate's float32 ``[8192, H x 128]``, the latents, the
    hidden state transposed for the down projections' dW)."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("dots3_note_prev.s8192_scan",
                                      tiny=False)
    kind = cfg.layer_kinds[layer == "sliding"]
    assert kind == (hlo.default_kind(cfg) if layer == "full"
                    else cfg.layer_kinds[-1]) and (batch, seq) == (1, 8192)
    heads = cfg.position(kind)[0].heads_here
    assert heads == {"full": 32, "sliding": 16}[layer]
    groups, by_kernel, others = hlo.account(
        hlo.compiled_text(cfg, batch, seq, kind))
    flash = {"full": {"indexer_scores_fwd", "indexer_scores_bwd",
                      "flash_dsa_fwd", "dsa_attend_kl_fwd", "flash_delta",
                      "flash_dsa_bwd_fused"},
             "sliding": {"flash_swa_fwd", "flash_delta",
                         "flash_swa_bwd_fused"}}[layer]
    assert flash | {"qk_rope_fwd", "qk_rope_bwd"} == set(by_kernel)
    # q and k each way by the calls' operands and results (aliased: of
    # each the kernel MOVES a head's second lane block alone, half of it),
    # with the tables and the shared key a HEAD's lanes wide, its gradient
    # the one touched lane block; the full layer's indexer rotates its
    # queries and key by the same kernels
    W = heads * 256
    indexer = 2 * seq * (64 + 1) * 128 * 2 + 4 * seq * 128 * 4 \
        if layer == "full" else 0
    assert by_kernel["qk_rope_fwd"] == by_kernel["qk_rope_bwd"] \
        + seq * 128 * 2 \
        == 4 * seq * W * 2 + seq * 256 * 2 + 4 * seq * 256 * 4 + indexer
    # the activations' (a weight [rank, H, 256] is padded once a layer)
    wide = r"\[(1,)?8192,%d,256\]|\[1024,8,%d,256\]|f32\[(1,)?8192,%d\]" % (
        heads, heads, W)
    assert not [o for o in others if re.search(wide, o[3])], others[:9]
    select = sum(o[0] for o in others if "convert_reduce" in o[1])
    assert groups["other"] - select < {"full": 5.5e9, "sliding": 3e9}[layer]


DSA_CELL = (1, 16384, 32, 4, 128, 16, 64)   # B, S, H, Hkv, D, Hi, Di


@pytest.mark.parametrize("kernel", ["flash_dsa", "indexer_scores",
                                    "dsa_lse", "dsa_attend_kl"])
def test_the_learned_sparse_kernels_compile_for_a_v5e(one_chip, kernel):
    """``keye_vl2_30b_a3b.s16384_scan``'s kernels through Mosaic at the
    cell's shapes, forward and backward: the two masked sweeps (a grid row a
    (batch row, key/value head) pair, a step one of the triangle's 528 tiles
    with the group's eight query heads looped inside; the forward the
    statistic alone, the backward ONE sweep with dk and dv of all 16,384
    positions in VMEM, what it asks stated by ``dsa_bwd_vmem_bytes`` and
    under ``SWEEP_VMEM``), the indexer's scores (the backward's dk of the
    one key head whole in VMEM) and the pass with the statistic known (a
    (tile, key/value head) a grid step, heads innermost; the q block's ``o``
    of all 32 heads one output block and a float32 accumulator a head in
    scratch: the VMEM the call states; its backward the masked sweep and the
    scores' backward on ``G``, the cotangent's scalar its gain in SMEM)."""
    ix = importlib.import_module("paddle_tpu.kernels.indexer")
    B, S, H, Hkv, D, Hi, Di = DSA_CELL

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    q, kv = sds((B, S, H * D)), sds((B, S, Hkv * D))
    scores, tau = sds((B, S, S), f32), sds((B, S), f32)
    steps = fa.kv_blocks(S, 512, 512, True)
    assert steps == 528
    stats = sds((B, H, S, 1), f32)
    if kernel == "flash_dsa":
        # the two sweeps alone, as the layer's calls reach them
        def both(q, k, v, do, lse, delta, scores, tau):
            return (ix._lse_call(q, k, scores, tau, H, Hkv, D ** -0.5, 512,
                                 512, False),) + tuple(ix._dsa_bwd_call(
                q, k, v, do, lse, delta, scores, tau, H, Hkv, D ** -0.5,
                512, 512, False))
        args, names = (q, kv, kv, q, stats, stats, scores,
                       sds((B, S, 1), f32)), {
            "flash_dsa_fwd": (B, Hkv, steps),
            "flash_dsa_bwd_fused": (B, Hkv, steps)}
    elif kernel == "indexer_scores":
        def both(q, k, w, g):
            out, vjp = jax.vjp(lambda *x: ix.indexer_scores(
                *x, interpret=False), q, k, w)
            return (out,) + vjp(g)
        args, names = (sds((B, S, Hi * Di)), sds((B, S, Di)),
                       sds((B, S, Hi), f32), scores), {
            "indexer_scores_fwd": (B, S // 512, S // 512),
            "indexer_scores_bwd": (B, steps)}
    elif kernel == "dsa_lse":
        def both(q, k, scores, tau):
            return ix.dsa_lse(q, k, scores, tau, H, Hkv, interpret=False)
        args, names = (q, kv, scores, tau), {
            "flash_dsa_fwd": (B, Hkv, steps)}
    else:
        def both(q, k, v, qi, ki, w, scores, tau, lse, lse_i, do):
            (o, kl), vjp = jax.vjp(lambda q, k, v, *indexer: ix.dsa_attend_kl(
                q, k, v, indexer, scores, tau, lse, lse_i, H, Hkv,
                interpret=False), q, k, v, qi, ki, w)
            return (o, kl) + vjp((do, jnp.ones_like(kl)))
        args, names = (q, kv, kv, sds((B, S, Hi * Di)), sds((B, S, Di)),
                       sds((B, S, Hi), f32), scores, tau,
                       sds((B, H, S), f32), tau, q), {
            "dsa_attend_kl_fwd": (B, steps, Hkv),
            "flash_dsa_bwd_fused": (B, Hkv, steps),
            "indexer_scores_bwd": (B, steps)}
    traced = jax.jit(both).trace(*args)
    grids = _grids(str(traced.jaxpr))
    assert {n: grids[n] for n in names} == names
    text = traced.lower().compile().as_text()
    for name in names:
        asked, took = _vmem(text, name)
        assert took < (asked or fa.SCOPED_VMEM), (name, asked, took)
    if "flash_dsa_fwd" in names:
        # eight heads' running statistics: past what Mosaic gives unasked
        assert _vmem(text, "flash_dsa_fwd")[0] \
            == ix.dsa_fwd_vmem_bytes(H // Hkv, D, 2) < 32 * 2 ** 20
    if "flash_dsa_bwd_fused" in names:
        asked = ix.dsa_bwd_vmem_bytes(S, H // Hkv, D, D, 2)
        assert ix.heads_a_step(H // Hkv, lambda n: ix.dsa_bwd_vmem_bytes(
            S, n, D, D, 2)) == H // Hkv
        assert _vmem(text, "flash_dsa_bwd_fused")[0] == asked < fa.SWEEP_VMEM
    if kernel == "dsa_attend_kl":
        asked, took = _vmem(text, "dsa_attend_kl_fwd")
        assert asked == ix.attend_kl_vmem_bytes(H, D, 2, H // Hkv) \
            == 36 * 2 ** 20
        assert 24 * 2 ** 20 < took < asked


# tiny model -> the masked sweeps one traced forward + backward counts in
# ``monitor.kernels.flash_dsa_calls``, (part, group, heads in a step,
# statistic only): Keye's sixteen heads on two (a group of eight, all in a
# step), dots3's full layers a head a step; under remat the statistic is
# kept, so the scanned layer's sweep is traced once and its backward once
MASKED_SWEEPS = {
    "keye_vl2": {("fwd", 8, 8, 1): 1, ("bwd", 8, 8, 0): 1},
    "dots3": {("fwd", 1, 1, 1): 2, ("bwd", 1, 1, 0): 2},
}


@pytest.mark.parametrize("model", list(MASKED_SWEEPS))
def test_the_masked_sweeps_a_tiny_program_traces(tmp_path, model):
    """No chip and no compile: a monitor session around one trace of the
    tiny model's differentiated loss (``kernels/_common.count_call``)."""
    from paddle_tpu import monitor
    from paddle_tpu.parallel import decoder, transformer as T

    module = importlib.import_module("paddle_tpu.models." + model)
    cfg = getattr(module, model + "_tiny_config")(remat=True)
    params = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    loss = lambda p, i: jnp.sum(decoder.forward(p, i, cfg)[0].astype(
        jnp.float32))
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()        # the registry is the process's
        jax.eval_shape(jax.grad(loss), params, ids)
        got = {tuple(r["labels"][n] for n in (
            "part", "group", "heads_in_step", "statistic_only")): r["value"]
            for r in mon.registry.snapshot()
            if r["name"] == "monitor.kernels.flash_dsa_calls"}
    finally:
        monitor.disable()
    assert got == MASKED_SWEEPS[model]


DOTS3_FULL = (1, 8192, 32, 256, 128, 64, 128)    # B, S, H, lanes, Dv, Hi, Di
DOTS3_SLIDING = (1, 8192, 16, 256, 128, 513)     # B, S, H, D, Dv, window


@pytest.mark.parametrize("kernel", ["indexer_scores", "dsa_lse",
                                    "dsa_attend_kl", "flash_swa",
                                    "rope_first_columns"])
def test_the_dots3_kernels_compile_for_a_v5e(one_chip, kernel):
    """``dots3_note_prev.s8192_scan``'s kernel modes through Mosaic at the
    cell's shapes, forward and backward: the indexer's scores at 64 heads of
    128 (a q block of 8,192 lanes: both calls state their VMEM, the
    backward's dq accumulator 16 MiB of it); the masked sweeps (the
    statistic alone, which reads no value; a head a step) and the pass with
    the statistic known at 32 heads of 192 in 256 lanes against values of
    128 (its backward the masked sweep, dk at 256 and dv at 128 lanes of all
    8,192 positions in VMEM);
    the windowed mode at 16 heads of 256 against values of 128 under a
    window of 513 (two kv blocks a q block: 31 steps); and the indexer's
    rotation of a head's first 64 columns as ONE pass of the row kernel."""
    ix = importlib.import_module("paddle_tpu.kernels.indexer")
    B, S, H, lanes, Dv, Hi, Di = DOTS3_FULL

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    q, v = sds((B, S, H * lanes)), sds((B, S, H * Dv))
    scores, tau = sds((B, S, S), f32), sds((B, S), f32)
    steps = fa.kv_blocks(S, 512, 512, True)
    assert steps == 136
    shape = dict(scale=192 ** -0.5, v_head_dim=Dv, interpret=False)
    if kernel == "indexer_scores":
        def both(q, k, w, g):
            out, vjp = jax.vjp(lambda *x: ix.indexer_scores(
                *x, interpret=False), q, k, w)
            return (out,) + vjp(g)
        args, names = (sds((B, S, Hi * Di)), sds((B, S, Di)),
                       sds((B, S, Hi), f32), scores), {
            "indexer_scores_fwd": (B, S // 512, S // 512),
            "indexer_scores_bwd": (B, steps)}
    elif kernel == "dsa_lse":
        def both(q, k, scores, tau):
            return ix.dsa_lse(q, k, scores, tau, H, scale=shape["scale"],
                              interpret=False)
        args, names = (q, q, scores, tau), {
            "flash_dsa_fwd": (B, H, steps)}
    elif kernel == "dsa_attend_kl":
        def both(q, k, v, qi, ki, w, scores, tau, lse, lse_i, do):
            (o, kl), vjp = jax.vjp(lambda q, k, v, *indexer: ix.dsa_attend_kl(
                q, k, v, indexer, scores, tau, lse, lse_i, H, **shape),
                q, k, v, qi, ki, w)
            return (o, kl) + vjp((do, jnp.ones_like(kl)))
        # the scores' backward at 4 heads here: its 64 are the case above
        args, names = (q, q, v, sds((B, S, 4 * Di)), sds((B, S, Di)),
                       sds((B, S, 4), f32), scores, tau,
                       sds((B, H, S), f32), tau, v), {
            "dsa_attend_kl_fwd": (B, steps, H),
            "flash_dsa_bwd_fused": (B, H, steps),
            "indexer_scores_bwd": (B, steps)}
    elif kernel == "flash_swa":
        B, S, H, D, Dv, window = DOTS3_SLIDING
        band = fa.kv_blocks(S, 512, 512, True, window)
        assert band == 31

        def both(q, k, v, do):
            o, vjp = jax.vjp(lambda *x: fa.flash_attention_packed(
                *x, H, causal=True, block_q=512, block_k=512, window=window,
                v_head_dim=Dv, interpret=False), q, k, v)
            return (o,) + vjp(do)
        x, y = sds((B, S, H * D)), sds((B, S, H * Dv))
        # eight of the share's sixteen head-blocks a forward step, two a
        # backward one (PR 70; ``tests/test_chip_compile_flash.py``)
        args, names = (x, x, y, y), {
            "flash_swa_fwd": (B, H // 8, 1, band),
            "flash_swa_bwd_fused": (B, H // 2, band)}
    else:
        T = importlib.import_module("paddle_tpu.parallel.transformer")
        rope = importlib.import_module("paddle_tpu.kernels.qk_rope")
        rope._on_tpu, was = (lambda: True), rope._on_tpu
        try:
            def both(x, do):
                out, vjp = jax.vjp(
                    lambda x: T._rope_first_columns(x, Di, 64, 8e7), x)
                return (out,) + vjp(do)
            x = sds((B, S, Hi * Di))
            text = jax.jit(both).trace(x, x).lower().compile().as_text()
        finally:
            rope._on_tpu = was
        assert text.count("tpu_custom_call") == 2
        return
    traced = jax.jit(both).trace(*args)
    grids = _grids(str(traced.jaxpr))
    assert {n: grids[n] for n in names} == names
    text = traced.lower().compile().as_text()
    for name in names:
        asked, took = _vmem(text, name)
        assert took < (asked or fa.SCOPED_VMEM), (name, asked, took)
    if kernel == "indexer_scores":
        asked = {n: _vmem(text, n)[0] for n in names}
        assert asked["indexer_scores_fwd"] == ix.scores_vmem_bytes(
            512, 512, Hi * Di, Hi, 2)
        assert asked["indexer_scores_bwd"] == ix.scores_vmem_bytes(
            512, 512, Hi * Di, Hi, 2, True, S) < 96 * 2 ** 20
    if "flash_dsa_fwd" in names:
        assert ix._past_scoped(ix.dsa_fwd_vmem_bytes(1, lanes, 2)) == {}
        assert _vmem(text, "flash_dsa_fwd")[0] is None
    if "flash_dsa_bwd_fused" in names:
        assert _vmem(text, "flash_dsa_bwd_fused")[0] \
            == ix.dsa_bwd_vmem_bytes(S, 1, lanes, Dv, 2) < fa.SWEEP_VMEM
