"""The flash kernels compiled for a described v5e, at the benchmark cells'
real shapes: what interpret mode cannot see (Mosaic's tiling rules, the
scoped VMEM a grid step may hold).  Nothing runs; no chip is needed.  All
such compiles live in this one file and describe the chip inside a fixture,
so that only the worker that is given the file loads the TPU's library."""

import base64
import functools
import hashlib
import importlib
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("what,entry,B,S,H,D,causal,pairs", [
    ("bert_base.s128_scan", "packed", 256, 128, 12, 64, False, 6),
    ("bert_base.s512_scan", "packed", 64, 512, 12, 64, False, 1),
    ("fine-tuning at 384", "packed", 32, 384, 12, 64, False, 2),
    ("olmoe_1b_7b.s4096_scan", "packed", 4, 4096, 16, 128, True, 1),
    # the same heads and length at the looped stack's batch: 48 layer
    # applications a step call these
    ("ouro_2_6b.s4096_scan", "packed", 2, 4096, 16, 128, True, 1),
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
    if S > 512:     # several blocks: the sweeps' step tables are the grids,
        # and the backward ONE sweep, dk and dv of all 4,096 positions in
        # VMEM (22 MiB asked of Mosaic)
        heads, steps = H * D // 128, fa.kv_blocks(S, 512, 512, causal)
        assert grids == {"flash_fwd": (B, heads, 1, steps),
                         "flash_bwd_fused": (B, heads, steps)} and steps == 36, what
        asked, took = _vmem(text, "flash_bwd_fused")
        assert asked == fa.fused_sweep_vmem_bytes(S, 128, 2) == 22 * 2 ** 20
        assert 6 * 2 ** 20 < took < asked, what
        assert mosaic == SWEEP_MOSAIC[what], what
    else:           # one block: the kernels PR 28 left, to the letter
        assert set(grids) == {"flash_fwd", "flash_bwd_fused"}, what
        assert _vmem(text, "flash_bwd_fused")[0] is None, what
        assert mosaic == ONE_BLOCK_MOSAIC[what], what


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
# backward, as PR 41's parent (c1b744a) lowers them: stacking the heads of a
# 64-wide lane block (LFM2's row, not pinned) left every other cell's module
# as it was.  Replaced like ``ONE_BLOCK_MOSAIC``.  PR 68 took SmallThinker's
# two entries anew ON PURPOSE (a group's seven query heads ride one grid
# step; they were 55126c1bd654 / 170f86a03873 full and 985173cce04a /
# 42134345da2e windowed): the three UNGROUPED entries are c1b744a's still,
# one head-block a step lowers to the text it did.
SWEEP_MOSAIC = {
    "olmoe_1b_7b.s4096_scan": ["05626943d473", "eba4a626459f"],
    "ouro_2_6b.s4096_scan": ["8aec72d32a18", "afa8aab872da"],
    "smallthinker_21b_a3b.s16384_scan, a full layer":
        ["465f38b6b128", "1d46a9c9d9f7"],
    "smallthinker_21b_a3b.s16384_scan, a windowed layer":
        ["e3a26b4fe57d", "987d580b7182"],
    "mistral_small_4_119b.s16384_scan, the latent expanded to 32 heads":
        ["e267681a781d", "ad2a365dd0c8"],
}


def _compiled(attn, *shapes):
    """(the compiled program's text, {kernel name: grid}, the digests of the
    kernels' Mosaic modules in call order) of ``attn``'s forward and
    backward: the flash kernels' own.  The row kernel in front of a
    several-block backward (``flash_delta``, PR 55) is another module's and
    has a test of its own below."""
    def both(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return (o,) + vjp(do)

    traced = jax.jit(both).trace(*shapes)
    grids = {name: tuple(int(n) for n in grid.split(",") if n.strip())
             for grid, name in re.findall(
                 r"grid=\(([\d, ]*)\).*?name=(flash_\w+)", str(traced.jaxpr),
                 re.S)}
    grids.pop("flash_delta", None)
    lowered = traced.lower()
    return (lowered.compile().as_text(), grids,
            _mosaic_digests(lowered.as_text(), skip=("flash_delta",)))


def _mosaic_digests(lowered_text, skip=()):
    """sha1 (12 hex digits) of each ``tpu_custom_call`` body's Mosaic text
    without debug info, in call order, but for the kernels named ``skip``."""
    from jaxlib.mlir import ir

    digests = []
    for body, name in re.findall(
            r'body\\22: \\22([A-Za-z0-9+/=]+).*?kernel_name = "(\w+)"',
            lowered_text):
        if name in skip:
            continue
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            digests.append(hashlib.sha1(module.operation.get_asm(
                enable_debug_info=False).encode()).hexdigest()[:12])
    return digests


def _grids(jaxpr_text):
    """``{kernel name: grid}`` of a traced program's Pallas calls: a call
    prints its grid, its kernel's body, then its name on a line of its own,
    so a name's grid is the last one printed before it (the row kernel in
    front of a flash backward, ``flash_delta``, has both of its own)."""
    grids = [(m.start(), m.group(1)) for m in re.finditer(
        r"grid=\(([\d, ]*)\)", jaxpr_text)]
    out = {}
    for m in re.finditer(r"^\s*name=(\w+)$", jaxpr_text, re.M):
        before = [grid for at, grid in grids if at < m.start()]
        if before:
            out[m.group(1)] = tuple(
                int(n) for n in before[-1].split(",") if n.strip())
    return out


def _vmem(text, kernel):
    """(bytes of VMEM the call of ``kernel`` asks Mosaic for, None where it
    leaves the scope at its default; bytes the compiled kernel took).  XLA
    may keep an array of its own in VMEM beside the call (the forward's
    ``o`` between the kernels that read it, where nothing of XLA's does:
    64 MiB at OLMoE's shape, PR 55): the call's scope then starts past it,
    and what the kernel took is counted from the scope's start."""
    scope = r'\{"memory_space":"1","offset":"(\d+)","size":"(\d+)"\}'
    line, = [l for l in text.splitlines()
             if "tpu_custom_call" in l and re.search(
                 r"%%?[\w.\-]*%s[\w.\-]* = " % kernel, l)]
    (start, asked), = re.findall(
        r'"scoped_memory_configs":\[(?:%s)?\]' % scope, line)
    (first, took), = re.findall(
        r'"used_scoped_memory_configs":\[%s\]' % scope, line)
    took = int(first) + int(took) - max(int(start or 0), int(first))
    return (int(asked) if asked else None), took


SMALLTHINKER, LFM2 = (1, 16384, 28, 4, 128), (2, 8192, 32, 8, 64)
MISTRAL4 = (1, 16384, 32, 32, 128)      # every head its own key and value
TRINITY, NEMOTRON = (1, 6144, 48, 8, 128), (2, 8192, 32, 2, 128)
SOLAR, JAMBA = (1, 4096, 64, 8, 128), (1, 8192, 20, 1, 128)


@pytest.mark.parametrize("what,shape,window,names,steps,heads,mib", [
    ("smallthinker_21b_a3b.s16384_scan, a full layer", SMALLTHINKER, None,
     ("flash_fwd", "flash_bwd_fused"), 528, 7, (23.75, 44.5)),
    ("smallthinker_21b_a3b.s16384_scan, a windowed layer", SMALLTHINKER, 4096,
     ("flash_swa_fwd", "flash_swa_bwd_fused"), 252, 7, (23.75, 44.5)),
    ("lfm2_8b_a1b.s8192_scan, two heads a lane block", LFM2, None,
     ("flash_fwd", "flash_bwd_fused"), 136, 4, (27.5, 39.5)),
    ("mistral_small_4_119b.s16384_scan, the latent expanded to 32 heads",
     MISTRAL4, None, ("flash_fwd", "flash_bwd_fused"), 528, 1, (16, 40)),
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
    ungrouped modules are the parent's (``SWEEP_MOSAIC``) and ask what they
    asked.  The grids are the tables: (row, key/value head-block, chunk of
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
    64); the grids are a one-width call's."""
    B, S, H, D, Dv = 1, 16384, 32, 256, 128
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xv = jax.ShapeDtypeStruct((B, S, H * Dv), jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, scale=192 ** -0.5, block_q=512, block_k=512,
        interpret=False, v_head_dim=Dv)
    assert jax.eval_shape(attn, xq, xq, xv).shape == xv.shape
    text, grids, _ = _compiled(attn, xq, xq, xv, xv)
    steps = fa.kv_blocks(S, 512, 512, True)
    assert grids == {"flash_fwd": (B, H, 1, steps),
                     "flash_bwd_fused": (B, H, steps)}
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


@pytest.mark.parametrize("what,N,V,E,norm", [
    ("smallthinker_21b_a3b.s16384_scan", 16384, 37984, 2560, "rms"),
    ("olmoe_1b_7b.s4096_scan", 16384, 50304, 2048, "rms"),
    # a looped stack's four exits' rows through the head in one call
    ("ouro_2_6b.s4096_scan", 4 * 8192, 49152, 2048, "rms"),
    ("bert_base.s512_scan", 32768, 30528, 768, "layer"),
])
def test_head_matrix_gradient_is_tiled_in_a_few_windows(one_chip, what, N, V,
                                                        E, norm):
    """The tp=1 head's backward at a cell's head shape.  A vocabulary
    chunk's float32 dW matmul, accumulated into ``demb`` in place, is one
    fusion a chunk whose result is ``f32[V, E]``; the compiler walks it in
    ``iteration_bounds`` windows.  With chunks of 9,496 = 8 x 1,187 rows
    (37,984 / 4; 1,187 is prime) it found 1,187 windows of one 8-row tile,
    and the four fusions took a fifth of the SmallThinker cell's step."""
    T = importlib.import_module("paddle_tpu.parallel.transformer")
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    emb = sds((V, E))

    def loss(x, scale, bias, emb, labels, mask):
        return jnp.sum(T._chunked_vocab_nll(x, scale, bias, emb, labels, mask,
                                            norm=(norm, 1e-5)) * mask)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3))).lower(
        sds((N, E)), sds((E,)), sds((E,)) if norm == "layer" else None, emb,
        sds((N,), jnp.int32), sds((N,), jnp.float32)).compile().as_text()
    windows = []
    for line in text.splitlines():
        if (re.match(r"\s*%%?[\w.\-]+ = f32\[%d,%d\]" % (V, E), line)
                and "/while/body/" in line and "window_config" in line):
            config = json.loads(
                line[line.index("backend_config=") + 15:])["window_config"]
            windows.append(math.prod(
                int(b) for b in config["iteration_bounds"]))
    assert len(windows) == len(T._vocab_chunks(emb)), (what, windows)
    assert max(windows) <= 300, (what, windows)


@pytest.mark.parametrize("chunk", [1024, 2048])
def test_power_retention_compiles_for_a_v5e_at_the_cell_s_shapes(one_chip,
                                                                 chunk):
    """``brumby_14b.s16384_scan``: [1, 16384, 40 x 128] queries on 8
    key/value heads, bf16, forward and backward through Mosaic at the
    configured chunk length and at the longest the configuration allows.
    The grid is (batch, key/value head, chunk): a step serves the five query
    heads of a group, stacked along rows, in one sweep of the state's 65
    tiles.  At chunks of 2,048 the stacked step would hold 142 MiB by the
    compiler's count, over ``VMEM_LIMIT``: there the kernels' own rule
    (``sweep_heads``) sweeps the group a head at a time on a fourth grid
    axis, the path Mosaic has to take as well.  The state of 65 x 128 x 128
    float32 and its gradient are VMEM scratch, and nothing tokens x 8,320
    wide is among the program's buffers."""
    pr = importlib.import_module("paddle_tpu.kernels.power_retention")
    S, Hq, Hkv = 16384, 40, 8
    q = jax.ShapeDtypeStruct((1, S, Hq * 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, S, Hkv * 128), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, S, Hkv), jnp.float32, sharding=one_chip)

    def both(q, k, v, g, do):
        o, vjp = jax.vjp(lambda *a: pr.power_retention(
            *a, chunk=chunk, interpret=False), q, k, v, g)
        return (o,) + vjp(do)

    traced = jax.jit(both).trace(q, k, k, g, q)
    grids = {name: tuple(int(n) for n in grid.split(",") if n.strip())
             for grid, name in re.findall(
                 r"grid=\(([\d, ]*)\).*?name=(power_retention_\w+)",
                 str(traced.jaxpr), re.S)}
    parts = {1024: (), 2048: (5,)}[chunk]
    assert pr.sweep_heads(Hq // Hkv, chunk) == (1 if parts else 5)
    assert pr.state_sweeps(Hq, Hkv, S, chunk) == {1024: 128, 2048: 320}[chunk]
    assert grids == {"power_retention_fwd": (1, Hkv, S // chunk) + parts,
                     "power_retention_bwd": (1, Hkv, S // chunk) + parts}
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in grids:
        asked, took = _vmem(text, kernel)
        assert asked == pr.VMEM_LIMIT and took < asked, (kernel, took)
        # the rule that decides how a group is swept counts no less
        assert took <= pr._step_vmem_bytes(
            pr.sweep_heads(Hq // Hkv, chunk), chunk, 2), (kernel, took)
    # the saved chunk states, and no expansion of the tokens
    assert "f32[1,8,%d,65,128,128]" % (S // chunk) in text
    assert not re.search(r"\[(?:\d+,)*16384,(?:\d+,)*(?:8320|8256)", text)


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


@pytest.mark.parametrize("what,shape,heads,groups,chunk,dtype", [
    ("nemotron3_nano_30b_a3b.s8192_scan", (2, 8192, 6144), 64, 8, 128,
     jnp.bfloat16),
    ("heads a lane tile wide, float32", (1, 512, 2048 + 256), 16, 1, 64,
     jnp.float32),
])
def test_the_ssd_scan_compiles_for_a_v5e(one_chip, what, shape, heads, groups,
                                         chunk, dtype):
    """Both kernels of the chunked Mamba-2 scan through Mosaic at the cell's
    shape (64 heads of 64 in 8 groups: two heads a lane tile, a group's 512
    channels a block, B and C a lane block each of the filter's ONE output)
    and at heads a whole lane tile wide, within the VMEM their call asks
    for."""
    ssd = importlib.import_module("paddle_tpu.kernels.ssd_scan")
    b, S, W = shape
    N = 128
    d = W - 2 * groups * N

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    args = (sds(shape, dtype), sds((b, S, heads), jnp.float32),
            sds((heads,), jnp.float32), sds((heads,), jnp.float32))

    def both(*a):
        out, vjp = jax.vjp(lambda *q: ssd.ssd_scan(
            *q, heads=heads, groups=groups, d_state=N, chunk=chunk,
            interpret=False), *a[:-1])
        return (out,) + vjp(a[-1])

    assert ssd.supported(shape, heads, groups, N, chunk)
    text = jax.jit(both).lower(*args, sds((b, S, d), dtype)) \
        .compile().as_text()
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
        asked, took = _vmem(text, kernel)
        assert asked == ssd.vmem_bytes(chunk, d // groups, N,
                                       jnp.dtype(dtype).itemsize)
        assert took < asked < 64 * 2 ** 20, (what, kernel, took, asked)


@pytest.mark.parametrize("what,shape,chunk,dtype,over_one", [
    ("kimi_linear_48b_a3b.s16384_scan", (1, 16384, 32, 128), 64,
     jnp.bfloat16, False),
    ("float32 operands, four chunks a stack, one grid step",
     (2, 512, 4, 128), 32, jnp.float32, False),
    # 64 heads (8,192 lanes), strengths in (0, 2): the solve by doubling
    ("solar_open2_250b.s4096_scan", (1, 4096, 64, 128), 64, jnp.bfloat16,
     True),
])
def test_the_kda_chunk_kernels_compile_for_a_v5e(one_chip, what, shape,
                                                 chunk, dtype, over_one):
    """Both kernels of the chunked delta rule through Mosaic at the cell's
    shape (32 heads of 128, a lane block each of the mixer's [b, S, 4096]
    arrays, 256 chunks of 64 a head in eight-stack grid steps) and in
    float32 at chunks of 32, within the VMEM their call asks for."""
    kda = importlib.import_module("paddle_tpu.kernels.kda_chunk")
    b, S, H, d = shape

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    flat = (b, S, H * d)
    args = (sds(flat, dtype),) * 3 + (sds(flat, jnp.float32),
                                      sds((b, S, H), jnp.float32))

    def both(*a):
        out, vjp = jax.vjp(lambda *q: kda.kda_chunk(
            *q, heads=H, chunk=chunk, interpret=False, over_one=over_one),
            *a[:-1])
        return (out,) + vjp(a[-1])

    assert kda.supported(shape, d, chunk, dtype)
    text = jax.jit(both).lower(*args, sds(flat, dtype)).compile().as_text()
    for kernel in ("kda_chunk_fwd", "kda_chunk_bwd"):
        asked, took = _vmem(text, kernel)
        assert asked == kda.vmem_bytes(
            chunk, H, jnp.dtype(dtype).itemsize,
            kda._step_stacks(S // kda.ROWS))
        assert took < asked < 64 * 2 ** 20, (what, kernel, took, asked)


QK_ROPE_CELLS = {   # batch, positions, query heads, kv heads, head width, norm
    "trinity_large_preview.s6144_scan": (1, 6144, 48, 8, 128, "head"),
    "olmoe_1b_7b.s4096_scan": (4, 4096, 16, 16, 128, "whole"),
    "lfm2_8b_a1b.s8192_scan": (2, 8192, 32, 8, 64, "head"),
    "smallthinker_21b_a3b.s16384_scan": (1, 16384, 28, 4, 128, None),
    # one of ``_by_row_blocks``' eight blocks of rows, its first traced
    "brumby_14b.s16384_scan": (1, 2048, 40, 8, 128, "head"),
}


@pytest.mark.parametrize("what", QK_ROPE_CELLS)
def test_the_qk_rope_kernel_compiles_for_a_v5e(one_chip, what):
    """``kernels/qk_rope.py`` forward and backward on q and on k at the five
    decoders' shapes, bf16, rotary with a traced first position, and
    Trinity's full layer's norm alone: two lane-aligned loads a head, a lane
    rotation, the lane reduces and the backward's eight-sublane partial sums
    are what Mosaic has to take.  A call asks for what its own estimate says
    (``vmem_bytes``) and the compiled kernel takes less."""
    qr = importlib.import_module("paddle_tpu.kernels.qk_rope")
    b, S, heads, kv_heads, dh, norm = QK_ROPE_CELLS[what]
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    cases = [(heads, True), (kv_heads, True)]
    if what.startswith("trinity"):
        cases.append((heads, False))
    for n, rotary in cases:
        W = n * dh
        x = jax.ShapeDtypeStruct((b, S, W), jnp.bfloat16, sharding=one_chip)
        w = norm and jax.ShapeDtypeStruct(
            (dh if norm == "head" else W,), jnp.float32, sharding=one_chip)

        def both(x, w, first, g):
            out, vjp = jax.vjp(lambda x, w: qr.qk_rope(
                x, w, qr.angle_tables(S, dh, 1e4, first) if rotary else None,
                head_dim=dh, norm=norm, eps=1e-5, interpret=False), x, w)
            return (out,) + vjp(g)

        text = jax.jit(both).lower(x, w, first, x).compile().as_text()
        rows = qr.block_rows(S, W, 2)
        assert rows in (128, 256) and qr.supported(x.shape, dh, 2), what
        for kernel in ("qk_rope_fwd", "qk_rope_bwd"):
            asked, took = _vmem(text, kernel)
            assert asked == qr.vmem_bytes(rows, W, 2) < 20 * 2 ** 20, what
            assert took < asked, (what, n, kernel, took, asked)


# The rotate-half row kernels' Mosaic modules, forward and backward, as PR
# 57's parent (7080338) lowers them (sha1 of each ``tpu_custom_call`` body's
# text without debug info, as ``_compiled`` takes it): the ``pairs``
# convention and the shared lane block are static arguments of the same
# kernel bodies, and the six rotary decoders' calls must not see them.
# name: (batch, positions, heads, head width, norm, rotary), digests
QK_ROPE_MOSAIC = {
    "trinity q": ((1, 6144, 48, 128, "head", True),
                  ["5ba19f1fc58f", "77886e8c8b5e"]),
    "olmoe q": ((4, 4096, 16, 128, "whole", True),
                ["f95e8cf9ebc5", "97a4b9729c57"]),
    "lfm2 k": ((2, 8192, 8, 64, "head", True),
               ["469429c99fd7", "58516d195468"]),
    "smallthinker q": ((1, 16384, 28, 128, None, True),
                       ["3f476868dd0c", "1b36c676b621"]),
    "trinity q, a full layer": ((1, 6144, 48, 128, "head", False),
                                ["cc75a56a54db", "9b0e63e2c42d"]),
}


@pytest.mark.parametrize("what", QK_ROPE_MOSAIC)
def test_the_rotate_half_row_kernels_lower_to_the_parent_s_mosaic(one_chip,
                                                                  what):
    qr = importlib.import_module("paddle_tpu.kernels.qk_rope")
    (b, S, heads, dh, norm, rotary), want = QK_ROPE_MOSAIC[what]
    W = heads * dh
    x = jax.ShapeDtypeStruct((b, S, W), jnp.bfloat16, sharding=one_chip)
    w = norm and jax.ShapeDtypeStruct(
        (dh if norm == "head" else W,), jnp.float32, sharding=one_chip)
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def both(x, w, first, g):
        out, vjp = jax.vjp(lambda x, w: qr.qk_rope(
            x, w, qr.angle_tables(S, dh, 1e4, first) if rotary else None,
            head_dim=dh, norm=norm, eps=1e-5, interpret=False), x, w)
        return (out,) + vjp(g)

    got = _mosaic_digests(jax.jit(both).lower(x, w, first, x).as_text())
    assert got == want, what


@pytest.mark.parametrize("S,traced", [(16384, False), (1024, True)])
def test_the_latent_q_and_k_passes_compile_for_a_v5e(one_chip, S, traced):
    """``kernels/qk_rope.py``'s ``pairs`` convention at Mistral-Small-4's
    shape ``[1, 16384, 32 x 128]`` (the cell's: the whole sequence from
    position 0) and at a row block of 1,024 positions with a traced first
    position, bf16: q rotated and scaled through its tables, k the padded
    heads plus the shared lane block (its gradient the backward's second
    result).  Two lane rotations by one and a select by lane parity are what
    Mosaic has to take; a call asks for ``vmem_bytes`` and takes less."""
    qr = importlib.import_module("paddle_tpu.kernels.qk_rope")
    b, W, dr = 1, 4096, 64
    freqs = [1e4 ** (-2 * j / dr) for j in range(dr // 2)]
    x = jax.ShapeDtypeStruct((b, S, W), jnp.bfloat16, sharding=one_chip)
    kr = jax.ShapeDtypeStruct((b, S, 128), jnp.bfloat16, sharding=one_chip)
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((S,), jnp.float32, sharding=one_chip)
    rows = qr.block_rows(S, W, 2)
    assert rows == 256 and qr.supported(x.shape, 128, 2)

    def q_pass(x, scale, first, g):
        out, vjp = jax.vjp(lambda x: qr.qk_rope(
            x, None, qr.pair_tables(S, freqs, 128, first if traced else 0,
                                    1.0, scale),
            head_dim=128, pairs=True, interpret=False), x)
        return (out,) + vjp(g)

    def k_pass(x, kr, first, g):
        out, vjp = jax.vjp(lambda x, kr: qr.qk_rope(
            x, None, qr.pair_tables(S, freqs, 128, first if traced else 0),
            head_dim=128, pairs=True, shared=kr, interpret=False), x, kr)
        return (out,) + vjp(g)

    for fn, args, shared in ((q_pass, (x, scale, first, x), False),
                             (k_pass, (x, kr, first, x), True)):
        text = jax.jit(fn).lower(*args).compile().as_text()
        for kernel in ("qk_rope_fwd", "qk_rope_bwd"):
            asked, took = _vmem(text, kernel)
            assert asked == qr.vmem_bytes(rows, W, 2, shared) < 20 * 2 ** 20
            assert took < asked, (kernel, shared, took, asked)


# cell: positions, held heads, (plain, rotated, tail) lanes of a head of 256,
# rotary
WIDE_HEAD_CELLS = {
    "dots3_note_prev.s8192_scan, a full layer":
        (8192, 32, (128, 64, 64), True),
    "dots3_note_prev.s8192_scan, a sliding layer":
        (8192, 16, (192, 64, 0), True),
    "kimi_linear_48b_a3b.s16384_scan, the latent layer's k":
        (16384, 32, (128, 64, 64), False),
    # no cell's: a head whose rotated lanes lie across both lane blocks, so
    # that both are visited (the same kernels, the grid's third axis 2 long,
    # the shared key's gradient two lane blocks wide)
    "a head of 96 + 64 in 256 lanes": (8192, 16, (96, 64, 96), True),
}


@pytest.mark.parametrize("what", WIDE_HEAD_CELLS)
def test_the_row_kernel_at_a_head_of_two_lane_blocks_compiles_for_a_v5e(
        one_chip, what):
    """``kernels/qk_rope.py`` at a head of TWO lane blocks, bf16, forward and
    backward, at dots3's two shapes (q rotated; k the padded heads plus the
    shared key, rotated) and at Kimi-Linear's k (the shared key added, no
    tables): a head's first lane block, which holds nothing rotated and
    nothing shared, never moved (x aliased to the result, ONE lane block of
    1,024 rows a grid step, the shared key's gradient summed over the heads
    in a scratch block).  A call asks for ``touched_vmem_bytes`` and takes
    less."""
    qr = importlib.import_module("paddle_tpu.kernels.qk_rope")
    S, heads, (plain, dr, tail), rotary = WIDE_HEAD_CELLS[what]
    lanes = plain + dr + tail
    W = heads * lanes
    freqs = [1e4 ** (-2 * j / dr) for j in range(dr // 2)]
    x = jax.ShapeDtypeStruct((1, S, W), jnp.bfloat16, sharding=one_chip)
    ks = jax.ShapeDtypeStruct((1, S, lanes), jnp.bfloat16, sharding=one_chip)
    assert qr.supported(x.shape, lanes, 2) and qr.touched_rows(S, 2) == 1024
    rotate = functools.partial(qr.qk_rope, head_dim=lanes, pairs=True,
                               plain_blocks=plain // 128, interpret=False)
    tables = lambda: qr.pair_tables(S, freqs, lanes, tail=tail) \
        if rotary else None

    def q_pass(x, g):
        out, vjp = jax.vjp(lambda x: rotate(x, None, tables()), x)
        return (out,) + vjp(g)

    def k_pass(x, ks, g):
        out, vjp = jax.vjp(lambda x, ks: rotate(x, None, tables(),
                                                shared=ks), x, ks)
        return (out,) + vjp(g)

    for fn, args, shared in ((q_pass, (x, x), False), (k_pass, (x, ks, x),
                                                       True)):
        if not (rotary or shared):
            continue        # a q without positions makes no call
        text = jax.jit(fn).lower(*args).compile().as_text()
        for kernel in ("qk_rope_fwd", "qk_rope_bwd"):
            asked, took = _vmem(text, kernel)
            assert asked == qr.touched_vmem_bytes(1024, 2) == 9 * 2 ** 20
            # a lane block a step takes 1.8 to 4.6 MiB
            assert took < 5 * 2 ** 20, (what, kernel, shared, took, asked)


def test_the_latent_layer_s_text_cuts_no_activation_across_lanes(one_chip):
    """Mistral-Small-4's latent layer, recompute + backward, through
    ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 57 was sized
    by): both row kernels are in the text, and outside the matmuls and
    kernels no 63- or 1-lane float32 slice of the rolls, no head 192 lanes
    wide to cut k_nope and v from, no float32 array of q's size is left, in
    sixteen row blocks or whole.  The parent moved 21.0 GB there by the same
    count (7.9 of them the rotation's, the scale's and the assembly's
    fusions; a row-block loop's slices counted by the block), this tree 2.0:
    the hidden state transposed for the two down projections' dW, and the
    latents."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("mistral_small_4_119b.s16384_scan",
                                      tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, kind) == (1, 16384, (None, True)) and cfg.latent
    groups, by_kernel, others = hlo.account(
        hlo.compiled_text(cfg, batch, seq, kind))
    assert {"qk_rope_fwd", "qk_rope_bwd", "flash_fwd", "flash_delta",
            "flash_bwd_fused"} == set(by_kernel)
    # q and k each way: read and written once, with the tables and the
    # shared lane block (its gradient in the backward)
    assert by_kernel["qk_rope_fwd"] == by_kernel["qk_rope_bwd"] \
        == 4 * seq * 4096 * 2 + seq * 128 * 2 + 4 * seq * 128 * 4
    cut = [o for o in others if re.search(
        r"f32\[1,\d+,32,(63|1|64|128)\]|bf16\[1,\d+,32,(192|64)\]", o[3])]
    assert not cut, cut[:9]
    assert not [o for o in others if o[0] > 140e6 and o[3].startswith("f32")]
    assert groups["other"] < 3e9 and groups["matmul"] > 3e9


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


def test_a_rotary_layer_s_text_holds_no_float32_heads_outside_the_kernels(
        one_chip):
    """Trinity's windowed rotary layer, recompute + backward, through
    ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 47 was sized
    by): no float32 array of the q projection's size is left in HBM by the
    norm or the rotation (the parent broadcast ``cos`` and ``sin`` to
    ``f32[6144,48,128]`` and moved 9.3 GB outside its matmuls and kernels;
    what is left is the gate's, the flash backward's ``delta`` and the
    copies around the matmuls), and both row kernels are in the text."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    hlo = importlib.import_module("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("trinity_large_preview.s6144_scan",
                                      tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, kind) == (1, 6144, (4096, True))
    text = hlo.compiled_text(cfg, batch, seq, kind)
    kernels = importlib.import_module("paddle_tpu.kernels._common")
    assert kernels.on_tpu() is False            # the probes are put back
    groups, by_kernel, others = hlo.account(text)
    assert {"qk_rope_fwd", "qk_rope_bwd", "flash_swa_fwd",
            "flash_swa_bwd_fused"} <= set(by_kernel)
    # the entry computation's own instructions, fusions' insides left out
    assert not [o for o in others if o[0] > 140e6 and o[3].startswith("f32")
                and (o[2] == "broadcast" or "6144,48,64" in o[3])], others[:9]
    assert groups["other"] < 3.5e9 and groups["matmul"] > 1.8e9


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


# --- the selective scan at its door (PR 49) ----------------------------------

def _script(name):
    """A module of ``scripts/``."""
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    return importlib.import_module(name)


def _moved(text, at_door):
    """Of a door's instructions ({array: names}, ``jamba_kernels_receipt.
    door``) those that move the array; XLA's own prefetch of a small
    operand (``copy-start``) keeps its tiles."""
    comps, entry = _script("attn_outside_hlo").computations(text)
    ops = {name: op for name, _, op, _, _ in comps[entry]}
    return {array: [n for n in names if ops[n] in (
        "copy", "reshape", "transpose", "fusion", "slice")]
        for array, names in at_door.items()}


@pytest.mark.parametrize("what,shape,chunk,dtype", [
    ("jamba2_3b.s8192_scan", (1, 8192, 5120), 128, jnp.bfloat16),
    ("a token group that is a whole chunk", (1, 64, 1024), 8, jnp.bfloat16),
    ("a bfloat16 tile split by a chunk edge", (1, 48, 1024), 24, jnp.bfloat16),
    ("float32 x and z, two groups of rows", (1, 256, 2048), 128, jnp.float32),
])
def test_the_selective_scan_compiles_for_a_v5e(one_chip, what, shape, chunk,
                                               dtype):
    """Both kernels through Mosaic at the cell's shape and at the shapes the
    door's addressing adds (a dynamic strided sublane index; ``[chunk, d]``
    blocks of a 16-row-tiled array at chunks of 8 and 24), within the VMEM
    their call asks for; and the compiled program re-tiles NOTHING: no
    instruction but the kernels touches a per-token array."""
    ss = importlib.import_module("paddle_tpu.kernels.selective_scan")
    b, S, d = shape
    N = 16

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    args = (sds(shape, dtype), sds(shape, jnp.float32),
            sds((b, S, N), jnp.float32), sds((b, S, N), jnp.float32),
            sds(shape, dtype), sds((d, N), jnp.float32),
            sds((d,), jnp.float32))

    def both(*a):
        out, vjp = jax.vjp(lambda *q: ss.selective_scan(
            *q, chunk=chunk, interpret=False), *a[:-1])
        return (out,) + vjp(a[-1])

    assert ss.supported(shape, N, chunk)
    text = jax.jit(both).lower(*args, sds(shape, dtype)).compile().as_text()
    for kernel in ("selective_scan_fwd", "selective_scan_bwd"):
        asked, took = _vmem(text, kernel)
        assert asked == ss.vmem_bytes(chunk, d, N, jnp.dtype(dtype).itemsize)
        assert took < asked < 128 * 2 ** 20, (what, kernel, took, asked)
    # what the receipt times as the door (scripts/jamba_kernels_receipt.py)
    moved = _moved(text, _script("jamba_kernels_receipt").door(text))
    assert set(moved) == {"x", "dt", "z", "out", "dout", "dx", "ddt", "dz"}
    assert not any(moved.values()), (what, moved)


def test_the_selective_scan_reads_z_in_the_packed_projection(one_chip):
    """The cell's call with z the second half of ``in_proj``'s ``[1, 8192,
    10240]`` (``z_at=1``): the same kernels within the same VMEM, the packed
    array their operand as it is (no slice of it, no copy), and z's
    gradient padded back to the packed width by XLA."""
    ss = importlib.import_module("paddle_tpu.kernels.selective_scan")
    shape, N, chunk = (1, 8192, 5120), 16, 128

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    args = (sds(shape, jnp.bfloat16), sds(shape, jnp.float32),
            sds((1, 8192, N), jnp.float32), sds((1, 8192, N), jnp.float32),
            sds((1, 8192, 10240), jnp.bfloat16), sds((5120, N), jnp.float32),
            sds((5120,), jnp.float32))

    def both(*a):
        out, vjp = jax.vjp(lambda *q: ss.selective_scan(
            *q, chunk=chunk, interpret=False, z_at=1), *a[:-1])
        return (out,) + vjp(a[-1])

    text = jax.jit(both).lower(*args, sds(shape, jnp.bfloat16)) \
        .compile().as_text()
    for kernel in ("selective_scan_fwd", "selective_scan_bwd"):
        asked, took = _vmem(text, kernel)
        assert took < asked == ss.vmem_bytes(chunk, 5120, N, 2)
    at_door = _script("jamba_kernels_receipt").door(text)
    assert at_door["z"] == [] and at_door["x"] == []
    comps, entry = _script("attn_outside_hlo").computations(text)
    dz, = [types for name, types, _, _, _ in comps[entry]
           if name in at_door["dz"] and "10240" in types]
    assert dz.startswith("bf16[1,8192,10240]")


MAMBA_KERNELS = {"selective_scan_fwd", "selective_scan_bwd",
                 "mamba_filter_fwd", "mamba_filter_bwd"}


def test_a_mamba_layer_s_text_holds_no_float32_copy_at_the_scan_s_door(
        one_chip):
    """The mixer's recompute + backward at d = 1,024 channels, S = 256,
    bfloat16, through ``scripts/attn_outside_hlo.py``: the step sizes reach
    the kernels, and their gradient the ``dt_proj`` matmuls, with no ``copy``
    (and no ``reshape`` or ``transpose`` that moves) of a float32 per-token
    array in the entry computation: XLA takes the door's view as a
    bitcast."""
    hlo = _script("attn_outside_hlo")
    jamba = importlib.import_module("paddle_tpu.models.jamba")
    T = importlib.import_module("paddle_tpu.parallel.transformer")
    cfg = jamba.jamba_tiny_config(d_inner=1024, dtype="bfloat16",
                                  scan_chunk=128, max_seq=256)
    text = hlo.compiled_text(cfg, 1, 256, T.MAMBA)
    groups, by_kernel, others = hlo.account(text)
    assert set(by_kernel) == MAMBA_KERNELS
    elements = 256 * 1024
    relayouts = [o for o in others if o[2] in ("copy", "reshape", "transpose")
                 and o[3].startswith("f32") and o[0] >= 2 * 4 * elements]
    assert not relayouts, relayouts
    # the softplus writes the kernels' view itself: one float32 pass
    assert re.search(r"= f32\[1,32,64,128\]\S* fusion\(", text)


# --- the filter in front of the scan (PR 51) ---------------------------------

@pytest.mark.parametrize("what,shape,width,before,dtype", [
    ("jamba2_3b.s8192_scan, the packed projection", (1, 8192, 10240), 5120,
     False, jnp.bfloat16),
    ("a block of positions past the first", (1, 2048, 10240), 5120, True,
     jnp.bfloat16),
    ("float32 alone, one block of 8 rows", (2, 8, 128), 128, True,
     jnp.float32),
    ("float32, three lane blocks of 128", (1, 1024, 768), 384, False,
     jnp.float32),
])
def test_the_mamba_filter_compiles_for_a_v5e(one_chip, what, shape, width,
                                             before, dtype):
    """Both kernels through Mosaic at the cell's shape (the x half of the
    packed projection, blocks of 2,048 x 512 walked 32 rows a turn: a sublane
    rotation of a 40-row window, a 16-row bf16 tile before and after each
    block) and at the other shapes ``supported`` takes, within the VMEM
    their call asks for; the projection reaches both kernels as it is."""
    mf = importlib.import_module("paddle_tpu.kernels.mamba_filter")
    b, S, W = shape
    taps, itemsize = 4, jnp.dtype(dtype).itemsize

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    args = (sds(shape, dtype), sds((taps, width), jnp.float32),
            sds((width,), jnp.float32),
            sds((b, taps - 1, width), jnp.float32) if before else None)

    def both(x, conv_w, conv_b, rows, g):
        out, vjp = jax.vjp(lambda *q: mf.mamba_filter(
            *q, width=width, interpret=False), x, conv_w, conv_b, rows)
        return (out,) + vjp(g)

    assert mf.supported((b, S, width), taps, itemsize)
    text = jax.jit(both).lower(*args, sds((b, S, width), dtype)) \
        .compile().as_text()
    bs, lb = mf.block_rows(S, itemsize), mf.block_lanes(width)
    for kernel in ("mamba_filter_fwd", "mamba_filter_bwd"):
        # the scope starts behind what XLA itself keeps in VMEM (the taps)
        asked, took = _vmem(text, kernel)
        assert asked == mf.vmem_bytes(bs, lb, itemsize)
        assert took < asked <= 32 * 2 ** 20, (what, kernel, took, asked)
    receipt = _script("jamba_kernels_receipt")
    moved = _moved(text, receipt.door(text, receipt.FILTER_KERNELS,
                                      receipt.FILTER_DOOR))
    assert moved == {"xz": [], "xz_again": []}, (what, moved)


def test_a_mamba_layer_s_filter_reads_the_projection_in_place(one_chip):
    """The cell's Mamba layer, recompute + backward, through
    ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 51 was sized
    by): its kernels are exactly the scan's and the filter's; ``in_proj``'s
    matmul hands its packed result to ``mamba_filter_fwd`` itself; and no
    float32 array of x's size is left in HBM between them or anywhere
    else in the entry computation (the parent wrote x in float32 for the
    shifts and the float32 pre-activation for the backward: 3.2 GB outside
    the matmuls and kernels where 1.7 are left)."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("jamba2_3b.s8192_scan", tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, kind, cfg.d_inner) == (1, 8192, "mamba", 5120)
    text = hlo.compiled_text(cfg, batch, seq, kind)
    groups, by_kernel, others = hlo.account(text)
    assert set(by_kernel) == MAMBA_KERNELS
    comps, entry = hlo.computations(text)
    by = {name: (types, op, operands, attrs)
          for name, types, op, operands, attrs in comps[entry]}
    assert not [n for n, (types, op, _, _) in by.items()
                if "f32[1,8192,5120]" in types and op != "custom-call"]
    call, = [n for n, (_, op, _, _) in by.items()
             if op == "custom-call" and "mamba_filter_fwd" in n]
    types, op, _, attrs = by[by[call][2][0]]
    assert types.startswith("bf16[1,8192,10240]") and op == "fusion"
    called = re.search(r"calls=%?([\w.\-]+)", attrs).group(1)
    assert any(o in ("convolution", "dot") for _, _, o, _, _ in comps[called])
    assert groups["other"] < 2.4e9 and groups["matmul"] > 2.0e9


# --- the gate and the group norm behind the SSD scan (PR 53) -----------------

@pytest.mark.parametrize("what,shape,groups,packed,dtype", [
    ("nemotron3_nano_30b_a3b.s8192_scan, z at lane 6,144 of the projection",
     (2, 8192, 4096), 8, 10240, jnp.bfloat16),
    ("the tiny configuration, float32", (2, 64, 256), 2, 768, jnp.float32),
    ("one group of 1,024 lanes, the gate alone", (1, 1024, 1024), 1, 1024,
     jnp.bfloat16),
    ("float32, one block of 40 rows", (1, 40, 128), 1, 128, jnp.float32),
])
def test_the_gated_norm_compiles_for_a_v5e(one_chip, what, shape, groups,
                                           packed, dtype):
    """Both kernels through Mosaic at the cell's shape (a group's 512
    channels a lane block, 1,024 rows a grid step walked 128 a turn, z read
    at lane block 12 of the packed projection) and at the other shapes
    ``supported`` takes; a call asks for the module's own count
    (``vmem_bytes``) and the compiled kernel takes less; the projection
    reaches both kernels as it is, and z's gradient leaves padded to the
    packed width by XLA."""
    gn = importlib.import_module("paddle_tpu.kernels.gated_norm")
    b, S, d = shape
    itemsize = jnp.dtype(dtype).itemsize

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    def both(y, z, scale, g):
        out, vjp = jax.vjp(lambda *q: gn.gated_norm(
            *q, groups=groups, eps=1e-5, interpret=False), y, z, scale)
        return (out,) + vjp(g)

    assert gn.supported(shape, groups, packed, itemsize)
    text = jax.jit(both).lower(
        sds(shape, dtype), sds((b, S, packed), dtype),
        sds((d,), jnp.float32), sds(shape, dtype)).compile().as_text()
    bs = gn.block_rows(S, d // groups, itemsize)
    for kernel in ("gated_norm_fwd", "gated_norm_bwd"):
        asked, took = _vmem(text, kernel)
        assert asked == gn.vmem_bytes(bs, d // groups, itemsize)
        assert took < asked < 16 * 2 ** 20, (what, kernel, took, asked)
    comps, entry = _script("attn_outside_hlo").computations(text)
    by = {name: (types, op, operands)
          for name, types, op, operands, _ in comps[entry]}
    for name, (types, op, operands) in by.items():
        if op == "custom-call" and "gated_norm" in name:
            # z: the argument itself (or XLA's own prefetch of a small one)
            types, op, _ = by[operands[1]]
            assert op in ("parameter", "copy-done") and types.startswith(
                "%s[%d,%d,%d]" % ("bf16" if itemsize == 2 else "f32", b, S,
                                  packed)), (what, name, types, op)


def test_a_mamba2_layer_s_text_holds_no_float32_pass_behind_the_scan(
        one_chip):
    """The cell's Mamba-2 layer, recompute + backward, through
    ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 53 was sized
    by): its kernels are the filter's, the scan's and the norm's; no float32
    array of y's size ([2, 8192, 4096], or its [2048, 8, 8, 512] tiles, or
    [2, 8192, 8, 512]) is left in HBM by a ``copy``, ``reshape``,
    ``broadcast`` or fusion of the entry computation (the parent wrote five
    such and moved 5.5 GB outside its matmuls and kernels where 1.0 is
    left), and z's gradient reaches ``w_in``'s backward matmuls beside the
    filter's as pads inside their fusions: no array of the packed width but
    the projection itself."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("nemotron3_nano_30b_a3b.s8192_scan",
                                      tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, kind, cfg.d_inner, cfg.ssm_groups) \
        == (2, 8192, "mamba2", 4096, 8)
    text = hlo.compiled_text(cfg, batch, seq, kind)
    groups, by_kernel, others = hlo.account(text)
    assert set(by_kernel) == {
        "mamba_filter_fwd", "mamba_filter_bwd", "ssd_scan_fwd",
        "ssd_scan_bwd", "gated_norm_fwd", "gated_norm_bwd"}
    elements = 2 * 8192 * 4096
    assert not [o for o in others if o[3].lstrip("(").startswith("f32")
                and o[0] >= 4 * elements], others[:9]
    comps, entry = hlo.computations(text)
    wide = [(name, op) for name, types, op, _, _ in comps[entry]
            if "[2,8192,10240]" in types and op != "parameter"]
    assert len(wide) == 1 and wide[0][1] == "fusion", wide   # h @ w_in
    assert not [name for name, _, op, _, _ in comps[entry]
                if op == "concatenate"]
    assert groups["other"] < 1.2e9 and groups["matmul"] > 2.0e9


# --- the flash backward's delta in one pass (PR 55) --------------------------

FLASH_DELTA_CELLS = {   # batch, positions, query heads, head width
    "smallthinker_21b_a3b.s16384_scan": (1, 16384, 28, 128),
    "trinity_large_preview.s6144_scan": (1, 6144, 48, 128),
    "mistral_small_4_119b.s16384_scan": (1, 16384, 32, 128),
    "nemotron3_nano_30b_a3b.s8192_scan": (2, 8192, 32, 128),
    "olmoe_1b_7b.s4096_scan": (4, 4096, 16, 128),
    "ouro_2_6b.s4096_scan": (2, 4096, 16, 128),
    "lfm2_8b_a1b.s8192_scan": (2, 8192, 32, 64),
    "jamba2_3b.s8192_scan": (1, 8192, 20, 128),
}


@pytest.mark.parametrize("what", FLASH_DELTA_CELLS)
def test_the_flash_delta_kernel_compiles_for_a_v5e(one_chip, what):
    """``kernels/flash_delta.py`` at the eight decoder cells' ``o`` and
    ``do``, bf16: a dynamic lane-block slice of both, a lane reduce (two
    masked ones at LFM2's two heads a lane block) and a store of a [rows,
    heads a block] column into a dynamically indexed plane of the
    statistic's block are what Mosaic has to take.  A call asks for what
    its own estimate says (``vmem_bytes``) and the compiled kernel takes no
    more; the result is the backward kernels' array as it lies (the default
    tiled layout a Pallas operand has: 128 lanes a row of numbers)."""
    fd = importlib.import_module("paddle_tpu.kernels.flash_delta")
    B, S, H, D = FLASH_DELTA_CELLS[what]
    x = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    assert fd.supported(x.shape, D, 2), what
    text = jax.jit(lambda o, do: fd.flash_delta(
        o, do, head_dim=D, interpret=False)).lower(x, x).compile().as_text()
    rows = fd.block_rows(S, H * D, 2)
    assert rows == (256 if H * D > 2560 else 512), what
    asked, took = _vmem(text, "flash_delta")
    assert asked == fd.vmem_bytes(rows, H * D, 2) <= 32 * 2 ** 20, what
    # (a small result, Ouro's 64 MiB and Jamba's 80, XLA keeps in VMEM
    # itself in this standalone program: below the scope's start, ``_vmem``)
    assert took <= asked, (what, took, asked)
    assert "f32[%d,%d,%d,%d]{3,2,1,0:T(8,128)" % (
        B, H * D // 128, S, 128 // D) in text, what


@pytest.mark.parametrize("cell,shape,parent_other,chain", [
    # the chain at PR 54 (ISSUE 55's table; "other" by this PR's script on
    # the parent's tree): copy.16 + reduce + copy.17
    ("smallthinker_21b_a3b.s16384_scan", (1, 16384, 28), 2.1415e9, 0.71e9),
    # copy.35 + reduce + copy.28; a third of fusion.2 rode a matmul
    ("trinity_large_preview.s6144_scan", (1, 6144, 48), 2.4386e9, 0.456e9),
])
def test_a_flash_layer_s_text_holds_no_float32_product_of_o_and_do(
        one_chip, cell, shape, parent_other, chain):
    """SmallThinker's and Trinity's windowed layer, recompute + backward,
    through ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 55
    was sized by): ``flash_delta`` stands between the forward's ``o``, the
    cotangent ``wo``'s dX matmul hands on, and the backward kernel; no
    float32 array of tokens x H x D elements is an instruction's result
    anywhere in the entry computation, a matmul fusion's second output
    included (the parent's f32[1,16384,3584] product rode
    ``convert_multiply_fusion``, was copied into another tiling, reduced,
    and the result copied again); and "other" is lower than the parent's by
    at least the chain's bytes."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config(cell, tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, cfg.n_heads, cfg.head_dim, kind) == shape + (
        128, (4096, True))
    text = hlo.compiled_text(cfg, batch, seq, kind)
    groups, by_kernel, others = hlo.account(text)
    assert {"flash_swa_fwd", "flash_delta", "flash_swa_bwd_fused"} \
        <= set(by_kernel)
    comps, entry = hlo.computations(text)
    elements = batch * seq * cfg.n_heads * cfg.head_dim
    wide = [(name, op, types) for name, types, op, _, _ in comps[entry]
            for dims in re.findall(r"\bf32\[([\d,]+)\]", types)
            if math.prod(int(d) for d in dims.split(",")) >= elements]
    assert not wide, wide
    by = {name: (op, operands) for name, _, op, operands, _ in comps[entry]}
    delta, = [n for n in by if "flash_delta" in n and by[n][0] == "custom-call"]
    # the statistic goes to the backward kernel as it is
    bwd, = [n for n in by if "flash_swa_bwd_fused" in n
            and by[n][0] == "custom-call"]
    assert delta in by[bwd][1], by[bwd][1]
    assert groups["other"] <= parent_other - chain, groups
    # XLA's own estimate rides beside the bytes
    assert all(len(o) == 5 for o in others)
    assert 0 < sum(o[4] for o in others) < 1.5e6, groups


# --- the KDA mixer's passes around the delta rule (PR 60) --------------------

@pytest.mark.parametrize("what,shape,dtype", [
    ("kimi_linear_48b_a3b.s16384_scan", (1, 16384, 4096), jnp.bfloat16),
    ("float32, two heads, one block of 40 rows", (2, 40, 256), jnp.float32),
    ("solar_open2_250b.s4096_scan, 64 heads", (1, 4096, 8192), jnp.bfloat16),
])
def test_the_kda_row_kernels_compile_for_a_v5e(one_chip, what, shape, dtype):
    """The five kernels of ``kernels/kda_rows.py`` through Mosaic at the
    cell's shape (four heads a lane block, 1,024 rows a grid step walked
    128 a turn) and in float32 at a shape off the row blocks' powers of
    two (``log_decay``'s forward is XLA's: no kernel); a call asks for the
    module's own count (``vmem_bytes``) and the compiled kernel takes
    less."""
    kr = importlib.import_module("paddle_tpu.kernels.kda_rows")
    b, S, P = shape
    itemsize = jnp.dtype(dtype).itemsize

    def sds(shape_, dtype_):
        return jax.ShapeDtypeStruct(shape_, dtype_, sharding=one_chip)

    def both(fn):
        def run(g, *args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(g)
        return jax.jit(run)

    f32 = jnp.float32
    calls = {
        "l2_heads": (lambda x: kr.l2_heads(x, scale=0.5, interpret=False),
                     itemsize, [sds(shape, dtype)] * 2),
        "log_decay": (lambda *a: kr.log_decay(*a, interpret=False), 4,
                      [sds(shape, f32), sds(shape, f32), sds((P,), f32),
                       sds((P // 128,), f32)]),
        "norm_gate": (lambda *a: kr.norm_gate(*a, eps=1e-5, interpret=False),
                      itemsize, [sds(shape, dtype), sds(shape, dtype),
                                 sds(shape, f32), sds((128,), f32)]),
    }
    assert kr.supported(shape, 128, itemsize) and set(calls) == set(kr.PARTS)
    for part, (fn, narrowest, args) in calls.items():
        text = both(fn).lower(*args).compile().as_text()
        bs, _, lanes = kr.geometry(S, P, narrowest)
        assert "kda_log_decay_fwd" not in text
        for way in ("bwd",) if part == "log_decay" else ("fwd", "bwd"):
            asked, took = _vmem(text, "kda_%s_%s" % (part, way))
            assert asked == kr.vmem_bytes(part, bs, lanes, narrowest)
            assert took < asked < 20 * 2 ** 20, (what, part, way, took, asked)


def test_a_kda_layer_s_text_holds_no_float32_view_by_heads(one_chip):
    """The cell's KDA layer, recompute + backward, through
    ``scripts/attn_outside_hlo.py`` (the no-chip reading ISSUE 60 was sized
    by): its kernels are the filters', the delta rule's and the five row
    kernels'; no float32 array of a projection's size ([1, 16384, 4096], or
    [16384, 32, 128], or its [2048, 8, 32, 128] tiles) is moved by a
    ``reshape``, ``copy``, ``broadcast``, ``convert`` or pointwise fusion of
    the entry computation (the parent made thirty-nine such instructions,
    nine float32 copies and six float32 reshapes among them, and moved 20.44
    GB outside its matmuls and kernels where 0.77 is left)."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("kimi_linear_48b_a3b.s16384_scan",
                                      tiny=False)
    kind = hlo.default_kind(cfg)
    assert (batch, seq, kind, cfg.kda_heads, cfg.kda_head_dim) \
        == (1, 16384, "kda", 32, 128)
    text = hlo.compiled_text(cfg, batch, seq, kind)
    groups, by_kernel, others = hlo.account(text)
    assert set(by_kernel) == {
        "mamba_filter_fwd", "mamba_filter_bwd", "kda_chunk_fwd",
        "kda_chunk_bwd", "kda_l2_heads_fwd", "kda_l2_heads_bwd",
        "kda_log_decay_bwd", "kda_norm_gate_fwd", "kda_norm_gate_bwd"}
    elements = seq * cfg.kda_heads * cfg.kda_head_dim
    assert not [o for o in others if o[3].lstrip("(").startswith("f32")
                and o[0] >= 4 * elements], others[:9]
    assert not [o for o in others if o[2] in (
        "reshape", "copy", "broadcast", "convert") and o[0] >= elements], \
        others[:9]
    assert groups["other"] < 1.0e9 and groups["matmul"] > 5.0e9
    # the five row kernels move what the work needs: 3.6 GB (the decays'
    # forward is the epilogue of its matmul)
    assert sum(v for k, v in by_kernel.items()
               if k.startswith(("kda_l2", "kda_log", "kda_norm"))) < 3.8e9


def test_solar_open2_s_gated_nope_gqa_position_compiles_for_a_v5e(one_chip):
    """``solar_open2_250b.s4096_scan``'s attention position, recompute +
    backward, at the published shape (64 query heads on 8 key/value heads of
    128, a group of 8 a key/value head-block; no positions: NO row kernel
    rotates or norms q and k; the element-wise gate XLA's): its kernels are
    the flash forward, the delta pass and ONE fused backward sweep, and no
    K or V is repeated to the query heads' width in HBM ([4096, 8192] in
    bf16 would be 64 MB an array)."""
    hlo = _script("attn_outside_hlo")
    cfg, batch, seq = hlo.cell_config("solar_open2_250b.s4096_scan",
                                      tiny=False)
    kind = cfg.layer_kinds[0]
    assert (batch, seq, kind, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.attn_gate, cfg.positions) == (
        1, 4096, (None, False), 64, 8, 128, True, None)
    text = hlo.compiled_text(cfg, batch, seq, kind)
    groups, by_kernel, others = hlo.account(text)
    assert set(by_kernel) == {"flash_fwd", "flash_delta", "flash_bwd_fused"}
    assert fa.kv_blocks(seq, 512, 512, True, None) == 36
    # q, o, do, dq at 64 heads, k, v, dk, dv at 8: the kernels move under
    # 0.56 GB (0.524 read; a K and V repeated to 64 heads would add 0.35)
    assert sum(by_kernel.values()) < 0.56e9, by_kernel
    assert groups["matmul"] > groups["other"]


@pytest.mark.parametrize("what,overrides,need_gb", [
    pytest.param("the published widths at S = 4,096", (), (13.5, 14.5),
                 marks=pytest.mark.slow),
    # one period at every kind's own kernels, the odd router (320) and
    # share (10) kept: 64 KDA heads -> 4, GQA 64 / 8 -> 8 / 1 (the group of
    # 8), the stream 512, experts of 256, 2,048 rows of vocabulary, S = 256
    # (two stacks of the delta rule's 128 rows)
    ("a tiny shape of the same step", (
        "S=256", "vocab_size=2048", "hidden=512", "kda_heads=4", "n_heads=8",
        "n_kv_heads=1", "ffn_hidden=256", "shared_ffn_hidden=256"),
     (0.2, 0.4)),
])
def test_solar_open2_s_whole_step_compiles_for_a_v5e(one_chip, what,
                                                     overrides, need_gb):
    """``solar_open2_250b.s4096_scan``'s whole ``run_steps`` (two staged
    batches, AdamW, per-layer remat) compiled for the described chip as
    ``scripts/step_memory_count.py`` compiles it, every kernel through
    Mosaic: the step's NEED by the program's own account
    (``memscope.need_bytes``) at the published widths is the 13.97 GB the
    configuration's file quotes, under the 16.4 GB a step is held to (90 s:
    ``slow``); the tiny shape of it stays in tier-1 (20 s)."""
    count = _script("step_memory_count")
    memscope = importlib.import_module("paddle_tpu.monitor.memscope")
    kda = importlib.import_module("paddle_tpu.kernels.kda_chunk")
    compiled, n_params, _ = count.count("solar_open2_250b.s4096_scan",
                                        *overrides)
    assert not kda._on_tpu()            # put back as ``count`` returned
    text = compiled.as_text()
    for kernel in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_l2_heads_fwd",
                   "kda_norm_gate_bwd", "kda_log_decay_bwd",
                   "mamba_filter_fwd", "flash_fwd", "flash_bwd_fused",
                   "moe_rows_sum"):
        assert kernel in text, (what, kernel)
    need = memscope.need_bytes(memscope.program_ledger(compiled)) / 1e9
    assert need_gb[0] < need < need_gb[1] < 16.4, (what, need)
    if not overrides:
        assert n_params == 1_420_916_544


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


# cell -> the several-block sweeps one traced forward + backward of its
# PUBLISHED configuration counts in ``monitor.kernels.flash_sweep_calls``,
# (part, group, heads in a step): SmallThinker's full and windowed layer
# kinds (a forward each and one recomputed under remat), a group's seven
# heads in every step; Nemotron's sixteen; LFM2's four stacked lane blocks
SWEEPS = {
    "smallthinker": ("smallthinker_21b_a3b_config", (1, 16384),
                     {("fwd", 7, 7): 4, ("bwd", 7, 7): 2}),
    "nemotron_h": ("nemotron3_nano_30b_a3b_config", (2, 8192),
                   {("fwd", 16, 16): 2, ("bwd", 16, 16): 1}),
    "lfm2": ("lfm2_8b_a1b_config", (2, 8192),
             {("fwd", 4, 4): 2, ("bwd", 4, 4): 1}),
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


@pytest.mark.parametrize("b,S", [(1, 16384), (2, 8192)])
def test_the_three_stream_rotary_pass_compiles_for_a_v5e(one_chip, b, S):
    """The row kernel with positions that are DATA, as
    ``transformer._norm_and_rotate`` calls it where a batch carries
    ``positions`` [3, b, S] (temporal, height, width; sections [16, 24, 24]
    of a head's 64 pairs): the tables [b * S, 128] from ``angle_tables(
    positions=)``, the batch folded into the rows, the per-head norm in the
    same pass, on q (32 heads of 128) and k (4), forward and backward, at
    the cell's rows.  The cell itself sends text positions and no such
    field, so it takes the plain tables: this compile is all that holds the
    stream path to Mosaic's rules."""
    qr = importlib.import_module("paddle_tpu.kernels.qk_rope")
    dh, sections = 128, (16, 24, 24)
    positions = jax.ShapeDtypeStruct((3, b, S), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((dh,), jnp.float32, sharding=one_chip)
    for heads in (32, 4):
        W = heads * dh
        x = jax.ShapeDtypeStruct((b, S, W), jnp.bfloat16, sharding=one_chip)

        def both(x, w, positions, g):
            tables = qr.angle_tables(S, dh, 1e7, 0, positions, sections)
            assert tables[0].shape == (b * S, 128)
            out, vjp = jax.vjp(lambda x, w: qr.qk_rope(
                x.reshape(1, b * S, W), w, tables, head_dim=dh, norm="head",
                eps=1e-6, interpret=False).reshape(x.shape), x, w)
            return (out,) + vjp(g)

        text = jax.jit(both).lower(x, w, positions, x).compile().as_text()
        rows = qr.block_rows(b * S, W, 2)
        assert qr.supported((1, b * S, W), dh, 2)
        for kernel in ("qk_rope_fwd", "qk_rope_bwd"):
            asked, took = _vmem(text, kernel)
            assert asked == qr.vmem_bytes(rows, W, 2) < 20 * 2 ** 20
            assert took < asked, (heads, kernel, took, asked)


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
        args, names = (x, x, y, y), {
            "flash_swa_fwd": (B, H, 1, band),
            "flash_swa_bwd_fused": (B, H, band)}
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
