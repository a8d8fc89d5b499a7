"""The row kernels compiled for a described v5e at the cells' shapes:
``qk_rope`` (its Mosaic digests: ``QK_ROPE_MOSAIC``), ``flash_delta``,
``gated_norm``, ``mamba_filter`` and ``kda_rows``, and the layers' texts that
hold no float32 pass outside them (a rotary layer's, Mistral's latent
layer's, a Mamba, a Mamba-2, a flash and a KDA layer's).  Nothing runs; no
chip is needed (``tests/tpu_compile.py``)."""

import functools
import importlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (MAMBA_KERNELS, _mosaic_digests, _moved, _script,
                         _vmem, one_chip)  # noqa: F401


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
