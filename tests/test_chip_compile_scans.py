"""The scans compiled for a described v5e at the cells' shapes:
``selective_scan``, ``ssd_scan``, ``kda_chunk`` and ``power_retention``.
Nothing runs; no chip is needed (``tests/tpu_compile.py``)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (MAMBA_KERNELS, _moved, _script, _vmem, one_chip)


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
