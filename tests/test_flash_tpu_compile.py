"""The flash kernels compiled for a described v5e, at the benchmark cells'
real shapes: what interpret mode cannot see (Mosaic's tiling rules, the
scoped VMEM a grid step may hold).  Nothing runs; no chip is needed.  All
such compiles live in this one file and describe the chip inside a fixture,
so that only the worker that is given the file loads the TPU's library."""

import importlib
import json
import math
import os
import re

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

    text, grids = _compiled(attn, x, x, x, x)
    assert text.count("tpu_custom_call") >= 2, what
    if S > 512:     # several blocks: the sweeps' step tables are the grids
        heads, steps = H * D // 128, fa.kv_blocks(S, 512, 512, causal)
        assert grids == {"flash_fwd": (B, heads, 1, steps),
                         "flash_bwd_dq": (B, heads, 1, steps),
                         "flash_bwd_dkv": (B, heads, steps)} and steps == 36, what


def _compiled(attn, *shapes):
    """(the compiled program's text, {kernel name: grid}) of ``attn``'s
    forward and backward."""
    def both(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return (o,) + vjp(do)

    traced = jax.jit(both).trace(*shapes)
    grids = {name: tuple(int(n) for n in grid.split(",") if n.strip())
             for grid, name in re.findall(
                 r"grid=\(([\d, ]*)\).*?name=(flash_\w+)", str(traced.jaxpr),
                 re.S)}
    return traced.lower().compile().as_text(), grids


SMALLTHINKER, LFM2 = (1, 16384, 28, 4, 128), (2, 8192, 32, 8, 64)


@pytest.mark.parametrize("what,shape,window,names,steps", [
    ("smallthinker_21b_a3b.s16384_scan, a full layer", SMALLTHINKER, None,
     ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"), 528),
    ("smallthinker_21b_a3b.s16384_scan, a windowed layer", SMALLTHINKER, 4096,
     ("flash_swa_fwd", "flash_swa_bwd_dq", "flash_swa_bwd_dkv"), 252),
    ("lfm2_8b_a1b.s8192_scan, two heads a lane block", LFM2, None,
     ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"), 136),
])
def test_grouped_and_windowed_kernels_compile_for_a_v5e(one_chip, what, shape,
                                                        window, names, steps):
    """28 query heads on 4 key/value heads of 128 over 16,384 positions:
    the index maps' reads of the scalar-prefetched step table and the dk/dv
    sweep over a group's heads are what Mosaic has to take; at 32 on 8 heads
    of 64, the select of a key/value block's half by a traced scalar and the
    dk/dv sums into that half as well.  The grids are the tables: (row,
    key/value head-block, query head-block of its group) by the blocks under
    the diagonal (in the band), the dk/dv sweep's (row, key/value
    head-block) by the group's times as many."""
    B, S, H, Hkv, D = shape
    xq = jax.ShapeDtypeStruct((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    xk = jax.ShapeDtypeStruct((B, S, Hkv * D), jnp.bfloat16, sharding=one_chip)
    attn = lambda q, k, v: fa.flash_attention_packed(
        q, k, v, H, causal=True, block_q=512, block_k=512, interpret=False,
        n_kv_heads=Hkv, window=window)

    text, grids = _compiled(attn, xq, xk, xk, xq)
    for name in names:
        assert name in text, (what, name)
    kv_blocks, group = Hkv * D // 128, H // Hkv
    assert fa.kv_blocks(S, 512, 512, True, window) == steps
    assert grids == dict(zip(names, [(B, kv_blocks, group, steps)] * 2
                             + [(B, kv_blocks, group * steps)])), what


@pytest.mark.parametrize("what,N,V,E,norm", [
    ("smallthinker_21b_a3b.s16384_scan", 16384, 37984, 2560, "rms"),
    ("olmoe_1b_7b.s4096_scan", 16384, 50304, 2048, "rms"),
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
