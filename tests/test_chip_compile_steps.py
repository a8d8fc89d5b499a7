"""Whole steps and positions compiled for a described v5e, and the head
matrix's gradient in a few windows.  Nothing runs; no chip is needed
(``tests/tpu_compile.py``)."""

import importlib
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest

from tpu_compile import (_script, one_chip)  # noqa: F401

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.mark.parametrize("what,N,V,E,norm", [
    ("smallthinker_21b_a3b.s16384_scan", 16384, 37984, 2560, "rms"),
    ("olmoe_1b_7b.s4096_scan", 16384, 50304, 2048, "rms"),
    # a looped stack's four exits' rows through the head in one call
    ("ouro_2_6b.s4096_scan", 4 * 8192, 49152, 2048, "rms"),
    ("bert_base.s512_scan", 32768, 30528, 768, "layer"),
    # the widest vocabulary of all, a chip's rows of it (PR 74)
    ("kanana_2_30b_a3b.s8192_ep4", 8192, 128256, 2048, "rms"),
])
def test_head_matrix_gradient_is_tiled_in_a_few_windows(one_chip, what, N, V,
                                                        E, norm):
    """The tp=1 head's gradient at a cell's head shape (since PR 74 made in
    the forward rule's loop, beside a row block's kept logits).  A
    vocabulary chunk's float32 dW matmul, accumulated into ``demb`` in
    place, is one fusion a chunk whose result is ``f32[V, E]``; the compiler
    walks it in ``iteration_bounds`` windows.  With chunks of 9,496 = 8 x
    1,187 rows (37,984 / 4; 1,187 is prime) it found 1,187 windows of one
    8-row tile, and the four fusions took a fifth of the SmallThinker cell's
    step."""
    T = importlib.import_module("paddle_tpu.parallel.transformer")
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    emb = sds((V, E))

    def loss(x, scale, bias, emb, labels, wgt):
        return T._weighted_vocab_nll(x, scale, bias, emb, labels, wgt,
                                     norm=(norm, 1e-5))[0]

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


@pytest.mark.parametrize("what,overrides,need_gb", [
    pytest.param("the published widths at S = 8,192", (), (13.0, 14.2),
                 marks=pytest.mark.slow),
    # the dense layer and two sparse ones at the exchange's own granule: 4
    # heads of 128 + 64 against values of 128, the stream 512, 8 experts of
    # 256 top-2 (two a chip), 2,048 rows of vocabulary, S = 1,024: 2,048
    # pairs a chip, a round of 1,024 rows a destination
    ("a tiny shape of the same step", (
        "S=1024", "n_layers=3", "vocab_size=2048", "hidden=512", "n_heads=4",
        "kv_lora_rank=128", "ffn_hidden=256", "dense_ffn_hidden=1024",
        "shared_ffn_hidden=512", "n_experts=8", "experts_per_token=2"),
     (0.1, 0.5)),
])
def test_kanana2_s_four_chip_step_compiles_for_a_v5e_host(one_chip, what,
                                                          overrides, need_gb):
    """``kanana_2_30b_a3b.s8192_ep4``'s whole ``run_steps`` (dp = 4: the
    experts ride it; two staged batches, AdamW, per-layer remat) compiled for
    the described host's FOUR devices as ``scripts/step_memory_count.py``
    compiles it, every kernel through Mosaic and the collectives with the
    rest: the exchange's ``all-to-all`` stands in the program beside the
    gradients' ``all-reduce``, and ONE device's NEED by the program's own
    account at the published widths is the 13.60 GB the configuration's file
    quotes, under the 16.4 GB a step is held to (three minutes: ``slow``);
    the tiny shape of it stays in tier-1."""
    count = _script("step_memory_count")
    memscope = importlib.import_module("paddle_tpu.monitor.memscope")
    compiled, n_params, state_bytes = count.count(
        "kanana_2_30b_a3b.s8192_ep4", *overrides)
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_fused", "gmm", "tgmm",
                   "moe_rows_sum"):
        assert kernel in text, (what, kernel)
    assert " all-to-all(" in text and "all-reduce" in text, what
    need = memscope.need_bytes(memscope.program_ledger(compiled)) / 1e9
    assert need_gb[0] < need < need_gb[1] < 16.4, (what, need)
    if not overrides:
        assert n_params == 3_149_554_688
        # a device's share of the state: its 32 experts' leaves and moments
        assert round(state_bytes / 1e9, 2) == 8.03
