"""Jamba-class hybrid state-space decoder LM pretraining (AI21 Jamba2-3B,
2025-10; HF ``model_type`` ``jamba``): a dense pre-norm decoder (RMS norms,
no bias but the filter's, a gated-SiLU FFN of width 8,192 in EVERY layer
since ``num_experts`` is 1, a tied head) whose layers are Mamba-1 mixers
(Gu & Dao, arXiv:2312.00752, with Jamba's three inner RMS norms on the step
sizes' input, B and C) thirteen to one beside multi-query attention: layer i
is attention where ``i mod attn_layer_period = attn_layer_offset`` (7 of 14:
20 query heads of 128 on ONE key/value head), else Mamba (inner width
5,120, 16 state cells a channel, 4 taps, step sizes through a rank of 160).
No positions are added or rotated anywhere: the recurrence carries the
order.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``layer_pattern`` of MAMBA positions around one position-free
attention position, ``run_scan`` so that a period's 7 + 1 + 6 layers are
three scanned runs and not fourteen copies, ``positions`` None,
``dense_ffn_hidden`` without experts, ``n_kv_heads``, ``tie_head``); forward,
loss, trainer and builder are ``parallel/decoder.py``'s.

A chip holds whole periods of the depth; the vocabulary is whole.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else.
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import MAMBA, TransformerConfig

__all__ = ["layer_kinds", "jamba2_3b_config", "jamba_tiny_config",
           "build_jamba_trainer"]


def layer_kinds(period=14, offset=7):
    """One period's kinds as ``modeling_jamba.py`` reads the two keys:
    attention (full, no rotary) at ``offset``, Mamba elsewhere."""
    return tuple((0, False) if i == offset else MAMBA for i in range(period))


def jamba2_3b_config(n_layers=28, vocab_size=65536, **kw):
    """ai21labs/AI21-Jamba2-3B ``config.json``.  Arguments: the depth (whole
    periods of 14) and the rows of the vocabulary this chip holds.  What the
    published file does not carry (the inner norms' places, the seeding of
    the mixer's own leaves) is listed in ``benchmark/configs/jamba2_3b.json``
    under ``assumed``."""
    d = dict(vocab_size=vocab_size, hidden=2560, n_layers=n_layers,
             n_heads=20, n_kv_heads=1, head_width=128, ffn_hidden=8192,
             dense_ffn_hidden=8192, max_seq=262144, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-6, positions=None,
             layer_pattern=layer_kinds(14, 7), run_scan=True, bias=False,
             tie_head=True, expert_act="silu", d_inner=2 * 2560, d_state=16,
             d_conv=4, dt_rank=160, scan_chunk=128)
    d.update(kw)
    return TransformerConfig(**d)


def jamba_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: two periods of
    4 layers with attention at offset 2 (runs of 2, 1 and 1), 5 query heads
    on 1 key/value head of 128 (640 wide where the hidden size is 64), an
    inner width of 128 (one lane block of channels), 16 state cells, 4
    taps, rank 8, chunks of 16 under S = 64 (4 chunks), a gated FFN of
    width 96, float32."""
    return jamba2_3b_config(**dict(dict(
        n_layers=8, vocab_size=256, hidden=64, n_heads=5, n_kv_heads=1,
        ffn_hidden=96, dense_ffn_hidden=96, max_seq=64, dtype="float32",
        layer_pattern=layer_kinds(4, 2), d_inner=128, d_state=16, d_conv=4,
        dt_rank=8, scan_chunk=16), **kw))


build_jamba_trainer = functools.partial(
    decoder.build_decoder_trainer, label="jamba")
