"""Mistral-Small-4-class sparse decoder LM pretraining (Mistral AI
Mistral-Small-4-119B-2603, 2026-03; HF ``model_type`` ``mistral4``, the
language model alone): a pre-norm decoder with RMS norms and no bias whose
every layer has multi-head LATENT attention and, beside top-4 of 128 routed
gated-SiLU experts, ONE shared expert that every token meets; an untied
head.

Latent attention: queries come off a latent of 1,024 (``wq_a``, an RMS norm,
``wq_b``), keys and values off ONE latent of 256 (``wkv_a``, an RMS norm,
``wkv_b``).  A head is 128 wide, its first 64 columns without positions, its
last 64 rotated in the adjacent-pair convention; the rotated 64 of the KEY
are one vector a token (64 more columns of ``wkv_a``) that all 32 heads
share.  Positions are YaRN's (factor 128 over 8,192 original positions: a
pair that turns less than once over those is interpolated by 128, one that
turns more than 32 times keeps its frequency, a ramp between), the softmax
scale carries ``mscale^2`` = 2.2058, and the query of position p is scaled
by ``1 + 0.1 ln(1 + p // 8192)``.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``kv_lora_rank`` and the other latent sizes, the ``rope_*``
keys, ``q_scale_beta``, ``shared_ffn_hidden``, ``routing``,
``experts_held``), on the flash kernels' packed causal mode and
``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss, trainer and
builder are ``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer, as in ``models/smallthinker.py``:
``experts_held`` of the 128 routed experts from ``first_expert`` and a slice
of the vocabulary.  Every share computes the shared expert; a sum over the
shares counts it once.  No exchange between shares exists here.

Seeded weights: where a chip holds a share, the embedding's rows are N(0,
1), not the block's fan-in scale (``init_transformer_params``: a share of
the experts that no selection bias balances).  The router reads the normed
stream after attention, and at the fan-in scale that stream is attention's
slowly varying output with the token's own row at a sixtieth of it:
neighbouring tokens rank the experts alike (busiest expert 1.9 times the
mean), a layer's held pairs ranged from 3,693 to 5,029 of 65,536 at ONE
seed, and a layer past the first static capacity (5,120) costs 25 ms in
that step (measured; PERF.md section 6, PR 39).  At unit scale the token's
own row leads and a sixteenth of the pairs meets the 8 held experts in
every layer: the BALANCED case, and the only one the benchmark's cell
measures.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (the published configuration carries no auxiliary
coefficient).
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import TransformerConfig

__all__ = ["mistral_small_4_config", "mistral4_tiny_config",
           "build_mistral4_trainer"]


def mistral_small_4_config(n_layers=36, experts_held=128, first_expert=0,
                           vocab_size=131072, **kw):
    """mistralai/Mistral-Small-4-119B-2603 ``config.json`` (the text model).
    Arguments: the depth, the routed experts this chip holds of the 128 and
    the first of them, the rows of the vocabulary it holds.  What the file
    names by key and not by formula is listed under ``assumed`` in
    ``benchmark/configs/mistral_small_4_119b.json``."""
    d = dict(vocab_size=vocab_size, hidden=4096, n_layers=n_layers,
             n_heads=32, head_width=128, ffn_hidden=2048, max_seq=1048576,
             causal=True, dtype="bfloat16", norm="rms", norm_eps=1e-6,
             positions="rotary", rope_theta=10000.0, qk_norm=False,
             bias=False, tie_head=False, q_lora_rank=1024, kv_lora_rank=256,
             qk_nope_dim=64, qk_rope_dim=64, v_head_dim=128,
             rope_factor=128.0, rope_original_max=8192, rope_beta_fast=32.0,
             rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
             q_scale_beta=0.1, n_experts=128, experts_per_token=4,
             experts_held=experts_held, first_expert=first_expert,
             routing=moe.TOP_K_SOFTMAX, expert_act="silu",
             shared_ffn_hidden=2048)
    d.update(kw)
    return TransformerConfig(**d)


def mistral4_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: two layers, 4
    heads of 128 (96 without positions and 32 rotated, so the two parts
    differ; values of 128; 512 wide where the hidden size is 64) through
    the flash kernels in 16-row blocks, latents of 32 and 16, YaRN by 8
    over 16 original positions under S = 64 (so the blend is live and the
    query scale has four steps), 8 routed experts of width 32 of which this
    share holds 2 (the second of four shares), top-2, a shared expert of
    width 48, float32."""
    return mistral_small_4_config(**dict(dict(
        n_layers=2, vocab_size=256, hidden=64, n_heads=4, ffn_hidden=32,
        max_seq=64, q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=96,
        qk_rope_dim=32, rope_factor=8.0, rope_original_max=16,
        rope_beta_fast=4.0, rope_beta_slow=0.5, n_experts=8,
        experts_per_token=2, experts_held=2, first_expert=2,
        shared_ffn_hidden=48, dtype="float32", flash_block_q=16,
        flash_block_k=16), **kw))


build_mistral4_trainer = functools.partial(
    decoder.build_decoder_trainer, label="mistral4")
