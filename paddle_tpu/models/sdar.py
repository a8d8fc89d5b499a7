"""SDAR-class block-diffusion MoE decoder (JetLM/SDAR-30B-A3B-Chat
``config.json``, ``model_type`` ``sdar_moe``, 2025-10; arXiv:2510.06303,
which adapts an autoregressive Qwen3-MoE model to BD3-LM's block diffusion,
arXiv:2503.09573): a pre-norm decoder block with RMS norms and no bias,
grouped-query attention (32 query heads on 4 key/value heads of 128) whose q
and k are RMS-normed a head and rotated (rotate-half, theta 1e6), top-8 of
128 gated-SiLU experts of width 768 in every layer, the eight weights the
softmax's divided by their sum (``norm_topk_prob``), no shared expert, an
untied head.

What makes it a block-diffusion model is how it is TRAINED, and that is
configuration of the one block and the one trainer, not a second of either
(``TransformerConfig.block_diffusion`` = the block length Bd):

- a batch carries its noise beside its ``ids`` [B, S]: ``t`` [B, S / Bd] in
  (0, 1), one level a block, and ``u`` [B, S] uniform; token i is masked
  where ``u_i < t_{i // Bd}`` and reads ``mask_token_id``
  (``decoder.noised_rows``);
- the stack runs on the noised copy over the clean one, ``[x_t ; x_0]``, 2 S
  rows a layer; both copies carry the sequence's positions (row r rotates by
  ``r mod S``);
- attention's mask is the rule's three parts (``kernels/flash_attention.py``,
  ``blockdiff_seen``): a noised query sees the noised keys of ITS OWN block,
  both directions, and the clean keys of EARLIER blocks; a clean query the
  clean keys of its own and earlier blocks; nothing else.  It follows from
  the shapes: the kernels' step table and in-tile mask take it as a rule, no
  mask is an operand and nothing [2 S, 2 S] stands anywhere;
- the loss is the masked tokens' cross entropy on the NOISED rows, unshifted
  (row i's target is token i), ``(1 / S) sum_i m_i / t_{i // Bd} CE_i``
  (BD3-LM's linear schedule; the divisor the sequence's length); the clean
  rows feed keys and values and no loss (``decoder._denoising_loss``).

What the config leaves open is ASSUMED (``benchmark/configs/
sdar_30b_a3b_chat.json`` lists each with its source): the block length 4
(the model card's generation default), the noise (the batch's: the program
takes any levels in (0, 1)), the per-head q/k norm (the Qwen3-MoE lineage's),
no auxiliary router loss, the mask token's row.  Decoding (a step that
yields a block) is not here: the program trains.

A chip may hold its SHARE of a layer (``experts_held`` of the 128 experts
from ``first_expert``, a slice of the vocabulary whose LAST row stands for
the mask token), as one of the chips that divide it would.  No exchange
between shares exists here.

Seeded weights (all ASSUMED, as ``models/keye_vl2.py``'s): matrices N(0, 1 /
fan_in); embedding rows N(0, 1) and every branch's output projection times
48^(-1/2); the per-head q/k norm weights at 2^(1/2), so a row's softmax is
visibly uneven and a wrong mask shows in the logits; and the MASK TOKEN's
embedding row times 2^(-10) (``mask_embed_gain``): every masked token reads
that one row, and at a token's size all of a sequence's masked rows (a third
of the stack's) met the same eight experts in every layer, so a share's held
pairs and its step's time followed which of them it holds, by the seed; seeded
small, a masked row's stream is what attention brings it, by its position
(and carries bf16's rounding of a whole branch: the benchmark's witness reads
the masked rows by their median for that reason).
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import TransformerConfig

__all__ = ["sdar_30b_a3b_config", "sdar_tiny_config", "build_sdar_trainer",
           "FULL_DEPTH", "BLOCK_LENGTH"]

FULL_DEPTH = 48
BLOCK_LENGTH = 4


def sdar_30b_a3b_config(n_layers=FULL_DEPTH, experts_held=128, first_expert=0,
                        vocab_size=151936, **kw):
    """JetLM/SDAR-30B-A3B-Chat ``config.json``.  Arguments: the depth, the
    experts this chip holds of the 128 and the first of them, and the rows
    of the vocabulary it holds (the last stands for the mask token unless
    ``mask_token_id`` says otherwise)."""
    d = dict(vocab_size=vocab_size, hidden=2048, n_layers=n_layers,
             n_heads=32, n_kv_heads=4, head_width=128, ffn_hidden=768,
             max_seq=32768, causal=False, dtype="bfloat16", norm="rms",
             norm_eps=1e-6, positions="rotary", rope_theta=1e6,
             qk_norm="head", qk_norm_gain=2 ** 0.5, bias=False,
             tie_head=False, n_experts=128, experts_per_token=8,
             experts_held=experts_held, first_expert=first_expert,
             routing="top_k_softmax", expert_act="silu",
             block_diffusion=BLOCK_LENGTH, mask_embed_gain=2.0 ** -10,
             residual_out_gain=FULL_DEPTH ** -0.5)
    d.update(kw)
    return TransformerConfig(**d)


def sdar_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: 2 layers, 16
    query heads on 2 key/value heads of 128 (a group of 8), blocks of 4 at S
    = 64 (128 rows a layer) in 16-row tiles, 8 experts of width 32 of which
    this share holds 2 (the second of four shares), top-2, float32."""
    return sdar_30b_a3b_config(**dict(dict(
        vocab_size=256, hidden=64, n_layers=2, n_heads=16, n_kv_heads=2,
        ffn_hidden=32, max_seq=128, n_experts=8, experts_per_token=2,
        experts_held=2, first_expert=2, dtype="float32", flash_block_q=16,
        flash_block_k=16), **kw))


build_sdar_trainer = functools.partial(
    decoder.build_decoder_trainer, label="sdar")
