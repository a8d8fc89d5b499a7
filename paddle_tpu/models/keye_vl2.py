"""Keye-VL-2.0-class sparse-attention MoE decoder, the language model
(Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``, ``model_type`` ``KeyeVL2``,
2026-06): a pre-norm decoder block with RMS norms and no bias, GROUPED-QUERY
attention (32 query heads on 4 key/value heads of 128) whose q and k are
RMS-normed a head and rotated by THREE position streams (``mrope_section``
[16, 24, 24]: temporal, height, width; a text token has the three equal,
which is plain rotary), and which reads, of a query's causal keys, the 2,048
an INDEXER ranks best (16 heads of 64 on one LayerNormed key head,
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; ties at the threshold
kept, fewer than 2,048 causal keys: all of them); top-8 of 128 gated-SiLU
experts of width 768 in every layer, the eight weights a softmax over the
eight logits (``norm_topk_prob``), no shared expert, an untied head.

The loss is next-token cross entropy plus the indexer's own term, the mean
over layers and tokens of ``KL(mean over heads of attention's probabilities
|| softmax over the selected keys of I)``, with a stop-gradient on that
target and on the indexer's input and none through the selection: the
indexer's five leaves learn from the KL alone and every other leaf from the
cross entropy alone (the sparse-training stage of DeepSeek-V3.2-Exp's
indexer, arXiv:2512.02556; the config carries no recipe, so this and the
coefficient 1 are ASSUMED).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``indexer_heads`` / ``indexer_dim`` / ``indexer_topk`` /
``mrope_sections`` / ``qk_norm="head"`` / ``n_kv_heads`` / ``experts_held``),
on ``kernels/indexer.py`` (scores, selection, the masked sweeps, the KL
pass) and ``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss, trainer and builder are
``parallel/decoder.py``'s.  The vision tower is not here: the program trains
the language model on token ids, and the three position streams are an input
(``batch["positions"]`` [3, B, S] of a trainer built with
``positions=True``; absent: the token index three times).

A chip may hold its SHARE of a layer (``experts_held`` of the 128 experts
from ``first_expert``, a slice of the vocabulary), as one of the chips that
divide it would.  No exchange between shares exists here.

Seeded weights (all ASSUMED, the config seeds nothing): matrices N(0, 1 /
fan_in); embedding rows N(0, 1) and every branch's output projection times
``n_layers_full^(-1/2)`` = 48^(-1/2), so that the stream a router reads is
the token's own row whatever the depth cut (PERF.md section 6, PRs 31, 39);
the per-head q/k norm weights and the indexer key norm's scale at 2^(1/2)
(``qk_norm_gain``): a head's scores are then N(0, 4) over a row's keys and
the indexer's N(0, about 1), so the main softmax and ``I`` are visibly
uneven a row and a wrong selection shows in the logits.
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import TransformerConfig

__all__ = ["keye_vl2_30b_a3b_config", "keye_vl2_tiny_config",
           "build_keye_vl2_trainer", "FULL_DEPTH"]

FULL_DEPTH = 48


def keye_vl2_30b_a3b_config(n_layers=FULL_DEPTH, experts_held=128,
                            first_expert=0, vocab_size=151936, **kw):
    """Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``, the text model.
    Arguments: the depth, the experts this chip holds of the 128 and the
    first of them, and the rows of the vocabulary it holds."""
    d = dict(vocab_size=vocab_size, hidden=2048, n_layers=n_layers,
             n_heads=32, n_kv_heads=4, head_width=128, ffn_hidden=768,
             max_seq=16384, causal=True, dtype="bfloat16", norm="rms",
             norm_eps=1e-6, positions="rotary", rope_theta=1e7,
             mrope_sections=(16, 24, 24), qk_norm="head",
             qk_norm_gain=2 ** 0.5, bias=False, tie_head=False,
             n_experts=128, experts_per_token=8, experts_held=experts_held,
             first_expert=first_expert, routing="top_k_softmax",
             expert_act="silu", indexer_heads=16, indexer_dim=64,
             indexer_topk=2048,
             residual_out_gain=FULL_DEPTH ** -0.5)
    d.update(kw)
    return TransformerConfig(**d)


def keye_vl2_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: 2 layers, 16
    query heads on 2 key/value heads of 128 (a group of 8), sections [16, 24,
    24], an indexer of 4 heads of 16 that keeps 8 of a row's keys at S = 64
    in 16-row blocks, 8 experts of width 32 of which this share holds 2 (the
    second of four shares), top-2, float32."""
    return keye_vl2_30b_a3b_config(**dict(dict(
        vocab_size=256, hidden=64, n_layers=2, n_heads=16, n_kv_heads=2,
        ffn_hidden=32, max_seq=64, n_experts=8, experts_per_token=2,
        experts_held=2, first_expert=2, dtype="float32", flash_block_q=16,
        flash_block_k=16, indexer_heads=4, indexer_dim=16, indexer_topk=8),
        **kw))


build_keye_vl2_trainer = functools.partial(
    decoder.build_decoder_trainer, label="keye_vl2")
