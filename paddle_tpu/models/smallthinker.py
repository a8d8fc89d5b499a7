"""SmallThinker-class sparse decoder LM pretraining (PowerInfer
SmallThinker-21BA3B-Instruct, 2025-07, arXiv:2507.20984): a pre-norm decoder
block with RMS norms and no bias, GROUPED-QUERY attention (28 query heads on
4 key/value heads of 128, so heads x width is not the hidden size), a layer
pattern of period four (one full-attention layer with NO positional encoding,
then three rotary layers that see a sliding window of 4,096), a router that
reads the block's INPUT (before its first norm and before attention), top-6
of 64 ReLU-gated experts whose weights are a softmax over the six logits, no
dense FFN, no shared expert, an untied head.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``head_width`` / ``n_kv_heads`` / ``layer_pattern`` /
``router_input`` / ``routing`` / ``expert_act`` / ``experts_held``), on the
flash kernels' grouped and windowed modes and ``parallel/moe.py``'s
``dropless_moe_ffn``; forward, loss, trainer and builder are
``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer, as one of the chips that divide it
would: ``experts_held`` of the 64 experts from ``first_expert`` (the router
still ranks all 64; the layer gives its own experts' part of the result and
that partial result goes on) and a slice of the vocabulary (a smaller
vocabulary: ids, logits and loss are over the slice).  No exchange between
shares exists here.

Seeded weights: the embedding's rows are N(0, 1), not the block's fan-in
scale (``init_transformer_params``, for every block whose router reads its
input).  The router ranks by the un-normed residual stream,
and at the fan-in scale that stream is the embedding at a fiftieth of every
branch's output: a token that met no held expert then carries little but
attention's slowly varying mean, neighbouring tokens rank the experts alike,
and a share's rows swing from an eighth to six tenths of a layer's pairs
with the seed (measured; PERF.md section 6, PR 31).  At unit scale the
token's own row leads and a quarter of the pairs meets the 16 held experts
in every layer: the BALANCED case, and the only one the benchmark's cell
measures (no trained router's balance was checked against it).

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (the published configuration carries no auxiliary
coefficient).
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import TransformerConfig

__all__ = ["smallthinker_21b_a3b_config", "smallthinker_tiny_config",
           "build_smallthinker_trainer", "PERIOD", "WINDOW"]

WINDOW = 4096
# the published rope_layout and sliding_window_layout are this period,
# thirteen times: a full layer without positions, three windowed rotary ones
PERIOD = ((0, False), (WINDOW, True), (WINDOW, True), (WINDOW, True))


def smallthinker_21b_a3b_config(n_layers=52, experts_held=64, first_expert=0,
                                vocab_size=151936, **kw):
    """PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``.  Arguments:
    the depth (whole periods of four), the experts this chip holds of the 64
    and the first of them, and the rows of the vocabulary it holds."""
    d = dict(vocab_size=vocab_size, hidden=2560, n_layers=n_layers,
             n_heads=28, n_kv_heads=4, head_width=128, ffn_hidden=768,
             max_seq=16384, causal=True, dtype="bfloat16", norm="rms",
             norm_eps=1e-6, positions="rotary", rope_theta=1.5e6,
             layer_pattern=PERIOD, qk_norm=False, bias=False, tie_head=False,
             n_experts=64, experts_per_token=6, experts_held=experts_held,
             first_expert=first_expert, routing="top_k_softmax",
             expert_act="relu", router_input="block")
    d.update(kw)
    return TransformerConfig(**d)


def smallthinker_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: 4 layers (one
    period), 6 query heads on 2 key/value heads of 128 (a group of 3; 768
    wide where the hidden size is 64) through the flash kernels in 16-row
    blocks, a window of 24 (no multiple of the block) under S = 64, 8
    experts of width 32 of which this share holds 2 (the second of four
    shares), top-2, float32."""
    return smallthinker_21b_a3b_config(**dict(dict(
        vocab_size=256, hidden=64, n_layers=4, n_heads=6, n_kv_heads=2,
        ffn_hidden=32, max_seq=64, n_experts=8, experts_per_token=2,
        experts_held=2, first_expert=2, dtype="float32", flash_block_q=16,
        flash_block_k=16,
        layer_pattern=((0, False),) + ((24, True),) * 3), **kw))


build_smallthinker_trainer = functools.partial(
    decoder.build_decoder_trainer, label="smallthinker")
