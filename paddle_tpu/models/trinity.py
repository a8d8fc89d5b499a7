"""Trinity-class sparse decoder LM pretraining (Arcee AI
Trinity-Large-Preview, 2026-01; HF ``model_type`` ``afmoe``, 400B-A13B): a
decoder with RMS norms and no bias whose every branch is SANDWICHED between
two norms (one on its input, one on its output), whose attention carries a
sigmoid OUTPUT GATE, and whose layers come three with a 4,096-token window
and rotary positions to one with full attention and NO positions.  48 query
heads on 8 key/value heads of 128, q and k RMS-normed per head.  The first
``num_dense_layers`` layers carry a dense gated-SiLU FFN of width 12,288;
every other layer 256 gated-SiLU experts of width 3,072 of which a token
meets 4, beside ONE shared expert that every token meets.  The router is a
sigmoid: the 4 largest of ``sigmoid(logits) + bias``, weighted by the
sigmoids without the bias, renormalised and multiplied by ``route_scale``
2.448; the bias is running state that the load moves
(``parallel/moe.py:balance_bias``, by ``load_balance_coeff``) and no
gradient reaches.  The embedding is multiplied by sqrt(hidden)
(``mup_enabled``); the head is untied.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``attn_gate``, ``post_norm``, ``route_scale``,
``embed_scale``; ``layer_pattern`` with windows, ``prefix_pattern`` /
``dense_ffn_hidden``, ``qk_norm="head"``, ``n_kv_heads``,
``shared_ffn_hidden``, ``routing``, ``router_bias_rate``, ``experts_held``),
on the flash kernels' grouped mode, full and windowed, and
``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss, trainer and
builder are ``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer, as in ``models/smallthinker.py``:
``experts_held`` of the 256 routed experts from ``first_expert`` and a slice
of the vocabulary.  Every share computes the shared expert; a sum over the
shares counts it once, and BEFORE the FFN's output norm, which is not
linear.  No exchange between shares exists here; the load that moves a
share's biases is counted over its own tokens, for all 256 experts.

Seeded weights: the embedding's rows are N(0, 1 / hidden), the block's
fan-in scale, and the multiplier brings the stream to the unit scale that
the other sparse decoders seed directly; the output norms' scales are
seeded at ``POST_NORM_GAIN``, so that a token's own row and not attention's
near-constant mean ranks its experts, and the selection biases with
``ROUTER_BIAS_STD``, so that every expert draws near the mean load: the
BALANCED case, and the only one the benchmark's cell measures.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (no auxiliary loss: the bias balances).
"""

import functools
import math

from ..parallel import decoder, moe
from ..parallel.transformer import TransformerConfig

__all__ = ["trinity_large_preview_config", "trinity_tiny_config",
           "build_trinity_trainer", "LAYER_TYPES", "layer_kinds", "WINDOW"]

WINDOW = 4096
PUBLISHED_DENSE_LAYERS = 6
# what the output norms' scales are seeded at (assumed; the catalog's
# "depth-scaled sandwich norm" names no value).  Chosen by measurement: an
# output norm hands its branch on at its gain WHATEVER the branch computed,
# and attention's branch is near the same vector for every token (a mean
# over thousands of values).  By the reference's router on the CPU, the (token,
# expert) pairs a layer's 8 held experts met of 768 expected: at gain 1, 114
# to 2,572 (the busiest expert 18 to 47 times the mean; half the layers past
# the first static capacity of 1,024 rows, and the step's time followed the
# seed by 0.79 % where a cell is admitted at 0.5 %); at 60^-1/2 = 0.129, 613
# to 936; at 0.05, 724 to 825 (PERF.md section 6, PR 45)
POST_NORM_GAIN = 0.05
# what the selection biases are seeded with (assumed; a trained model's are
# whatever balanced its load).  A bias is added to a sigmoid SCORE, and the
# fourth of 256 scores stands near 0.9, where a step of 0.1 in the score is a
# whole unit of the logit: at the block's default 0.1 some of a share's 8
# experts drew NO pair and others up to 688 of a layer's 768 (the busiest
# expert 2.5 to 8 times the mean), the grouped matmuls visit a row tile for
# every expert that has a row, and the step's time followed the seed by
# 0.86 % where a cell is admitted at 0.5 %.  At 0.01 an expert draws 42 to
# 177 pairs where 96 is the mean (PERF.md section 6, PR 45)
ROUTER_BIAS_STD = 0.01
# the published ``layer_types``, 60 entries: every fourth layer is full
LAYER_TYPES = tuple("full_attention" if i % 4 == 3 else "sliding_attention"
                    for i in range(60))


def layer_kinds(n_layers, n_dense_layers, window=WINDOW,
                layer_types=LAYER_TYPES):
    """``(prefix_pattern, layer_pattern)`` of a stack cut to ``n_layers``:
    the LAST ``n_dense_layers`` of the published dense layers (published
    layers 6 - n_dense_layers .. 5), then the published layers from the
    first sparse one on (index 6), which have to come out as whole periods
    of four (sliding, full, sliding, sliding)."""
    def kind(name):
        return (window, True) if name == "sliding_attention" else (0, False)

    first = PUBLISHED_DENSE_LAYERS
    rest = layer_types[first:][:n_layers - n_dense_layers]
    period = rest[:4]
    assert 0 <= n_dense_layers <= first \
        and len(rest) == n_layers - n_dense_layers and len(rest) % 4 == 0 \
        and rest == period * (len(rest) // 4), (n_layers, rest)
    return (tuple(kind(t) for t in layer_types[first - n_dense_layers:first]),
            tuple(kind(t) for t in period))


def trinity_large_preview_config(n_layers=58, n_dense_layers=6,
                                 experts_held=256, first_expert=0,
                                 vocab_size=200192, window=WINDOW, **kw):
    """arcee-ai/Trinity-Large-Preview ``config.json``.  Arguments: the depth
    (the leading dense layers and whole periods of four from published
    layer 6 on: the published 60 end on half a period, which the scan over
    whole periods does not express, so the deepest stack here is 58), the
    leading dense layers, the routed experts this chip holds of the 256 and
    the first of them, the rows of the vocabulary it holds, the window.
    What the file names by key and not by formula is listed under
    ``assumed`` in ``benchmark/configs/trinity_large_preview.json``."""
    prefix, period = layer_kinds(n_layers, n_dense_layers, window)
    d = dict(vocab_size=vocab_size, hidden=3072, n_layers=n_layers,
             n_heads=48, n_kv_heads=8, head_width=128, ffn_hidden=3072,
             dense_ffn_hidden=12288, max_seq=262144, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-5, positions="rotary",
             rope_theta=10000.0, layer_pattern=period, prefix_pattern=prefix,
             qk_norm="head", bias=False, tie_head=False, attn_gate=True,
             post_norm=True, post_norm_gain=POST_NORM_GAIN, n_experts=256,
             experts_per_token=4,
             experts_held=experts_held, first_expert=first_expert,
             routing=moe.SIGMOID_BIASED, router_bias_rate=5e-5,
             router_bias_std=ROUTER_BIAS_STD,
             route_scale=2.448, expert_act="silu", shared_ffn_hidden=3072)
    d.update(kw)
    # mup_enabled: the rows enter the stream times sqrt(hidden)
    d.setdefault("embed_scale", math.sqrt(d["hidden"]))
    return TransformerConfig(**d)


def trinity_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: one dense layer
    (sliding) and one period (sliding, full, sliding, sliding), 6 query
    heads on 2 key/value heads of 128 (a group of 3; 768 wide where the
    hidden size is 64) through the flash kernels in 16-row blocks, a window
    of 24 (no multiple of the block) under S = 64, 8 experts of width 32 of
    which this share holds 2 (the second of four shares), top-2 (selection
    biases seeded with 0.1: the second of 8 scores stands where the sigmoid
    is steep, and a fault in their use has to show), a shared expert of
    width 48, float32."""
    return trinity_large_preview_config(**dict(dict(
        n_layers=5, n_dense_layers=1, vocab_size=256, hidden=64, n_heads=6,
        n_kv_heads=2, ffn_hidden=32, dense_ffn_hidden=96,
        shared_ffn_hidden=48, max_seq=64, window=24, n_experts=8,
        experts_per_token=2, experts_held=2, first_expert=2,
        router_bias_std=0.1, dtype="float32", flash_block_q=16,
        flash_block_k=16), **kw))


build_trinity_trainer = functools.partial(
    decoder.build_decoder_trainer, label="trinity")
