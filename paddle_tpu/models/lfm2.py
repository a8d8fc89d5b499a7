"""LFM2-MoE-class hybrid decoder LM pretraining (LiquidAI LFM2-8B-A1B,
2025-10; HF ``model_type`` ``lfm2_moe``): a pre-norm stack with RMS norms and
no bias whose layers own DIFFERENT leaves.  Most layers have no attention:
their operator is a gated short convolution (``in_proj`` [E, 3E] split into
two gates and a value, a causal depthwise filter of three taps over
``gate_b * value``, ``gate_c *`` that, ``out_proj`` [E, E]).  The others
have grouped-query attention at head width 64 (32 query heads on 8 key/value
heads), q and k RMS-normed per head before rotary positions.  The first
``num_dense_layers`` layers carry a dense gated-SiLU FFN; every other layer
32 gated-SiLU experts of which a token meets 4, chosen by a SIGMOID router:
the k largest of ``sigmoid(logits) + bias``, weighted by the sigmoids
without the bias, renormalised.  The bias is running state that the load
moves (``parallel/moe.py:balance_bias``) and no gradient reaches.  The head
is the embedding (tied).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``layer_pattern`` with CONV positions, ``prefix_pattern`` /
``dense_ffn_hidden``, ``qk_norm="head"``, ``n_kv_heads``, ``routing``,
``experts_held``, ``tie_head``), on the flash kernels' grouped mode at two
heads a lane block and ``parallel/moe.py``'s ``dropless_moe_ffn``; forward,
loss, trainer and builder are ``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer, as in ``models/smallthinker.py``:
``experts_held`` of the 32 experts from ``first_expert`` and a slice of the
vocabulary.  No exchange between shares exists here; in particular the load
that moves a share's biases is counted over its own tokens, for all 32
experts.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (no auxiliary loss: the bias balances).
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import CONV, TransformerConfig

__all__ = ["lfm2_8b_a1b_config", "lfm2_tiny_config", "build_lfm2_trainer",
           "LAYER_TYPES", "layer_kinds"]

# the published ``layer_types``, 24 entries
LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))
PUBLISHED_DENSE_LAYERS = 2
BIAS_RATE = 1e-3        # assumed: the config gives use_expert_bias and no rate


def layer_kinds(n_layers, n_dense_layers, layer_types=LAYER_TYPES):
    """``(prefix_pattern, layer_pattern)`` of a stack cut to ``n_layers``:
    the first ``n_dense_layers`` published layers, then the published
    layers from the first expert layer on (index 2), which have to come out
    as whole periods of four."""
    def kind(name):
        return CONV if name == "conv" else (0, True)

    rest = layer_types[PUBLISHED_DENSE_LAYERS:][:n_layers - n_dense_layers]
    period = rest[:4]
    assert len(rest) == n_layers - n_dense_layers and len(rest) % 4 == 0 \
        and rest == period * (len(rest) // 4), (n_layers, rest)
    return (tuple(kind(t) for t in layer_types[:n_dense_layers]),
            tuple(kind(t) for t in period))


def lfm2_8b_a1b_config(n_layers=18, n_dense_layers=2, experts_held=32,
                       first_expert=0, vocab_size=65536, **kw):
    """LiquidAI/LFM2-8B-A1B ``config.json``.  Arguments: the depth (the
    leading dense layers and whole periods of four from published layer 2
    on; past 18 the published pattern is no whole period and is not
    supported), the leading dense layers, the experts this chip holds of
    the 32 and the first of them, the rows of the vocabulary it holds."""
    prefix, period = layer_kinds(n_layers, n_dense_layers)
    d = dict(vocab_size=vocab_size, hidden=2048, n_layers=n_layers,
             n_heads=32, n_kv_heads=8, head_width=64, ffn_hidden=1792,
             dense_ffn_hidden=7168, max_seq=128000, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-5, positions="rotary",
             rope_theta=1e6, layer_pattern=period, prefix_pattern=prefix,
             conv_taps=3, qk_norm="head", bias=False, tie_head=True,
             n_experts=32, experts_per_token=4, experts_held=experts_held,
             first_expert=first_expert, routing=moe.SIGMOID_BIASED,
             router_bias_rate=BIAS_RATE, expert_act="silu")
    d.update(kw)
    return TransformerConfig(**d)


def lfm2_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: one dense layer
    (a convolution) and one period (attention, three convolutions), 4 query
    heads on 2 key/value heads of 64 (a group of 2, both heads of a lane
    block on one key/value head) through the flash kernels in 16-row
    blocks, 8 experts of width 32 of which this share holds 2 (the second
    of four shares), top-2, float32."""
    return lfm2_8b_a1b_config(**dict(dict(
        n_layers=5, n_dense_layers=1, vocab_size=256, hidden=64, n_heads=4,
        n_kv_heads=2, ffn_hidden=32, dense_ffn_hidden=96, max_seq=64,
        n_experts=8, experts_per_token=2, experts_held=2, first_expert=2,
        dtype="float32", flash_block_q=16, flash_block_k=16), **kw))


build_lfm2_trainer = functools.partial(
    decoder.build_decoder_trainer, label="lfm2")
