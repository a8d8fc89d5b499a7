"""Kimi-Linear-class hybrid decoder LM pretraining (Moonshot AI
Kimi-Linear-48B-A3B, 2025-10; HF ``model_type`` ``kimi_linear``; Kimi Linear,
arXiv:2510.26692): a pre-norm decoder with RMS norms (eps 1e-5), no bias and
an untied head whose layers come THREE with Kimi Delta Attention to ONE with
latent attention (``full_attn_layers`` 4, 8, ..., 24, 27 of 27).

A KDA layer (32 heads of 128, ``linear_attn_config``): q, k and v each
through a causal depthwise filter of 4 taps and ``silu``, q and k
L2-normalised a head, a log-decay for every CHANNEL of the key and a write
strength a head off low-rank gates, a [128, 128] state a head corrected by
the delta rule and carried along the sequence, a head-wise RMS norm and then
a sigmoid gate (``transformer.kda_mixer``, ``kernels/kda_chunk.py``,
``kernels/kda_rows.py``).  A
latent layer (``mla_use_nope``: NO positions anywhere, the recurrence
carries the order; ``q_lora_rank`` null: ONE query matrix): 32 heads of 128
+ 64 columns against keys ``[k_nope_i | k_s]`` off a latent of 512 whose 64
shared columns are the same in every head, values of 128, the softmax at
``192^(-1/2)`` (the packed flash kernels' value mode, q and k a head in 256
lanes).  The first layer's FFN is dense, width 9,216
(``first_k_dense_replace`` 1); every other layer 256 gated-SiLU experts of
width 1,024 of which a token meets 8, beside ONE shared expert: the 8 largest
of ``sigmoid(logits) + bias``, weighted by the sigmoids without the bias,
renormalised, times 2.446; the bias is running state that the load moves
(``moe.balance_bias``) and no gradient reaches.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``prefix_pattern`` / ``layer_pattern`` of KDA and attention
positions, ``run_scan``, the latent form with ``positions`` None and
``q_lora_rank`` 0 at widths 192 / 128, ``routing`` ``moe.SIGMOID_BIASED``
with ``route_scale``, ``shared_ffn_hidden``, ``experts_held``); forward,
loss, trainer and builder are ``parallel/decoder.py``'s.

The published depth is the leading dense layer and six and a HALF periods
(layers 26 and 27 are KDA, latent): the scan over whole periods does not
express the half, so the deepest stack here is 25 of 27, as
``models/trinity.py`` builds 58 of 60.

A chip may hold its SHARE of a layer: ``experts_held`` of the 256 routed
experts from ``first_expert`` and a slice of the vocabulary.  Every share
computes the mixers, the dense FFN and the shared expert; a sum over the
shares counts the shared expert once.

Seeded weights (assumed; a trained model's are whatever its training left):
every branch's output projection at the published depth's inverse root
beside embedding rows N(0, 1), as ``models/nemotron_h.py`` argues (a router
then reads the token's own row and small branch outputs), and the selection
biases at 0.01, as ``models/trinity.py`` argues for 8 of 256: the BALANCED
case, and the only one the benchmark's cell measures.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (no auxiliary loss: the bias balances).
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import KDA, TransformerConfig

__all__ = ["PERIOD", "layer_kinds", "kimi_linear_48b_a3b_config",
           "kimi_linear_tiny_config", "build_kimi_linear_trainer"]

# the published ``kda_layers`` / ``full_attn_layers``: layer 1 (the dense
# one) is KDA, then (KDA, KDA, latent, KDA) from layer 2 on
PERIOD = (KDA, KDA, (0, False), KDA)
PUBLISHED_LAYERS = 27
RESIDUAL_OUT_GAIN = PUBLISHED_LAYERS ** -0.5
ROUTER_BIAS_STD = 0.01
# what a step moves each selection bias by (the config has no key for it).
# DeepSeek-V3, whose routing rule this is, publishes 1e-3; ONE share alone
# trains its routers toward the experts it holds (only their outputs reach
# its loss), and at 1e-3 two of the cell's four sparse layers passed the
# first static capacity within twenty steps (PERF.md section 6, PR 58; the
# same finding and the same rate as ``models/nemotron_h.py``)
ROUTER_BIAS_RATE = 5e-3


def layer_kinds(n_layers):
    """``(prefix_pattern, layer_pattern)`` of the published layers 1 ..
    ``n_layers``: the leading dense KDA layer, then whole periods."""
    assert n_layers >= 5 and (n_layers - 1) % len(PERIOD) == 0, \
        "the leading layer and whole periods of four: %d" % n_layers
    return (KDA,), PERIOD


def kimi_linear_48b_a3b_config(n_layers=25, experts_held=0, first_expert=0,
                               vocab_size=163840, **kw):
    """moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``.  Arguments:
    the depth (the published layers 1 .. n_layers: the dense layer and whole
    periods, 25 at most), the routed experts this chip holds (0: all 256)
    from ``first_expert`` on, the rows of the vocabulary it holds.  What the
    published file does not carry is listed in
    ``benchmark/configs/kimi_linear_48b_a3b.json`` under ``assumed``."""
    prefix, period = layer_kinds(n_layers)
    d = dict(vocab_size=vocab_size, hidden=2304, n_layers=n_layers,
             n_heads=32, head_width=192, ffn_hidden=1024,
             dense_ffn_hidden=9216, shared_ffn_hidden=1024, max_seq=1048576,
             causal=True, dtype="bfloat16", norm="rms", norm_eps=1e-5,
             positions=None, prefix_pattern=prefix, layer_pattern=period,
             run_scan=True, bias=False, tie_head=False, q_lora_rank=0,
             kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
             v_head_dim=128, kda_heads=32, kda_head_dim=128,
             kda_gate_rank=128, kda_chunk=64, d_conv=4, n_experts=256,
             experts_per_token=8, experts_held=experts_held,
             first_expert=first_expert, routing=moe.SIGMOID_BIASED,
             route_scale=2.446, router_bias_rate=ROUTER_BIAS_RATE,
             router_bias_std=ROUTER_BIAS_STD,
             residual_out_gain=RESIDUAL_OUT_GAIN, expert_act="silu")
    d.update(kw)
    return TransformerConfig(**d)


def kimi_linear_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: the five layers
    KDA (dense FFN), KDA, KDA, latent, KDA; 2 KDA heads of 16 with a gate
    rank of 8 in chunks of 16 under S = 64 (four chunks: the carry matters);
    2 latent heads at the PUBLISHED widths 128 + 64 against values of 128
    (whole lane blocks: the packed flash kernels' value mode, in 16-row
    blocks) off a latent of 32; 8 experts of width 32 top-2 of which 4 are
    held (the second of two shares), a shared expert of width 48, a dense
    FFN of 96, float32."""
    return kimi_linear_48b_a3b_config(**dict(dict(
        n_layers=5, vocab_size=256, hidden=64, n_heads=2, ffn_hidden=32,
        dense_ffn_hidden=96, shared_ffn_hidden=48, max_seq=64,
        kv_lora_rank=32, kda_heads=2, kda_head_dim=16, kda_gate_rank=8,
        kda_chunk=16, n_experts=8, experts_per_token=2, experts_held=4,
        first_expert=4, router_bias_std=0.1, dtype="float32",
        flash_block_q=16, flash_block_k=16), **kw))


build_kimi_linear_trainer = functools.partial(
    decoder.build_decoder_trainer, label="kimi_linear")
