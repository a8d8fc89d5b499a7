"""Solar-Open2-class hybrid decoder LM pretraining (upstage
Solar-Open2-250B, 2026-07; HF ``model_type`` ``solar_open2``, 250B-A15B): a
pre-norm decoder with RMS norms (eps 1e-5), no bias, no positions anywhere
and an untied head whose 48 layers come ONE softmax layer (``gqa_layers`` 0,
4, ..., 44) to THREE with Kimi Delta Attention, every layer over experts
(``first_k_dense_replace`` 0: the stack opens on attention and no layer's
FFN is dense).

A KDA layer (64 heads of 128, ``linear_attn_config``: keys and values 8,192
wide, twice the stream) is Kimi Linear's (``models/kimi_linear.py``,
``transformer.kda_mixer``, ``kernels/kda_chunk.py``, ``kernels/kda_rows.py``)
but for its write strength: ``kda_allow_neg_eigval`` true, ``beta = 2
sigmoid(h @ w_beta)`` in (0, 2) (``kda_beta_scale`` 2; Grazzi et al.,
arXiv:2411.12537), so that the state's transition along ``k_t``, ``1 -
beta_t``, lies in (-1, 1) and a write past 1 REFLECTS what the state held
along the key.  A softmax layer is grouped-query attention, 64 query heads on
8 key/value heads of 128, NOTHING rotated (``use_rope`` false: the
recurrence carries the order), the heads' output times ``sigmoid(h @ wz)``
element by element before ``wo`` (``use_gqa_gate``; ``attn_gate`` True,
``models/trinity.py``'s).  Every layer's FFN: 320 gated-SiLU experts of width
1,280 of which a token meets 8, beside ONE shared expert of 1,280: the 8
largest of ``sigmoid(logits) + bias``, weighted by the sigmoids without the
bias, renormalised, times 1; the bias is running state that the load moves
(``moe.balance_bias``) and no gradient reaches.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (a ``layer_pattern`` of one attention and three KDA positions
with NO ``prefix_pattern``, ``run_scan``, ``positions`` None, ``n_kv_heads``,
``attn_gate``, ``kda_beta_scale``, ``routing`` ``moe.SIGMOID_BIASED``,
``shared_ffn_hidden``, ``experts_held``); forward, loss, trainer and builder
are ``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer: ``experts_held`` of the 320 routed
experts from ``first_expert`` and a slice of the vocabulary.  Every share
computes the mixers and the shared expert; a sum over the shares counts the
shared expert once.

Seeded weights (assumed; a trained model's are whatever its training left):
as ``models/kimi_linear.py``'s, every branch's output projection at the
published depth's inverse root (48^-1/2) beside embedding rows N(0, 1), the
selection biases at 0.01 and moved 5e-3 a step (a share cell's rate until
the exchange exists): the BALANCED case, the only one the benchmark's cell
measures.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else (no auxiliary loss: the bias balances).
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import KDA, TransformerConfig

__all__ = ["PERIOD", "solar_open2_250b_config", "solar_open2_tiny_config",
           "build_solar_open2_trainer"]

# the published ``gqa_layers`` (0, 4, ..., 44; ``gqa_interval`` 3): a period
# is full attention without positions, then three KDA layers
PERIOD = ((0, False), KDA, KDA, KDA)
PUBLISHED_LAYERS = 48
RESIDUAL_OUT_GAIN = PUBLISHED_LAYERS ** -0.5
ROUTER_BIAS_STD = 0.01
# as ``models/kimi_linear.py``: ONE share alone trains its routers toward
# the experts it holds, and 5e-3 a step keeps the held pairs inside the
# first static capacity (PERF.md section 6, PRs 52, 58, 67)
ROUTER_BIAS_RATE = 5e-3


def solar_open2_250b_config(n_layers=48, experts_held=0, first_expert=0,
                            vocab_size=196608, **kw):
    """upstage/Solar-Open2-250B ``config.json``.  Arguments: the depth (the
    published layers 0 .. n_layers - 1, whole periods of four), the routed
    experts this chip holds (0: all 320) from ``first_expert`` on, the rows
    of the vocabulary it holds.  What the published file does not carry is
    listed in ``benchmark/configs/solar_open2_250b.json`` under
    ``assumed``."""
    assert n_layers >= len(PERIOD) and n_layers % len(PERIOD) == 0, \
        "whole periods of four from layer 0: %d" % n_layers
    d = dict(vocab_size=vocab_size, hidden=4096, n_layers=n_layers,
             n_heads=64, n_kv_heads=8, head_width=128, ffn_hidden=1280,
             shared_ffn_hidden=1280, max_seq=1048576, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-5, positions=None,
             layer_pattern=PERIOD, run_scan=True, bias=False,
             tie_head=False, attn_gate=True, kda_heads=64, kda_head_dim=128,
             kda_gate_rank=128, kda_chunk=64, kda_beta_scale=2.0, d_conv=4,
             n_experts=320, experts_per_token=8, experts_held=experts_held,
             first_expert=first_expert, routing=moe.SIGMOID_BIASED,
             route_scale=1.0, router_bias_rate=ROUTER_BIAS_RATE,
             router_bias_std=ROUTER_BIAS_STD,
             residual_out_gain=RESIDUAL_OUT_GAIN, expert_act="silu")
    d.update(kw)
    return TransformerConfig(**d)


def solar_open2_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: one period (full
    attention, KDA, KDA, KDA); 4 query heads on 2 key/value heads of the
    PUBLISHED 128 (a group of 2; 512 wide where the stream is 64) through
    the flash kernels in 16-row blocks, with the element-wise gate; 2 KDA
    heads of 16 with a gate rank of 8 in chunks of 16 under S = 64 (four
    chunks: the carry matters) at strengths in (0, 2); 8 experts of width 32
    top-2 of which 4 are held (the second of two shares), a shared expert of
    width 48, float32."""
    return solar_open2_250b_config(**dict(dict(
        n_layers=4, vocab_size=256, hidden=64, n_heads=4, n_kv_heads=2,
        ffn_hidden=32, shared_ffn_hidden=48, max_seq=64,
        kda_heads=2, kda_head_dim=16, kda_gate_rank=8, kda_chunk=16,
        n_experts=8, experts_per_token=2, experts_held=4, first_expert=4,
        router_bias_std=0.1, dtype="float32", flash_block_q=16,
        flash_block_k=16), **kw))


build_solar_open2_trainer = functools.partial(
    decoder.build_decoder_trainer, label="solar_open2")
