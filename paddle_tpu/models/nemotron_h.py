"""Nemotron-H-class hybrid decoder LM pretraining (NVIDIA
Nemotron-3-Nano-30B-A3B, 2025-12; HF ``model_type`` ``nemotron_h``; the
family: arXiv:2504.03624): a pre-norm decoder whose every layer is ONE
residual branch, ``x + branch(rms(x))``, the branch by
``hybrid_override_pattern`` a Mamba-2 mixer (``M``; Dao and Gu,
arXiv:2405.21060: 64 heads of 64 channels, one scalar decay a head, B and C
of 128 state cells shared by the 8 heads of a group, 4 taps, a gated group
norm), grouped-query attention (``*``: 32 query heads on 2 key/value heads of
128, NO positions added or rotated: the recurrence carries the order) or the
sparse feed-forward part (``E``: a sigmoid router over 128 experts, the 6 a
token chosen by score plus a bias that a step moves against the load and
weighted by their scores without it, renormalised, times 2.5; UNGATED
``relu^2`` experts of width 1,856 beside a shared one of width 3,712).  RMS
norms at eps 1e-5, no bias but the filter's, an untied head.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``single_branch``, a ``layer_pattern`` of MAMBA2, attention
and FFN positions, ``positions`` None, ``expert_gated`` off with
``expert_act`` ``relu2``, ``routing`` ``moe.SIGMOID_BIASED`` with
``route_scale``, ``shared_ffn_hidden``, ``experts_held``); forward, loss,
trainer and builder are ``parallel/decoder.py``'s.

A chip holds whole layers of the depth (a stage of a pipeline), a share of
each sparse layer's experts and a slice of the vocabulary's rows.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else.
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import FFN, MAMBA2, TransformerConfig

__all__ = ["PATTERN", "layer_kinds", "nemotron3_nano_30b_a3b_config",
           "nemotron_h_tiny_config", "build_nemotron_h_trainer"]

# Seeded weights (assumed; a trained model's are whatever its training left).
# The published ``rescale_prenorm_residual`` scales a residual branch's output
# projection by the published depth's inverse root at initialisation; here
# every branch's (the mixer's ``w_out``, attention's ``wo``, the experts' and
# the shared expert's down matrices) is seeded so, beside embedding rows
# N(0, 1): a router then reads the token's own row and small branch outputs.
# At the block's defaults every branch re-enters the stream at unit scale with
# a part that is the SAME for every token (the positive mean of ``relu^2``
# hidden rows, a slow state's running mean), the routers behind it rank the
# experts alike for every token, and the busiest expert of a sparse layer
# drew 3.6 to 6.4 times the mean with the biases at ZERO (8 to 11 at their
# default 0.1: the sixth of 128 sigmoid scores stands near 0.85, where 0.1
# of the score is most of a unit of the logit); seeded so, 1.4 to 2.1 times,
# and a share's 16 experts 11,796 to 12,532 pairs of the 12,288 that balance
# brings (PERF.md section 6, PR 52): the BALANCED case, the only one the
# benchmark's cell measures.
RESIDUAL_OUT_GAIN = 52 ** -0.5
ROUTER_BIAS_STD = 0.01
# what a step moves each selection bias by (the config has no key for it).
# A share ALONE trains its routers toward the experts it holds (only their
# outputs reach its loss: ROADMAP Reach 2), and here fast: at DeepSeek-V3's
# published 1e-3 the pairs that met a held expert rose from 12,300 to 30,000
# a layer in 50 steps of the cell, every sparse layer but the first passed
# the first static capacity inside the timed window and the step's time
# followed the seed by 3.3 %; at 3e-3 they stayed within 15,285 (capacity
# 15,360), at 5e-3 within the headroom (PERF.md section 6, PR 52)
ROUTER_BIAS_RATE = 5e-3

# the published ``hybrid_override_pattern``: 23 M, 23 E, 6 *
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_KINDS = {"M": MAMBA2, "E": FFN, "*": (0, False)}


def layer_kinds(pattern):
    """The layers' kinds of a stretch of the published pattern: ``M`` the
    Mamba-2 mixer, ``E`` the feed-forward part, ``*`` attention (full, no
    rotary)."""
    return tuple(_KINDS[c] for c in pattern)


def nemotron3_nano_30b_a3b_config(n_layers=52, first_layer=0, experts_held=0,
                                  first_expert=0, vocab_size=131072, **kw):
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``.
    Arguments: the published layers this chip holds (``n_layers`` from
    ``first_layer`` on, ONE period of the scan), the routed experts it holds
    (0: all 128) from ``first_expert`` on, and the rows of the vocabulary it
    holds.  What the published file does not carry is listed in
    ``benchmark/configs/nemotron3_nano_30b_a3b.json`` under ``assumed``."""
    d = dict(vocab_size=vocab_size, hidden=2688, n_layers=n_layers,
             n_heads=32, n_kv_heads=2, head_width=128, ffn_hidden=1856,
             shared_ffn_hidden=3712, max_seq=262144, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-5, positions=None,
             layer_pattern=layer_kinds(
                 PATTERN[first_layer:first_layer + n_layers]),
             single_branch=True, bias=False, tie_head=False,
             n_experts=128, experts_per_token=6, experts_held=experts_held,
             first_expert=first_expert, routing=moe.SIGMOID_BIASED,
             route_scale=2.5, router_bias_rate=ROUTER_BIAS_RATE,
             router_bias_std=ROUTER_BIAS_STD,
             residual_out_gain=RESIDUAL_OUT_GAIN, expert_act="relu2",
             expert_gated=False, d_inner=64 * 64, ssm_heads=64, ssm_groups=8,
             d_state=128, d_conv=4, scan_chunk=128)
    d.update(kw)
    return TransformerConfig(**d)


def nemotron_h_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: the five layers
    ``EM*EM`` (all three kinds, a mixer beside attention with nothing
    between), 4 query heads on 2 key/value heads of 128, 16 Mamba-2 heads of
    16 channels in 2 groups of 128 state cells (8 heads a group: one sublane
    tile of the scalars' rows), 4 taps, chunks of 16 under S = 64, 8 experts
    of width 192 (a multiple of 64 that is no multiple of 128) top-2 of
    which 4 are held, a shared expert of width 128, float32."""
    return nemotron3_nano_30b_a3b_config(**dict(dict(
        n_layers=5, vocab_size=256, hidden=64, n_heads=4, n_kv_heads=2,
        ffn_hidden=192, shared_ffn_hidden=128, max_seq=64, dtype="float32",
        layer_pattern=layer_kinds("EM*EM"), n_experts=8, experts_per_token=2,
        experts_held=4, d_inner=256, ssm_heads=16, ssm_groups=2, d_state=128,
        d_conv=4, scan_chunk=16), **kw))


build_nemotron_h_trainer = functools.partial(
    decoder.build_decoder_trainer, label="nemotron_h")
