"""Kanana-2-class sparse decoder LM pretraining (Kakao
kanana-2-30b-a3b-instruct-2601 ``config.json``, ``model_type``
``deepseek_v3``, 2025-12 / 2026-01; 30B-A3B): a pre-norm decoder with RMS
norms (eps 1e-6) and no bias whose every layer has multi-head LATENT
attention, whose first layer has a dense gated-SiLU FFN and whose every
other layer has, beside top-6 of 128 routed gated-SiLU experts of width 768,
two shared experts (one FFN of 1,536) that every token meets; an untied
head.

Latent attention, of the normed stream n [S, 2,048]: queries at FULL rank
(``q_lora_rank`` null: ``q = n wq``, 32 heads of 192), keys and values off
ONE latent of 512 (``wkv_a``, an RMS norm, ``wkv_b``: 128 position-free key
columns and 128 value columns a head).  The last 64 columns of every query
head and ONE 64-wide vector a token (64 more columns of ``wkv_a``) that all
32 heads share as the rest of their key are rotated in the adjacent-pair
convention (``rope_interleave``; theta 1e6, no scaling); the softmax scale
is 192^(-1/2).

Routing (``noaux_tc``, ``n_group`` = ``topk_group`` = 1: no group limit):
``s = sigmoid(n W_r)`` in float32; the 6 largest of ``s + b`` (b the
selection bias, which no gradient reaches and which a step moves against the
GLOBAL load, ``moe.balance_bias``); weights ``2.448 s_e / sum of the chosen
s`` (``norm_topk_prob``, ``routed_scaling_factor``).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``kv_lora_rank`` and the other latent sizes, ``prefix_pattern``
and ``dense_ffn_hidden``, ``shared_ffn_hidden``, ``routing``,
``route_scale``, ``expert_parallel``), on the flash kernels' packed causal
mode and ``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss, trainer
and builder are ``parallel/decoder.py``'s.

EXPERT-PARALLEL (``expert_parallel``, on by default): the routed experts
ride the mesh's ``dp`` axis.  On ``MeshSpec(dp=4)`` chip c holds experts
32 c .. 32 c + 31 of every sparse layer; for its own sequences it routes over
all 128, sends each (token, expert) pair's row to the chip that holds the
expert (``lax.all_to_all``), computes the rows it receives, sends the
results back and sums a token's six: to rounding what one device holding all
128 gives, and no pair is dropped whatever the routing.  Attention, norms,
router, selection bias, shared experts, the dense FFN, embedding and head are
replicated and data-parallel.  At ``dp`` 1 (or with the field off) it is the
all-held layer: no collective, no packing.  This is the model that fits ONE
v5e host whole a layer: a sparse layer is 640 M parameters, 5.1 GB at this
repository's 8 bytes a parameter, so no one chip holds the guide's floor of
five layers, and four chips that share each layer hold 187 M of it each.

Seeded weights (all ASSUMED, as ``models/mistral4.py``'s and
``models/kimi_linear.py``'s): matrices N(0, 1 / fan_in); embedding rows N(0,
1) and every branch's output projection times 48^(-1/2) (the published
depth), so that the stream a router reads is the token's own row whatever
the cut (the BALANCED case; ``models/mistral4.py`` says why); selection
biases N(0, 0.01^2), moved by 1e-3 a step (DeepSeek-V3's report; the config
has no key for either).

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy, the mean over the GLOBAL batch's positions, and nothing else (the
published configuration carries no auxiliary coefficient).
"""

import functools

from ..parallel import decoder, moe
from ..parallel.transformer import TransformerConfig

__all__ = ["kanana2_30b_a3b_config", "kanana2_tiny_config",
           "build_kanana2_trainer", "PUBLISHED_LAYERS"]

PUBLISHED_LAYERS = 48
ROUTER_BIAS_STD = 0.01
ROUTER_BIAS_RATE = 1e-3
FULL_ROTARY = (0, True)         # a full-attention layer with rotary positions


def kanana2_30b_a3b_config(n_layers=PUBLISHED_LAYERS, vocab_size=128256,
                           **kw):
    """kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``.  Arguments:
    the depth (published layers 0 .. n_layers - 1: the leading dense layer
    and the sparse ones behind it) and the vocabulary's rows.  What the
    published file does not carry is listed in
    ``benchmark/configs/kanana_2_30b_a3b.json`` under ``assumed``."""
    assert n_layers >= 2, "the leading dense layer and a sparse one"
    d = dict(vocab_size=vocab_size, hidden=2048, n_layers=n_layers,
             n_heads=32, head_width=192, ffn_hidden=768,
             dense_ffn_hidden=6144, shared_ffn_hidden=1536, max_seq=32768,
             causal=True, dtype="bfloat16", norm="rms", norm_eps=1e-6,
             positions="rotary", rope_theta=1e6,
             prefix_pattern=(FULL_ROTARY,), layer_pattern=(FULL_ROTARY,),
             bias=False, tie_head=False, q_lora_rank=0, kv_lora_rank=512,
             qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, n_experts=128,
             experts_per_token=6, expert_parallel=True,
             routing=moe.SIGMOID_BIASED, route_scale=2.448,
             router_bias_rate=ROUTER_BIAS_RATE,
             router_bias_std=ROUTER_BIAS_STD,
             residual_out_gain=PUBLISHED_LAYERS ** -0.5, expert_act="silu")
    d.update(kw)
    return TransformerConfig(**d)


def kanana2_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: three layers (the
    dense one and two sparse), 4 heads at the PUBLISHED widths 128 + 64
    against values of 128 (whole lane blocks: the packed flash kernels' value
    mode, in 16-row blocks) off a latent of 32, 8 experts of width 32 top-2
    (two a device on four: selection biases seeded with 0.1, so that the
    second of 8 scores stands where the sigmoid is steep), a shared expert of
    width 48, a dense FFN of 96, float32."""
    return kanana2_30b_a3b_config(**dict(dict(
        n_layers=3, vocab_size=256, hidden=64, n_heads=4, ffn_hidden=32,
        dense_ffn_hidden=96, shared_ffn_hidden=48, max_seq=64,
        kv_lora_rank=32, n_experts=8, experts_per_token=2,
        router_bias_std=0.1, dtype="float32", flash_block_q=16,
        flash_block_k=16), **kw))


build_kanana2_trainer = functools.partial(
    decoder.build_decoder_trainer, label="kanana2")
