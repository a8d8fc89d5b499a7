"""dots3-note-prev-class sparse decoder, the language model (dots-studio/
dots3-note-prev ``config.json``, ``model_type`` ``dots3_note``, 288B-A17B,
2026-08): a pre-norm decoder with RMS norms and no bias whose attention is
LATENT in every layer and of TWO SHAPES in one stack:

- a FULL layer (``layer_types[i] == "full_attention"``: published layers 0,
  1, 5, 9, ...): 128 heads of [128 without positions | 64 rotated] against
  values of 128, queries off a latent of 1,024 and keys and values off one
  of 512, ``rope_theta`` 8e7, and of a query's causal keys the 2,048 an
  INDEXER ranks best (64 heads of 128 whose queries come off the QUERY
  latent, one LayerNormed key head, both rotated over their first 64
  columns; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``);
- a SLIDING layer: 64 heads of [192 | 64] against values of 128, latents of
  1,024 and 1,024, ``swa_rope_theta`` 50,000, key s seen from t iff ``0 <= t
  - s < 513``, no indexer.

Each normed latent is multiplied by ``(hidden / rank)^(1/2)``
(``apply_mla_qkv_lora_rescale``), the rotated 64 of a KEY are one vector a
token that every head shares, rotary pairs are ADJACENT columns (the
convention of this repo's latent path, ``transformer.rope_pairs``; the
indexer's are rotate-half over its first 64 columns), and a HEAD-WISE
sigmoid gate (one scalar a head and token, ``attention_gate_type``) scales
each head's output before ``wo``, in both kinds.  Layer 0's FFN is a dense
gated SiLU one of width 13,824; every other layer has 256 gated-SiLU experts
of width 1,536 of which a token meets 8 (the largest of ``sigmoid(logits) +
bias``, weighted by the sigmoids without the bias over their sum,
``routed_scaling_factor`` 1) beside ONE shared expert that every token
meets.  The head is untied.

The loss is next-token cross entropy plus the indexer's own term, as
``models/keye_vl2.py`` has it: the mean over the layers THAT HAVE an indexer
and over tokens of ``KL(mean over the held heads of attention's
probabilities || softmax over the selected keys of I)``, coefficient 1, a
stop-gradient on the target and the indexer's inputs and none through the
selection (ASSUMED: the config carries no recipe).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration: the two shapes are two ``AttentionShape`` positions of one
pattern (each reads its OWN heads, ranks, widths, theta, window, indexer),
on ``latent_rescale``, ``attn_gate="head"``, ``indexer_query="latent"``,
``indexer_rope_dim``, ``heads_held`` / ``first_head`` beside ``experts_held``
/ ``first_expert``, ``prefix_pattern`` / ``dense_ffn_hidden``,
``shared_ffn_hidden`` and ``routing``; on the flash kernels' window mode and
``kernels/indexer.py``'s masked sweeps at a value width of their own and
``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss, trainer and
builder are ``parallel/decoder.py``'s.  The vision tower, the audio encoder
and the multi-token-prediction module are not here.

A chip may hold its SHARE of a layer: ``experts_held`` of the 256 routed
experts from ``first_expert``, a slice of the vocabulary, and, the first
share of this repo that does, its HEADS (``heads_held_share``: one in so
many of a layer's heads from ``first_head``'s share on, 32 of 128 and 16 of
64 at a quarter): ``wq_b``, ``wkv_b`` and ``wo`` hold the held heads'
columns and rows alone, the gate's ``wz`` is whole and read at the share's
columns, the latents' down-projections and norms, the indexer, the dense
FFN, the shared expert and the router are whole, and the branch adds its
heads' PARTIAL output to the stream.  Nothing stands in for the absent heads,
the all-reduce or the expert exchange.

Seeded weights (all ASSUMED): matrices N(0, 1 / fan_in); embedding rows N(0,
1) and every branch's output projection times 46^(-1/2) (the published
depth), so that the stream a router reads is the token's own row whatever
the cut; selection biases N(0, 0.01^2) and moved by 5e-5 a step
(``models/trinity.py``'s share cell: PERF.md section 6, PR 45).
"""

import functools

from ..monitor.devscope import MLA_DSA, MLA_SWA
from ..parallel import decoder, moe
from ..parallel.transformer import AttentionShape, TransformerConfig

__all__ = ["dots3_note_prev_config", "dots3_tiny_config",
           "build_dots3_trainer", "layer_kinds", "LAYER_TYPES", "WINDOW",
           "PUBLISHED_LAYERS"]

PUBLISHED_LAYERS = 46
WINDOW = 513
# the published ``layer_types``: layer 0 full, then (full, sliding x 3)
LAYER_TYPES = ("full_attention",) + tuple(
    "sliding_attention" if i % 4 else "full_attention"
    for i in range(PUBLISHED_LAYERS - 1))
ROUTER_BIAS_STD = 0.01
ROUTER_BIAS_RATE = 5e-5


def layer_kinds(n_layers, full, sliding, layer_types=LAYER_TYPES):
    """``(prefix_pattern, layer_pattern)`` of the published layers 0 ..
    ``n_layers`` - 1: layer 0 (full attention over the dense FFN), then
    whole periods of four from layer 1 on (full, sliding, sliding,
    sliding); ``full`` and ``sliding`` are the two kinds' positions."""
    kinds = [full if t == "full_attention" else sliding
             for t in layer_types[:n_layers]]
    period = kinds[1:5]
    assert len(kinds) == n_layers and (n_layers - 1) % 4 == 0 \
        and kinds[1:] == period * ((n_layers - 1) // 4), n_layers
    return tuple(kinds[:1]), tuple(period)


def dots3_note_prev_config(n_layers=45, experts_held=0, first_expert=0,
                           heads_held_share=1, first_head_share=0,
                           vocab_size=152064, window=WINDOW, full=None,
                           sliding=None, **kw):
    """dots-studio/dots3-note-prev ``config.json``, the language model.
    Arguments: the depth (layer 0 and whole periods of four: the published
    46 end on a lone full layer, which the scan over whole periods does not
    express, so the deepest stack here is 45); the routed experts this chip
    holds of the 256 (0: all) from ``first_expert`` on; the share of every
    layer's heads it holds, one in ``heads_held_share``, the
    ``first_head_share``-th of them; the rows of the vocabulary it holds;
    the window; ``full`` / ``sliding``: fields of the two kinds' shapes in
    the published ones' place (the tiny configuration's).  What the file
    names by key and not by formula is listed under ``assumed`` in
    ``benchmark/configs/dots3_note_prev.json``."""
    shapes = []
    for name, shape, own in (
            (MLA_DSA, dict(n_heads=128, q_lora_rank=1024, kv_lora_rank=512,
                           qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                           rope_theta=8e7), full),
            (MLA_SWA, dict(n_heads=64, q_lora_rank=1024, kv_lora_rank=1024,
                           qk_nope_dim=192, qk_rope_dim=64, v_head_dim=128,
                           rope_theta=5e4, window=window, indexer=False),
             sliding)):
        shape.update(own or {})
        heads = shape["n_heads"]
        assert heads % heads_held_share == 0, (heads, heads_held_share)
        held = heads // heads_held_share
        shapes.append(AttentionShape(
            rotary=True, heads_held=held, first_head=first_head_share * held,
            head_width=shape["qk_nope_dim"] + shape["qk_rope_dim"],
            scope=name, **shape))
    prefix, period = layer_kinds(n_layers, *shapes)
    # the configuration's own attention fields are the full layers': every
    # attention position reads its AttentionShape's
    of_full = {k: v for k, v in shapes[0].own().items()
               if k not in ("heads_held", "first_head")}
    d = dict(vocab_size=vocab_size, hidden=5120, n_layers=n_layers, **of_full,
             latent_rescale=True, attn_gate="head", ffn_hidden=1536,
             dense_ffn_hidden=13824, shared_ffn_hidden=1536,
             max_seq=524288, causal=True, dtype="bfloat16", norm="rms",
             norm_eps=1e-5, positions="rotary",
             prefix_pattern=prefix, layer_pattern=period, run_scan=True,
             bias=False, tie_head=False, indexer_heads=64, indexer_dim=128,
             indexer_topk=2048, indexer_rope_dim=64, indexer_query="latent",
             n_experts=256, experts_per_token=8, experts_held=experts_held,
             first_expert=first_expert, routing=moe.SIGMOID_BIASED,
             route_scale=1.0, router_bias_rate=ROUTER_BIAS_RATE,
             router_bias_std=ROUTER_BIAS_STD,
             residual_out_gain=PUBLISHED_LAYERS ** -0.5, expert_act="silu")
    d.update(kw)
    return TransformerConfig(**d)


def dots3_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: layer 0 (full,
    dense FFN of 96) and one period (full, sliding x 3); the full layers 8
    heads at the PUBLISHED widths 128 + 64 against values of 128 off latents
    of 32 and 16, the sliding ones 4 heads of 192 + 64 off latents of 24 and
    32 (other head counts, ranks and widths), of each the SECOND half held
    (4 and 2 heads); through the flash kernels in 16-row blocks, a window of
    17 (a key more than a block) under S = 64; an indexer of 4 heads of 128
    rotated over 64 columns that keeps 8 of a row's keys; 8 experts of width
    32 of which this share holds 2 (the second of four shares), top-2, a
    shared expert of width 48, float32."""
    return dots3_note_prev_config(**dict(dict(
        n_layers=5, vocab_size=256, hidden=64, window=17,
        heads_held_share=2, first_head_share=1,
        full=dict(n_heads=8, q_lora_rank=32, kv_lora_rank=16),
        sliding=dict(n_heads=4, q_lora_rank=24, kv_lora_rank=32),
        ffn_hidden=32,
        dense_ffn_hidden=96, shared_ffn_hidden=48, max_seq=64, n_experts=8,
        experts_per_token=2, experts_held=2, first_expert=2,
        router_bias_std=0.1, indexer_heads=4, indexer_topk=8,
        dtype="float32", flash_block_q=16, flash_block_k=16), **kw))


build_dots3_trainer = functools.partial(
    decoder.build_decoder_trainer, label="dots3")
