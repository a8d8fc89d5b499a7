"""Brumby-class attention-free decoder LM pretraining (Manifest AI
Brumby-14B-Base, 2025-10; HF ``model_type`` ``brumby``): a dense pre-norm
decoder on the Qwen3-14B skeleton (RMS norms, no bias, 40 query heads on 8
key/value heads of 128, q and k RMS-normed per head, rotate-half rotary
positions, a gated-SiLU FFN of width 17,408 in every layer, an untied head)
whose every layer replaces softmax attention by POWER RETENTION of degree 2
(Buckman, Gelada et al., arXiv:2507.04239): the weight of key j for query t
is ``(q_t . k_j / sqrt(dh))^2`` times ``exp`` of the summed log-decays
between them, ``logsigmoid(u_t @ wg)`` a token and key/value head; the
output is the weighted sum of the values over the sum of the weights.  The
same as a recurrence on a state of 8,256 x 128 numbers a key/value head,
which is how it runs (``kernels/power_retention.py``, chunk by chunk).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``layer_pattern`` of one RETENTION position,
``dense_ffn_hidden`` without experts, ``qk_norm="head"``, ``n_kv_heads``,
``tie_head``); forward, loss, trainer and builder are
``parallel/decoder.py``'s.

A chip may hold its SHARE of a layer: a slice of the vocabulary (a smaller
vocabulary: ids, logits and loss are over the slice).  Retention and the FFN
are whole here; the chips that share a layer run their own sequences.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else.
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import RETENTION, TransformerConfig

__all__ = ["brumby_14b_config", "brumby_tiny_config", "build_brumby_trainer"]


def brumby_14b_config(n_layers=40, vocab_size=151936, **kw):
    """manifestai/Brumby-14B-Base ``config.json``.  Arguments: the depth and
    the rows of the vocabulary this chip holds.  The operator's own sizes
    (degree 2, one gate a key/value head, the chunk length) are not in the
    published file: ``benchmark/configs/brumby_14b.json`` lists each under
    ``assumed``."""
    d = dict(vocab_size=vocab_size, hidden=5120, n_layers=n_layers,
             n_heads=40, n_kv_heads=8, head_width=128, ffn_hidden=17408,
             dense_ffn_hidden=17408, max_seq=32768, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-6, positions="rotary",
             rope_theta=1e6, layer_pattern=(RETENTION,), qk_norm="head",
             bias=False, tie_head=False, expert_act="silu",
             retention_chunk=1024)
    d.update(kw)
    return TransformerConfig(**d)


def brumby_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: two layers, 10
    query heads on 2 key/value heads of 128 (a group of 5; 1,280 wide where
    the hidden size is 64), chunks of 16 under S = 64 (4 chunks), a gated
    FFN of width 96, float32."""
    return brumby_14b_config(**dict(dict(
        n_layers=2, vocab_size=256, hidden=64, n_heads=10, n_kv_heads=2,
        ffn_hidden=96, dense_ffn_hidden=96, max_seq=64, dtype="float32",
        retention_chunk=16), **kw))


build_brumby_trainer = functools.partial(
    decoder.build_decoder_trainer, label="brumby")
