"""Ouro-class LOOPED decoder LM pretraining (ByteDance Seed and others,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741; the
Ouro 1.4B / 2.6B LoopLM, HF ``model_type`` ``ouro``, 2025-10): a dense
decoder (RMS norms on each branch's input AND output, no bias, 16 heads of
128 on as many key/value heads, rotate-half rotary positions at theta 1e6, a
gated-SiLU FFN of width 5,632 in every layer, an untied head) whose WHOLE
stack is applied ``total_ut_steps`` = 4 times to the stream in a step, every
pass over the same leaves.  The model's one final norm stands at the end of
every pass and its output is what the next pass reads; after every pass a
one-column exit gate ``lam_t = sigmoid(h_t . w_e + b_e)`` and the one head
read that normed state.  A token's exit distribution is ``p_t = lam_t
prod_{j<t} (1 - lam_j)``, the last exit taking what is left, and the Stage-I
training loss is the exits' cross entropies weighted by it, less ``beta``
times its entropy: ``sum_t p_t nll_t - beta H(p)``, gradient through ``p``
into the gate and the stack.

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``loop_passes``, ``exit_entropy_coef``, ``post_norm``,
``dense_ffn_hidden`` without experts on the one-tree stack, ``head_width``,
``tie_head``); forward, loss, trainer and builder are
``parallel/decoder.py``'s.  What is not run: adaptive exit at serving
(``early_exit_threshold``) and Stage II, the gate trained alone against a
frozen model.

batch dict: ``ids`` int32 [B, S] alone.
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import TransformerConfig

__all__ = ["ouro_2_6b_config", "ouro_tiny_config", "build_ouro_trainer"]


def ouro_2_6b_config(n_layers=48, vocab_size=49152, **kw):
    """ByteDance/Ouro-2.6B ``config.json``.  Arguments: the depth and the
    rows of the vocabulary this chip holds.  What the published file has no
    key for (the output norms, the gate on the normed state, ``beta`` = 0.1)
    ``benchmark/configs/ouro_2_6b.json`` lists under ``assumed``."""
    d = dict(vocab_size=vocab_size, hidden=2048, n_layers=n_layers,
             n_heads=16, head_width=128, ffn_hidden=5632,
             dense_ffn_hidden=5632, max_seq=65536, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-6, positions="rotary",
             rope_theta=1e6, bias=False, tie_head=False, expert_act="silu",
             post_norm=True, loop_passes=4, exit_entropy_coef=0.1)
    d.update(kw)
    return TransformerConfig(**d)


def ouro_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: two layers, 4
    heads of 16, a gated FFN of width 96, THREE passes (a first, a middle
    and a last exit, which differ), float32."""
    return ouro_2_6b_config(**dict(dict(
        n_layers=2, vocab_size=256, hidden=64, n_heads=4, head_width=16,
        ffn_hidden=96, dense_ffn_hidden=96, max_seq=64, dtype="float32",
        loop_passes=3), **kw))


build_ouro_trainer = functools.partial(
    decoder.build_decoder_trainer, label="ouro")
