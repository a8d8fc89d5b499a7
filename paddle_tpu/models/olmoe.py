"""OLMoE-class sparse decoder LM pretraining (Muennighoff et al. 2024,
arXiv:2409.02060; HF ``model_type`` ``olmoe``): a pre-norm decoder block with
RMS norms, QK-norm over the whole projection, rotary positions, causal
attention without biases, and a top-8-of-64 DROPLESS mixture of gated-SiLU
experts in place of the FFN; an untied head.

The block is ``parallel/transformer.py``'s own, chosen by configuration
(``TransformerConfig.norm`` / ``positions`` / ``qk_norm`` / ``bias`` /
``tie_head`` / ``n_experts``), not a second layer function here: the norm,
the projections, the flash kernel, ``run_layers``' scan and remat and the
row-block head are the code BERT runs, so one change to them is measured on
both.  The MoE is ``parallel/moe.py``'s ``dropless_moe_ffn``; forward, loss,
trainer and builder are ``parallel/decoder.py``'s, as every decoder's.

batch dict: ``ids`` int32 [B, S] alone.  The loss is next-token cross
entropy and the router's auxiliary losses, mean over layers:
``ce + router_aux_coef * load_balance + router_z_coef * router_z``.
"""

import functools

from ..parallel import decoder
from ..parallel.transformer import TransformerConfig

__all__ = ["olmoe_1b_7b_config", "olmoe_tiny_config", "build_olmoe_trainer"]


def olmoe_1b_7b_config(**kw):
    """allenai/OLMoE-1B-7B-0125-Instruct ``config.json``; the two router
    coefficients are the paper's (HF ``router_aux_loss_coef`` 0.01, z-loss
    0.001).  Its ``norm_topk_prob`` is false, which is the one routing
    ``parallel/moe.py`` has: the top-k weights are not renormalised."""
    d = dict(vocab_size=50304, hidden=2048, n_layers=16, n_heads=16,
             ffn_hidden=1024, max_seq=4096, causal=True, dtype="bfloat16",
             norm="rms", norm_eps=1e-5, positions="rotary", rope_theta=10000.0,
             qk_norm=True, bias=False, tie_head=False, n_experts=64,
             experts_per_token=8, router_aux_coef=0.01,
             router_z_coef=0.001)
    d.update(kw)
    return TransformerConfig(**d)


def olmoe_tiny_config(**kw):
    """Tiny shapes for the CPU tests: 2 layers, 4 heads of 16, 8 experts of
    width 32, top-2, float32."""
    return olmoe_1b_7b_config(**dict(dict(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, ffn_hidden=32,
        max_seq=32, n_experts=8, experts_per_token=2, dtype="float32"), **kw))


build_olmoe_trainer = functools.partial(
    decoder.build_decoder_trainer, label="olmoe")
