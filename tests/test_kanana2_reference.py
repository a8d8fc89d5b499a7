"""The Kanana-2 decoder through the normal path (``models/kanana2.py`` over
``parallel/transformer.py``'s latent attention at full-rank queries with
rotary positions in adjacent pairs, the flash kernels' value-width mode in
interpret mode, the leading dense layer and ``parallel/moe.py``'s dropless
layer under biased sigmoid routing beside a shared expert) against the
benchmark's plain float32 reference
(``benchmark/reference/kanana_2_30b_a3b.py``), on seeded weights at
``kanana2_tiny_config`` on ONE device, where an expert-parallel configuration
is the all-held layer: three layers (dense, sparse, sparse), 4 heads of 128 +
64 against values of 128, 8 experts top-2, a shared expert, vocab 256.  The
exchange over four and two devices is ``test_kanana2_expert_parallel.py``'s,
the trainer's steps, scan, counters and scopes on the mesh
``test_kanana2_ep_trainer.py``'s.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only)."""

import jax
import numpy as np
import pytest

import decoder_reference as H
import kanana2_case as K
from benchmark.reference import kanana_2_30b_a3b as reference
from paddle_tpu.models import kanana2
from paddle_tpu.parallel import moe, transformer as T

S, TOL = 64, 1e-5


def _mechanism():
    cfg = kanana2.kanana2_tiny_config()
    assert cfg.latent and cfg.per_position and not cfg.run_scan
    assert cfg.prefix_kinds == cfg.layer_kinds == ((None, True),)
    assert (cfg.n_layers, cfg.n_periods, cfg.moe_layers) == (3, 2, 2)
    assert cfg.positions == "rotary" and cfg.q_lora_rank == 0
    assert (cfg.n_heads, cfg.head_dim, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim, cfg.kv_lora_rank) == (4, 192, 128, 64, 128, 32)
    assert (cfg.n_experts, cfg.experts_here, cfg.experts_per_token,
            cfg.shared_ffn_hidden, cfg.dense_ffn_hidden) == (8, 8, 2, 48, 96)
    assert cfg.expert_parallel and not cfg.experts_held
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.route_scale == 2.448
    big = kanana2.kanana2_30b_a3b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.head_dim,
            big.q_lora_rank, big.kv_lora_rank, big.qk_nope_dim,
            big.qk_rope_dim, big.v_head_dim, big.ffn_hidden,
            big.dense_ffn_hidden, big.shared_ffn_hidden, big.n_experts,
            big.experts_per_token, big.vocab_size, big.norm_eps,
            big.rope_theta, big.n_periods, big.router_bias_rate) == (
        48, 2048, 32, 192, 0, 512, 128, 64, 128, 768, 6144, 1536, 128, 6,
        128256, 1e-6, 1e6, 47, 1e-3)
    # a share without the exchange and the exchange exclude each other
    with pytest.raises(AssertionError):
        kanana2.kanana2_tiny_config(experts_held=2)
    with pytest.raises(AssertionError):     # the dense layer and a sparse one
        kanana2.kanana2_30b_a3b_config(n_layers=1)


def _shapes(both):
    params = both.params
    l0, p0 = params["prefix_layers"]["l0"], params["params_layers"]["p0"]
    assert l0["wq"].shape == (64, 4 * 192) and l0["w_gate_up"].shape == (
        64, 192)
    assert p0["wkv_a"].shape == (2, 64, 32 + 64)
    assert p0["wkv_b"].shape == (2, 32, 4 * (128 + 128))
    assert p0["we_gate_up"].shape == (2, 8, 64, 64)
    assert p0["ws_gate_up"].shape == (2, 64, 96)
    assert not {"wq_a", "q_a_norm", "wk", "wv"} & set(p0)
    assert params["router_bias"].shape == (2, 8)


def _specs(specs):
    p0 = specs["params_layers"]["p0"]
    assert p0["we_gate_up"] == p0["we_down"] == T.P(None, "dp")
    assert p0["router"] == T.P(None, None, None)
    assert specs["prefix_layers"]["l0"]["w_gate_up"] == T.P()


CASE = K.case(
    mechanism=_mechanism, spec_configs=({},), bfloat16=True,
    pieces={"QUERY_BLOCK": 16, "HEAD_GROUP": 1, "VOCAB_CHUNK": 100,
            "EXPERT_GROUP": 3, "DENSE_CHUNK": 20},
    pieces_hold=("loss",), witness=H.Witness(),
    also={"leaves": _shapes, "specs": _specs})
globals().update(H.common(CASE))


def test_on_one_device_the_field_compiles_to_the_all_held_layer(both):
    """With the field on and no axis to ride, and with the field off: the
    same loss program, text for text; no collective and no packing."""
    from paddle_tpu.parallel import decoder

    def text(field):
        loss = decoder.make_loss_fn(CASE.config(expert_parallel=field))
        return jax.jit(loss).lower(
            both.params, {"ids": jax.numpy.asarray(both.ids)}).as_text()

    assert text(True) == text(False)
    assert "all_to_all" not in text(True) and "all-to-all" not in text(True)


def test_the_witness_reads_every_sequence_first_and_last_row(witnessed):
    params, ids, program, model = witnessed
    big = reference.witness_positions(8192)
    assert len(big) == 64 and big[0] == 0 and big[-1] == 8191
    assert np.all(np.diff(big) > 0)
    at = reference.witness_positions(S)
    assert len(at) == S and at[0] == 0 and at[-1] == S - 1
    each = reference.position_errors(program, params, {"ids": ids}, model)
    assert each.shape == (ids.shape[0] * S,) and each.max() < TOL
    assert reference.logits_error(program, params, {"ids": ids}, model) \
        == float(np.quantile(each, 0.75))
    assert reference.sequence_errors(
        program, params, {"ids": ids}, model).shape == (ids.shape[0],)
