"""The OLMoE decoder through the normal path (``models/olmoe.py``'s
configuration through ``parallel/decoder.py``, ``parallel/transformer.py``
and ``parallel/moe.py``) against the benchmark's
plain float32 reference (``benchmark/reference/olmoe_1b_7b.py``), on seeded
weights at ``olmoe_tiny_config``: 2 layers, hidden 64, 4 heads of 16, 8
experts of width 32, top-2, vocab 256, S = 32.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only): computing in bfloat16 moves the
loss by 1e-4 and fails it, as ``test_a_bfloat16_shortcut_...`` shows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import olmoe_1b_7b as reference
from paddle_tpu import monitor
from paddle_tpu.models import olmoe
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, transformer as T
from paddle_tpu.parallel.mesh import MeshSpec

B, S, TOL = 4, 32, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
         "norm_topk_prob": False, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}
LEAVES = ("tok_emb", "lm_head", "lnf_scale", "ln1_scale", "ln2_scale", "wq",
          "wk", "wv", "wo", "q_norm", "k_norm", "router", "we_gate_up",
          "we_down")
CASE = H.Case(
    "olmoe", reference, MODEL, LEAVES, B=B, S=S, loss_floor=np.log(256),
    # several row blocks (4 of 8), chunks that do not divide the vocabulary
    # (100, 100, 56), a last group of experts that is short (3, 3, 2)
    pieces={"QUERY_BLOCK": 8, "VOCAB_CHUNK": 100, "EXPERT_GROUP": 3},
    pieces_hold=("loss", "logits", "grads"), steps=3, trained_cfg={})
globals().update(H.common(CASE))


def test_a_bfloat16_shortcut_would_fail_the_tolerance(both):
    cfg, params, ids, (f32_loss, _), _ = both
    bf16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
        jax.tree.map(jnp.asarray, params))
    low = decoder.make_loss_fn(olmoe.olmoe_tiny_config(dtype="bfloat16"))(
        bf16, {"ids": jnp.asarray(ids)})
    assert abs(float(low) - float(f32_loss)) / float(f32_loss) > 5 * TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_s_faults_move_its_loss(both, fault):
    """The switches ``benchmark/tools/olmoe_ref_sensitivity.py`` throws at
    the published sizes do something at the tiny one too."""
    _, params, ids, _, (want, _) = both
    bad = reference.loss(params, {"ids": ids}, MODEL, faults=(fault,))
    assert abs(bad - float(want)) / float(want) > 10 * TOL


def test_four_way_data_parallel_gives_the_one_device_loss():
    """The router's shares and means are over the dp-global batch, so the
    load-balance loss (not linear in them) is the same on any mesh."""
    batch = {"ids": H.ids(CASE, seed=6)[0]}
    np.testing.assert_allclose(float(H.trainer(CASE, dp=4).step(batch, 0.0)),
                               float(H.trainer(CASE, dp=1).step(batch, 0.0)),
                               rtol=1e-5)


def test_moe_counter_and_gauge_only_under_a_monitor_session(tmp_path):
    tr = H.trainer(CASE)
    assert monitor.active() is None
    tr._observe({"ids": H.Unreadable(CASE)})         # off: nothing runs
    assert tr._probe_fn is None
    batches = [{"ids": i} for i in H.ids(CASE, seed=8, n=2)]
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        load = mon.registry.gauge("monitor.train.moe_load_max_over_mean")
        tr.step(batches[0], 1e-3)
        # the token-slots a step routes: tokens x top-2 x 2 layers
        assert batches[0]["ids"].size * tr.cfg.experts_per_token \
            * tr.cfg.moe_layers == B * S * 2 * 2
        first = load.value
        assert 1.0 <= first <= 8.0
        # the gauge is the busiest expert over the mean, largest over layers,
        # of the call's first batch at the weights the call starts from
        _, aux = decoder.forward(tr.state["params"],
                                jnp.asarray(batches[0]["ids"]), tr.cfg)
        want = float(jnp.max(aux["load_max_over_mean"]))
        tr.run_steps(H.staged(tr, batches), 1e-3)
        np.testing.assert_allclose(load.value, want, rtol=1e-6)
    finally:
        monitor.disable()


def test_the_compiled_step_s_moe_instructions_are_under_moe_and_router():
    tr = H.trainer(CASE, remat=True)
    tr.run_steps(H.staged(tr, [{"ids": i} for i in H.ids(CASE, n=2)]), 1e-3)
    names = H.scope_map(tr)
    got = {devscope.classify(op) for op in names.values()}
    for scope in ("moe", "router", "attention", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("recompute", "moe") in got and ("optimizer", "optimizer") in got
    assert ("forward", "mlp") not in got        # no dense FFN in this block
    by_scope = {}
    for op in names.values():
        by_scope.setdefault(devscope.classify(op)[1], []).append(op)
    # the CPU compiler expands ragged_dot into masked dot_generals; the
    # sort of the assignments and the top-k are their own instructions
    assert any(op.endswith("/sort") for op in by_scope["moe"])
    assert any("dot_general" in op for op in by_scope["moe"])
    assert any("top_k" in op for op in by_scope["router"])
    assert not any(op.endswith("/sort") or "top_k" in op
                   for scope, ops in by_scope.items()
                   if scope not in ("moe", "router", "lm_head") for op in ops)


def test_published_config_and_its_parameter_count():
    cfg = olmoe.olmoe_1b_7b_config()
    assert (cfg.hidden, cfg.n_heads, cfg.head_dim, cfg.n_layers) == (2048, 16, 128, 16)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.ffn_hidden) == (64, 8, 1024)
    assert (cfg.vocab_size, cfg.max_seq, cfg.norm_eps, cfg.rope_theta) == (
        50304, 4096, 1e-5, 10000.0)
    assert cfg.causal and cfg.qk_norm and not (cfg.bias or cfg.tie_head)
    shapes = jax.eval_shape(lambda: T.init_transformer_params(
        jax.random.PRNGKey(0), olmoe.olmoe_1b_7b_config(n_layers=3)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    layer = sum(int(np.prod(a.shape[1:]))
                for a in jax.tree.leaves(shapes["params_layers"]))
    assert round(layer / 1e6, 1) == 419.6 and round(count / 1e9, 3) == 1.465
    assert "pos_emb" not in shapes and "lnf_bias" not in shapes
    assert shapes["params_layers"]["we_gate_up"].dtype == jnp.bfloat16
    assert shapes["params_layers"]["router"].dtype == jnp.float32


def test_unsharded_parts_refuse_a_tensor_parallel_mesh():
    with pytest.raises(AssertionError):
        olmoe.olmoe_tiny_config(tp=2)
    with pytest.raises(AssertionError):
        olmoe.build_olmoe_trainer(olmoe.olmoe_tiny_config(), MeshSpec(pp=2),
                                  devices=jax.devices()[:2])


def test_rotary_embedding_is_the_rotate_half_rotation():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 4 * 8).astype("f4")            # 4 heads of 8
    got = np.asarray(T.rope(jnp.asarray(x), 4, 10000.0)).reshape(2, 6, 4, 8)
    xs = x.reshape(2, 6, 4, 8)
    for pos in range(6):
        for i in range(4):                              # pair (i, i + 4)
            a = pos * 10000.0 ** (-2 * i / 8)
            c, s = np.cos(a), np.sin(a)
            np.testing.assert_allclose(
                got[:, pos, :, i], xs[:, pos, :, i] * c - xs[:, pos, :, i + 4] * s,
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[:, pos, :, i + 4], xs[:, pos, :, i + 4] * c + xs[:, pos, :, i] * s,
                rtol=1e-5, atol=1e-6)
    # position 0 is the identity and the norm of every pair is kept
    np.testing.assert_allclose(got[:, 0], xs[:, 0], rtol=1e-6)


def test_rms_norm_in_float32_whatever_the_input():
    x = np.random.RandomState(2).randn(5, 64).astype("f4") * 3
    g = np.linspace(0.5, 1.5, 64).astype("f4")
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(T.rms_norm(jnp.asarray(x), g, 1e-5), want,
                               rtol=1e-5)
    low = T.rms_norm(jnp.asarray(x, jnp.bfloat16), g, 1e-5)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, "f4"), want, rtol=2e-2)
