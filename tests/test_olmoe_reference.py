"""The OLMoE decoder through the normal path (``models/olmoe.py``'s
configuration through ``parallel/decoder.py``, ``parallel/transformer.py``
and ``parallel/moe.py``) against the benchmark's
plain float32 reference (``benchmark/reference/olmoe_1b_7b.py``), on seeded
weights at ``olmoe_tiny_config``: 2 layers, hidden 64, 4 heads of 16, 8
experts of width 32, top-2, vocab 256, S = 32.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only): computing in bfloat16 moves the
loss by 1e-4 and fails it, as ``test_a_bfloat16_shortcut_...`` shows."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import olmoe_1b_7b as reference  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import olmoe  # noqa: E402
from paddle_tpu.monitor import devscope  # noqa: E402
from paddle_tpu.parallel import decoder, optim, transformer as T  # noqa: E402
from paddle_tpu.parallel.mesh import MeshSpec  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

B, S, TOL = 4, 32, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
         "norm_topk_prob": False, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001}
LEAVES = ("tok_emb", "lm_head", "lnf_scale", "ln1_scale", "ln2_scale", "wq",
          "wk", "wv", "wo", "q_norm", "k_norm", "router", "we_gate_up",
          "we_down")


def _trainer(dp=1, seed=3, **cfg):
    return olmoe.build_olmoe_trainer(
        olmoe.olmoe_tiny_config(**cfg), MeshSpec(dp=dp),
        optimizer=optim.adamw(), seed=seed, devices=jax.devices()[:dp])


def _ids(seed=0, n=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 256, (B, S)).astype(np.int32) for _ in range(n)]


def _seeded_params(tr):
    """The trainer's seeded weights with the norm scales moved off 1, so
    that a missing or misplaced scale shows."""
    rng = np.random.RandomState(11)

    def off_one(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "_norm" in name:
            return np.asarray(a) * rng.uniform(0.5, 1.5, a.shape).astype("f4")
        return np.asarray(a)

    return jax.tree_util.tree_map_with_path(off_one, tr.state["params"])


@pytest.fixture(scope="module")
def both():
    """Loss and gradients of program and reference on the same weights."""
    tr = _trainer()
    params = _seeded_params(tr)
    ids = _ids()[0]
    loss_fn = decoder.make_loss_fn(tr.cfg)
    got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"ids": jnp.asarray(ids)})))(params)
    want = jax.value_and_grad(
        lambda p: reference.forward(p, ids, MODEL, keep_logits=False)[0])(
            jax.tree.map(jnp.asarray, params))
    return tr.cfg, params, ids, got, want


def _leaf(tree, name):
    return tree[name] if name in tree else tree["params_layers"][name]


def test_loss_equals_the_reference(both):
    _, _, _, (got, _), (want, _) = both
    assert abs(float(got) - float(want)) / float(want) < TOL
    assert float(got) > np.log(256)       # the router losses are in it


def test_every_position_s_logits_equal_the_reference(both):
    cfg, params, ids, _, _ = both
    x, _ = jax.jit(lambda p, i: decoder.forward(p, i, cfg))(params, ids)
    got = T.rms_norm(x, params["lnf_scale"], cfg.norm_eps) @ params["lm_head"].T
    _, want = reference.forward(params, ids, MODEL)
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-4, atol=TOL)


@pytest.mark.parametrize("name", LEAVES)
def test_gradient_of_every_leaf_equals_the_reference(both, name):
    _, params, _, (_, got), (_, want) = both
    g, w = np.asarray(_leaf(got, name)), np.asarray(_leaf(want, name))
    assert g.shape == _leaf(params, name).shape and np.abs(w).max() > 0
    np.testing.assert_allclose(g, w, rtol=1e-4, atol=TOL * np.abs(w).max())


def test_the_leaves_tested_are_all_there_are(both):
    _, params, _, _, _ = both
    names = {re.findall(r"'(\w+)'", jax.tree_util.keystr(p))[-1]
             for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert names == set(LEAVES)


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


def test_the_reference_in_small_pieces_equals_itself_whole(both, monkeypatch):
    """At the tiny size a sequence is one block of rows, the head one chunk
    of columns.  Cut as the published size cuts them (several row blocks,
    chunks that do not divide the vocabulary, a last group of experts that
    is short), the reference gives the same loss, logits and gradient."""
    _, params, ids, _, (want, want_grad) = both
    params = jax.tree.map(jnp.asarray, params)
    _, whole = reference.forward(params, ids, MODEL)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)        # 4 blocks of 32
    monkeypatch.setattr(reference, "VOCAB_CHUNK", 100)      # 100, 100, 56
    monkeypatch.setattr(reference, "EXPERT_GROUP", 3)       # 3, 3, 2
    (loss, logits), grad = jax.value_and_grad(
        lambda p: reference.forward(p, ids, MODEL), has_aux=True)(params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-6
    np.testing.assert_allclose(np.stack(logits), np.stack(whole),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * np.abs(w).max())


def test_run_steps_over_three_batches_equals_three_steps():
    batches = [{"ids": i} for i in _ids(seed=5, n=3)]
    one, scan = _trainer(), _trainer()
    singly = [float(one.step(b, 1e-3)) for b in batches]
    scanned = scan.run_steps(
        stack_batches(scan.mesh, decoder.BATCH_SPECS, batches), 1e-3)
    np.testing.assert_allclose(scanned, singly, rtol=1e-5)
    assert singly[0] != singly[1]
    for a, b in zip(jax.tree.leaves(one.state["params"]),
                    jax.tree.leaves(scan.state["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_four_way_data_parallel_gives_the_one_device_loss():
    """The router's shares and means are over the dp-global batch, so the
    load-balance loss (not linear in them) is the same on any mesh."""
    batch = {"ids": _ids(seed=6)[0]}
    np.testing.assert_allclose(float(_trainer(dp=4).step(batch, 0.0)),
                               float(_trainer(dp=1).step(batch, 0.0)),
                               rtol=1e-5)


class _Unreadable:
    shape, size = (B, S), B * S

    def __array__(self, *a, **k):
        raise AssertionError("the ids were read back with no monitor on")


def test_moe_counter_and_gauge_only_under_a_monitor_session(tmp_path):
    tr = _trainer()
    assert monitor.active() is None
    tr._observe({"ids": _Unreadable()})         # off: nothing runs
    assert tr._probe_fn is None
    batches = [{"ids": i} for i in _ids(seed=8, n=2)]
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
        tr.run_steps(stack_batches(tr.mesh, decoder.BATCH_SPECS, batches), 1e-3)
        np.testing.assert_allclose(load.value, want, rtol=1e-6)
    finally:
        monitor.disable()


def test_the_compiled_step_s_moe_instructions_are_under_moe_and_router():
    tr = _trainer(remat=True)
    tr.run_steps(stack_batches(tr.mesh, decoder.BATCH_SPECS,
                               [{"ids": i} for i in _ids(n=2)]), 1e-3)
    names = devscope.scope_maps()["olmoe.run_steps"]
    got = {devscope.classify(op) for op in names.values()}
    for scope in ("moe", "router", "attention", "layer_norm", "lm_head",
                  "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
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
