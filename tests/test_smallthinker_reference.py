"""The SmallThinker decoder through the normal path (``models/smallthinker.py``
over ``parallel/transformer.py``, ``parallel/moe.py`` and the flash kernels'
grouped and windowed modes) against the benchmark's plain float32 reference
(``benchmark/reference/smallthinker_21b_a3b.py``), on seeded weights at
``smallthinker_tiny_config``: one period of 4 layers (a position-free full
layer, three rotary ones with a window of 24), hidden 64, 6 query heads on 2
key/value heads of 128, 8 experts of width 32 of which this share holds 2,
top-2, vocab 256, S = 64 (above the window).

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only): computing in bfloat16 moves the
loss by more and fails it, as ``test_a_bfloat16_shortcut_...`` shows."""

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

from benchmark.reference import smallthinker_21b_a3b as reference  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.kernels.flash_attention import kv_blocks  # noqa: E402
from paddle_tpu.models import bert, olmoe, smallthinker  # noqa: E402
from paddle_tpu.monitor import devscope  # noqa: E402
from paddle_tpu.parallel import (decoder, moe, optim,  # noqa: E402
                                 transformer as T)
from paddle_tpu.parallel.mesh import MeshSpec  # noqa: E402
from paddle_tpu.parallel.train import stack_batches  # noqa: E402

B, S, TOL = 2, 64, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 6, "num_key_value_heads": 2,
         "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
         "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
         "sliding_window_size": 24, "moe_num_active_primary_experts": 2,
         "moe_num_primary_experts": 2, "moe_router_width": 8,
         "moe_first_expert_held": 2}
LEAVES = ("tok_emb", "lm_head", "lnf_scale", "ln1_scale", "ln2_scale", "wq",
          "wk", "wv", "wo", "router", "we_gate_up", "we_down")


def _trainer(seed=3, **cfg):
    return smallthinker.build_smallthinker_trainer(
        smallthinker.smallthinker_tiny_config(**cfg), MeshSpec(dp=1),
        optimizer=optim.adamw(), seed=seed, devices=jax.devices()[:1])


def _ids(seed=0, n=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 256, (B, S)).astype(np.int32) for _ in range(n)]


def _seeded_params(tr):
    """The trainer's seeded weights with the norm scales moved off 1, so
    that a missing or misplaced scale shows, and a router steep enough that
    the top-2 weights are not all one half."""
    rng = np.random.RandomState(11)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return np.asarray(a) * rng.uniform(0.5, 1.5, a.shape).astype("f4")
        return np.asarray(a) * (3.0 if "router" in name else 1.0)

    return jax.tree_util.tree_map_with_path(moved, tr.state["params"])


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


def test_the_tiny_configuration_keeps_every_mechanism():
    cfg = smallthinker.smallthinker_tiny_config()
    assert cfg.n_heads // cfg.kv_heads == 3
    assert cfg.n_heads * cfg.head_dim != cfg.hidden
    assert cfg.layer_kinds == ((None, False),) + ((24, True),) * 3
    assert 24 < S and 24 % cfg.flash_block_k           # edge blocks masked
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert) == (8, 2, 2)
    assert T._packed_flash_blocks(cfg, 6, S, 2) == (16, 16)   # the kernels run
    big = smallthinker.smallthinker_21b_a3b_config()
    assert big.layer_kinds == ((None, False),) + ((4096, True),) * 3
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.n_experts,
            big.experts_per_token, big.experts_here, big.vocab_size) == (
        52, 2560, 28, 4, 128, 768, 64, 6, 64, 151936)


def test_loss_equals_the_reference(both):
    _, _, _, (got, _), (want, _) = both
    assert abs(float(got) - float(want)) / float(want) < TOL


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
    """The same program in bfloat16 against the float32 one: its logits miss
    the tolerance the logits test holds by orders (the scalar loss, a mean
    over 126 positions, hides most of it: these tests hold more than the
    loss for that reason)."""
    cfg, params, ids, _, _ = both
    low_cfg = smallthinker.smallthinker_tiny_config(dtype="bfloat16")
    bf16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
        jax.tree.map(jnp.asarray, params))

    def logits(c, p):
        x, _ = decoder.forward(p, jnp.asarray(ids), c)
        return (T.rms_norm(x, p["lnf_scale"], c.norm_eps).astype(jnp.float32)
                @ p["lm_head"].T.astype(jnp.float32))

    off = np.abs(np.asarray(logits(low_cfg, bf16))
                 - np.asarray(logits(cfg, jax.tree.map(jnp.asarray, params))))
    assert off.max() > 100 * TOL and np.median(off) > 10 * TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_s_faults_move_its_loss(both, fault):
    """The switches ``benchmark/tools/smallthinker_ref_sensitivity.py``
    throws at the published sizes do something at the tiny one too."""
    _, params, ids, _, (want, _) = both
    bad = reference.loss(params, {"ids": ids}, MODEL, faults=(fault,))
    assert abs(bad - float(want)) / float(want) > 2 * TOL


@pytest.fixture(scope="module")
def program_logits(both):
    """The trainer's own logits at the witness's positions, on the weights
    and ids of ``both``."""
    _, params, ids, _, _ = both
    tr = _trainer()
    tr.state["params"] = jax.tree.map(jnp.asarray, params)
    at = reference.witness_positions(S)
    assert len(at) == S and (at == np.arange(S)).all()      # S < WITNESS_ROWS
    return np.asarray(tr.logits_at(ids, at))


def test_the_witness_holds_the_program_s_logits(both, program_logits):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    ``StepTrainer``'s own forward at the witness's positions against the
    reference's logits, as one relative error."""
    _, params, ids, _, _ = both
    assert program_logits.shape == (B, S, 256)
    assert reference.logits_error(program_logits, params, {"ids": ids},
                                  MODEL) < TOL
    assert reference.witness_positions(16384)[[0, 1, -1]].tolist() == [
        32, 96, 16352]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_witness_sees_every_fault(both, program_logits, fault):
    """Each fault in the reference moves its logits away from the program's
    by a thousand times what the two differ by when both are sound."""
    _, params, ids, _, _ = both
    assert reference.logits_error(program_logits, params, {"ids": ids},
                                  MODEL, faults=(fault,)) > 1e3 * TOL


def test_the_reference_in_small_pieces_equals_itself_whole(both, monkeypatch):
    """Cut as the published size cuts it (several row blocks, chunks that
    do not divide the vocabulary, one expert at a time), the reference gives
    the same loss, logits and gradient."""
    _, params, ids, _, (want, want_grad) = both
    params = jax.tree.map(jnp.asarray, params)
    _, whole = reference.forward(params, ids, MODEL)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)       # 4 blocks of 64
    monkeypatch.setattr(reference, "VOCAB_CHUNK", 100)      # 100, 100, 56
    monkeypatch.setattr(reference, "EXPERT_GROUP", 1)
    (loss, logits), grad = jax.value_and_grad(
        lambda p: reference.forward(p, ids, MODEL), has_aux=True)(params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-6
    np.testing.assert_allclose(np.stack(logits), np.stack(whole),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * np.abs(w).max())


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The PROGRAM's expert layer on each of the four shares of 2 experts,
    summed, is the REFERENCE's layer with all 8 experts held: what a share
    leaves out is exactly what the other three compute."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    h1 = jax.random.normal(ks[1], (S, 64))
    x = jax.random.normal(ks[2], (S, 64))              # the block's input
    scale = jax.random.uniform(ks[3], (64,), minval=0.5, maxval=1.5)
    r = x @ whole["router"]
    want = reference.moe_part(h1, r, scale, whole["we_gate_up"],
                              whole["we_down"], 0, 2, 1e-6)
    parts = []
    for first in range(0, 8, 2):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(
            share, T.rms_norm(h1, scale, 1e-6), 2, rule=moe.TOP_K_SOFTMAX,
            act="relu", logits=moe.router_logits(share["router"], x),
            first_held=first)
        parts.append(y)
        # and the reference given the same share gives the same part
        np.testing.assert_allclose(y, reference.moe_part(
            h1, r, scale, share["we_gate_up"], share["we_down"], first, 2,
            1e-6), rtol=1e-5, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)


def test_run_steps_over_three_batches_equals_three_steps():
    batches = [{"ids": i} for i in _ids(seed=5, n=3)]
    one, scan = _trainer(remat=True), _trainer(remat=True)
    singly = [float(one.step(b, 1e-3)) for b in batches]
    scanned = scan.run_steps(
        stack_batches(scan.mesh, decoder.BATCH_SPECS, batches), 1e-3)
    np.testing.assert_allclose(scanned, singly, rtol=1e-5)
    assert singly[0] != singly[1]
    for a, b in zip(jax.tree.leaves(one.state["params"]),
                    jax.tree.leaves(scan.state["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_two_periods_scanned_equal_the_reference():
    """8 layers are two periods: the scan's second turn runs the same four
    kinds on the second half of the stacked leaves."""
    tr = _trainer(n_layers=8)
    params = _seeded_params(tr)
    ids = _ids(seed=2)[0]
    got = jax.jit(decoder.make_loss_fn(tr.cfg))(params, {"ids": jnp.asarray(ids)})
    model = dict(MODEL, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
                 sliding_window_layout=[0, 1, 1, 1] * 2)
    want = reference.loss(params, {"ids": ids}, model)
    assert abs(float(got) - want) / want < TOL


def test_heads_the_kernel_cannot_tile_are_refused():
    """Heads of 16 (no 128-lane block holds one): grouped and windowed
    attention has no path but the packed kernel, and says so."""
    tr = _trainer(head_width=16)
    assert T._packed_flash_blocks(tr.cfg, 6, S, 2) is None
    with pytest.raises(AssertionError, match="packed flash kernel"):
        decoder.make_loss_fn(tr.cfg)(tr.state["params"],
                                   {"ids": jnp.asarray(_ids(seed=4)[0])})


def _lowered(stack, layers, x):
    """StableHLO of the gradient of ``stack``'s summed output, private
    function numbering aside."""
    def loss(layers, x):
        return jnp.sum(stack(layers, x).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(layers, x).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize("model", ["bert", "olmoe"])
def test_a_period_of_one_layer_is_the_scan_over_layers_it_was(model):
    """BERT's and OLMoE's stacks: ``run_layers`` lowers to the program a
    plain scan of the (rematerialised) block over the stacked leaves gives,
    which is what it was before layer patterns; and a pattern that names
    the one kind in full gives the same program again."""
    cfg = (bert.bert_tiny_config(remat=True) if model == "bert"
           else olmoe.olmoe_tiny_config(remat=True, scan_unroll=2))
    assert cfg.layer_pattern == () and len(cfg.layer_kinds) == 1
    layers = T.init_transformer_params(jax.random.PRNGKey(0),
                                       cfg)["params_layers"]
    x = jnp.ones((2, 32, cfg.hidden), cfg.jdtype)

    def as_it_was(layers, x):
        body = jax.checkpoint(T.transformer_layer, static_argnums=(2,))
        return jax.lax.scan(lambda x, pl: body(pl, x, cfg), x, layers,
                            unroll=cfg.scan_unroll)[0]

    was = _lowered(as_it_was, layers, x)
    assert "stablehlo.while" in was
    assert _lowered(lambda l, x: T.run_layers(l, x, cfg), layers, x) == was
    if model == "olmoe":
        named = olmoe.olmoe_tiny_config(remat=True, scan_unroll=2,
                                        layer_pattern=((0, True),))
        assert _lowered(lambda l, x: T.run_layers(l, x, named), layers,
                        x) == was


class _Unreadable:
    shape, size = (B, S), B * S

    def __array__(self, *a, **k):
        raise AssertionError("the ids were read back with no monitor on")


def test_counters_and_gauges_only_under_a_monitor_session(tmp_path):
    tr = _trainer()
    assert monitor.active() is None
    tr._observe({"ids": _Unreadable()})         # off: nothing runs
    assert tr._probe_fn is None
    batches = [{"ids": i} for i in _ids(seed=8, n=2)]
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        reg = mon.registry
        held = reg.counter("monitor.train.moe_rows_held")
        held_start = held.value
        tr.run_steps(stack_batches(tr.mesh, decoder.BATCH_SPECS, batches), 1e-3)
        cfg = tr.cfg
        # batches x tokens x top-2 x L
        pairs = 2 * batches[0]["ids"].size * cfg.experts_per_token \
            * cfg.moe_layers
        assert pairs == 2 * B * S * 2 * 4
        got = held.value - held_start
        assert 0 < got < pairs
        share = reg.gauge("monitor.train.moe_held_rows_share").value
        np.testing.assert_allclose(share, got / pairs)
        assert 0.1 < share < 0.5                # 2 of 8 experts held
        # what the sum back's row kernel is sized by: a layer's pair slots,
        # and the rows of its first capacity (at this size one 512-row tile
        # would pass the slots, so they are the only capacity)
        slots = B * S * cfg.experts_per_token
        assert moe._held_capacities(slots, cfg.experts_here,
                                    cfg.n_experts)[0] == slots == B * S * 2
        # the flash kernels' grids by layer kind: S = 64 in 16-blocks, a
        # window of 24 visits 2 or 3 kv blocks a q block (the grid is the
        # table of visited blocks: it holds no other step)
        blocks = T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads)
        window = max(k[0] or 0 for k in cfg.layer_kinds) or None
        assert (blocks, window) == ((16, 16), 24)
        assert kv_blocks(S, *blocks, True, None) == 10
        assert kv_blocks(S, *blocks, True, window) == 9
    finally:
        monitor.disable()


def test_the_pre_attention_router_s_instructions_are_under_router():
    """The router's logits are computed before the attention scope, from
    the block's input: in the compiled step they carry the scope ``router``
    (forward and backward), and every scope of the block is there."""
    tr = _trainer(remat=True)
    tr.run_steps(stack_batches(tr.mesh, decoder.BATCH_SPECS,
                               [{"ids": i} for i in _ids(n=2)]), 1e-3)
    names = devscope.scope_maps()["smallthinker.run_steps"]
    got = {devscope.classify(op) for op in names.values()}
    for scope in ("moe", "router", "attention", "layer_norm", "lm_head",
                  "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the logits' matmul sits under ``router`` and under no ``moe``
    before = [op for op in names.values()
              if "/router/dot_general" in op and "/moe/" not in op]
    assert {devscope.classify(op) for op in before} >= {
        ("forward", "router"), ("backward", "router")}
