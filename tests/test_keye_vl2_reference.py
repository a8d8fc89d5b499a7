"""The Keye-VL-2.0 decoder on the program's normal path against the plain
reference (``benchmark/reference/keye_vl2_30b_a3b.py``) at the tiny size:
logits, both loss terms and every leaf's gradient; which leaves hear which
term; the selection (rows before ``topk``, the selected sets); the three
position streams; the shares' parts adding up to the uncut layer; a trainer
step; what a differentiated step under remat runs a layer and keeps.  ONE
traced program of the model for the file's comparisons."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2_30b_a3b as ref
from paddle_tpu.kernels import indexer as ix
from paddle_tpu.models import keye_vl2
from paddle_tpu.parallel import decoder, optim, transformer as T
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.train import stack_batches

CFG = keye_vl2.keye_vl2_tiny_config(remat=True)
S, TOPK = 64, CFG.indexer_topk
INDEXER_LEAVES = ("wq_idx", "wk_idx", "w_idx", "idx_k_norm_scale",
                  "idx_k_norm_bias")


def model_of(cfg):
    """The reference's ``model`` of a configuration."""
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.kv_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {"mrope_section": list(cfg.mrope_sections)},
            "sa_config": {"indexer_num_heads": cfg.indexer_heads,
                          "indexer_head_dim": cfg.indexer_dim,
                          "indexer_num_kv_heads": 1,
                          "topk": cfg.indexer_topk},
            "num_experts_per_tok": cfg.experts_per_token,
            "first_expert_held": cfg.first_expert,
            "num_hidden_layers": cfg.n_layers}


def grid_positions(b, s):
    """Streams that differ: 16 text tokens, a 6 x 8 image grid (one
    temporal index, rows and columns counted), text again."""
    t, h, w = (np.arange(s) for _ in range(3))
    at = np.arange(16, 64)
    t[at], h[at], w[at] = 16, 16 + (at - 16) // 8, 16 + (at - 16) % 8
    return np.broadcast_to(np.stack([t, h, w])[:, None], (3, b, s)).astype(
        np.int32)


def leaves_of(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def case():
    params = T.init_transformer_params(jax.random.PRNGKey(0), CFG)
    # off their seeds, so that each shows: the norms' weights and the bias
    r = np.random.RandomState(1)
    layers = dict(params["params_layers"])
    for name in ("q_norm", "k_norm", "idx_k_norm_scale", "idx_k_norm_bias"):
        layers[name] = layers[name] + jnp.asarray(
            0.1 * r.randn(*layers[name].shape), jnp.float32)
    params = dict(params, params_layers=layers)
    ids = r.randint(0, CFG.vocab_size, (2, S)).astype(np.int32)
    grid = grid_positions(2, S)

    def terms(p, positions=None):
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to((jnp.arange(S) < S - 1).astype(jnp.float32),
                                ids.shape)
        x, aux = decoder.forward(p, ids, CFG, positions)
        return (T.final_logits_loss(p, x, labels, mask, CFG),
                jnp.mean(aux["dsa_kl"])), T.head_logits(p, x, CFG)

    def program(p):
        (ce, kl), pull, logits = jax.vjp(terms, p, has_aux=True)
        one, zero = jnp.ones(()), jnp.zeros(())
        text = terms(p, jnp.broadcast_to(jnp.arange(S), (3, 2, S)))
        image = terms(p, jnp.asarray(grid))
        pl, h = decoder._first_layer_input(p, ids, CFG)
        scores, tau = T.indexer_selection(pl, h, CFG)
        return dict(ce=ce, kl=kl, logits=logits, d_ce=pull((one, zero))[0],
                    d_kl=pull((zero, one))[0], text=text, image=image,
                    selected=ix.selected(scores, tau),
                    loss=decoder.make_loss_fn(CFG)(p, {"ids": ids}))

    with jax.default_matmul_precision("highest"):
        got = jax.device_get(jax.jit(program)(params))
        model = model_of(CFG)
        selections = []
        want = ref.forward_terms(params, {"ids": ids}, model,
                                 selections=selections)
        want["grad"] = jax.grad(lambda p: ref.forward(
            p, {"ids": ids}, model, keep_logits=False)[0])(params)
        want["image"] = ref.forward_terms(
            params, {"ids": ids, "positions": grid}, model)
    want["selected"] = selections[0]
    return params, ids, got, jax.device_get(want)


def close(got, want, tolerance=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tolerance * max(
        1e-3, np.max(np.abs(want)))


def test_logits_and_both_loss_terms_agree_with_the_reference(case):
    _, _, got, want = case
    assert close(got["logits"], np.stack(want["logits"]))
    assert close(got["ce"], want["ce"], 1e-6)
    assert close(got["kl"], want["kl"], 1e-5) and want["kl"] > 0.05
    assert close(got["loss"], want["ce"] + want["kl"], 1e-6)


LEAVES = sorted(leaves_of(jax.eval_shape(
    lambda: T.init_transformer_params(jax.random.PRNGKey(0), CFG))))


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leaf_s_gradient_agrees_with_the_reference(case, leaf):
    """float32 both sides; 2e-5 of the leaf's largest entry: the kernels
    sum a tile's products in another order than ``jnp`` does."""
    _, _, got, want = case
    total = leaves_of(got["d_ce"])[leaf] + leaves_of(got["d_kl"])[leaf]
    assert np.max(np.abs(leaves_of(want["grad"])[leaf])) > 0
    assert close(total, leaves_of(want["grad"])[leaf], 2e-5), leaf


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_leaf_hears_one_term_alone(case, leaf):
    """The indexer's five leaves get EXACTLY nothing from the cross entropy
    and every other leaf exactly nothing from the KL term."""
    _, _, got, _ = case
    indexer = leaf.split("'")[-2] in INDEXER_LEAVES
    silent, heard = ("d_ce", "d_kl") if indexer else ("d_kl", "d_ce")
    assert not np.any(leaves_of(got[silent])[leaf]), leaf
    assert np.any(leaves_of(got[heard])[leaf]), leaf


def test_the_selected_sets_are_the_reference_s(case):
    _, _, got, want = case
    assert np.array_equal(got["selected"], want["selected"])
    kept = got["selected"].sum(-1)
    assert np.array_equal(kept[:, :TOPK],
                          np.broadcast_to(np.arange(1, TOPK + 1), (2, TOPK)))
    # exactly k, but for ties at the threshold, which are kept (a row whose
    # k-th score is an exact zero, every head's product negative)
    assert np.all(kept[:, TOPK:] >= TOPK)
    assert np.mean(kept[:, TOPK:] == TOPK) > 0.9


def test_equal_streams_are_plain_rotary_and_a_grid_s_agree(case):
    _, _, got, want = case
    (ce, kl), logits = got["text"]
    assert close(logits, got["logits"], 1e-6) and close(ce, got["ce"], 1e-7)
    (ce, kl), logits = got["image"]
    assert close(logits, np.stack(want["image"]["logits"]))
    assert close(ce, want["image"]["ce"], 1e-6)
    assert close(kl, want["image"]["kl"], 1e-5)
    # and the streams are not inert: the grid moves the logits
    assert not close(logits, got["logits"], 1e-3)


def test_rows_before_topk_are_dense_causal_attention():
    from paddle_tpu.kernels.flash_attention import flash_attention_packed

    r = np.random.RandomState(2)
    q, k, v = (jnp.asarray(r.randn(1, S, n * 128), jnp.float32)
               for n in (8, 2, 2))
    scores = jnp.where(np.tril(np.ones((S, S), bool)),
                       jnp.asarray(r.randn(1, S, S), jnp.float32), -jnp.inf)
    tau = ix.kth_largest(scores, TOPK)
    blocks = dict(block_q=16, block_k=16)
    # the scores' operands are read by the backward alone
    indexer = tuple(jnp.zeros((1, S, n), jnp.float32) for n in (16, 16, 1))
    sparse = ix.dsa_attend_kl(
        q, k, v, indexer, scores, tau,
        ix.dsa_lse(q, k, scores, tau, 8, 2, **blocks),
        ix.selected_lse(scores, tau), 8, 2, **blocks)[0]
    full = flash_attention_packed(q, k, v, 8, causal=True, n_kv_heads=2,
                                  **blocks)
    assert close(sparse[:, :TOPK], full[:, :TOPK], 1e-6)
    assert not close(sparse[:, TOPK:], full[:, TOPK:], 1e-2)


def test_a_step_under_remat_runs_one_online_forward_a_layer():
    """The differentiated step of a stack under ``cfg.remat``, by its
    jaxpr's kernels (the scan holds ONE layer, forward and backward): ONE
    masked online forward (the statistic's, in the forward pass), the pass
    with the statistic known TWICE (forward and recompute), one masked
    backward.  What the policy keeps of a layer beside its input: the
    thresholds and the selected keys' normaliser [b, S] and the statistic
    [b, H, S] as dense arrays, and nothing [.., S, S]."""
    from jax._src.ad_checkpoint import saved_residuals
    from paddle_tpu import monitor

    cfg = keye_vl2.keye_vl2_tiny_config(remat=True, max_seq=128)
    b, s, layers, heads = 2, 128, cfg.n_layers, cfg.n_heads
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(
            lambda: T.init_transformer_params(jax.random.PRNGKey(0), cfg)))
    loss = lambda p: decoder.make_loss_fn(cfg)(
        p, {"ids": np.zeros((b, s), np.int32)})
    mon = monitor.enable()
    try:
        calls = mon.registry.counter("monitor.kernels.dsa_attend_kl_calls")
        before = calls.value
        forward = str(jax.make_jaxpr(loss)(params))
        # one call a layer body traced: the scan's one layer
        assert calls.value - before == 1
    finally:
        monitor.disable()
    kernels = lambda text: {name: len(re.findall(
        r"name=%s\w*" % name, text)) for name in (
            "flash_dsa_fwd", "dsa_attend_kl_fwd", "flash_dsa_bwd_",
            "indexer_scores_fwd", "indexer_scores_bwd", "indexer_kl")}
    assert kernels(forward) == {
        "flash_dsa_fwd": 1, "dsa_attend_kl_fwd": 1, "flash_dsa_bwd_": 0,
        "indexer_scores_fwd": 1, "indexer_scores_bwd": 0, "indexer_kl": 0}
    assert kernels(str(jax.make_jaxpr(jax.grad(loss))(params))) == {
        "flash_dsa_fwd": 1, "dsa_attend_kl_fwd": 2, "flash_dsa_bwd_": 1,
        "indexer_scores_fwd": 2, "indexer_scores_bwd": 1, "indexer_kl": 0}
    kept = sorted(tuple(aval.shape) for aval, what in saved_residuals(
        loss, params) if "output of scan" in what)
    assert kept == sorted([(layers, b, s), (layers, b, s),
                           (layers, b, heads, s),
                           (layers, b, s, cfg.hidden)]), kept
    assert T.DSA_KEPT == ("dsa_tau", "dsa_lse_i", "dsa_lse")


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """Four shares of two experts each, attention counted once: the uncut
    reference's layer (all eight experts held)."""
    whole = keye_vl2.keye_vl2_tiny_config(experts_held=8, first_expert=0)
    params = T.init_transformer_params(jax.random.PRNGKey(3), whole)
    pl = jax.tree.map(lambda a: a[0], params["params_layers"])
    x = jnp.asarray(np.random.RandomState(3).randn(1, S, whole.hidden),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        parts = []
        for share in range(4):
            cfg = keye_vl2.keye_vl2_tiny_config(first_expert=2 * share)
            mine = dict(pl, **{name: pl[name][2 * share:2 * share + 2]
                               for name in ("we_gate_up", "we_down")})
            parts.append(T.transformer_layer(mine, x, cfg)[0])
        attended = T.transformer_layer(
            dict(mine, we_down=jnp.zeros_like(mine["we_down"])), x, cfg)[0]
        model = dict(model_of(whole), num_hidden_layers=1)
        o, _, _ = ref.attention_part(
            x[0], {name: pl[name] for name in ref.ATTENTION_LEAVES},
            jnp.broadcast_to(jnp.arange(S), (3, S)), model)
        h1 = x[0] + o
        want = h1 + ref.moe_part(h1, pl["ln2_scale"], pl["router"],
                                 pl["we_gate_up"], pl["we_down"], 0,
                                 whole.experts_per_token, whole.norm_eps)
    assert close(attended[0], h1)
    assert close(sum(parts)[0] - 3 * attended[0], want)
    assert not close(parts[0][0], want, 1e-3)


def test_a_trainer_steps_and_probes():
    tr = keye_vl2.build_keye_vl2_trainer(
        CFG, MeshSpec(dp=1), optimizer=optim.adamw(), seed=0,
        devices=jax.devices()[:1])
    ids = np.random.RandomState(0).randint(0, 256, (3, 2, S)).astype("i4")
    losses = np.asarray(tr.run_steps(stack_batches(
        tr.mesh, decoder.BATCH_SPECS, [{"ids": i} for i in ids]), 1e-2))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    read = jax.device_get(jax.jit(lambda p, i: decoder.probe(p, i, CFG))(
        tr.state["params"], ids[0]))
    assert 0.0 < read["dsa_kl_mean"] < 2.0
    # an indexer that knows nothing: near the rows' own selected share
    assert 0.1 < read["dsa_mass_selected"] < 0.6
    assert tr.logits_at(ids[0], [0, S - 1]).shape == (2, 2, 256)


def test_a_trainer_built_for_positions_takes_the_streams():
    """``build_decoder_trainer(positions=True)``: every batch carries its
    three streams; equal streams give the plain trainer's loss, a grid's
    another."""
    ids = np.random.RandomState(0).randint(0, 256, (1, 2, S)).astype("i4")
    specs = dict(decoder.BATCH_SPECS, **decoder.POSITION_SPECS)
    losses = {}
    for name, streams in (("text", np.broadcast_to(
            np.arange(S, dtype=np.int32), (3, 2, S))),
            ("grid", grid_positions(2, S))):
        tr = keye_vl2.build_keye_vl2_trainer(
            CFG, MeshSpec(dp=1), optimizer=optim.adamw(), seed=0,
            devices=jax.devices()[:1], positions=True)
        losses[name] = float(np.asarray(tr.run_steps(stack_batches(
            tr.mesh, specs, [{"ids": ids[0], "positions": streams}]),
            1e-2))[0])
    plain = keye_vl2.build_keye_vl2_trainer(
        CFG, MeshSpec(dp=1), optimizer=optim.adamw(), seed=0,
        devices=jax.devices()[:1])
    want = float(np.asarray(plain.run_steps(stack_batches(
        plain.mesh, decoder.BATCH_SPECS, [{"ids": ids[0]}]), 1e-2))[0])
    assert abs(losses["text"] - want) < 1e-5 * want
    assert abs(losses["grid"] - want) > 1e-4 * want
