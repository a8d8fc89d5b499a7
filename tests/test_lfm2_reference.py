"""The LFM2-MoE hybrid decoder through the normal path (``models/lfm2.py``
over ``parallel/transformer.py``'s per-position leaves, prefix layer and
short convolution, ``parallel/moe.py``'s biased sigmoid rule and the flash
kernels' grouped mode at two heads a lane block) against the benchmark's
plain float32 reference (``benchmark/reference/lfm2_8b_a1b.py``), on seeded
weights at ``lfm2_tiny_config``: one dense layer (a convolution) and one
period (attention, three convolutions), hidden 64, 4 query heads on 2
key/value heads of 64, 8 experts of width 32 of which this share holds 2,
top-2, vocab 256, S = 64.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import lfm2_8b_a1b as reference
from paddle_tpu.models import lfm2
from paddle_tpu.parallel import decoder, moe, optim, transformer as T

B, S, TOL = 2, 64, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 5, "num_dense_layers": 1,
         "first_expert_layer": 2, "layer_types": list(lfm2.LAYER_TYPES),
         "norm_eps": 1e-5, "rope_theta": 1000000, "conv_L_cache": 3,
         "num_experts_per_tok": 2, "num_experts": 2, "moe_router_width": 8,
         "moe_first_expert_held": 2, "norm_topk_prob": True,
         "use_expert_bias": True, "routed_scaling_factor": 1}
CONV = ("ln1_scale", "ln2_scale", "conv_in", "conv_w", "conv_out")
ATTN = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
EXPERTS = ("router", "we_gate_up", "we_down")
LEAVES = (["tok_emb", "lnf_scale"]
          + ["prefix_layers/l0/" + n for n in CONV + ("w_gate_up", "w_down")]
          + ["params_layers/p0/" + n for n in ATTN + EXPERTS]
          + ["params_layers/p%d/%s" % (p, n) for p in (1, 2, 3)
             for n in CONV + EXPERTS])


def _mechanism():
    cfg = lfm2.lfm2_tiny_config()
    assert cfg.prefix_kinds == (T.CONV,)
    assert cfg.layer_kinds == ((None, True), T.CONV, T.CONV, T.CONV)
    assert cfg.per_position and cfg.n_periods == 1 and cfg.moe_layers == 4
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 64)
    assert cfg.qk_norm == "head" and cfg.tie_head
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert) == (8, 2, 2)
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.router_bias_rate == 1e-3
    assert T._packed_flash_blocks(cfg, 4, S, 2) == (16, 16)   # the kernels run
    big = lfm2.lfm2_8b_a1b_config()
    assert big.prefix_kinds == (T.CONV, T.CONV) and big.n_periods == 4
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.dense_ffn_hidden,
            big.n_experts, big.experts_per_token, big.experts_here,
            big.vocab_size, big.conv_taps) == (
        18, 2048, 32, 8, 64, 1792, 7168, 32, 4, 32, 65536, 3)
    assert lfm2.LAYER_TYPES.count("full_attention") == 6
    cut = lfm2.lfm2_8b_a1b_config(n_layers=9, n_dense_layers=1)
    assert cut.prefix_kinds == (T.CONV,) and cut.n_periods == 2
    with pytest.raises(AssertionError):
        lfm2.lfm2_8b_a1b_config(n_layers=24)     # no whole period past 18


def _bias(both):
    assert both.params["router_bias"].shape == (4, 8)


def _specs(specs):
    assert specs["router_bias"] == T.P()
    assert specs["prefix_layers"]["l0"]["conv_in"] == T.P()


def _bfloat16(both):
    assert reference.witness_positions(8192)[[0, 1, -1]].tolist() == [
        16, 48, 8176]


def _steps(trained):
    assert np.abs(trained.params["router_bias"]).max() > 0


def _counters(trained):
    cfg = trained.scan.cfg
    # batches x tokens x top-2 x MoE layers
    pairs = 3 * trained.batches[0]["ids"].size * cfg.experts_per_token \
        * cfg.moe_layers
    assert pairs == 3 * B * S * 2 * 4
    got = trained.value("monitor.train.moe_rows_held")
    assert 0 < got < pairs
    np.testing.assert_allclose(
        trained.value("monitor.train.moe_held_rows_share"), got / pairs)
    bias = trained.value("monitor.train.router_bias_abs_max")
    assert 0.1 < bias < 0.6                 # N(0, 0.1^2), 32 draws


CASE = H.Case(
    "lfm2", reference, MODEL, tuple(LEAVES), aux=True, biased=True,
    # a router steep enough that the scores are not all one half, and biases
    # large enough to change who is chosen at many tokens
    gain=H.steep("router"),
    mechanism=_mechanism,
    leaves_test="test_the_leaves_tested_are_all_there_are_but_the_bias",
    spec_configs=({},), bfloat16=True,
    # 4 row blocks of 64; chunks of 100, 100, 56; an expert at a time; the
    # dense layer's 96 columns as 40, 40, 16
    pieces={"QUERY_BLOCK": 16, "VOCAB_CHUNK": 100, "EXPERT_GROUP": 1,
            "DENSE_CHUNK": 40},
    # a trainer that holds HALF the experts (4 of 8, the second half): with 2
    # of 8 held, half the positions meet no held expert in any layer, a
    # routing fault does not touch them and the witness's first quartile is
    # theirs; with 4 held, two in a thousand are such (and at the cell's
    # sizes, 8 layers of top-4 with 8 of 32 held, six in a hundred thousand)
    witness=H.Witness(cfg={"experts_held": 4, "first_expert": 4},
                      model={"num_experts": 4, "moe_first_expert_held": 4},
                      rows=None, pieces={"DENSE_CHUNK": 32}),  # 3 chunks of 96
    steps=3, counters=True,
    also={"leaves": _bias, "specs": _specs, "bfloat16": _bfloat16,
          "steps": _steps, "counters": _counters})
globals().update(H.common(CASE))


def test_every_share_seeds_the_same_biases():
    """Each share of ``experts_here`` experts draws its own biases from the
    same key: b_e = x_(e mod held), off zero, another draw a layer."""
    cfg = lfm2.lfm2_tiny_config()
    bias = np.asarray(T.init_transformer_params(
        jax.random.PRNGKey(7), cfg)["router_bias"])
    assert bias.shape == (4, 8) and bias.dtype == np.float32
    for first in range(2, 8, 2):
        np.testing.assert_array_equal(bias[:, first:first + 2], bias[:, :2])
    assert 0.02 < np.abs(bias).mean() < 0.3 and len(np.unique(bias[:, 0])) == 4
    whole = np.asarray(T.init_transformer_params(
        jax.random.PRNGKey(7), lfm2.lfm2_tiny_config(experts_held=8,
                                                     first_expert=0))[
        "router_bias"])
    assert len(np.unique(whole)) == 32          # every expert its own


def test_the_witness_holds_the_program_s_logits(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    ``StepTrainer``'s own forward at the witness's positions against the
    reference's logits, as one relative error."""
    params, ids, program, model = witnessed
    assert program.shape == (B, S, 256)
    each = reference.position_errors(program, params, {"ids": ids}, model)
    assert each.shape == (B * S,) and each.max() < TOL
    assert reference.logits_error(program, params, {"ids": ids}, model) \
        == np.quantile(each, 0.25)


def _layer_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    m = jax.random.normal(ks[1], (S, 64))
    bias = 0.3 * jax.random.normal(ks[2], (8,))
    return whole, m, bias


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The PROGRAM's expert layer on each of the four shares of 2 experts,
    summed, is the REFERENCE's layer with all 8 experts held: what a share
    leaves out is exactly what the other three compute."""
    whole, m, bias = _layer_inputs()
    want = reference.moe_part(m, whole["router"], bias, whole["we_gate_up"],
                              whole["we_down"], 0, 2)
    parts = []
    for first in range(0, 8, 2):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(share, m, 2, rule=moe.SIGMOID_BIASED,
                                      first_held=first, bias=bias)
        parts.append(y)
        assert int(jnp.sum(aux["load"])) == 2 * S       # over all 8 experts
        np.testing.assert_allclose(y, reference.moe_part(
            m, whole["router"], bias, share["we_gate_up"], share["we_down"],
            first, 2), rtol=1e-5, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)


def test_the_bias_changes_who_is_chosen_and_never_a_weight():
    whole, m, bias = _layer_inputs()
    logits = moe.router_logits(whole["router"], m)
    scores = np.asarray(jax.nn.sigmoid(logits))
    plain_p, plain_e, _ = moe.route_top_k(
        whole["router"], m, 2, moe.SIGMOID_BIASED, bias=jnp.zeros(8))
    top_p, top_e, aux = moe.route_top_k(
        whole["router"], m, 2, moe.SIGMOID_BIASED, bias=bias)
    moved = np.asarray(jnp.sort(top_e, -1) != jnp.sort(plain_e, -1)).any(-1)
    assert 0.1 < moved.mean() < 1.0             # many tokens, not all
    # the chosen are the two largest of score + bias ...
    want_e = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :2]
    assert (np.sort(want_e, -1) == np.sort(np.asarray(top_e), -1)).all()
    # ... and their weights are the scores WITHOUT it, over their sum
    picked = np.take_along_axis(scores, np.asarray(top_e), -1)
    np.testing.assert_allclose(
        top_p, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(top_p).sum(-1), 1.0, atol=1e-4)
    assert np.asarray(aux["load"]).tolist() == np.bincount(
        np.asarray(top_e).ravel(), minlength=8).tolist()
    # no gradient reaches it, through the weights or anything else
    grad = jax.grad(lambda b: jnp.sum(moe.dropless_moe_ffn(
        whole, m, 2, rule=moe.SIGMOID_BIASED, bias=b)[0] ** 2))(bias)
    assert not np.asarray(grad).any()


def test_a_step_moves_the_bias_by_the_rule_and_nothing_else_does():
    """After one step ``b += u * sign(mean_load - load)`` from that step's
    own counts; Adam's update and a weight decay large enough to show have
    not touched it, and have moved the rest."""
    tr = H.trainer(CASE, optimizer=optim.adamw(weight_decay=0.5))
    ids = H.ids(CASE, seed=6)[0]
    params0 = jax.tree.map(np.asarray, tr.state["params"])
    _, aux = jax.jit(lambda p, i: decoder.forward(p, i, tr.cfg))(
        tr.state["params"], ids)
    load = np.asarray(aux["load"], np.float32)              # [4 layers, 8]
    assert load.shape == (4, 8) and (load.sum(-1) == B * S * 2).all()
    tr.step({"ids": jnp.asarray(ids)}, 0.1)
    after = jax.tree.map(np.asarray, tr.state["params"])
    want = params0["router_bias"] + np.float32(1e-3) * np.sign(
        load.mean(-1, keepdims=True) - load)
    np.testing.assert_array_equal(after["router_bias"], want.astype("f4"))
    assert (np.abs(after["router_bias"] - params0["router_bias"])
            <= 1.0001e-3).all()
    router = "params_layers/p1/router"
    assert np.abs(H.leaf(after, router) - H.leaf(params0, router)).max() > 1e-3
    # the balance rule itself
    np.testing.assert_allclose(
        moe.balance_bias(jnp.zeros(4), jnp.array([5, 1, 3, 3]), 0.5),
        [-0.5, 0.5, 0.0, 0.0])


def test_the_convolution_is_causal_and_starts_from_zeros():
    """A change at position t moves no output before t; positions 0 and 1
    see zeros where the sequence has no history."""
    E = 64
    pl = jax.tree.map(lambda a: a[0], T._position_leaves(
        jax.random.PRNGKey(2), lfm2.lfm2_tiny_config(), T.CONV, 1, True))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, S, E))
    out = np.asarray(T.short_conv(pl, h))
    t = 20
    moved = np.asarray(T.short_conv(pl, h.at[:, t].add(1.0)))
    assert np.array_equal(moved[:, :t], out[:, :t])
    for at in (t, t + 1, t + 2):                # three taps reach two ahead
        assert np.abs(moved[:, at] - out[:, at]).max() > 1e-3
    np.testing.assert_allclose(moved[:, t + 3:], out[:, t + 3:], atol=1e-6)
    # by hand at the sequence's start: c_0 = w_2 v_0, c_1 = w_1 v_0 + w_2 v_1
    gate_b, gate_c, z = np.split(np.asarray(h[0] @ pl["conv_in"]), 3, -1)
    v, w = gate_b * z, np.asarray(pl["conv_w"])
    c = np.stack([w[2] * v[0], w[1] * v[0] + w[2] * v[1],
                  w[0] * v[0] + w[1] * v[1] + w[2] * v[2]])
    np.testing.assert_allclose(
        out[0, :3], (gate_c[:3] * c) @ np.asarray(pl["conv_out"]),
        rtol=1e-4, atol=1e-5)


def test_two_periods_scanned_equal_the_reference():
    """9 layers are the dense layer and two periods: the scan's second turn
    runs the same four kinds on the second half of each position's leaves
    and the second four rows of the biases."""
    tr = H.trainer(CASE, n_layers=9)
    assert tr.cfg.n_periods == 2 and tr.cfg.moe_layers == 8
    params = H.moved(CASE, tr.state["params"])
    ids = H.ids(CASE, seed=2)[0]
    got, _ = jax.jit(decoder.make_loss_fn(tr.cfg))(
        params, {"ids": jnp.asarray(ids)})
    want = reference.loss(params, {"ids": ids},
                          dict(MODEL, num_hidden_layers=9))
    assert abs(float(got) - want) / want < TOL


def test_the_short_convolution_s_instructions_are_under_their_scope(trained):
    got = trained.scopes()
    for scope in ("short_conv", "moe", "router", "attention", "mlp",
                  "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("recompute", "short_conv") in got
