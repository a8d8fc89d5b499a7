"""The Trinity decoder through the normal path (``models/trinity.py`` over
``parallel/transformer.py``'s gated attention and sandwich norms,
``parallel/moe.py``'s held-experts path under a sigmoid router with a bias
and a route scale, and the flash kernels' grouped mode, full and windowed,
in interpret mode) against the benchmark's plain float32 reference
(``benchmark/reference/trinity_large_preview.py``), on seeded weights at
``trinity_tiny_config``: one dense layer and one period (sliding, full,
sliding, sliding), hidden 64, 6 query heads on 2 key/value heads of 128, a
window of 24 under S = 64, 8 routed experts of width 32 of which this share
holds 2, top-2, a shared expert of width 48, vocab 256.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import trinity_large_preview as reference
from paddle_tpu.kernels.flash_attention import packed_grid
from paddle_tpu.models import trinity
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, moe, transformer as T

B, S, TOL = 2, 64, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 128,
         "hidden_size": 64, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "sliding_window": 24, "layer_types": list(trinity.LAYER_TYPES),
         "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
         "mup_enabled": True, "num_shared_experts": 1,
         "tie_word_embeddings": False, "num_experts_per_tok": 2,
         "num_experts": 2, "moe_router_width": 8, "moe_first_expert_held": 2,
         "num_dense_layers": 1, "first_expert_layer": 6,
         "num_hidden_layers": 5}
ATTENTION = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale",
             "wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm")
SPARSE = ATTENTION + ("router", "we_gate_up", "we_down", "ws_gate_up",
                      "ws_down")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["prefix_layers/l0/" + n for n in ATTENTION + ("w_gate_up", "w_down")] \
    + ["params_layers/p%d/%s" % (i, n) for i in range(4) for n in SPARSE]


def _mechanism():
    cfg = trinity.trinity_tiny_config()
    assert cfg.prefix_kinds == ((24, True),)
    assert cfg.layer_kinds == ((24, True), (None, False), (24, True),
                               (24, True))
    assert cfg.per_position and cfg.n_periods == 1 and cfg.moe_layers == 4
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (6, 2, 128)
    assert cfg.qk_norm == "head" and not cfg.tie_head
    assert cfg.attn_gate and cfg.post_norm
    assert cfg.route_scale == 2.448 and cfg.embed_scale == 8.0
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_ffn_hidden) == (8, 2, 2, 2, 48)
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.router_bias_rate == 5e-5
    assert T._packed_flash_blocks(cfg, 6, S, 2) == (16, 16)   # the kernels run
    big = trinity.trinity_large_preview_config()
    assert big.prefix_kinds == ((4096, True),) * 3 + ((None, False),) \
        + ((4096, True),) * 2
    assert big.layer_kinds == ((4096, True), (None, False), (4096, True),
                               (4096, True)) and big.n_periods == 13
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.dense_ffn_hidden,
            big.shared_ffn_hidden, big.n_experts, big.experts_per_token,
            big.experts_here, big.vocab_size, big.norm_eps, big.rope_theta
            ) == (58, 3072, 48, 8, 128, 3072, 12288, 3072, 256, 4, 256,
                  200192, 1e-5, 10000.0)
    assert big.embed_scale == math.sqrt(3072)
    assert trinity.LAYER_TYPES.count("full_attention") == 15
    # the cell's cut: published layers 5 to 9
    cut = trinity.trinity_large_preview_config(
        n_layers=5, n_dense_layers=1, experts_held=8, vocab_size=25024)
    assert cut.prefix_kinds == ((4096, True),) and cut.n_periods == 1
    with pytest.raises(AssertionError):     # the published 60 end mid-period
        trinity.trinity_large_preview_config(n_layers=60)


def _shapes(both):
    params = both.params
    assert params["router_bias"].shape == (4, 8)
    p1 = params["params_layers"]["p1"]
    assert p1["wz"].shape == p1["wq"].shape == (1, 64, 6 * 128)
    assert p1["wk"].shape == (1, 64, 2 * 128)
    assert p1["ln1_post_scale"].shape == p1["ln2_post_scale"].shape == (1, 64)
    assert p1["ws_gate_up"].shape == (1, 64, 96)
    assert p1["we_gate_up"].shape == (1, 2, 64, 64)
    assert p1["router"].shape == (1, 64, 8)
    assert params["prefix_layers"]["l0"]["w_gate_up"].shape == (64, 192)


def test_one_rule_builds_the_ffn_leaves_of_both_trees(both):
    """Without the dense prefix the layers own the same leaves and the tree
    is ONE stack: it holds the shared expert, the selection biases, the gate
    and the output norms as the per-position tree does, and its loss is the
    reference's (read a position at a time)."""
    cfg = trinity.trinity_tiny_config(n_layers=4, n_dense_layers=0)
    assert not cfg.per_position and cfg.moe_layers == 4
    params = H.moved(CASE, T.init_transformer_params(jax.random.PRNGKey(5), cfg))
    stacked = params["params_layers"]
    assert set(stacked) == set(SPARSE) and "prefix_layers" not in params
    assert params["router_bias"].shape == (4, 8)
    assert np.abs(params["router_bias"]).min() > 0
    ids = H.ids(CASE, seed=2)[0]
    got, stepped = jax.jit(decoder.make_loss_fn(cfg))(
        params, {"ids": jnp.asarray(ids)})
    by_position = dict(params, params_layers={
        "p%d" % i: {k: v[i::4] for k, v in stacked.items()}
        for i in range(4)})
    want = reference.forward(
        by_position, ids, dict(MODEL, num_dense_layers=0, num_hidden_layers=4),
        keep_logits=False)[0]
    assert abs(float(got) - float(want)) / float(want) < TOL
    assert stepped["router_bias"].shape == (4, 8)


def _layer(seed=4, at="p1", **kw):
    """A sparse layer's leaves (position 1: the full layer), unstacked, and
    a stream to run it on."""
    cfg = trinity.trinity_tiny_config(**kw)
    params = H.moved(CASE, T.init_transformer_params(jax.random.PRNGKey(seed), cfg))
    pl = jax.tree.map(lambda a: jnp.asarray(a[0]),
                      params["params_layers"][at])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, 64))
    return cfg, pl, x, jnp.asarray(params["router_bias"][1])


def test_the_gate_multiplies_the_heads_before_wo():
    """With ``wz`` zeroed every gate is one half, and the attention branch
    BEFORE its output norm is half the ungated block's; a gate after ``wo``
    has no shape to stand on (6 x 128 columns against 64), so what the
    reference's faults misplace is what it reads and what it multiplies."""
    cfg, pl, x, bias = _layer(post_norm=False)
    quiet = dict(pl, we_down=jnp.zeros_like(pl["we_down"]),
                 ws_down=jnp.zeros_like(pl["ws_down"]))
    kind = cfg.layer_kinds[1]
    halved, _ = T.transformer_layer(
        dict(quiet, wz=jnp.zeros_like(pl["wz"])), x, cfg, kind, False, bias)
    ungated, _ = T.transformer_layer(
        quiet, x, trinity.trinity_tiny_config(post_norm=False,
                                              attn_gate=False),
        kind, False, bias)
    np.testing.assert_allclose(halved - x, 0.5 * (ungated - x), rtol=1e-4,
                               atol=1e-5)
    gated, _ = T.transformer_layer(quiet, x, cfg, kind, False, bias)
    assert np.abs(gated - halved).max() > 1e-2
    # the gate itself: the sigmoid of the projection, in float32
    o = jax.random.normal(jax.random.PRNGKey(1), (B, S, 768))
    z = jax.random.normal(jax.random.PRNGKey(2), (B, S, 768))
    np.testing.assert_allclose(T._gate_heads(o, z), o / (1 + np.exp(-z)),
                               rtol=1e-5, atol=1e-6)


def test_each_branch_s_output_is_normed_before_it_is_added():
    """Sandwich norms: scaling ``wo`` (or the experts' down projections) by
    ten leaves the layer's output where it was, because the branch's output
    norm divides it out; without the output norms it does not."""
    cfg, pl, x, bias = _layer()
    kind = cfg.layer_kinds[1]
    out, _ = T.transformer_layer(pl, x, cfg, kind, False, bias)
    scaled = dict(pl, wo=10 * pl["wo"], we_down=10 * pl["we_down"],
                  ws_down=10 * pl["ws_down"])
    again, _ = T.transformer_layer(scaled, x, cfg, kind, False, bias)
    # (up to the norm's eps beside a branch's small mean square)
    np.testing.assert_allclose(again, out, rtol=1e-2, atol=5e-3)
    bare = trinity.trinity_tiny_config(post_norm=False)
    plain = {k: v for k, v in pl.items() if "post" not in k}
    a, _ = T.transformer_layer(plain, x, bare, kind, False, bias)
    b, _ = T.transformer_layer(
        {k: scaled[k] for k in plain}, x, bare, kind, False, bias)
    assert np.abs(a - b).max() > 1.0
    # a branch's norm weight reaches the stream as it is
    zeroed, _ = T.transformer_layer(
        dict(pl, ln1_post_scale=jnp.zeros(64), ln2_post_scale=jnp.zeros(64)),
        x, cfg, kind, False, bias)
    np.testing.assert_array_equal(zeroed, x)


@pytest.mark.parametrize("gain", [None, 1.0, 0.25])
def test_the_output_norms_scales_are_seeded_at_the_gain(gain):
    """``post_norm_gain`` seeds the output norms' scales and nothing else:
    the input norms' stay at one, and the model's own gain is
    ``POST_NORM_GAIN``."""
    kw = {} if gain is None else {"post_norm_gain": gain}
    cfg = trinity.trinity_tiny_config(**kw)
    want = trinity.POST_NORM_GAIN if gain is None else gain
    assert cfg.post_norm_gain == want
    params = T.init_transformer_params(jax.random.PRNGKey(0), cfg)
    for tree in (params["prefix_layers"]["l0"], params["params_layers"]["p2"]):
        for name in ("ln1", "ln2"):
            np.testing.assert_array_equal(
                tree[name + "_post_scale"], np.float32(want))
            np.testing.assert_array_equal(tree[name + "_scale"], 1.0)


@pytest.mark.parametrize("std", [None, 0.1, 0.02])
def test_the_selection_biases_are_seeded_with_the_std(std):
    """``router_bias_std`` scales the seeded selection biases and nothing
    else: one draw a share, tiled over the shares; the published size's own
    is ``ROUTER_BIAS_STD``, the tiny size's the block's default."""
    assert trinity.trinity_large_preview_config().router_bias_std \
        == trinity.ROUTER_BIAS_STD
    kw = {} if std is None else {"router_bias_std": std}
    cfg = trinity.trinity_tiny_config(**kw)
    want = 0.1 if std is None else std
    assert cfg.router_bias_std == want
    key = jax.random.PRNGKey(5)
    bias = T._router_bias(key, cfg)["router_bias"]
    draw = jax.random.normal(key, (cfg.moe_layers, cfg.experts_here))
    assert bias.shape == (4, 8) and bias.dtype == jnp.float32
    np.testing.assert_allclose(
        bias, np.tile(want * np.asarray(draw), (1, 4)), rtol=1e-6)


@pytest.mark.parametrize("std, balanced", [(trinity.ROUTER_BIAS_STD, True),
                                           (0.1, False)])
def test_the_seeded_biases_leave_every_held_expert_near_the_mean(std,
                                                                 balanced):
    """Why ``ROUTER_BIAS_STD``: a bias is added to a sigmoid SCORE, and the
    fourth of 256 scores stands where the sigmoid is flat.  On unit-normal
    logits (what seeded weights give a token's own row) the cell's 6,144
    tokens leave each of a share's 8 experts between a third of and twice
    the mean's 96 pairs at the model's std; at the block's default 0.1 some
    expert of some layer draws under a tenth of the mean or over five times
    it, and the grouped matmuls' tiles, so the step's time, follow the
    seed."""
    cfg = trinity.trinity_large_preview_config(
        n_layers=5, n_dense_layers=1, experts_held=8, router_bias_std=std)
    bias = T._router_bias(jax.random.PRNGKey(11), cfg)["router_bias"]
    logits = jax.random.normal(jax.random.PRNGKey(12), (6144, 256))
    held = []
    for layer in range(cfg.moe_layers):
        _, top_e, _ = moe.route_top_k(jnp.zeros((1, 256)), None, 4,
                                      moe.SIGMOID_BIASED, logits=logits,
                                      bias=bias[layer])
        held.append(np.bincount(np.asarray(top_e).ravel(),
                                minlength=256)[:8])
    held = np.stack(held)
    assert 512 < held.sum(1).min() and held.sum(1).max() < 1024
    if balanced:
        assert 32 <= held.min() and held.max() <= 192, held
    else:
        assert held.min() < 10 or held.max() > 480, held


def test_the_full_layer_carries_no_positions_and_the_sliding_ones_a_window():
    """The full layer's q and k are blind to WHERE their rows stand (the
    same rows with ``first`` moved give the same q and k), the sliding
    layers' are not; and a sliding layer's rows past the window do not see
    the first tokens, while the full layer's do."""
    cfg, pl, x, bias = _layer()
    a = T._qkv(pl, x, cfg, False)
    b = T._qkv(pl, x, cfg, False, first=7)
    c = T._qkv(pl, x, cfg, True, first=7)
    np.testing.assert_array_equal(a[0], b[0])
    assert np.abs(np.asarray(a[0]) - np.asarray(c[0])).max() > 1e-2
    other = x.at[:, :8].set(jax.random.normal(jax.random.PRNGKey(3),
                                              (B, 8, 64)))
    quiet = dict(pl, we_down=jnp.zeros_like(pl["we_down"]),
                 ws_down=jnp.zeros_like(pl["ws_down"]))
    for kind, sees in (((24, True), False), ((None, False), True)):
        one, _ = T.transformer_layer(quiet, x, cfg, kind, False, bias)
        two, _ = T.transformer_layer(quiet, other, cfg, kind, False, bias)
        late = np.abs(np.asarray(one - two))[:, 8 + 24:].max()
        assert (late > 1e-3) == sees, (kind, late)


def _layer_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    whole["ws_gate_up"] = jax.random.normal(ks[2], (64, 96)) / 8
    whole["ws_down"] = jax.random.normal(ks[3], (48, 64)) / 7
    bias = 0.3 * jax.random.normal(ks[4], (8,))
    return whole, jax.random.normal(ks[1], (S, 64)), bias


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """The PROGRAM's FFN branch of a sparse layer on each of the four shares
    of 2 routed experts: every share computes the shared expert, so the four
    routed parts summed, plus the shared expert counted ONCE, is the
    REFERENCE's ``f`` with all 8 experts held, BEFORE the output norm.  The
    norm is not linear: the shares' NORMED branches do not add up to the
    uncut layer's, so the sum over shares is taken before it (as an exchange
    between the chips would)."""
    whole, m, bias = _layer_inputs()
    cfg = trinity.trinity_tiny_config()
    model = dict(MODEL, num_experts=8, moe_first_expert_held=0)
    routed_want, shared_want = reference.ffn_sum(
        m, whole.__getitem__, bias, model)

    def ffn_branch(first):
        """``transformer_layer``'s FFN branch on normed rows ``m``, for the
        share that holds experts [first, first + 2)."""
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(
            share, m, 2, rule=moe.SIGMOID_BIASED, first_held=first,
            bias=bias, scale=cfg.route_scale)
        shared = T.gated_ffn({"w_gate_up": share["ws_gate_up"],
                              "w_down": share["ws_down"]}, m[None], cfg)[0]
        return y, shared, aux

    parts = [ffn_branch(first) for first in range(0, 8, 2)]
    for first, (y, shared, aux) in zip(range(0, 8, 2), parts):
        np.testing.assert_allclose(shared, shared_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y, reference.ffn_sum(
            m, dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                    we_down=whole["we_down"][first:first + 2]).__getitem__,
            bias, dict(model, moe_first_expert_held=first))[0],
            rtol=1e-5, atol=1e-5)
    assert sum(int(p[2]["rows_held"]) for p in parts) == 2 * S
    assert all(float(jnp.abs(p[0]).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(p[0] for p in parts) + parts[0][1],
                               routed_want + shared_want, rtol=1e-5,
                               atol=1e-5)
    # ... counted four times it is not
    assert np.abs(sum(p[0] + p[1] for p in parts)
                  - (routed_want + shared_want)).max() > 0.1
    # ... and the norm is not linear: the normed shares do not add up
    g = jnp.ones(64)
    normed = sum(T.rms_norm(p[0] + p[1], g, 1e-5) for p in parts)
    assert np.abs(normed - T.rms_norm(routed_want + shared_want, g, 1e-5)
                  ).max() > 0.1


def test_the_route_scale_multiplies_the_weights_and_nothing_else():
    whole, m, bias = _layer_inputs()
    for rule in moe.RULES:
        one = moe.route_top_k(whole["router"], m, 2, rule, bias=bias)
        two = moe.route_top_k(whole["router"], m, 2, rule, bias=bias,
                              scale=2.448)
        np.testing.assert_allclose(two[0], 2.448 * one[0], rtol=1e-6)
        np.testing.assert_array_equal(two[1], one[1])
    top_p, _, _ = moe.route_top_k(whole["router"], m, 2, moe.SIGMOID_BIASED,
                                  bias=bias, scale=2.448)
    np.testing.assert_allclose(top_p.sum(-1), 2.448, rtol=1e-5)


def test_the_bias_chooses_and_does_not_weigh():
    """A bias that lifts one expert over all others has every token choose
    it, and the weights are still the scores' (its own sigmoid over the
    chosen sigmoids' sum, times the scale)."""
    whole, m, _ = _layer_inputs()
    bias = jnp.zeros(8).at[5].set(10.0)
    top_p, top_e, aux = moe.route_top_k(
        whole["router"], m, 2, moe.SIGMOID_BIASED, bias=bias, scale=2.448)
    assert (np.asarray(top_e)[:, 0] == 5).all() and int(aux["load"][5]) == S
    s = np.asarray(jax.nn.sigmoid(m @ whole["router"]))
    chosen = np.take_along_axis(s, np.asarray(top_e), 1)
    np.testing.assert_allclose(
        top_p, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_the_embedding_enters_the_stream_times_the_multiplier():
    cfg = trinity.trinity_tiny_config()
    params = T.init_transformer_params(jax.random.PRNGKey(2), cfg)
    ids = jnp.asarray(H.ids(CASE)[0])
    got = T.embed(params, ids, cfg)
    np.testing.assert_allclose(got, 8.0 * params["tok_emb"][ids], rtol=1e-6)
    # the rows are seeded at the fan-in scale: the stream starts at unit scale
    big = T.init_transformer_params(
        jax.random.PRNGKey(2), trinity.trinity_tiny_config(vocab_size=4096))
    assert abs(float(jnp.std(T.embed(
        big, jnp.arange(4096)[None], trinity.trinity_tiny_config(
            vocab_size=4096)))) - 1.0) < 0.02


def test_the_witness_reads_both_sides_of_the_window_s_edge(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    the trainer's own forward at the witness's positions against the
    reference's logits; the statistic is the larger group's third
    quartile."""
    params, ids, program, model = witnessed
    groups = reference.witness_groups(S)
    assert groups["edge"].tolist() == list(range(12, 20)) + [60, 61, 62, 63]
    assert not set(groups["edge"]) & set(groups["spread"])
    big = reference.witness_groups(6144)
    assert big["edge"].tolist() == list(range(4088, 4104)) + list(
        range(6136, 6144))
    # (one of the 256 spread positions, 4,092, stands in the edge group)
    assert len(big["spread"]) == 255 and (big["spread"] >= 4096).sum() == 85
    each = reference.position_errors(program, params, {"ids": ids}, model)
    assert each.shape == (S,) and each.max() < TOL
    parts = reference.group_errors(program, params, {"ids": ids}, model)
    assert reference.logits_error(program, params, {"ids": ids}, model) \
        == max(parts.values())
    assert parts["edge"] == np.quantile(each[:12], 0.75)


def test_a_step_moves_the_biases_by_the_rate_against_the_load(trained):
    """``load_balance_coeff`` as the sign rule's rate: after one step every
    layer's biases stand 5e-5 up or down, against that layer's load over
    this chip's tokens, for all 8 experts."""
    _, aux = jax.jit(lambda p, i: decoder.forward(p, i, trained.scan.cfg))(
        trained.after[0], trained.batches[0]["ids"])
    load = np.asarray(aux["load"], np.float32)
    bias0, bias1 = (p["router_bias"] for p in trained.after[:2])
    assert load.shape == (4, 8) and (load.sum(-1) == B * S * 2).all()
    want = bias0 + np.float32(5e-5) * np.sign(
        load.mean(-1, keepdims=True) - load)
    np.testing.assert_array_equal(bias1, want.astype("f4"))
    assert np.abs(bias1 - bias0).max() > 0


def _counters(trained):
    cfg = trinity.trinity_tiny_config()
    # batches x tokens x top-2 x MoE layers
    pairs = 2 * B * S * cfg.experts_per_token * cfg.moe_layers
    assert pairs == 2 * B * S * 2 * 4
    rows_held = trained.value("monitor.train.moe_rows_held")
    assert 0 < rows_held < pairs
    share = trained.value("monitor.train.moe_held_rows_share")
    assert share == rows_held / pairs and 0.1 < share < 0.5
    assert trained.value("monitor.train.moe_load_max_over_mean") >= 1.0
    # seeded gates stand near one half: neither stuck shut nor open
    assert 0.4 < trained.value("monitor.train.attn_gate_mean") < 0.6
    assert trained.value("monitor.train.router_bias_abs_max") > 0
    # a layer's grid: the causal triangle's 10 of 4 x 4 blocks a (sequence,
    # key/value head), a group's three query heads riding each step (PR
    # 68), from the function the kernels take their grid from
    assert packed_grid(
        B, S, cfg.n_heads, cfg.head_dim,
        *T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads),
        itemsize=cfg.jdtype.itemsize, n_kv_heads=cfg.kv_heads,
        causal=True) == (3, 40)


def _specs(specs):
    # the gate's projection is cut as the queries' are (columns by head)
    assert specs["params_layers"]["p0"]["wz"] \
        == specs["params_layers"]["p0"]["wq"] == T.P(None, None, "tp")
    assert specs["params_layers"]["p0"]["ln1_post_scale"] == T.P(None, None)
    assert specs["prefix_layers"]["l0"]["wz"] == specs["router_bias"] == T.P()


CASE = H.Case(
    "trinity", reference, MODEL, tuple(LEAVES), aux=True, biased=True,
    # a router steep enough that the scores are not all one half, and biases
    # large enough to change who is chosen at many tokens
    gain=H.steep("router"),
    mechanism=_mechanism, spec_configs=({},), bfloat16=True,
    # 4 row blocks of 64; chunks of 100, 100, 56; an expert at a time; the
    # dense layer's columns as 20, 20, 8
    pieces={"QUERY_BLOCK": 16, "VOCAB_CHUNK": 100, "EXPERT_GROUP": 1,
            "DENSE_CHUNK": 20},
    # a trainer that holds HALF the experts (4 of 8, the second half): with 2
    # of 8 held, many positions meet no held expert and a routing fault does
    # not touch them
    witness=H.Witness(cfg={"experts_held": 4, "first_expert": 4},
                      model={"num_experts": 4, "moe_first_expert_held": 4}),
    steps=2, steps_atol=5e-5, counters=True,
    also={"leaves": _shapes, "specs": _specs, "counters": _counters})
globals().update(H.common(CASE))


def test_the_new_scopes_hold_their_instructions(trained):
    names, got = trained.names, trained.scopes()
    for scope in ("attention", "attn_gate", "post_norm", "shared_expert",
                  "moe", "router", "mlp", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    for scope in ("attention", "attn_gate", "post_norm", "shared_expert"):
        assert ("recompute", scope) in got, scope
    # the gate and the output norms lie INSIDE the layer's scopes
    bare = [devscope._WRAPPERS.sub("", op) for op in names.values()]
    paths = [op for op in bare if "/post_norm/" in op]
    assert any("/attention/post_norm/" in op for op in paths)
    assert any("/moe/post_norm/" in op for op in paths)
    assert any("/mlp/post_norm/" in op for op in paths)
    assert all("/attention/attn_gate/" in op for op in bare
               if "/attn_gate/" in op)
