"""The Nemotron-H decoder through the normal path (``models/nemotron_h.py``
over ``parallel/transformer.py``'s single-branch layers: MAMBA2, position-free
grouped-query attention and FFN positions; ``parallel/moe.py``'s ungated
``relu^2`` experts; ``kernels/ssd_scan.py``'s kernels, in interpret mode)
against the benchmark's plain float32 reference
(``benchmark/reference/nemotron3_nano_30b_a3b.py``, the per-token
recurrence), on seeded weights at ``nemotron_h_tiny_config``: the five layers
``EM*EM``, hidden 64, 4 query heads on 2 key/value heads of 128, 16 Mamba-2
heads of 16 channels in 2 groups of 128 state cells, 4 taps, chunks of 16
under S = 64, 8 experts of width 192 (no multiple of 128) top-2 of which 4
are held beside a shared expert of width 128, vocab 256, an untied head.

What the tiny configuration keeps of the published one: every leaf and every
line of the three branches, a mixer beside attention with nothing between,
the sigmoid router with its bias outside the weights and its scale, a share
of the experts, the state carried over three chunk edges.  What it drops: the
period's length and the widths.

The tiny configuration computes in float32, so the tolerance is 1e-5 on the
loss (the two differ by accumulation order only) and three times that on a
single logit row or gradient element, against the largest of its leaf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import nemotron3_nano_30b_a3b as reference
from paddle_tpu import monitor
from paddle_tpu.kernels import moe_rows, ssd_scan as ssd
from paddle_tpu.kernels.flash_attention import packed_grid
from paddle_tpu.models import nemotron_h
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import moe, transformer as T

B, S, TOL = 2, 64, 1e-5
EACH = 3 * TOL         # one logit row, one gradient element
PATTERN = "EM*EM"
# the reference reads the published keys; the pattern is the layers held
MODEL = {"hybrid_override_pattern": PATTERN, "first_layer": 0,
         "num_hidden_layers": 5, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 16,
         "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 128,
         "conv_kernel": 4, "chunk_size": 16, "n_routed_experts": 4,
         "router_experts": 8, "first_expert": 0, "num_experts_per_tok": 2,
         "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
         "topk_group": 1, "norm_eps": 1e-5, "tie_word_embeddings": False}
EXPERT = ("ln2_scale", "router", "we_up", "we_down", "ws_up", "ws_down")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["params_layers/%s/%s" % (p, n) for p in ("p0", "p3") for n in EXPERT] \
    + ["params_layers/%s/%s" % (p, n) for p in ("p1", "p4")
       for n in ("ln1_scale",) + reference.MAMBA_LEAVES] \
    + ["params_layers/p2/" + n
       for n in ("ln1_scale",) + reference.ATTENTION_LEAVES]


def _mechanism():
    cfg = nemotron_h.nemotron_h_tiny_config()
    attention = (None, False)
    assert cfg.layer_kinds == (T.FFN, T.MAMBA2, attention, T.FFN, T.MAMBA2)
    assert cfg.single_branch and cfg.per_position and cfg.n_periods == 1
    assert cfg.ffn_positions == (True, False, False, True, False)
    assert cfg.moe_layers == 2 and cfg.positions is None
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 128)
    assert not cfg.tie_head and not cfg.expert_gated \
        and cfg.expert_act == "relu2" and cfg.routing == moe.SIGMOID_BIASED
    assert cfg.ffn_hidden % 64 == 0 and cfg.ffn_hidden % 128
    width = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
    assert ssd.supported((B, S, width), cfg.ssm_heads, cfg.ssm_groups,
                         cfg.d_state, cfg.scan_chunk)
    big = nemotron_h.nemotron3_nano_30b_a3b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads, big.head_dim,
            big.ffn_hidden, big.shared_ffn_hidden, big.vocab_size,
            big.norm_eps, big.d_inner, big.ssm_heads, big.ssm_groups,
            big.d_state, big.d_conv, big.scan_chunk, big.n_experts,
            big.experts_per_token, big.route_scale) == (
        52, 2688, 32, 2, 128, 1856, 3712, 131072, 1e-5, 4096, 64, 8, 128, 4,
        128, 128, 6, 2.5)
    kinds = big.layer_kinds
    assert (kinds.count(T.MAMBA2), kinds.count(T.FFN),
            kinds.count(attention)) == (23, 23, 6) and big.moe_layers == 23
    assert [i for i, k in enumerate(kinds) if k == attention] \
        == [5, 12, 19, 26, 33, 42]
    # the cell's cut: published layers 34 to 42, the pattern's own period
    cut = nemotron_h.nemotron3_nano_30b_a3b_config(
        n_layers=9, first_layer=34, experts_held=16, vocab_size=16384)
    assert cut.layer_kinds == (T.FFN, T.MAMBA2) * 4 + (attention,)
    assert cut.moe_layers == 4 and cut.experts_here == 16
    assert ssd.supported((2, 8192, 6144), 64, 8, 128, 128)


CASE = H.Case(
    "nemotron_h", reference, MODEL, tuple(LEAVES), each=EACH, aux=True,
    # the norm scales, the skip and the rates off their seeds
    off_one=("scale", "_norm", "d_skip", "a_log"), mechanism=_mechanism,
    logits=False, leaves_test=None, grad_rtol=1e-3,
    grad_test="test_every_leaf_s_gradient_equals_the_reference")
globals().update(H.common(CASE))


def test_logits_at_the_witness_positions_equal_the_reference(both):
    _, params, ids, _, _ = both
    at = reference.witness_positions(S)
    assert len(at) and set(range(16, 20)) <= set(at.tolist())
    tr = H.at_weights(both.tr, params)
    got = np.asarray(tr.logits_at(ids, at))
    want = reference.logits(params, {"ids": ids}, MODEL)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=EACH * np.abs(want).max())
    assert reference.logits_error(got, params, {"ids": ids}, MODEL) < 1e-4


def test_the_selection_bias_takes_no_gradient_and_a_step_moves_it(both):
    cfg, params, _, (_, got), _ = both
    assert not np.asarray(got["router_bias"]).any()
    moved = np.asarray(both.stepped["router_bias"]) - params["router_bias"]
    assert moved.shape == (2, 8) and np.allclose(
        np.abs(moved)[moved != 0], cfg.router_bias_rate)


# the ninth, ``bfloat16_throughout``, is a precision: read on the chip at the
# published widths (``benchmark/tools/nemotron_ref_sensitivity.py``)
@pytest.mark.parametrize("fault", reference.FAULTS[:-1])
def test_every_fault_of_the_reference_moves_the_witness(both, fault):
    _, params, ids, _, _ = both
    sound = reference.logits(params, {"ids": ids}, MODEL)
    err = reference.logits_error(sound, params, {"ids": ids}, MODEL,
                                 faults=(fault,))
    assert err > 1e-3, (fault, err)


def _recurrence(xbc, dt, a, d_skip, heads, groups, n_state):
    """The per-token recurrence, float32: the kernels' yardstick."""
    b, s, width = xbc.shape
    d = width - 2 * groups * n_state
    per = heads // groups
    x = xbc[..., :d].reshape(b, s, heads, d // heads)
    bm, cm = (jnp.repeat(t.reshape(b, s, groups, n_state), per, axis=2)
              for t in (xbc[..., d:d + groups * n_state],
                        xbc[..., d + groups * n_state:]))

    def token(h, turn):
        x_t, dt_t, b_t, c_t = turn
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + d_skip[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((b, heads, d // heads, n_state)),
                        tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)))
    return y.swapaxes(0, 1).reshape(b, s, d)


@pytest.mark.parametrize("steps", [(1e-3, 1e-1), (1.0, 4.0)],
                         ids=["decays_near_one", "decays_near_zero"])
@pytest.mark.parametrize("seq, chunk", [(32, 32), (64, 16), (96, 32)])
def test_ssd_kernels_equal_the_per_token_recurrence(seq, chunk, steps):
    heads, groups, n_state, width = 16, 2, 128, 16
    shape = dict(heads=heads, groups=groups, d_state=n_state, chunk=chunk)
    k = jax.random.split(jax.random.PRNGKey(seq), 5)
    xbc = jax.random.normal(k[0], (B, seq, heads * width
                                   + 2 * groups * n_state)) * 0.5
    dt = jnp.exp(jax.random.uniform(k[1], (B, seq, heads),
                                    minval=np.log(steps[0]),
                                    maxval=np.log(steps[1])))
    a = -jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0)
    d_skip = jax.random.normal(k[3], (heads,))
    weigh = jax.random.normal(k[4], (B, seq, heads * width))
    assert ssd.supported(xbc.shape, heads, groups, n_state, chunk)
    ways = {"recurrence": lambda *o: _recurrence(*o, heads, groups, n_state),
            "chunked": lambda *o: ssd.ssd_scan_chunked(*o, **shape),
            "kernels": lambda *o: ssd.ssd_scan(*o, **shape)}
    out = {name: jax.value_and_grad(
        lambda *o: jnp.sum(f(*o) * weigh), argnums=(0, 1, 2, 3))(
            xbc, dt, a, d_skip) for name, f in ways.items()}
    want_y = ways["recurrence"](xbc, dt, a, d_skip)
    for name in ("chunked", "kernels"):
        np.testing.assert_allclose(ways[name](xbc, dt, a, d_skip), want_y,
                                   atol=EACH * np.abs(want_y).max())
        # the rates' gradient is a sum of thousands of terms of both signs
        for got, want, room in zip(out[name][1], out["recurrence"][1],
                                   (1, 1, 30, 1)):
            np.testing.assert_allclose(
                got, want, atol=room * EACH * np.abs(want).max(),
                err_msg=name)


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """The guide's section 4: the parts that all the shares of the experts
    give, the shared expert counted once, add up to the uncut reference's
    whole ``E`` layer."""
    cfg = nemotron_h.nemotron_h_tiny_config(experts_held=0)
    whole = T._init_params(jax.random.PRNGKey(5), cfg)
    pl = jax.tree.map(lambda a: a[0], whole["params_layers"]["p0"])
    bias = whole["router_bias"][0]
    h = jax.random.normal(jax.random.PRNGKey(6), (B * S, cfg.hidden))
    shares = 4
    held = cfg.n_experts // shares
    total = 0.0
    for share in range(shares):
        part = dict(pl, we_up=pl["we_up"][share * held:(share + 1) * held],
                    we_down=pl["we_down"][share * held:(share + 1) * held])
        y, aux = moe.dropless_moe_ffn(
            part, h, cfg.experts_per_token, rule=cfg.routing,
            act=cfg.expert_act, first_held=share * held, bias=bias,
            scale=cfg.route_scale)
        total = total + y
    shared = T.gated_ffn({"w_up": pl["ws_up"], "w_down": pl["ws_down"]},
                         h[None], cfg)[0]
    model = dict(MODEL, n_routed_experts=cfg.n_experts)
    tree = jax.tree.map(lambda a: a[None], pl)
    cast = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.expert_part([h], tree, bias, model, (), cast)[0] \
            + reference.shared_part([h], tree, (), cast)[0]
    np.testing.assert_allclose(total + shared, want,
                               atol=EACH * np.abs(want).max())


@pytest.mark.parametrize("width", [384, 640])
def test_the_row_kernel_takes_rows_of_an_odd_number_of_registers(width):
    """A hidden size like 2,688 = 21 x 128: bfloat16 rows whose half is no
    whole number of registers go as the larger half's words, and the sum
    back is still the gather and sum bit for bit."""
    tokens, k = 275, 6
    m = tokens * k // 3 + 4
    rng = np.random.RandomState(width)
    held = tokens * k // 8
    inv = np.full(tokens * k, m, np.int32)
    inv[rng.choice(tokens * k, held, replace=False)] = rng.permutation(
        m)[:held]
    rows = jax.random.normal(jax.random.PRNGKey(width), (m, width),
                             jnp.float32).astype(jnp.bfloat16)
    assert moe_rows._half(width) == (width + 128) // 2
    got = moe_rows.moe_rows_sum(rows, jnp.asarray(inv), k)
    want = jnp.sum(rows.at[jnp.asarray(inv).reshape(-1, k)].get(
        mode="fill", fill_value=0).astype(jnp.float32), axis=1).astype(
            rows.dtype)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint16), np.asarray(want).view(np.uint16))


@pytest.mark.parametrize("width", [192, 320, 1856])
def test_a_width_off_the_lane_tile_goes_whole_through_the_tiling(width):
    """1,856-like widths: a multiple of 64 that is no multiple of 128 has no
    equal parts of whole lane tiles, so it is one column tile and one
    contraction tile."""
    assert width % 64 == 0 and width % 128
    assert moe._parts(width) == [width]
    for dw in (False, True):
        up = moe._tiling(15360, 2688, width, 16, 2, dw=dw)
        down = moe._tiling(15360, width, 2688, 16, 2, dw=dw)
        assert up[2] == width and down[1] == width
        assert moe._vmem_bytes(*up, 2, dw) <= moe.VMEM_BUDGET
        assert moe._vmem_bytes(*down, 2, dw) <= moe.VMEM_BUDGET


@pytest.mark.parametrize("width", [192, 320])
def test_the_grouped_matmul_s_backward_at_a_width_off_the_lane_tile(width):
    groups, hidden, rows = 4, 64, 96
    k = jax.random.split(jax.random.PRNGKey(width), 4)
    x = jax.random.normal(k[0], (rows, hidden))
    w_up = jax.random.normal(k[1], (groups, hidden, width)) * 0.1
    w_down = jax.random.normal(k[2], (groups, width, hidden)) * 0.1
    sizes = jnp.asarray([40, 0, 33, 23], jnp.int32)
    weigh = jax.random.normal(k[3], (rows, hidden))

    def through(matmul):
        def f(x, w_up, w_down):
            return jnp.sum(matmul(jnp.square(jax.nn.relu(
                matmul(x, w_up, sizes))), w_down, sizes) * weigh)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, w_up, w_down)

    got = through(moe._grouped_matmul)
    want = through(jax.lax.ragged_dot)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=EACH * np.abs(w).max())


def test_the_trainer_steps_under_remat_and_the_gauges_of_a_call():
    tr = H.trainer(CASE, remat=True)
    batches = H.staged(
        tr, [{"ids": H.ids(CASE, seed)[0]} for seed in (0, 1)] * 3)
    mon = monitor.enable()
    try:
        losses = np.asarray(tr.run_steps(batches, 1e-3))
        names = devscope.scope_maps()["nemotron_h.run_steps"]
    finally:
        monitor.disable()
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    reg = mon.registry
    # step sizes seeded log-uniform in [1e-3, 1e-1] under unit noise
    assert 1e-3 < reg.gauge("monitor.train.mamba2_dt_mean").value < 0.2
    # the fastest head (a rate up to 16) under the largest step of the batch
    assert 0.0 < reg.gauge("monitor.train.mamba2_decay_min").value < 0.9
    assert reg.counter("monitor.kernels.ssd_scan_calls", fused=1).value > 0
    assert reg.counter("monitor.kernels.gated_norm_calls", fused=1).value > 0
    assert reg.gauge("monitor.train.moe_load_max_over_mean").value >= 1.0
    assert 0.0 < reg.gauge("monitor.train.moe_held_rows_share").value < 1.0
    # the attention layer's grid: a step a (sequence, head)
    cfg = tr.cfg
    assert packed_grid(
        B, S, cfg.n_heads, cfg.head_dim,
        *T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads),
        itemsize=cfg.jdtype.itemsize, n_kv_heads=cfg.kv_heads,
        causal=True) == (1, 8)
    scopes = {devscope.classify(op)[1] for op in names.values()}
    assert {"mamba2", "ssd_scan", "attention", "moe", "router",
            "shared_expert"} <= scopes
