"""The Solar-Open2 decoder through the normal path (``models/solar_open2.py``
over ``parallel/transformer.py``'s KDA mixer at write strengths in (0, 2) and
its gated grouped-query attention without positions, one attention position
and a run of three KDA positions in a period with NO leading layer,
``kernels/kda_chunk.py``'s chunked delta rule, the flash kernels in interpret
mode and ``parallel/moe.py``'s held-experts path) against the benchmark's
plain float32 reference (``benchmark/reference/solar_open2_250b.py``: the
recurrence a TOKEN at a time), on seeded weights at
``solar_open2_tiny_config``: the four layers GQA, KDA, KDA, KDA; 4 query
heads on 2 key/value heads of 128; 2 KDA heads of 16 in chunks of 16 under S
= 64; 8 experts top-2 of which 4 are held, a shared expert, vocab 256.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only).  ONE traced program of the tiny
model (``both``: loss, every position's logits and every leaf's gradient)
serves the reference tests; one trainer's ``run_steps`` (``ran``) serves
the counters' and the scopes'."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import solar_open2_250b as reference
from paddle_tpu import monitor
from paddle_tpu.models import solar_open2
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, moe, transformer as T

B, S, TOL = 2, 64, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
         "rms_norm_eps": 1e-5, "use_rope": False, "use_gqa_gate": True,
         "kda_allow_neg_eigval": True, "kda_use_full_proj": False,
         "norm_topk_prob": True, "first_k_dense_replace": 0,
         "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
         "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                                "num_heads": 2, "num_kv_heads": None},
         "num_experts_per_tok": 2, "n_routed_experts": 4, "router_width": 8,
         "first_expert_held": 4, "n_shared_experts": 1,
         "routed_scaling_factor": 1, "num_hidden_layers": 4}
SPARSE = ("ln1_scale", "ln2_scale", "router", "we_gate_up", "we_down",
          "ws_gate_up", "ws_down")
KDA_NAMES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
             "dt_bias", "a_log", "w_beta", "w_ga", "w_gb", "o_norm", "wo")
GQA_NAMES = ("wq", "wk", "wv", "wz", "wo")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["params_layers/r0/" + n for n in GQA_NAMES + SPARSE] \
    + ["params_layers/r1/" + n for n in KDA_NAMES + SPARSE]


def _mechanism():
    cfg = solar_open2.solar_open2_tiny_config()
    assert not cfg.latent and cfg.per_position and cfg.run_scan
    assert cfg.prefix_kinds == () and cfg.layer_kinds == (
        (None, False), T.KDA, T.KDA, T.KDA)
    assert cfg.runs == ((0, (None, False), 1), (1, T.KDA, 3))
    assert cfg.positions is None and cfg.attn_gate is True
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank,
            cfg.kda_chunk, cfg.d_conv, cfg.kda_beta_scale) == (
        2, 16, 8, 16, 4, 2.0)
    assert S // cfg.kda_chunk == 4                      # the carry matters
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_ffn_hidden,
            cfg.dense_ffn_hidden) == (8, 4, 4, 2, 48, 0)
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.route_scale == 1.0
    big = solar_open2.solar_open2_250b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.shared_ffn_hidden,
            big.n_experts, big.experts_here, big.experts_per_token,
            big.vocab_size, big.norm_eps, big.kda_heads, big.kda_head_dim,
            big.kda_gate_rank, big.d_conv, big.n_periods,
            big.kda_beta_scale, big.residual_out_gain) == (
        48, 4096, 64, 8, 128, 1280, 1280, 320, 320, 8, 196608, 1e-5, 64, 128,
        128, 4, 12, 2.0, 48 ** -0.5)
    # whole periods of four from layer 0
    for depth in (0, 3, 6, 47):
        with pytest.raises(AssertionError):
            solar_open2.solar_open2_250b_config(n_layers=depth)
    with pytest.raises(AssertionError):     # the range is (0, 1) or (0, 2)
        solar_open2.solar_open2_tiny_config(kda_beta_scale=1.5)
    # every older configuration's default: the sigmoid alone
    assert T.TransformerConfig.kda_beta_scale == 1.0


def _shapes(both):
    params = both.params
    r0, r1 = params["params_layers"]["r0"], params["params_layers"]["r1"]
    assert r0["wq"].shape == r0["wz"].shape == (1, 1, 64, 4 * 128)
    assert r0["wk"].shape == r0["wv"].shape == (1, 1, 64, 2 * 128)
    assert r0["wo"].shape == (1, 1, 4 * 128, 64)
    assert r1["wq"].shape == (1, 3, 64, 32) and r1["conv_k"].shape == (
        1, 3, 4, 32)
    assert r1["w_fa"].shape == (1, 3, 64, 8) and r1["w_gb"].shape == (
        1, 3, 8, 32)
    assert r1["a_log"].shape == (1, 3, 2) and r1["o_norm"].shape == (1, 3, 16)
    assert r1["w_beta"].shape == (1, 3, 64, 2)
    assert "prefix_layers" not in params and "w_gate_up" not in r0
    assert params["router_bias"].shape == (4, 8)
    # the strengths the moved ``w_beta`` gives reach both sides of 1
    pl = jax.tree.map(lambda a: a[0, 0], r1)
    beta = np.asarray(T.kda_write_strength(pl, jax.random.normal(
        jax.random.PRNGKey(1), (1, S, 64)), solar_open2.solar_open2_tiny_config()))
    assert 0 < beta.min() < 0.5 and 1.5 < beta.max() < 2


def _gain(name):
    """A router steep enough that the weights are not all alike, ``w_beta``
    steep enough that the strengths spread over (0, 2), and branch outputs
    at the fan-in scale again (the seeded 48^-1/2 would hide a wrong branch
    behind the embedding)."""
    if "router_bias" in name:
        return 1.0
    if name.endswith("['wo']") or "down" in name:
        return 48 ** 0.5
    return 3.0 if "router" in name or "w_beta" in name else 1.0


CASE = H.Case(
    "solar_open2", reference, MODEL, tuple(LEAVES), aux=True, biased=True,
    gain=_gain, mechanism=_mechanism,
    # ONE sequence (the cell's batch): the reference walks a sequence at a
    # time, op by op, and a second one doubles the file's longest fixture;
    # ONE traced program: loss, every position's logits and the gradients
    rows=1, one_program=True,
    forward=lambda p, ids: reference.forward(p, ids, MODEL),
    spec_configs=({}, {"run_scan": False}), bfloat16=True,
    pieces={"QUERY_BLOCK": 16, "HEAD_GROUP": 1, "VOCAB_CHUNK": 100,
            "EXPERT_GROUP": 1, "DENSE_CHUNK": 20},
    pieces_hold=("loss",), witness=H.Witness(from_logits=True),
    also={"leaves": _shapes})
globals().update(H.common(CASE))


def _layer_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    whole["ws_gate_up"] = jax.random.normal(ks[2], (64, 96)) / 8
    whole["ws_down"] = jax.random.normal(ks[3], (48, 64)) / 7
    bias = 0.1 * jax.random.normal(ks[4], (8,))
    return whole, jax.random.normal(ks[1], (S, 64)), bias


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """The guide's section 4: the PROGRAM's FFN half of a layer on each of
    the two shares of 4 routed experts: every share computes the shared
    expert, so the two routed parts summed, plus the shared expert counted
    ONCE, is the REFERENCE's layer with all 8 experts held."""
    whole, m, bias = _layer_inputs()
    cfg = solar_open2.solar_open2_tiny_config()
    routed_want = reference.moe_part(
        m, whole["router"], bias, whole["we_gate_up"], whole["we_down"], 0,
        2, 1.0)
    shared_want = reference.dense_part(m, whole["ws_gate_up"],
                                       whole["ws_down"])

    def ffn_half(first):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 4],
                     we_down=whole["we_down"][first:first + 4])
        y, aux = moe.dropless_moe_ffn(
            share, m, 2, rule=moe.SIGMOID_BIASED, first_held=first,
            bias=bias, scale=1.0)
        shared = T.gated_ffn({"w_gate_up": share["ws_gate_up"],
                              "w_down": share["ws_down"]}, m[None], cfg)[0]
        return y, shared, aux

    parts = [ffn_half(first) for first in (0, 4)]
    for first, (y, shared, aux) in zip((0, 4), parts):
        np.testing.assert_allclose(shared, shared_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y, reference.moe_part(
            m, whole["router"], bias, whole["we_gate_up"][first:first + 4],
            whole["we_down"][first:first + 4], first, 2, 1.0),
            rtol=1e-5, atol=1e-5)
    assert sum(int(p[2]["rows_held"]) for p in parts) == 2 * S
    assert all(float(jnp.abs(p[0]).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(p[0] for p in parts) + parts[0][1],
                               routed_want + shared_want, rtol=1e-5,
                               atol=1e-5)
    # ... and counted twice it is not
    assert np.abs(sum(p[0] + p[1] for p in parts)
                  - (routed_want + shared_want)).max() > 0.1


def test_the_witness_stands_after_the_chunk_edges(witnessed):
    params, ids, got, _ = witnessed
    big = reference.witness_groups(4096)
    assert big["edge"].tolist() == [
        at + i for at in (64, 512, 4032) for i in range(8)] + list(
            range(4088, 4096))
    # a stride of 16 from 8: position 4,088 is the last group's
    assert len(big["spread"]) == 255 and not set(big["edge"]) & set(
        big["spread"])
    assert len(reference.witness_positions(4096)) == 287
    groups = reference.witness_groups(S)
    assert groups["edge"].tolist() == [
        at + i for at in (16, 32, 48) for i in range(8)] + list(range(56, 64))
    batch = {"ids": ids}
    each = reference.position_errors(got, params, batch, MODEL)
    assert each.shape == (S,) and each.max() < TOL
    parts = reference.group_errors(got, params, batch, MODEL)
    assert reference.logits_error(got, params, batch, MODEL) \
        == max(parts.values())


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    """One trainer's ``run_steps`` over two batches under a monitor session:
    the losses, the registry's snapshot and the program's scope map."""
    batches = [{"ids": i} for i in H.ids(CASE, seed=5, n=2)]
    tr = H.trainer(CASE, remat=True)
    assert monitor.active() is None
    mon = monitor.enable(str(tmp_path_factory.mktemp("mon")), flight=False)
    try:
        losses = tr.run_steps(
            H.staged(tr, batches), 1e-3)
        rows = mon.registry.snapshot()
    finally:
        monitor.disable()
    return batches, np.asarray(losses), rows, \
        devscope.scope_maps()["solar_open2.run_steps"]


def test_run_steps_first_loss_is_the_loss_at_the_seeded_weights(ran):
    """The scan's first loss is the loss function's on batch 0 at the
    weights the trainer was seeded with (a second trainer of the same seed:
    no step taken), and the second step's, on another batch after an
    update, is another number."""
    batches, scanned, _, _ = ran
    fresh = H.trainer(CASE, remat=True)
    first = jax.jit(lambda p, ids: decoder.make_loss_fn(fresh.cfg)(
        p, {"ids": ids})[0])(fresh.state["params"], batches[0]["ids"])
    np.testing.assert_allclose(scanned[0], float(first), rtol=1e-5)
    assert scanned.shape == (2,) and scanned[0] != scanned[1]


def test_counters_and_gauges_of_a_monitor_session(ran):
    _, _, rows, _ = ran

    def value(name, **labels):
        got = [r["value"] for r in rows if r["name"] == name and all(
            str(r["labels"].get(k)) == str(v) for k, v in labels.items())]
        assert len(got) == 1, (name, labels, got)
        return got[0]

    # one call a KDA layer body traced (ONE run of three), on the jnp form
    calls = value("monitor.kernels.kda_chunk_calls", fused=0)
    assert calls > 0
    mean = value("monitor.train.kda_decay_mean")
    least = value("monitor.train.kda_decay_min")
    assert 0 < least < mean < 1 and mean > 0.5
    # the negative eigenvalues in use: about half the writes at seeded weights
    assert 0.3 < value("monitor.train.kda_write_over_one_share") < 0.7
    assert 0 < value("monitor.train.attn_gate_mean") < 1
    assert value("monitor.train.moe_load_max_over_mean") >= 1
    assert value("monitor.train.router_bias_abs_max") > 0
    assert 0 < value("monitor.train.moe_held_rows_share") < 1
    assert value("monitor.kernels.mamba_filter_calls", fused=0,
                 halo="zeros") == calls * 3


def test_the_scopes_hold_their_instructions(ran):
    names = ran[3]
    got = {devscope.classify(op) for op in names.values()}
    for scope in ("kda", "kda_chunk", "attention", "shared_expert", "moe",
                  "router", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    for scope in ("kda", "kda_chunk", "attention"):
        assert ("recompute", scope) in got, scope
    # no latent position, and no dense FFN anywhere in this stack
    assert not {"latent_attention", "mlp"} & {s for _, s in got}


def test_a_kimi_configuration_has_no_new_reading():
    """``kda_write_over_one_share`` is the configuration's: a stack whose
    strengths stay under 1 reads what it read."""
    from paddle_tpu.models import kimi_linear

    cfg = kimi_linear.kimi_linear_tiny_config()
    params = jax.eval_shape(
        lambda: T.init_transformer_params(jax.random.PRNGKey(0), cfg))
    out = jax.eval_shape(lambda p, i: decoder.probe(p, i, cfg), params,
                         jax.ShapeDtypeStruct((1, S), jnp.int32))
    assert "kda_decay_min" in out and "kda_write_over_one_share" not in out
