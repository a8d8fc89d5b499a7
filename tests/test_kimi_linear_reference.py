"""The Kimi-Linear decoder through the normal path (``models/kimi_linear.py``
over ``parallel/transformer.py``'s KDA mixer and its latent attention without
positions inside a pattern, ``kernels/kda_chunk.py``'s chunked delta rule,
the flash kernels' value-width mode in interpret mode and ``parallel/moe.py``'s
held-experts path) against the benchmark's plain float32 reference
(``benchmark/reference/kimi_linear_48b_a3b.py``: the recurrence a TOKEN at a
time), on seeded weights at ``kimi_linear_tiny_config``: the five layers KDA
(dense FFN), KDA, KDA, latent, KDA; 2 KDA heads of 16 in chunks of 16 under S
= 64; 2 latent heads of 128 + 64 against values of 128; 8 experts top-2 of
which 4 are held, a shared expert, vocab 256.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import kimi_linear_48b_a3b as reference
from paddle_tpu import monitor
from paddle_tpu.models import kimi_linear, mistral4
from paddle_tpu.parallel import decoder, moe, transformer as T

# the module: ``paddle_tpu.kernels`` exports a function of the same name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
B, S, TOL = 2, 64, 1e-5
LINEAR = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 16,
          "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26],
          "num_heads": 2, "short_conv_kernel_size": 4}
# the reference reads the published keys
MODEL = {"num_attention_heads": 2, "q_lora_rank": None, "kv_lora_rank": 32,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
         "rms_norm_eps": 1e-5, "mla_use_nope": True, "moe_renormalize": True,
         "num_expert_group": 1, "topk_group": 1,
         "moe_router_activation_func": "sigmoid", "linear_attn_config": LINEAR,
         "num_experts_per_token": 2, "num_experts": 4, "moe_router_width": 8,
         "moe_first_expert_held": 4, "num_shared_experts": 1,
         "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
         "num_hidden_layers": 5}
KDA_NAMES = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "conv_q", "conv_k",
             "conv_v", "w_fa", "w_fb", "dt_bias", "a_log", "w_beta", "w_ga",
             "w_gb", "o_norm", "wo")
SPARSE = ("router", "we_gate_up", "we_down", "ws_gate_up", "ws_down")
LATENT_NAMES = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_a_norm", "wkv_b",
                "wo")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["prefix_layers/l0/" + n for n in KDA_NAMES + ("w_gate_up", "w_down")] \
    + ["params_layers/r0/" + n for n in KDA_NAMES + SPARSE] \
    + ["params_layers/r1/" + n for n in LATENT_NAMES + SPARSE] \
    + ["params_layers/r2/" + n for n in KDA_NAMES + SPARSE]


def _mechanism():
    cfg = kimi_linear.kimi_linear_tiny_config()
    assert cfg.latent and cfg.per_position and cfg.run_scan
    assert cfg.prefix_kinds == (T.KDA,) and cfg.layer_kinds == (
        T.KDA, T.KDA, (None, False), T.KDA)
    assert cfg.runs == ((0, T.KDA, 2), (2, (None, False), 1), (3, T.KDA, 1))
    assert cfg.positions is None and cfg.q_lora_rank == 0
    assert (cfg.n_heads, cfg.head_dim, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim, cfg.kv_lora_rank) == (2, 192, 128, 64, 128, 32)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank,
            cfg.kda_chunk, cfg.d_conv) == (2, 16, 8, 16, 4)
    assert S // cfg.kda_chunk == 4                      # the carry matters
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_ffn_hidden,
            cfg.dense_ffn_hidden) == (8, 4, 4, 2, 48, 96)
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.route_scale == 2.446
    # the kernels run: a head of 192 in 256 lanes, values of 128
    assert T._latent_head_lanes(cfg) == 256
    assert T._packed_flash_blocks(cfg, 2, S, widths=(256, 128)) == (16, 16)
    assert not fa.packed_layout_supported(2, 192) \
        and not fa.packed_layout_supported(2, 192, None, 128) \
        and fa.packed_layout_supported(2, 256, None, 128) \
        and not fa.packed_layout_supported(4, 256, 2, 128)
    big = kimi_linear.kimi_linear_48b_a3b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.head_dim,
            big.q_lora_rank, big.kv_lora_rank, big.qk_nope_dim,
            big.qk_rope_dim, big.v_head_dim, big.ffn_hidden,
            big.dense_ffn_hidden, big.shared_ffn_hidden, big.n_experts,
            big.experts_here, big.experts_per_token, big.vocab_size,
            big.norm_eps, big.kda_heads, big.kda_head_dim,
            big.kda_gate_rank, big.d_conv, big.n_periods) == (
        25, 2304, 32, 192, 0, 512, 128, 64, 128, 1024, 9216, 1024, 256, 256,
        8, 163840, 1e-5, 32, 128, 128, 4, 6)
    # the published depth ends on half a period: 27 is not expressible
    for depth in (27, 26, 4):
        with pytest.raises(AssertionError):
            kimi_linear.kimi_linear_48b_a3b_config(n_layers=depth)
    # what each latent branch needs, and nothing else
    with pytest.raises(AssertionError):     # a rotated latent pads nothing
        mistral4.mistral4_tiny_config(v_head_dim=64)
    with pytest.raises(AssertionError):     # no rotation: nothing shapes one
        kimi_linear.kimi_linear_tiny_config(q_scale_beta=0.1)
    with pytest.raises(AssertionError):     # a latent position is full
        kimi_linear.kimi_linear_tiny_config(
            layer_pattern=(T.KDA, T.KDA, (16, False), T.KDA))
    with pytest.raises(AssertionError):     # KDA needs its widths
        kimi_linear.kimi_linear_tiny_config(kda_gate_rank=0)


def _shapes(both):
    params = both.params
    r0, r1 = params["params_layers"]["r0"], params["params_layers"]["r1"]
    assert r0["wq"].shape == (1, 2, 64, 32) and r0["conv_k"].shape == (
        1, 2, 4, 32)
    assert r0["w_fa"].shape == (1, 2, 64, 8) and r0["w_gb"].shape == (
        1, 2, 8, 32)
    assert r0["a_log"].shape == (1, 2, 2) and r0["o_norm"].shape == (1, 2, 16)
    assert r1["wq"].shape == (1, 1, 64, 2 * 192)
    assert r1["wkv_a"].shape == (1, 1, 64, 32 + 64)
    assert r1["wkv_b"].shape == (1, 1, 32, 2 * (128 + 128))
    assert r1["wo"].shape == (1, 1, 2 * 128, 64)
    assert not {"wq_a", "q_a_norm", "wk", "wv"} & set(r1)
    assert params["router_bias"].shape == (4, 8)


def test_positions_of_the_period_without_run_scan_give_the_same_loss():
    """``run_scan`` stacks the runs (K, K), (latent), (K); without it each
    position is a tree of its own, seeded alike: the same numbers."""
    ids = jnp.asarray(H.ids(CASE, seed=4)[0])
    losses = []
    for run_scan in (True, False):
        tr = H.trainer(CASE, run_scan=run_scan)
        losses.append(float(jax.jit(lambda p: decoder.make_loss_fn(tr.cfg)(
            p, {"ids": ids})[0])(tr.state["params"])))
    assert abs(losses[0] - losses[1]) < 1e-5 * losses[0]


def _latent_layer(seed=4):
    cfg = kimi_linear.kimi_linear_tiny_config()
    params = T.init_transformer_params(jax.random.PRNGKey(seed), cfg)
    pl = jax.tree.map(lambda a: a[0, 0], params["params_layers"]["r1"])
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, 64))
    return cfg, pl, h


def test_the_shared_key_is_one_unrotated_vector_for_all_heads():
    """q and k stand a head in 256 lanes: 128 of the head's own, the SAME 64
    of ``wkv_a``'s last columns as they are (no position moves them), 64
    zeros; v is the head's own 128."""
    cfg, pl, h = _latent_layer()
    q, k, v = T._qkv(pl, h, cfg, False)
    assert q.shape == k.shape == (B, S, 2 * 256) and v.shape == (B, S, 2 * 128)
    q, k = (np.asarray(a).reshape(B, S, 2, 256) for a in (q, k))
    ks = np.asarray(h @ pl["wkv_a"])[..., 32:]
    for head in range(2):
        np.testing.assert_allclose(k[:, :, head, 128:192], ks, rtol=1e-6)
        assert not k[:, :, head, 192:].any() and not q[:, :, head, 192:].any()
    assert np.abs(k[:, :, 1, :128] - k[:, :, 0, :128]).max() > 1e-2
    np.testing.assert_allclose(
        q[..., :192], np.asarray(h @ pl["wq"]).reshape(B, S, 2, 192),
        rtol=1e-5, atol=1e-6)
    c = T._rms(np.asarray(h @ pl["wkv_a"])[..., :32], pl["kv_a_norm"], 1e-5)
    kv = np.asarray(c @ pl["wkv_b"]).reshape(B, S, 2, 256)
    np.testing.assert_allclose(k[..., :128], kv[..., :128], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v).reshape(B, S, 2, 128),
                               kv[..., 128:], rtol=1e-5, atol=1e-6)


def test_the_key_s_assembly_takes_the_row_kernel_and_equals_the_line(
        tmp_path, monkeypatch):
    """The tiny latent layer's k goes through ``kernels/qk_rope.py`` at a
    head of two lane blocks with the shared key and no tables (q is a plain
    matmul: no call), counted ``fused`` 1; with the kernel refused the
    broadcast add through the [b, S, H, 256] view gives the same q, k, v and
    the same gradients of the chain's leaves and of the rows."""
    from paddle_tpu.kernels import qk_rope

    cfg, pl, h = _latent_layer()
    w = [jax.random.normal(jax.random.PRNGKey(9 + i), (B, S, 2 * n))
         for i, n in enumerate((256, 256, 128))]

    def run(pl, h):
        out = T._qkv(pl, h, cfg, False)
        return sum(jnp.sum(a * b) for a, b in zip(out, w)), out

    def counted():
        mon = monitor.enable(str(tmp_path), flight=False)
        try:
            mon.registry.reset()
            jax.eval_shape(lambda pl, h: run(pl, h)[0], pl, h)
            return {tuple(r["labels"][k] for k in (
                "dh", "convention", "rotary", "fused")): r["value"]
                    for r in mon.registry.snapshot()
                    if r["name"] == "monitor.kernels.qk_rope_calls"}
        finally:
            monitor.disable()

    assert qk_rope.supported((B, S, 512), 256, 4)
    assert counted() == {(256, "pairs", 0, 1): 1}
    (_, got), got_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(pl, h)
    monkeypatch.setattr(qk_rope, "supported", lambda *a: False)
    assert counted() == {(256, "pairs", 0, 0): 1}
    (_, want), want_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(
        pl, h)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)
    for name in ("wq", "wkv_a", "wkv_b", "kv_a_norm"):
        a, r = got_grads[0][name], want_grads[0][name]
        assert a.shape == pl[name].shape and np.abs(r).max() > 0
        np.testing.assert_allclose(a, r, rtol=1e-4,
                                   atol=1e-5 * np.abs(r).max(), err_msg=name)
    np.testing.assert_allclose(got_grads[1], want_grads[1], rtol=1e-4,
                               atol=1e-4)


def _plain(q, k, v, heads, scale):
    b, s, _ = q.shape
    q, k, v = (a.reshape(b, s, heads, -1) for a in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                      precision="highest").reshape(b, s, -1)


# several blocks in ONE backward sweep; the two-sweep backward (SWEEP_VMEM
# refused); one block
@pytest.mark.parametrize("block,sweeps", [(16, 1), (16, 2), (64, 1)])
def test_flash_at_192_128_equals_plain_attention(monkeypatch, block, sweeps):
    """The packed kernels' value mode in interpret mode: q and k a head of
    192 in 256 lanes, v and o at 128, the scale 192^-1/2; outputs and dq,
    dk, dv against plain softmax attention."""
    if sweeps == 2:
        monkeypatch.setattr(fa, "SWEEP_VMEM", 0)
    ks = jax.random.split(jax.random.PRNGKey(block + sweeps), 4)
    q, k = (jax.random.normal(key, (B, S, 2, 256)).at[..., 192:].set(0)
            .reshape(B, S, -1) for key in ks[:2])
    v, w = (jax.random.normal(key, (B, S, 2 * 128)) for key in ks[2:])
    scale = 192 ** -0.5

    def flash(q, k, v):
        return fa.flash_attention_packed(
            q, k, v, 2, causal=True, scale=scale, block_q=block,
            block_k=block, v_head_dim=128)

    assert fa._Geom(q, k, 2, block, block, Dv=128).bwd_sweeps == sweeps
    got, want = flash(q, k, v), _plain(q, k, v, 2, scale)
    assert got.shape == (B, S, 256)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(
        q, k, v) for fn in (flash, lambda *a: _plain(*a, 2, scale))]
    for g, wnt in zip(*grads):
        np.testing.assert_allclose(g, wnt, rtol=1e-4, atol=1e-5)
    # the zero lanes take no gradient that is not theirs
    assert not np.asarray(grads[0][1]).reshape(B, S, 2, 256)[..., 192:].any()


def test_one_width_callers_build_the_geometry_they_always_did():
    """A caller that gives no value width: the same blocks, maps and budget
    as before the mode (``vw`` is ``qw``; the fused sweep's bytes those of
    two accumulators of one width)."""
    q = jax.ShapeDtypeStruct((1, 1024, 4 * 128), jnp.bfloat16)
    g = fa._Geom(q, q, 4, 512, 512)
    assert (g.vw, g.Dv, g.o_shape, g.dk_shape, g.dv_shape) == (
        g.qw, g.D, q.shape, q.shape, q.shape)
    assert fa.fused_sweep_vmem_bytes(16384, 128, 2) == \
        2 * 16384 * 128 * 4 + 2 * 16384 * 128 * 2 + fa.SCOPED_VMEM
    # the cell's shape: dk at 256 lanes, dv at 128 fit one sweep
    assert fa.fused_sweep_vmem_bytes(16384, 256, 2, 128) == \
        16384 * 384 * 6 + fa.SCOPED_VMEM
    assert fa.bwd_sweeps(16384, 512, 256, 2, 1, 128) == 1


def test_the_mixer_normalises_then_gates(both):
    """Step 4's order: ``rms_head(o) * o_norm * gate``; Mamba-2's
    gate-then-norm gives other numbers (the reference's fault)."""
    _, params, ids, _, _ = both
    sound = reference.loss(params, {"ids": ids}, MODEL)
    swapped = reference.loss(params, {"ids": ids}, MODEL,
                             faults=("gate_before_norm",))
    assert abs(swapped - sound) > 100 * TOL * sound


def _layer_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    whole["ws_gate_up"] = jax.random.normal(ks[2], (64, 96)) / 8
    whole["ws_down"] = jax.random.normal(ks[3], (48, 64)) / 7
    bias = 0.1 * jax.random.normal(ks[4], (8,))
    return whole, jax.random.normal(ks[1], (S, 64)), bias


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """The PROGRAM's FFN half of a sparse layer on each of the four shares
    of 2 routed experts: every share computes the shared expert, so the
    four routed parts summed, plus the shared expert counted ONCE, is the
    REFERENCE's layer with all 8 experts held."""
    whole, m, bias = _layer_inputs()
    cfg = kimi_linear.kimi_linear_tiny_config()
    routed_want = reference.moe_part(
        m, whole["router"], bias, whole["we_gate_up"], whole["we_down"], 0,
        2, 2.446)
    shared_want = reference.dense_part(m, whole["ws_gate_up"],
                                       whole["ws_down"])

    def ffn_half(first):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(
            share, m, 2, rule=moe.SIGMOID_BIASED, first_held=first,
            bias=bias, scale=2.446)
        shared = T.gated_ffn({"w_gate_up": share["ws_gate_up"],
                              "w_down": share["ws_down"]}, m[None], cfg)[0]
        return y, shared, aux

    parts = [ffn_half(first) for first in range(0, 8, 2)]
    for first, (y, shared, aux) in zip(range(0, 8, 2), parts):
        np.testing.assert_allclose(shared, shared_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y, reference.moe_part(
            m, whole["router"], bias, whole["we_gate_up"][first:first + 2],
            whole["we_down"][first:first + 2], first, 2, 2.446),
            rtol=1e-5, atol=1e-5)
    assert sum(int(p[2]["rows_held"]) for p in parts) == 2 * S
    assert all(float(jnp.abs(p[0]).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(p[0] for p in parts) + parts[0][1],
                               routed_want + shared_want, rtol=1e-5,
                               atol=1e-5)
    # ... and counted four times it is not
    assert np.abs(sum(p[0] + p[1] for p in parts)
                  - (routed_want + shared_want)).max() > 0.1


def test_the_witness_stands_after_the_chunk_edges(witnessed):
    params, ids, program, _ = witnessed
    big = reference.witness_groups(16384)
    assert big["edge"].tolist() == [
        at + i for at in (64, 512, 4096, 16320) for i in range(8)] + list(
            range(16376, 16384))
    assert len(big["spread"]) == 256 and not set(big["edge"]) & set(
        big["spread"])
    groups = reference.witness_groups(S)
    assert groups["edge"].tolist() == [
        at + i for at in (16, 32, 48) for i in range(8)] + list(range(56, 64))
    each = reference.position_errors(program, params, {"ids": ids}, MODEL)
    assert each.shape == (S,) and each.max() < TOL
    parts = reference.group_errors(program, params, {"ids": ids}, MODEL)
    assert reference.logits_error(program, params, {"ids": ids}, MODEL) \
        == max(parts.values())


def _counters(trained):
    # one call a KDA layer body traced: the leading layer's, and ONE a
    # run of the period (the runs (K, K) and (K)), all on the jnp form
    calls = trained.value("monitor.kernels.kda_chunk_calls", fused=0)
    assert calls > 0 and calls % 3 == 0
    mean = trained.value("monitor.train.kda_decay_mean")
    least = trained.value("monitor.train.kda_decay_min")
    assert 0 < least < mean < 1
    # the seeded decays: most channels outlive many chunks
    assert mean > 0.5
    assert trained.value("monitor.train.moe_load_max_over_mean") >= 1
    assert trained.value("monitor.train.router_bias_abs_max") > 0
    # a filter's three calls a KDA body, refused off whole lane blocks
    assert trained.value("monitor.kernels.mamba_filter_calls", fused=0,
                         halo="zeros") == calls * 3


def _specs(specs):
    specs = specs["params_layers"]
    assert specs["p0"]["w_fb"] == specs["p0"]["o_norm"] == T.P()
    assert specs["p2"]["wkv_b"] == T.P(None, None, None)


def _gain(name):
    """A router steep enough that the weights are not all alike, and branch
    outputs at the fan-in scale again (the seeded 27^-1/2 would hide a wrong
    branch behind the embedding)."""
    if "router_bias" in name:
        return 1.0
    if name.endswith("['wo']") or "down" in name:
        return 27 ** 0.5
    return 3.0 if "router" in name else 1.0


CASE = H.Case(
    "kimi_linear", reference, MODEL, tuple(LEAVES), aux=True, biased=True,
    gain=_gain, mechanism=_mechanism,
    # ``run_scan`` stacks the runs; without it each position is its own tree
    spec_configs=({}, {"run_scan": False}), bfloat16=True,
    pieces={"QUERY_BLOCK": 16, "HEAD_GROUP": 1, "VOCAB_CHUNK": 100,
            "EXPERT_GROUP": 1, "DENSE_CHUNK": 20},
    pieces_hold=("loss",),
    # ``both``'s trainer and weights, on ONE sequence (the cell's batch)
    # the counters' own trainer, without ``remat``: under it the runs (K, K)
    # and (K) share ONE cached trace of the layer body and count once
    witness=H.Witness(), steps=2, counters={},
    also={"leaves": _shapes, "specs": _specs, "counters": _counters})
globals().update(H.common(CASE))


def test_the_new_scopes_hold_their_instructions(trained):
    got = trained.scopes()
    for scope in ("kda", "kda_chunk", "latent_attention", "shared_expert",
                  "moe", "router", "mlp", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    for scope in ("kda", "kda_chunk", "latent_attention"):
        assert ("recompute", scope) in got, scope
    assert "attention" not in {s for _, s in got}
